"""Test configuration: run everything on a virtual 8-device CPU mesh.

Mirrors the reference's strategy of running all distributed tests
multi-process on one host (SURVEY §4): here, multi-chip is simulated with
8 XLA:CPU devices, so sharding/collective logic is exercised without TPU
hardware. Must run before any jax array is created.
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax

# Unit tests run on the virtual CPU mesh whatever the machine holds.
# PADDLE_TPU_TEST_PLATFORM=tpu switches to the on-chip lane
# (run_shards.py --platform=tpu): tests run on the real chip with fp32
# matmuls forced to full precision — TPU fp32 dots default to a
# bf16-class mode whose error (~1e-2) would void the sweep's 1e-5
# oracle comparisons (reference device-lane discipline:
# op_test.py:2925 check_output_with_place).
if os.environ.get("PADDLE_TPU_TEST_PLATFORM", "cpu") == "cpu":
    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_num_cpu_devices", 8)
else:
    jax.config.update("jax_default_matmul_precision", "highest")
    # persistent compile cache: the full on-chip schema sweep pays one
    # XLA compile per case; repeat lane runs hit the disk cache instead
    from paddle_tpu.core.compile_cache import enable_compile_cache

    enable_compile_cache()


# ---------------------------------------------------------------------------
# Dispatch-name recorder: every op name that goes through apply_op during
# this pytest session is recorded and checked at session end against the
# schema registry + white lists (reference role: ops cannot exist outside
# ops.yaml). Strays fail the run. The same record is also dumped for
# run_shards.py to merge across shard processes.
# ---------------------------------------------------------------------------
_RECORDED_NAMES = set()


def pytest_configure(config):
    from paddle_tpu.ops.dispatch import record_dispatch

    record_dispatch(_RECORDED_NAMES)


def pytest_sessionfinish(session, exitstatus):
    from paddle_tpu.ops.dispatch import record_dispatch
    from paddle_tpu.ops.schemas import SCHEMAS
    from paddle_tpu.ops.schemas_extended import (DYNAMIC_DISPATCH,
                                                 NO_SCHEMA_WHITE_LIST)

    record_dispatch(None)
    dump = os.environ.get("PADDLE_TPU_DISPATCH_DUMP")
    if dump:
        with open(f"{dump}.{os.getpid()}", "w") as fh:
            fh.write("\n".join(sorted(_RECORDED_NAMES)))
    # observability snapshot per shard process: run_shards merges these
    # into benchmarks/telemetry_lane.json (fused-conv hit rates, compile
    # counts) next to tpu_lane_results.json
    tdump = os.environ.get("PADDLE_TPU_TELEMETRY_DUMP")
    if tdump:
        import json

        from paddle_tpu import observability

        with open(f"{tdump}.{os.getpid()}.json", "w") as fh:
            json.dump(observability.snapshot(), fh)
    strays = {
        n for n in _RECORDED_NAMES
        if n not in SCHEMAS and n not in NO_SCHEMA_WHITE_LIST
        and n not in DYNAMIC_DISPATCH["enumerated"]
        and not n.startswith(DYNAMIC_DISPATCH["prefixes"])
    }
    if strays:
        reporter = session.config.pluginmanager.get_plugin("terminalreporter")
        msg = ("ops dispatched without a schema or white-list entry "
               f"(add to ops/schemas*.py): {sorted(strays)}")
        if reporter:
            reporter.write_sep("=", "SCHEMA ENFORCEMENT FAILURE")
            reporter.write_line(msg)
        session.exitstatus = 1
