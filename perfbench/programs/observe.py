"""What the benchmark reads of the program's own counters: compiles by
``observability/recompile.py`` and the flash-decode dispatch counters.
Nothing else of the program's observability is read (PERF.md says why)."""

from __future__ import annotations


def _sum(snapshot, name):
    fam = snapshot["metrics"].get(name)
    return int(sum(s["value"] for s in fam["samples"])) if fam else 0


def counters():
    from paddle_tpu import observability

    snap = observability.snapshot()
    return {
        "compiles": observability.recompile.total_compiles(),
        "flash_decode_hits": _sum(snap, "paddle_tpu_flash_decode_hits_total"),
        "flash_decode_fallbacks": _sum(
            snap, "paddle_tpu_flash_decode_fallbacks_total"),
    }


def enable_compile_cache():
    from paddle_tpu.core.compile_cache import enable_compile_cache as f

    return f()
