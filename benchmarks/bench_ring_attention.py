"""Ring-attention microbench: per-hop kernel timing on the real chip +
multi-device correctness/shape of the full ring on the CPU mesh.

The full ring (distributed/sequence_parallel.py ring_attention) needs
one device per sequence shard. What can be measured on ONE chip is the
ring's inner per-hop update — blockwise attention of the local Q
shard against the resident KV block with online-softmax accumulation —
which is the compute a real n-chip ring runs n times per layer while
ppermute rotates KV over ICI (the transfer overlaps compute: a KV block
is 2*s_loc*h*d*2 bytes vs ~45 GB/s per ICI link on v5e, a small fraction
of the hop's compute time at these shapes).

Writes benchmarks/ring_attention_results.json:
  hop_ms        — measured per-hop time (chained-scan method, see
                  bench_flash_attention.py for why)
  ring_step_ms  — n_ranks * hop_ms (per layer, per ring pass)
  est_tflops    — achieved TF/s on the hop's useful flops

Run: python benchmarks/bench_ring_attention.py  (on the chip)
"""

from __future__ import annotations

import json
import math
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.bench_flash_attention import bench


def ring_hop(qm, km, vm, o, lse):
    """One ring hop (mirrors sequence_parallel.ring_attention's block
    body, minus the ppermute): the Pallas flash kernel consumes the
    resident KV block (no [sl, sl] score tensor in HBM) and the
    normalized partial merges through its log-sum-exp. Shapes [bh, sl,
    d]; non-causal hop (the common case — n-1 of n hops)."""
    from paddle_tpu.pallas_kernels.flash_attention import _flash_lse

    sl, d = qm.shape[1], qm.shape[2]
    o_i, lse_i = _flash_lse(qm, km, vm, None, False, 1.0 / math.sqrt(d),
                            min(1024, sl), min(1024, sl))
    lse_new = jnp.logaddexp(lse, lse_i)
    o_new = (o * jnp.exp(lse - lse_new)[..., None]
             + o_i.astype(jnp.float32) * jnp.exp(lse_i - lse_new)[..., None])
    return o_new, lse_new


def main():
    n_ranks = int(os.environ.get("RING_RANKS", "8"))
    b, h, d = 1, 12, 64
    s_global = int(os.environ.get("RING_SEQ", "32768"))
    s_loc = s_global // n_ranks

    rng = np.random.RandomState(0)
    qm = jnp.asarray(rng.randn(b * h, s_loc, d), jnp.bfloat16)
    km = jnp.asarray(rng.randn(b * h, s_loc, d), jnp.bfloat16)
    vm = jnp.asarray(rng.randn(b * h, s_loc, d), jnp.bfloat16)
    o = jnp.zeros((b * h, s_loc, d), jnp.float32)
    lse = jnp.full((b * h, s_loc), -jnp.inf, jnp.float32)

    def hop(qm, km, vm, o, lse):
        o2, lse2 = ring_hop(qm, km, vm, o, lse)
        # fold o2 into the qm chain: the bench returns carry[0], and
        # without this dependence XLA dead-code-eliminates the whole hop
        return (qm + o2.astype(qm.dtype) * 1e-6, km, vm, o2, lse2)

    hop_s = bench(lambda *a: hop(*a), qm, km, vm, o, lse, iters=50)
    flops = 2 * 2 * b * h * s_loc * s_loc * d  # QK^T + PV
    out = {
        "backend": jax.default_backend(),
        "n_ranks": n_ranks,
        "seq_global": s_global,
        "seq_local": s_loc,
        "hop_ms": round(hop_s * 1e3, 3),
        "ring_step_ms": round(hop_s * 1e3 * n_ranks, 3),
        "est_tflops": round(flops / hop_s / 1e12, 1),
    }
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "ring_attention_results.json")
    with open(path, "w") as f:
        json.dump(out, f, indent=1)
    print(json.dumps(out))


if __name__ == "__main__":
    main()
