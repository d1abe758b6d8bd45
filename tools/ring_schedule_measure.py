"""Measured zigzag-vs-contiguous causal ring schedule, on real TPU.

An 8-way ring does not fit the four chips of one host, so the
lockstep ring's critical path is measured on one chip: each hop
KERNEL (the exact flash shapes the two layouts dispatch per hop) is
timed on the real chip, and the per-hop ring step time is composed as
the max across devices — which is what a lockstep ppermute ring
executes. The cost-model test (tests/test_distributed.py
test_zigzag_schedule_is_balanced) asserts the same structure in
abstract units; this pins real milliseconds to it.

Shapes: GPT-1.3B long-context defaults — S_global=32768 over an 8-way
sep ring => S_local=4096 per device, half-chunk 2048, H=16, D=128.

Writes chiprun_out/RING_SCHEDULE.json.
Usage: python tools/ring_schedule_measure.py [--out FILE]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def _time_call(fn, args, iters=60):
    """Scan-amortized wall time of fn(*args): one launch, a
    carry-perturbed operand, every output element consumed."""
    import jax
    import jax.numpy as jnp

    def scanned(*a):
        def body(c, _):
            out = fn(a[0] + c.astype(a[0].dtype), *a[1:])
            leaves = jax.tree_util.tree_leaves(out)
            s = sum(l.astype(jnp.float32).sum() for l in leaves)
            return s * 1e-30, None
        s, _ = jax.lax.scan(body, jnp.float32(0), None, length=iters)
        return s

    jitted = jax.jit(scanned)
    float(jitted(*args))  # compile + warm
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(jitted(*args))
        times.append(time.perf_counter() - t0)
    return sorted(times)[1] / iters


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=None)
    ap.add_argument("--s-local", type=int, default=4096)
    ap.add_argument("--heads", type=int, default=16)
    ap.add_argument("--head-dim", type=int, default=128)
    ap.add_argument("--ring", type=int, default=8)
    args = ap.parse_args()

    import jax.numpy as jnp

    from paddle_tpu.ops.pallas.flash_attention import flash_attention_lse

    from bench import device_block, out_path

    s_loc, h, d, n = args.s_local, args.heads, args.head_dim, args.ring
    c = s_loc // 2
    rng = np.random.default_rng(0)

    def mk(s):
        return jnp.asarray(rng.standard_normal(
            (1, s, h, d)).astype(np.float32).astype(jnp.bfloat16))

    q_full, k_full, v_full = mk(s_loc), mk(s_loc), mk(s_loc)
    k_half, v_half = mk(c), mk(c)
    q_half = mk(c)
    scale = 1.0 / np.sqrt(d)

    hops_ms = {
        # contiguous-layout hop kernels
        "contiguous_full": _time_call(
            lambda q, k, v: flash_attention_lse(q, k, v, causal=False,
                                                scale=scale),
            (q_full, k_full, v_full)) * 1e3,
        "contiguous_diag_causal": _time_call(
            lambda q, k, v: flash_attention_lse(q, k, v, causal=True,
                                                scale=scale),
            (q_full, k_full, v_full)) * 1e3,
        # zigzag-layout hop kernels (earlier / local / later)
        "zigzag_earlier": _time_call(
            lambda q, k, v: flash_attention_lse(q, k, v, causal=False,
                                                scale=scale),
            (q_full, k_half, v_half)) * 1e3,
        "zigzag_later": _time_call(
            lambda q, k, v: flash_attention_lse(q, k, v, causal=False,
                                                scale=scale),
            (q_half, k_full, v_full)) * 1e3,
    }
    hops_ms["zigzag_local_causal"] = hops_ms["contiguous_diag_causal"]

    # lockstep composition: ring step time = max over devices per hop
    # (contiguous: hop 0 all-diagonal, every later hop has a
    # fully-visible device; zigzag: hop 0 local-causal, later hops
    # max(earlier, later))
    cont = hops_ms["contiguous_diag_causal"] + \
        (n - 1) * hops_ms["contiguous_full"]
    zig = hops_ms["zigzag_local_causal"] + \
        (n - 1) * max(hops_ms["zigzag_earlier"], hops_ms["zigzag_later"])

    report = {
        "config": {"s_local": s_loc, "half_chunk": c, "heads": h,
                   "head_dim": d, "ring_devices": n, "batch": 1,
                   "dtype": "bfloat16",
                   "device": device_block()},
        "hop_kernel_ms": {k: round(v, 3) for k, v in hops_ms.items()},
        "composed_ring_fwd_ms": {
            "contiguous": round(cont, 2),
            "zigzag": round(zig, 2),
            "speedup": round(cont / zig, 3)},
        "method": (
            "per-hop flash kernels measured on the real chip "
            "(scanned launches); lockstep ring step = "
            "max over devices per hop, summed over n hops. The measured "
            "kernels are exactly what distributed/sp.py dispatches per "
            "hop in each layout."),
    }
    print(json.dumps(report, indent=2))
    with open(args.out or out_path("RING_SCHEDULE.json"), "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")


if __name__ == "__main__":
    main()
