"""The flash attention kernels alone (ops/pallas/flash_attention.py), at
the shapes of the four cells that run them:

- ``train``: ``[2, 16, 2048, 128]`` causal, forward and both backward
  calls (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``);
- ``prefill-1024`` / ``prefill-2048``: ``[1, 16, S, 128]`` (GPT-1.3B's
  prefill buckets);
- ``window-8192`` / ``global-8192``: ``[1, 28, 8192, 128]`` over 4 KV
  heads (a group of 7) with and without a window of 4096 (SmallThinker);
- ``solar-8192`` / ``solar-32768``: ``[1, 64, S, 128]`` over 8 KV heads
  (a group of 8).

A call's time is the DEVICE time of the Pallas custom call in a
profiler trace (read with the benchmark's reader,
``benchmarks/xplane.py``), the median over ``--reps`` calls; beside it
the least time the chip could take for the call's required operations
(``benchmarks/flops.py``: the causal half, and inside a window only the
keys a query sees), the time a RELEVANT block (a ``block_q x block_k``
grid step that holds a visible key) and a grid step of any kind, and
last a least-squares split of the forward calls' times into what a
relevant step, a skipped step and a row of blocks (its start and finish)
cost. Before the timing each case is compared with the dense float32
attention on the same device at a short length (``max_err``).

Where jax's own ``pallas.ops.tpu`` flash and splash attention lower at
the train shape they are timed too: a yardstick for what the chip
reaches with this blocking, not a replacement.

Nothing here is a benchmark cell. To read another tree's kernels (the
parent's), pass ``--repo <checkout>``: ``paddle_tpu`` is imported from
there.

    chiprun -- python3 tools/flash_report.py --tag change
    chiprun -- python3 tools/flash_report.py --repo _checkout/parent --tag parent
    JAX_PLATFORMS=cpu python3 tools/flash_report.py --tiny   # control flow only
"""

from __future__ import annotations

import argparse
import functools
import json
import os
import shutil
import statistics
import sys
import tempfile

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (name, batch, query heads, KV heads, sequence, window, backward too)
CASES = [("train", 2, 16, 16, 2048, None, True),
         ("prefill-1024", 1, 16, 16, 1024, None, False),
         ("prefill-2048", 1, 16, 16, 2048, None, False),
         ("window-8192", 1, 28, 4, 8192, 4096, False),
         ("global-8192", 1, 28, 4, 8192, None, False),
         ("solar-8192", 1, 64, 8, 8192, None, False),
         ("solar-32768", 1, 64, 8, 32768, None, False)]
# required matmuls a kernel is charged with: the backward's five are dq's
# own and the scores' share (2), and dk, dv and dp (3)
MATMULS = {"flash_fwd": 2, "flash_fwd_single": 2, "flash_bwd_dq": 2,
           "flash_bwd_dkv": 3, "flash_bwd_fused": 5}
TINY = [("train", 1, 2, 2, 512, None, True),
        ("window", 1, 4, 2, 512, 200, False),
        ("global", 1, 4, 2, 512, None, False)]


def _custom_call_us(trace_dir):
    """Device µs of every Pallas custom call in the trace, by kernel
    name (``flash_fwd.3`` -> ``flash_fwd``)."""
    from benchmarks import xplane
    out = {}
    for e in xplane.leaf_ops(xplane.load_events(
            xplane.find_xplane(trace_dir))):
        if xplane.op_kind(e) == "custom-call":
            stem = xplane.short_name(e).lstrip("%").split(".")[0]
            out.setdefault(stem, []).append(e["dur"] * 1e6)
    return out


def _traced(fn, args, reps):
    import jax
    jax.block_until_ready(fn(*args))  # compiles
    tdir = tempfile.mkdtemp(prefix="flash_")
    try:
        with jax.profiler.trace(tdir):
            for _ in range(reps):
                out = fn(*args)
            jax.block_until_ready(out)
        return _custom_call_us(tdir)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def _window_pairs(seq, window):
    """(query, key) pairs of a causal attention over ``seq`` positions
    under a window: a query at p sees min(p + 1, window) keys."""
    window = min(window, seq)
    return window * (window + 1) // 2 + (seq - window) * window


def _blocks(seq, block_q, block_k, window):
    """(relevant, all) grid steps a head: a step is relevant if its
    block holds a key some query of it sees."""
    nq, nk = seq // block_q, seq // block_k
    rel = 0
    for i in range(nq):
        for j in range(nk):
            seen = j * block_k <= (i + 1) * block_q - 1
            if window is not None:
                seen = seen and ((j + 1) * block_k - 1
                                 >= i * block_q - (window - 1))
            rel += seen
    return rel, nq * nk


def _dense(q, k, v, window):
    """float32 attention of [B, S, H, D] over [B, S, KVH, D], causal."""
    import jax
    import jax.numpy as jnp
    b, s, h, d = q.shape
    g = h // k.shape[2]
    qf, kf, vf = (x.astype(jnp.float32) for x in (q, k, v))
    kf, vf = jnp.repeat(kf, g, axis=2), jnp.repeat(vf, g, axis=2)
    with jax.default_matmul_precision("highest"):
        sc = jnp.einsum("bqhd,bkhd->bhqk", qf, kf) * d ** -0.5
        pos = jnp.arange(s)
        seen = pos[:, None] >= pos[None, :]
        if window is not None:
            seen = seen & (pos[None, :] > pos[:, None] - window)
        sc = jnp.where(seen, sc, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(sc, -1), vf)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tag", default="change")
    ap.add_argument("--repo", default=HERE,
                    help="checkout to import paddle_tpu from")
    ap.add_argument("--only", default="",
                    help="comma-separated case names (default: all)")
    ap.add_argument("--no-yardstick", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes, for a rehearsal on the CPU")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "flash_report"))
    a = ap.parse_args()
    sys.path.insert(0, HERE)  # benchmarks/ (the reader) from this tree
    sys.path.insert(0, os.path.abspath(a.repo))

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not a.tiny:
        print("no TPU here: a time from the CPU is no device number "
              "(pass --tiny for a rehearsal)", file=sys.stderr)
        return 2
    if not on_chip:  # the Pallas interpreter, as the tests run it
        fa.pl.pallas_call = functools.partial(fa.pl.pallas_call,
                                              interpret=True)
    from benchmarks import flops
    from benchmarks.peaks import peaks_of
    peak = peaks_of(dev.device_kind if on_chip else "TPU v5 lite")["flops"]
    d = 128
    blk_q, blk_k = (128, 128) if a.tiny else (fa.DEFAULT_BLOCK_Q,
                                              fa.DEFAULT_BLOCK_K)
    dt = jnp.float32 if a.tiny else jnp.bfloat16
    rng = np.random.default_rng(a.seed)
    only = set(filter(None, a.only.split(",")))

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), dt)

    rows = []
    for name, b, h, kvh, seq, window, backward in (TINY if a.tiny else CASES):
        if only and name not in only:
            continue

        def attend(q, k, v, window=window, kvh=kvh, h=h):
            if kvh == h and window is None:
                return fa.flash_attention(q, k, v, causal=True,
                                          block_q=blk_q, block_k=blk_k)
            return fa.flash_attention_grouped(q, k, v, window=window,
                                              block_q=blk_q, block_k=blk_k)

        # -- against the dense float32 attention, at a short length ---------
        s_chk = min(seq, 512 if a.tiny else 2048)
        w_chk = None if window is None else min(window, s_chk // 2 + 72)
        q, k, v = draw(1, s_chk, h, d), draw(1, s_chk, kvh, d), \
            draw(1, s_chk, kvh, d)
        got = jax.jit(functools.partial(attend, window=w_chk))(q, k, v)
        err = float(jnp.max(jnp.abs(got.astype(jnp.float32)
                                    - _dense(q, k, v, w_chk))))

        # -- the calls' device time -----------------------------------------
        q, k, v = draw(b, seq, h, d), draw(b, seq, kvh, d), \
            draw(b, seq, kvh, d)
        if backward:
            w = draw(b, seq, h, d)
            fn = jax.jit(jax.grad(
                lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)
                                        * w.astype(jnp.float32)),
                argnums=(0, 1, 2)))
        else:
            fn = jax.jit(attend)
        reps = max(2, a.reps // 2) if seq >= 32768 else a.reps
        us = _traced(fn, (q, k, v), reps)
        bq, bk = fa._resolve_blocks(seq, seq, blk_q, blk_k)
        rel, steps = _blocks(seq, bq, bk, window)
        # one matmul over the pairs a query sees; inside a window
        # benchmarks/flops.py has no count of its own
        unit = (flops.flash_flops(b, h, seq, d, False) / 2 if window is None
                else 2.0 * d * _window_pairs(seq, window) * h * b)
        # the CPU has no device plane: one row, no time
        for kernel in [k for k in MATMULS if k in us] or ["flash_fwd"]:
            matmuls = MATMULS[kernel]
            row = {"tag": a.tag, "case": name, "kernel": kernel,
                   "shape": [b, h, seq, d], "kv_heads": kvh,
                   "window": window, "blocks": [bq, bk],
                   "relevant_blocks": rel * b * h,
                   "grid_steps": steps * b * h,
                   "block_rows": seq // bq * b * h, "max_err": err}
            if kernel in us:
                t = statistics.median(us[kernel])
                least = unit * matmuls / peak * 1e6
                row.update(us_a_call=round(t, 1), calls=len(us[kernel]),
                           least_us=round(least, 1),
                           mxu_pct=round(100 * least / t, 2),
                           us_a_relevant_block=round(t / (rel * b * h), 3),
                           us_a_grid_step=round(t / (steps * b * h), 3))
            rows.append(row)
            print(json.dumps(row), flush=True)

    # -- what a step of each kind costs, over the forward calls ---------------
    fwd = [r for r in rows if r["kernel"] == "flash_fwd" and "us_a_call" in r]
    if len(fwd) >= 3:
        a_mat = np.array([[r["relevant_blocks"],
                           r["grid_steps"] - r["relevant_blocks"],
                           r["block_rows"]] for r in fwd], float)
        y = np.array([r["us_a_call"] for r in fwd])
        # relative errors: the 32,768 call must not drown the short ones
        fit = np.linalg.lstsq(a_mat / y[:, None], np.ones(len(y)),
                              rcond=None)[0]
        row = {"tag": a.tag, "fit_over": [r["case"] for r in fwd],
               "us_a_relevant_step": round(float(fit[0]), 3),
               "us_a_skipped_step": round(float(fit[1]), 3),
               "us_a_row_of_blocks": round(float(fit[2]), 3),
               "worst_residual_pct": round(float(100 * np.max(np.abs(
                   a_mat @ fit / y - 1))), 1)}
        rows.append(row)
        print(json.dumps(row), flush=True)

    # -- jax's own kernels at the train shape, as a yardstick ---------------
    if on_chip and not a.no_yardstick and (not only or "train" in only):
        b, h, seq = 2, 16, 2048
        q, k, v = (jnp.swapaxes(draw(b, seq, h, d), 1, 2) for _ in range(3))
        unit = flops.flash_flops(b, h, seq, d, False) / 2
        for label, build in (("jax_flash_512", _jax_flash),
                             ("jax_splash_512", _jax_splash)):
            row = {"tag": a.tag, "case": "train", "kernel": label}
            try:
                us = _traced(build(seq, h, d), (q, k, v), a.reps)
                t = sum(statistics.median(x) for x in us.values())
                row.update(us_a_call=round(t, 1), kernels=sorted(us),
                           mxu_pct=round(100 * unit * 2 / peak * 1e6 / t, 2))
            except Exception as e:  # does not lower here: say so
                row["error"] = f"{type(e).__name__}: {str(e)[:200]}"
            rows.append(row)
            print(json.dumps(row), flush=True)

    os.makedirs(a.out, exist_ok=True)
    with open(os.path.join(a.out, f"report-{a.tag}.json"), "w") as f:
        json.dump({"device": {"platform": dev.platform,
                              "kind": dev.device_kind},
                   "repo": os.path.abspath(a.repo), "reps": a.reps,
                   "rows": rows,
                   "note": ("device times from the profiler's XLA Ops line"
                            if on_chip else "CPU rehearsal: no device "
                            "number")}, f, indent=1)
    return 0


def _jax_flash(seq, h, d):
    """jax's flash attention forward, [B, H, S, D], blocks of 512."""
    import jax
    from jax.experimental.pallas.ops.tpu import flash_attention as jfa
    bs = jfa.BlockSizes(block_q=512, block_k_major=512, block_k=512,
                        block_b=1)
    return jax.jit(functools.partial(jfa.flash_attention, causal=True,
                                     sm_scale=d ** -0.5, block_sizes=bs))


def _jax_splash(seq, h, d):
    """jax's splash attention forward under a causal mask, a batch row
    at a time (its kernel has no batch axis), blocks of 512."""
    import jax
    from jax.experimental.pallas.ops.tpu.splash_attention import (
        splash_attention_kernel as sk, splash_attention_mask as sm)
    mask = sm.MultiHeadMask([sm.CausalMask((seq, seq))] * h)
    kernel = sk.make_splash_mha_single_device(
        mask, block_sizes=sk.BlockSizes(block_q=512, block_kv=512,
                                        block_kv_compute=512))
    return jax.jit(jax.vmap(lambda q, k, v: kernel(q * d ** -0.5, k, v)))


if __name__ == "__main__":
    sys.exit(main())
