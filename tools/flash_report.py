"""The flash attention kernels alone (ops/pallas/flash_attention.py), at
the shapes of the four cells that run them:

- ``train``: ``[2, 16, 2048, 128]`` causal, forward and both backward
  calls (``flash_fwd``, ``flash_bwd_dq``, ``flash_bwd_dkv``);
- ``prefill-1024`` / ``prefill-2048``: ``[1, 16, S, 128]`` (GPT-1.3B's
  prefill buckets);
- ``window-8192`` / ``global-8192``: ``[1, 28, 8192, 128]`` over 4 KV
  heads (a group of 7) with and without a window of 4096 (SmallThinker);
- ``solar-8192`` / ``solar-32768``: ``[1, 64, S, 128]`` over 8 KV heads
  (a group of 8);
- ``glm-8192`` / ``glm-32768``: ``[1, 20, S, 256]`` (latent attention's
  expanded prefill);
- ragged, ``<case>@<length>``: the serving prefill's call on a bucket
  that a prompt of ``length`` fills in part, handed the true length
  (``flash_attention_grouped(lengths=...)``): ``glm-32768`` and
  ``solar-32768`` at 32768 / 24576 / 16896, ``window-8192`` at 6144. A
  tree whose forward takes no lengths (``--repo`` of a parent before PR
  35) runs the same rows without: what it pays for the bucket.

A call's time is the DEVICE time of the Pallas custom call in a
profiler trace (read with the benchmark's reader,
``benchmarks/xplane.py``), the median over ``--reps`` calls; beside it
the least time the chip could take for the call's required operations
(``benchmarks/flops.py``: the causal half, and inside a window only the
keys a query sees), the time a RELEVANT block (a ``block_q x block_k``
grid step that holds a visible key) and a grid step of any kind, and
last a least-squares split of the forward calls' times into what a
relevant step, a skipped step of a live row of blocks, a skipped step of
a row past the true length and a row of blocks (its start and finish)
cost. A ragged row's required operations are those of its true length.
Before the timing each case is compared with the dense float32
attention on the same device at a short length (``max_err``; a ragged
case over the rows before the length, and the blocks of rows past it
must come back zero).

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
import inspect
import json
import os
import shutil
import statistics
import sys
import tempfile

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (name, batch, query heads, KV heads, sequence, window, backward too,
#  head size, true length or None)
CASES = [("train", 2, 16, 16, 2048, None, True, 128, None),
         ("prefill-1024", 1, 16, 16, 1024, None, False, 128, None),
         ("prefill-2048", 1, 16, 16, 2048, None, False, 128, None),
         ("window-8192", 1, 28, 4, 8192, 4096, False, 128, None),
         ("global-8192", 1, 28, 4, 8192, None, False, 128, None),
         ("solar-8192", 1, 64, 8, 8192, None, False, 128, None),
         ("solar-32768", 1, 64, 8, 32768, None, False, 128, None),
         ("glm-8192", 1, 20, 20, 8192, None, False, 256, None),
         ("glm-32768", 1, 20, 20, 32768, None, False, 256, None)]
CASES += [(f"{name}@{length}", 1, h, kvh, seq, window, False, d, length)
          for name, h, kvh, seq, window, d, lengths in (
              ("glm-32768", 20, 20, 32768, None, 256, (32768, 24576, 16896)),
              ("solar-32768", 64, 8, 32768, None, 128, (32768, 24576, 16896)),
              ("window-8192", 28, 4, 8192, 4096, 128, (6144,)))
          for length in lengths]
# required matmuls a kernel is charged with: the backward's five are dq's
# own and the scores' share (2), and dk, dv and dp (3)
MATMULS = {"flash_fwd": 2, "flash_fwd_single": 2, "flash_bwd_dq": 2,
           "flash_bwd_dkv": 3, "flash_bwd_fused": 5}
TINY = [("train", 1, 2, 2, 512, None, True, 128, None),
        ("window", 1, 4, 2, 512, 200, False, 128, None),
        ("global", 1, 4, 2, 512, None, False, 128, None),
        ("global@300", 1, 4, 2, 512, None, False, 128, 300),
        ("window@129", 1, 4, 2, 512, 200, False, 128, 129)]


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


def _pairs(seq, window):
    """(query, key) pairs of a causal attention over ``seq`` positions:
    a query at p sees p + 1 keys, under a window min(p + 1, window)."""
    window = seq if window is None else min(window, seq)
    return window * (window + 1) // 2 + (seq - window) * window


def _blocks(seq, block_q, block_k, window, length=None):
    """(relevant, skipped in a live row, skipped in a row past the
    length) grid steps a head: a step is relevant if its block holds a
    key some query of it sees and, with ``length``, neither its queries
    nor its keys start at or past it."""
    nq, nk = seq // block_q, seq // block_k
    rel = dead = 0
    for i in range(nq):
        if length is not None and i * block_q >= length:
            dead += nk
            continue
        for j in range(nk):
            seen = j * block_k <= (i + 1) * block_q - 1
            if window is not None:
                seen = seen and ((j + 1) * block_k - 1
                                 >= i * block_q - (window - 1))
            if length is not None:
                seen = seen and j * block_k < length
            rel += seen
    return rel, nq * nk - rel - dead, dead


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
    blk_q, blk_k = (128, 128) if a.tiny else (fa.DEFAULT_BLOCK_Q,
                                              fa.DEFAULT_BLOCK_K)
    dt = jnp.float32 if a.tiny else jnp.bfloat16
    rng = np.random.default_rng(a.seed)
    only = set(filter(None, a.only.split(",")))
    takes_lengths = "lengths" in inspect.signature(
        fa.flash_attention_grouped).parameters

    def draw(*shape):
        return jnp.asarray(rng.standard_normal(shape), dt)

    rows = []
    for name, b, h, kvh, seq, window, backward, d, length in (
            TINY if a.tiny else CASES):
        if only and not {name, name.split("@")[0]} & only:
            continue
        ragged = length is not None and takes_lengths

        def attend(q, k, v, n=None, window=window, kvh=kvh, h=h,
                   length=length):
            if kvh == h and window is None and length is None:
                return fa.flash_attention(q, k, v, causal=True,
                                          block_q=blk_q, block_k=blk_k)
            extra = {} if n is None else {"lengths": n}
            return fa.flash_attention_grouped(q, k, v, window=window,
                                              block_q=blk_q, block_k=blk_k,
                                              **extra)

        def lens(n):
            return (jnp.full((b,), n, jnp.int32),) if ragged else ()

        # -- against the dense float32 attention, at a short length ---------
        s_chk = min(seq, 512 if a.tiny else 2048)
        w_chk = None if window is None else min(window, s_chk // 2 + 72)
        n_chk = s_chk if length is None else -(-s_chk * length // seq)
        q, k, v = draw(1, s_chk, h, d), draw(1, s_chk, kvh, d), \
            draw(1, s_chk, kvh, d)
        got = jax.jit(functools.partial(attend, window=w_chk))(
            q, k, v, *lens(n_chk)).astype(jnp.float32)
        err = float(jnp.max(jnp.abs(got - _dense(q, k, v, w_chk))[:, :n_chk]))
        bq, bk = fa._resolve_blocks(s_chk, s_chk, blk_q, blk_k)
        if ragged and bool(jnp.any(got[:, -(-n_chk // bq) * bq:])):
            err = float("inf")  # a row of a block past the length is not 0

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
        us = _traced(fn, (q, k, v) + lens(length), reps)
        bq, bk = fa._resolve_blocks(seq, seq, blk_q, blk_k)
        rel, skipped, past = _blocks(seq, bq, bk, window,
                                     length if ragged else None)
        # one matmul over the pairs a query sees: those of the true length
        unit = 2.0 * d * _pairs(length or seq, window) * h * b
        # the CPU has no device plane: one row, no time
        for kernel in [k for k in MATMULS if k in us] or ["flash_fwd"]:
            matmuls = MATMULS[kernel]
            row = {"tag": a.tag, "case": name, "kernel": kernel,
                   "shape": [b, h, seq, d], "kv_heads": kvh,
                   "window": window, "blocks": [bq, bk],
                   "relevant_blocks": rel * b * h,
                   "skipped_steps": skipped * b * h,
                   "block_rows": seq // bq * b * h, "max_err": err}
            if length is not None:
                row.update(length=length, lengths_taken=ragged,
                           steps_past_length=past * b * h)
            if kernel in us:
                t = statistics.median(us[kernel])
                least = unit * matmuls / peak * 1e6
                steps = (rel + skipped + past) * b * h
                row.update(us_a_call=round(t, 1), calls=len(us[kernel]),
                           least_us=round(least, 1),
                           mxu_pct=round(100 * least / t, 2),
                           us_a_relevant_block=round(t / (rel * b * h), 3),
                           us_a_grid_step=round(t / steps, 3))
            rows.append(row)
            print(json.dumps(row), flush=True)

    # -- what a step of each kind costs, over the forward calls of a head
    # size (a step's matmuls grow with it) ----------------------------------
    # (a ragged row that was handed no lengths is its base case again)
    fwd = [r for r in rows if r["kernel"] == "flash_fwd" and "us_a_call" in r
           and r.get("lengths_taken", True)]
    for d in sorted({r["shape"][3] for r in fwd}):
        of_d = [r for r in fwd if r["shape"][3] == d]
        kinds = ["relevant_blocks", "skipped_steps", "steps_past_length",
                 "block_rows"]
        if not any(r.get("steps_past_length") for r in of_d):
            kinds.remove("steps_past_length")
        a_mat = np.array([[r.get(c, 0) for c in kinds] for r in of_d], float)
        if np.linalg.matrix_rank(a_mat) < len(kinds):
            continue  # too few shapes of this head size to tell them apart
        y = np.array([r["us_a_call"] for r in of_d])
        # relative errors: the 32,768 call must not drown the short ones
        coef = np.linalg.lstsq(a_mat / y[:, None], np.ones(len(y)),
                               rcond=None)[0]
        fit = dict(zip(kinds, coef))
        row = {"tag": a.tag, "head_size": d,
               "fit_over": [r["case"] for r in of_d],
               "us_a_relevant_step": round(float(fit["relevant_blocks"]), 3),
               "us_a_skipped_step": round(float(fit["skipped_steps"]), 3),
               "us_a_row_of_blocks": round(float(fit["block_rows"]), 3),
               "worst_residual_pct": round(float(100 * np.max(np.abs(
                   a_mat @ coef / y - 1))), 1)}
        if "steps_past_length" in fit:
            row["us_a_skipped_step_of_a_padded_row"] = round(
                float(fit["steps_past_length"]), 3)
        rows.append(row)
        print(json.dumps(row), flush=True)

    # -- jax's own kernels at the train shape, as a yardstick ---------------
    if on_chip and not a.no_yardstick and (not only or "train" in only):
        b, h, seq, d = 2, 16, 2048, 128
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
