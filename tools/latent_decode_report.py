"""The absorbed latent decode kernel alone (ops/pallas/paged_attention.py
``paged_decode_latent``) at the shapes of the GLM-4.7-Flash serve cell
(20 heads, rows of 512 + 64 stored 640 wide, 48 slots, pages of 64, bf16)
and flash attention at head size 256 beside 128 at equal FLOPs.

- ``paged_decode_latent`` with 0 (all parked), 8, 24 and 48 live slots
  at contexts of 1,024, 8,192 and 32,768 positions: device µs a call,
  then a least-squares split into fixed, a live slot, a page
  (``us = fixed + a_slot * live + a_page * pages walked``), and the
  bandwidth the page term reaches against the REQUIRED bytes (576 wide);
- ``flash_fwd`` over one prompt of ``--flash-len`` positions, causal,
  group 1: 20 heads of 256 (this cell's prefill) against 40 heads of
  128 (the same QK^T + PV operations).

A call's time is the DEVICE time of the Pallas custom call in a
profiler trace (read with the benchmark's reader,
``benchmarks/xplane.py``), the median over ``--reps`` calls. Before the
timing each kernel is compared with its plain reference on the same
device (``max_err``). ``--blocks`` times the latent kernel at several
pages a block.

Nothing here is a benchmark cell.

    chiprun -- python3 tools/latent_decode_report.py
    JAX_PLATFORMS=cpu python3 tools/latent_decode_report.py --tiny   # control flow only
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import statistics
import sys
import tempfile

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, HERE)


def _kernel_us(trace_dir, name):
    from benchmarks import xplane
    return [e["dur"] * 1e6 for e in xplane.leaf_ops(xplane.load_events(
        xplane.find_xplane(trace_dir))) if name in e["name"]
        and xplane.op_kind(e) == "custom-call"]


def _traced(fn, reps, name):
    import jax
    jax.block_until_ready(fn())  # compiles
    tdir = tempfile.mkdtemp(prefix="latent_")
    try:
        with jax.profiler.trace(tdir):
            for _ in range(reps):
                out = fn()
            jax.block_until_ready(out)
        return _kernel_us(tdir, name)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--blocks", default="4",
                    help="pages a block of the latent kernel, e.g. 2,4,8")
    ap.add_argument("--flash-len", type=int, default=8192)
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes, for a rehearsal on the CPU")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "latent_decode_report"))
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.served import dense_attention
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import paged_attention as pa

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not a.tiny:
        print("no TPU here: a time from the CPU is no device number "
              "(pass --tiny for a rehearsal)", file=sys.stderr)
        return 2
    from benchmarks.peaks import peaks_of
    pk = peaks_of(dev.device_kind if on_chip else "TPU v5 lite")
    hbm, peak = pk["hbm_bytes_per_s"], pk["flops"]
    if a.tiny:
        heads, rank, rope, w, page, slots, n_pages = 4, 128, 32, 256, 16, 4, 40
        contexts, lives, dt = (16, 100), (0, 2, 4), jnp.float32
        flash_len = 64
    else:
        heads, rank, rope, w, page, slots, n_pages = 20, 512, 64, 640, 64, \
            48, 9216
        contexts, lives, dt = (1024, 8192, 32768), (0, 8, 24, 48), \
            jnp.bfloat16
        flash_len = a.flash_len
    scale = (192 + rope) ** -0.5
    max_pages = -(-max(contexts) // page) + 32
    rng = np.random.default_rng(a.seed)
    pool = jnp.asarray(rng.standard_normal((n_pages + 1, page, w)), dt) \
        .at[..., rank + rope:].set(0)
    q = jnp.asarray(rng.standard_normal((slots, heads, rank + rope)), dt)
    kernel = not a.tiny  # on the CPU the entry routes to the reference

    def table_for(live, ctx):
        """``live`` slots of ``ctx`` positions over distinct pages (as
        far as the pool has them), the others parked."""
        need = -(-ctx // page)
        t = np.full((slots, max_pages), n_pages, np.int32)
        lens = np.zeros(slots, np.int32)
        at = rng.permutation(n_pages)
        for i in range(live):
            t[i, :need] = np.resize(at[(i * need) % n_pages:], need)
            lens[i] = ctx
        return jnp.asarray(t), jnp.asarray(lens)

    # -- against the gather-and-softmax, on this device ---------------------
    t, lens = table_for(min(3, slots), contexts[0] - 7)
    got = pa.paged_attention_latent(q, pool, t, lens, rank, scale,
                                    interpret=a.tiny)
    want = pa.paged_attention_latent_reference(
        q.astype(jnp.float32), pool.astype(jnp.float32), t, lens, rank,
        scale)
    err = {"latent": float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))),
           "parked_rows_zero": bool(jnp.all(got[3:] == 0))}
    d_small = 256 if not a.tiny else 32
    n_small = 512 if not a.tiny else 64
    qs, ks, vs = (jnp.asarray(rng.standard_normal(
        (1, n_small, heads, d_small)), dt) for _ in range(3))
    if kernel:
        o = fa.flash_attention_grouped(qs, ks, vs, window=None,
                                       scale=d_small ** -0.5)
        o_ref = dense_attention(qs.astype(jnp.float32),
                                ks.astype(jnp.float32),
                                vs.astype(jnp.float32), None,
                                d_small ** -0.5)
        err["flash_d256"] = float(jnp.max(jnp.abs(
            o.astype(jnp.float32) - o_ref)))
    print(json.dumps({"max_err": err}), flush=True)

    rows = []
    # -- paged_decode_latent --------------------------------------------------
    for block in (int(b) for b in a.blocks.split(",")):
        call = jax.jit(lambda q, p, t, n, block=block:
                       pa.paged_attention_latent(q, p, t, n, rank, scale,
                                                 block=block))
        fit_x, fit_y = [], []
        for live in lives:
            for ctx in (contexts if live else contexts[:1]):
                t, lens = table_for(live, ctx)
                us = _traced(lambda: call(q, pool, t, lens), a.reps,
                             "paged_decode_latent") if kernel else []
                pages = live * -(-ctx // page)
                row = {"kernel": "paged_decode_latent", "block": block,
                       "slots": slots, "live": live, "context": ctx,
                       "pages": pages}
                if us:
                    med = statistics.median(us)
                    least = live * ctx * (rank + rope) \
                        * jnp.dtype(dt).itemsize / hbm * 1e6
                    row.update(us_a_call=round(med, 2),
                               least_us=round(least, 2),
                               roofline_pct=round(100 * least / med, 1))
                    fit_x.append([1.0, live, pages])
                    fit_y.append(med)
                rows.append(row)
                print(json.dumps(row), flush=True)
        if len(fit_y) >= 3:
            (fixed, a_slot, a_page), *_ = np.linalg.lstsq(
                np.asarray(fit_x), np.asarray(fit_y), rcond=None)
            row = {"kernel": "paged_decode_latent", "block": block,
                   "fit_us": {"fixed": round(float(fixed), 2),
                              "a_live_slot": round(float(a_slot), 3),
                              "a_page": round(float(a_page), 4)},
                   "page_gbytes_per_s_required": round(
                       page * (rank + rope) * jnp.dtype(dt).itemsize
                       / max(float(a_page), 1e-9) / 1e3, 1)}
            rows.append(row)
            print(json.dumps(row), flush=True)

    # -- flash at d 256 beside d 128, equal FLOPs ---------------------------
    for h, d in ((heads, d_small), (2 * heads, d_small // 2)):
        x = [jnp.asarray(rng.standard_normal((1, flash_len, h, d)), dt)
             for _ in range(3)]
        fn = jax.jit(lambda q, k, v, d=d: fa.flash_attention_grouped(
            q, k, v, window=None, scale=d ** -0.5))
        us = _traced(lambda: fn(*x), a.reps, "flash_fwd") if kernel else []
        work = 4.0 * h * d * flash_len * (flash_len + 1) / 2
        row = {"kernel": "flash_fwd", "heads": h, "head_dim": d,
               "len": flash_len, "flops": work}
        if us:
            med = statistics.median(us)
            row.update(us_a_call=round(med, 1),
                       roofline_pct=round(100 * work / peak / med * 1e6, 1))
        rows.append(row)
        print(json.dumps(row), flush=True)

    os.makedirs(a.out, exist_ok=True)
    with open(os.path.join(a.out, "report.json"), "w") as f:
        json.dump({"device": {"platform": dev.platform,
                              "kind": dev.device_kind},
                   "max_err": err, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
