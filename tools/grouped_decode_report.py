"""The grouped page walk alone (ops/pallas/paged_attention.py
``paged_decode_grouped``) at the shapes of its two callers:

- ``mixed`` (``serve-smallthinker-mixed-sat``): 16 slots, 28 query heads
  over 4 KV heads of 128 (a group of 7 padded to 16), pages of 64, bf16;
  a global layer over ``[4097, 4, 64, 128]`` pools and a 256-wide table,
  and a window layer's ring (``[16 * 66 + 1, 4, 64, 128]``, a 66-wide
  table turned as ``models/smallthinker.py ring_view`` turns it, the
  window of 4,096 as ``kv_start``);
- ``state`` (``serve-solar2-longctx-sat``): 32 slots, 64 query heads over
  8 KV heads (a group of 8), ``[8193, 8, 64, 128]`` pools, a 545-wide
  table.

For each: 0 (all parked), a quarter, half and all slots live at contexts
of 1,024, 4,096 and 8,192 positions (and 32,768 in the state shape):
device µs a call, then a least-squares split into fixed, a live slot, a
page (``us = fixed + a_slot * live + a_page * pages walked``), and the
bandwidth the page term reaches (a page is its K and its V: 128 KB at 4
KV heads, 256 KB at 8).

A call's time is the DEVICE time of the Pallas custom call in a
profiler trace (read with the benchmark's reader,
``benchmarks/xplane.py``), the median over ``--reps`` calls. Before the
timing every variant is compared with
``paged_attention_grouped_reference`` on the same device (``max_err``),
over four slots: one live slot ending mid-page, a parked one, a long
one and a single-position one. ``--blocks`` times the walk at several
pages a block as well as at the rule's choice (``auto``);
``--repo <tree>`` times another checkout's kernel (a parent's, which
knows ``auto`` alone).

Nothing here is a benchmark cell.

    chiprun -- python3 tools/grouped_decode_report.py --blocks auto,4,8,16
    chiprun -- python3 tools/grouped_decode_report.py --repo _checkout/parent --tag parent
    JAX_PLATFORMS=cpu python3 tools/grouped_decode_report.py --tiny   # control flow only
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


def _kernel_us(trace_dir, name):
    from benchmarks import xplane
    return [e["dur"] * 1e6 for e in xplane.leaf_ops(xplane.load_events(
        xplane.find_xplane(trace_dir))) if name in e["name"]
        and xplane.op_kind(e) == "custom-call"]


def _traced(fn, reps, name):
    import jax
    jax.block_until_ready(fn())  # compiles
    tdir = tempfile.mkdtemp(prefix="grouped_")
    try:
        with jax.profiler.trace(tdir):
            for _ in range(reps):
                out = fn()
            jax.block_until_ready(out)
        return _kernel_us(tdir, name)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


# name: (slots, query heads, KV heads, pool pages, table width, window)
SHAPES = {
    "mixed.global": (16, 28, 4, 4097, 256, None),
    "mixed.ring": (16, 28, 4, 16 * 66 + 1, 66, 4096),
    "state": (32, 64, 8, 8193, 545, None),
}
TINY = {
    "mixed.global": (4, 14, 2, 41, 12, None),
    "mixed.ring": (4, 14, 2, 4 * 6 + 1, 6, 64),
    "state": (4, 16, 2, 41, 12, None),
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=8)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--blocks", default="auto",
                    help="pages a block: auto (the rule's) and/or numbers, "
                         "e.g. auto,4,8,16")
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--repo", default=HERE,
                    help="the checkout whose kernel is timed")
    ap.add_argument("--tag", default="change")
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes in interpret mode, for a rehearsal "
                         "on the CPU")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "grouped_decode_report"))
    a = ap.parse_args()
    sys.path[:0] = [os.path.abspath(a.repo), HERE]

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import flash_attention as fa
    from paddle_tpu.ops.pallas import paged_attention as pa

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not a.tiny:
        print("no TPU here: a time from the CPU is no device number "
              "(pass --tiny for a rehearsal)", file=sys.stderr)
        return 2
    from benchmarks.peaks import peaks_of
    hbm = peaks_of(dev.device_kind if on_chip else "TPU v5 lite")[
        "hbm_bytes_per_s"]
    blocks = [b if b == "auto" else int(b) for b in a.blocks.split(",")]
    if not hasattr(pa, "grouped_block_pages"):  # a parent's tree
        blocks = ["auto"]
    page, d = (16, 128) if a.tiny else (64, 128)
    dt = jnp.float32 if a.tiny else jnp.bfloat16
    if a.tiny:  # Mosaic's gates answer no on the CPU: interpret the kernel
        import functools
        fa.pl.pallas_call = functools.partial(fa.pl.pallas_call,
                                              interpret=True)
        force = fa.force_flash_for_aot()
        force.__enter__()
    rng = np.random.default_rng(a.seed)
    key = jax.random.PRNGKey(a.seed)
    rows, errs = [], {}

    for name in a.shapes.split(","):
        slots, hq, kvh, n_pool, width, window = (TINY if a.tiny
                                                 else SHAPES)[name]
        if a.tiny:
            contexts, lives = (20, 90), (0, 2, 4)
        else:
            contexts = (1024, 4096, 8192) + ((32768,) if name == "state"
                                             else ())
            lives = (0, slots // 4, slots // 2, slots)
        k1, k2, k3, key = jax.random.split(key, 4)
        kp = jax.random.normal(k1, (n_pool, kvh, page, d), dt)
        vp = jax.random.normal(k2, (n_pool, kvh, page, d), dt)
        q = jax.random.normal(k3, (slots, 1, hq, d), dt)
        page_bytes = 2 * kvh * page * d * jnp.dtype(dt).itemsize

        def table_for(lens_in):
            """Distinct pages a live slot (as far as the pool has them),
            the scratch page for the rest; a ring turned so that entry 0
            holds the oldest key the window sees."""
            lens = np.asarray(lens_in, np.int32)
            t = np.full((len(lens), width), n_pool - 1, np.int32)
            lo = np.zeros(len(lens), np.int32)
            at = rng.permutation(n_pool - 1)
            for i, n in enumerate(lens):
                if window is not None:
                    lo[i] = max(int(n) - window, 0)
                    first = lo[i] // page
                    lens[i] -= first * page
                    lo[i] -= first * page
                need = -(-int(lens[i]) // page)
                t[i, :need] = np.resize(at[(i * need) % (n_pool - 1):], need)
            return (jnp.asarray(t), jnp.asarray(lens),
                    None if window is None else jnp.asarray(lo))

        def call_for(block):
            if block == "auto":
                return jax.jit(lambda q, k, v, t, n, lo:
                               pa.paged_attention_grouped(
                                   q, k, v, t, n, kv_start=lo))

            def fn(q, k, v, t, n, lo):
                b_, _, h, _ = q.shape
                lo = jnp.zeros_like(n) if lo is None else lo
                return pa._paged_decode_grouped_pallas(
                    q.reshape(b_, h, d), k, v, t, n, lo, d ** -0.5,
                    block).reshape(q.shape)
            return jax.jit(fn)

        # -- against the gather-and-softmax, on this device ------------------
        c0, c1 = contexts[0], contexts[-1]
        t, lens, lo = table_for([c0 - 7, 0, c1 + 5, 1])
        want = pa.paged_attention_grouped_reference(
            q[:4].astype(jnp.float32), kp.astype(jnp.float32),
            vp.astype(jnp.float32), t, lens, kv_start=lo)
        for block in blocks:
            got = call_for(block)(q[:4], kp, vp, t, lens, lo)
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - want)))
            errs[f"{name}.{block}"] = {
                "max_err": err, "parked_row_zero": bool(jnp.all(got[1] == 0))}
        print(json.dumps({"shape": name, "max_err": {
            k: v for k, v in errs.items() if k.startswith(name + ".")}}),
            flush=True)

        # -- device time a call -----------------------------------------------
        auto = (pa.grouped_block_pages(kvh, page, d, jnp.dtype(dt).itemsize,
                                       width)
                if hasattr(pa, "grouped_block_pages") else None)
        for block in (b for b in blocks if b != auto):
            call = call_for(block)
            fit_x, fit_y = [], []
            for live in lives:
                for ctx in (contexts if live else contexts[:1]):
                    t, lens, lo = table_for([ctx] * live
                                            + [0] * (slots - live))
                    firsts = (np.zeros(slots, np.int64) if lo is None
                              else np.asarray(lo) // page)
                    walked = int(np.sum(np.where(
                        np.asarray(lens) > 0,
                        -(-np.asarray(lens) // page) - firsts, 0)))
                    row = {"kernel": "paged_decode_grouped", "tree": a.tag,
                           "shape": name, "block": block, "auto": auto,
                           "live": live,
                           "context": ctx, "pages": walked}
                    us = [] if a.tiny else _traced(
                        lambda: call(q, kp, vp, t, lens, lo), a.reps,
                        "paged_decode_grouped")
                    if us:
                        med = statistics.median(us)
                        least = walked * page_bytes / hbm * 1e6
                        row.update(us_a_call=round(med, 2),
                                   least_us=round(least, 2),
                                   roofline_pct=round(100 * least / med, 1))
                        fit_x.append([1.0, live, walked])
                        fit_y.append(med)
                    rows.append(row)
                    print(json.dumps(row), flush=True)
            if len(fit_y) >= 3:
                (fixed, a_slot, a_page), *_ = np.linalg.lstsq(
                    np.asarray(fit_x), np.asarray(fit_y), rcond=None)
                pred = np.asarray(fit_x) @ np.asarray([fixed, a_slot, a_page])
                row = {"kernel": "paged_decode_grouped", "tree": a.tag,
                       "shape": name, "block": block,
                       "fit_us": {"fixed": round(float(fixed), 2),
                                  "a_live_slot": round(float(a_slot), 3),
                                  "a_page": round(float(a_page), 4)},
                       "worst_residual_pct": round(float(np.max(np.abs(
                           pred / np.asarray(fit_y) - 1))) * 100, 1),
                       "page_gbytes_per_s": round(
                           page_bytes / max(float(a_page), 1e-9) / 1e3, 1)}
                rows.append(row)
                print(json.dumps(row), flush=True)

    os.makedirs(a.out, exist_ok=True)
    with open(os.path.join(a.out, f"{a.tag}.json"), "w") as f:
        json.dump({"device": {"platform": dev.platform,
                              "kind": dev.device_kind},
                   "repo": os.path.abspath(a.repo), "max_err": errs,
                   "rows": rows}, f, indent=1)
    worst = max(v["max_err"] for v in errs.values())
    parked = all(v["parked_row_zero"] for v in errs.values())
    return 0 if worst < (2e-3 if a.tiny else 5e-2) and parked else 1


if __name__ == "__main__":
    sys.exit(main())
