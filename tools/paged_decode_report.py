"""The paged decode call, split into what is fixed and what the pages
cost, at the GPT-1.3B serve cell's shapes (16 slots, pools
``[513, 64, 16, 128]`` bf16, table ``[16, 32]``, W ``[2048, 2048]`` bf16
and a bias).

For each mix of live slots and contexts (the three serve mixes' mean
step, every slot parked, every slot live) it times two ways of getting
the attention block's output row:

- ``fused``: ``paged_attention_fused`` (the engine's decode op);
- ``unfused``: ``paged_attention`` + ``jnp.matmul`` + bias, the
  composition ``fused_step=False`` traces.

A call's time is the DEVICE time of its ops in a profiler trace (read
with the benchmark's reader, ``benchmarks/xplane.py``) of one program that chains ``--layers`` calls (each call's output feeds the next
call's query, as a model's layers do), ``--reps`` launches: ``kernel``
is the Pallas custom call, ``rest`` every other op of the program a call
(the chain's glue; in ``unfused`` also XLA's matmul and bias add). The
fixed part is the reading with every length 0; what a live slot and a
page cost beyond it is a least-squares fit over the mixes (a page = K
and V of 64 positions).

Nothing here is a benchmark cell. To read another tree's kernel (the
parent's), pass ``--repo <checkout>``: the script imports ``paddle_tpu``
from there.

    chiprun -- python3 tools/paged_decode_report.py --tag change
    chiprun -- python3 tools/paged_decode_report.py --repo _checkout/parent --tag parent
    JAX_PLATFORMS=cpu python3 tools/paged_decode_report.py --tiny   # control flow only
"""

from __future__ import annotations

import argparse
import json
import os
import re
import shutil
import statistics
import sys
import tempfile
import time
import zlib

import numpy as np

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (name, live slots, context in tokens): streams a step and the mean
# context of each mix's decode steps (PERF.md §5)
MIXES = [("parked", 0, 0), ("long-r80", 3, 1390), ("chat-r80", 4, 300),
         ("chat-sat", 11, 300), ("all-chat", 16, 300),
         ("all-long", 16, 1390)]


def _inputs(a, live, ctx, seed):
    """``(table, lens, pages)``: live slots spread among parked ones,
    distinct pages each."""
    rng = np.random.default_rng(seed)
    lens = np.zeros(a.slots, np.int32)
    if live:
        where = np.linspace(0, a.slots - 1, live).round().astype(int)
        lens[where] = np.minimum(ctx, a.max_pages * a.page)
    table = rng.permutation(a.pool - 1)[:a.slots * a.max_pages]
    table = table.reshape(a.slots, a.max_pages).astype(np.int32)
    return table, lens, int(np.sum(-(-lens // a.page)))


def _device_op_us(trace_dir):
    """``(kernel, other)``: device µs of each Pallas custom call, and
    µs under each other op's stem (``fusion.12`` -> ``fusion``), read
    with the benchmark's own trace reader; nothing where the backend
    has no device plane (the CPU)."""
    from benchmarks import xplane
    kernel, other = [], {}
    for e in xplane.leaf_ops(xplane.load_events(xplane.find_xplane(
            trace_dir))):
        if xplane.op_kind(e) == "custom-call":
            kernel.append(e["dur"] * 1e6)
        else:
            stem = re.sub(r"[.\d]+$", "", xplane.short_name(e))
            other[stem] = other.get(stem, 0.0) + e["dur"] * 1e6
    return kernel, other


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--repo", default=HERE,
                    help="checkout to import paddle_tpu from")
    ap.add_argument("--tag", default="change")
    ap.add_argument("--layers", type=int, default=24)
    ap.add_argument("--reps", type=int, default=20)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes, for a rehearsal on the CPU")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "paged_decode_report"))
    a = ap.parse_args()
    sys.path[:0] = [os.path.abspath(a.repo), HERE]

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not a.tiny:
        print("no TPU here: a time from the CPU is no device number "
              "(pass --tiny for a rehearsal)", file=sys.stderr)
        return 2
    a.slots, a.max_pages = 16, 32
    a.page, a.h, a.d = (8, 2, 64) if a.tiny else (64, 16, 128)
    if a.tiny:
        a.layers, a.reps = 2, 2
    a.pool = a.slots * a.max_pages + 1
    e = a.h * a.d
    dt = jnp.bfloat16
    key = jax.random.PRNGKey(a.seed)
    kq, kk, kv, kw, kb = jax.random.split(key, 5)
    q0 = jax.random.normal(kq, (a.slots, 1, a.h, a.d), dt)
    kp = jax.random.normal(kk, (a.pool, a.page, a.h, a.d), dt)
    vp = jax.random.normal(kv, (a.pool, a.page, a.h, a.d), dt)
    # a weight and a bias of its own for every call of the chain, as a
    # model's layers have: one array shared by all of them is small
    # enough for XLA to keep in VMEM for the whole program (128 MiB on
    # a v5e), and its copy then costs nothing (read so, PR 31)
    ws = [(jax.random.normal(k, (e, e), jnp.float32) / e ** 0.5).astype(dt)
          for k in jax.random.split(kw, a.layers)]
    bs = [jax.random.normal(k, (e,), dt)
          for k in jax.random.split(kb, a.layers)]

    def fused(q, kp, vp, w, b, table, lens):
        return pa.paged_attention_fused(q, kp, vp, table, lens, w, b)

    def unfused(q, kp, vp, w, b, table, lens):
        ctx = pa.paged_attention(q, kp, vp, table, lens)
        return jnp.matmul(ctx.reshape(a.slots, 1, e), w) + b

    def chain(op):
        def run(q, kp, vp, ws, bs, table, lens):
            for w, b in zip(ws, bs):
                out = op(q, kp, vp, w, b, table, lens)
                q = q0 + out.reshape(q.shape) * jnp.asarray(1e-3, dt)
            return q
        return jax.jit(run)

    rows = []
    for impl, op in (("fused", fused), ("unfused", unfused)):
        prog = chain(op)
        for name, live, ctx in MIXES:
            if a.tiny:
                ctx = min(ctx, 100)
            table, lens, pages = _inputs(a, live, ctx, a.seed)
            args = (q0, kp, vp, ws, bs, jnp.asarray(table),
                    jnp.asarray(lens))
            # what 24 chained calls leave: equal between two trees,
            # their kernels agree to the bit
            crc = zlib.crc32(np.asarray(
                prog(*args).astype(jnp.float32)).tobytes())  # compiles
            trace_dir = tempfile.mkdtemp(prefix="pdr_")
            try:
                with jax.profiler.trace(trace_dir):
                    t0 = time.perf_counter()
                    for _ in range(a.reps):
                        out = prog(*args)
                    out.block_until_ready()
                    wall = time.perf_counter() - t0
                kern, other = _device_op_us(trace_dir)
            finally:
                shutil.rmtree(trace_dir, ignore_errors=True)
            calls = a.layers * a.reps
            row = {"impl": impl, "mix": name, "live": live, "ctx": ctx,
                   "pages": pages, "out_crc32": crc,
                   "wall_us_a_call": round(wall / calls * 1e6, 2)}
            if kern:
                rest = sum(other.values())
                row.update(
                    kernel_us=round(statistics.median(kern), 2),
                    kernel_calls=len(kern),
                    rest_us=round(rest / calls, 2),
                    call_us=round((sum(kern) + rest) / calls, 2),
                    rest_us_by_op={k: round(v / calls, 2)
                                   for k, v in sorted(other.items())})
            rows.append(row)
            print(json.dumps(row), flush=True)

    fits = {}
    for impl in ("fused", "unfused"):
        mine = [r for r in rows if r["impl"] == impl]
        for what in ("kernel_us", "call_us"):
            if not all(what in r for r in mine):
                continue
            # call = fixed + a live slot's start (its first page's DMA
            # is waited for in its own grid step) + the pages
            x = np.asarray([[1.0, r["live"], r["pages"]] for r in mine])
            y = np.asarray([r[what] for r in mine], float)
            (icpt, slot, page), *_ = np.linalg.lstsq(x, y, rcond=None)
            fits[f"{impl}.{what}"] = {
                "parked": float(y[x[:, 2] == 0][0]),
                "fit_fixed_us": round(float(icpt), 2),
                "fit_us_a_live_slot": round(float(slot), 3),
                "fit_us_a_page": round(float(page), 4)}
    head = {"tag": a.tag, "repo": os.path.abspath(a.repo),
            "device": {"platform": dev.platform, "kind": dev.device_kind},
            "shapes": {"slots": a.slots, "pool": [a.pool, a.page, a.h, a.d],
                       "table": [a.slots, a.max_pages], "w": [e, e],
                       "dtype": "bfloat16"},
            "layers": a.layers, "reps": a.reps,
            "note": ("device times from the profiler's XLA Ops line"
                     if on_chip else
                     "CPU rehearsal: wall times of the reference path, "
                     "no device number"),
            "fits": fits, "rows": rows}
    print(json.dumps({"fits": fits, "device": head["device"]}), flush=True)
    os.makedirs(a.out, exist_ok=True)
    with open(os.path.join(a.out, f"{a.tag}.json"), "w") as f:
        json.dump(head, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
