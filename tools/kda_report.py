"""The two KDA kernels alone (ops/pallas/kda.py), at the shapes of the
Solar Open 2 serve cell: 64 heads of 128, float32 states.

- ``kda_decode`` over a state pool of 32 slots with 0 (all parked), 8
  and 32 of them live;
- ``kda_chunk_fwd`` over one prompt of 1,024, 8,192 and 32,768 positions
  (a segment of 8,192 at a time, as the model runs it), true length =
  the bucket and a third less (the padded chunks are skipped).

A call's time is the DEVICE time of the Pallas custom call in a
profiler trace (read with the benchmark's reader,
``benchmarks/xplane.py``), the median over ``--reps`` calls; beside it
the least time the chip could take for the call's required bytes and
operations (``benchmarks/flops_solar_open2.py``) and their ratio. Before
the timing each kernel is compared with the plain recurrence at a small
length on the same device (``max_err``).

Nothing here is a benchmark cell.

    chiprun -- python3 tools/kda_report.py
    JAX_PLATFORMS=cpu python3 tools/kda_report.py --tiny   # control flow only
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
    out = fn()
    jax.block_until_ready(out)  # compiles
    tdir = tempfile.mkdtemp(prefix="kda_")
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
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes, for a rehearsal on the CPU")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "kda_report"))
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import kda

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not a.tiny:
        print("no TPU here: a time from the CPU is no device number "
              "(pass --tiny for a rehearsal)", file=sys.stderr)
        return 2
    from benchmarks.peaks import peaks_of
    pk = peaks_of(dev.device_kind if on_chip else "TPU v5 lite")
    hbm, peak = pk["hbm_bytes_per_s"], pk["flops"]
    h, d, slots, seg = (2, 16, 4, 16) if a.tiny else (64, 128, 32, 8192)
    dt = jnp.float32 if a.tiny else jnp.bfloat16
    rng = np.random.default_rng(a.seed)

    def draw(*shape, lo=None, hi=None, dtype=jnp.float32):
        x = rng.standard_normal(shape) if lo is None \
            else rng.uniform(lo, hi, shape)
        return jnp.asarray(x, dtype)

    rows = []

    # -- against the recurrence, at a small length --------------------------
    t = 24 if a.tiny else 200
    q, k, v = (draw(1, t, h * d, dtype=dt) for _ in range(3))
    g = -draw(1, t, h * d, lo=1e-3, hi=0.5)
    beta = draw(1, t, h, lo=0.0, hi=2.0)
    s0 = draw(1, h, d, d)
    lens = jnp.asarray([t - 5], jnp.int32)
    o, s = kda.kda_chunk_fwd(q, k, v, g, beta, s0, lens, heads=h)

    def by_token(s, xs):
        qt, kt, vt, gt, bt, i = xs
        o, s2 = kda.decode_body(qt, kt, vt, gt, bt[..., None], s, d ** -0.5)
        return jnp.where(i < lens[0], s2, s), o

    def heads_of(x):
        return x.astype(jnp.float32).reshape(t, h, -1)

    with jax.default_matmul_precision("highest"):
        s_ref, o_ref = jax.lax.scan(
            by_token, s0[0], (heads_of(q[0]), heads_of(k[0]), heads_of(v[0]),
                              heads_of(g[0]), beta[0], jnp.arange(t)))
    n = int(lens[0])
    err = {"chunk_o": float(jnp.max(jnp.abs(
        o[0, :n].astype(jnp.float32) - o_ref.reshape(t, -1)[:n]))),
        "chunk_state": float(jnp.max(jnp.abs(s[0] - s_ref)))}
    pool = draw(slots + 1, h, d, d)
    live = jnp.asarray(np.arange(slots) % 2 == 0)
    qd, kd, vd = (draw(slots, h, d, dtype=dt) for _ in range(3))
    gd, bd = -draw(slots, h, d, lo=1e-3, hi=0.5), draw(slots, h, lo=0, hi=2)
    at = jnp.arange(slots, dtype=jnp.int32)
    od, pd = kda.kda_decode(qd, kd, vd, gd, bd, pool, at, live)
    o_want, s_want = kda.decode_body(
        qd.astype(jnp.float32), kd.astype(jnp.float32),
        vd.astype(jnp.float32), gd, bd[..., None], pool[:slots], d ** -0.5)
    keep = np.asarray(live)
    err.update(
        decode_o=float(jnp.max(jnp.abs(
            od.astype(jnp.float32)[keep] - o_want[keep]))),
        decode_state=float(jnp.max(jnp.abs(pd[:slots][keep]
                                           - s_want[keep]))),
        parked_rows_untouched=bool(jnp.all(pd[:slots][~keep]
                                           == pool[:slots][~keep])))
    print(json.dumps({"max_err": err}), flush=True)

    # -- kda_decode ---------------------------------------------------------
    step = jax.jit(kda.kda_decode, donate_argnums=(5,))
    state_bytes = 4 * h * d * d
    for n_live in (0, slots // 4, slots):
        mask = np.zeros(slots, bool)
        if n_live:
            mask[np.linspace(0, slots - 1, n_live).round().astype(int)] = True
        live = jnp.asarray(mask)
        box = [pool]

        def call():
            o, box[0] = step(qd, kd, vd, gd, bd, box[0], at, live)
            return o
        us = _traced(call, a.reps, "kda_decode")
        pool = box[0]
        row = {"kernel": "kda_decode", "slots": slots, "live": n_live}
        if us:
            least = 2.0 * state_bytes * n_live / hbm * 1e6
            row.update(us_a_call=round(statistics.median(us), 2),
                       least_us=round(least, 2),
                       roofline_pct=round(100 * least
                                          / statistics.median(us), 1))
        rows.append(row)
        print(json.dumps(row), flush=True)

    # -- kda_chunk_fwd ------------------------------------------------------
    chunk = jax.jit(lambda *xs: kda.kda_chunk_fwd(*xs, heads=h))
    for bucket in ((16, 48) if a.tiny else (1024, 8192, 32768)):
        for true_len in (bucket, bucket * 2 // 3):
            length = min(bucket, seg)
            q, k, v = (draw(1, length, h * d, dtype=dt) for _ in range(3))
            g = -draw(1, length, h * d, lo=1e-3, hi=0.5)
            beta = draw(1, length, h, lo=0.0, hi=2.0)
            us_all = []
            for lo in range(0, bucket, seg):
                left = jnp.asarray([max(0, min(true_len - lo, length))],
                                   jnp.int32)
                us_all.append(_traced(
                    lambda: chunk(q, k, v, g, beta, s0, left), a.reps,
                    "kda_chunk_fwd"))
            row = {"kernel": "kda_chunk_fwd", "bucket": bucket,
                   "true_len": true_len, "segments": len(us_all)}
            if all(us_all):
                us = sum(statistics.median(u) for u in us_all)
                item = jnp.dtype(dt).itemsize
                least = max(
                    6.0 * d * d * h * true_len / peak,
                    ((4 * h * d * item + 4 * h * d + 4 * h) * true_len
                     + state_bytes) / hbm) * 1e6
                row.update(us_a_prompt=round(us, 1),
                           us_a_chunk_head=round(
                               us / (-(-true_len // 64) * h), 3),
                           least_us=round(least, 1),
                           roofline_pct=round(100 * least / us, 2))
            rows.append(row)
            print(json.dumps(row), flush=True)

    os.makedirs(a.out, exist_ok=True)
    with open(os.path.join(a.out, "report.json"), "w") as f:
        json.dump({"device": {"platform": dev.platform,
                              "kind": dev.device_kind},
                   "shapes": {"heads": h, "head_dim": d, "slots": slots,
                              "segment": seg, "dtype": str(jnp.dtype(dt))},
                   "reps": a.reps, "max_err": err, "rows": rows,
                   "note": ("device times from the profiler's XLA Ops line"
                            if on_chip else "CPU rehearsal: no device "
                            "number")}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
