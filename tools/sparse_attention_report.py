"""The sparse attention kernels alone (ops/pallas/sparse_attention.py) at
the shapes of the GLM-5.3-Flash serve cell (64 heads over latent rows
of 512, bf16; an indexer of 32 heads of 128 over pooled keys of blocks
of 4, the top 2,048; 32 slots, pages of 64).

- ``sparse_decode_latent`` with 0 (all parked), 8, 16 and 32 live slots
  at contexts of 4,096, 16,384 and 32,768 positions: device µs a call,
  then a least-squares split into fixed, a live slot and a page walked
  (``us = fixed + a_slot * live + a_page * pages``), the bandwidth of
  the page term, and the share of the REQUIRED bytes (the selected
  rows) over the call's time;
- the decoded token's indexer as the model runs it (the gather of the
  slot's pooled keys, the scores, the exact 2,048th and the selection:
  XLA ops), device µs a step at 32 live slots;
- a prompt's last segment of 8,192 queries at 16,384 and 32,768
  positions: ``dsa_index_prefill``, the threshold (``kth_largest``, XLA)
  and the two forms of the sparse attention: ``sparse_prefill_latent``
  (dense tiles in the absorbed form, the selection a mask) and the
  selected rows gathered (``jax.numpy``: a query's 8,192 rows of 512,
  then the absorbed products), timed over ``--gather-queries`` queries
  and scaled to the segment;
- a whole prompt in the 32,768 bucket (its four segments of 8,192, as
  the model runs them) through ``sparse_prefill_latent`` without the
  prompt's length and with it, at fills of 0.5, 0.75 and 1.0 of the
  bucket: device µs a prompt, the share of (query tile, key tile)
  pairs the length skips, and the largest difference on live query
  rows between the two calls (0: the same tiles in the same order).

A call's time is the device time of its ops in a profiler trace (read
with the benchmark's reader, ``benchmarks/xplane.py``), the median over
``--reps`` calls. Before the timing each kernel is compared with its
plain path on the same device (``max_err``).

Nothing here is a benchmark cell.

    python3 tools/sparse_attention_report.py            # on one TPU chip
    JAX_PLATFORMS=cpu python3 tools/sparse_attention_report.py --tiny   # control flow only
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


def _device_us(fn, reps, name=None, calls=1):
    """Device µs a call: of the custom calls named ``name`` (``calls`` of
    them a call of ``fn``, summed), or of every op (their union) where
    ``name`` is None; the median over ``reps``."""
    import jax
    from benchmarks import xplane
    jax.block_until_ready(fn())  # compiles
    tdir = tempfile.mkdtemp(prefix="sparse_")
    try:
        with jax.profiler.trace(tdir):
            for _ in range(reps):
                jax.block_until_ready(fn())
        ev = xplane.load_events(xplane.find_xplane(tdir))
    finally:
        shutil.rmtree(tdir, ignore_errors=True)
    if name is not None:
        us = [e["dur"] * 1e6 for e in sorted(
            xplane.leaf_ops(ev), key=lambda e: e["start"])
              if name in e["name"] and xplane.op_kind(e) == "custom-call"]
        us = [sum(us[i:i + calls]) for i in range(0, len(us), calls)]
        return statistics.median(us) if us else None
    return xplane.busy_seconds(ev) * 1e6 / reps


def gathered_prefill(qa, rows, scores, k, pool, scale):
    """The other form of a prompt's sparse attention: each query's k best
    blocks gathered (k * pool rows of W), the absorbed products over
    them. qa [H, Q, W]; rows [T, W]; scores [Q, T / pool]."""
    import jax
    import jax.numpy as jnp
    _, blk = jax.lax.top_k(scores, k)  # [Q, k]
    at = (blk[..., None] * pool + jnp.arange(pool)).reshape(blk.shape[0], -1)
    sel = rows[at]  # [Q, k * pool, W]
    logits = jnp.einsum("hqw,qtw->hqt", qa, sel,
                        preferred_element_type=jnp.float32) * scale
    p = jax.nn.softmax(logits, axis=-1)
    return jnp.einsum("hqt,qtw->hqw", p.astype(rows.dtype), sel,
                      preferred_element_type=jnp.float32)


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=6)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--gather-queries", type=int, default=256)
    ap.add_argument("--tiny", action="store_true",
                    help="small shapes, for a rehearsal on the CPU")
    ap.add_argument("--out", default=os.path.join(
        HERE, "chiprun_out", "sparse_attention_report"))
    a = ap.parse_args()

    import jax
    import jax.numpy as jnp
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.ops.pallas import sparse_attention as sa

    dev = jax.devices()[0]
    on_chip = dev.platform == "tpu"
    if not on_chip and not a.tiny:
        print("no TPU here: a time from the CPU is no device number "
              "(pass --tiny for a rehearsal)", file=sys.stderr)
        return 2
    from benchmarks.peaks import peaks_of
    pk = peaks_of(dev.device_kind if on_chip else "TPU v5 lite")
    hbm, peak = pk["hbm_bytes_per_s"], pk["flops"]
    pool = 4
    if a.tiny:
        heads, rank, page, slots, n_pages, hi, di, k = 4, 128, 16, 4, 64, \
            2, 128, 8
        contexts, lives, prompts, seg, dt = (64, 200), (0, 2, 4), \
            (1024,), 512, jnp.float32
        gq = 16
    else:
        heads, rank, page, slots, n_pages, hi, di, k = 64, 512, 64, 32, \
            13312, 32, 128, 2048
        contexts, lives, prompts, seg, dt = (4096, 16384, 32768), \
            (0, 8, 16, 32), (16384, 32768), 8192, jnp.bfloat16
        gq = a.gather_queries
    scale = 256 ** -0.5
    itemsize = jnp.dtype(dt).itemsize
    max_pages = -(-max(contexts) // page)
    rng = np.random.default_rng(a.seed)
    lat = jnp.asarray(rng.standard_normal((n_pages + 1, page, rank)), dt)
    idx_pool = jnp.asarray(rng.standard_normal(
        (n_pages + 1, page // pool, di)), dt)
    q = jnp.asarray(rng.standard_normal((slots, heads, rank)), dt)
    qi = jnp.asarray(rng.standard_normal((slots, hi, di)), dt)
    wi = jnp.asarray(np.abs(rng.standard_normal((slots, hi))), jnp.float32)

    def table_for(live, ctx):
        need = -(-ctx // page)
        t = np.full((slots, max_pages), n_pages, np.int32)
        lens = np.zeros(slots, np.int32)
        at = rng.permutation(n_pages)
        for i in range(live):
            t[i, :need] = np.resize(at[(i * need) % n_pages:], need)
            lens[i] = ctx
        return jnp.asarray(t), jnp.asarray(lens)

    def select(t, lens):
        pos = jnp.maximum(lens - 1, 0)
        s = sa.index_scores_paged(qi, wi, idx_pool, t, pos, pool)
        picked = sa.select_blocks(s, sa.kth_largest(s, k), pos, pool)
        return jnp.repeat(picked, pool, axis=1)

    select_jit = jax.jit(select)

    # -- the decode walk against its plain path, on this device ---------------
    t, lens = table_for(min(3, slots), contexts[-1] - 7)
    sel = select_jit(t, lens)
    got = pa.paged_attention_latent(q, lat, t, lens, rank, scale,
                                    interpret=a.tiny, sel=sel)
    want = pa.paged_attention_latent_reference(
        q.astype(jnp.float32), lat.astype(jnp.float32), t, lens, rank, scale,
        sel=sel)
    err = {"decode": float(jnp.max(jnp.abs(got.astype(jnp.float32) - want))),
           "parked_rows_zero": bool(jnp.all(got[3:] == 0))}
    rows = []
    kernel = not a.tiny  # on the CPU the entry routes to the plain path

    # -- sparse_decode_latent ---------------------------------------------
    call = jax.jit(lambda q, p, t, n, s: pa.paged_attention_latent(
        q, p, t, n, rank, scale, sel=s))
    fit_x, fit_y = [], []
    for live in lives:
        for ctx in (contexts if live else contexts[:1]):
            t, lens = table_for(live, ctx)
            sel = select_jit(t, lens)
            n_sel = int(jnp.sum(sel[:, :ctx] * (lens[:, None] > 0)))
            pages = live * -(-ctx // page)
            row = {"kernel": "sparse_decode_latent", "slots": slots,
                   "live": live, "context": ctx, "pages": pages,
                   "selected": n_sel}
            if kernel:
                us = _device_us(lambda: call(q, lat, t, lens, sel), a.reps,
                                "sparse_decode_latent")
                least = n_sel * rank * itemsize / hbm * 1e6
                row.update(us_a_call=round(us, 2), least_us=round(least, 2),
                           roofline_pct=round(100 * least / us, 1))
                fit_x.append([1.0, live, pages])
                fit_y.append(us)
            rows.append(row)
            print(json.dumps(row), flush=True)
    if len(fit_y) >= 3:
        (fixed, a_slot, a_page), *_ = np.linalg.lstsq(
            np.asarray(fit_x), np.asarray(fit_y), rcond=None)
        row = {"kernel": "sparse_decode_latent",
               "fit_us": {"fixed": round(float(fixed), 2),
                          "a_live_slot": round(float(a_slot), 3),
                          "a_page": round(float(a_page), 4)},
               "page_gbytes_per_s": round(page * rank * itemsize
                                          / max(float(a_page), 1e-9) / 1e3,
                                          1),
               "a_selected_block_us_at_32k": round(
                   float(a_page) * (32768 // page) / (k + 1), 4)}
        rows.append(row)
        print(json.dumps(row), flush=True)

    # -- the decoded token's indexer (XLA) ------------------------------------
    t, lens = table_for(slots, contexts[-1])
    row = {"ops": "decode indexer (gather, scores, kth_largest, select)",
           "live": slots, "context": contexts[-1]}
    if kernel:
        row["us_a_step"] = round(_device_us(lambda: select_jit(t, lens),
                                            a.reps), 1)
    rows.append(row)
    print(json.dumps(row), flush=True)

    # -- a prompt's last segment ---------------------------------------------
    for t_len in prompts:
        lo = t_len - seg
        lat_rows = jnp.asarray(rng.standard_normal((1, t_len, rank)), dt)
        keys = jnp.asarray(rng.standard_normal((1, t_len // pool, di)), dt)
        pq = jnp.asarray(rng.standard_normal((1, seg, hi, di)), dt)
        pw = jnp.asarray(np.abs(rng.standard_normal((1, seg, hi))),
                         jnp.float32)
        qa = jnp.asarray(rng.standard_normal((1, heads, seg, rank)), dt)
        scores_fn = jax.jit(lambda pq, pw, keys, lo=lo: sa.index_scores(
            pq, pw, keys, lo, pool, interpret=a.tiny))
        scores = scores_fn(pq, pw, keys)
        tau_fn = jax.jit(lambda s: sa.kth_largest(s, k))
        tau = tau_fn(scores)
        attn_fn = jax.jit(
            lambda qa, r, s, tau, lo=lo: sa.sparse_prefill_latent(
                qa, r, s, tau, lo, pool, scale, interpret=a.tiny))
        out = attn_fn(qa, lat_rows, scores, tau)
        # the segment's last 16 queries against the plain path
        want = sa.sparse_prefill_reference(
            qa[:, :, -16:].astype(jnp.float32), lat_rows.astype(jnp.float32),
            scores[:, -16:], tau[:, -16:], t_len - 16, pool, scale)
        err[f"prefill_{t_len}"] = float(jnp.max(jnp.abs(
            out[:, :, -16:].astype(jnp.float32) - want)))
        g = min(gq, seg)
        gather_fn = jax.jit(lambda qa, r, s: gathered_prefill(
            qa[0, :, -g:], r[0], s[0, -g:], k, pool, scale))
        n_pairs = sum(min(tt + 1, k * pool + tt % pool + 1)
                      for tt in range(lo, t_len))
        work = 2.0 * heads * (256 + 256) * n_pairs
        row = {"prompt": t_len, "segment": [lo, t_len],
               "selected_pairs": n_pairs}
        if kernel:
            idx_us = _device_us(lambda: scores_fn(pq, pw, keys), a.reps,
                                "dsa_index_prefill")
            scored = sum(tt // pool for tt in range(lo, t_len))
            attn_us = _device_us(lambda: attn_fn(qa, lat_rows, scores, tau),
                                 a.reps, "sparse_prefill_latent")
            gat_us = _device_us(lambda: gather_fn(qa, lat_rows, scores),
                                a.reps) * seg / g
            row.update(
                dsa_index_prefill_us=round(idx_us, 1),
                dsa_index_roofline_pct=round(
                    100 * 2.0 * hi * di * scored / peak / idx_us * 1e6, 1),
                kth_largest_us=round(_device_us(lambda: tau_fn(scores),
                                                a.reps), 1),
                sparse_prefill_latent_us=round(attn_us, 1),
                sparse_prefill_roofline_pct=round(
                    100 * work / peak / attn_us * 1e6, 1),
                gathered_us_scaled=round(gat_us, 1),
                gathered_queries_timed=g)
        rows.append(row)
        print(json.dumps(row), flush=True)

    # -- a whole prompt, with and without its length ---------------------------
    t_len = prompts[-1]
    segs = list(range(0, t_len, seg))
    lat_rows = jnp.asarray(rng.standard_normal((1, t_len, rank)), dt)
    keys = jnp.asarray(rng.standard_normal((1, t_len // pool, di)), dt)
    pq = jnp.asarray(rng.standard_normal((1, seg, hi, di)), dt)
    pw = jnp.asarray(np.abs(rng.standard_normal((1, seg, hi))), jnp.float32)
    qa = jnp.asarray(rng.standard_normal((1, heads, seg, rank)), dt)
    selection = []
    for lo in segs:
        scores = jax.jit(lambda pq, pw, keys, lo=lo: sa.index_scores(
            pq, pw, keys, lo, pool, interpret=a.tiny))(pq, pw, keys)
        selection.append((scores, tau_fn(scores)))

    @jax.jit
    def prompt_fn(qa, r, selection, lengths=None):
        return [sa.sparse_prefill_latent(qa, r, sc, tau, lo, pool, scale,
                                         interpret=a.tiny, lengths=lengths)
                for lo, (sc, tau) in zip(segs, selection)]

    @jax.jit
    def live_diff(a_, b_, lengths):
        return jnp.max(jnp.stack([jnp.max(jnp.where(
            (lo + jnp.arange(seg) < lengths[:, None])[:, None, :, None],
            jnp.abs(x.astype(jnp.float32) - y.astype(jnp.float32)), 0.0))
            for lo, x, y in zip(segs, a_, b_)]))

    plain = prompt_fn(qa, lat_rows, selection)
    plain_us = _device_us(lambda: prompt_fn(qa, lat_rows, selection),
                          a.reps, "sparse_prefill_latent",
                          calls=len(segs)) if kernel else None
    for fill in (0.5, 0.75, 1.0):
        n = jnp.asarray([int(fill * t_len)], jnp.int32)
        run, grid = (sum(int(x) for x in v) for v in zip(
            *(sa.prefill_tile_pairs(n, lo, seg) for lo in segs)))
        ragged = prompt_fn(qa, lat_rows, selection, n)
        row = {"whole_prompt": t_len, "length": int(n[0]),
               "tile_pairs": {"run": run, "grid": grid},
               "skipped_pct": round(100 * (1 - run / grid), 2),
               "max_live_diff": float(live_diff(plain, ragged, n))}
        if kernel:
            us = _device_us(lambda: prompt_fn(qa, lat_rows, selection, n),
                            a.reps, "sparse_prefill_latent", calls=len(segs))
            row.update(us_without_length=round(plain_us, 1),
                       us_with_length=round(us, 1),
                       time_saved_pct=round(100 * (1 - us / plain_us), 2))
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"max_err": err}), flush=True)

    os.makedirs(a.out, exist_ok=True)
    with open(os.path.join(a.out, "report.json"), "w") as f:
        json.dump({"device": {"platform": dev.platform,
                              "kind": dev.device_kind},
                   "max_err": err, "rows": rows}, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
