"""Per-op micro-benchmark harness.

Reference parity: tools/test_op_benchmark.sh + the op micro-bench binary
paddle/fluid/operators/benchmark/op_tester.cc — measures registered ops'
latency over standard configs and emits one JSON line per case, which
check_op_benchmark_result.py gates against a stored baseline.

Usage:
    python tools/op_benchmark.py [--ops matmul,softmax,...] \
        [--output logs_dir] [--repeat 50] [--platform cpu|tpu]
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

# Standard configs: (op, args builder). Shapes picked to match the
# reference harness's medium configs (tileable on TPU).
_RNG = np.random.default_rng(0)


def _f32(*shape):
    return _RNG.standard_normal(shape).astype(np.float32)


def default_cases():
    return {
        "matmul": lambda: (_f32(512, 512), _f32(512, 512)),
        "add": lambda: (_f32(1024, 1024), _f32(1024, 1024)),
        "multiply": lambda: (_f32(1024, 1024), _f32(1024, 1024)),
        "softmax": lambda: (_f32(256, 1024),),
        "layer_norm": lambda: (_f32(256, 1024), (1024,)),
        "gelu": lambda: (_f32(1024, 1024),),
        "relu": lambda: (_f32(1024, 1024),),
        "sum": lambda: (_f32(1024, 1024),),
        "mean": lambda: (_f32(1024, 1024),),
        "transpose": lambda: (_f32(1024, 1024), (1, 0)),
        "concat": lambda: ([_f32(512, 512), _f32(512, 512)],),
        "exp": lambda: (_f32(1024, 1024),),
        "sigmoid": lambda: (_f32(1024, 1024),),
        "conv2d": lambda: (_f32(8, 16, 64, 64), _f32(32, 16, 3, 3)),
        "cross_entropy": lambda: (
            _f32(512, 1000),
            _RNG.integers(0, 1000, (512, 1)).astype(np.int64)),
    }


def _paged_case():
    # decode-shaped ragged paged attention: 8 sequences, 16-token
    # pages, ragged lengths spanning 1..8 pages (the kernel-contract
    # shape class; on cpu the dense-gather reference runs)
    n_pages, page, h, d = 65, 16, 8, 64
    kp = _f32(n_pages, page, h, d)
    vp = _f32(n_pages, page, h, d)
    table = np.arange(8 * 8, dtype=np.int32).reshape(8, 8)
    lens = np.asarray([128, 112, 96, 80, 64, 48, 32, 16], np.int32)
    return (_f32(8, 1, h, d), kp, vp, table, lens)


def _prefill_chunk_case():
    # one chained prefill chunk (r11 chunked prefill / chained
    # suffix prefill hot shape): a 64-token chunk appended at
    # position 128 attends the stored 128-token prefix plus itself
    # through the q_offsets path — seq_lens is the POST-append
    # length, q_offsets the chunk's first absolute position. The
    # r13 fusion landed against this mixed prefill+decode shape class,
    # not just s=1 decode.
    n_pages, page, h, d = 65, 16, 8, 64
    done, chunk = 128, 64
    kp = _f32(n_pages, page, h, d)
    vp = _f32(n_pages, page, h, d)
    table = np.arange(12, dtype=np.int32).reshape(1, 12)
    lens = np.asarray([done + chunk], np.int32)
    q_offsets = np.asarray([done], np.int32)
    # positional tail (k_scale, v_scale, scale) stays None-static
    return (_f32(1, chunk, h, d), kp, vp, table, lens,
            None, None, None, q_offsets)


_prefill_chunk_case.op_name = "paged_attention"


def pending_cases():
    """Ops benchable through this harness whose baseline set is not yet
    complete on ANY committed platform dir (tools/op_baselines/
    PENDING.json records which platform is missing and why). Kept OUT
    of default_cases() so test_op_benchmark_gate's completeness check
    over the committed baseline dirs stays exact; the gate runs these
    through the harness and holds them to PENDING.json, and compares
    no wall time for them.

    A case whose name is not itself a registered op (a named SHAPE
    CLASS of one) carries the op on its builder's ``op_name``
    attribute — bench_op and the gate test resolve through it."""
    return {"paged_attention": _paged_case}


def promoted_cases():
    """Cases with a REAL committed cpu_smoke baseline (gated by
    test_op_benchmark_gate exactly like default_cases' cpu lane) whose
    tpu_v5e number is still chip-pending — the r13 burn-down of the
    staged pending tier: `paged_attention_head_sharded` and
    `prefill_chunk_step` were promoted out of PENDING.json, and the
    r13 fused decode hot path lands its three shape classes here with
    baselines from day one.

    Chip-pending paper trail (the PENDING.json role for this tier):
    each case's tpu_v5e log requires tools/op_benchmark_tpu.sh on a
    chip-attached host, where the Mosaic kernels run instead of the
    CPU references these baselines measure. Once measured on chip,
    move the case into default_cases() and its log into
    op_baselines/tpu_v5e/."""
    def fused_decode_step():
        # r13 fused decode hot shape: the SAME ragged decode class as
        # paged_attention with the out-projection epilogue folded in
        # (one launch for attention + head-concat + o-proj + bias)
        h, d = 8, 64
        e = h * d
        return _paged_case() + (_f32(e, e), _f32(e))

    fused_decode_step.op_name = "paged_attention_fused"

    def fused_verify():
        # r13 one-program speculative verify shape: a k+1 = 5-position
        # verify window appended at position 128 scores through the
        # chained q_offsets path WITH the fused epilogue
        n_pages, page, h, d = 65, 16, 8, 64
        done, s = 128, 5
        e = h * d
        kp = _f32(n_pages, page, h, d)
        vp = _f32(n_pages, page, h, d)
        table = np.arange(12, dtype=np.int32).reshape(1, 12)
        lens = np.asarray([done + s], np.int32)
        q_offsets = np.asarray([done], np.int32)
        return (_f32(1, s, h, d), kp, vp, table, lens, _f32(e, e),
                _f32(e), None, None, None, q_offsets)

    fused_verify.op_name = "paged_attention_fused"

    def fused_sample():
        # r13 streaming lm_head sampling: greedy argmax over vocab
        # tiles of a [4096, 256] vocab-major head — the [B, vocab]
        # logits tensor never materializes (tile=1024 -> 4 tiles)
        return (_f32(8, 256), _f32(4096, 256), None, True, None, 1024)

    def prefix_restore():
        # r15 hierarchical prefix cache restore shape: splice one
        # spilled 16-token page's KV block back into the standard
        # decode pool (device_put + .at[page].set scatter — the
        # engine's per-pool primitive; the whole-restore path runs one
        # such splice per layer pool per restored page). This is the
        # op whose latency must sit well under the chained prefill a
        # restore replaces.
        return (_f32(65, 16, 8, 64), _f32(16, 8, 64), 5)

    prefix_restore.op_name = "paged_page_splice"

    def page_fetch_splice():
        # r20 disaggregated serving: the decode-side splice of a
        # FETCHED chain run — a 4-page contiguous prefix pulled over
        # fetch_pages scatters into the pool in one call (pool.at[
        # pages].set, the same op the r15 restore uses page-at-a-time;
        # the engine batches the whole run into one donate-in-place
        # program). This latency plus the wire RPC is what a handoff
        # costs against the chained prefill it replaces.
        pages = np.asarray([3, 9, 27, 41], np.int32)
        return (_f32(65, 16, 8, 64), _f32(4, 16, 8, 64), pages)

    page_fetch_splice.op_name = "paged_page_splice"

    def blob_encode_decode():
        # r23 KV byte substrate: host-lane codec cost of one fp page
        # through pack(int8) + unpack — the work every spill, every
        # fetch_pages reply and every prefetch import pays per page.
        # A HOST case (host_fn below): the codecs are deliberately
        # numpy-only (they run on the serving thread next to the
        # socket, never inside a jit), so the harness times the plain
        # python call instead of a scanned device launch.
        rng = np.random.default_rng(0)
        layers = [(rng.standard_normal((16, 8, 64)).astype(np.float32),
                   rng.standard_normal((16, 8, 64)).astype(np.float32),
                   None, None) for _ in range(4)]
        return (layers, "int8")

    def _blob_roundtrip(layers, fmt):
        from paddle_tpu.serving.prefix_cache import (pack_page_blob,
                                                     unpack_page_blob)
        return unpack_page_blob(pack_page_blob(layers, fmt=fmt))

    blob_encode_decode.host_fn = _blob_roundtrip

    return {"paged_attention_head_sharded": _paged_case,
            "blob_encode_decode": blob_encode_decode,
            "page_fetch_splice": page_fetch_splice,
            "prefill_chunk_step": _prefill_chunk_case,
            "fused_decode_step": fused_decode_step,
            "fused_verify": fused_verify,
            "fused_sample": fused_sample,
            "prefix_restore": prefix_restore}


def bench_op(name: str, make_args, repeat: int) -> dict:
    # host cases (builder.host_fn, r23): pure-python/numpy hot paths
    # with no device launch to scan — timed as direct calls. Same log
    # schema, same gate.
    host = getattr(make_args, "host_fn", None)
    if host is not None:
        full_args = make_args()
        host(*full_args)  # warm (allocator pools, import caches)
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            for _ in range(repeat):
                host(*full_args)
            times.append((time.perf_counter() - t0) / repeat)
        dt = sorted(times)[1]  # median window
        return {"case": name, "avg_us": round(dt * 1e6, 2),
                "repeat": repeat}

    import jax

    from paddle_tpu.ops.registry import get_op

    # a case may be a named shape class of another op (see
    # pending_cases): the builder's op_name attribute wins
    fn = get_op(getattr(make_args, "op_name", name)).fn
    full_args = make_args()
    # only array(-list) args are traced; shapes/perm tuples stay static
    is_arr = [isinstance(a, np.ndarray) or
              (isinstance(a, list) and a and
               isinstance(a[0], np.ndarray)) for a in full_args]
    args = [a for a, m in zip(full_args, is_arr) if m]

    def call(*arrs):
        it = iter(arrs)
        return fn(*[next(it) if m else a
                    for a, m in zip(full_args, is_arr)])

    import jax.numpy as jnp

    # The whole repeat loop runs INSIDE one launch (lax.scan with a
    # serial carry dependency): a per-call loop would time the launch
    # round trip, not the op. The carry perturbs the first float arg so XLA can neither hoist the op
    # out of the loop nor DCE it.
    def scan_all(*arrs):
        def body(c, _):
            it = iter(arrs)
            perturbed = False
            call_args = []
            for a, m in zip(full_args, is_arr):
                v = next(it) if m else a
                if m and not perturbed:
                    if isinstance(v, (list, tuple)) and len(v) and \
                            jnp.issubdtype(jnp.asarray(v[0]).dtype,
                                           jnp.floating):
                        # list-args (concat): perturb the first element,
                        # else the body is loop-invariant and hoisted
                        v = [v[0] + c.astype(v[0].dtype), *v[1:]]
                        perturbed = True
                    elif not isinstance(v, (list, tuple)) and \
                            jnp.issubdtype(jnp.asarray(v).dtype,
                                           jnp.floating):
                        v = v + c.astype(v.dtype)
                        perturbed = True
                call_args.append(v)
            out = fn(*call_args)
            leaf = jax.tree_util.tree_leaves(out)[0]
            # consume EVERY output element (a fused cheap reduce): a
            # single-element carry would let XLA slice the op down to
            # computing one element
            return (leaf.astype(jnp.float32).sum() * 1e-30), None

        c, _ = jax.lax.scan(body, jnp.asarray(0.0, jnp.float32), None,
                            length=repeat)
        return c

    # stage the operand arrays on device ONCE: passing numpy would
    # re-transfer them every timed window
    args = jax.tree_util.tree_map(jnp.asarray, args)
    jitted = jax.jit(scan_all)
    # warm (compile); the host fetch of the scalar is the barrier
    float(jitted(*args))
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(jitted(*args))
        times.append((time.perf_counter() - t0) / repeat)
    dt = sorted(times)[1]  # median window
    return {"case": name, "avg_us": round(dt * 1e6, 2),
            "repeat": repeat}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--ops", default="", help="comma list; default all")
    ap.add_argument("--output", default="", help="dir for per-case logs")
    ap.add_argument("--repeat", type=int, default=None,
                    help="scan length per window; default 20 on cpu, "
                         "10000 on tpu (amortizes the launch round "
                         "trip)")
    ap.add_argument("--platform", default="cpu", choices=["cpu", "tpu"])
    args = ap.parse_args()
    if args.repeat is None:
        args.repeat = 10000 if args.platform == "tpu" else 20

    if args.platform == "cpu":
        import jax
        jax.config.update("jax_platforms", "cpu")
    import paddle_tpu  # noqa: F401 - registers ops

    cases = default_cases()
    if args.ops:  # pending/promoted cases run only when asked by name
        cases.update(pending_cases())
        cases.update(promoted_cases())
        wanted = args.ops.split(",")
        missing = [w for w in wanted if w not in cases]
        if missing:
            print(f"no standard config for: {missing}", file=sys.stderr)
            return 2
        cases = {k: cases[k] for k in wanted}

    results = []
    for name, make in cases.items():
        r = bench_op(name, make, args.repeat)
        results.append(r)
        line = json.dumps(r)
        print(line, flush=True)
        if args.output:
            os.makedirs(args.output, exist_ok=True)
            with open(os.path.join(args.output, f"{name}.log"), "w") as f:
                f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
