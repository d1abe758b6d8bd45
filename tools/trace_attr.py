"""Device-trace attribution: where does a train step's time go?

Runs one traced window of a model's train step under ``jax.profiler``,
parses the chrome trace, and aggregates device op time by ``hlo_category`` with achieved TFLOP/s
and GB/s per category (from the trace's model_flops/bytes_accessed).
This is ground truth the ablation harnesses approximate: e.g. it
showed ResNet-50's convolutions run at 755 GB/s — 92% of v5e HBM peak
— settling that the model is bandwidth-bound, not kernel-bound.

Usage: python tools/trace_attr.py [--model resnet|bert|gpt] [--merge]
  --merge writes a "trace_attribution" section into the matching
  chiprun_out/PROFILE*.json.
"""

from __future__ import annotations

import argparse
import collections
import glob
import gzip
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

PROFILE_FILE = {"resnet": "PROFILE_RESNET.json",
                "bert": "PROFILE_BERT.json",
                "gpt": "PROFILE.json"}


def _resnet_step():
    import numpy as np
    import jax.numpy as jnp

    import paddle_tpu as pt
    import paddle_tpu.dispatch as dispatch
    import paddle_tpu.optimizer as optim
    from bench_all import _to_bf16_except_norms
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import resnet50

    F = dispatch.wrapped_ops
    pt.seed(0)
    model = resnet50(data_format="NCHW")
    _to_bf16_except_norms(model)

    def train_fn(m, b):
        return F["mean"](F["cross_entropy"](
            F["cast"](m(b[0]), "float32"), b[1]))

    step = TrainStep(model, optim.Momentum(learning_rate=0.1,
                                           momentum=0.9), train_fn)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((128, 3, 224, 224)),
                    dtype=jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, 10, (128,)).astype(np.int64))
    steps = 4
    xs, ys = jnp.stack([x] * steps), jnp.stack([y] * steps)
    return (lambda: float(step.multi_step((xs, ys))[-1])), steps


def _bert_step():
    import numpy as np
    import jax.numpy as jnp

    import paddle_tpu as pt
    import paddle_tpu.optimizer as optim
    from bench_all import _to_bf16_except_norms
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.bert import BertForPretraining, bert_base

    pt.seed(0)
    cfg = bert_base(hidden_dropout_prob=0.0,
                    attention_probs_dropout_prob=0.0)
    model = BertForPretraining(cfg)
    _to_bf16_except_norms(model)
    step = TrainStep(model, optim.AdamW(learning_rate=1e-4),
                     lambda m, b: m(b[0], masked_positions=b[1],
                                    labels=b[2]))
    rng = np.random.default_rng(0)
    b, s, mp = 64, 512, 76
    ids = rng.integers(0, cfg.vocab_size, (b, s)).astype(np.int32)
    pos = np.stack([rng.choice(s, mp, replace=False)
                    for _ in range(b)]).astype(np.int32)
    labels = np.take_along_axis(ids, pos, 1).astype(np.int64)
    steps = 4
    staged = tuple(jnp.asarray(np.stack([a] * steps))
                   for a in (ids, pos, labels))
    return (lambda: float(step.multi_step(staged)[-1])), steps


def _gpt_step():
    import numpy as np
    import jax.numpy as jnp

    import paddle_tpu as pt
    import paddle_tpu.optimizer as optim
    from bench_all import _to_bf16_except_norms
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    pt.seed(0)
    cfg = GPTConfig(vocab_size=32768, hidden_size=2048, num_layers=24,
                    num_heads=16, max_seq_len=2048, dropout=0.0,
                    attn_dropout=0.0, dtype="bfloat16",
                    loss_chunk_size=512)
    model = GPTForCausalLM(cfg)
    _to_bf16_except_norms(model)
    step = TrainStep(model, optim.AdamW(learning_rate=1e-4),
                     lambda m, b: m(b[0], labels=b[1]))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (2, 2048)).astype(np.int32)
    steps = 4
    xs = jnp.asarray(np.stack([ids] * steps))
    return (lambda: float(step.multi_step((xs, xs))[-1])), steps


def _decode_runs(int8=False):
    """Two generate() lengths at the decode bench's best batch; the
    category-wise DIFFERENCE isolates the decode loop (prefill + launch
    cancel, as in bench_all's wall-clock subtraction)."""
    import numpy as np
    import jax.numpy as jnp

    import paddle_tpu as pt
    from bench_all import _to_bf16_except_norms
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    pt.seed(0)
    cfg = GPTConfig(vocab_size=32768, hidden_size=2048, num_layers=24,
                    num_heads=16, max_seq_len=2048, dropout=0.0,
                    attn_dropout=0.0, dtype="bfloat16",
                    use_flash_attention=False, loss_chunk_size=0)
    model = GPTForCausalLM(cfg)
    _to_bf16_except_norms(model)
    model.eval()
    n_layers_converted = 0
    if int8:
        from paddle_tpu.quantization.quant import (
            convert_to_weight_only_int8)
        n_layers_converted = convert_to_weight_only_int8(model)
    b, prompt = 128, 128
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, cfg.vocab_size,
                                   (b, prompt)).astype(np.int32))

    def run_n(n):
        got = model.generate(pt.Tensor(ids), max_new_tokens=n,
                             temperature=0.0, use_jit=True)
        v = got.value if hasattr(got, "value") else got
        np.asarray(v[:, -1])

    n_params = sum(int(np.prod(p.shape))
                   for p in model.parameters())
    return run_n, b, n_params, n_layers_converted


def decode_attribution(int8=False):
    """Per-decode-step device attribution: trace generate(8) and
    generate(64), subtract per category, divide by the 56 extra
    steps."""
    short_n, long_n = 8, 64
    run_n, b, n_params, n_conv = _decode_runs(int8=int8)
    run_n(short_n)
    run_n(long_n)  # compile + warm both lengths
    short = trace_and_aggregate(lambda: run_n(short_n), 1)
    long_ = trace_and_aggregate(lambda: run_n(long_n), 1)
    d = long_n - short_n
    sc = {r["category"]: r for r in short["by_category"]}
    lc = {r["category"]: r for r in long_["by_category"]}
    zero = {"ms_per_step": 0.0, "gb_per_step": 0.0}
    rows = []
    # union of categories: one present only in the short trace carries
    # a NEGATIVE correction that must not be dropped
    for cat in {**sc, **lc}:
        l = lc.get(cat, zero)
        s = sc.get(cat, zero)
        ms = (l["ms_per_step"] - s["ms_per_step"]) / d
        gb = (l["gb_per_step"] - s["gb_per_step"]) / d
        rows.append({"category": cat,
                     "ms_per_decode_step": round(ms, 4),
                     "gb_per_decode_step": round(gb, 4),
                     "gb_per_s": round(gb / ms * 1e3, 1)
                     if ms > 1e-6 else 0.0})
    rows.sort(key=lambda r: -r["ms_per_decode_step"])
    total = sum(r["ms_per_decode_step"] for r in rows)
    return {"batch": b, "n_params": n_params,
            "int8_layers_converted": n_conv,
            "total_ms_per_decode_step": round(total, 3),
            "by_category": rows}


def trace_and_aggregate(run, steps, trace_dir=None):
    import jax

    trace_dir = trace_dir or tempfile.mkdtemp(prefix="pt_trace_")
    run()  # compile + warm
    jax.profiler.start_trace(trace_dir)
    run()
    jax.profiler.stop_trace()
    traces = sorted(glob.glob(os.path.join(
        trace_dir, "**", "*.trace.json.gz"), recursive=True))
    events = json.load(gzip.open(traces[-1]))["traceEvents"]
    cat_us = collections.Counter()
    cat_flops = collections.Counter()
    cat_bytes = collections.Counter()
    total_us = 0.0
    for e in events:
        if e.get("ph") != "X" or "dur" not in e:
            continue
        args = e.get("args", {})
        hc = args.get("hlo_category")
        # the outer `while` (the multi-step scan) contains everything
        # once; count only leaf ops
        if not hc or e["name"].startswith("while"):
            continue
        total_us += e["dur"]
        cat_us[hc] += e["dur"]
        cat_flops[hc] += int(args.get("model_flops") or 0)
        cat_bytes[hc] += int(args.get("bytes_accessed") or 0)
    rows = []
    for hc, us in cat_us.most_common():
        sec = us * 1e-6
        rows.append({
            "category": hc,
            "ms_per_step": round(us / steps / 1e3, 3),
            "tflops_per_s": round(cat_flops[hc] / sec / 1e12, 1)
            if sec else 0.0,
            "gb_per_s": round(cat_bytes[hc] / sec / 1e9, 1) if sec
            else 0.0,
            "gb_per_step": round(cat_bytes[hc] / steps / 1e9, 2),
        })
    return {"total_ms_per_step": round(total_us / steps / 1e3, 2),
            "by_category": rows}


def main():
    from bench import out_path

    ap = argparse.ArgumentParser()
    ap.add_argument("--model", default="resnet",
                    choices=("resnet", "bert", "gpt", "decode"))
    ap.add_argument("--int8", action="store_true",
                    help="decode mode: weight-only int8 model")
    ap.add_argument("--merge", action="store_true",
                    help="merge into the matching "
                         "chiprun_out/PROFILE*.json")
    args = ap.parse_args()
    if args.model == "decode":
        report = decode_attribution(int8=args.int8)
        # weights+KV streaming roofline (r4 verdict weak #4)
        hbm_gbps = 819.0
        wbytes = report["n_params"] * (1 if args.int8 else 2)
        # KV per decode step: read the whole cache once (24 layers x
        # 2 (k,v) x b x S_cur x 2048 x 2B); S grows 128->192 over the
        # run, use the midpoint
        kv = 24 * 2 * report["batch"] * 160 * 2048 * 2
        floor_ms = (wbytes + kv) / hbm_gbps / 1e6
        report["roofline"] = {
            "hbm_gbps": hbm_gbps,
            "weight_bytes": wbytes,
            "kv_bytes_per_step_midpoint": kv,
            "streaming_floor_ms_per_step": round(floor_ms, 3),
            "measured_over_floor": round(
                report["total_ms_per_decode_step"] / floor_ms, 2)
            if floor_ms else None,
        }
        print(json.dumps(report, indent=1))
        if args.merge:
            path = out_path("PROFILE_DECODE.json")
            full = json.load(open(path)) if os.path.exists(path) else {}
            key = "int8_weight_only" if args.int8 else "bf16"
            full[key] = report
            with open(path, "w") as f:
                json.dump(full, f, indent=2)
                f.write("\n")
        return
    run, steps = {"resnet": _resnet_step, "bert": _bert_step,
                  "gpt": _gpt_step}[args.model]()
    report = trace_and_aggregate(run, steps)
    print(json.dumps(report, indent=1))
    if args.merge:
        path = out_path(PROFILE_FILE[args.model])
        full = json.load(open(path)) if os.path.exists(path) else {}
        full["trace_attribution"] = report
        with open(path, "w") as f:
            json.dump(full, f, indent=2)
            f.write("\n")


if __name__ == "__main__":
    main()
