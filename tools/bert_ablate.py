"""Attribute the BERT-base encoder's non-matmul overhead.

Timing-only ablations on the body-only step (tools/mfu_breakdown.py
harness): patch wrapped_ops before the model builds, time the step,
restore. The patched ops change semantics — numbers are attribution
evidence, never a shipped configuration.

Writes/merges an "attribution" section into
chiprun_out/PROFILE_BERT.json.

Per-op device truth comes from tools/trace_attr.py; this tool only
measures full-step deltas on the host clock.

Usage: python tools/bert_ablate.py
"""

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def run_variant(name, patch=None):
    import paddle_tpu.dispatch as dispatch
    from tools.mfu_breakdown import bert_step_time_ms
    saved = {}
    if patch:
        for key, fn in patch.items():
            saved[key] = dispatch.wrapped_ops[key]
            dispatch.wrapped_ops[key] = fn
    try:
        ms, _ = bert_step_time_ms(batch=64, steps=16, max_preds=-1)
    finally:
        for key, fn in saved.items():
            dispatch.wrapped_ops[key] = fn
    print(f"{name}: {ms:.2f} ms", flush=True)
    return round(ms, 2)


def main():
    import paddle_tpu  # noqa: F401  (registers ops)
    import paddle_tpu.dispatch as dispatch
    F = dispatch.wrapped_ops

    out = {"method": (
        "surgical wrapped_ops patches on the body-only b64 S512 step "
        "(same scan-16 harness as the sweep); each "
        "variant removes one component's fwd+bwd work")}
    out["base_ms"] = run_variant("base")
    out["no_attention_mix_ms"] = run_variant(
        "no_attention_mix",
        {"scaled_dot_product_attention": lambda q, k, v, **kw: v})
    out["no_layernorm_ms"] = run_variant(
        "no_layernorm",
        {"layer_norm": lambda x, shape, w, b, eps=1e-5, **kw: x})
    out["relu_instead_of_gelu_ms"] = run_variant(
        "relu_instead_of_gelu", {"gelu": F["relu"]})
    from bench import out_path
    path = out_path("PROFILE_BERT.json")
    report = json.load(open(path)) if os.path.exists(path) else {}
    # cross-references to the device trace are read from the artifact's
    # own trace_attribution section at write time, so a re-run after
    # tools/trace_attr.py updates them never stamps stale numbers
    tcat = {r["category"]: r for r in
            report.get("trace_attribution", {}).get("by_category", [])}
    cc = tcat.get("custom-call", {})
    fmt = tcat.get("data formatting", {})
    mm = tcat.get("convolution fusion", {})
    out["readings"] = [
        (f"the attention mix (QK/softmax/PV, fwd+bwd) costs "
         f"{out['base_ms'] - out['no_attention_mix_ms']:.0f} ms of the "
         f"{out['base_ms']:.0f} ms step — ~half the wall time for ~10% "
         f"of the model's FLOPs; the encoder matmuls in the remaining "
         f"{out['no_attention_mix_ms']:.0f} ms run near peak"
         + (f" ({mm['tflops_per_s']} TFLOP/s, trace_attribution)"
            if mm else "")),
        ("layernorm and gelu each cost ~16-18 ms fwd+bwd (deltas "
         "overlap under XLA fusion; not additive)"),
    ]
    if cc and fmt:
        out["readings"].insert(1, (
            f"device-trace ground truth (trace_attribution section): "
            f"the flash custom-calls take ~{cc['ms_per_step']:.0f} "
            f"ms/step of device time and the [B,H,S,D] transpose "
            f"round-trips around them ~{fmt['ms_per_step']:.0f} ms "
            f"more ('data formatting') — S^2-score work at d=64 is "
            f"intrinsically cheap on FLOPs but expensive on "
            f"bandwidth/VPU, so it cannot reach matmul-class "
            f"efficiency at this shape"))
    else:
        out["readings"].insert(1, (
            "no trace_attribution section present — run "
            "tools/trace_attr.py --model bert --merge for the per-op "
            "device-time ground truth"))
    report["attribution"] = out
    with open(path, "w") as f:
        json.dump(report, f, indent=2)
        f.write("\n")
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
