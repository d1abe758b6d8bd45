"""Measure the fused eval bottleneck kernel on ResNet-50 NHWC b128:
eager XLA eval forward vs the Pallas fused-block path, scanned and
floor-subtracted like every other bench.

Usage: python tools/fused_eval_bench.py [--batch 128]
"""

from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def eval_fwd_ms(batch=128, steps=16, windows=3, fused=True):
    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    import paddle_tpu.ops.pallas.fused_conv_block as fc
    from bench_all import _timed_windows, _to_bf16_except_norms
    from paddle_tpu.autograd.engine import no_grad
    from paddle_tpu.jit import functional_state
    from paddle_tpu.nn.layer import bind_state
    from paddle_tpu.vision.models import resnet50

    fc.enable_fused_conv_eval(fused)
    if not fused:
        real = fc.fused_bottleneck_supported
        fc.fused_bottleneck_supported = lambda *a, **k: False
    try:
        pt.seed(0)
        model = resnet50(data_format="NHWC")
        _to_bf16_except_norms(model)
        model.eval()
        state = functional_state(model)
        rng = np.random.default_rng(0)
        x = jnp.asarray(rng.standard_normal(
            (batch, 3, 224, 224)).astype(np.float32), jnp.bfloat16)
        xs = jnp.stack([x] * steps)

        def fwd_scan(params, buffers, batches):
            def body(carry, b):
                model.eval()
                with bind_state(model, {"params": params,
                                        "buffers": buffers}), no_grad():
                    logits = model(pt.Tensor(b))
                return carry, jnp.mean(
                    logits.value.astype(jnp.float32))
            _, outs = jax.lax.scan(body, 0, batches)
            return outs

        jitted = jax.jit(fwd_scan)
        run = lambda: float(jitted(state["params"], state["buffers"],
                                   xs)[-1])
        run()
        dt, _ = _timed_windows(run, n_windows=windows)
        return dt / steps * 1e3
    finally:
        fc.enable_fused_conv_eval(False)
        if not fused:
            fc.fused_bottleneck_supported = real


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--batch", type=int, default=128)
    args = ap.parse_args()
    eager = eval_fwd_ms(args.batch, fused=False)
    fused = eval_fwd_ms(args.batch, fused=True)
    out = {
        "config": f"resnet50 NHWC b{args.batch} eval forward, bf16, "
                  "scan-16 floor-subtracted",
        "eager_xla_ms": round(eager, 2),
        "fused_block_ms": round(fused, 2),
        "speedup": round(eager / fused, 3),
        "imgs_per_s_fused": round(args.batch * 1e3 / fused, 1),
    }
    print(json.dumps(out, indent=1))


if __name__ == "__main__":
    main()
