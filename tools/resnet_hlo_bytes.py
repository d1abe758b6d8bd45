"""Per-fusion HBM byte audit of the ResNet-50 train step.

Lowers the bench's exact train step to optimized HLO for the TPU
target (AOT compile — nothing executes) and ranks every top-level
instruction by the bytes it moves (sum of operand + result buffer
sizes). This grounds the fused-backward kernel design in which
round-trips actually carry the r4-measured ~27 GB of backward traffic
(pre-round record: the device trace shows conv fusions at 92% of
HBM peak — byte COUNT, not per-kernel efficiency, is the whole game).

Usage: python tools/resnet_hlo_bytes.py [--top 40] [--out F.json]
"""

from __future__ import annotations

import argparse
import json
import os
import re
import sys
from collections import defaultdict

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np

_SHAPE_RE = re.compile(r"(bf16|f32|f16|s32|s64|u8|u32|pred)\[([\d,]*)\]")
_BYTES = {"bf16": 2, "f32": 4, "f16": 2, "s32": 4, "s64": 8, "u8": 1,
          "u32": 4, "pred": 1}


def shapes_bytes(sig: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(sig):
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _BYTES[dt]
    return total


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--top", type=int, default=40)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    import paddle_tpu as pt
    import paddle_tpu.dispatch as dispatch
    import paddle_tpu.optimizer as optim
    from bench_all import _to_bf16_except_norms
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import resnet50

    F = dispatch.wrapped_ops
    pt.seed(0)
    model = resnet50(data_format="NHWC")
    _to_bf16_except_norms(model)

    def train_fn(m, b):
        logits = m(b[0])
        return F["mean"](F["cross_entropy"](
            F["cast"](logits, "float32"), b[1]))

    step = TrainStep(model, optim.Momentum(learning_rate=0.1,
                                           momentum=0.9), train_fn)
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal(
        (args.batch, 3, 224, 224)).astype(np.float32), jnp.bfloat16)
    y = jnp.asarray(rng.integers(0, 10, (args.batch,)).astype(np.int64))
    lr = jnp.asarray(0.1, jnp.float32)

    low = step._step.lower(step.params, step.buffers, step.opt_state,
                           step._key, lr, (x, y))
    compiled = low.compile()
    hlo = compiled.as_text()

    # top-level (entry) computation instruction lines: "  %name = sig op(...)"
    entry = []
    in_entry = False
    for line in hlo.splitlines():
        if line.startswith("ENTRY"):
            in_entry = True
            continue
        if in_entry:
            if line.startswith("}"):
                break
            entry.append(line)

    rows = []
    for ln in entry:
        m = re.match(r"\s+(%?[\w.\-]+) = (.*)", ln)
        if not m:
            continue
        name, rest = m.groups()
        opm = re.match(r"[^ ]+ ([\w\-]+)\(", rest)
        if opm:
            op = opm.group(1)
        else:
            head = rest.split("(")[0].split()
            op = head[-1] if head else "unknown"
        b = shapes_bytes(rest)
        rows.append({"name": name, "op": op, "bytes": b,
                     "sig": rest[:160]})
    rows.sort(key=lambda r: -r["bytes"])
    total = sum(r["bytes"] for r in rows)
    by_op = defaultdict(int)
    for r in rows:
        by_op[r["op"]] += r["bytes"]
    print(f"total bytes touched (operands+results, entry): "
          f"{total/1e9:.2f} GB across {len(rows)} instructions")
    print("\nby op kind:")
    for k, v in sorted(by_op.items(), key=lambda kv: -kv[1])[:15]:
        print(f"  {k:34s} {v/1e9:7.2f} GB")
    print(f"\ntop {args.top} instructions:")
    for r in rows[:args.top]:
        print(f"  {r['bytes']/1e6:9.1f} MB  {r['name'][:52]:52s} "
              f"{r['sig'][:90]}")
    if args.out:
        with open(args.out, "w") as f:
            json.dump({"total_gb": round(total / 1e9, 2),
                       "by_op_gb": {k: round(v / 1e9, 3)
                                    for k, v in by_op.items()},
                       "top": rows[:args.top]}, f, indent=1)
            f.write("\n")


if __name__ == "__main__":
    main()
