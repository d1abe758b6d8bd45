"""Decode feature-ladder bisection: the README bisect rule, executable.

Every serving feature keeps an escape hatch whose OFF position is
byte-for-byte the previous engine (mesh=None, --no-fused-step,
speculative off, --prefill-chunk unset), and greedy outputs are
pinned bit-identical across all of them. When a deployment's outputs look wrong, the rule is:
walk the hatches one at a time against a pinned stream and file the
bug against the FIRST rung that diverges — not against "the engine".

This tool runs that walk. It generates a deterministic prompt stream
(rng(0), the same shape the engine test suites pin), runs the vanilla
per-token reference (everything off), then re-runs the stream up the
feature ladder, enabling one feature per rung:

    mesh -> chunked prefill -> speculative -> fused step

and reports the first rung whose greedy stream differs from the
reference. Exit code 0: every rung bit-identical (the pinned
contract holds); 2: a rung diverged (named on stdout, with the
per-request first-divergence offsets).

Usage:
    JAX_PLATFORMS=cpu python tools/bisect_decode.py \
        [--model gpt_tiny] [--speculate 3] \
        [--prefill-chunk 8] [--mesh N] [--max-new 8] [--seed 0]

On CPU with gpt_tiny this takes ~a minute; on a chip point it at the
deployment's model and real knob values.
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def _build_model(name: str):
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import (GPTForCausalLM, gpt_125m,
                                       gpt_1p3b, gpt_350m, gpt_tiny)
    configs = {"gpt_tiny": gpt_tiny, "gpt_125m": gpt_125m,
               "gpt_350m": gpt_350m, "gpt_1p3b": gpt_1p3b}
    if name not in configs:
        raise SystemExit(f"unknown model {name!r} "
                         f"(expected one of {sorted(configs)})")
    pt.seed(0)
    m = GPTForCausalLM(configs[name]())
    m.eval()
    return m


def _pinned_stream(vocab: int, seed: int, count: int = 4):
    rng = np.random.default_rng(seed)
    lens = (5, 9, 13, 7, 21, 11)[:count]
    return [rng.integers(0, vocab, n).astype(np.int32) for n in lens]


def _run(model, prompts, max_new: int, **kw):
    """One pinned-stream run -> per-request generated-token lists."""
    from paddle_tpu.inference import create_decode_engine
    eng = create_decode_engine(model, **kw)
    rids = [eng.submit(p, max_new_tokens=max_new) for p in prompts]
    try:
        res = eng.run()
        return [[int(t) for t in res[r][len(p):]]
                for r, p in zip(rids, prompts)]
    finally:
        eng.close()


def _ladder(args, mesh):
    """Feature rungs, reference first. Each entry is (name, what the
    rung ADDS over the previous one, engine kwargs)."""
    from paddle_tpu.inference import SpeculativeConfig

    spec = (None if args.speculate <= 0
            else SpeculativeConfig(k=args.speculate, draft=args.draft))
    rungs = [("reference (everything off)", None, {})]
    acc = {}
    if mesh is not None:
        acc = dict(acc, mesh=mesh)
        rungs.append((f"mesh ({args.mesh}-way)", "mesh", dict(acc)))
    if args.prefill_chunk:
        acc = dict(acc, prefill_chunk_tokens=args.prefill_chunk)
        rungs.append(("chunked prefill", "prefill_chunk", dict(acc)))
    if spec is not None:
        acc = dict(acc, speculative=spec)
        rungs.append((f"speculative (k={args.speculate}, "
                      f"{args.draft})", "speculative", dict(acc)))
    # fused is ON by default at every rung above; the fused-off lane
    # is its own rung so a fusion regression bisects apart from the
    # features under it
    rungs.append(("fused step OFF (--no-fused-step lane)", "no-fused",
                  dict(acc, fused_step=False)))
    return rungs


def _first_divergence(a, b):
    for r, (xs, ys) in enumerate(zip(a, b)):
        if xs != ys:
            off = next((i for i, (x, y) in enumerate(zip(xs, ys))
                        if x != y), min(len(xs), len(ys)))
            return r, off
    return None


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description="bisect a greedy-output divergence down the "
                    "serving feature ladder")
    p.add_argument("--model", default="gpt_tiny")
    p.add_argument("--speculate", type=int, default=3,
                   help="draft k (0 = skip the speculative rung)")
    p.add_argument("--draft", default="ngram",
                   choices=["ngram", "self"],
                   help="draft source for the speculative rung")
    p.add_argument("--prefill-chunk", type=int, default=8,
                   help="chunk tokens (0 = skip the chunk rung)")
    p.add_argument("--mesh", type=int, default=0,
                   help="model-axis size (0 = skip the mesh rung)")
    p.add_argument("--max-new", type=int, default=8)
    p.add_argument("--num-slots", type=int, default=2)
    p.add_argument("--page-size", type=int, default=8)
    p.add_argument("--max-seq-len", type=int, default=64)
    p.add_argument("--seed", type=int, default=0,
                   help="pinned-stream rng seed")
    args = p.parse_args(argv)

    model = _build_model(args.model)
    prompts = _pinned_stream(model.config.vocab_size, args.seed)
    mesh = None
    if args.mesh > 1:
        from paddle_tpu.distributed.topology import make_serving_mesh
        mesh = make_serving_mesh(args.mesh)

    base_kw = dict(num_slots=args.num_slots, page_size=args.page_size,
                   max_seq_len=args.max_seq_len)
    rungs = _ladder(args, mesh)
    print(f"pinned stream: {len(prompts)} prompts, "
          f"max_new={args.max_new}, model={args.model}")
    reference = None
    for name, feature, kw in rungs:
        got = _run(model, prompts, args.max_new, **base_kw, **kw)
        if reference is None:
            reference = got
            print(f"  [ok]      {name}")
            continue
        div = _first_divergence(reference, got)
        if div is None:
            print(f"  [ok]      {name}")
            continue
        r, off = div
        print(f"  [DIVERGE] {name}")
        print(f"\nfirst diverging rung: {name} (feature: {feature})")
        print(f"  request #{r} diverges at generated offset {off}:")
        print(f"    reference: {reference[r]}")
        print(f"    this rung: {got[r]}")
        print("file the bug against this feature's layer; every rung "
              "below it matched the reference.")
        return 2
    print("\nall rungs bit-identical to the per-token reference — the "
          "pinned greedy contract holds on this stream.")
    return 0


if __name__ == "__main__":
    sys.exit(main())
