"""Benchmark: GPT-1.3B training throughput on one TPU chip.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": ..., "vs_baseline": N,
   "device": {"platform": ..., "kind": ..., "count": N}, ...}

The reference publishes no absolute numbers (BASELINE.md), so
``vs_baseline`` is MFU / 0.45 — the north-star target from BASELINE.json
(ERNIE-3.0-10B hybrid at >=45% MFU); >1.0 means the per-chip efficiency
target is met on this config.

A measurement needs the chip: without a TPU this fails (typed
`UnavailableError`), it does not fall back. ``--cpu-rehearsal`` runs
the same code at gpt_tiny size on the CPU to check paths and control
flow; its line names the CPU and carries no device metric.
"""

from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np

# Peak dense bf16 TFLOP/s of one chip, keyed by what
# ``jax.devices()[0].device_kind`` reports. Source: Google Cloud TPU
# documentation, system architecture pages "TPU v4", "TPU v5e",
# "TPU v5p", "TPU v6e" (per-chip peak compute, bf16).
PEAK_BF16_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,   # v5e
    "TPU v5": 459.0,        # v5p
    "TPU v6 lite": 918.0,   # v6e
}


def require_tpu():
    """``jax.devices()`` of a process that was asked to measure on the
    chip; raises when the default backend is not a TPU."""
    import jax

    from paddle_tpu.core.enforce import UnavailableError
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise UnavailableError(
            f"this run needs a TPU and JAX found "
            f"{devs[0].platform}:{devs[0].device_kind}; there is no CPU "
            f"fallback (a rehearsal on the CPU is asked for explicitly)")
    return devs


def device_block() -> dict:
    """The device as JAX reports it, for every result line."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peak_flops() -> float:
    """Peak bf16 FLOP/s of the attached chip; an unknown kind is an
    error, not a default."""
    import jax

    from paddle_tpu.core.enforce import NotFoundError
    kind = jax.devices()[0].device_kind
    if kind not in PEAK_BF16_TFLOPS:
        raise NotFoundError(
            f"no peak FLOP/s on record for device kind {kind!r}; add it "
            f"to bench.PEAK_BF16_TFLOPS with its source")
    return PEAK_BF16_TFLOPS[kind] * 1e12


def out_path(name: str) -> str:
    """``chiprun_out/<name>`` in the checkout: where a run leaves what
    is too long for its output (the directory a chip call brings
    back; git-ignored)."""
    out = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "chiprun_out")
    os.makedirs(out, exist_ok=True)
    return os.path.join(out, name)


def gpt1p3b_config(rehearsal: bool = False):
    """``(cfg, batch, seq)`` of the benchmark: GPT-3 1.3B-class
    (BASELINE.md staged config #3), bf16, B2 x S2048, flash attention,
    no remat. ``rehearsal`` swaps in gpt_tiny shapes (CPU rehearsal)."""
    from paddle_tpu.models import GPTConfig, gpt_tiny

    if rehearsal:
        return gpt_tiny(), 2, 64
    # Measured sweep (v5e MFU, pre-round records): B1 67.5%, B2 72.3%,
    # B3 70.1%; longer-seq/no-remat B2xS3072 70.3%, B1xS4096 71.2%;
    # selective remat B4xS2048 every=3 62.8% — B2xS2048 no-remat is the
    # sweet spot. Flash is explicit: every number above ran the Pallas
    # flash kernel.
    cfg = GPTConfig(vocab_size=32768, hidden_size=2048, num_layers=24,
                    num_heads=16, max_seq_len=2048, dropout=0.0,
                    attn_dropout=0.0, dtype="bfloat16",
                    use_flash_attention=True, loss_chunk_size=0)
    return cfg, 2, 2048


def gpt1p3b_model(cfg, rehearsal: bool = False):
    """The benchmark's model from seed 0: bf16 weights with fp32 norm
    parameters (fp32 throughout at rehearsal size)."""
    import paddle_tpu as pt
    from paddle_tpu.models import GPTForCausalLM

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    if not rehearsal:
        from bench_all import _to_bf16_except_norms
        _to_bf16_except_norms(model)
    return model


def gpt1p3b_optimizer():
    """bf16 Adam slots: multi_precision f32 moments would not leave
    room for 1.3B params + activations in 16G HBM."""
    import paddle_tpu.optimizer as optim
    return optim.AdamW(learning_rate=1e-4)


def train_loss(model, batch):
    return model(batch[0], labels=batch[1])


def gpt1p3b_train_step(rehearsal: bool = False):
    """The benchmark's trainer through `paddle_tpu.jit.TrainStep`.
    Returns ``(cfg, model, step, batch, seq)``."""
    from paddle_tpu.jit import TrainStep

    cfg, batch, seq = gpt1p3b_config(rehearsal)
    model = gpt1p3b_model(cfg, rehearsal)
    step = TrainStep(model, gpt1p3b_optimizer(), train_loss)
    return cfg, model, step, batch, seq


def train_flops_per_token(model, cfg, seq: int) -> float:
    """6ND model FLOPs + the attention term (fwd+bwd)."""
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    return 6.0 * n_params + 12.0 * cfg.num_layers * cfg.hidden_size * seq


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="run at gpt_tiny size on the CPU (paths and control flow "
             "only; the output names the CPU and no device metric)")
    args = parser.parse_args(argv)

    import jax
    if args.cpu_rehearsal:
        jax.config.update("jax_platforms", "cpu")
    else:
        require_tpu()
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()

    cfg, model, step, batch, seq = gpt1p3b_train_step(args.cpu_rehearsal)
    steps = 3 if args.cpu_rehearsal else 8

    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)

    # The hot loop is multi_step: the whole timed window is ONE device
    # launch (lax.scan over stacked batches) — the TPU-native analog of
    # the reference's C++ trainer loop (Executor::RunFromDataset), which
    # likewise never returns to Python between steps.
    timed_batches = (np.broadcast_to(ids, (steps,) + ids.shape).copy(),) * 2
    # warmup at the SAME scan length as the timed window (scan length is
    # part of the compiled shape; a different length would recompile
    # inside the timed region)
    jax.block_until_ready(step.multi_step(timed_batches))

    # Median of >=3 timed windows with the run-to-run spread quantified.
    # Each window ends in block_until_ready: chip_smoke.py's train phase
    # times the same window both ways (block_until_ready and a host
    # fetch of the last loss) on every run and fails if they disagree.
    n_windows = max(1, int(os.environ.get("PT_BENCH_WINDOWS", "3")))
    window_toks = []
    tokens_per_step = batch * seq
    for _ in range(n_windows):
        t0 = time.perf_counter()
        losses = jax.block_until_ready(step.multi_step(timed_batches))
        dt = time.perf_counter() - t0
        window_toks.append(tokens_per_step * steps / dt)
    final_loss = float(losses[-1])
    if not (np.isfinite(final_loss) and final_loss < 12.0):
        raise RuntimeError(
            f"training diverged during benchmark: {final_loss}")

    tok_s = float(np.median(window_toks))
    spread_pct = 100.0 * (max(window_toks) - min(window_toks)) / tok_s
    result = {
        "metric": "gpt_tiny_train_tokens_per_sec_cpu_rehearsal"
                  if args.cpu_rehearsal
                  else "gpt1p3b_train_tokens_per_sec_chip",
        "value": round(tok_s, 1),
        "unit": "tokens/s",
        "windows": [round(t, 1) for t in window_toks],
        "spread_pct": round(spread_pct, 2),
        "steps_per_window": steps,
        "jax_version": jax.__version__,
        "device": device_block(),
    }
    if not args.cpu_rehearsal:
        mfu = tok_s * train_flops_per_token(model, cfg, seq) / peak_flops()
        result["vs_baseline"] = round(mfu / 0.45, 4)
        result["mfu_pct"] = round(100.0 * mfu, 2)
    print(json.dumps(result))


if __name__ == "__main__":
    main()
