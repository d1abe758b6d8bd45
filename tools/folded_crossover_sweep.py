"""Measure the d=128 CAUSAL folded-vs-streaming attention crossover —
r5 verdict item 6: `folded_attention_supported`'s d=128 causal cap was
the gate's one unmeasured edge ("unmeasured beyond; conservative"), and
d=128 causal is exactly the Llama-family shape class.

On a chip-attached host this sweeps S in {256, 512, 1024} at d=128
causal, scanned fwd+bwd (the same amortized-launch harness as the r4/r5
crossover sweeps), and writes FOLDED_CROSSOVER.json. Off-chip it emits the CPU-derived cost
model that currently backs the gate cap, with on_chip_pending=true —
the artifact then records WHY the cap is where it is until a chip run
replaces the model with data.

Cost model (calibrated on the r5 on-chip d=64 causal measurements
cited in folded_attention.folded_attention_supported):

- folded pays the full S^2 score block in ONE fused pass; its backward
  recomputes in-kernel (no lse, no delta prepass): ~14 MAC-units of
  S^2*d work fwd+bwd, zero transposes.
- the streaming kernel skips fully-masked K blocks under causal, so it
  pays ~(S^2/2 + S*block/2) plus a separate delta prepass and per-block
  online-softmax state: ~15 MAC-units on HALF the pairs, PLUS the
  [B,S,H,D]<->[B,H,S,D] transpose round trips ("data formatting") and
  per-block grid overhead that dominates small grids.
- at d=64 the streaming kernel's half-lane (64-wide) contractions halve
  its MXU efficiency, which cancels its 2x causal-pair advantage —
  measured: folded wins the WHOLE single-block range (512: 5.68 vs
  6.62 ms; 1024: 4.33 vs 5.25). At d=128 the contractions are
  full-lane, so the 2x pair advantage is real; what folded keeps is the
  fused single pass + no transposes + no per-block overhead, which the
  d=64 data bounds at ~15-25% of the streaming step.
- => at d=128 the calibrated model (see _cost_model) has streaming at
  ~0.6-0.7x folded's time for every S where streaming is eligible
  (S >= 512, its own measured XLA crossover), and folded keeping only
  the one-256-block class where streaming is below that crossover.
  The cap therefore MOVES from the r5 conservative 512 down to 256 —
  the model says the old cap was routing the Llama-shape S=512 causal
  class to the slower kernel.

Usage: python tools/folded_crossover_sweep.py [--out FOLDED_CROSSOVER.json]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# (S, batch, heads) per sweep point: constant B*S*H token volume keeps
# the three points comparable (the r5 d=64 sweep convention)
POINTS = ((256, 32, 8), (512, 16, 8), (1024, 8, 8))
D = 128

# r5 on-chip d=64 causal measurements (ms/iter, scanned fwd+bwd) that
# calibrate the off-chip cost model — cited in the gate docstring.
D64_MEASURED = {
    "S512_b64_h12": {"folded": 5.68, "streaming": 6.62},
    "S1024_b8_h12": {"folded": 4.33, "streaming": 5.25},
}


def _cost_model():
    """folded/streaming fwd+bwd time ratio at d=128 causal; >1 means
    streaming wins. Calibrated decomposition, in units of folded's
    fused fwd+bwd cost (14 MAC-passes over the full S^2 block = 14.0):

    - d=64 measured streaming/folded: 1.166 (S=512), 1.212 (S=1024).
      Streaming's MAC work under causal is 15 passes over S^2/2 pairs
      at HALF-lane (64-wide) MXU efficiency = 15.0 units; the measured
      remainder (16.3 - 15.0 = 1.3 at S=512; 17.0 - 15.0 = 2.0 at
      S=1024) is non-MXU: per-block online-softmax state, the delta
      prepass, transposes.
    - d=128 halves ONLY the MAC term (full-lane contractions): 7.5
      units; the non-MXU remainder carries over. Streaming therefore
      models at 8.8 (S=512) / 9.5 (S=1024) vs folded's 14.0 — ratios
      ~1.6 and ~1.5, OUTSIDE any plausible calibration error, so the
      model says streaming wins wherever it is eligible (S >= 512, its
      own measured XLA crossover). At S=256 streaming is below that
      crossover (r4: XLA beats it under 512; folded beats XLA at 256),
      so folded keeps the one-256-block causal class."""
    folded = 14.0
    d64_ratio = {256: 1.12, 512: 1.166, 1024: 1.212}  # 256 interpolated
    out = {}
    for s, _, _ in POINTS:
        non_mxu = folded * d64_ratio[s] - 15.0
        streaming = 7.5 + non_mxu
        out[f"S{s}"] = {
            "folded_units": folded,
            "streaming_units_d128": round(streaming, 2),
            "streaming_non_mxu_units_from_d64": round(non_mxu, 2),
            "ratio_folded_over_streaming": round(folded / streaming, 3),
            "streaming_eligible": s >= 512,
            "folded_wins": s < 512 or folded < streaming,
        }
    return out


def _measure_one(s, b, h, use_folded: bool):
    """Scanned causal fwd+bwd at [b, s, h, 128], folded vs streaming
    forced through their public entries."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    from paddle_tpu.ops.pallas.folded_attention import folded_attention

    rng = np.random.default_rng(0)
    q, k, v = (jnp.asarray(rng.standard_normal((b, s, h, D)),
                           jnp.bfloat16) for _ in range(3))
    fn = folded_attention if use_folded else flash_attention

    def loss(q_, k_, v_):
        return jnp.sum(fn(q_, k_, v_, causal=True).astype(jnp.float32))

    grad = jax.grad(loss, argnums=(0, 1, 2))

    def scan_all(q_, k_, v_):
        def body(c, _):
            dq, dk, dv = grad(q_ + c.astype(q_.dtype), k_, v_)
            return (jnp.sum(dq.astype(jnp.float32)) * 1e-30 +
                    jnp.sum(dk.astype(jnp.float32)) * 1e-30 +
                    jnp.sum(dv.astype(jnp.float32)) * 1e-30), None

        c, _ = jax.lax.scan(body, jnp.asarray(0.0, jnp.float32), None,
                            length=20)
        return c

    jitted = jax.jit(scan_all)
    float(jitted(q, k, v))  # compile + warm
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(jitted(q, k, v))
        times.append((time.perf_counter() - t0) / 20)
    return sorted(times)[1] * 1e3  # median window, ms/iter


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "FOLDED_CROSSOVER.json"))
    args = ap.parse_args()

    import jax
    on_chip = jax.default_backend() == "tpu"
    result = {
        "sweep": "d=128 causal folded-vs-streaming, scanned fwd+bwd",
        "points": [f"S{s}_b{b}_h{h}" for s, b, h in POINTS],
        "calibration_d64_causal_measured_ms": D64_MEASURED,
        "cost_model": _cost_model(),
        "gate_decision": (
            "d=128 causal cap set to ONE 256 block "
            "(folded_attention.folded_attention_supported, changed "
            "from the r5 conservative 512): the calibrated model puts "
            "folded at ~1.6x streaming's time at S=512 and ~1.5x at "
            "S=1024 — full-lane streaming's 2x causal-pair skip "
            "dominates once it is eligible — while at S=256 streaming "
            "sits below its own measured XLA crossover and folded "
            "keeps the class; d=64 causal keeps the full single-block "
            "range (measured wins at 512 AND 1024: half-lane "
            "streaming forfeits the pair advantage)"),
        "on_chip_pending": not on_chip,
    }
    if on_chip:
        measured = {}
        for s, b, h in POINTS:
            row = {}
            for name, use_folded in (("folded", True),
                                     ("streaming", False)):
                try:
                    row[name] = round(_measure_one(s, b, h, use_folded),
                                      3)
                except Exception as e:  # shape not supported/compile
                    row[name] = f"{type(e).__name__}: {str(e)[:100]}"
            measured[f"S{s}_b{b}_h{h}"] = row
            print(f"S{s}_b{b}_h{h}: {row}", flush=True)
        result["measured_ms_per_iter"] = measured
        result["on_chip_pending"] = False
    else:
        result["note"] = (
            "no TPU reachable from this host (cpu backend) - committed "
            "with the cost model standing in; rerun on a chip-attached "
            "host to replace it with measurements and re-derive the cap")
    with open(args.out, "w") as f:
        json.dump(result, f, indent=2)
        f.write("\n")
    print(json.dumps(result, indent=2))
    return 0


if __name__ == "__main__":
    sys.exit(main())
