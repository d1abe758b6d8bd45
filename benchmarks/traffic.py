"""One general generator for every traffic mix: the mix is a data file.

Training (``kind: train``): a distinct batch of token ids for every
step, drawn from the seed.

Serving (``kind: serve``): an open loop. ``rate_per_s`` requests a
second for ``seconds``; the arrival count is fixed (rate x seconds,
rounded). The schedule (every due time, prompt length and answer
length, in their order) is drawn from the MIX's own ``schedule_seed``,
so every run of a cell offers the same work at the same instants; the
run's seed gives the token ids (and, in the driver, the weights). A
tail over a few hundred requests of a queue near its knee is set by
where the bursts and the long answers fall: schedules that differ by
seed move a 95th percentile by a factor of two (measured, PERF.md), and
no bound could hold that. Arrival gaps are rescaled so that the window
is spanned exactly.

Length distributions (``prompt``, ``output``): ``{"dist": "lognormal",
"median": m, "sigma": s, "min": a, "max": b}``, ``{"dist": "uniform",
"min": a, "max": b}`` or ``{"dist": "fixed", "value": v}``. Arrivals
(``arrivals``): ``{"process": "poisson"}`` or ``{"process": "gamma",
"shape": k}`` (shape < 1 is bursty). ``shared_prefix``: ``{"tokens": n,
"groups": g}`` makes requests of one group share their first n tokens.
"""

from __future__ import annotations

import numpy as np


def rng_of(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), int(stream)])


def draw_lengths(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    dist = spec["dist"]
    if dist == "fixed":
        return np.full((n,), int(spec["value"]), np.int64)
    if dist == "uniform":
        return rng.integers(int(spec["min"]), int(spec["max"]) + 1, n)
    if dist == "lognormal":
        x = np.exp(rng.normal(np.log(float(spec["median"])),
                              float(spec["sigma"]), n))
        return np.clip(np.rint(x), int(spec["min"]),
                       int(spec["max"])).astype(np.int64)
    raise ValueError(f"unknown length distribution {dist!r}")


def arrival_times(spec: dict, n: int, seconds: float,
                  rng: np.random.Generator) -> np.ndarray:
    """``n`` due times in [0, seconds): gaps from the process, rescaled
    so that the n gaps (the one after the last arrival included) span
    the window exactly."""
    proc = spec.get("process", "poisson")
    if proc == "poisson":
        gaps = rng.exponential(1.0, n + 1)
    elif proc == "gamma":
        k = float(spec["shape"])
        gaps = rng.gamma(k, 1.0 / k, n + 1)
    else:
        raise ValueError(f"unknown arrival process {proc!r}")
    t = np.cumsum(gaps)
    return t[:-1] / t[-1] * seconds


def serve_schedule(traffic: dict, vocab: int, seed: int,
                   seconds: float) -> list:
    """The requests of one run: ``[{"due": s, "prompt": [...],
    "max_new_tokens": n}, ...]`` in due order."""
    n = max(1, int(round(float(traffic["rate_per_s"]) * seconds)))
    fixed = int(traffic.get("schedule_seed", 0))
    sizes = rng_of(fixed, 1)
    plen = draw_lengths(traffic["prompt"], n, sizes)
    olen = draw_lengths(traffic["output"], n, sizes)
    due = arrival_times(traffic.get("arrivals", {}), n, seconds,
                        rng_of(fixed, 2))
    ids = rng_of(seed, 3)
    shared = traffic.get("shared_prefix")
    prefixes = None
    if shared:
        prefixes = ids.integers(0, vocab, (int(shared["groups"]),
                                           int(shared["tokens"])))
    out = []
    for i in range(n):
        prompt = ids.integers(0, vocab, int(plen[i]))
        if prefixes is not None:
            pre = prefixes[int(ids.integers(0, len(prefixes)))]
            k = min(len(pre), len(prompt) - 1)
            prompt[:k] = pre[:k]
        out.append({"due": float(due[i]), "prompt": prompt.tolist(),
                    "max_new_tokens": int(olen[i])})
    return out


def train_batch(traffic: dict, vocab: int, seed: int, step: int) -> np.ndarray:
    """Token ids ``[steps_per_launch, batch, seq]`` of launch ``step``:
    every row of every step differs."""
    shape = (int(traffic["steps_per_launch"]), int(traffic["batch"]),
             int(traffic["seq"]))
    return rng_of(seed, 1000 + step).integers(0, vocab, shape).astype(np.int32)
