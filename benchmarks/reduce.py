"""Small arithmetic shared by the drivers and the per-layer readers."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """Nearest-rank percentile (q in 0..100) of a non-empty list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = max(0, min(len(xs) - 1, math.ceil(q / 100.0 * len(xs)) - 1))
    return float(xs[k])


def tokens_in(log: list, t_lo: float, t_hi: float, skip_first: bool = False):
    """``(request, index)`` of every streamed token stamped in
    [t_lo, t_hi]; ``index`` 0 is a request's first token."""
    out = []
    for r in log:
        for j, t in enumerate(r["token_times"]):
            if t_lo <= t <= t_hi and not (skip_first and j == 0):
                out.append((r, j))
    return out


def spans_named(traces: list, name: str) -> list:
    """Every span called ``name`` with its request's ``request`` span
    args merged in (``prompt_len`` among them)."""
    out = []
    for tr in traces:
        req = next((s.get("args") or {} for s in tr.get("spans", ())
                    if s.get("name") == "request"), {})
        for s in tr.get("spans", ()):
            if s.get("name") == name and s.get("t1_us") is not None:
                out.append({**req, **(s.get("args") or {}),
                            "t0": s["t0_us"] * 1e-6, "t1": s["t1_us"] * 1e-6})
    return out
