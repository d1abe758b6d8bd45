"""Where the engine thread's wall time goes, from the step timeline.

Every record of the engine's step timeline (the server's ``trace`` op)
carries the stepping thread's host phases on ``time.monotonic``:
``host_us`` (``{phase: us}`` inside the step's ``ms``: ``admit``,
``upload``, ``launch``, ``wait``, ``emit``, and ``other``, what they
leave of ``ms``), ``commit_us`` (the timeline's own cost, after ``ms``),
``gap_us`` (the caller's loop between the previous record's commit and
this step's start) and ``cpu_us`` (the thread's CPU time over all
three). The thread's wall time over a window's records is

    sum(ms) + sum(commit_us) + sum(gap_us),

leaving out a ``gap_us`` that follows a record with no active slot and
nothing queued (the thread asleep, not the host at work) and the first
record's (what went before it is not in the window). Each reader under
``layer_metrics/engine_thread_*`` is one share of that. ``loop`` has no
reader: it read under 1 % in every serve cell on the chip (PERF.md);
its time is in the wall time all the shares divide by.
"""

from __future__ import annotations

STEP_PHASES = ("admit", "upload", "launch", "wait", "emit")
PHASES = STEP_PHASES + ("commit", "loop")
OTHER_LIMIT = 0.02  # of the wall time; above it the shares say too little


def wall_us(timeline) -> dict | None:
    """``{"wall", "other", "cpu", <phase>...}`` in microseconds over the
    records, or ``None`` where they carry no ``host_us``."""
    records = list(timeline or ())
    if not records or any("host_us" not in e for e in records):
        return None
    out = dict.fromkeys(PHASES + ("other", "cpu", "wall"), 0.0)
    prev = None
    for e in records:
        for name, us in e["host_us"].items():
            out[name if name in STEP_PHASES else "other"] += us
        out["commit"] += e.get("commit_us", 0.0)
        if prev is not None and (prev["slots_active"] or prev["queued"]):
            out["loop"] += e.get("gap_us", 0.0)
        out["cpu"] += e.get("cpu_us", 0.0)
        out["wall"] += e["ms"] * 1e3
        prev = e
    out["wall"] += out["commit"] + out["loop"]
    return out


def share_pct(timeline, name: str):
    """Per cent of the engine thread's wall time spent in phase ``name``
    (or on the CPU, ``"cpu"``); ``None``, never 0, where there is
    nothing to read or the phases leave more than ``OTHER_LIMIT``."""
    w = wall_us(timeline)
    if not w or w["wall"] <= 0 or w["other"] > OTHER_LIMIT * w["wall"]:
        return None
    return 100.0 * w[name] / w["wall"]
