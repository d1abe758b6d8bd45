"""The engine thread's phases on the device trace's clock, and the
device's idle time by the phase and the kind of call that covers it.

Two artifacts of a traced serving run meet here. ``art["timeline"]``:
the engine's step-timeline records of the whole window, on
``time.monotonic``; each carries ``phases``, the stretches the step's
host phases ran in (``[name, start_us - t_us, us, kind?]``; a
``launch`` names the kind of program it dispatched, a ``wait`` the kind
it fetched), and ``commit`` and ``loop`` follow from ``t_us``, ``ms``,
``commit_us`` and ``gap_us``. ``art["events"]``: the device planes of
the traced seconds, on the profiler's clock. No clock is shared: the
offset ``delta`` (device time = monotonic + ``delta``) is estimated
per capture from the programs themselves.

- *Programs*: the ``XLA Modules`` events of the first device plane
  named ``jit_step`` (decode) or ``jit_prefill`` (prefill), by start.
- *Pairing*: the n-th traced program is the (n + k)-th ``launch`` of a
  decode or prefill kind, for the ONE shift ``k`` under which the
  interleaving of kinds agrees over the whole traced stretch and the
  bracket below is not empty (a shift with an empty bracket puts a
  program before its dispatch or after its fetch: it is ruled out, not
  guessed away). A decode program is fetched by the ``wait`` of kind
  ``decode`` that settles it: waits settle launches in launch order,
  and a record's ``decode_ahead`` says how many launches were in flight
  when its own was made (0 or 1), which drops a step that was launched
  and never fetched. A prefill is fetched by the ``wait`` of its kind
  that follows its launch in the same call.
- *The offset*: a program cannot start before its dispatch began nor
  end after its fetch returned, so ``max(program end - wait end) <=
  delta <= min(program start - launch start)`` over the pairs. The
  attribution is made at the bracket's middle; its width bounds how
  far a share can be off.
- *Attribution*: the device's idle time is the complement of the ops
  of the first device plane within the traced window
  (``xplane.idle_pct``'s numerator). The part inside a program's
  ``XLA Modules`` span is ``in_program``. The rest is split by overlap
  over the host's stretches shifted by ``delta``: ``wait``, ``emit``,
  ``admit``, ``upload``, ``launch``, ``commit``, ``loop``; what none
  covers is ``unplaced``. The same seconds are cut a second way, by
  the call whose ``[t_us - gap_us, t_us + ms + commit_us]`` covers
  them: ``prefill_calls`` (its ``programs`` hold a prefill) and
  ``settled_calls`` (it launched a decode with ``decode_ahead`` 0 and
  no prefill); the rest are calls launched ahead.

Nothing to pair, no shift or more than one, an empty bracket, a
timeline without ``phases`` (an older program): ``None`` from every
function, never a guess and never 0.
"""

from __future__ import annotations

import json
import re
import sys

import numpy as np

from benchmarks import xplane

PHASES = ("wait", "launch", "upload", "emit", "admit", "commit", "loop")
NAMES = PHASES + ("in_program", "unplaced")
CALLS = ("prefill_calls", "settled_calls")
PROGRAMS = (("decode", re.compile(r"^jit_step\b")),
            ("prefill", re.compile(r"^jit_prefill\b")))
LAUNCH_KINDS = {"decode": "decode", "prefill": "prefill",
                "prefill_chained": "prefill"}


def programs(events) -> list:
    """``[(kind, start, end)]`` in seconds on the trace's clock: the
    decode and prefill programs of the first device plane, by start."""
    planes = xplane.device_planes(events or ())
    out = []
    for e in events or ():
        if planes and e["plane"] == planes[0] \
                and e["line"] == xplane.MODULES_LINE:
            kind = next((k for k, rx in PROGRAMS if rx.search(e["name"])),
                        None)
            if kind:
                out.append((kind, e["start"], e["start"] + e["dur"]))
    return sorted(out, key=lambda p: p[1])


def launches(timeline):
    """``[(kind, start, end, fetched)]`` in seconds on
    ``time.monotonic``: every ``launch`` of a decode or prefill kind in
    the timeline's order, with the end of the ``wait`` that fetched its
    program (``None`` where none did: a chunk that is not the last, a
    step dropped in flight). ``None`` where a record has no ``phases``."""
    out, flying = [], []
    for e in timeline or ():
        if "phases" not in e:
            return None
        t = e["t_us"]
        own = {}  # a prefill is fetched inside its own call
        for seg in e["phases"]:
            kind = LAUNCH_KINDS.get(seg[3]) if len(seg) > 3 else None
            if kind is None:
                continue
            a, b = (t + seg[1]) * 1e-6, (t + seg[1] + seg[2]) * 1e-6
            if seg[0] == "launch":
                if seg[3] not in e.get("programs", ()):
                    continue  # a dispatch that raised launched nothing
                if kind == "decode":
                    ahead = int(e.get("decode_ahead", 0))
                    flying = flying[len(flying) - ahead:] if ahead else []
                    flying.append(len(out))
                else:
                    own[seg[3]] = len(out)
                out.append([kind, a, b, None])
            elif seg[0] == "wait":
                i = (flying.pop(0) if flying else None) \
                    if kind == "decode" else own.pop(seg[3], None)
                if i is not None:
                    out[i][3] = b
    return [tuple(x) for x in out]


def agreeing_shifts(progs: list, calls: list) -> np.ndarray:
    """Every ``k`` with ``progs[n].kind == calls[n + k].kind`` for all
    ``n``."""
    n, m = len(progs), len(calls)
    if not n or n > m:
        return np.zeros(0, int)
    is_prefill = np.array([c[0] == "prefill" for c in calls])
    at = [i for i, p in enumerate(progs) if p[0] == "prefill"]
    count = np.concatenate([[0], np.cumsum(is_prefill)])
    ok = count[n:] - count[:m - n + 1] == len(at)
    for i in at:
        ok &= is_prefill[i:i + m - n + 1]
    return np.flatnonzero(ok)


def brackets(progs: list, calls: list, shifts) -> tuple:
    """``(lo, hi)`` arrays over ``shifts``: the offsets each shift
    allows (``lo > hi``: none)."""
    n = len(progs)
    start = np.array([p[1] for p in progs])
    end = np.array([p[2] for p in progs])
    began = np.array([c[1] for c in calls])
    fetched = np.array([np.inf if c[3] is None else c[3] for c in calls])
    began = np.lib.stride_tricks.sliding_window_view(began, n)
    fetched = np.lib.stride_tricks.sliding_window_view(fetched, n)
    shifts = np.asarray(shifts, int)
    lo, hi = np.empty(len(shifts)), np.empty(len(shifts))
    for i in range(0, len(shifts), 256):  # a block of rows at a time
        k = shifts[i:i + 256]
        hi[i:i + 256] = (start[None, :] - began[k]).min(axis=1)
        lo[i:i + 256] = (end[None, :] - fetched[k]).max(axis=1)
    return lo, hi


def estimate(timeline, events):
    """``{"shift", "lo", "hi", "delta", "programs", "fetched"}``
    (seconds) or ``None``."""
    calls = launches(timeline)
    progs = programs(events)
    if not calls or not progs:
        return None
    shifts = agreeing_shifts(progs, calls)
    if not len(shifts):
        return None
    lo, hi = brackets(progs, calls, shifts)
    fits = np.flatnonzero((lo <= hi) & np.isfinite(lo))
    if len(fits) != 1:
        return None
    k = int(shifts[fits[0]])
    lo, hi = float(lo[fits[0]]), float(hi[fits[0]])
    return {"shift": k, "lo": lo, "hi": hi, "delta": 0.5 * (lo + hi),
            "programs": len(progs),
            "fetched": sum(c[3] is not None
                           for c in calls[k:k + len(progs)])}


def host_spans(timeline, delta: float) -> list:
    """``[(name, start, end)]`` on the trace's clock, in order: every
    stretch of every record, its ``loop`` before and its ``commit``
    after (what ``xplane.breakdown(events, host_spans)`` takes)."""
    out = []
    for e in timeline:
        t = e["t_us"] * 1e-6 + delta
        if e.get("gap_us"):
            out.append(("loop", t - e["gap_us"] * 1e-6, t))
        for seg in e["phases"]:
            a = t + seg[1] * 1e-6
            out.append((seg[0], a, a + seg[2] * 1e-6))
        c = t + e["ms"] * 1e-3
        out.append(("commit", c, c + e.get("commit_us", 0.0) * 1e-6))
    return out


def call_spans(timeline, delta: float) -> list:
    """``[(name, start, end)]``: each call from the start of its loop
    to the end of its commit, named by the second cut (``None``: a call
    that launched ahead, or launched nothing)."""
    out = []
    for e in timeline:
        t = e["t_us"] * 1e-6 + delta
        progs = e.get("programs", {})
        name = None
        if any(LAUNCH_KINDS.get(k) == "prefill" for k in progs):
            name = "prefill_calls"
        elif "decode" in progs and not e.get("decode_ahead", 0):
            name = "settled_calls"
        out.append((name, t - e.get("gap_us", 0.0) * 1e-6,
                    t + e["ms"] * 1e-3 + e.get("commit_us", 0.0) * 1e-6))
    return out


def _merged(intervals) -> tuple:
    """Sorted disjoint ``(starts, ends)`` arrays of the union."""
    iv = np.array([(a, b) for a, b in intervals if b > a], float)
    if not len(iv):
        return np.zeros(0), np.zeros(0)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    reach = np.maximum.accumulate(iv[:, 1])
    first = np.concatenate([[True], iv[1:, 0] > reach[:-1]])
    return iv[first, 0], reach[np.concatenate([first[1:], [True]])]


def _covered(starts, ends, t):
    """Length of the sorted disjoint intervals that lies before each
    of ``t``."""
    if not len(starts):
        return np.zeros_like(t)
    before = np.concatenate([[0.0], np.cumsum(ends - starts)])
    j = np.maximum(np.searchsorted(starts, t, side="right") - 1, 0)
    return before[j] + np.clip(t - starts[j], 0.0, (ends - starts)[j])


def _by_name(spans: list, names) -> dict:
    """``{name: merged (starts, ends)}`` of ``[(name, start, end)]``."""
    kept = {n: [] for n in names}
    for n, a, b in spans:
        if n in kept:
            kept[n].append((a, b))
    return {n: _merged(v) for n, v in kept.items()}


def idle_pieces(events) -> tuple:
    """``(window, idle, outside)``: the traced window of the first
    device plane, its idle seconds, and the idle stretches that lie
    outside every program, as sorted disjoint arrays."""
    planes = xplane.device_planes(events)
    ops = [(e["start"], e["start"] + e["dur"])
           for e in xplane.ops_of(events, planes[0])]
    t0, t1 = min(a for a, _ in ops), max(b for _, b in ops)
    busy = xplane.union_seconds(ops)
    mods = [(e["start"], e["start"] + e["dur"]) for e in events
            if e["plane"] == planes[0] and e["line"] == xplane.MODULES_LINE]
    s, e = _merged(ops + [(max(a, t0), min(b, t1)) for a, b in mods])
    return t1 - t0, (t1 - t0) - busy, (e[:-1], s[1:])


def attribution(timeline, events):
    """``at(delta)``: seconds of the device's idle time under each of
    ``NAMES`` and ``CALLS`` with the host's clock shifted by ``delta``,
    and ``window``, the denominator of ``xplane.idle_pct``. What does
    not depend on the offset is worked out once: the idle stretches
    are moved onto the host's clock, not the host's onto theirs."""
    window, idle, (a, b) = idle_pieces(events)
    rest = float(np.sum(b - a))
    cover = _by_name(host_spans(timeline, 0.0), PHASES)
    cover.update(_by_name(call_spans(timeline, 0.0), CALLS))

    def at(delta: float) -> dict:
        out = {n: float(np.sum(_covered(s, e, b - delta)
                               - _covered(s, e, a - delta)))
               for n, (s, e) in cover.items()}
        out["in_program"] = idle - rest
        out["unplaced"] = rest - sum(out[n] for n in PHASES)
        out["window"] = window
        return out
    return at


def attribute(timeline, events, delta: float) -> dict:
    return attribution(timeline, events)(delta)


def shares(art: dict):
    """Per cent of the traced window the device idled under each name,
    and ``bracket_us``; worked out once a run and kept in ``art``.
    ``None`` where nothing can be paired."""
    if "host_clock" in art:
        return art["host_clock"]
    timeline, events = art.get("timeline"), art.get("events")
    out = None
    est = estimate(timeline, events) if timeline and events else None
    if est is not None:
        seconds = attribution(timeline, events)

        def at(delta):
            got = seconds(delta)
            return {k: 100.0 * got[k] / got["window"]
                    for k in NAMES + CALLS}
        out = dict(at(est["delta"]),
                   bracket_us=(est["hi"] - est["lo"]) * 1e6)
        # for PERF.md: what the error does, read at both edges
        print("[bench] host_clock " + json.dumps({
            "shift": est["shift"], "programs": est["programs"],
            "fetched": est["fetched"], "bracket_us": out["bracket_us"],
            "delta_s": est["delta"], "mid": out,
            "lo": at(est["lo"]), "hi": at(est["hi"])}), file=sys.stderr)
    art["host_clock"] = out
    return out


def read(art: dict, name: str):
    got = shares(art)
    return None if got is None else got[name]
