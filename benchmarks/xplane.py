"""From a profiler trace to numbers: the benchmark's own reduction.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote
(with ``jax.profiler.ProfileData``) into plain records; everything else
works on those records, so the arithmetic is tested on a small recorded
table without a chip.

A record: ``{"plane", "line", "name", "start": s, "dur": s, "stats":
{...}}``, times in seconds. Device planes are those named
``/device:TPU:n``. On such a plane the line ``XLA Ops`` holds one event
per executed HLO op (a Pallas kernel is one custom-call op), and the
line ``XLA Modules`` one event per executed program, named after the
jitted function.
"""

from __future__ import annotations

import bisect
import functools
import glob
import os
import re

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
KEEP_STATS = False  # `run.py --dump-trace` keeps each op's stats to print


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load_events(path: str) -> list:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            if line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                stats = {}
                if KEEP_STATS and line.name == OPS_LINE:
                    for k, v in ev.stats:
                        if isinstance(v, bytes):
                            v = v.decode("utf-8", "replace")
                        if not isinstance(v, str) or len(v) <= 400:
                            stats[str(k)] = v
                rec = {"plane": plane.name, "line": line.name,
                       "name": ev.name, "start": ev.start_ns * 1e-9,
                       "dur": ev.duration_ns * 1e-9, "stats": stats}
                out.append(rec)
    return out


def device_planes(events: list) -> list:
    return sorted({e["plane"] for e in events
                   if e["plane"].startswith("/device:TPU")})


def ops_of(events: list, plane: str) -> list:
    return [e for e in events if e["plane"] == plane
            and e["line"] == OPS_LINE]


def union_seconds(intervals) -> float:
    """Length of the union of ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def busy_seconds(events: list) -> float:
    """Seconds in which an operation ran on the device, averaged over
    the device planes."""
    planes = device_planes(events)
    if not planes:
        return 0.0
    return sum(union_seconds((e["start"], e["start"] + e["dur"])
                             for e in ops_of(events, p))
               for p in planes) / len(planes)


def window_seconds(events: list) -> float:
    """First start to last end over the device planes' ops."""
    ev = [e for p in device_planes(events) for e in ops_of(events, p)]
    if not ev:
        return 0.0
    return max(e["start"] + e["dur"] for e in ev) - min(e["start"] for e in ev)


def idle_pct(events: list):
    """1 - busy over the traced window, in per cent; nothing traced,
    nothing returned."""
    window = window_seconds(events) if events else 0.0
    return 100.0 * (1.0 - busy_seconds(events) / window) if window else None


# An op's event is named by its whole HLO instruction, the same text at
# every execution: what follows is worked out once per distinct name.

def short_name(e: dict) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return re.sub(r"^%", "", e["name"].split(" = ", 1)[0])


def op_kind(e: dict) -> str:
    """The HLO opcode of an op event (``fusion``, ``custom-call``,
    ``while``, ``copy-start``, ...)."""
    return _kind_of(e["name"])


@functools.lru_cache(maxsize=None)
def _kind_of(name: str) -> str:
    text = name.split(" = ", 1)[-1]
    m = re.search(r"(?:^|[\s)}])([a-z][a-z\-]*)\(", text)
    return m.group(1) if m else re.sub(r"[.\d]+$", "",
                                       short_name({"name": name}))


def _is_container(e: dict) -> bool:
    """A while/conditional/call op spans the ops of its body, which are
    events of their own: counting both would count the time twice."""
    return op_kind(e) in ("while", "conditional", "call")


def result_shape(e: dict) -> str:
    text = e["name"].split(" = ", 1)[-1]
    m = re.match(r"\(?([a-z]+\d*\[[\d,]*\])", text)
    return m.group(1) if m else ""


def op_group(e: dict) -> str:
    return _group_of(e["name"])


@functools.lru_cache(maxsize=None)
def _group_of(name: str) -> str:
    """The name an op's time is summed under. The trace of this runtime
    carries no ``hlo_category`` and no kernel name: a fusion is grouped
    by its kind (kOutput: a matmul with its epilogue; kLoop:
    elementwise; kInput: a reduction) and its name's stem, a custom call
    (a Pallas kernel is one) by its name's stem, which is the innermost
    ``jax.named_scope`` it was traced under, and its result's shape."""
    e = {"name": name}
    kind, stem = op_kind(e), re.sub(r"[.\d]+$", "", short_name(e))
    if kind == "fusion":
        m = re.search(r"kind=(k\w+)", name)
        return f"{stem} {m.group(1)}" if m else stem
    if kind == "custom-call":
        return f"custom-call {stem} -> {result_shape(e)}"
    return kind


def leaf_ops(events: list) -> list:
    return [e for p in device_planes(events) for e in ops_of(events, p)
            if not _is_container(e)]


def time_by_group(events: list) -> dict:
    n = max(1, len(device_planes(events)))
    out = {}
    for e in leaf_ops(events):
        g = op_group(e)
        out[g] = out.get(g, 0.0) + e["dur"] / n
    return out


def seconds_matching(events: list, pattern: str,
                     module: str | None = None) -> tuple:
    """``(seconds, calls)`` of the leaf ops whose HLO text matches
    ``pattern``, inside programs whose name matches ``module`` where
    given; averaged over device planes."""
    n = max(1, len(device_planes(events)))
    rx = re.compile(pattern)
    total, calls = 0.0, 0
    spans = module_spans(events, module) if module else None
    for e in leaf_ops(events):
        if not rx.search(e["name"]):
            continue
        if spans is not None and not _inside(e, spans):
            continue
        total += e["dur"]
        calls += 1
    return total / n, calls


def module_spans(events: list, pattern: str) -> dict:
    """Per device plane, the sorted ``(start, end)`` of the programs
    whose name matches ``pattern``."""
    rx = re.compile(pattern)
    out = {}
    for e in events:
        if e["line"] == MODULES_LINE and rx.search(e["name"]):
            out.setdefault(e["plane"], []).append(
                (e["start"], e["start"] + e["dur"]))
    return {p: sorted(v) for p, v in out.items()}


def _inside(e: dict, spans: dict) -> bool:
    sp = spans.get(e["plane"], ())
    i = bisect.bisect_right(sp, (e["start"], float("inf"))) - 1
    return i >= 0 and sp[i][0] <= e["start"] <= sp[i][1]


def module_seconds(events: list, pattern: str) -> tuple:
    """``(busy seconds, launches)`` of the programs matching
    ``pattern``: the union of the ops that ran inside them."""
    spans = module_spans(events, pattern)
    planes = device_planes(events)
    if not spans or not planes:
        return 0.0, 0
    busy = sum(union_seconds(
        (e["start"], e["start"] + e["dur"]) for e in ops_of(events, p)
        if _inside(e, spans)) for p in planes) / len(planes)
    return busy, sum(len(v) for v in spans.values()) // len(planes)


def idle_gaps(events: list, host_spans: list, top: int = 10,
              fallback: str = "engine host") -> list:
    """The device's idle time by what the host was doing: every gap
    between consecutive device ops of the first device plane is given
    to the benchmark's own span (``(name, start, end)``, same clock)
    that covers its middle, or to ``fallback``; returns the ``top``
    names by total seconds as ``[[name, seconds], ...]``."""
    planes = device_planes(events)
    if not planes:
        return []
    iv = sorted((e["start"], e["start"] + e["dur"])
                for e in ops_of(events, planes[0]))
    out, end = {}, None
    for s, e in iv:
        if end is not None and s > end:
            mid = 0.5 * (s + end)
            name = next((n for n, a, b in host_spans if a <= mid <= b),
                        fallback)
            out[name] = out.get(name, 0.0) + (s - end)
        end = e if end is None else max(end, e)
    return [[k, v] for k, v in sorted(out.items(), key=lambda kv: -kv[1])[:top]]


def breakdown(events: list, host_spans: list = (), top: int = 10) -> dict:
    groups = sorted(time_by_group(events).items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in groups[:top]],
            "idle_gaps": idle_gaps(events, list(host_spans), top)}


def inventory(events: list, top: int = 60) -> str:
    """What a trace holds, for reading one by hand."""
    lines = []
    for p in sorted({e["plane"] for e in events}):
        for ln in sorted({e["line"] for e in events if e["plane"] == p}):
            ev = [e for e in events if e["plane"] == p and e["line"] == ln]
            agg = {}
            for e in ev:
                a = agg.setdefault(e["name"], [0, 0.0, e["stats"]])
                a[0] += 1
                a[1] += e["dur"]
            lines.append(f"== {p} | {ln}: {len(ev)} events")
            for name, (c, d, st) in sorted(agg.items(),
                                           key=lambda kv: -kv[1][1])[:top]:
                lines.append(f"  {d * 1e3:10.3f} ms x{c:<6d} {name[:300]} "
                             f"{ {k: str(v)[:200] for k, v in st.items()} }")
    sig = {}
    mods = sorted({e["name"].split("(")[0] for e in events
                   if e["line"] == MODULES_LINE})
    spans = {m: module_spans(events, "^" + re.escape(m)) for m in mods}
    for e in leaf_ops(events):
        if op_kind(e) != "custom-call":
            continue
        where = next((m for m in mods if _inside(e, spans[m])), "?")
        shapes = re.findall(r"[a-z]+\d*\[[\d,]*\]", e["name"])
        tgt = re.search(r'custom_call_target="([^"]+)"', e["name"])
        key = (where, re.sub(r"[.\d]+$", "", short_name(e)),
               tgt.group(1) if tgt else "", " ".join(shapes[:8]))
        a = sig.setdefault(key, [0, 0.0])
        a[0] += 1
        a[1] += e["dur"]
    lines.append("== custom calls by program, scope, target and shapes")
    for key, (c, d) in sorted(sig.items(), key=lambda kv: -kv[1][1]):
        lines.append(f"  {d * 1e3:10.3f} ms x{c:<6d} {key}")
    return "\n".join(lines)
