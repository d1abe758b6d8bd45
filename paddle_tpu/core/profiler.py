"""Profiling: RecordEvent markers + jax.profiler integration.

TPU-native equivalent of the reference's profiler
(reference: paddle/fluid/platform/profiler.h:127 RecordEvent,
:213 EnableProfiler; device events via CUPTI device_tracer.h:43). Host
events are collected in-process; device-side tracing delegates to
``jax.profiler`` (XLA/TPU trace → TensorBoard), and every RecordEvent also
opens a ``jax.named_scope`` so markers show up inside XLA traces.

Host phases (``host_phase`` / ``HostPhases``) say what a host thread
does between two device programs. A phase is a
``jax.profiler.TraceAnnotation("pt.host.<name>")``: whenever a profiler
session runs (``jax.profiler.start_trace``, the server's ``profile``
op, the benchmark's ``--trace 1``) it is an event on the profiler's
host plane, in the same ``.xplane.pb`` and on the same clock as the
device's ``XLA Ops`` / ``XLA Modules`` lines; with no session it costs
a flag check. Given a ``HostPhases`` accumulator, the same enter and
exit also read ``time.monotonic`` once each, add the phase's own time
to it and keep the stretch itself, ``(name, start, end, kind)`` — the
engine's step timeline is fed from there (``host_us``: how long;
``phases``: when, which is what lays a phase against the device's idle
gaps once the trace's clock is tied to ``time.monotonic``), always on.
``RecordEvent`` holds the same annotation, so the marker API and the
engine reach the profiler by one path.
"""

from __future__ import annotations

import contextlib
import json
import threading
import time
from dataclasses import dataclass, field
from typing import List, Optional

import jax

from .flags import get_flag, set_flags


@dataclass
class _Event:
    name: str
    start_us: float
    end_us: float
    thread_id: int
    annotation: Optional[str] = None


@dataclass
class _ProfilerState:
    enabled: bool = False
    events: List[_Event] = field(default_factory=list)
    lock: threading.Lock = field(default_factory=threading.Lock)


_STATE = _ProfilerState()

HOST_PREFIX = "pt.host."
_TraceAnnotation = jax.profiler.TraceAnnotation
_monotonic = time.monotonic


class HostPhases:
    """One thread's per-step accumulator of host-phase time.

    ``us[name]`` holds the seconds spent in phase ``name`` since the
    owner last emptied it (``take()``), and ``segs`` the stretches
    themselves in the order they ended: ``(name, start, end, kind)`` on
    ``time.monotonic``, one for every stretch a phase ran, so a phase
    paused by an inner one leaves two. ``kind`` is what the caller gave
    ``phase(...)``: the program a ``launch`` dispatched or a ``wait``
    fetched, else ``None``. ``t`` is the newest stamp any phase read,
    so a caller that needs "now" at a phase boundary reads no clock of
    its own. Phases never overlap: entering one inside another pauses
    the outer (its annotation closes, its time stops) and resumes it
    on exit, so the sum over names is wall time spent inside phases,
    each second counted once, and the segments are disjoint. Not
    thread-safe: one accumulator belongs to one thread at a time (the
    engine's)."""

    __slots__ = ("us", "segs", "t", "_open")

    def __init__(self):
        self.us: dict = {}
        self.segs: list = []
        self.t = 0.0
        self._open: Optional["_Phase"] = None

    def phase(self, name: str, kind: Optional[str] = None) -> "_Phase":
        """``with acc.phase(name):`` — ``host_phase(name)`` whose own
        time is also added to ``acc.us[name]`` and whose stretches go
        to ``acc.segs``, tagged ``kind``."""
        return _Phase(self, name, kind)

    def take(self) -> tuple:
        """The accumulated ``({phase: seconds}, [segments])``, and
        start afresh."""
        out, self.us, self.segs = (self.us, self.segs), {}, []
        return out


class _Phase:
    """One ``with acc.phase(name, kind):`` block. ``t0`` / ``t1`` are
    its entry and exit on ``time.monotonic`` (for callers that feed an
    older counter or a span from the same stamps); every stretch it
    ran goes to ``acc.segs`` from the stamps it reads anyway. The
    annotation's
    own enter and exit fall inside the stamps, so what a phase costs
    is counted as that phase's."""

    __slots__ = ("name", "kind", "t0", "t1", "_acc", "_outer", "_since",
                 "_ann")

    def __init__(self, acc: HostPhases, name: str,
                 kind: Optional[str] = None):
        self._acc = acc
        self.name = name
        self.kind = kind

    def _run(self, now: float) -> None:
        """Start, or resume after an inner phase: a new annotation."""
        self._since = now
        self._ann = ann = _TraceAnnotation(HOST_PREFIX + self.name)
        ann.__enter__()

    def _halt(self) -> float:
        """Stop, or pause for an inner phase; returns the stamp, which
        the phase that runs next starts from (one clock read for both)."""
        self._ann.__exit__(None, None, None)
        acc = self._acc
        acc.t = now = _monotonic()
        acc.us[self.name] = acc.us.get(self.name, 0.0) + (now - self._since)
        acc.segs.append((self.name, self._since, now, self.kind))
        return now

    def __enter__(self) -> "_Phase":
        acc = self._acc
        outer = self._outer = acc._open
        if outer is not None:
            now = outer._halt()
        else:
            acc.t = now = _monotonic()
        self.t0 = now
        acc._open = self
        self._run(now)
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = now = self._halt()
        outer = self._acc._open = self._outer
        if outer is not None:
            outer._run(now)
        return False


def host_phase(name: str):
    """The profiler annotation ``pt.host.<name>`` alone, for a phase
    whose thread keeps no timeline (``HostPhases.phase`` is the one
    that also counts)."""
    return _TraceAnnotation(HOST_PREFIX + name)


class RecordEvent:
    """RAII host-event marker; nests a jax.named_scope for device
    traces and a profiler annotation of the same name for the host
    plane (see ``host_phase``)."""

    def __init__(self, name: str, annotation: Optional[str] = None):
        self.name = name
        self.annotation = annotation
        self._scope = None
        self._ann = None
        self._start = 0.0

    def __enter__(self):
        self._start = time.perf_counter() * 1e6
        self._ann = _TraceAnnotation(self.name)
        self._ann.__enter__()
        self._scope = jax.named_scope(self.name)
        self._scope.__enter__()
        return self

    def __exit__(self, *exc):
        self._scope.__exit__(*exc)
        self._ann.__exit__(*exc)
        if _STATE.enabled or get_flag("profiler_enabled"):
            evt = _Event(self.name, self._start, time.perf_counter() * 1e6,
                         threading.get_ident(), self.annotation)
            with _STATE.lock:
                _STATE.events.append(evt)
        return False


def enable_profiler() -> None:
    set_flags({"profiler_enabled": True})
    _STATE.enabled = True
    with _STATE.lock:
        _STATE.events.clear()


def disable_profiler() -> None:
    set_flags({"profiler_enabled": False})
    _STATE.enabled = False


def reset_profiler() -> None:
    with _STATE.lock:
        _STATE.events.clear()


def profiler_events() -> List[_Event]:
    with _STATE.lock:
        return list(_STATE.events)


def export_chrome_trace(path: str) -> None:
    """Write collected host events as a chrome://tracing JSON file."""
    with _STATE.lock:
        events = list(_STATE.events)
    trace = {"traceEvents": [
        {"name": e.name, "ph": "X", "ts": e.start_us,
         "dur": max(e.end_us - e.start_us, 0.01), "pid": 0,
         "tid": e.thread_id % 1_000_000,
         "args": ({"annotation": e.annotation} if e.annotation else {})}
        for e in events]}
    with open(path, "w") as f:
        json.dump(trace, f)


@contextlib.contextmanager
def profiler_guard(trace_dir: Optional[str] = None):
    """Context manager enabling host events and optional XLA device trace."""
    enable_profiler()
    if trace_dir is not None:
        jax.profiler.start_trace(trace_dir)
    try:
        yield
    finally:
        if trace_dir is not None:
            jax.profiler.stop_trace()
        disable_profiler()
