"""What every driver needs: the device as JAX reports it, the refusal
to run without the chips, the profiler window, the peak of memory."""

from __future__ import annotations

import time


def device_block(chips: int) -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs) if chips > 1 else 1}


def require_chips(chips: int, rehearsal: bool) -> None:
    """No accelerator, or fewer chips than the cell asks for: an error,
    never a fallback. A rehearsal on the CPU is asked for explicitly."""
    import jax
    devs = jax.devices()
    if rehearsal:
        return
    if devs[0].platform != "tpu" or len(devs) < chips:
        raise SystemExit(
            f"this cell needs {chips} TPU chip(s); JAX found "
            f"{len(devs)} x {devs[0].platform}:{devs[0].device_kind}")


def open_cell(workload: str, rehearsal: bool):
    """What every entry point does first: find the cell, pin a rehearsal
    to the CPU, refuse to run without the chips, and turn on the
    program's compile cache ($JAX_COMPILATION_CACHE_DIR, else
    <checkout>/.jax_cache)."""
    from benchmarks import spec
    cell = spec.Cell(workload, rehearsal=rehearsal)
    import jax
    if rehearsal:
        jax.config.update("jax_platforms", "cpu")
    require_chips(cell.chips, rehearsal)
    from paddle_tpu.core.compile_cache import enable_compile_cache
    enable_compile_cache()
    return cell


def memory_peak_bytes() -> int:
    """Peak on the fullest chip, as the backend reports it (the CPU
    reports nothing: 0)."""
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


class Compiles:
    """Counts backend compiles, so that one inside the measured window
    shows (stderr, not a metric)."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event.endswith("backend_compile_duration"):
            self.n += 1


class Marks:
    """Where a run's wall time goes: ``marks(name)`` stamps the end of
    a phase, ``since()`` gives each phase's seconds (stderr notes, not
    a metric)."""

    def __init__(self):
        self.t = [("start", time.monotonic())]

    def __call__(self, name: str) -> None:
        self.t.append((name, time.monotonic()))

    def since(self) -> dict:
        return {b[0]: round(b[1] - a[1], 2)
                for a, b in zip(self.t, self.t[1:])}


class GcPauses:
    """Times the interpreter's garbage collections. Building and tracing
    a 24-layer model leaves millions of objects, and one full collection
    over them stalls the thread that launches: ``settle()`` collects once
    and freezes what set-up made, so that a collection inside the window
    scans only what the window made. ``notes()`` gives the longest pause
    before and after (stderr notes, not a metric)."""

    def __init__(self):
        import gc
        self._t, self.before, self.after = 0.0, [], []
        self._into = self.before
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
        elif self._into is not None:
            self._into.append((round((time.monotonic() - self._t) * 1e3, 1),
                               info["generation"]))

    def settle(self) -> None:
        import gc
        gc.collect()
        gc.freeze()
        self._into = self.after

    def release(self) -> None:
        """After the window: what was frozen can be collected again, so
        that the program's state is freed before the reference runs.
        Collections from here on are the check's, not the window's."""
        import gc
        self._into = None
        gc.unfreeze()

    def notes(self) -> dict:
        return {"setup_max_ms_gen": max(self.before, default=None),
                "window_max_ms_gen": max(self.after, default=None),
                "window_collections": len(self.after)}


def trace(work) -> tuple:
    """Trace whatever runs on the device while ``work()`` runs, into a
    directory that is removed again; returns the reduced events and the
    traced window on the host's monotonic clock."""
    import shutil
    import tempfile
    import jax
    from benchmarks import xplane
    tdir = tempfile.mkdtemp(prefix="pt-bench-trace-")
    try:
        jax.profiler.start_trace(tdir)
        t0 = time.monotonic()
        try:
            work()
        finally:
            t1 = time.monotonic()
            jax.profiler.stop_trace()
        return xplane.load_events(xplane.find_xplane(tdir)), (t0, t1)
    finally:
        shutil.rmtree(tdir, ignore_errors=True)


def norm_gap(prog: dict, ref: dict, keep=None) -> tuple:
    """Worst leaf of |prog norm - ref norm| over max(ref's norm of that
    leaf, the median leaf's): ``(gap, leaf)``. The gap between norms,
    not the norm of a difference."""
    names = [k for k in ref if keep is None or k in keep]
    med = sorted(ref[k] for k in names)[len(names) // 2]
    worst, leaf = 0.0, None
    for k in names:
        g = abs(prog[k] - ref[k]) / max(ref[k], med, 1e-30)
        if g > worst or leaf is None:
            worst, leaf = g, k
    return worst, leaf


def sample_index(size: int, n: int = 16384):
    """The same ``n`` places of a flattened leaf of ``size`` elements
    for the program and the reference: fixed, spread over the leaf."""
    import numpy as np
    return np.random.default_rng(size).integers(0, size, min(n, size))


def diff_gap(prog: dict, ref: dict) -> tuple:
    """Worst leaf of |prog - ref| over max(|ref| of that leaf, the
    median leaf's), on the sampled places: ``(gap, leaf)``. Sees
    rounding that is unbiased, which a gap between norms cannot."""
    import numpy as np
    norms = {k: float(np.linalg.norm(v)) for k, v in ref.items()}
    med = sorted(norms.values())[len(norms) // 2]
    worst, leaf = 0.0, None
    for k, r in ref.items():
        d = float(np.linalg.norm(np.asarray(prog[k], np.float64)
                                 - np.asarray(r, np.float64)))
        g = d / max(norms[k], med, 1e-30)
        if g > worst or leaf is None:
            worst, leaf = g, k
    return worst, leaf


def judge(numbers: dict, limits: dict) -> tuple:
    """``numbers``: name -> value. Every number that has a limit is held
    to it; one without is printed as not compared. Returns ``(correct,
    rows)`` with rows ``{name: {"value", "limit"}}``."""
    rows, ok = {}, True
    for name, value in numbers.items():
        limit = limits.get(name)
        rows[name] = {"value": value, "limit": limit}
        if limit is not None and not (value <= limit):  # NaN fails
            ok = False
    return ok, rows
