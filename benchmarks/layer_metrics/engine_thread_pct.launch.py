"""Engine step: share of the engine thread's wall time inside jit calls
(`launch`: the dispatch of every program; the call returns futures)."""
from benchmarks import host_phases


def read(art):
    return host_phases.share_pct(art.get("timeline"), "launch")
