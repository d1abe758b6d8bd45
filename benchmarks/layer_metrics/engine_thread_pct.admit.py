"""Scheduler and cache manager: share of the engine thread's wall time in
`admit` (deadline and stall sweeps, scheduler pick, prefix-cache match
and restore, page allocation, table row), up to a prefill's argument
build."""
from benchmarks import host_phases


def read(art):
    return host_phases.share_pct(art.get("timeline"), "admit")
