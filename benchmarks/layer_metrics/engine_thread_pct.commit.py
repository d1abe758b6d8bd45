"""Engine step: share of the engine thread's wall time in `_tl_commit`
(`commit_us`: page accounting for every slot, `occupancy()`, the
record): what the always-on step timeline costs."""
from benchmarks import host_phases


def read(art):
    return host_phases.share_pct(art.get("timeline"), "commit")
