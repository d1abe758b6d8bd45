"""Engine step: share of the engine thread's wall time in the per-slot
loop after a fetch (`emit`: token append, `on_token` into the outbox,
finish, eviction, page free, prefix-cache insert)."""
from benchmarks import host_phases


def read(art):
    return host_phases.share_pct(art.get("timeline"), "emit")
