"""Engine step: share of the engine thread's wall time blocked on a device
result (`wait`: `int(nxt)` after a prefill, the two fetches after a
decode). The engine is synchronous, so the device works only between a
launch and the end of the wait that follows: higher is better, against
the device's busy share."""
from benchmarks import host_phases


def read(art):
    return host_phases.share_pct(art.get("timeline"), "wait")
