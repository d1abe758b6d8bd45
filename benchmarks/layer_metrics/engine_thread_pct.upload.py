"""Engine step: share of the engine thread's wall time building device
arguments (`upload`: `_fresh_state`, every `jnp.asarray` of table,
lengths, ids and current tokens)."""
from benchmarks import host_phases


def read(art):
    return host_phases.share_pct(art.get("timeline"), "upload")
