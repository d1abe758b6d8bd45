"""Device: 1 - union of the device-op intervals over the traced window."""
from benchmarks import xplane


def read(art):
    return xplane.idle_pct(art.get("events"))
