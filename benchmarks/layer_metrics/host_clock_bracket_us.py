"""Device: width of the bracket of offsets between `time.monotonic` and
the trace's clock that keep every traced program between the start of
its dispatch and the end of its fetch (`benchmarks/host_clock.py`). The
`device_idle_pct.serve.*` shares are read at its middle, so no stretch
of the host is off by more than half of this. Nothing to pair: nothing
returned."""
from benchmarks import host_clock


def read(art):
    return host_clock.read(art, "bracket_us")
