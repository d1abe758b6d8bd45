"""Engine step: share of the traced window the device idled, outside every
program, while the engine thread was in `upload`: building a
program's device arguments (a prefill's row, lengths and ids; the decode
step's packed mirrors after a host write).
Share of the TRACED WINDOW (the denominator of `device_idle_pct.serve`);
read from the record's `phases` and the first device plane's ops and
programs, the host's clock laid on the trace's by
`benchmarks/host_clock.py`; off by at most what `host_clock_bracket_us`
allows. Nothing to pair (an older program's records carry no `phases`,
no single shift, an empty bracket): nothing returned."""
from benchmarks import host_clock


def read(art):
    return host_clock.read(art, "upload")
