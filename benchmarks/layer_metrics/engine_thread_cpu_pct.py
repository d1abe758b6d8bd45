"""Engine step: the engine thread's CPU time (`time.thread_time_ns`) over
its wall time. Read against 100 - `engine_thread_pct.wait`: equal, the
host's part is Python running; lower, the thread waits for something no
phase names (the interpreter lock behind the connection threads)."""
from benchmarks import host_phases


def read(art):
    return host_phases.share_pct(art.get("timeline"), "cpu")
