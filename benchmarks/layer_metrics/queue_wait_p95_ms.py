"""Scheduler: the `queue` span of every request (SpanTracer at sample
1.0), from submit to the committed admission."""
from benchmarks.reduce import percentile, spans_named


def read(art):
    waits = [s["t1"] - s["t0"] for s in spans_named(art.get("traces", ()),
                                                    "queue")]
    return percentile(waits, 95) * 1e3 if waits else None
