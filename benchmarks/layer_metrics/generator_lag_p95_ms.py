"""How late the load generator sent: actual send - due time, its own
clock. A starved generator must not read as a fast server."""
from benchmarks.reduce import percentile


def read(art):
    lag = [r["sent"] - r["due"] for r in art.get("log", ())
           if r.get("sent") is not None]
    return percentile(lag, 95) * 1e3 if lag else None
