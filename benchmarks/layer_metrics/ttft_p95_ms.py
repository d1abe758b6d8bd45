"""What the client waits for a first token: the 95th percentile over all
requests due in the window of (first streamed token at the client - the
time the request was due to be sent); a failed request waits to the
drain limit. Per layer, not end to end: over the few hundred requests
of a window it spreads by more than any bound could hold (PERF.md)."""


def read(art):
    return art["end_to_end"].get("ttft_p95_ms")
