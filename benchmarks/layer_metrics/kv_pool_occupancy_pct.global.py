"""Cache manager: the median share of the allocator's pages in use (the
pages of the layers that keep every position; `kv_pages.global` of the
step timeline, a layer's worth) over the window's records. The window
layers' rings are bounded per sequence and are not the allocator's."""
from benchmarks.reduce import percentile


def read(art):
    cell = art.get("cell")
    used = [e["kv_pages"]["global"] for e in art.get("timeline", ())
            if "kv_pages" in e]
    if not used or cell is None:
        return None
    return 100.0 * percentile(used, 50) / int(
        cell.config["engine"]["num_pages"])
