"""Cache manager: the median share of the state pool's rows that hold a
live sequence (`state_slots` of the step timeline over the engine's
slots) over the window's records. A row is a whole sequence's memory in
the state layers, whatever its length."""
from benchmarks.reduce import percentile


def read(art):
    cell = art.get("cell")
    used = [e["state_slots"] for e in art.get("timeline", ())
            if "state_slots" in e]
    if not used or cell is None:
        return None
    return 100.0 * percentile(used, 50) / int(
        cell.config["engine"]["num_slots"])
