"""Engine step: the step timeline's `ms` (host clock around a step that
ends in a token fetch) over the window's steps that decoded."""
from statistics import median


def read(art):
    ms = [e["ms"] for e in art.get("timeline", ())
          if e.get("slots_decoding", 0) > 0]
    return median(ms) if ms else None
