"""Engine step: share of the window's decode steps that fed the decode
program its own outputs and uploaded nothing (`decode_h2d` 0 in the
step timeline's record: the host wrote no slot since the step before).
Counted over the records of steps that left a slot decoding and carry
the key; a program that records no `decode_h2d` gives nothing."""


def read(art):
    h2d = [e["decode_h2d"] for e in art.get("timeline", ())
           if e.get("slots_decoding", 0) > 0 and "decode_h2d" in e]
    return 100.0 * h2d.count(0) / len(h2d) if h2d else None
