"""Engine step: share of the window's decode steps that were launched
while the tokens of the step before them were still unfetched
(`decode_ahead` 1 in the step timeline's record: the device went from
one decode program to the next with no gap, and the fetch, the emit
loop, the commit and the server's loop passed under device time).
Counted over the records of steps that left a slot decoding and carry
the key; a program that records no `decode_ahead` gives nothing."""


def read(art):
    ahead = [e["decode_ahead"] for e in art.get("timeline", ())
             if e.get("slots_decoding", 0) > 0 and "decode_ahead" in e]
    return 100.0 * ahead.count(1) / len(ahead) if ahead else None
