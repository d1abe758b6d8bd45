"""Paged decode attention over two kinds of cache: the least time to
read the K and V pages the decoded tokens see (every page of the
context in a global layer, only the pages the window spans in a window
layer; grouped heads, so a page is `num_key_value_heads` wide) over HBM
bandwidth, over the device time of the `paged_decode_grouped` calls in
the decode programs of the traced seconds."""
from benchmarks import flops_smallthinker as fl, xplane
from benchmarks.reduce import tokens_in

KERNEL = r"^%?paged_decode_grouped[.\d]* = "
MODULE = r"^jit_step\("
BOUND = "bandwidth"


def read(art):
    ev, win = art.get("events"), art.get("trace_window")
    if not ev or not win or not art.get("peaks"):
        return None
    secs, calls = xplane.seconds_matching(ev, KERNEL, module=MODULE)
    toks = tokens_in(art.get("log", ()), win[0], win[1], skip_first=True)
    if not calls or not toks or secs <= 0:
        return None
    byts = fl.paged_decode_bytes(
        art["cell"].config, [r["prompt_len"] + j + 1 for r, j in toks])
    return 100.0 * byts / art["peaks"]["hbm_bytes_per_s"] / secs
