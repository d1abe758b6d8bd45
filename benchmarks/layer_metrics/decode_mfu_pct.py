"""Model FLOPs of the tokens decoded in the traced seconds (every
streamed token but a request's first, each attending its whole
context), over the device time of the decode programs there, over
peak."""
from benchmarks import flops, xplane
from benchmarks.reduce import tokens_in

MODULE = r"^jit_step\("  # the engine's decode program, as the trace names it


def read(art):
    ev, win = art.get("events"), art.get("trace_window")
    if not ev or not win or not art.get("peaks"):
        return None
    busy, launches = xplane.module_seconds(ev, MODULE)
    toks = tokens_in(art.get("log", ()), win[0], win[1], skip_first=True)
    if not launches or not toks or busy <= 0:
        return None
    cfg = art["cell"].config
    work = flops.decode_flops(cfg, [r["prompt_len"] + j + 1 for r, j in toks])
    return 100.0 * work / busy / art["peaks"]["flops"]
