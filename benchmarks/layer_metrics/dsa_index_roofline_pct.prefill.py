"""The lightning indexer of a prompt, compute-bound: the least time for
its scores (`2 Hi Di` a query a complete block before its own, every
DSA layer; `flops_glm5_next.py`) of the prompts prefilled wholly inside
the traced seconds, at their TRUE lengths, over peak, over the device
time of the `dsa_index_prefill` calls inside the prefill programs
there. A prompt that was partly prefilled outside the traced seconds
adds kernel time and no work: the share is never read high."""
from benchmarks import flops_glm5_next as fl, xplane
from benchmarks.reduce import spans_named

KERNEL = r"^%?dsa_index_prefill[.\d]* = "
MODULE = r"^jit_prefill\("
BOUND = "compute"


def read(art):
    ev, win = art.get("events"), art.get("trace_window")
    if not ev or not win or not art.get("peaks"):
        return None
    secs, calls = xplane.seconds_matching(ev, KERNEL, module=MODULE)
    t0 = art["t0"]
    done = [s for s in spans_named(art.get("traces", ()), "prefill")
            if win[0] <= s["t0"] - t0 and s["t1"] - t0 <= win[1]]
    if not calls or not done or secs <= 0:
        return None
    cfg = art["cell"].config
    work = sum(fl.index_flops(cfg, 0, int(s["prompt_len"])) for s in done)
    return 100.0 * work / art["peaks"]["flops"] / secs
