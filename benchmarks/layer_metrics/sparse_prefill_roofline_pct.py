"""A prompt's sparse attention, compute-bound: the least time for the
attention of the prompts prefilled wholly inside the traced seconds, at
their TRUE lengths, over the positions each query selects (`2 (qk + v)`
a head a selected key, the expanded form, every DSA layer;
`flops_glm5_next.py`) over peak, over the device time of the
`sparse_prefill_latent` calls inside the prefill programs there. The
kernel computes dense tiles in the absorbed form (twice the expanded
form's operations a pair) and masks what a query did not select, so
the share shows what the selection saves that the kernel does not. A
prompt that was partly prefilled outside the traced seconds adds
kernel time and no work: the share is never read high."""
from benchmarks import flops_glm5_next as fl, xplane
from benchmarks.reduce import spans_named

KERNEL = r"^%?sparse_prefill_latent[.\d]* = "
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
    work = sum(fl.sparse_prefill_flops(cfg, int(s["prompt_len"]))
               for s in done)
    return 100.0 * work / art["peaks"]["flops"] / secs
