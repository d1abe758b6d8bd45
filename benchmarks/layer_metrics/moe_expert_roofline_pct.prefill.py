"""The expert layer at prefill, compute-bound: the expert FLOPs of the
prompts prefilled in the traced seconds at their true lengths (each
token's own picks, three matmuls an expert) over peak, over the device
time of the grouped expert matmuls (`moe_ffn_in`, `moe_ffn_out`) inside
the prefill programs there."""
from benchmarks import flops_smallthinker as fl, xplane
from benchmarks.reduce import spans_named

KERNEL = r"^%?moe_ffn_(in|out)[.\d]* = "
MODULE = r"^jit_prefill\("
BOUND = "compute"


def read(art):
    ev, win = art.get("events"), art.get("trace_window")
    if not ev or not win or not art.get("peaks"):
        return None
    secs, calls = xplane.seconds_matching(ev, KERNEL, module=MODULE)
    t0 = art["t0"]
    done = [s for s in spans_named(art.get("traces", ()), "prefill")
            if win[0] <= s["t1"] - t0 <= win[1]]
    if not calls or not done or secs <= 0:
        return None
    work = fl.expert_flops(art["cell"].config,
                           sum(int(s["prompt_len"]) for s in done))
    return 100.0 * work / art["peaks"]["flops"] / secs
