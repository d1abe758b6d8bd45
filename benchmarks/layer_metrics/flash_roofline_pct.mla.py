"""Flash attention under latent attention's expanded prefill (20 heads
of 256, group 1, keys and values expanded from the latent),
compute-bound: the least time for the causal attention of the prompts
prefilled in the traced seconds at their TRUE lengths (causal keys x
heads x 2 x (qk + v), every layer; `flops_glm4_moe_lite.py`) over peak,
over the device time of the `flash_fwd*` calls inside the prefill
programs there."""
from benchmarks import flops_glm4_moe_lite as fl, xplane
from benchmarks.reduce import spans_named

KERNEL = r"^%?flash_fwd\w*?[.\d]* = "
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
    cfg = art["cell"].config
    work = sum(fl.flash_flops(cfg, int(s["prompt_len"])) for s in done)
    return 100.0 * work / art["peaks"]["flops"] / secs
