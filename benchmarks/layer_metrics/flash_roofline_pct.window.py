"""Flash attention in prefill with grouped heads and a window: the
least time for the attention of the prompts prefilled in the traced
seconds at their true lengths, only the keys inside causal AND window
counted (the larger of FLOPs over peak and bytes over HBM bandwidth),
over the device time of the flash forward calls (`flash_fwd`,
`flash_fwd_single`) inside the prefill programs there."""
from benchmarks import flops_smallthinker as fl, xplane
from benchmarks.reduce import spans_named

KERNEL = r"^%?flash_fwd(_single)?[.\d]* = "
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
    cfg, pk = art["cell"].config, art["peaks"]
    least = sum(max(fl.flash_flops(cfg, int(s["prompt_len"])) / pk["flops"],
                    fl.flash_bytes(cfg, int(s["prompt_len"]))
                    / pk["hbm_bytes_per_s"]) for s in done)
    return 100.0 * least / secs
