"""The chunked delta-rule scan at prefill: the least time for the
recurrence of the prompts prefilled in the traced seconds at their TRUE
lengths (the larger of its FLOPs over peak and, over HBM bandwidth, the
bytes of q, k, v, g, beta, o plus one state a sequence;
`flops_solar_open2.py`), over the device time of the `kda_*` calls
inside the prefill programs there."""
from benchmarks import flops_solar_open2 as fl, xplane
from benchmarks.reduce import spans_named

KERNEL = r"^%?kda_\w+?[.\d]* = "
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
    least = sum(max(fl.kda_prefill_flops(cfg, int(s["prompt_len"]))
                    / pk["flops"],
                    fl.kda_prefill_bytes(cfg, int(s["prompt_len"]))
                    / pk["hbm_bytes_per_s"]) for s in done)
    return 100.0 * least / secs
