"""Flash attention in prefill: the least time for the causal attention
of the prompts prefilled in the traced seconds at their true lengths
(the larger of FLOPs over peak and bytes over HBM bandwidth), over the
device time of the flash forward calls inside the prefill programs."""
from benchmarks import flops, xplane
from benchmarks.reduce import spans_named

# The trace names no kernel: a Pallas call is a `tpu_custom_call`, named
# after the scope it was traced under. Inside a prefill program the ones
# whose (first) result is bf16 are the flash forward calls, which return
# the output and the log-sum-exp rows (the other, with an s32 result, is
# the sampling kernel).
KERNEL = r'^\S+ = \(?bf16\[[^=]* custom-call\(.*custom_call_target="tpu_custom_call"'
MODULE = r"^jit_prefill\("


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
    h, d, n = cfg["num_heads"], cfg["head_dim"], cfg["num_layers"]
    least = sum(max(
        flops.flash_flops(1, h, int(s["prompt_len"]), d, False)
        / art["peaks"]["flops"],
        flops.flash_bytes(1, h, int(s["prompt_len"]), d, 2, False)
        / art["peaks"]["hbm_bytes_per_s"]) for s in done) * n
    return 100.0 * least / secs
