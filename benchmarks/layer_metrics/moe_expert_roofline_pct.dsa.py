"""GLM-5.3-Flash's routed experts held here at decode, bandwidth-bound
on their weights: the least time to read the experts the live rows
touched (the step timeline's `moe.touched`: distinct experts hit among
the 36 held, summed over the four expert layers, of every decode step
inside the traced seconds; three matrices of 4096 x 2048 an expert,
bf16; `flops_glm5_next.py`) over HBM bandwidth, over the device time of
the grouped expert matmuls (`moe_ffn_in`, `moe_ffn_out`, the kernels
under `pt.moe.experts`) inside the decode programs there. A record that
also ran a prefill keeps the larger of the two programs' counts, the
prefill's: its decode step's experts are left out of the bytes (the
share reads low by that step's part, never high)."""
from benchmarks import flops_glm5_next as fl, xplane

KERNEL = r"^%?moe_ffn_(in|out)[.\d]* = "
MODULE = r"^jit_step\("
BOUND = "bandwidth"


def read(art):
    ev, win = art.get("events"), art.get("trace_window")
    if not ev or not win or not art.get("peaks"):
        return None
    secs, calls = xplane.seconds_matching(ev, KERNEL, module=MODULE)
    t0 = art["t0"]
    touched = sum(e["moe"]["touched"] for e in art.get("timeline", ())
                  if win[0] <= e["t_us"] * 1e-6 - t0 <= win[1]
                  and "touched" in e.get("moe", ())
                  and (e.get("programs") or {}).get("decode")
                  and not (e.get("programs") or {}).get("prefill"))
    if not calls or not touched or secs <= 0:
        return None
    byts = fl.expert_bytes(art["cell"].config, touched)
    return 100.0 * byts / art["peaks"]["hbm_bytes_per_s"] / secs
