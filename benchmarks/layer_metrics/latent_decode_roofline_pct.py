"""The absorbed latent decode, bandwidth-bound on the cached rows: the
least time to read, for every token decoded inside the traced seconds,
its context's rows in every layer at their REQUIRED width (`rank + rope`
values, 1,152 B in bf16 at the published widths, whatever the pool pads
a row to; `flops_glm4_moe_lite.py`) over HBM bandwidth, over the device
time of the `paged_decode_latent` calls inside the decode programs
there. Its 43.5 kFLOP a position a layer (20 heads x 2 x (576 + 512))
sit at 38 FLOP/B, under the chip's 240: the bytes bound it."""
from benchmarks import flops_glm4_moe_lite as fl, xplane
from benchmarks.reduce import tokens_in

KERNEL = r"^%?paged_decode_latent[.\d]* = "
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
    byts = fl.latent_decode_bytes(
        art["cell"].config, [r["prompt_len"] + j + 1 for r, j in toks])
    return 100.0 * byts / art["peaks"]["hbm_bytes_per_s"] / secs
