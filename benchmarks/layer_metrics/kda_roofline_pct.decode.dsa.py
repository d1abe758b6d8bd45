"""GLM-5.3-Flash's delta-rule single-token step, bandwidth-bound on the
states: the least time to read and write the states of the live
sequences (the step timeline's `state_slots` of every record inside the
traced seconds that launched a decode program, the KDA layers held,
float32; `flops_glm5_next.py`) over HBM bandwidth, over the device time
of the `kda_decode` calls inside the decode programs there."""
from benchmarks import flops_glm5_next as fl, xplane

KERNEL = r"^%?kda_decode[.\d]* = "
MODULE = r"^jit_step\("
BOUND = "bandwidth"


def read(art):
    ev, win = art.get("events"), art.get("trace_window")
    if not ev or not win or not art.get("peaks"):
        return None
    secs, calls = xplane.seconds_matching(ev, KERNEL, module=MODULE)
    t0 = art["t0"]
    slots = sum(e["state_slots"] for e in art.get("timeline", ())
                if win[0] <= e["t_us"] * 1e-6 - t0 <= win[1]
                and "state_slots" in e
                and (e.get("programs") or {}).get("decode"))
    if not calls or not slots or secs <= 0:
        return None
    byts = fl.kda_decode_bytes(art["cell"].config, slots)
    return 100.0 * byts / art["peaks"]["hbm_bytes_per_s"] / secs
