"""Flash attention in training: the least time the chip could take for
the forward and backward attention of the steps traced (the larger of
FLOPs over peak and bytes over HBM bandwidth, both from shapes), over
the summed device time of the flash custom calls. Compute-bound at
S2048 x d128 (says `BOUND`)."""
from benchmarks import flops, xplane

# The trace names no kernel: inside the train step every Pallas call
# (`tpu_custom_call`) is a flash forward or backward call.
KERNEL = r'custom_call_target="tpu_custom_call"'
MODULE = r"^jit_multi_impl\("
BOUND = "compute"


def read(art):
    ev = art.get("events")
    if not ev or not art.get("peaks"):
        return None
    secs, calls = xplane.seconds_matching(ev, KERNEL, module=MODULE)
    _, steps = xplane.module_seconds(ev, MODULE)
    if not calls or not steps or secs <= 0:
        return None
    cell = art["cell"]
    cfg, tr = cell.config, cell.traffic
    b, s = int(tr["batch"]), int(tr["seq"]) 
    h, d, n = cfg["num_heads"], cfg["head_dim"], cfg["num_layers"]
    k = int(tr["steps_per_launch"])
    work = sum(flops.flash_flops(b, h, s, d, bw) for bw in (False, True))
    byts = sum(flops.flash_bytes(b, h, s, d, 2, bw) for bw in (False, True))
    least = max(work / art["peaks"]["flops"],
                byts / art["peaks"]["hbm_bytes_per_s"]) * n * k * steps
    return 100.0 * least / secs
