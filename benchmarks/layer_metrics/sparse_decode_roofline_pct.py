"""The sparse latent walk of a decoded token, bandwidth-bound on the
cached rows: the least time to read, for every token decoded inside the
traced seconds, the latent rows of the positions its indexer selected
(its top `index_topk` blocks of `index_kpool` and its own block, or its
whole context while that is shorter; `rank` values, 1,024 B in bf16,
every DSA layer; `flops_glm5_next.py`) over HBM bandwidth, over the
device time of the `sparse_decode_latent` calls inside the decode
programs there. The walk reads every page of the context under a mask
(ops/pallas/sparse_attention.py), so past 8,192 positions it reads
more than it must and the share shows by how much. The indexer's
scores at decode run as XLA ops, not in this kernel: their pooled keys'
bytes and their time are both left out."""
from benchmarks import flops_glm5_next as fl, xplane
from benchmarks.reduce import tokens_in

KERNEL = r"^%?sparse_decode_latent[.\d]* = "
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
    byts = fl.sparse_decode_bytes(
        art["cell"].config, [r["prompt_len"] + j + 1 for r, j in toks])
    return 100.0 * byts / art["peaks"]["hbm_bytes_per_s"] / secs
