"""The whole serving step's share of the chip's peak, for learned sparse
attention over latent pages beside KDA state layers and routed experts
under a residual of four streams: required FLOPs
(`flops_glm5_next.py`: the mHC projections, the matmuls, the
recurrence's 6 d_k d_v a head a token, the indexer's scored blocks, the
SELECTED keys in the form with fewer operations — expanded for a
prompt's token, absorbed for a decoded one —, a token's own picks that
land on experts held and the shared expert, the head at sampled
positions) of every prompt prefilled (first token inside the window)
and every token decoded inside the window, over the window, over peak:
the share of the whole step that bounds any later claim in this
cell."""
from benchmarks import flops_glm5_next as fl
from benchmarks.reduce import tokens_in


def read(art):
    if not art.get("peaks") or not art.get("log"):
        return None
    cfg, secs = art["cell"].config, art["window_s"]
    toks = tokens_in(art["log"], 0.0, secs)
    if not toks:
        return None
    work = sum(fl.prefill_flops(cfg, r["prompt_len"])
               for r, j in toks if j == 0)
    work += fl.decode_flops(
        cfg, [r["prompt_len"] + j + 1 for r, j in toks if j > 0])
    return 100.0 * work / secs / art["peaks"]["flops"]
