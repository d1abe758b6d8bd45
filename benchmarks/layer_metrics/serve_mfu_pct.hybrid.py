"""The whole serving step's share of the chip's peak, for the hybrid of
state layers, one softmax layer and routed experts: required FLOPs
(`flops_solar_open2.py`: a token's own picks that land on experts held,
the shared expert, causal keys in the softmax layer, the recurrence's 6
d_k d_v a head a token, the head at sampled positions) of every prompt
prefilled (first token inside the window) and every token decoded
inside the window, over the window, over peak."""
from benchmarks import flops_solar_open2 as fl
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
