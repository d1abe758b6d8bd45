"""The whole serving step's share of the chip's peak: model FLOPs of
every prompt prefilled (first token inside the window) and every token
decoded inside the window, over the window, over peak."""
from benchmarks import flops
from benchmarks.reduce import tokens_in


def read(art):
    if not art.get("peaks") or not art.get("log"):
        return None
    cfg, secs = art["cell"].config, art["window_s"]
    toks = tokens_in(art["log"], 0.0, secs)
    if not toks:
        return None
    work = sum(flops.prefill_flops(cfg, r["prompt_len"])
               for r, j in toks if j == 0)
    work += flops.decode_flops(
        cfg, [r["prompt_len"] + j + 1 for r, j in toks if j > 0])
    return 100.0 * work / secs / art["peaks"]["flops"]
