"""The whole training step's share of the chip's peak: the traced run's
tokens per second times the required FLOPs per token (forward and
backward, causal attention at the half that is needed, nothing
recomputed counted), over peak."""
from benchmarks import flops


def read(art):
    rate = art["end_to_end"].get("train_tokens_per_s")
    if not art.get("peaks") or not rate:
        return None
    cell = art["cell"]
    per_token = flops.train_flops_per_token(cell.config,
                                            int(cell.traffic["seq"]))
    return 100.0 * rate * per_token / art["peaks"]["flops"]
