"""Model FLOPs of the prompt tokens prefilled in the traced seconds,
over the device time of the prefill programs there (the union of the
ops inside programs the trace calls `jit_prefill`), over peak.
FLOPs from the prompts' true lengths (`prefill` spans of the request
trees that end inside the traced seconds), not the padded buckets."""
from benchmarks import flops, xplane
from benchmarks.reduce import spans_named

MODULE = r"^jit_prefill\("  # one program per prompt bucket


def read(art):
    ev, win = art.get("events"), art.get("trace_window")
    if not ev or not win or not art.get("peaks"):
        return None
    busy, launches = xplane.module_seconds(ev, MODULE)
    t0 = art["t0"]
    done = [s for s in spans_named(art.get("traces", ()), "prefill")
            if win[0] <= s["t1"] - t0 <= win[1]]
    if not launches or not done or busy <= 0:
        return None
    cfg = art["cell"].config
    work = sum(flops.prefill_flops(cfg, int(s["prompt_len"])) for s in done)
    return 100.0 * work / busy / art["peaks"]["flops"]
