"""Paged decode attention: the least time to read the K and V pages
that the decoded tokens' contexts span (bytes over HBM bandwidth: the
kernel is bandwidth-bound, one query row per head), over the device
time of the paged decode calls in the traced seconds."""
from benchmarks import flops, xplane
from benchmarks.reduce import tokens_in

MODULE = r"^jit_step\("
BOUND = "bandwidth"


def kernel_pattern(cfg: dict) -> str:
    """The trace names no kernel: the paged decode call is the custom
    call that takes the K/V pools, `[pages + 1, page, heads, head_dim]`."""
    e = cfg["engine"]
    pool = (f"{int(e['num_pages']) + 1},{int(e['page_size'])},"
            f"{cfg['num_heads']},{cfg['head_dim']}")
    return r"custom-call\(.*\[" + pool + r"\]"


def read(art):
    ev, win = art.get("events"), art.get("trace_window")
    if not ev or not win or not art.get("peaks"):
        return None
    secs, calls = xplane.seconds_matching(
        ev, kernel_pattern(art["cell"].config), module=MODULE)
    toks = tokens_in(art.get("log", ()), win[0], win[1], skip_first=True)
    if not calls or not toks or secs <= 0:
        return None
    cell = art["cell"]
    cfg = cell.config
    byts = flops.paged_decode_bytes(
        [r["prompt_len"] + j + 1 for r, j in toks],
        int(cfg["engine"]["page_size"]), cfg["num_heads"], cfg["head_dim"],
        2, cfg["num_layers"])
    return 100.0 * byts / art["peaks"]["hbm_bytes_per_s"] / secs
