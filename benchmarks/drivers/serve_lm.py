"""The serving driver of every architecture but the first: the traffic
``kind`` ``serve_lm``. As ``drivers/serve.py`` (whose ``rpc``, ``warm``,
``drive``, ``end_to_end``, ``sample`` and ``backlog`` it uses as they
are), but the model is found by the configuration's ``builder`` key:
``builders/<name>.py`` gives ``build(cfg, seed)``, the program's model
loaded with the benchmark's weights, and ``make_weights(cfg, seed)``,
the same weights for the plain reference.

A routed model's comparison. Routing is discontinuous: where the last
pick's router logit and the first left-out expert's lie closer than the
mix's ``router_margin_delta`` in any layer of the reference, activations
rounded to bf16 may pick the other expert legitimately. Those positions
are left out of ``served_gap_mean`` and ``served_gap``; their share of
the sampled positions is a number of its own, ``near_tie_share``, with a
limit of its own (a program that routes at random would show it by the
gap of the positions kept; a reference whose margins collapse shows
here). A configuration whose reference returns no ``margins`` is
compared on every position.

By hand, on the chip (their output is for PERF.md; the numbers a cell
runs at are in its traffic file):

    python benchmarks/drivers/serve_lm.py --workload <cell> --rates 1,2,3 --seconds 30
    python benchmarks/drivers/serve_lm.py --workload <cell> --readings --seed <n>

The first is the knee sweep (``sweep.py`` is bound to ``serve``): one
server, warmed once, a window at each rate. The second gives the
readings for the limits in one process: the program, the
lower-precision control and each fault the reference can plant, at the
sampled positions of one run, every margin and gap written to
``chiprun_out/`` for choosing ``router_margin_delta``.
"""

from __future__ import annotations

import gc
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if __name__ == "__main__":
    sys.path.insert(0, ROOT)  # first: this checkout's benchmarks/

from benchmarks import common, traffic as gen  # noqa: E402
from benchmarks.drivers import serve  # noqa: E402


def builder_of(cell):
    return cell.load_module("builders", cell.config["builder"])


def build_server(cell, seed: int, traced: bool):
    from paddle_tpu.serving.server import ServingServer
    cfg, eng = cell.config, cell.config["engine"]
    model = builder_of(cell).build(cfg, seed)
    model.eval()
    return ServingServer(
        model, port=0, prefix_cache=bool(eng["prefix_cache"]),
        max_new_tokens_cap=int(eng["max_new_tokens_cap"]),
        trace_sample=1.0 if traced else 0.0, trace_max=1 << 16,
        num_slots=int(eng["num_slots"]), page_size=int(eng["page_size"]),
        max_seq_len=int(eng["max_seq_len"]), num_pages=int(eng["num_pages"]),
        timeline_steps=1 << 17)


def serve_window(cell, opts) -> dict:
    """Set-up, the window and the stop: what ``drivers/serve.py run``
    does up to its check, with this driver's server."""
    tr = cell.traffic
    seed, seconds = opts.seed, float(opts.seconds)
    drain_s = float(tr.get("drain_s", 60.0))
    compiles = common.Compiles()
    marks = common.Marks()
    pauses = common.GcPauses()
    server = build_server(cell, seed, bool(opts.trace))
    port = server.start()
    marks("built")
    try:
        serve.warm(server, port, cell, seed)
        marks("warmed")
        pauses.settle()
        schedule = gen.serve_schedule(tr, cell.config["vocab_size"], seed,
                                      seconds)
        in_setup = compiles.n
        tlen = min(float(tr.get("trace_seconds", 4.0)), seconds * 0.5) \
            if opts.trace else 0.0
        win = serve.drive(cell, port, schedule, seconds, drain_s, tlen)
        marks("drained")
        in_window = compiles.n - in_setup
        side = serve.rpc(port, {"op": "trace"}) if opts.trace else {}
        flight = server.engine.flight_summary()
    finally:
        server.stop()
    marks("stopped")
    peak = common.memory_peak_bytes()
    del server
    pauses.release()
    gc.collect()
    return {"schedule": schedule, "win": win, "side": side, "peak": peak,
            "marks": marks, "pauses": pauses, "drain_s": drain_s,
            "notes": {"compiles_in_setup": in_setup,
                      "compiles_in_window": in_window,
                      "model_counters": flight.get("model_counters"),
                      "window_ring_pages": flight.get("window_ring_pages")}}


def run(cell, opts) -> dict:
    seconds = float(opts.seconds)
    got = serve_window(cell, opts)
    win, side = got["win"], got["side"]
    t0, log = win["t0"], win["log"]
    e2e, attempted, failed = serve.end_to_end(log, seconds, got["drain_s"])
    timeline = [e for e in side.get("step_timeline", ())
                if t0 <= e["t_us"] * 1e-6 <= t0 + seconds]
    numbers = check(cell, opts.seed, got["schedule"], log,
                    control=bool(getattr(opts, "control", False)))
    got["marks"]("checked")
    return {
        "setup_done": t0, "attempted": attempted, "failed": failed,
        "memory_peak_bytes": got["peak"], "end_to_end": e2e,
        "numbers": numbers,
        "notes": dict(got["notes"], requests=len(log),
                      phases_s=got["marks"].since(),
                      gc=got["pauses"].notes(),
                      slowest_steps=sorted(
                          ((e["ms"], e.get("programs"), e.get("queued"))
                           for e in timeline), key=lambda x: -x[0])[:3]),
        "artifacts": {"events": win["events"],
                      "trace_window": win["trace_window"],
                      "window_s": seconds, "log": log, "t0": t0,
                      "timeline": timeline, "traces": side.get("traces", [])},
    }


def reference_rows(cell, seed: int, schedule: list, log: list,
                   control: bool = False, fault=None) -> dict:
    """The reference over the sample: per sampled position the served
    token's gap, the router's smallest margin where the reference gives
    one and, with ``control``, the lower precision's gap."""
    cfg = cell.config
    idx = serve.sample(log, seed, int(cell.traffic.get("check_sample", 6)))
    if not idx:
        return {}
    ref_mod = cell.load_module("references", cfg["reference"])
    seqs = [schedule[i]["prompt"] + list(log[i]["tokens"]) for i in idx]
    plens = [len(schedule[i]["prompt"]) for i in idx]
    kw = {} if fault is None else {"fault": fault}
    res = ref_mod.served_token_gaps(
        cfg, builder_of(cell).make_weights(cfg, seed), seqs, plens,
        control=control, **kw)
    out = {"gaps": np.concatenate(res["gaps"])}
    if res.get("margins"):
        out["margins"] = np.concatenate(res["margins"])
    if control:
        out["control_gaps"] = np.concatenate(res["control_gaps"])
    return out


def numbers_of(rows: dict, delta: float) -> dict:
    """The numbers `correct` compares, from the per-position rows: the
    positions whose margin is under ``delta`` left out of the gaps."""
    if not rows:
        return {"served_gap_mean": float("inf"), "sampled_tokens": 0}
    gaps = rows["gaps"]
    keep = rows["margins"] >= delta if "margins" in rows \
        else np.ones(gaps.shape, bool)
    out = {"sampled_tokens": int(gaps.size),
           "near_tie_share": float(1.0 - np.mean(keep))}
    if not keep.any():
        return dict(out, served_gap_mean=float("inf"))
    out.update(served_gap=float(np.max(gaps[keep])),
               served_gap_mean=float(np.mean(gaps[keep])),
               served_gap_mean_all=float(np.mean(gaps)))
    if "control_gaps" in rows:
        low = rows["control_gaps"]
        out.update(control_gap=float(np.max(low[keep])),
                   control_gap_mean=float(np.mean(low[keep])))
    return out


def check(cell, seed: int, schedule: list, log: list,
          control: bool = False, fault=None) -> dict:
    return numbers_of(
        reference_rows(cell, seed, schedule, log, control, fault),
        float(cell.traffic.get("router_margin_delta", 0.0)))


# -- by hand: the knee sweep and the readings for the limits -----------------

def sweep(cell, opts) -> int:
    compiles = common.Compiles()
    server = build_server(cell, opts.seed, False)
    port = server.start()
    rows = []
    try:
        serve.warm(server, port, cell, opts.seed)
        s, drain = opts.seconds, float(cell.traffic.get("drain_s", 90.0))
        for i, rate in enumerate(float(x) for x in opts.rates.split(",")):
            tr = dict(cell.traffic, rate_per_s=rate)
            sched = gen.serve_schedule(tr, cell.config["vocab_size"],
                                       opts.seed + i, s)
            win = serve.drive(cell, port, sched, s, drain)
            e2e, n, failed = serve.end_to_end(win["log"], s, drain)

            def mean_backlog(a, b):
                ts = [a + (b - a) * k / 8 for k in range(9)]
                return sum(serve.backlog(win["log"], t) for t in ts) / 9.0
            tail = sum(1 for r in win["log"] for t in r["token_times"]
                       if 0.5 * s <= t <= s) / (0.5 * s)
            row = {"rate_per_s": rate, "requests": n, "failed": failed,
                   "compiles_so_far": compiles.n,
                   "backlog_mid": mean_backlog(0.45 * s, 0.55 * s),
                   "backlog_end": mean_backlog(0.9 * s, s),
                   "tokens_per_s_second_half": tail, **e2e}
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        server.stop()
    print(json.dumps({"device": common.device_block(cell.chips),
                      "memory_peak_bytes": common.memory_peak_bytes(),
                      "sweep": rows}))
    return 0


def readings(cell, opts) -> int:
    got = serve_window(cell, opts)
    log, sched = got["win"]["log"], got["schedule"]
    delta = float(cell.traffic.get("router_margin_delta", 0.0))
    ref_mod = cell.load_module("references", cell.config["reference"])
    out = {"seed": opts.seed, "delta": delta, "notes": got["notes"],
           "device": common.device_block(cell.chips)}
    rows = reference_rows(cell, opts.seed, sched, log, control=True)
    out["program"] = numbers_of(rows, delta)
    keep = {k: np.asarray(v, np.float64).round(6).tolist()
            for k, v in rows.items()}
    for fault in getattr(ref_mod, "FAULTS", ()):
        frows = reference_rows(cell, opts.seed, sched, log, fault=fault)
        out["fault_" + fault] = numbers_of(frows, delta)
        keep["gaps_" + fault] = np.asarray(
            frows.get("gaps", ()), np.float64).round(6).tolist()
        if "margins" in frows:
            keep["margins_" + fault] = np.asarray(
                frows["margins"], np.float64).round(6).tolist()
    path = os.path.join(ROOT, "chiprun_out",
                        f"readings-{cell.name}-{opts.seed}.json")
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w", encoding="utf-8") as f:
        json.dump(keep, f)
    print(json.dumps(out), flush=True)
    return 0


def main(argv=None) -> int:
    import argparse
    p = argparse.ArgumentParser(description="knee sweep and readings for "
                                "the limits of a serve_lm cell")
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", default=None)
    p.add_argument("--readings", action="store_true")
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--cpu-rehearsal", action="store_true")
    opts = p.parse_args(argv)
    opts.trace = 0
    cell = common.open_cell(opts.workload, opts.cpu_rehearsal)
    if opts.readings:
        return readings(cell, opts)
    if not opts.rates:
        p.error("give --rates or --readings")
    return sweep(cell, opts)


if __name__ == "__main__":
    sys.exit(main())
