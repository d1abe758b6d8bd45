"""The serving driver: ``ServingServer`` built in this process (the one
process that holds the chip), driven over its socket by the load
generator, a child process that never imports JAX.

Set-up: model from the configuration with the benchmark's weights, the
server with the configuration's engine sizes (every other engine switch
at the value the program ships), one warm request for each prompt bucket
the mix can reach and so for the decode program. The window: the mix's
arrival schedule, every request timed at the client from its due time.
After the close the generator waits for the replies still owed (late is
late, not wrong), the server is stopped, the peak of memory read, the
program's state freed, and the plain reference run over a sample of the
finished requests, drawn from the seed with the longest in it.
"""

from __future__ import annotations

import gc
import json
import os
import socket
import subprocess
import sys
import time

import numpy as np

from benchmarks import common, reduce as red, traffic as gen, weights as wts
from benchmarks.model import build_gpt


def rpc(port: int, payload: dict, timeout_s: float = 600.0) -> dict:
    """One newline-JSON request to the server; streamed tokens are
    skipped, the final reply returned."""
    with socket.create_connection(("127.0.0.1", port), timeout=timeout_s) as s:
        s.sendall((json.dumps(payload) + "\n").encode())
        for line in s.makefile("r", encoding="utf-8"):
            msg = json.loads(line)
            if "token" not in msg:
                return msg
    raise ConnectionError("server closed the connection mid-request")


def build_server(cell, seed: int, traced: bool):
    from paddle_tpu.serving.server import ServingServer
    cfg, eng = cell.config, cell.config["engine"]
    model = build_gpt(cfg, seed)
    model.eval()
    return ServingServer(
        model, port=0, prefix_cache=bool(eng["prefix_cache"]),
        max_new_tokens_cap=int(eng["max_new_tokens_cap"]),
        trace_sample=1.0 if traced else 0.0, trace_max=1 << 16,
        num_slots=int(eng["num_slots"]), page_size=int(eng["page_size"]),
        max_seq_len=int(eng["max_seq_len"]), num_pages=int(eng["num_pages"]),
        timeline_steps=1 << 17)


def buckets_of(server, traffic: dict) -> list:
    """The engine's prompt buckets that the mix's lengths can reach."""
    p = traffic["prompt"]
    lo = int(p.get("min", p.get("value", 1)))
    hi = int(p.get("max", p.get("value", lo)))
    ladder = list(server.engine.prompt_buckets)
    first = next(b for b in ladder if b >= lo)
    last = next(b for b in ladder if b >= hi)
    return [b for b in ladder if first <= b <= last]


def warm(server, port: int, cell, seed: int) -> None:
    """One request for each prompt bucket of the mix: compiles (or reads
    from the cache) that bucket's prefill and the decode program."""
    rng = gen.rng_of(seed, 7)
    ladder = list(server.engine.prompt_buckets)
    for b in buckets_of(server, cell.traffic):
        below = max([x for x in ladder if x < b], default=0)
        n = max(below + 1, b - 8)
        rep = rpc(port, {"op": "generate", "max_new_tokens": 4, "stream": True,
                         "prompt": rng.integers(
                             0, cell.config["vocab_size"], n).tolist()})
        if "error" in rep or len(rep.get("generated", ())) != 4:
            raise RuntimeError(f"warm-up of bucket {b} failed: "
                               f"{json.dumps(rep)[:300]}")


def drive(cell, port: int, schedule: list, seconds: float, drain_s: float,
          trace_seconds: float = 0.0) -> dict:
    """One window: start the generator, trace a few seconds of it if
    asked, wait for the generator's log. ``t0`` is the window's start on
    the monotonic clock."""
    child = subprocess.Popen(
        [sys.executable, os.path.join(cell.base, "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE)
    events, trace_win = None, None
    try:
        t0 = time.monotonic() + float(cell.traffic.get("lead_s", 0.5))
        child.stdin.write(json.dumps({
            "port": port, "t0": t0, "window_s": seconds, "drain_s": drain_s,
            "requests": schedule}).encode())
        child.stdin.close()
        if trace_seconds > 0:
            time.sleep(max(0.0, t0 + seconds * 0.4 - time.monotonic()))
            events, (a, b) = common.trace(lambda: time.sleep(trace_seconds))
            trace_win = (a - t0, b - t0)
        out = child.stdout.read()
        child.wait(timeout=seconds + drain_s + 120)
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()
    return {"t0": t0, "log": json.loads(out)["requests"], "events": events,
            "trace_window": trace_win}


def run(cell, opts) -> dict:
    cfg, tr = cell.config, cell.traffic
    seed, seconds = opts.seed, float(opts.seconds)
    drain_s = float(tr.get("drain_s", 60.0))
    compiles = common.Compiles()
    marks = common.Marks()
    pauses = common.GcPauses()

    server = build_server(cell, seed, bool(opts.trace))
    port = server.start()
    marks("built")
    try:
        warm(server, port, cell, seed)
        marks("warmed")
        pauses.settle()
        schedule = gen.serve_schedule(tr, cfg["vocab_size"], seed, seconds)
        compiled_in_setup = compiles.n
        tlen = min(float(tr.get("trace_seconds", 4.0)), seconds * 0.5) \
            if opts.trace else 0.0
        win = drive(cell, port, schedule, seconds, drain_s, tlen)
        marks("drained")
        compiled_in_window = compiles.n - compiled_in_setup
        side = rpc(port, {"op": "trace"}) if opts.trace else {}
    finally:
        server.stop()
    marks("stopped")
    peak = common.memory_peak_bytes()
    t0, log = win["t0"], win["log"]

    e2e, attempted, failed = end_to_end(log, seconds, drain_s)
    timeline = [e for e in side.get("step_timeline", ())
                if t0 <= e["t_us"] * 1e-6 <= t0 + seconds]

    # -- free the program's state, then the reference ---------------------
    del server
    pauses.release()
    gc.collect()
    numbers = check(cell, seed, schedule, log,
                    control=bool(getattr(opts, "control", False)))
    marks("checked")
    return {
        "setup_done": t0, "attempted": attempted, "failed": failed,
        "memory_peak_bytes": peak, "end_to_end": e2e, "numbers": numbers,
        "notes": {"requests": len(log), "compiles_in_setup": compiled_in_setup,
                  "compiles_in_window": compiled_in_window,
                  "phases_s": marks.since(), "gc": pauses.notes(),
                  "slowest_steps": sorted(
                      ((e["ms"], e.get("programs"), e.get("queued"))
                       for e in timeline), key=lambda x: -x[0])[:3]},
        "artifacts": {"events": win["events"],
                      "trace_window": win["trace_window"],
                      "window_s": seconds, "log": log, "t0": t0,
                      "timeline": timeline, "traces": side.get("traces", [])},
    }


def backlog(log: list, t: float) -> int:
    """Requests due by ``t`` whose first token had not come by ``t``."""
    return sum(1 for r in log if r["due"] <= t
               and not (r["token_times"] and r["token_times"][0] <= t))


def end_to_end(log: list, seconds: float, drain_s: float) -> tuple:
    """The client's view. A request that failed, was refused or got
    fewer tokens than it asked for is failed, and its wait counts as
    the worst there is: up to the drain limit."""
    limit = seconds + drain_s
    ttft, gaps, inside, failed = [], [], 0, 0
    for r in log:
        tt = r["token_times"]
        ok = r["error"] is None and r["tokens"] is not None \
            and len(r["tokens"]) == r["max_new_tokens"]
        failed += not ok
        ttft.append((tt[0] if tt and ok else limit) - r["due"])
        gaps += [b - a for a, b in zip(tt, tt[1:])]
        inside += sum(1 for t in tt if 0.0 <= t <= seconds)
    e2e = {"serve_tokens_per_s": inside / seconds,
           "ttft_p95_ms": red.percentile(ttft, 95) * 1e3}
    if gaps:
        e2e["tpot_p95_ms"] = red.percentile(gaps, 95) * 1e3
    return e2e, len(log), failed


def sample(log: list, seed: int, k: int) -> list:
    """Indices of ``k`` finished requests, drawn from the seed, the
    longest (prompt and answer) among them."""
    done = [i for i, r in enumerate(log) if r["error"] is None
            and r["tokens"] and len(r["tokens"]) == r["max_new_tokens"]]
    if not done:
        return []
    longest = max(done, key=lambda i: log[i]["prompt_len"] + len(log[i]["tokens"]))
    rest = [i for i in done if i != longest]
    pick = gen.rng_of(seed, 4).permutation(len(rest))[:max(0, k - 1)]
    return [longest] + [rest[j] for j in pick]


def check(cell, seed: int, schedule: list, log: list,
          control: bool = False) -> dict:
    """The gap by which a served token's logit lies below the plain
    reference's best, over the sample: the widest and the mean. With
    ``control`` the same readings for the token that the lower
    precision puts first (for setting the limits, never in the
    benchmark's own runs)."""
    cfg = cell.config
    idx = sample(log, seed, int(cell.traffic.get("check_sample", 6)))
    if not idx:
        return {"served_gap": float("inf"), "sampled_tokens": 0}
    ref_mod = cell.load_module("references", cfg["reference"])
    seqs = [schedule[i]["prompt"] + list(log[i]["tokens"]) for i in idx]
    plens = [len(schedule[i]["prompt"]) for i in idx]
    res = ref_mod.served_token_gaps(cfg, wts.make_weights(cfg, seed),
                                    seqs, plens, control=control)
    gaps = np.concatenate(res["gaps"])
    out = {"served_gap": float(np.max(gaps)),
           "served_gap_mean": float(np.mean(gaps)),
           "sampled_tokens": int(gaps.size)}
    if control:
        low = np.concatenate(res["control_gaps"])
        out.update(control_gap=float(np.max(low)),
                   control_gap_mean=float(np.mean(low)))
    return out
