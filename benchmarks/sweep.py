"""Find the knee of a serving mix, once, by a sweep on the chip:

    python benchmarks/sweep.py --workload <cell> --rates 3,4,5,6 --seconds 20

One server, warmed once; a window at each offered rate; for each the
tokens per second that reached clients, the tails, and the backlog
(requests due with no first token yet) at the window's middle and end.
The knee is the highest rate whose backlog at the end is no longer than
at the middle. Its output is for PERF.md; the rate a cell runs at is a
number in its traffic file.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)  # first: this checkout's benchmarks/, no other


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--rates", required=True)
    p.add_argument("--seconds", type=float, default=20.0)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--cpu-rehearsal", action="store_true")
    opts = p.parse_args(argv)

    from benchmarks import common, traffic as gen
    cell = common.open_cell(opts.workload, opts.cpu_rehearsal)
    serve = cell.load_module("drivers", "serve")
    compiles = common.Compiles()
    server = serve.build_server(cell, opts.seed, False)
    port = server.start()
    rows = []
    try:
        serve.warm(server, port, cell, opts.seed)
        for i, rate in enumerate(float(x) for x in opts.rates.split(",")):
            tr = dict(cell.traffic, rate_per_s=rate)
            sched = gen.serve_schedule(tr, cell.config["vocab_size"],
                                       opts.seed + i, opts.seconds)
            win = serve.drive(cell, port, sched, opts.seconds, 90.0)
            e2e, n, failed = serve.end_to_end(win["log"], opts.seconds, 90.0)
            s = opts.seconds

            def mean_backlog(a, b):
                ts = [a + (b - a) * k / 8 for k in range(9)]
                return sum(serve.backlog(win["log"], t) for t in ts) / 9.0
            first = sorted((r["token_times"][0] - r["due"]) * 1e3
                           for r in win["log"] if r["token_times"])
            row = {"rate_per_s": rate, "requests": n, "failed": failed,
                   "compiles_so_far": compiles.n,
                   "backlog_mid": mean_backlog(0.45 * s, 0.55 * s),
                   "backlog_end": mean_backlog(0.9 * s, s), **e2e,
                   "ttft_p50_p90_max_ms": [first[len(first) // 2],
                                           first[len(first) * 9 // 10],
                                           first[-1]] if first else None}
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        server.stop()
    print(json.dumps({"device": common.device_block(cell.chips),
                      "sweep": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
