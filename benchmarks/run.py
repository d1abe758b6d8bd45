"""One run of one cell:

    python benchmarks/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A new process that loads, warms up, measures for ``--seconds`` and
prints one JSON object as its last line: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with ``--trace
0``, its per-layer metrics with ``--trace 1``), ``device`` and, traced,
``breakdown``; the numbers `correct` compared come last, under
``checks``, and as the last lines of standard error. Without the chips
the cell asks for it exits non-zero and prints no result;
``--cpu-rehearsal`` runs the same code on the CPU at the sizes each
file gives under ``rehearsal`` and reports no device metric.

Everything about a cell is data found by name (see README.md).
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

T_START = time.monotonic()

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)  # first: this checkout's benchmarks/, no other


def parse(argv=None):
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--seconds", type=float, default=None)
    p.add_argument("--trace", type=int, choices=(0, 1), default=0)
    p.add_argument("--cpu-rehearsal", action="store_true",
                   help="run on the CPU at each file's `rehearsal` sizes: "
                        "paths and control flow only, no device metric")
    p.add_argument("--control", action="store_true",
                   help="serving: also read the lower precision's gap at "
                        "the sampled positions (a reading for the limits; "
                        "the benchmark's own runs do not run it)")
    p.add_argument("--dump-trace", default=None, metavar="FILE",
                   help="with --trace 1: write what the trace holds to FILE "
                        "(for reading one by hand)")
    return p.parse_args(argv)


def main(argv=None) -> int:
    opts = parse(argv)
    from benchmarks import common, spec, xplane
    from benchmarks.peaks import peaks_of

    cell = common.open_cell(opts.workload, opts.cpu_rehearsal)
    if opts.seconds is None:
        opts.seconds = float(spec.load_benchmark()["run_seconds"])

    xplane.KEEP_STATS = bool(opts.dump_trace)
    device = common.device_block(cell.chips)
    driver = cell.load_module("drivers", cell.traffic["kind"])
    out = driver.run(cell, opts)

    device["memory_peak_bytes"] = out["memory_peak_bytes"]
    correct, rows = common.judge(out["numbers"],
                                 cell.traffic.get("limits", {}))
    correct = correct and out["failed"] == 0 and out["attempted"] > 0
    values = dict(out["end_to_end"])
    values["setup_s"] = out["setup_done"] - T_START
    result = {"correct": bool(correct), "attempted": out["attempted"],
              "failed": out["failed"]}

    if opts.trace:
        art = out["artifacts"]
        art.update(cell=cell, end_to_end=values, seconds=opts.seconds,
                   peaks=None if opts.cpu_rehearsal
                   else peaks_of(device["kind"]))
        events = art.get("events") or []
        metrics = {}
        for m in cell.per_layer:
            reader = cell.load_module("layer_metrics", m["name"])
            value = reader.read(art)
            if value is not None:  # nothing to read: left out, never 0
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["metrics"] = metrics
        device["busy_s"] = xplane.busy_seconds(events)
        device["window_s"] = xplane.window_seconds(events)
        # no span of the benchmark's can be placed on the trace's clock
        # yet (PERF.md, open questions): every gap is the host's
        result["breakdown"] = xplane.breakdown(events)
        if opts.dump_trace:
            os.makedirs(os.path.dirname(os.path.abspath(opts.dump_trace)),
                        exist_ok=True)
            with open(opts.dump_trace, "w", encoding="utf-8") as f:
                f.write(xplane.inventory(events))
    else:
        result["metrics"] = {
            m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
            for m in cell.end_to_end if m["name"] in values}
    if opts.cpu_rehearsal:
        # a CPU run gives no time, rate or share of a device: the names
        # the run would have reported, and no number under any of them
        result["rehearsal_metric_names"] = sorted(result["metrics"])
        result["metrics"] = {}
    result["device"] = device
    result["notes"] = out.get("notes", {})
    result["checks"] = rows

    print(f"[bench] {cell.name} seed {opts.seed} seconds {opts.seconds} "
          f"trace {opts.trace}: {json.dumps(result['notes'])}",
          file=sys.stderr)
    for name, row in rows.items():
        print(f"[bench] check {name} = {row['value']} limit {row['limit']}",
              file=sys.stderr)
    print(f"[bench] correct {result['correct']} attempted "
          f"{result['attempted']} failed {result['failed']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
