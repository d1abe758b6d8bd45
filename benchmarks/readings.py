"""The readings a training cell's limits are set from, in one process:

    python benchmarks/readings.py --workload <cell> --seeds 101,102,... --controls 3

For every seed: the program's first launches against the plain reference
(the lower reading of each number). For the first ``--controls`` seeds
also the reference put in the program's place with its matmuls in the
nearest lower precision (the control), with half of the batch left out
and with every step returning its state unchanged (two faults). Needs
no measured window. A serving cell's readings come from runs of
``run.py --control`` (``served_gap*`` and ``control_gap*``).
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)  # first: this checkout's benchmarks/, no other


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--controls", type=int, default=3)
    p.add_argument("--cpu-rehearsal", action="store_true")
    opts = p.parse_args(argv)

    from benchmarks import common, traffic as gen
    cell = common.open_cell(opts.workload, opts.cpu_rehearsal)
    drv = cell.load_module("drivers", "train")
    cfg, tr = cell.config, cell.traffic
    rows = []
    for n, seed in enumerate(int(x) for x in opts.seeds.split(",")):
        step = drv.build(cell, seed)
        feed = [gen.train_batch(tr, cfg["vocab_size"], seed, i)
                for i in range(int(tr["check_launches"]))]
        prog = drv.first_steps(step, cell, seed, feed)
        del step
        gc.collect()
        ref = drv.reference(cell, seed, feed)
        row = {"seed": seed, "program": drv.compare(prog, ref)}
        if n < opts.controls:
            for name, kw in (("control_lowp", {"lowp": True}),
                             ("fault_half_batch", {"fault": "half_batch"}),
                             ("fault_state_unchanged",
                              {"fault": "state_unchanged"})):
                row[name] = drv.compare(drv.reference(cell, seed, feed, **kw),
                                        ref)
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({"device": common.device_block(cell.chips),
                      "readings": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
