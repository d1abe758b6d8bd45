"""The `serve_lm` driver and the SmallThinker cell at rehearsal size on
the CPU: the command end to end, traced and untraced; the readings the
limits stand on (the program correct, the lower-precision control and
each planted fault not correct); the operations and bytes of
`flops_smallthinker.py` against hand counts; the new readers on small
recorded inputs."""

import json
from types import SimpleNamespace

import numpy as np
import pytest

from benchmarks import common, flops_smallthinker as fl, spec
from benchmarks.tests.test_harness import CONTRACT_KEYS, ROOT, run_cell

CELL = "serve-smallthinker-mixed-sat"
NEW_READERS = ("serve_mfu_pct.moe", "moe_expert_roofline_pct.decode",
               "moe_expert_roofline_pct.prefill",
               "paged_decode_roofline_pct.window",
               "flash_roofline_pct.window", "kv_pool_occupancy_pct.global")


@pytest.mark.parametrize("trace", [0, 1])
def test_command_end_to_end_rehearsal(trace):
    p = run_cell(ROOT, "--workload", CELL, "--seed", str(2**31 + 11),
                 "--seconds", "3", "--trace", str(trace), "--cpu-rehearsal")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert CONTRACT_KEYS <= set(line) and list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["metrics"] == {}
    assert line["notes"]["compiles_in_window"] == 0
    assert line["notes"]["window_ring_pages"] == 4
    assert {"moe.touched", "moe.max_load", "moe.max_over_mean"} <= \
        set(line["notes"]["model_counters"])
    cell = spec.Cell(CELL)
    names = set(line["rehearsal_metric_names"])
    if trace:
        # what needs a device trace or a peak stays out on the CPU
        assert "kv_pool_occupancy_pct.global" in names
        assert names <= {m["name"] for m in cell.per_layer}
    else:
        assert names == {"serve_tokens_per_s", "setup_s"}


@pytest.fixture(scope="module")
def window():
    """One rehearsal window in this process and the cell it ran."""
    cell = spec.Cell(CELL, rehearsal=True)
    driver = cell.load_module("drivers", cell.traffic["kind"])
    opts = SimpleNamespace(seed=2**31 + 5, seconds=4.0, trace=0)
    return cell, driver, opts, driver.serve_window(cell, opts)


def _judge(cell, numbers):
    return common.judge(numbers, cell.traffic["limits"])


def test_program_is_correct_and_the_control_is_not(window):
    cell, driver, opts, got = window
    rows = driver.reference_rows(cell, opts.seed, got["schedule"],
                                 got["win"]["log"], control=True)
    nums = driver.numbers_of(rows, cell.traffic["router_margin_delta"])
    assert nums["sampled_tokens"] >= 100
    ok, judged = _judge(cell, nums)
    assert ok, judged
    # the control's token in the program's place fails both gap limits
    low = dict(nums, served_gap=nums["control_gap"],
               served_gap_mean=nums["control_gap_mean"])
    assert nums["control_gap"] > cell.traffic["limits"]["served_gap"]
    assert nums["control_gap_mean"] > \
        cell.traffic["limits"]["served_gap_mean"]
    assert not _judge(cell, low)[0]


@pytest.mark.parametrize("fault", ["window_page", "rope_global", "top5"])
def test_planted_fault_is_not_correct(window, fault):
    cell, driver, opts, got = window
    ref = cell.load_module("references", cell.config["reference"])
    assert fault in ref.FAULTS
    nums = driver.check(cell, opts.seed, got["schedule"], got["win"]["log"],
                        fault=fault)
    ok, judged = _judge(cell, nums)
    assert not ok, judged
    assert nums["served_gap_mean"] > \
        10 * cell.traffic["limits"]["served_gap_mean"]


def test_near_ties_are_left_out_and_counted():
    driver = spec.Cell(CELL).load_module("drivers", "serve_lm")
    rows = {"gaps": np.array([0.0, 0.5, 0.0, 0.25]),
            "margins": np.array([0.2, 0.001, 0.3, 0.05])}
    nums = driver.numbers_of(rows, 0.01)
    assert nums["near_tie_share"] == 0.25
    assert nums["served_gap"] == 0.25
    assert nums["served_gap_mean"] == pytest.approx(0.25 / 3)
    assert nums["served_gap_mean_all"] == pytest.approx(0.75 / 4)
    every = driver.numbers_of(rows, 1.0)  # nothing kept: not correct
    assert every["served_gap_mean"] == float("inf")
    assert driver.numbers_of({}, 0.01)["sampled_tokens"] == 0


# -- operations and bytes from shapes, against hand counts ------------------

@pytest.fixture(scope="module")
def cfg():
    return spec.Cell(CELL).config


def test_published_sizes_give_the_issue_arithmetic(cfg):
    assert fl.expert_params(cfg) == 3 * 2560 * 768 == 5898240
    attn = 2 * 2560 * 3584 + 2 * 2560 * 512
    assert fl.matmul_params_per_token(cfg) == \
        attn + 2560 * 64 + 6 * 5898240
    assert fl.layer_windows(cfg) == [None, 4096, 4096, 4096] * 3
    assert fl.expert_bytes(cfg, 51) == 51 * 5898240 * 2
    assert fl.expert_flops(cfg, 10) == 2.0 * 6 * 5898240 * 12 * 10


def test_keys_seen_under_a_window():
    assert fl.keys_seen_sum(0, 5, None) == 15
    assert fl.keys_seen_sum(3, 2, None) == 4 + 5
    assert fl.keys_seen_sum(0, 5, 3) == 1 + 2 + 3 + 3 + 3
    assert fl.keys_seen_sum(10, 4, 3) == 12
    assert fl.keys_seen_sum(1, 3, 3) == 2 + 3 + 3


def test_pages_spanned_by_kind_of_layer(cfg):
    assert fl.pages_spanned(100, 64, None) == 2
    assert fl.pages_spanned(9000, 64, None) == 141
    # the window starts at 9000 - 4096 = 4904, inside page 76
    assert fl.pages_spanned(9000, 64, 4096) == 141 - 76
    assert fl.pages_spanned(100, 64, 4096) == 2
    one = 2.0 * 64 * 4 * 128 * 2
    assert fl.paged_decode_bytes(cfg, [9000]) == \
        one * (3 * 141 + 9 * 65)
    # never more than the ring a window layer holds
    assert max(fl.pages_spanned(c, 64, 4096)
               for c in range(1, 16385, 61)) <= \
        cfg["engine"]["window_pages_per_slot"] - 1


# -- the new readers on small recorded inputs --------------------------------

def _reader(name):
    return spec.Cell(CELL).load_module("layer_metrics", name).read


@pytest.mark.parametrize("name", NEW_READERS)
def test_new_reader_finds_nothing_on_a_parent(name):
    read = _reader(name)
    assert read({"end_to_end": {}}) is None
    # a parent's artifacts: a GPT timeline and trace, no counter of ours
    cell = spec.Cell(CELL)
    art = {"cell": cell, "timeline": [{"t_us": 1e6, "ms": 9.0}],
           "events": [], "trace_window": (1.0, 2.0), "t0": 0.0,
           "log": [], "traces": [], "window_s": 3.0, "peaks": None}
    assert read(art) is None


def test_occupancy_reader_is_the_median_share_of_the_allocators_pages():
    cell = spec.Cell(CELL)
    tl = [{"kv_pages": {"global": g, "window": 5}} for g in (10, 30, 20)]
    got = _reader("kv_pool_occupancy_pct.global")(
        {"cell": cell, "timeline": tl})
    assert got == pytest.approx(100.0 * 20 / cell.config["engine"]["num_pages"])


def test_every_new_entry_lists_the_new_cell_alone():
    bm = spec.load_benchmark()
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    cell = spec.Cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == \
        {"serve_tokens_per_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= reported
    # none that counts GPT's shapes
    assert not reported & {"serve_mfu_pct", "decode_mfu_pct",
                           "prefill_mfu_pct", "paged_decode_roofline_pct",
                           "flash_roofline_pct.prefill"}
