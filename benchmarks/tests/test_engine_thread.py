"""The engine-thread readers on a small recorded timeline: each share
against the hand count, sleeping gaps left out, and `None` where there
is nothing to read or the phases say too little."""

import copy

import pytest

from benchmarks import host_phases, spec

# Three steps as the engine records them (the keys the readers use).
# Step 1 ends with nothing left to do: the thread sleeps before step 2,
# whose gap (9,000 us) is left out. Step 0 is the window's first: what
# went before it is not in the window, so its gap is left out too.
TIMELINE = [
    {"step": 0, "ms": 1.0, "slots_active": 2, "queued": 1,
     "host_us": {"admit": 100.0, "upload": 150.0, "launch": 50.0,
                 "wait": 600.0, "emit": 90.0, "other": 10.0},
     "commit_us": 40.0, "gap_us": 700.0, "cpu_us": 900.0},
    {"step": 1, "ms": 2.0, "slots_active": 0, "queued": 0,
     "host_us": {"admit": 300.0, "upload": 250.0, "launch": 150.0,
                 "wait": 1100.0, "emit": 190.0, "other": 10.0},
     "commit_us": 60.0, "gap_us": 100.0, "cpu_us": 1000.0},
    {"step": 2, "ms": 1.0, "slots_active": 1, "queued": 0,
     "host_us": {"upload": 100.0, "launch": 100.0, "wait": 700.0,
                 "emit": 80.0, "other": 20.0},
     "commit_us": 60.0, "gap_us": 9000.0, "cpu_us": 500.0},
]
# wall: ms 4,000 + commit 160 + the one counted gap 100 = 4,260 us
WALL = 4260.0
BY_HAND = {
    "engine_thread_pct.wait": 2400.0,
    "engine_thread_pct.launch": 300.0,
    "engine_thread_pct.upload": 500.0,
    "engine_thread_pct.emit": 360.0,
    "engine_thread_pct.admit": 400.0,
    "engine_thread_pct.commit": 160.0,
    "engine_thread_cpu_pct": 2400.0,
}


@pytest.fixture(scope="module")
def cell():
    return spec.Cell("serve-1p3b-chat-sat")


def _read(cell, metric, timeline):
    return cell.load_module("layer_metrics", metric).read(
        {"timeline": timeline})


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_share_worked_out_by_hand(cell, metric):
    assert _read(cell, metric, TIMELINE) == pytest.approx(
        100.0 * BY_HAND[metric] / WALL)


def test_the_phase_shares_and_other_sum_to_the_wall():
    w = host_phases.wall_us(TIMELINE)
    assert w["wall"] == WALL and w["other"] == 40.0
    assert sum(w[p] for p in host_phases.PHASES) + w["other"] == WALL


def test_sleeping_gaps_are_left_out():
    # `loop` has no metric of its own (under 1 % in every serve cell on
    # the chip, PERF.md): its time is in the wall all shares divide by
    assert host_phases.share_pct(TIMELINE, "loop") == pytest.approx(
        100.0 * 100.0 / WALL)
    awake = copy.deepcopy(TIMELINE)
    awake[1]["queued"] = 3  # something waited: step 2's gap is work
    assert host_phases.share_pct(awake, "loop") == pytest.approx(
        100.0 * 9100.0 / (WALL + 9000.0))


@pytest.mark.parametrize("metric", sorted(BY_HAND))
def test_nothing_to_read_is_none_never_zero(cell, metric):
    old = [{k: v for k, v in e.items()
            if k not in ("host_us", "commit_us", "gap_us", "cpu_us")}
           for e in TIMELINE]  # the parent's records
    assert _read(cell, metric, old) is None
    assert _read(cell, metric, []) is None
    assert cell.load_module("layer_metrics", metric).read({}) is None
    # phases that miss 5 % of `ms`: the shares say too little
    holed = copy.deepcopy(TIMELINE)
    for e in holed:
        miss = 0.05 * e["ms"] * 1e3
        e["host_us"]["wait"] -= miss
        e["host_us"]["other"] += miss
    assert _read(cell, metric, holed) is None


def test_every_new_metric_is_listed_for_the_serve_cells():
    bm = spec.load_benchmark()
    mine = {m["name"]: m for m in bm["per_layer"] if m["name"] in BY_HAND}
    assert set(mine) == set(BY_HAND)
    serve = [w["name"] for w in bm["workloads"]
             if w["config"] == "gpt3-1p3b-serve"]
    for m in mine.values():
        assert m["workloads"] == serve and m["unit"] == "%"
        assert m["source"] == "program_counter"
        assert m["moves"] == "serve_tokens_per_s"
