"""`decode_ahead_step_pct` on a small recorded timeline: the share
against the hand count, idle records left out, and `None` where no
record carries the key (the parent's program)."""

import pytest

from benchmarks import spec

METRIC = "decode_ahead_step_pct"

# As the engine records them (the keys the reader uses). Step 0 admitted
# and uploaded with nothing in flight; 1 and 2 were launched over the
# step before them; a finish was found under step 2, so step 3 settled
# it first and uploaded; step 4 ran ahead but its last stream finished
# in the step it settled (nothing left decoding: not counted); step 5
# ran no decode program (an idle step carries no key).
TIMELINE = [
    {"step": 0, "slots_decoding": 2, "decode_h2d": 1, "decode_ahead": 0},
    {"step": 1, "slots_decoding": 2, "decode_h2d": 0, "decode_ahead": 1},
    {"step": 2, "slots_decoding": 1, "decode_h2d": 0, "decode_ahead": 1},
    {"step": 3, "slots_decoding": 1, "decode_h2d": 1, "decode_ahead": 0},
    {"step": 4, "slots_decoding": 0, "decode_h2d": 0, "decode_ahead": 1},
    {"step": 5, "slots_decoding": 0},
    {"step": 6, "slots_decoding": 1, "decode_h2d": 0, "decode_ahead": 0},
]


@pytest.fixture(scope="module")
def read():
    return spec.Cell("serve-1p3b-chat-sat").load_module(
        "layer_metrics", METRIC).read


def test_share_worked_out_by_hand(read):
    # five counted records, two of them launched ahead
    assert read({"timeline": TIMELINE}) == pytest.approx(100.0 * 2 / 5)
    assert read({"timeline": TIMELINE[:1]}) == 0.0
    assert read({"timeline": TIMELINE[1:3]}) == 100.0


def test_idle_records_are_left_out(read):
    # a record that left nothing decoding, or ran no decode program,
    # moves neither the count nor the share
    assert read({"timeline": TIMELINE[:4]}) == \
        read({"timeline": TIMELINE[:6]}) == pytest.approx(50.0)
    assert read({"timeline": TIMELINE[4:6]}) is None


def test_a_program_without_the_counter_gives_nothing(read):
    parent = [{k: v for k, v in e.items() if k != "decode_ahead"}
              for e in TIMELINE]  # records as the parent writes them
    assert read({"timeline": parent}) is None
    assert read({"timeline": []}) is None
    assert read({}) is None


def test_the_metric_is_listed_for_the_serve_cells():
    bm = spec.load_benchmark()
    (entry,) = [m for m in bm["per_layer"] if m["name"] == METRIC]
    serve = [w["name"] for w in bm["workloads"]
             if w["name"].startswith("serve-")]
    assert len(serve) == 5 and sorted(entry["workloads"]) == sorted(serve)
    assert {k: v for k, v in entry.items() if k != "workloads"} == {
        "name": METRIC, "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "engine step",
        "moves": "serve_tokens_per_s"}
