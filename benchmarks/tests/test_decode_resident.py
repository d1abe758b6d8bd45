"""`decode_resident_step_pct` on a small recorded timeline: the share
against the hand count, and `None` where no record carries the key."""

import pytest

from benchmarks import spec

METRIC = "decode_resident_step_pct"

# As the engine records them (the keys the reader uses). Steps 0 and 3
# uploaded (an admission, a finish before them); step 4 decoded but its
# last stream finished in it (nothing left decoding: not counted);
# step 5 ran no decode program (an idle step carries no key).
TIMELINE = [
    {"step": 0, "slots_decoding": 2, "decode_h2d": 1},
    {"step": 1, "slots_decoding": 2, "decode_h2d": 0},
    {"step": 2, "slots_decoding": 2, "decode_h2d": 0},
    {"step": 3, "slots_decoding": 1, "decode_h2d": 1},
    {"step": 4, "slots_decoding": 0, "decode_h2d": 0},
    {"step": 5, "slots_decoding": 0},
    {"step": 6, "slots_decoding": 1, "decode_h2d": 0},
]


@pytest.fixture(scope="module")
def read():
    return spec.Cell("serve-1p3b-chat-sat").load_module(
        "layer_metrics", METRIC).read


def test_share_worked_out_by_hand(read):
    # five counted records, three of them with no upload
    assert read({"timeline": TIMELINE}) == pytest.approx(100.0 * 3 / 5)
    assert read({"timeline": TIMELINE[:1]}) == 0.0
    assert read({"timeline": TIMELINE[1:3]}) == 100.0


def test_nothing_to_read_is_none(read):
    parent = [{k: v for k, v in e.items() if k != "decode_h2d"}
              for e in TIMELINE]  # a program without the counter
    assert read({"timeline": parent}) is None
    assert read({"timeline": []}) is None
    assert read({}) is None
    # records that carry the key but left nothing decoding
    assert read({"timeline": TIMELINE[4:6]}) is None


def test_the_metric_is_listed_for_the_serve_cells():
    bm = spec.load_benchmark()
    (entry,) = [m for m in bm["per_layer"] if m["name"] == METRIC]
    serve = [w["name"] for w in bm["workloads"]
             if w["config"] == "gpt3-1p3b-serve"]
    assert entry == {"name": METRIC, "unit": "%", "better": "higher",
                     "source": "program_counter", "layer": "engine step",
                     "moves": "serve_tokens_per_s", "workloads": serve}
    assert bm["per_layer"][-1] is entry  # appended, nothing moved
