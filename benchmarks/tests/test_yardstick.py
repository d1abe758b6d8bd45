"""The yardstick's arithmetic against hand counts: FLOPs and bytes, the
trace reduction on a small recorded table, the generator's draws."""

import json
import os

import numpy as np
import pytest

from benchmarks import flops, peaks, reduce as red, traffic, xplane

HERE = os.path.dirname(os.path.abspath(__file__))
CFG = {"hidden_size": 8, "ffn_hidden_size": 32, "num_layers": 2,
       "vocab_size": 100, "num_heads": 2, "head_dim": 4}


def test_forward_flops_hand_count():
    # per layer: qkv 8x24, out 8x8, fc 8x32 twice = 768 params -> 1536 FLOPs
    # per token; 2 layers; head 2*8*100
    assert flops.matmul_params_per_layer(CFG) == 768
    one = flops.forward_flops(CFG, 1, 1)
    assert one == 2 * 768 * 2 + 4 * 8 * 2 * 1 + 2 * 8 * 100


def test_causal_attention_is_counted_at_the_half():
    s = 2048
    assert flops.causal_context_sum(0, s) == s * (s + 1) // 2
    cfg = dict(CFG, hidden_size=2048, ffn_hidden_size=8192, num_layers=24,
               vocab_size=32768)
    per_tok = flops.train_flops_per_token(cfg, s)
    n_mat = 24 * flops.matmul_params_per_layer(cfg) + 2048 * 32768
    attn = per_tok - 6.0 * n_mat
    # 6*L*H*(S+1), not bench.py's 12*L*H*S
    assert attn == pytest.approx(6.0 * 24 * 2048 * (s + 1))
    assert attn < 0.51 * 12.0 * 24 * 2048 * s


def test_prefill_and_decode_flops():
    assert flops.prefill_flops(CFG, 3) == flops.forward_flops(
        CFG, 3, 1 + 2 + 3, head_tokens=1)
    assert flops.prefill_flops(CFG, 5, cached=2) == flops.forward_flops(
        CFG, 3, 3 + 4 + 5, head_tokens=1)
    assert flops.decode_flops(CFG, [4, 9]) == flops.forward_flops(CFG, 2, 13)


def test_flash_flops_and_bytes():
    b, h, s, d = 2, 16, 2048, 128
    fwd = flops.flash_flops(b, h, s, d, False)
    assert fwd == 2 * (2.0 * d * s * (s + 1) / 2 * h * b)
    assert flops.flash_flops(b, h, s, d, True) == 2.5 * fwd
    assert flops.flash_bytes(b, h, s, d, 2, False) == 4 * b * s * h * d * 2


def test_pages_read_for_given_lengths():
    # 1 token -> 1 page; 64 -> 1; 65 -> 2; K and V, 16 heads of 128, bf16
    page = 64 * 16 * 128 * 2
    assert flops.paged_decode_bytes([1], 64, 16, 128, 2) == 2 * page
    assert flops.paged_decode_bytes([64, 65], 64, 16, 128, 2) == 2 * 3 * page
    assert flops.paged_decode_bytes([65], 64, 16, 128, 2, layers=24) \
        == 24 * 2 * 2 * page


def test_peaks_unknown_kind_is_an_error():
    assert peaks.peaks_of("TPU v5 lite")["flops"] == 197e12
    assert peaks.peaks_of("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_of("TPU v99")


@pytest.fixture(scope="module")
def events():
    with open(os.path.join(HERE, "recorded_trace.json")) as f:
        return json.load(f)["events"]


def test_union_of_intervals():
    assert xplane.union_seconds([(0, 1), (0.5, 2), (3, 4)]) == pytest.approx(3)
    assert xplane.union_seconds([]) == 0


def test_busy_idle_and_window(events):
    # ops: [1.000,1.010] [1.010,1.030] [1.025,1.040] | while [1.100,1.110]
    # covers its body -> busy 0.040 + 0.010; window 1.000..1.110
    assert xplane.busy_seconds(events) == pytest.approx(0.050)
    assert xplane.window_seconds(events) == pytest.approx(0.110)


def test_kernel_time_by_shape_and_by_program(events):
    pool = r"custom-call\(.*\[513,64,16,128\]"
    assert xplane.seconds_matching(events, pool) == (pytest.approx(0.004), 1)
    flash = r"= \(?bf16\[[^=]* custom-call\(.*tpu_custom_call"
    assert xplane.seconds_matching(events, flash, module="jit_prefill") \
        == (pytest.approx(0.020), 1)
    assert xplane.seconds_matching(events, r"\[1,16,128,128\][^=]* custom-call",
                                   module="jit_step") == (0.0, 0)
    busy, launches = xplane.module_seconds(events, "jit_prefill")
    assert (busy, launches) == (pytest.approx(0.040), 1)
    busy, launches = xplane.module_seconds(events, "jit_step")
    assert (busy, launches) == (pytest.approx(0.010), 1)


def test_breakdown_groups_and_skips_containers(events):
    bd = xplane.breakdown(events, [("generator waiting", 1.04, 1.1)])
    ops = dict(bd["device_ops"])
    assert ops["fusion kOutput"] == pytest.approx(0.025)
    assert ops["custom-call pt.prefill -> bf16[1,16,128,128]"] == \
        pytest.approx(0.020)
    assert ops["custom-call pt.decode_step -> bf16[16,1,2048]"] == \
        pytest.approx(0.004)
    assert ops["add_fusion kLoop"] == pytest.approx(0.003)
    assert "while" not in ops
    assert bd["idle_gaps"] == [["generator waiting", pytest.approx(0.060)]]
    assert xplane.breakdown(events)["idle_gaps"][0][0] == "engine host"
    assert "custom calls by program" in xplane.inventory(events)


def test_percentile_is_nearest_rank():
    assert red.percentile(range(1, 101), 95) == 95
    assert red.percentile([5], 95) == 5
    assert red.percentile([1, 2, 3, 4], 50) == 2


MIX = {"rate_per_s": 10.0, "arrivals": {"process": "poisson"},
       "prompt": {"dist": "lognormal", "median": 192, "sigma": 0.6,
                  "min": 32, "max": 512},
       "output": {"dist": "uniform", "min": 16, "max": 64}}


def test_schedule_is_reproducible_from_the_seed():
    a = traffic.serve_schedule(MIX, 1000, 2**31 + 5, 10.0)
    b = traffic.serve_schedule(MIX, 1000, 2**31 + 5, 10.0)
    assert a == b and len(a) == 100
    assert all(0 <= r["due"] < 10.0 for r in a)
    assert all(32 <= len(r["prompt"]) <= 512 for r in a)
    assert all(16 <= r["max_new_tokens"] <= 64 for r in a)
    assert all(0 <= t < 1000 for r in a for t in r["prompt"])


def test_every_seed_sends_the_same_schedule_with_other_tokens():
    a = traffic.serve_schedule(MIX, 1000, 1, 10.0)
    b = traffic.serve_schedule(MIX, 1000, 2, 10.0)
    shape = lambda s: [(r["due"], len(r["prompt"]), r["max_new_tokens"])
                       for r in s]
    assert shape(a) == shape(b)
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    other = traffic.serve_schedule(dict(MIX, schedule_seed=9), 1000, 1, 10.0)
    assert shape(other) != shape(a)


def test_readers_find_their_programs_and_kernels_in_the_recorded_trace(events):
    """Every reader's patterns compile and match the names this runtime
    writes (the recorded table holds one prefill and one decode)."""
    import types
    from benchmarks import spec
    cell = types.SimpleNamespace(
        config={"hidden_size": 2048, "ffn_hidden_size": 8192,
                "num_layers": 1, "vocab_size": 50304, "num_heads": 16,
                "head_dim": 128,
                "engine": {"num_pages": 512, "page_size": 64}},
        traffic={})
    log = [{"prompt_len": 100, "token_times": [0.5, 0.6]}]
    traces = [{"spans": [
        {"name": "request", "t0_us": 0, "t1_us": 9e5,
         "args": {"prompt_len": 100}},
        {"name": "prefill", "t0_us": 4e5, "t1_us": 5e5, "args": {}}]}]
    art = {"events": events, "trace_window": (0.0, 1.0), "t0": 0.0,
           "log": log, "traces": traces, "cell": cell, "window_s": 1.0,
           "peaks": peaks.peaks_of("TPU v5 lite"), "end_to_end": {}}
    base = spec.Cell.__new__(spec.Cell)
    base.base = os.path.join(spec.ROOT, "benchmarks")
    got = {}
    for name in ("prefill_mfu_pct", "decode_mfu_pct",
                 "flash_roofline_pct.prefill", "paged_decode_roofline_pct",
                 "device_idle_pct.serve"):
        got[name] = base.load_module("layer_metrics", name).read(art)
        assert got[name] is not None and got[name] > 0, name
    # one page of K and V, one layer, over 819 GB/s, against 4 ms
    page = 2 * 2 * 64 * 16 * 128 * 2
    assert got["paged_decode_roofline_pct"] == pytest.approx(
        100 * page / 819e9 / 0.004)
    assert got["device_idle_pct.serve"] == pytest.approx(100 * (1 - 50 / 110))


def test_gamma_arrivals_and_shared_prefixes():
    mix = dict(MIX, arrivals={"process": "gamma", "shape": 0.5},
               shared_prefix={"tokens": 16, "groups": 2})
    s = traffic.serve_schedule(mix, 1000, 3, 10.0)
    heads = {tuple(r["prompt"][:16]) for r in s}
    assert len(heads) == 2
    due = np.array([r["due"] for r in s])
    assert np.all(np.diff(due) >= 0)


def test_train_batches_differ_by_step_and_row():
    tr = {"steps_per_launch": 1, "batch": 2, "seq": 64}
    a = traffic.train_batch(tr, 512, 7, 0)
    assert a.shape == (1, 2, 64) and a.dtype == np.int32
    assert np.array_equal(a, traffic.train_batch(tr, 512, 7, 0))
    assert not np.array_equal(a, traffic.train_batch(tr, 512, 7, 1))
    assert not np.array_equal(a[0, 0], a[0, 1])
