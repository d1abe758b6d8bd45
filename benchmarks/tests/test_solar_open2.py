"""The Solar Open 2 cell at rehearsal size on the CPU: the command end
to end, traced and untraced; the readings the limits stand on (the
program correct, the lower-precision control and each planted fault not
correct); the configuration's file against the catalog's published
keys; the operations and bytes of `flops_solar_open2.py` against hand
counts; the new readers on small recorded inputs."""

import json
from types import SimpleNamespace

import pytest

from benchmarks import common, flops_solar_open2 as fl, spec
from benchmarks.peaks import peaks_of
from benchmarks.tests.test_harness import CONTRACT_KEYS, ROOT, run_cell

CELL = "serve-solar2-longctx-sat"
NEW_READERS = ("serve_mfu_pct.hybrid", "kda_roofline_pct.prefill",
               "kda_roofline_pct.decode", "moe_expert_roofline_pct.held",
               "state_pool_occupancy_pct")
FAULTS = ("no_decay", "beta_one", "conv_tap", "gate_off", "shared_off",
          "top7")


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
    assert {"moe.touched", "moe.max_load", "moe.max_over_mean"} <= \
        set(line["notes"]["model_counters"])
    cell = spec.Cell(CELL)
    names = set(line["rehearsal_metric_names"])
    if trace:
        # what needs a device trace or a peak stays out on the CPU
        assert {"state_pool_occupancy_pct",
                "kv_pool_occupancy_pct.global"} <= names
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
    low = dict(nums, served_gap=nums["control_gap"],
               served_gap_mean=nums["control_gap_mean"])
    assert nums["control_gap_mean"] > \
        10 * cell.traffic["limits"]["served_gap_mean"]
    assert not _judge(cell, low)[0]


@pytest.mark.parametrize("fault", FAULTS)
def test_planted_fault_is_not_correct(window, fault):
    cell, driver, opts, got = window
    ref = cell.load_module("references", cell.config["reference"])
    assert set(ref.FAULTS) == set(FAULTS)
    nums = driver.check(cell, opts.seed, got["schedule"], got["win"]["log"],
                        fault=fault)
    ok, judged = _judge(cell, nums)
    assert not ok, judged
    assert nums["served_gap_mean"] > \
        10 * cell.traffic["limits"]["served_gap_mean"]


# -- the configuration's file --------------------------------------------------

@pytest.fixture(scope="module")
def cfg():
    return spec.Cell(CELL).config


def test_every_published_width_stands_and_the_cut_is_stated(cfg):
    published = {
        "model_type": "solar_open2", "hidden_size": 4096,
        "num_attention_heads": 64, "num_key_value_heads": 8,
        "head_dim": 128, "intermediate_size": 10240,
        "moe_intermediate_size": 1280, "rms_norm_eps": 1e-05,
        "max_position_embeddings": 1048576, "first_k_dense_replace": 0,
        "use_rope": False, "gqa_interval": 3, "use_gqa_gate": True,
        "kda_use_full_proj": False, "kda_allow_neg_eigval": True,
        "n_shared_experts": 1, "norm_topk_prob": True,
        "routed_scaling_factor": 1, "num_experts_per_tok": 8,
        "tie_word_embeddings": False, "rope_theta": 10000,
        "partial_rotary_factor": 1}
    for key, value in published.items():
        assert cfg[key] == value, key
    assert cfg["linear_attn_config"] == {
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 64,
        "num_kv_heads": None}
    assert cfg["gqa_layers"] == list(range(0, 48, 4))
    assert sorted(cfg["reduced"]) == ["n_routed_experts",
                                      "num_hidden_layers", "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 48,
                                "n_routed_experts": 320,
                                "vocab_size": 196608}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"], cfg["experts_held"]) == \
        (4, 40, 24576, [0, 40])
    assert cfg["vocab_size"] * 8 == 196608 and "8 chips" in cfg["deployment"]
    for key in ("kda", "convolution", "softmax_layer", "router", "expert",
                "precision", "initialisation"):
        assert cfg["assumed"][key]
    entry = {c["name"]: c for c in spec.load_benchmark()["configs"]}[
        cfg["name"]]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_the_builder_maps_the_file_onto_the_programs_config(cfg):
    builder = spec.Cell(CELL).load_module("builders", cfg["builder"])
    c = builder.program_config(cfg)
    assert (c.n_routed_experts, c.experts_held, c.num_experts_held) == \
        (320, (0, 40), 40)
    assert (c.vocab_size, c.num_hidden_layers, c.gqa_layers) == \
        (24576, 4, (0,))
    assert (c.kda_heads, c.kda_dim, c.conv_taps, c.prefill_segment) == \
        (64, 128, 4, 8192)
    shapes = {n: s for n, s, *_ in builder.leaf_table(cfg)}
    assert shapes["model.layers.{i}.router"] == (4096, 320)
    assert shapes["model.layers.{i}.w_gate"] == (40, 4096, 1280)
    assert shapes["model.layers.{i}.wqkv"] == (4096, 3 * 8192)
    assert shapes["lm_head"] == (24576, 4096)


# -- operations and bytes from shapes, against hand counts ------------------

def test_published_sizes_give_the_issue_arithmetic(cfg):
    assert fl.expert_params(cfg) == 3 * 4096 * 1280 == 15728640
    assert fl.layer_kinds(cfg) == ["softmax", "kda", "kda", "kda"]
    # the issue's: a KDA mixer 137.7 M, a softmax mixer 109.1 M
    assert fl.mixer_params(cfg, "kda") == \
        4 * 4096 * 8192 + 2 * (4096 * 128 + 128 * 8192) + 4096 * 64
    assert round(fl.mixer_params(cfg, "kda") / 1e6, 1) == 137.6
    assert fl.mixer_params(cfg, "softmax") == \
        3 * 4096 * 8192 + 2 * 4096 * 1024
    assert round(fl.mixer_params(cfg, "softmax") / 1e6, 1) == 109.1
    # a token's 8 picks, an eighth of them on the 40 experts held
    assert fl.router_width(cfg) == 320
    assert fl.picks_held_per_token(cfg) == 1.0
    assert fl.moe_params_per_token(cfg) == 4096 * 320 + 2 * 15728640
    assert fl.recurrence_flops(cfg, 10) == 6.0 * 128 * 128 * 64 * 10 * 3
    assert fl.attention_flops(cfg, 0, 4) == 4.0 * 64 * 128 * (1 + 2 + 3 + 4)
    assert fl.expert_bytes(cfg, 22) == 22 * 15728640 * 2
    assert fl.state_bytes(cfg) == 4 * 64 * 128 * 128 == 4194304
    assert fl.kda_decode_bytes(cfg, 32) == 2.0 * 4194304 * 3 * 32
    per_token = 4 * 8192 * 2 + 4 * 8192 + 4 * 64
    assert fl.kda_prefill_bytes(cfg, 1000) == \
        (per_token * 1000 + 4194304) * 3.0
    one = fl.decode_flops(cfg, [101])
    assert one == 2.0 * fl.matmul_params_per_token(cfg) \
        + 4.0 * 64 * 128 * 101 + 6.0 * 128 * 128 * 64 * 3 \
        + 2.0 * 4096 * 24576


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


def _call(name, out, start, dur):
    """A Pallas custom call as the v5e runtime names it (the whole HLO
    instruction)."""
    return {"plane": "/device:TPU:0", "line": "XLA Ops", "start": start,
            "dur": dur, "stats": {},
            "name": f"%{name} = {out} custom-call(bf16[1,8192,8192]{{2,1,0}} "
                    f"%q), custom_call_target=\"tpu_custom_call\""}


@pytest.fixture(scope="module")
def art():
    """A hand-made trace: one prefill program with two `kda_chunk_fwd`
    calls, one decode program with three `kda_decode` calls and the two
    grouped expert matmuls; a timeline of two decode records inside the
    traced seconds and one outside."""
    def module(name, start, dur):
        return {"plane": "/device:TPU:0", "line": "XLA Modules",
                "name": name, "start": start, "dur": dur, "stats": {}}
    events = [
        module("jit_prefill(11)", 1.0, 0.3), module("jit_step(12)", 1.4, .01),
        _call("kda_chunk_fwd.3", "(bf16[1,8192,8192]{2,1,0}, "
              "f32[1,64,128,128]{3,2,1,0})", 1.01, 0.05),
        _call("kda_chunk_fwd.4", "(bf16[1,8192,8192]{2,1,0}, "
              "f32[1,64,128,128]{3,2,1,0})", 1.10, 0.03),
        _call("kda_decode.1", "(bf16[32,64,128]{2,1,0}, "
              "f32[33,64,128,128]{3,2,1,0})", 1.4001, 0.0005),
        _call("kda_decode.2", "(bf16[32,64,128]{2,1,0}, "
              "f32[33,64,128,128]{3,2,1,0})", 1.401, 0.0005),
        _call("kda_decode.3", "(bf16[32,64,128]{2,1,0}, "
              "f32[33,64,128,128]{3,2,1,0})", 1.402, 0.001),
        _call("moe_ffn_in.5", "bf16[1024,1280]{1,0}", 1.404, 0.002),
        _call("moe_ffn_out.6", "bf16[1024,4096]{1,0}", 1.407, 0.001),
    ]
    timeline = [
        {"t_us": 0.5e6, "programs": {"decode": 1}, "state_slots": 20,
         "moe": {"touched": 80}},
        {"t_us": 0.6e6, "programs": {"decode": 1, "prefill": 1},
         "state_slots": 30, "moe": {"touched": 90}},
        {"t_us": 0.7e6, "programs": {"prefill": 1}, "state_slots": 31},
        {"t_us": 2.5e6, "programs": {"decode": 1}, "state_slots": 32,
         "moe": {"touched": 99}}]
    traces = [{"spans": [
        {"name": "request", "t0_us": 0, "t1_us": 9e5,
         "args": {"prompt_len": 5000}},
        {"name": "prefill", "t0_us": 4e5, "t1_us": 5e5, "args": {}}]}]
    log = [{"prompt_len": 5000, "token_times": [0.5, 0.6, 0.7]}]
    return {"events": events, "trace_window": (0.0, 1.0), "t0": 0.0,
            "log": log, "traces": traces, "timeline": timeline,
            "cell": spec.Cell(CELL), "window_s": 1.0,
            "peaks": peaks_of("TPU v5 lite"), "end_to_end": {}}


def test_readers_read_the_recorded_trace(art, cfg):
    hbm, peak = 819e9, 197e12
    got = {name: _reader(name)(art) for name in NEW_READERS}
    # the prompt's bytes bound the scan: against the 80 ms of kda_* calls
    least = max(fl.kda_prefill_flops(cfg, 5000) / peak,
                fl.kda_prefill_bytes(cfg, 5000) / hbm)
    assert least == fl.kda_prefill_bytes(cfg, 5000) / hbm
    assert got["kda_roofline_pct.prefill"] == pytest.approx(
        100 * least / 0.08)
    # the records inside the trace that launched a decode program
    assert got["kda_roofline_pct.decode"] == pytest.approx(
        100 * fl.kda_decode_bytes(cfg, 20 + 30) / hbm / 0.002)
    assert got["moe_expert_roofline_pct.held"] == pytest.approx(
        100 * fl.expert_bytes(cfg, 80 + 90) / hbm / 0.003)
    assert got["state_pool_occupancy_pct"] == pytest.approx(100 * 30 / 32)
    work = fl.prefill_flops(cfg, 5000) + fl.decode_flops(cfg, [5002, 5003])
    assert got["serve_mfu_pct.hybrid"] == pytest.approx(100 * work / peak)
    assert all(v > 0 for v in got.values())


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
    assert {"kv_pool_occupancy_pct.global", "device_idle_pct.serve",
            "decode_ahead_step_pct", "queue_wait_p95_ms"} <= reported
    # none that counts another model's shapes
    assert not reported & {"serve_mfu_pct", "serve_mfu_pct.moe",
                           "decode_mfu_pct", "prefill_mfu_pct",
                           "moe_expert_roofline_pct.decode",
                           "paged_decode_roofline_pct.window"}
    assert len(bm["workloads"]) == 7
    assert all(w["chips"] == 1 for w in bm["workloads"])
