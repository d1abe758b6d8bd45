"""The GLM-4.7-Flash cell at rehearsal size on the CPU: the command end
to end, traced and untraced; the readings the limits stand on (the
program correct, the lower-precision control and each planted fault not
correct); the configuration's file against the catalog's published
keys; the operations and bytes of `flops_glm4_moe_lite.py` against hand
counts; the new readers on small recorded inputs."""

import json
from types import SimpleNamespace

import pytest

from benchmarks import common, flops_glm4_moe_lite as fl, spec
from benchmarks.peaks import peaks_of
from benchmarks.tests.test_harness import CONTRACT_KEYS, ROOT, run_cell

CELL = "serve-glm47flash-longctx-sat"
NEW_READERS = ("serve_mfu_pct.mla", "latent_decode_roofline_pct",
               "flash_roofline_pct.mla", "moe_expert_roofline_pct.top4")
FAULTS = ("rope_k_off", "kv_norm_off", "scale_192", "scaling_one",
          "shared_off", "top3")
# the catalog's entry (model-configs guide, GLM-4.7-Flash), every key
PUBLISHED = {
    "attention_bias": False, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 10240, "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite", "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc", "norm_topk_prob": True,
    "num_attention_heads": 20, "n_group": 1, "topk_group": 1,
    "n_routed_experts": 64, "n_shared_experts": 1,
    "routed_scaling_factor": 1.8, "num_experts_per_tok": 4,
    "first_k_dense_replace": 1, "num_hidden_layers": 47,
    "num_key_value_heads": 20, "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1, "rms_norm_eps": 1e-05,
    "rope_scaling": None, "rope_theta": 1000000,
    "tie_word_embeddings": False, "q_lora_rank": 768, "kv_lora_rank": 512,
    "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256,
    "vocab_size": 154880}


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
    low = dict(nums, served_gap=nums["control_gap"],
               served_gap_mean=nums["control_gap_mean"])
    assert nums["control_gap_mean"] > \
        2 * cell.traffic["limits"]["served_gap_mean"]
    assert not _judge(cell, low)[0]
    assert got["notes"]["window_ring_pages"] is None


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
        8 * cell.traffic["limits"]["served_gap_mean"]


# -- the configuration's file --------------------------------------------------

@pytest.fixture(scope="module")
def cfg():
    return spec.Cell(CELL).config


def test_every_published_key_stands_and_the_cut_is_stated(cfg):
    for key, value in PUBLISHED.items():
        if key != "num_hidden_layers":
            assert key in cfg and cfg[key] == value, key
    assert cfg["reduced"] == ["num_hidden_layers"]
    assert cfg["published"] == {"num_hidden_layers": 47}
    assert cfg["num_hidden_layers"] == 6
    assert "8 pipeline stages" in cfg["deployment"] and \
        "no layer is shared" in cfg["deployment"]
    assert "multi-token-prediction" in cfg["not_held"]
    for key in ("mla", "rope", "scale", "router", "expert", "precision",
                "initialisation", "layouts"):
        assert cfg["assumed"][key]
    assert cfg["engine"]["num_slots"] == 48
    assert cfg["engine"]["num_pages"] == 9216
    assert "640" in cfg["engine"]["note"]
    entry = {c["name"]: c for c in spec.load_benchmark()["configs"]}[
        cfg["name"]]
    assert entry["reduced"] == cfg["reduced"]
    assert entry["source"] == cfg["source"]


def test_the_builder_maps_the_file_onto_the_programs_config(cfg):
    builder = spec.Cell(CELL).load_module("builders", cfg["builder"])
    c = builder.program_config(cfg)
    assert (c.num_hidden_layers, c.n_routed_experts, c.vocab_size) == \
        (6, 64, 154880)
    assert (c.kv_lora_rank, c.qk_rope_head_dim, c.qk_head_dim,
            c.v_head_dim, c.prefill_segment) == (512, 64, 256, 256, 8192)
    assert c.routed_scaling_factor == 1.8 and c.dtype == "bfloat16"
    shapes = {n: s for n, s, *_ in builder.leaf_table(cfg)}
    assert shapes["model.layers.{i}.w_uk"] == (20, 512, 192)
    assert shapes["model.layers.{i}.w_uv"] == (20, 512, 256)
    assert shapes["model.layers.{i}.wkv_a"] == (2048, 576)
    assert shapes["model.layers.{i}.w_gate"] == (64, 2048, 1536)
    assert shapes["lm_head"] == (154880, 2048)
    # the builder's weight bytes are the issue's arithmetic: no second
    # copy of W_kvb for the absorbed path
    assert round(builder.weight_bytes(cfg) / 1e9, 2) == 7.79
    params = sum(p.size for p in _abstract(builder, cfg).parameters())
    assert builder.weight_bytes(cfg) == 2 * params + 2 * (
        6 * (2048 + 768 + 512 + 2048) + 2048 + 5 * (2048 * 64 + 64))


def _abstract(builder, cfg):
    from paddle_tpu.models import Glm4MoeLiteForCausalLM
    return Glm4MoeLiteForCausalLM(builder.program_config(cfg), abstract=True)


# -- operations and bytes from shapes, against hand counts ------------------

def test_published_sizes_give_the_issue_arithmetic(cfg):
    assert fl.expert_params(cfg) == 3 * 2048 * 1536 == 9437184
    # the issue's: q_a 1.57, q_b 3.93, kv_a 1.18, kv_b 4.59, o 10.49 M
    assert fl.mixer_params(cfg) == 2048 * 768 + 768 * 20 * 256 \
        + 2048 * 576 + 512 * 20 * 448 + 20 * 256 * 2048
    assert round(fl.mixer_params(cfg) / 1e6, 2) == 21.76
    assert (fl.dense_layers(cfg), fl.expert_layers(cfg)) == (1, 5)
    assert fl.dense_ffn_params(cfg) == 3 * 2048 * 10240
    assert fl.moe_params_per_token(cfg) == 2048 * 64 + 5 * 9437184
    assert fl.matmul_params_per_token(cfg) == 6 * fl.mixer_params(cfg) \
        + 3 * 2048 * 10240 + 5 * (2048 * 64 + 5 * 9437184)
    assert fl.latent_row(cfg) == 576
    assert fl.expanded_flops_per_key(cfg) == 2.0 * 20 * 512
    # 43.5 kFLOP a position a layer, 38 FLOP a required byte
    assert fl.absorbed_flops_per_key(cfg) == 2.0 * 20 * (576 + 512) == 43520
    assert round(43520 / 1152) == 38
    assert fl.flash_flops(cfg, 4) == 2.0 * 20 * 512 * (1 + 2 + 3 + 4) * 6
    assert fl.expert_bytes(cfg, 22) == 22 * 9437184 * 2
    assert fl.latent_decode_bytes(cfg, [100, 11]) == 111 * 1152 * 6
    one = fl.decode_flops(cfg, [101])
    assert one == 2.0 * fl.matmul_params_per_token(cfg) \
        + 43520.0 * 101 * 6 + 2.0 * 2048 * 154880
    assert fl.prefill_flops(cfg, 7) == \
        2.0 * fl.matmul_params_per_token(cfg) * 7 \
        + fl.flash_flops(cfg, 7) + 2.0 * 2048 * 154880


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
            "name": f"%{name} = {out} custom-call(bf16[1,8192,20,256]"
                    f"{{3,2,1,0}} %q), custom_call_target=\"tpu_custom_call\""}


@pytest.fixture(scope="module")
def art():
    """A hand-made trace: one prefill program with two flash calls, one
    decode program with two latent decode calls and the two grouped
    expert matmuls (and a latent call OUTSIDE any decode program, which
    no reader may count); a timeline of two decode records inside the
    traced seconds and one outside."""
    def module(name, start, dur):
        return {"plane": "/device:TPU:0", "line": "XLA Modules",
                "name": name, "start": start, "dur": dur, "stats": {}}
    events = [
        module("jit_prefill(11)", 1.0, 0.3), module("jit_step(12)", 1.4, .01),
        _call("flash_fwd.3", "(bf16[1,20,8192,256]{3,2,1,0}, "
              "f32[1,20,8192,128]{3,2,1,0})", 1.01, 0.05),
        _call("flash_fwd_single.4", "(bf16[1,20,512,256]{3,2,1,0}, "
              "f32[1,20,512,128]{3,2,1,0})", 1.10, 0.03),
        _call("paged_decode_latent.1", "bf16[48,32,512]{2,1,0}", 1.4001,
              0.0005),
        _call("paged_decode_latent.2", "bf16[48,32,512]{2,1,0}", 1.401,
              0.0015),
        _call("paged_decode_latent.9", "bf16[48,32,512]{2,1,0}", 1.45,
              0.0015),
        _call("moe_ffn_in.5", "bf16[1024,1536]{1,0}", 1.404, 0.002),
        _call("moe_ffn_out.6", "bf16[1024,2048]{1,0}", 1.407, 0.001),
    ]
    timeline = [
        {"t_us": 0.5e6, "programs": {"decode": 1}, "moe": {"touched": 80}},
        {"t_us": 0.6e6, "programs": {"decode": 1, "prefill": 1},
         "moe": {"touched": 90}},
        {"t_us": 0.7e6, "programs": {"prefill": 1}},
        {"t_us": 2.5e6, "programs": {"decode": 1}, "moe": {"touched": 99}}]
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
    # the two tokens decoded in the trace, against the 2 ms of latent
    # calls inside the decode program
    assert got["latent_decode_roofline_pct"] == pytest.approx(
        100 * (5002 + 5003) * 1152 * 6 / hbm / 0.002)
    # the prompt's causal keys against the 80 ms of flash calls
    assert got["flash_roofline_pct.mla"] == pytest.approx(
        100 * fl.flash_flops(cfg, 5000) / peak / 0.08)
    assert got["moe_expert_roofline_pct.top4"] == pytest.approx(
        100 * fl.expert_bytes(cfg, 80 + 90) / hbm / 0.003)
    work = fl.prefill_flops(cfg, 5000) + fl.decode_flops(cfg, [5002, 5003])
    assert got["serve_mfu_pct.mla"] == pytest.approx(100 * work / peak)
    assert all(v > 0 for v in got.values())


def test_every_new_entry_lists_the_new_cell_alone():
    bm = spec.load_benchmark()
    by_name = {m["name"]: m for m in bm["per_layer"]}
    for name in NEW_READERS:
        assert by_name[name]["workloads"] == [CELL]
        assert by_name[name]["moves"] == "serve_tokens_per_s"
    assert [m["name"] for m in bm["per_layer"][-4:]] == list(NEW_READERS)
    assert bm["workloads"][-1]["name"] == CELL
    assert bm["configs"][-1]["name"] == "glm-4.7-flash-serve"
    cell = spec.Cell(CELL)
    assert {m["name"] for m in cell.end_to_end} == \
        {"serve_tokens_per_s", "setup_s"}
    reported = {m["name"] for m in cell.per_layer}
    assert set(NEW_READERS) <= reported
    assert {"kv_pool_occupancy_pct.global", "device_idle_pct.serve",
            "decode_ahead_step_pct", "decode_resident_step_pct",
            "queue_wait_p95_ms", "generator_lag_p95_ms",
            "engine_thread_cpu_pct", "engine_thread_pct.wait"} <= reported
    # none that counts another model's shapes
    assert not reported & {"serve_mfu_pct", "serve_mfu_pct.moe",
                           "serve_mfu_pct.hybrid", "decode_mfu_pct",
                           "prefill_mfu_pct", "kda_roofline_pct.decode",
                           "moe_expert_roofline_pct.held",
                           "moe_expert_roofline_pct.decode",
                           "state_pool_occupancy_pct",
                           "paged_decode_roofline_pct.window"}
    assert all(w["chips"] == 1 for w in bm["workloads"])
    # the mix is the long-context cell's letter for letter but the rate
    other = spec.Cell("serve-solar2-longctx-sat").traffic
    mine = cell.traffic
    for key in ("kind", "arrivals", "prompt", "output", "drain_s",
                "check_sample", "trace_seconds", "schedule_seed"):
        assert mine[key] == other[key], key
    assert mine["rate_per_s"] == pytest.approx(1.3 * mine["knee_per_s"],
                                               abs=0.011)
