"""The command end to end at rehearsal size on the CPU; every file that
BENCHMARK.json names is found by name; a cell, a configuration and a
per-layer metric added as new files only run without an edit; and a
run whose timed path is broken underneath comes out not correct."""

import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from benchmarks import common, spec

ROOT = spec.ROOT
BM = spec.load_benchmark()
CONTRACT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}


def run_cell(root, *args, timeout=900):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=ROOT + os.pathsep + os.environ.get("PYTHONPATH", ""))
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmarks", "run.py"), *args],
        cwd=root, env=env, capture_output=True, text=True, timeout=timeout)
    return p


def first_cell(kind):
    for w in BM["workloads"]:
        if spec.Cell(w["name"]).traffic["kind"] == kind:
            return w["name"]
    pytest.skip(f"no {kind} cell")


@pytest.mark.parametrize("kind,trace", [("train", 0), ("train", 1),
                                        ("serve", 0), ("serve", 1)])
def test_command_end_to_end_rehearsal(kind, trace):
    cell = first_cell(kind)
    p = run_cell(ROOT, "--workload", cell, "--seed", str(2**31 + 7),
                 "--seconds", "3", "--trace", str(trace), "--cpu-rehearsal")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert CONTRACT_KEYS <= set(line)
    assert list(line)[-1] == "checks"
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    assert line["device"]["platform"] == "cpu"
    assert line["metrics"] == {}  # no CPU number under a device metric's name
    c = spec.Cell(cell)
    want = {m["name"] for m in (c.per_layer if trace else c.end_to_end)}
    assert set(line["rehearsal_metric_names"]) <= want
    if not trace:
        assert set(line["rehearsal_metric_names"]) == want
    else:
        assert "breakdown" in line
    assert "[bench] check " in p.stderr


def test_no_chip_no_result():
    p = run_cell(ROOT, "--workload", BM["workloads"][0]["name"], "--seed", "1",
                 "--seconds", "1", "--trace", "0")
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_every_named_file_is_found_by_name():
    assert BM["command"][1].startswith(BM["paths"][0] + "/")
    for c in BM["configs"]:
        cfg = spec.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"]
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert os.path.exists(os.path.join(
            ROOT, BM["paths"][0], "references", cfg["reference"] + ".py"))
    e2e = {m["name"] for m in BM["end_to_end"]}
    for w in BM["workloads"]:
        cell = spec.Cell(w["name"])
        cell.load_module("drivers", cell.traffic["kind"])
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
        assert cell.per_layer, w["name"]
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
        reader = spec.Cell(m["workloads"][0]).load_module(
            "layer_metrics", m["name"])
        assert callable(reader.read)
        assert reader.read({"end_to_end": {}}) is None  # nothing to read


def test_a_cell_a_config_and_a_metric_come_as_files_only(tmp_path):
    """Copy the benchmark, ADD a configuration file, a traffic file, a
    reader and their entries, edit no file that was there, run it."""
    root = str(tmp_path)
    shutil.copytree(os.path.join(ROOT, "benchmarks"),
                    os.path.join(root, "benchmarks"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    base = os.path.join(root, "benchmarks")
    before = {}
    for d, _, fs in os.walk(base):
        for f in fs:
            with open(os.path.join(d, f), "rb") as fh:
                before[os.path.join(d, f)] = fh.read()
    serve_cell = first_cell("serve")
    old = spec.Cell(serve_cell)
    cfg = dict(old.config, name="gpt-new")
    with open(os.path.join(base, "configs", "gpt-new.json"), "w") as f:
        json.dump(cfg, f)
    mix = dict(old.traffic, arrivals={"process": "gamma", "shape": 0.5})
    with open(os.path.join(base, "traffic", "serve-new-bursts.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(base, "layer_metrics", "requests_sent.py"), "w") as f:
        f.write("def read(art):\n    return float(len(art['log'])) or None\n")
    bm = json.loads(json.dumps(BM))
    bm["configs"].append({"name": "gpt-new", "source": "test",
                          "file": "benchmarks/configs/gpt-new.json",
                          "reduced": [], "why": "test"})
    bm["workloads"].append({"name": "serve-new-bursts", "config": "gpt-new",
                            "traffic": "bursts", "chips": 1, "why": "test"})
    for m in bm["end_to_end"] + bm["per_layer"]:
        if "workloads" in m and serve_cell in m["workloads"]:
            m["workloads"].append("serve-new-bursts")
    bm["per_layer"].append({
        "name": "requests_sent", "unit": "requests", "better": "higher",
        "source": "program_counter", "layer": "harness load generator",
        "moves": "serve_tokens_per_s", "workloads": ["serve-new-bursts"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bm, f)
    p = run_cell(root, "--workload", "serve-new-bursts", "--seed", "5",
                 "--seconds", "3", "--trace", "1", "--cpu-rehearsal")
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True
    assert "requests_sent" in line["rehearsal_metric_names"]
    for path, content in before.items():
        with open(path, "rb") as fh:
            assert fh.read() == content, path


# -- a run with the timed path broken underneath is not correct -------------

def rehearse(monkeypatch, cell_name, seed=3, seconds=2.0):
    """The rest of a run in this process, without the look for a chip:
    the driver, then the judgement by the cell's own limits."""
    from types import SimpleNamespace
    cell = spec.Cell(cell_name, rehearsal=True)
    driver = cell.load_module("drivers", cell.traffic["kind"])
    return cell, driver, SimpleNamespace(seed=seed, seconds=seconds, trace=0,
                                         control=True)


def judged(cell, out):
    ok, rows = common.judge(out["numbers"], cell.traffic.get("limits", {}))
    return ok and out["failed"] == 0 and out["attempted"] > 0, rows


def test_sound_train_run_is_correct(monkeypatch):
    cell, driver, opts = rehearse(monkeypatch, first_cell("train"))
    ok, rows = judged(cell, driver.run(cell, opts))
    assert ok, rows


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_broken_train_step_is_not_correct(monkeypatch, fault):
    cell, driver, opts = rehearse(monkeypatch, first_cell("train"))
    sound = driver.launch

    def broken(step, ids):
        if fault == "half_batch":
            # half of the rows left out, the mean taken over the rest
            return sound(step, ids[:, : ids.shape[1] // 2])
        # a step that returns its state unchanged (donation off so the
        # old buffers stay alive)
        import jax
        keep = jax.tree_util.tree_map(
            lambda x: x.copy(), (step.params, step.opt_state))
        losses = sound(step, ids)
        step.params, step.opt_state = keep
        return losses
    monkeypatch.setattr(driver, "launch", broken)
    ok, rows = judged(cell, driver.run(cell, opts))
    assert not ok, rows


def test_train_control_in_lower_precision_is_not_correct(monkeypatch):
    """The reference in the program's place, its matmuls in the nearest
    precision below the configuration's: must fail a limit."""
    from benchmarks import traffic as gen
    cell, driver, opts = rehearse(monkeypatch, first_cell("train"))
    cfg, tr = cell.config, cell.traffic
    feed = [gen.train_batch(tr, cfg["vocab_size"], 3, i)
            for i in range(int(tr["check_launches"]))]
    sound = driver.reference(cell, 3, feed)
    low = driver.reference(cell, 3, feed, lowp=True)
    assert common.judge(driver.compare(sound, sound),
                        cell.traffic.get("limits", {}))[0]
    ok, rows = common.judge(driver.compare(low, sound),
                            cell.traffic.get("limits", {}))
    assert not ok, rows


def test_sound_serve_run_is_correct(monkeypatch):
    cell, driver, opts = rehearse(monkeypatch, first_cell("serve"))
    ok, rows = judged(cell, driver.run(cell, opts))
    assert ok, rows


def test_altered_token_is_not_correct(monkeypatch):
    """A token altered where it is produced: the decode program's
    output for every slot shifted by one id."""
    cell, driver, opts = rehearse(monkeypatch, first_cell("serve"))
    sound = driver.build_server

    def broken(cell, seed, traced):
        server = sound(cell, seed, traced)
        eng = server.engine
        build = eng._build_decode

        def build_broken():
            fn = build()

            def step(*a, **k):
                out = fn(*a, **k)
                return ((out[0] + 1) % cell.config["vocab_size"],) + tuple(out[1:])
            return step
        eng._build_decode = build_broken
        return server
    monkeypatch.setattr(driver, "build_server", broken)
    ok, rows = judged(cell, driver.run(cell, opts))
    assert not ok, rows


def test_serve_control_in_lower_precision_is_not_correct(monkeypatch):
    """The token the lower precision puts first, at each position of
    the same prompts and tokens: its gap must pass the limit."""
    cell, driver, opts = rehearse(monkeypatch, first_cell("serve"),
                                  seconds=5.0)
    out = driver.run(cell, opts)
    assert out["numbers"]["sampled_tokens"] >= 100
    limit = cell.traffic.get("limits", {}).get("served_gap")
    assert limit is not None
    assert out["numbers"]["served_gap"] <= limit
    assert out["numbers"]["control_gap"] > limit
    mean_limit = cell.traffic["limits"]["served_gap_mean"]
    assert out["numbers"]["served_gap_mean"] <= mean_limit
    assert out["numbers"]["control_gap_mean"] > mean_limit
