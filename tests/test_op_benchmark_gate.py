"""The per-op benchmark gate has teeth: committed baselines exist, the
compare logic fails on regressions, and a live CPU smoke run gates
against the committed CPU baseline.

Reference parity: tools/test_op_benchmark.sh:1 +
tools/check_op_benchmark_result.py:1 (CI fails on per-op speed
regressions against stored develop logs)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOOLS = os.path.join(ROOT, "tools")
sys.path.insert(0, TOOLS)


def _load_platform(platform):
    d = os.path.join(TOOLS, "op_baselines", platform)
    assert os.path.isdir(d), f"missing committed baseline: {d}"
    cases = {}
    for fn in os.listdir(d):
        with open(os.path.join(d, fn)) as f:
            r = json.loads(f.read().strip())
        cases[r["case"]] = r
    return cases


def test_committed_baselines_are_complete():
    """cpu_smoke carries default + promoted cases (r13: the promoted
    tier has REAL cpu baselines, only its chip number is pending);
    tpu_v5e carries exactly the default set — a promoted case showing
    up there means it should graduate into default_cases()."""
    from op_benchmark import default_cases, promoted_cases

    cpu = _load_platform("cpu_smoke")
    assert set(cpu) == set(default_cases()) | set(promoted_cases()), (
        sorted((set(default_cases()) | set(promoted_cases()))
               ^ set(cpu)))
    tpu = _load_platform("tpu_v5e")
    assert set(tpu) == set(default_cases()), (
        sorted(set(default_cases()) ^ set(tpu)))
    for cases in (cpu, tpu):
        assert all(r["avg_us"] > 0 for r in cases.values())


def test_compare_flags_regressions(tmp_path):
    from check_op_benchmark_result import compare, load_logs_dir

    dev = tmp_path / "dev"
    pr = tmp_path / "pr"
    dev.mkdir()
    pr.mkdir()
    (dev / "a.log").write_text(
        json.dumps({"case": "matmul", "avg_us": 100.0}) + "\n")
    (dev / "b.log").write_text(
        json.dumps({"case": "softmax", "avg_us": 50.0}) + "\n")
    (pr / "a.log").write_text(
        json.dumps({"case": "matmul", "avg_us": 200.0}) + "\n")  # 2x slower
    (pr / "b.log").write_text(
        json.dumps({"case": "softmax", "avg_us": 51.0}) + "\n")
    failures, checked = compare(load_logs_dir(str(dev)),
                                load_logs_dir(str(pr)), threshold=0.15)
    assert checked == 2
    assert [f[0] for f in failures] == ["matmul"]
    # and the CLI exit code mirrors the reference (8 on regression)
    r = subprocess.run(
        [sys.executable,
         os.path.join(TOOLS, "check_op_benchmark_result.py"),
         "--develop_logs_dir", str(dev), "--pr_logs_dir", str(pr)],
        capture_output=True)
    assert r.returncode == 8


def test_promoted_cases_are_real_ops_and_cpu_gated(tmp_path):
    """Promoted-tier cases (r13: real committed cpu_smoke baselines,
    tpu_v5e chip-pending — paged_attention_head_sharded,
    prefill_chunk_step, and the three fused decode-hot-path shape
    classes) must be (1) real registered dispatch entries, (2)
    disjoint from the default and pending tiers, and (3) re-measurable
    on this host within the catastrophic 4x threshold against their
    committed cpu_smoke baseline — the same live gate the default
    cases get."""
    from check_op_benchmark_result import compare, load_logs_dir
    from op_benchmark import (default_cases, pending_cases,
                              promoted_cases)

    import paddle_tpu.dispatch as dispatch

    prom = promoted_cases()
    assert prom, "drop this test when the promoted tier empties"
    assert not set(prom) & set(default_cases())
    assert not set(prom) & set(pending_cases())
    for name, builder in prom.items():
        # a case is either a registered dispatch op (possibly a named
        # shape class via builder.op_name) or a declared HOST case
        # (builder.host_fn, r23: e.g. blob_encode_decode — numpy
        # codecs with no device launch to scan)
        assert (getattr(builder, "op_name", name)
                in dispatch.wrapped_ops
                or callable(getattr(builder, "host_fn", None))), name

    dev = load_logs_dir(os.path.join(TOOLS, "op_baselines", "cpu_smoke"))
    dev = {k: v for k, v in dev.items() if k in prom}
    assert set(dev) == set(prom)

    def measure(out_dir):
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "op_benchmark.py"),
             "--platform", "cpu", "--ops", ",".join(sorted(prom)),
             "--repeat", "10", "--output", str(out_dir)],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stderr[-2000:]
        return load_logs_dir(str(out_dir))

    failures, checked = compare(dev, measure(tmp_path / "pr"),
                                threshold=4.0)
    assert checked == len(prom)
    if failures:  # transient host-load spike: reproduce before failing
        failures, _ = compare(dev, measure(tmp_path / "pr2"),
                              threshold=4.0)
    assert not failures, failures


def test_pending_cases_are_tracked_and_cpu_gated(tmp_path):
    """Pending-tier ops (benchable, but with no committed baseline on
    every platform — today: paged_attention) must be (1) real registered
    dispatch entries, (2) runnable through the harness (exit code 0, one
    well-formed log a case) and (3) accounted for in
    op_baselines/PENDING.json with the missing platform named — no
    silently unbaselined op. No wall time is compared here: this tier's
    CPU number is the dense-gather reference timed beside the other
    xdist workers, which says nothing about the kernel and failed tier-1
    on load alone (ROADMAP D5); what the kernels cost is read on the
    chip (tools/paged_decode_report.py, PERF.md)."""
    from check_op_benchmark_result import load_logs_dir
    from op_benchmark import default_cases, pending_cases

    import paddle_tpu.dispatch as dispatch

    pend = pending_cases()
    assert pend, "drop this test when the pending tier empties"
    assert not set(pend) & set(default_cases())
    with open(os.path.join(TOOLS, "op_baselines", "PENDING.json")) as f:
        tracked = json.load(f)
    assert set(tracked) == set(pend)
    for name, meta in tracked.items():
        # a case may be a named shape class of another registered op
        # (builder.op_name, e.g. prefill_chunk_step -> paged_attention)
        assert getattr(pend[name], "op_name", name) \
            in dispatch.wrapped_ops, name
        assert meta["missing"] and meta["why_missing"], name

    r = subprocess.run(
        [sys.executable, os.path.join(TOOLS, "op_benchmark.py"),
         "--platform", "cpu", "--ops", ",".join(sorted(pend)),
         "--repeat", "10", "--output", str(tmp_path / "pr")],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    logs = load_logs_dir(str(tmp_path / "pr"))
    assert set(logs) == set(pend)
    assert all(rec["avg_us"] > 0 and rec["repeat"] == 10
               for rec in logs.values()), logs


@pytest.mark.parametrize("ops", ["add,matmul,softmax,layer_norm"])
def test_cpu_smoke_gate_against_committed_baseline(tmp_path, ops):
    """Re-measure a subset on this host and gate against the committed
    CPU baseline with a catastrophic-only threshold (4x): cross-host
    variance is real, silent O(n^2) regressions are what this catches.
    The TPU baseline is gated the same way by tools/op_benchmark_tpu.sh
    on chip-attached hosts (the driver-visible path)."""
    from check_op_benchmark_result import compare, load_logs_dir

    def measure(out_dir):
        r = subprocess.run(
            [sys.executable, os.path.join(TOOLS, "op_benchmark.py"),
             "--platform", "cpu", "--ops", ops, "--repeat", "10",
             "--output", str(out_dir)],
            capture_output=True, text=True,
            env={**os.environ, "JAX_PLATFORMS": "cpu"})
        assert r.returncode == 0, r.stderr[-2000:]
        return load_logs_dir(str(out_dir))

    dev = load_logs_dir(os.path.join(TOOLS, "op_baselines", "cpu_smoke"))
    dev = {k: v for k, v in dev.items() if k in ops.split(",")}
    failures, checked = compare(dev, measure(tmp_path / "pr"),
                                threshold=4.0)
    assert checked == len(ops.split(","))
    if failures:
        # a transient host-load spike (e.g. a concurrent test lane) can
        # blow even the 4x catastrophic threshold; a regression in the
        # op itself reproduces on an immediate second measurement
        failures, _ = compare(dev, measure(tmp_path / "pr2"),
                              threshold=4.0)
    assert not failures, failures
