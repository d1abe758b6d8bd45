"""Fleet telemetry plane (r17): collector merge exactness, live SLO
monitor, outlier detection, probe-failure classification, crash flight
recorder, and the router's fleet surface.

The contracts this file pins (ISSUE r17 acceptance):

- fleet histogram merges are BUCKET-EXACT: merged ``_count``/
  ``_sum``/``_bucket`` equal the sum of the replica exports, +Inf
  overflow included; interpolated fleet quantiles land within a
  bucket width of the single-replica reservoir quantiles;
- a replica that dies mid-scrape is dropped from the rollup and
  marked stale — fleet totals are never poisoned by a corpse;
- the live SLO monitor counts the same lifecycle markers the traces
  carry, per class, over a rolling window, and merges by summing;
- the pressure verdict only flips after ``hysteresis`` consecutive
  identical raw verdicts;
- probe failures are classified (timeout/refused/malformed/...) and
  exported with restarts + backoff state through fleet_stats;
- flight bundles are written atomically, pruned to a byte budget
  (newest always kept), and lint clean via tools/flight_inspect.py.
"""

import importlib.util
import json
import os
import re
import socket
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.distributed import fault_inject as fi
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving.fleet_metrics import (FleetMetrics,
                                              FlightRecorder,
                                              PressureMonitor,
                                              merge_slo_exports,
                                              prometheus_export_lines,
                                              robust_zscores)
from paddle_tpu.serving.metrics import (Histogram, ServingMetrics,
                                        SLOAttainment,
                                        attainment_from_export,
                                        export_snapshot, merge_exports,
                                        quantile_from_buckets)
from paddle_tpu.serving.server import ServingServer, client_request
from paddle_tpu.serving.supervisor import (FailoverRouter, Supervisor,
                                           classify_probe_failure)

REPO = Path(__file__).resolve().parent.parent


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, REPO / "tools" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


flight_inspect = _load_tool("flight_inspect")


@pytest.fixture(autouse=True)
def _clean_injector():
    fi.reset()
    yield
    fi.reset()


@pytest.fixture(scope="module", autouse=True)
def _compile_cache(module_compile_cache):
    yield


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


ENGINE_KW = dict(num_slots=2, page_size=8, max_seq_len=96, num_pages=24)


def _server(m, **kw):
    merged = dict(ENGINE_KW)
    merged.update(kw)
    merged.setdefault("metrics", ServingMetrics(registry=StatRegistry()))
    return ServingServer(m, **merged)


# the exposition grammar (same regexes the r16 registry audit uses)
_PROM_TYPE = re.compile(
    r"^# TYPE ([a-zA-Z_:][a-zA-Z0-9_:]*) (counter|gauge|histogram)$")
_PROM_SAMPLE = re.compile(
    r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{[^{}]*\})? "
    r"(-?[0-9]+(\.[0-9]+)?([eE][+-]?[0-9]+)?|NaN|[+-]Inf)$")
_LABEL = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="([^"\\\n]*)"')


def _mk_export(n=4, ttft=5.0, step=1.0, errors=0, steps=10,
               slo_targets=(100.0, 10.0), queued=0.0, inflight=0.0,
               slots=4.0):
    """Synthetic ServingMetrics.export() with n finished requests."""
    m = ServingMetrics(registry=StatRegistry(),
                       slo=SLOAttainment(ttft_ms=slo_targets[0],
                                         tpot_ms=slo_targets[1]))
    for _ in range(n):
        m.ttft_ms.observe(ttft)
        m.tpot_ms.observe(step)
        m.step_ms.observe(step)
        m.slo.observe(1, ttft / 1e3, step / 1e3)
        m.counter("requests_total").add()
    if errors:
        m.counter("engine_errors_total").add(errors)
    e = m.export()
    e["gauges"] = {"queued_requests": queued, "inflight_slots": inflight,
                   "num_slots": slots, "prefill_debt_tokens": 0.0,
                   "engine_steps": float(steps)}
    return e


# ---------------------------------------------------------------------------
# Histogram.export() / merge_exports() (satellite: unit coverage)
# ---------------------------------------------------------------------------

class TestHistogramExportMerge:
    def test_export_counts_are_noncumulative_and_sum_to_total(self):
        h = Histogram("t.x")
        for v in (0.2, 3.0, 40.0, 99999.0):  # last lands in +Inf
            h.observe(v)
        e = h.export()
        assert sum(e["counts"]) == e["total"] == 4
        assert len(e["counts"]) == len(e["buckets"]) + 1
        assert e["counts"][-1] == 1  # the +Inf overflow slot
        assert "samples" not in e  # reservoirs don't travel

    def test_merge_is_bucket_exact_including_inf(self):
        hs = [Histogram("t.x") for _ in range(3)]
        rng = np.random.default_rng(0)
        for i, h in enumerate(hs):
            for v in rng.exponential(10.0 * (i + 1), size=50):
                h.observe(float(v))
            h.observe(1e9)  # force +Inf mass on every replica
        exports = [h.export() for h in hs]
        m = merge_exports(exports)
        # THE acceptance pin: fleet _count/_sum/_bucket == sum of
        # replica exports, element-wise, +Inf included
        assert m["total"] == sum(e["total"] for e in exports)
        assert m["sum"] == pytest.approx(
            sum(e["sum"] for e in exports))
        for i in range(len(m["counts"])):
            assert m["counts"][i] == sum(e["counts"][i]
                                         for e in exports)

    def test_merge_rejects_ladder_mismatch(self):
        a = Histogram("t.a").export()
        b = Histogram("t.b", buckets=(1.0, 2.0)).export()
        with pytest.raises(ValueError):
            merge_exports([a, b])

    def test_empty_replica_merges_as_identity(self):
        h = Histogram("t.x")
        for v in (1.0, 7.0):
            h.observe(v)
        alone = h.export()
        with_empty = merge_exports([h.export(),
                                    Histogram("t.x").export()])
        assert with_empty["counts"] == alone["counts"]
        assert with_empty["total"] == alone["total"]
        assert with_empty["sum"] == alone["sum"]

    def test_merge_of_nothing_is_empty(self):
        m = merge_exports([])
        assert m["total"] == 0
        assert quantile_from_buckets(m, 50) is None

    def test_interpolated_quantiles_track_reservoir_on_one_replica(
            self):
        """Single replica: the bucket-interpolated quantile must land
        within its containing bucket's width of the reservoir-exact
        percentile (the precision traded for mergeability)."""
        h = Histogram("t.x")
        rng = np.random.default_rng(1)
        for v in rng.gamma(2.0, 8.0, size=2000):
            h.observe(float(v))
        e = h.export()
        for p in (50, 90, 99):
            exact = h.percentile(p)
            interp = quantile_from_buckets(e, p)
            # containing-bucket width at the exact value
            edges = [0.0] + list(e["buckets"])
            width = None
            for lo, hi in zip(edges, edges[1:]):
                if lo <= exact <= hi:
                    width = hi - lo
                    break
            assert width is not None, f"p{p}={exact} out of ladder"
            assert abs(interp - exact) <= width, (p, exact, interp)

    def test_inf_quantile_clamps_to_top_edge(self):
        h = Histogram("t.x")
        for _ in range(10):
            h.observe(1e9)  # all mass in +Inf
        e = h.export()
        assert quantile_from_buckets(e, 99) == e["buckets"][-1]

    def test_export_snapshot_shape(self):
        h = Histogram("t.x")
        h.observe(5.0)
        s = export_snapshot(h.export())
        assert s["count"] == 1 and s["mean"] == 5.0
        assert s["p50"] is not None


# ---------------------------------------------------------------------------
# Live SLO monitor
# ---------------------------------------------------------------------------

class TestSLOAttainment:
    def test_per_class_counting(self):
        s = SLOAttainment(ttft_ms=100, tpot_ms=10)
        s.observe(2, 0.05, 0.005)   # interactive: met
        s.observe(2, 0.5, 0.005)    # interactive: ttft miss
        s.observe(0, 0.01, 0.05)    # batch: tpot miss
        att = s.attainment()
        assert att["interactive"] == 0.5
        assert att["batch"] == 0.0
        assert att["all"] == pytest.approx(1 / 3)

    def test_missing_marker_counts_as_met(self):
        s = SLOAttainment(ttft_ms=100, tpot_ms=10)
        s.observe(1, 0.05, None)  # 1-token request: no TPOT
        assert s.attainment()["all"] == 1.0

    def test_window_prunes_old_events(self):
        s = SLOAttainment(ttft_ms=100, window_s=10.0)
        s.observe(1, 0.5, None, now=100.0)   # miss, old
        s.observe(1, 0.05, None, now=150.0)  # met, fresh
        att = attainment_from_export(s.export(now=155.0))
        assert att["all"] == 1.0  # the old miss aged out

    def test_set_targets_resets_window(self):
        s = SLOAttainment(ttft_ms=100)
        s.observe(1, 0.5, None)
        s.set_targets(1000, None)
        assert s.attainment()["all"] is None  # fresh window

    def test_unconfigured_tracker_is_inert(self):
        s = SLOAttainment()
        assert not s.configured
        s.observe(1, 99.0, 99.0)
        assert s.attainment()["all"] == 1.0  # nothing binding

    def test_merge_sums_counts(self):
        a, b = SLOAttainment(ttft_ms=100), SLOAttainment(ttft_ms=100)
        a.observe(1, 0.05, None)
        a.observe(1, 0.5, None)
        b.observe(1, 0.05, None)
        m = merge_slo_exports([a.export(), b.export()])
        assert m["classes"]["normal"]["total"] == 3
        assert m["classes"]["normal"]["met"] == 2
        assert attainment_from_export(m)["all"] == pytest.approx(2 / 3)
        assert m["ttft_ms"] == 100.0


class TestPressureMonitor:
    def test_hysteresis_gates_the_flip(self):
        pm = PressureMonitor(hysteresis=3)
        assert pm.verdict == "steady"
        for i in range(2):
            r = pm.evaluate(0.5, 0.0, 0.0, 0.5)  # attainment collapse
            assert r["verdict"] == "steady"  # not yet
            assert r["raw"] == "scale_up"
        r = pm.evaluate(0.5, 0.0, 0.0, 0.5)
        assert r["verdict"] == "scale_up"  # third consecutive

    def test_flap_resets_streak(self):
        pm = PressureMonitor(hysteresis=2)
        pm.evaluate(0.5, 0.0, 0.0, 0.5)   # raw scale_up (1)
        pm.evaluate(0.95, 2.0, 0.0, 0.5)  # raw steady: streak broken
        r = pm.evaluate(0.5, 0.0, 0.0, 0.5)
        assert r["verdict"] == "steady"   # single raw, no flip

    def test_queue_and_debt_drive_scale_up(self):
        pm = PressureMonitor(hysteresis=1, queue_high=4.0)
        assert pm.evaluate(None, 10.0, 0.0, 0.5)["verdict"] == \
            "scale_up"
        pm2 = PressureMonitor(hysteresis=1, debt_high=100.0)
        assert pm2.evaluate(None, 0.0, 5000.0, 0.5)["verdict"] == \
            "scale_up"

    def test_idle_attained_fleet_hints_scale_down(self):
        pm = PressureMonitor(hysteresis=1)
        r = pm.evaluate(1.0, 0.0, 0.0, 0.05)
        assert r["verdict"] == "scale_down"
        # loaded-but-attaining stays steady
        pm2 = PressureMonitor(hysteresis=1)
        assert pm2.evaluate(1.0, 2.0, 0.0, 0.9)["verdict"] == "steady"


# ---------------------------------------------------------------------------
# Outlier detection + collector staleness
# ---------------------------------------------------------------------------

class TestOutliers:
    def test_robust_zscores_basics(self):
        assert robust_zscores({0: 1.0, 1: 2.0}) == {0: 0.0, 1: 0.0}
        z = robust_zscores({0: 1.0, 1: 1.1, 2: 0.9, 3: 50.0})
        assert z[3] > 3.5 and abs(z[0]) < 2.0

    def test_degenerate_spread_still_flags(self):
        # identical fleet + one 2x replica: MAD is 0, the fallback
        # median-ratio path must still produce a large score
        z = robust_zscores({0: 10.0, 1: 10.0, 2: 10.0, 3: 20.0})
        assert z[3] > 3.5
        assert z[0] == 0.0

    def test_fleet_flags_slow_replica(self):
        fm = FleetMetrics()
        for i in range(3):
            slow = i == 2
            # two scrapes with GROWING totals: the detector reads the
            # most recent interval's deltas, not lifetime means
            fm.ingest(i, _mk_export(n=2, step=40.0 if slow else 1.0))
            fm.ingest(i, _mk_export(n=6, step=40.0 if slow else 1.0))
        snap = fm.fleet_snapshot()
        assert "2" in snap["outliers"]
        assert "0" not in snap["outliers"]
        sig = snap["outliers"]["2"]
        assert "step_ms" in sig and sig["step_ms"]["z"] > 3.5
        assert snap["collector"]["outlier_flags_total"] == 1
        # re-snapshot: same flag, counter not double-charged
        assert fm.fleet_snapshot()["collector"][
            "outlier_flags_total"] == 1

    def test_mid_scrape_death_drops_replica_from_rollup(self):
        """THE staleness pin: a replica that dies between scrapes
        keeps its last export (postmortem) but is excluded from fleet
        totals — merged counts equal the sum of FRESH replicas only."""
        fm = FleetMetrics()
        for i in range(3):
            fm.ingest(i, _mk_export(n=4))
        fm.mark_stale(2)
        snap = fm.fleet_snapshot()
        assert snap["replicas_fresh"] == 2
        assert snap["replicas_known"] == 3
        assert snap["per_replica"]["2"]["stale"] is True
        assert snap["per_replica"]["0"]["stale"] is False
        # fleet totals: exactly the two fresh replicas
        assert snap["counters"]["requests_total"] == 8
        assert snap["histogram_exports"]["ttft_ms"]["total"] == 8
        assert snap["slo"]["classes"]["normal"]["total"] == 8
        # and the exposition agrees
        text = fm.prometheus_text()
        assert 'replica="2"' not in text
        assert "fleet_requests_total 8" in text

    def test_idle_replica_presents_no_stale_signals(self):
        """A replica with a bad past but a quiescent present must
        NOT keep reporting its lifetime means to the detector: a
        scrape interval with no new observations yields None signals
        (and so cannot be flagged)."""
        fm = FleetMetrics()
        for i in range(3):
            slow = i == 2
            fm.ingest(i, _mk_export(n=4, step=40.0 if slow else 1.0))
        # second scrape round: everyone idle (same totals)
        for i in range(3):
            slow = i == 2
            fm.ingest(i, _mk_export(n=4, step=40.0 if slow else 1.0))
        snap = fm.fleet_snapshot()
        assert snap["per_replica"]["2"]["signals"]["step_ms"] is None
        assert snap["outliers"] == {}

    def test_outlier_flags_stay_current_without_snapshot_polls(self):
        """The router's deprioritization path reads outliers()
        directly — flags must advance with scrape generations even
        if nothing ever calls fleet_snapshot."""
        fm = FleetMetrics()
        for i in range(3):
            fm.ingest(i, _mk_export(n=2, step=1.0))
        for i in range(3):
            fm.ingest(i, _mk_export(n=6,
                                    step=40.0 if i == 2 else 1.0))
        assert set(fm.outliers()) == {2}

    def test_pressure_streak_is_generation_gated(self):
        """Polling fleet_snapshot faster than the scrape cycle must
        not advance the hysteresis streak: between ingests, repeated
        snapshots return the cached verdict."""
        fm = FleetMetrics(pressure=PressureMonitor(hysteresis=2),
                          pressure_interval_s=0.0)
        for i in range(3):
            fm.ingest(i, _mk_export(n=2, queued=50.0))  # overload
        first = fm.fleet_snapshot()["pressure"]
        assert first["raw"] == "scale_up"
        for _ in range(5):  # poll storm, no new telemetry
            again = fm.fleet_snapshot()["pressure"]
            assert again["streak"] == first["streak"]
            assert again["verdict"] == first["verdict"] == "steady"
        # a new scrape generation advances the streak and flips
        for i in range(3):
            fm.ingest(i, _mk_export(n=4, queued=50.0))
        assert fm.fleet_snapshot()["pressure"]["verdict"] == \
            "scale_up"

    def test_one_bursty_cycle_cannot_flip_the_verdict(self):
        """Interleaved readers between the N per-replica ingests of
        one scrape cycle must not consume the hysteresis: pressure
        advances at most once per pressure_interval_s (default 1 s),
        so a single bursty cycle steps the streak once."""
        fm = FleetMetrics(pressure=PressureMonitor(hysteresis=3))
        for i in range(3):
            fm.ingest(i, _mk_export(n=2 + i, queued=50.0))
            fm.outliers()  # a router pick between ingests
            p = fm.fleet_snapshot()["pressure"]
        assert p["streak"] <= 1
        assert p["verdict"] == "steady"

    def test_telemetry_blackout_is_not_an_idle_fleet(self):
        """Zero fresh replicas = no evidence, not 'attained and
        idle': during a scrape blackout the pressure hint must hold
        the last published verdict with raw=no_data — never drift
        toward scale_down on an overloaded-but-unobservable fleet."""
        fm = FleetMetrics(pressure=PressureMonitor(hysteresis=1))
        for i in range(3):
            fm.ingest(i, _mk_export(n=2, queued=50.0))
        assert fm.fleet_snapshot()["pressure"]["verdict"] == \
            "scale_up"
        for i in range(3):  # every scrape fails
            fm.mark_stale(i)
        p = fm.fleet_snapshot()["pressure"]
        assert p["raw"] == "no_data"
        assert p["verdict"] == "scale_up"  # held, not flipped

    def test_aged_out_replica_leaves_rollup_without_generation_bump(
            self, monkeypatch):
        """Freshness depends on wall time: a replica whose export
        ages past stale_after_s must fall out of the rollup even
        when nothing calls mark_stale (wedged monitor thread) — the
        evaluation cache re-checks at least every second."""
        fm = FleetMetrics(stale_after_s=5.0)
        for i in range(2):
            fm.ingest(i, _mk_export(n=2))
        assert fm.fleet_snapshot()["replicas_fresh"] == 2
        real = time.monotonic
        monkeypatch.setattr(time, "monotonic", lambda: real() + 30.0)
        assert fm.fleet_snapshot()["replicas_fresh"] == 0

    def test_stale_replica_rejoins_on_next_ingest(self):
        fm = FleetMetrics()
        for i in range(2):
            fm.ingest(i, _mk_export(n=1))
        fm.mark_stale(1)
        assert fm.fleet_snapshot()["replicas_fresh"] == 1
        fm.ingest(1, _mk_export(n=1))
        assert fm.fleet_snapshot()["replicas_fresh"] == 2


# ---------------------------------------------------------------------------
# Fleet exposition (satellite: registry audit extended to the fleet)
# ---------------------------------------------------------------------------

class TestFleetExposition:
    def _fleet(self, n=3):
        fm = FleetMetrics()
        for i in range(n):
            fm.ingest(i, _mk_export(n=2 + i))
        return fm

    def _families(self, text):
        fams = {}
        for line in text.splitlines():
            m = _PROM_TYPE.match(line)
            if m:
                fams[m.group(1)] = m.group(2)
        return fams

    def test_exposition_parses_line_by_line(self):
        text = self._fleet().prometheus_text()
        assert text.endswith("\n")
        for line in text.splitlines():
            if not line:
                continue
            assert _PROM_TYPE.match(line) or _PROM_SAMPLE.match(line), \
                f"unparseable exposition line: {line!r}"

    def test_replica_label_values_well_formed(self):
        text = self._fleet().prometheus_text()
        saw = set()
        for line in text.splitlines():
            m = _PROM_SAMPLE.match(line)
            if not m or not m.group(2):
                continue
            labels = dict(_LABEL.findall(m.group(2)))
            # every labeled char was consumed by the label grammar
            reconstructed = "{" + ",".join(
                f'{k}="{v}"' for k, v in _LABEL.findall(
                    m.group(2))) + "}"
            assert reconstructed == m.group(2), line
            if "replica" in labels:
                assert re.fullmatch(r"[0-9]+", labels["replica"]), line
                saw.add(labels["replica"])
        assert saw == {"0", "1", "2"}

    def test_counter_families_end_total_and_no_collisions(self):
        text = self._fleet().prometheus_text()
        fams = self._families(text)
        hist = {n for n, t in fams.items() if t == "histogram"}
        counters = {n for n, t in fams.items() if t == "counter"}
        gauges = {n for n, t in fams.items() if t == "gauge"}
        for c in counters:
            assert c.endswith("_total"), c
            assert c[:-len("_total")] not in hist, c
        for h in hist:
            assert not h.endswith("_total"), h
            for sfx in ("_bucket", "_sum", "_count"):
                assert h + sfx not in counters | gauges | hist, h

    def test_fleet_rollups_and_replica_series_are_distinct_families(
            self):
        """The collision the satellite names: an UNLABELED rollup in
        a replica-labeled family would be ambiguous — rollups must
        live in their own fleet_* families."""
        text = self._fleet().prometheus_text()
        fams = self._families(text)
        serving = {f for f in fams if f.startswith("serving_")}
        fleet = {f for f in fams if f.startswith("fleet_")}
        assert serving and fleet
        assert not serving & fleet
        # every serving_* SAMPLE carries a replica label; no fleet_*
        # sample does
        for line in text.splitlines():
            m = _PROM_SAMPLE.match(line)
            if not m:
                continue
            if m.group(1).startswith("serving_"):
                assert m.group(2) and "replica=" in m.group(2), line
            if m.group(1).startswith("fleet_"):
                assert "replica=" not in (m.group(2) or ""), line

    def test_fleet_bucket_lines_equal_replica_sums(self):
        """Acceptance pin, exposition edition: each fleet _bucket/
        _sum/_count line equals the sum over the replica-labeled
        lines of the same family."""
        fm = self._fleet()
        text = fm.prometheus_text()
        per_bucket: dict = {}
        fleet_bucket: dict = {}
        for line in text.splitlines():
            m = _PROM_SAMPLE.match(line)
            if not m:
                continue
            name, labels, val = m.group(1), m.group(2) or "", \
                m.group(3)
            le = dict(_LABEL.findall(labels)).get("le")
            if name == "serving_ttft_ms_bucket":
                per_bucket[le] = per_bucket.get(le, 0) + float(val)
            elif name == "fleet_ttft_ms_bucket":
                fleet_bucket[le] = float(val)
        assert fleet_bucket and per_bucket
        assert fleet_bucket == per_bucket

    def test_malformed_label_value_raises(self):
        with pytest.raises(ValueError):
            prometheus_export_lines(_mk_export(),
                                    labels={"replica": 'a"b'})

    def test_type_lines_unique_and_families_contiguous(self):
        """Strict text-format contract: each family declares # TYPE
        exactly once and all its samples form one contiguous group —
        per-replica blocks would interleave families and re-declare
        TYPEs (the bug this pins out)."""
        text = self._fleet().prometheus_text()
        seen_types: set = set()
        closed_families: set = set()
        current = None
        for line in text.splitlines():
            tm = _PROM_TYPE.match(line)
            if tm:
                fam = tm.group(1)
                assert fam not in seen_types, \
                    f"duplicate TYPE line for {fam}"
                seen_types.add(fam)
                if current is not None:
                    closed_families.add(current)
                current = fam
                continue
            sm = _PROM_SAMPLE.match(line)
            if sm and current is not None:
                # a sample must belong to the family declared by the
                # nearest preceding TYPE line (histograms append
                # _bucket/_sum/_count)
                name = sm.group(1)
                assert name == current or name.startswith(
                    current + "_"), (name, current)
                assert not any(
                    name == f or name.startswith(f + "_")
                    for f in closed_families
                    if len(f) >= len(current)), \
                    f"family {name} resumed after being closed"

    def test_fleet_slo_attainment_gauge(self):
        text = self._fleet().prometheus_text()
        assert "# TYPE fleet_slo_attainment gauge" in text
        assert 'fleet_slo_attainment{class="all"} 1' in text


# ---------------------------------------------------------------------------
# Probe-failure classification (satellite)
# ---------------------------------------------------------------------------

class TestProbeClassification:
    def test_classification_table(self):
        assert classify_probe_failure(None) == "malformed"
        assert classify_probe_failure(socket.timeout()) == "timeout"
        assert classify_probe_failure(
            ConnectionRefusedError()) == "refused"
        assert classify_probe_failure(
            ConnectionResetError()) == "reset"
        assert classify_probe_failure(
            json.JSONDecodeError("x", "", 0)) == "torn_json"
        assert classify_probe_failure(
            ConnectionError("closed")) == "closed"
        assert classify_probe_failure(OSError(9, "x")) == "os_error"
        assert classify_probe_failure(ValueError("x")) == "error"

    def test_monitor_loop_counts_refused_probes(self):
        """A live process on a dead port: every probe is REFUSED and
        the classification counter says so (the old code collapsed this
        into a bare ok=False)."""
        sup = Supervisor(model="gpt_tiny", replicas=1,
                         probe_interval_s=0.05, probe_timeout_s=0.2,
                         ready_timeout_s=30.0, backoff_base_s=3600)
        rep = sup.replicas[0]
        rep.port = 1  # nothing listens
        rep.proc = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(600)"])
        rep.spawn_t = time.monotonic()
        t = threading.Thread(target=sup._monitor_loop, daemon=True)
        t.start()
        try:
            for _ in range(100):
                if rep.probe_failures_by_kind.get("refused", 0) >= 2:
                    break
                time.sleep(0.05)
            assert rep.probe_failures_by_kind.get("refused", 0) >= 2
            assert rep.last_probe_error.startswith("refused:")
            fs = sup.fleet_stats()
            s0 = fs["supervision"]["0"]
            assert s0["probe_failures_by_kind"]["refused"] >= 2
            assert "restarts" in s0 and "backoff_remaining_s" in s0
            assert fs["restarts_total"] == 0
        finally:
            sup._stop.set()
            t.join(timeout=2.0)
            rep.proc.kill()
            rep.proc.wait(timeout=5)


# ---------------------------------------------------------------------------
# Flight recorder + inspector (satellite)
# ---------------------------------------------------------------------------

def _bundle_payload(n_steps=3):
    return {"model": "stub", "engine": {"steps": n_steps},
            "recipe": {}, "restarts": 0, "consec_errors": 0,
            "step_timeline": [{"step": i, "ms": 1.0}
                              for i in range(n_steps)],
            "traces": [], "events": [],
            "metrics": ServingMetrics(registry=StatRegistry()).export(),
            "inflight": []}


class TestFlightRecorder:
    def test_atomic_write_no_tmp_left(self, tmp_path):
        fr = FlightRecorder(str(tmp_path), min_interval_s=0.0)
        p = fr.record("stall", _bundle_payload)
        assert p is not None and os.path.exists(p)
        assert not [f for f in os.listdir(tmp_path)
                    if f.endswith(".tmp")]
        obj = json.load(open(p))
        assert obj["reason"] == "stall" and obj["pid"] == os.getpid()
        assert flight_inspect.lint_bundle(obj) == []

    def test_rate_limit_per_reason(self, tmp_path):
        fr = FlightRecorder(str(tmp_path), min_interval_s=60.0)
        assert fr.record("stall", _bundle_payload) is not None
        assert fr.record("stall", _bundle_payload) is None
        # a DIFFERENT reason is not limited by the stall clock
        assert fr.record("resurrect", _bundle_payload) is not None
        assert fr.recorded_total == 2

    def test_retention_ring_holds_budget_newest_kept(self, tmp_path):
        def big():
            b = _bundle_payload()
            b["pad"] = "x" * 4096
            return b

        fr = FlightRecorder(str(tmp_path), budget_bytes=10_000,
                            min_interval_s=0.0)
        paths = [fr.record("stall", big) for _ in range(8)]
        assert all(p for p in paths)
        assert fr.total_bytes() <= 10_000 or len(fr.bundles()) == 1
        # the newest bundle always survives
        assert os.path.exists(paths[-1])
        assert fr.pruned_total > 0
        _, errors = flight_inspect.lint_dir(str(tmp_path),
                                            budget_bytes=10_000)
        assert errors == []

    def test_collect_failure_is_counted_not_raised(self, tmp_path):
        fr = FlightRecorder(str(tmp_path), min_interval_s=0.0)

        def boom():
            raise RuntimeError("collector died")

        assert fr.record("stall", boom) is None
        assert fr.record_failures_total == 1


class TestFlightInspect:
    def test_lint_catches_missing_keys(self):
        b = _bundle_payload()
        del b["step_timeline"]
        b.update(v=1, reason="stall", t_unix=time.time(),
                 pid=os.getpid())
        errs = flight_inspect.lint_bundle(b)
        assert any("step_timeline" in e for e in errs)

    def test_lint_catches_nonmonotonic_timeline(self):
        b = _bundle_payload()
        b["step_timeline"] = [{"step": 5}, {"step": 3}]
        b.update(v=1, reason="stall", t_unix=time.time(),
                 pid=os.getpid())
        assert any("monotonic" in e
                   for e in flight_inspect.lint_bundle(b))

    def test_lint_catches_open_embedded_trace(self):
        b = _bundle_payload()
        b["traces"] = [{"trace_id": "t", "pid": 1, "spans": [
            {"sid": "a:1", "parent": None, "name": "x",
             "t0_us": 1.0, "t1_us": None, "args": {}}]}]
        b.update(v=1, reason="resurrect", t_unix=time.time(),
                 pid=os.getpid())
        assert any("OPEN" in e for e in flight_inspect.lint_bundle(b))

    def test_lint_catches_inconsistent_histogram(self):
        b = _bundle_payload()
        hname = next(iter(b["metrics"]["histograms"]))
        b["metrics"]["histograms"][hname]["total"] = 99
        b.update(v=1, reason="stall", t_unix=time.time(),
                 pid=os.getpid())
        assert any("counts sum" in e
                   for e in flight_inspect.lint_bundle(b))

    def test_lint_dir_flags_over_budget_ring(self, tmp_path):
        for i in range(3):
            p = tmp_path / f"flight-{i:013d}-000{i}-stall.json"
            b = _bundle_payload()
            b.update(v=1, reason="stall", t_unix=1.0 + i, pid=1,
                     pad="x" * 4096)
            p.write_text(json.dumps(b))
        _, errors = flight_inspect.lint_dir(str(tmp_path),
                                            budget_bytes=1000)
        assert any("over budget" in e for e in errors)


# ---------------------------------------------------------------------------
# Server surface: export/slo ops + flight bundles on real failures
# ---------------------------------------------------------------------------

class TestServerFleetSurface:
    def test_export_op_is_structured_and_mergeable(self, model):
        srv = _server(model)
        port = srv.start()
        for _ in range(2):
            r = client_request("127.0.0.1", port,
                               {"op": "generate", "prompt": [1, 2, 3],
                                "max_new_tokens": 3})
            assert r.get("done"), r
        e = client_request("127.0.0.1", port, {"op": "export"})["export"]
        srv.stop()
        assert e["counters"]["requests_total"] == 2
        assert e["histograms"]["ttft_ms"]["total"] == 2
        assert sum(e["histograms"]["ttft_ms"]["counts"]) == 2
        assert e["slo"]["classes"]["normal"]["total"] == 2
        # the export is json-clean (it crossed a socket already) and
        # merges with itself bucket-exactly
        m = merge_exports([e["histograms"]["ttft_ms"]] * 2)
        assert m["total"] == 4

    def test_slo_op_runtime_retarget(self, model):
        srv = _server(model, slo_ttft_ms=10_000.0, slo_tpot_ms=10_000.0)
        port = srv.start()
        r = client_request("127.0.0.1", port,
                           {"op": "generate", "prompt": [1, 2, 3],
                            "max_new_tokens": 3})
        assert r.get("done")
        s = client_request("127.0.0.1", port, {"op": "slo"})["slo"]
        assert s["ttft_ms"] == 10_000.0
        assert s["attainment"]["all"] == 1.0  # generous target: met
        # retarget to an impossible 0.001ms: window resets, next
        # request misses
        s2 = client_request("127.0.0.1", port,
                            {"op": "slo", "ttft_ms": 0.001})["slo"]
        assert s2["attainment"]["all"] is None  # window reset
        # partial retarget PRESERVES the absent target (it must not
        # silently drop the TPOT SLO)
        assert s2["tpot_ms"] == 10_000.0
        client_request("127.0.0.1", port,
                       {"op": "generate", "prompt": [4, 5, 6],
                        "max_new_tokens": 3})
        s3 = client_request("127.0.0.1", port, {"op": "slo"})["slo"]
        assert s3["attainment"]["all"] == 0.0
        txt = client_request("127.0.0.1", port,
                             {"op": "metrics"})["text"]
        assert 'serving_slo_attainment{class="normal"} 0' in txt
        bad = client_request("127.0.0.1", port,
                             {"op": "slo", "ttft_ms": True})
        assert bad.get("error") == "BadRequest"
        srv.stop()

    def test_resurrection_writes_lintable_flight_bundle(
            self, model, tmp_path):
        """The black-box contract: an engine death mid-decode leaves a
        bundle capturing the DYING engine's timeline and in-flight set
        — written before teardown, linting clean, with the request
        that was being served visible in the inflight dump."""
        fi.get_injector().arm("engine.step", at_calls=[3, 4])
        srv = _server(model, max_engine_errors=2,
                      flight_dir=str(tmp_path), trace_sample=1.0)
        port = srv.start()
        r = client_request("127.0.0.1", port,
                           {"op": "generate", "prompt": [1, 2, 3, 4],
                            "max_new_tokens": 8})
        assert r.get("done") and r["stats"].get("replayed") is True
        bundles = srv.flight.bundles()
        assert len(bundles) == 1
        obj = json.load(open(bundles[0]))
        assert obj["reason"] == "resurrect"
        assert flight_inspect.lint_bundle(obj) == [], \
            flight_inspect.lint_bundle(obj)
        assert obj["inflight"], "dying engine's request not captured"
        assert obj["inflight"][0]["state"] in ("decoding", "queued",
                                               "prefill_partial")
        assert obj["engine"]["steps"] >= 1
        assert obj["step_timeline"], "timeline ring missing"
        srv.stop()
        _, errors = flight_inspect.lint_dir(str(tmp_path))
        assert errors == []

    def test_terminal_engine_failure_writes_bundle(self, model,
                                                   tmp_path):
        fi.get_injector().arm("engine.step", probability=1.0)
        srv = _server(model, max_engine_errors=2,
                      max_engine_restarts=0,
                      flight_dir=str(tmp_path))
        port = srv.start()
        r = client_request("127.0.0.1", port,
                           {"op": "generate", "prompt": [1, 2, 3],
                            "max_new_tokens": 4})
        # the in-flight client gets a typed reply either way (close()
        # evicts before the EngineFailed broadcast reaches survivors)
        assert r.get("error") in ("EngineFailed", "ServerEvicted"), r
        reasons = [json.load(open(p))["reason"]
                   for p in srv.flight.bundles()]
        assert "engine_failed" in reasons
        srv.stop()

    def test_no_flight_dir_no_writes(self, model):
        srv = _server(model)
        assert srv.flight is None
        srv._flight_record("stall")  # must be a no-op, not a crash
        srv.stop()


# ---------------------------------------------------------------------------
# Router fleet surface (no subprocesses: real Supervisor object,
# synthetic ingests; router ops over a real socket)
# ---------------------------------------------------------------------------

class _StubSup:
    """Duck-typed supervisor without the fleet plane."""

    def __init__(self):
        self.host = "127.0.0.1"
        self.replicas = []

    def live(self):
        return []


class TestRouterFleetOps:
    def _sup_with_data(self):
        sup = Supervisor(model="gpt_tiny", replicas=2)
        for i in range(2):
            sup.fleet.ingest(i, _mk_export(n=3 + i))
            sup.replicas[i].load = i
        return sup

    def test_fleet_stats_op_merges_and_carries_supervision(self):
        sup = self._sup_with_data()
        router = FailoverRouter(sup)
        port = router.start()
        fs = client_request("127.0.0.1", port,
                            {"op": "fleet_stats"})["fleet"]
        router.stop()
        assert fs["replicas_fresh"] == 2
        assert fs["counters"]["requests_total"] == 7
        assert fs["slo"]["attainment"]["all"] == 1.0
        assert fs["pressure"]["verdict"] in ("steady", "scale_up",
                                             "scale_down")
        assert set(fs["supervision"]) == {"0", "1"}
        assert "probe_failures_by_kind" in fs["supervision"]["0"]
        assert fs["router"]["deprioritize_outliers"] is False

    def test_fleet_metrics_op_exposition(self):
        sup = self._sup_with_data()
        router = FailoverRouter(sup)
        port = router.start()
        text = client_request("127.0.0.1", port,
                              {"op": "fleet_metrics"})["text"]
        router.stop()
        assert 'serving_requests_total{replica="0"} 3' in text
        assert 'serving_requests_total{replica="1"} 4' in text
        assert "fleet_requests_total 7" in text
        for line in text.splitlines():
            if line:
                assert _PROM_TYPE.match(line) or \
                    _PROM_SAMPLE.match(line), line

    def test_stub_supervisor_gets_typed_unavailable(self):
        router = FailoverRouter(_StubSup())
        port = router.start()
        r1 = client_request("127.0.0.1", port, {"op": "fleet_stats"})
        r2 = client_request("127.0.0.1", port, {"op": "fleet_metrics"})
        router.stop()
        assert r1["error"] == "FleetMetricsUnavailable"
        assert r2["error"] == "FleetMetricsUnavailable"

    def test_outlier_deprioritization_steers_unkeyed_picks(self):
        """Default off; when on, unkeyed picks avoid flagged replicas
        while they have healthy peers — and still use them when the
        whole fleet is flagged (never filter-to-empty)."""
        class _R:
            def __init__(self, idx):
                self.idx, self.ready = idx, True

            def alive(self):
                return True

        class _Sup:
            def __init__(self, flagged):
                self.host = "127.0.0.1"
                self.replicas = [_R(0), _R(1), _R(2)]
                self.fleet = type(
                    "F", (), {"outliers": lambda s: flagged})()

            def live(self):
                return self.replicas

        sup = _Sup({2: {"step_ms": {"z": 9.9}}})
        router = FailoverRouter(sup, deprioritize_outliers=True)
        picks = {router._pick(set()).idx for _ in range(12)}
        assert picks == {0, 1}
        # off: flagged replica still picked
        router_off = FailoverRouter(sup)
        picks = {router_off._pick(set()).idx for _ in range(12)}
        assert picks == {0, 1, 2}
        # all flagged: preference collapses, fleet still serves
        sup_all = _Sup({0: {}, 1: {}, 2: {}})
        router_all = FailoverRouter(sup_all,
                                    deprioritize_outliers=True)
        assert router_all._pick(set()) is not None
        # exclusion (failover) filters FIRST: flagged-but-only
        # survivor is used
        sup2 = _Sup({1: {}})
        router2 = FailoverRouter(sup2, deprioritize_outliers=True)
        assert router2._pick({0, 2}).idx == 1


# ---------------------------------------------------------------------------
# One real-fleet E2E: spawn a replica, scrape it, kill it
# ---------------------------------------------------------------------------

class TestFleetE2E:
    def test_supervisor_scrapes_and_staleness_tracks_death(
            self, tmp_path):
        """The live collector path end-to-end: a spawned replica's
        export is scraped into the fleet plane through the probe
        cycle, fleet_stats/fleet_metrics answer through the router,
        and killing the replica drops it from the rollup (marked
        stale) instead of poisoning fleet totals."""
        env = {"JAX_PLATFORMS": "cpu", "TPU_SKIP_MDS_QUERY": "true",
               "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}
        sup = Supervisor(
            model="gpt_tiny", replicas=1,
            server_args=["--page-size", "8", "--max-seq-len", "96",
                         "--num-slots", "2",
                         "--slo-ttft-ms", "60000",
                         "--slo-tpot-ms", "60000"],
            replica_env=env, probe_interval_s=0.2,
            backoff_base_s=3600)
        try:
            sup.start(wait_ready=True)
            router = FailoverRouter(sup)
            port = router.start()
            for i in range(2):
                r = client_request(
                    "127.0.0.1", port,
                    {"op": "generate", "prompt": [1, 2, 3 + i],
                     "max_new_tokens": 3}, timeout_s=120.0)
                assert r.get("done"), r
            # let the probe cycle scrape the post-completion export
            deadline = time.monotonic() + 20.0
            fs = None
            while time.monotonic() < deadline:
                fs = client_request("127.0.0.1", port,
                                    {"op": "fleet_stats"})["fleet"]
                if fs["counters"].get("requests_total", 0) >= 2:
                    break
                time.sleep(0.2)
            assert fs["counters"]["requests_total"] >= 2, fs
            assert fs["replicas_fresh"] == 1
            assert fs["slo"]["attainment"]["all"] == 1.0
            assert fs["histograms"]["ttft_ms"]["count"] >= 2
            text = client_request("127.0.0.1", port,
                                  {"op": "fleet_metrics"})["text"]
            assert 'serving_requests_total{replica="0"}' in text
            assert "fleet_replicas_fresh 1" in text
            # kill the replica: the collector must mark it stale and
            # empty the rollup, not keep serving corpse numbers
            sup.kill_replica(0)
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline:
                fs = client_request("127.0.0.1", port,
                                    {"op": "fleet_stats"})["fleet"]
                if fs["replicas_fresh"] == 0:
                    break
                time.sleep(0.2)
            assert fs["replicas_fresh"] == 0, fs
            assert fs["per_replica"]["0"]["stale"] is True
            assert fs["counters"] == {}
            router.stop()
        finally:
            sup.stop()


# ---------------------------------------------------------------------------
# Verdict -> action latch (r21): the autoscaler's consume contract
# ---------------------------------------------------------------------------

class TestConsumePressureLatch:
    def test_each_evaluation_generation_consumed_once(self):
        fm = FleetMetrics(pressure=PressureMonitor(hysteresis=1),
                          pressure_interval_s=0.0)
        for i in range(3):
            fm.ingest(i, _mk_export(n=2, queued=50.0))  # overload
        first = fm.consume_pressure()
        assert first is not None and first["verdict"] == "scale_up"
        # same generation: the actuator already acted on it — a
        # faster-than-scrape tick must see None, not a re-fire
        assert fm.consume_pressure() is None
        # a new scrape generation re-arms the latch
        for i in range(3):
            fm.ingest(i, _mk_export(n=2, queued=50.0))
        again = fm.consume_pressure()
        assert again is not None and again["verdict"] == "scale_up"

    def test_observation_reads_never_consume(self):
        fm = FleetMetrics(pressure=PressureMonitor(hysteresis=1),
                          pressure_interval_s=0.0)
        for i in range(3):
            fm.ingest(i, _mk_export(n=2, queued=50.0))
        for _ in range(5):  # dashboards poll, routers pick
            fm.fleet_snapshot()
            fm.outliers()
        got = fm.consume_pressure()
        assert got is not None and got["verdict"] == "scale_up"

    def test_no_telemetry_means_nothing_to_consume(self):
        fm = FleetMetrics(pressure=PressureMonitor(hysteresis=1),
                          pressure_interval_s=0.0)
        assert fm.consume_pressure() is None
