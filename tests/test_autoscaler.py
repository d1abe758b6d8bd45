"""Autoscaling actuator (r21): crash-safe fleet journal, recovery
planning, the closed-loop scale/shape actuator, and its guard rails.

The contracts pinned here (ISSUE r21 acceptance):

- the `FleetJournal` is atomic + crc-checked (tmp/rename/fsync — the
  ResilientCheckpointManager discipline): a reader either sees the
  previous committed state or the new one, never a torn file, and
  tools/flight_inspect.py lints the same bytes without importing
  paddle_tpu;
- `plan_recovery` is a PURE function a restarted supervisor obeys:
  adopt live replicas, respawn dead ones, resolve every half-finished
  action (adopt-or-reap an orphaned spawn, resume-or-re-admit a
  half-drained victim, finish a rerole as respawn-with-new-role) and
  never double-spawn;
- scale-down refuses TYPED when the survivor set would be empty,
  below the min envelope, or lose the last replica of a role;
- a successful ready probe RESETS the exponential-backoff state
  (satellite fix: one past crash loop must not penalise the next
  legitimate respawn);
- rendezvous ownership moves MINIMALLY under churn: scaling up moves
  only the keys the new replica now owns, scaling down only the
  victim's keys — the property the drain-handoff and router affinity
  both stand on;
- the shape rule (`desired_prefill` + `plan_shape`) is the README
  prefill:decode tuning guidance, executable.

Integration (slow lane): a live autoscaled fleet keeps keyed greedy
outputs BIT-IDENTICAL across scale events, and chaos INVARIANT 7
(tools/chaos_serving.py --autoscale-chaos) holds: SIGKILL the
supervisor mid-spawn and mid-scale-down, restart it from the journal
— no stranded processes, no lost chains, zero leaks, typed
termination everywhere.
"""

import importlib.util
import json
import os
import pathlib
import subprocess
import sys
import tempfile
import time

import pytest

from paddle_tpu.serving.autoscaler import (AutoscaleConfig, Autoscaler,
                                           FleetJournal, desired_prefill,
                                           load_journal, open_actions,
                                           plan_recovery,
                                           scan_marked_replicas)
from paddle_tpu.serving.supervisor import (Replica, Supervisor,
                                           rendezvous_owner)

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load_tool(name):
    spec = importlib.util.spec_from_file_location(
        name, REPO / "tools" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    # sys.modules registration: dataclasses in the tool resolve their
    # (future-import) string annotations through sys.modules
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def _sup(n=2, roles=None, tmp=None, **kw):
    """A Supervisor record set WITHOUT processes: construction never
    spawns (start() does), so guard/plan logic is unit-testable."""
    kw.setdefault("collect_metrics", False)
    sup = Supervisor(model="gpt_tiny", replicas=n, roles=roles,
                     log_dir=str(tmp) if tmp else None, **kw)
    return sup


# ---------------------------------------------------------------------------
# FleetJournal: atomic, crc-checked, bounded, lint-clean
# ---------------------------------------------------------------------------

class TestFleetJournal:
    def test_begin_before_action_then_commit_roundtrip(self, tmp_path):
        path = str(tmp_path / "j.json")
        j = FleetJournal(path)
        seq = j.begin("spawn", replica=3, role="mixed")
        # the begin is ON DISK before any process action: a reader
        # sees the intent even if the writer dies right here
        body, err = load_journal(path)
        assert err is None
        opens = open_actions(body)
        assert [a["seq"] for a in opens] == [seq]
        assert opens[0]["action"] == "spawn"
        j.update(seq, phase="launched", pid=4242, port=9999)
        body, _ = load_journal(path)
        # launched overlays its fields onto the merged open action
        assert open_actions(body)[0]["pid"] == 4242
        j.commit(seq)
        body, _ = load_journal(path)
        assert open_actions(body) == []
        assert j.seq == seq

    def test_rollback_resolves_and_crc_rejects_tamper(self, tmp_path):
        path = str(tmp_path / "j.json")
        j = FleetJournal(path)
        seq = j.begin("drain", replica=0)
        j.rollback(seq, reason="readmitted_below_min")
        body, err = load_journal(path)
        assert err is None and open_actions(body) == []
        # tamper one byte of the body: crc must refuse the whole file
        obj = json.loads(open(path).read())
        obj["body"]["seq"] = 999
        open(path, "w").write(json.dumps(obj))
        body, err = load_journal(path)
        assert body is None and "crc mismatch" in err

    def test_torn_write_leaves_previous_state(self, tmp_path):
        path = str(tmp_path / "j.json")
        j = FleetJournal(path)
        j.begin("spawn", replica=0)
        before = open(path).read()
        # a crash mid-write abandons the tmp; the rename is the commit
        open(path + ".tmp", "w").write(before[: len(before) // 2])
        body, err = load_journal(path)
        assert err is None and body is not None
        assert open(path).read() == before

    def test_bounded_tail_never_drops_unresolved(self, tmp_path):
        j = FleetJournal(str(tmp_path / "j.json"))
        stuck = j.begin("drain", replica=0)  # never resolved
        for _ in range(FleetJournal.MAX_ACTION_ENTRIES):
            s = j.begin("spawn", replica=1)
            j.commit(s)
        body, _ = load_journal(j.path)
        assert [a["seq"] for a in open_actions(body)] == [stuck]

    def test_adopt_body_keeps_seq_monotonic_across_generations(
            self, tmp_path):
        path = str(tmp_path / "j.json")
        j1 = FleetJournal(path)
        s1 = j1.begin("spawn", replica=0)
        j1.commit(s1)
        body, _ = load_journal(path)
        j2 = FleetJournal(path)  # the restarted supervisor
        j2.adopt_body(body)
        s2 = j2.begin("spawn", replica=1)
        assert s2 > s1
        body, _ = load_journal(path)
        assert body["supervisor_pid"] == os.getpid()

    def test_flight_inspect_lints_journal_bytes(self, tmp_path):
        fin = _load_tool("flight_inspect")
        path = str(tmp_path / "j.json")
        j = FleetJournal(path)
        seq = j.begin("spawn", replica=1, role="mixed")
        j.update(seq, phase="launched", pid=1234, port=8901)
        j.commit(seq)
        j.record_fleet([{"idx": 0, "pid": 111, "port": 8800,
                         "role": "mixed"},
                        {"idx": 1, "pid": 1234, "port": 8901,
                         "role": "mixed"}])
        obj = json.loads(open(path).read())
        assert fin.lint_fleet_journal(obj, allow_open_tail=0) == []
        # an open begin fails the strict lint and passes the tolerant
        # one — the chaos harness's "everything resolved" assertion
        j.begin("drain", replica=0)
        obj = json.loads(open(path).read())
        assert fin.lint_fleet_journal(obj, allow_open_tail=0)
        assert fin.lint_fleet_journal(obj, allow_open_tail=1) == []

    def test_write_failure_counted_not_raised(self, tmp_path):
        # journal "directory" is a regular file: every write fails —
        # counted, never raised; the fleet must keep running (chmod
        # tricks don't work for root, a file-as-parent does)
        blocker = tmp_path / "blocker"
        blocker.write_text("")
        j = FleetJournal(str(blocker / "j.json"))
        j.begin("spawn", replica=0)  # must not raise
        assert j.write_failures_total >= 1
        assert j.writes_total == 0


# ---------------------------------------------------------------------------
# plan_recovery: the pure restart contract
# ---------------------------------------------------------------------------

def _body(fleet=(), actions=(), seq=None):
    seqs = [a["seq"] for a in actions] or [0]
    return {"seq": seq if seq is not None else max(seqs),
            "supervisor_pid": 12345,
            "fleet": list(fleet), "actions": list(actions)}


class TestPlanRecovery:
    def test_adopts_live_respawns_dead(self):
        body = _body(fleet=[
            {"idx": 0, "pid": 100, "port": 8800, "role": "mixed"},
            {"idx": 1, "pid": 101, "port": 8801, "role": "decode"}])
        plan = plan_recovery(body, {}, 1, 4,
                             alive=lambda pid, port: pid == 100)
        assert [e["idx"] for e in plan["adopt"]] == [0]
        assert plan["respawn"] == [{"idx": 1, "role": "decode"}]
        assert plan["reap"] == [] and plan["resume"] == []

    def test_scan_overlays_stale_snapshot_pid(self):
        # monitor respawned replica 0 after the last snapshot: journal
        # pid is dead, the env-marker scan has the live one — adopt the
        # scanned pid, never respawn a duplicate
        body = _body(fleet=[{"idx": 0, "pid": 100, "port": 8800,
                             "role": "mixed"}])
        scan = {0: {"pid": 200, "port": 8810}}
        plan = plan_recovery(body, scan, 1, 4,
                             alive=lambda pid, port: pid == 200)
        assert [(e["idx"], e["pid"]) for e in plan["adopt"]] == \
            [(0, 200)]
        assert plan["respawn"] == []

    def test_open_spawn_live_under_envelope_adopted_and_committed(self):
        act = [{"seq": 5, "action": "spawn", "phase": "begin",
                "replica": 1, "role": "mixed"},
               {"seq": 5, "phase": "launched", "pid": 300,
                "port": 8900}]
        body = _body(fleet=[{"idx": 0, "pid": 100, "port": 8800,
                             "role": "mixed"}], actions=act)
        plan = plan_recovery(body, {}, 1, 4,
                             alive=lambda pid, port: True)
        assert sorted(e["idx"] for e in plan["adopt"]) == [0, 1]
        assert plan["resolve"] == [(5, "commit", "adopted_on_recovery")]

    def test_open_spawn_live_over_envelope_reaped(self):
        act = [{"seq": 5, "action": "spawn", "phase": "begin",
                "replica": 1, "role": "mixed"},
               {"seq": 5, "phase": "launched", "pid": 300,
                "port": 8900}]
        body = _body(fleet=[{"idx": 0, "pid": 100, "port": 8800,
                             "role": "mixed"}], actions=act)
        plan = plan_recovery(body, {}, 1, 1,  # max=1: no room
                             alive=lambda pid, port: True)
        assert [e["pid"] for e in plan["reap"]] == [300]
        assert plan["resolve"] == \
            [(5, "rollback", "reaped_over_envelope")]

    def test_open_spawn_dead_rolled_back_nothing_to_reap(self):
        act = [{"seq": 5, "action": "spawn", "phase": "begin",
                "replica": 1, "role": "mixed"}]
        body = _body(fleet=[{"idx": 0, "pid": 100, "port": 8800,
                             "role": "mixed"}], actions=act)
        plan = plan_recovery(body, {}, 1, 4,
                             alive=lambda pid, port: pid == 100)
        assert plan["reap"] == []
        assert plan["resolve"] == [(5, "rollback", "orphan_dead")]

    def test_open_drain_victim_dead_committed(self):
        act = [{"seq": 7, "action": "drain", "phase": "begin",
                "replica": 1, "pid": 101, "port": 8801}]
        body = _body(fleet=[
            {"idx": 0, "pid": 100, "port": 8800, "role": "mixed"},
            {"idx": 1, "pid": 101, "port": 8801, "role": "mixed"}],
            actions=act)
        plan = plan_recovery(body, {}, 1, 4,
                             alive=lambda pid, port: pid == 100)
        assert plan["resolve"] == \
            [(7, "commit", "victim_already_dead")]
        assert [e["idx"] for e in plan["adopt"]] == [0]

    def test_open_drain_victim_live_resumed_with_draining_flag(self):
        act = [{"seq": 7, "action": "drain", "phase": "begin",
                "replica": 1, "pid": 101, "port": 8801}]
        body = _body(fleet=[
            {"idx": 0, "pid": 100, "port": 8800, "role": "mixed"},
            {"idx": 1, "pid": 101, "port": 8801, "role": "mixed"}],
            actions=act)
        plan = plan_recovery(body, {}, 1, 4,
                             alive=lambda pid, port: True)
        assert plan["resume"] == [{"seq": 7, "action": "drain",
                                   "replica": 1}]
        dr = [e for e in plan["adopt"] if e["idx"] == 1]
        assert dr and dr[0].get("draining") is True

    def test_open_drain_readmitted_when_below_min(self):
        # killing the victim now would empty the fleet: roll back and
        # re-admit it as a full member instead
        act = [{"seq": 7, "action": "drain", "phase": "begin",
                "replica": 0, "pid": 100, "port": 8800}]
        body = _body(fleet=[{"idx": 0, "pid": 100, "port": 8800,
                             "role": "mixed"}], actions=act)
        plan = plan_recovery(body, {}, 1, 4,
                             alive=lambda pid, port: True)
        assert plan["resume"] == []
        assert plan["resolve"] == \
            [(7, "rollback", "readmitted_below_min")]
        ent = [e for e in plan["adopt"] if e["idx"] == 0][0]
        assert not ent.get("draining")

    def test_open_rerole_live_resumes_dead_respawns_with_new_role(self):
        act = [{"seq": 9, "action": "rerole", "phase": "begin",
                "replica": 1, "pid": 101, "port": 8801,
                "role_from": "mixed", "role_to": "prefill"}]
        body = _body(fleet=[
            {"idx": 0, "pid": 100, "port": 8800, "role": "mixed"},
            {"idx": 1, "pid": 101, "port": 8801, "role": "mixed"}],
            actions=act)
        live = plan_recovery(body, {}, 1, 4,
                             alive=lambda pid, port: True)
        assert live["resume"] == [{"seq": 9, "action": "rerole",
                                   "replica": 1, "role": "prefill"}]
        dead = plan_recovery(body, {}, 1, 4,
                             alive=lambda pid, port: pid == 100)
        assert {"idx": 1, "role": "prefill"} in dead["respawn"]
        assert dead["resolve"] == \
            [(9, "commit", "respawned_with_new_role")]

    def test_never_double_spawn_idx_claimed_once(self):
        # the same replica appears in the fleet snapshot AND the scan
        # AND an open spawn: exactly one adoption, zero respawns
        act = [{"seq": 5, "action": "spawn", "phase": "begin",
                "replica": 1, "role": "mixed"},
               {"seq": 5, "phase": "launched", "pid": 300,
                "port": 8900}]
        body = _body(fleet=[
            {"idx": 0, "pid": 100, "port": 8800, "role": "mixed"},
            {"idx": 1, "pid": 300, "port": 8900, "role": "mixed"}],
            actions=act)
        scan = {1: {"pid": 300, "port": 8900}}
        plan = plan_recovery(body, scan, 1, 4,
                             alive=lambda pid, port: True)
        assert sorted(e["idx"] for e in plan["adopt"]) == [0, 1]
        assert plan["respawn"] == []


# ---------------------------------------------------------------------------
# Scale-down guard: typed refusals (satellite 1)
# ---------------------------------------------------------------------------

class TestScaleDownGuard:
    def test_last_replica_refused(self, tmp_path):
        sup = _sup(1, tmp=tmp_path)
        assert sup.scale_down_guard(0) == "last_replica"
        out = sup.drain_replica(0)
        assert out["refused"] == "last_replica"
        assert out["drained"] is False

    def test_below_min_envelope_refused(self, tmp_path):
        sup = _sup(2, tmp=tmp_path)
        assert sup.scale_down_guard(0, min_replicas=2) == \
            "below_min_replicas(2)"
        assert sup.scale_down_guard(0, min_replicas=1) is None

    def test_last_role_advertising_replica_refused(self, tmp_path):
        sup = _sup(3, roles=["prefill", "decode", "decode"],
                   tmp=tmp_path)
        assert sup.scale_down_guard(0) == "last_prefill_replica"
        assert sup.scale_down_guard(1) is None  # a decode survives
        sup.replicas[2].draining = True  # draining is not a survivor
        assert sup.scale_down_guard(1) == "last_decode_replica"

    def test_unknown_idx_typed(self, tmp_path):
        sup = _sup(1, tmp=tmp_path)
        assert sup.scale_down_guard(99) == "no_such_replica"

    def test_mid_drain_victim_skips_guard(self, tmp_path):
        # recovery re-drains a victim whose removal was already
        # committed to — the guard must not refuse it
        sup = _sup(1, tmp=tmp_path)
        sup.replicas[0].draining = True
        out = sup.drain_replica(0)
        assert "refused" not in out


# ---------------------------------------------------------------------------
# Backoff reset on healthy probe (satellite 2)
# ---------------------------------------------------------------------------

class TestBackoffReset:
    def test_reset_backoff_clears_the_exponential_state(self):
        rep = Replica(0, "127.0.0.1")
        rep.consec_deaths = 5
        rep.probe_failures = 2
        rep.next_spawn_t = time.monotonic() + 60.0
        rep.reset_backoff()
        assert rep.consec_deaths == 0
        assert rep.probe_failures == 0
        assert rep.next_spawn_t is None


# ---------------------------------------------------------------------------
# Rendezvous churn: minimal key reassignment (satellite 3, unit half)
# ---------------------------------------------------------------------------

class _Cand:
    def __init__(self, idx):
        self.idx = idx


class TestRendezvousChurn:
    KEYS = [f"{i:016x}" for i in range(256)]

    def _owners(self, cands):
        return {k: rendezvous_owner(k, cands).idx for k in self.KEYS}

    def test_scale_up_moves_only_the_new_replicas_keys(self):
        old = [_Cand(i) for i in range(3)]
        new = old + [_Cand(3)]
        before, after = self._owners(old), self._owners(new)
        moved = [k for k in self.KEYS if before[k] != after[k]]
        assert moved, "a new replica must win some keys"
        assert all(after[k] == 3 for k in moved)
        # and roughly its fair share, not the whole keyspace
        assert len(moved) < len(self.KEYS) // 2

    def test_scale_down_moves_only_the_victims_keys(self):
        old = [_Cand(i) for i in range(4)]
        new = [c for c in old if c.idx != 2]
        before, after = self._owners(old), self._owners(new)
        for k in self.KEYS:
            if before[k] != 2:
                assert after[k] == before[k], \
                    "a survivor's keys must not move on scale-down"
            else:
                assert after[k] != 2


# ---------------------------------------------------------------------------
# Shape rule: desired_prefill + plan_shape (the README rule, executable)
# ---------------------------------------------------------------------------

class TestShapeRule:
    def test_desired_prefill_ratio_and_clamps(self):
        assert desired_prefill(0) == 0
        assert desired_prefill(1) == 0  # no shape below 2 replicas
        assert desired_prefill(2) == 1
        assert desired_prefill(4) == 1            # 1 prefill : 3 decode
        assert desired_prefill(8) == 2
        assert desired_prefill(4, decode_per_prefill=1.0) == 2
        # bias never strands a class: clamped to [1, n-1]
        assert desired_prefill(2, bias=-5) == 1
        assert desired_prefill(2, bias=+5) == 1
        assert desired_prefill(4, bias=+1) == 2
        assert desired_prefill(4, bias=-1) == 1

    def _asc(self, sup, tmp):
        return Autoscaler(sup, AutoscaleConfig(
            min_replicas=1, max_replicas=8),
            journal_path=str(tmp / "j.json"))

    def test_mixed_only_fleet_never_shaped(self, tmp_path):
        asc = self._asc(_sup(3, tmp=tmp_path), tmp_path)
        assert asc.plan_shape() is None

    def test_underrepresented_prefill_converts_a_mixed(self, tmp_path):
        sup = _sup(4, roles=["decode", "decode", "decode", "mixed"],
                   tmp=tmp_path)
        asc = self._asc(sup, tmp_path)
        plan = asc.plan_shape()
        assert plan == {"replica": 3, "role": "prefill",
                        "reason": "shape_prefill_up"}

    def test_overrepresented_prefill_converts_to_decode(self, tmp_path):
        sup = _sup(4, roles=["prefill", "prefill", "decode", "decode"],
                   tmp=tmp_path)
        asc = self._asc(sup, tmp_path)
        plan = asc.plan_shape()
        assert plan == {"replica": 0, "role": "decode",
                        "reason": "shape_decode_up"}

    def test_balanced_fleet_not_shaped(self, tmp_path):
        sup = _sup(2, roles=["prefill", "decode"], tmp=tmp_path)
        asc = self._asc(sup, tmp_path)
        assert asc.plan_shape() is None  # already at desired shape

    def test_handoff_failure_climb_biases_prefill_up(self, tmp_path):
        sup = _sup(4, roles=["prefill", "decode", "decode", "decode"],
                   tmp=tmp_path)

        class _R:
            handoff_prefill_failures_total = 3
        sup.router = _R()
        asc = self._asc(sup, tmp_path)
        # want jumps from 1 to 2: a decode donates (no mixed left)
        plan = asc.plan_shape()
        assert plan is not None and plan["role"] == "prefill"
        # the climb is edge-triggered: same counter, no second bump
        assert asc.plan_shape() is None


# ---------------------------------------------------------------------------
# Actuator refusals + observability (no processes)
# ---------------------------------------------------------------------------

class TestActuatorRefusals:
    def _asc(self, sup, tmp, **cfg):
        kw = dict(min_replicas=1, max_replicas=2)
        kw.update(cfg)
        return Autoscaler(sup, AutoscaleConfig(**kw),
                          journal_path=str(tmp / "j.json"))

    def test_envelope_validated(self, tmp_path):
        sup = _sup(1, tmp=tmp_path)
        with pytest.raises(ValueError):
            Autoscaler(sup, AutoscaleConfig(min_replicas=0),
                       journal_path=str(tmp_path / "j.json"))
        with pytest.raises(ValueError):
            Autoscaler(sup, AutoscaleConfig(min_replicas=3,
                                            max_replicas=2),
                       journal_path=str(tmp_path / "j2.json"))

    def test_scale_up_refused_at_max_even_forced(self, tmp_path):
        sup = _sup(2, tmp=tmp_path)
        asc = self._asc(sup, tmp_path)
        out = asc.scale_up(force=True)
        assert out["ok"] is False and out["reason"] == "refused_at_max"
        assert asc.actions_total[("spawn", "refused_at_max")] == 1

    def test_scale_up_refused_in_cooldown(self, tmp_path):
        sup = _sup(1, tmp=tmp_path)
        asc = self._asc(sup, tmp_path, max_replicas=4,
                        cooldown_up_s=3600.0)
        asc._last_up_t = time.monotonic()
        out = asc.scale_up()
        assert out["reason"] == "refused_cooldown"
        st = asc.status()
        assert st["cooldown_up_remaining_s"] > 0

    def test_scale_down_refused_no_eligible_victim(self, tmp_path):
        sup = _sup(1, tmp=tmp_path)  # the guard protects the only one
        asc = self._asc(sup, tmp_path)
        out = asc.scale_down(force=True)
        assert out["reason"] == "refused_no_eligible_victim"

    def test_rerole_typed_refusals(self, tmp_path):
        sup = _sup(2, roles=["prefill", "decode"], tmp=tmp_path)
        asc = self._asc(sup, tmp_path)
        assert asc.rerole(0, "gpu", force=True)["reason"] == \
            "refused_bad_role_gpu"
        assert asc.rerole(9, "decode", force=True)["reason"] == \
            "refused_no_such_replica"
        assert asc.rerole(0, "prefill", force=True)["reason"] == \
            "refused_already_that_role"
        # converting the last prefill would strand the class
        assert asc.rerole(0, "decode", force=True)["reason"] == \
            "refused_guard"

    def test_refusals_never_touch_the_journal(self, tmp_path):
        sup = _sup(2, tmp=tmp_path)
        asc = self._asc(sup, tmp_path)
        seq0 = asc.journal.seq
        asc.scale_up(force=True)           # at_max
        asc.rerole(0, "bogus", force=True)
        assert asc.journal.seq == seq0

    def test_prometheus_families_and_status(self, tmp_path):
        sup = _sup(2, roles=["prefill", "decode"], tmp=tmp_path)
        asc = self._asc(sup, tmp_path)
        asc.scale_up(force=True)  # refused: still a counted action
        lines = asc.prometheus_lines()
        text = "\n".join(lines)
        assert "# TYPE serving_autoscale_actions_total counter" in text
        assert 'serving_autoscale_actions_total{action="spawn",' \
               'reason="refused_at_max"} 1' in text
        assert 'serving_fleet_replicas{role="prefill"} 1' in text
        assert 'serving_fleet_replicas{role="decode"} 1' in text
        assert 'serving_fleet_replicas{role="mixed"} 0' in text
        st = asc.status()
        assert st["replicas_by_role"] == {"prefill": 1, "decode": 1}
        assert st["last_action"]["reason"] == "refused_at_max"
        assert st["actions_total"] == {"spawn|refused_at_max": 1}
        assert st["action_in_flight"] is False
        assert st["journal"]["path"] == str(tmp_path / "j.json")


# ---------------------------------------------------------------------------
# Flight-recorder autoscale bundles lint (satellite 4+6)
# ---------------------------------------------------------------------------

class TestAutoscaleBundleLint:
    def _bundle(self, **over):
        b = {"v": 1, "reason": "autoscale", "t_unix": time.time(),
             "pid": os.getpid(),
             "action": {"action": "spawn", "reason": "pressure",
                        "ok": True, "t_unix": time.time()},
             "fleet": [{"idx": 0, "pid": 1, "port": 8800,
                        "role": "mixed"}],
             "journal_tail": [{"seq": 1, "phase": "begin",
                               "action": "spawn"},
                              {"seq": 1, "phase": "commit"}]}
        b.update(over)
        return b

    def test_wellformed_bundle_lints_clean(self):
        fin = _load_tool("flight_inspect")
        assert fin.lint_bundle(self._bundle()) == []

    def test_malformed_bundles_rejected(self):
        fin = _load_tool("flight_inspect")
        assert fin.lint_bundle(self._bundle(action="not-a-dict"))
        assert fin.lint_bundle(self._bundle(
            journal_tail=[{"seq": 1, "phase": "exploded"}]))
        bad = self._bundle()
        del bad["fleet"]
        assert fin.lint_bundle(bad)

    def test_recorder_written_bundle_lints_end_to_end(self, tmp_path):
        # the actual write path: a refused action via an Autoscaler
        # wired to a real FlightRecorder produces a lint-clean bundle
        from paddle_tpu.serving.fleet_metrics import FlightRecorder
        fin = _load_tool("flight_inspect")
        sup = _sup(2, tmp=tmp_path)
        flight = FlightRecorder(str(tmp_path / "flight"),
                                min_interval_s=0.0)
        asc = Autoscaler(sup, AutoscaleConfig(min_replicas=1,
                                              max_replicas=2),
                         journal_path=str(tmp_path / "j.json"),
                         flight=flight)
        out = asc.scale_up(force=True)  # refused_at_max -> no bundle
        assert out["ok"] is False
        asc._record("drain", "unit", ok=True, replica=1)  # bundled
        bundles, errors = fin.lint_dir(str(tmp_path / "flight"))
        assert errors == []
        assert len(bundles) == 1


# ---------------------------------------------------------------------------
# Conftest stray-guard: adopted replicas are spared (satellite 6)
# ---------------------------------------------------------------------------

class TestConftestAdoption:
    def _conftest(self):
        spec = importlib.util.spec_from_file_location(
            "_conftest_under_test",
            REPO / "tests" / "conftest.py")
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def _marked_child(self, journal):
        env = dict(os.environ)
        env["PT_SUPERVISOR_JOURNAL"] = journal
        env["PT_REPLICA_IDX"] = "0"
        return subprocess.Popen([sys.executable, "-c",
                                 "import time; time.sleep(60)"],
                                env=env)

    def test_live_supervisor_in_journal_spares_the_orphan(
            self, tmp_path):
        ct = self._conftest()
        j = FleetJournal(str(tmp_path / "j.json"))  # our pid, alive
        j.record_fleet([])
        child = self._marked_child(j.path)
        try:
            # /proc/<pid>/environ shows the PRE-exec image for a
            # moment after Popen returns — wait for the marker
            deadline = time.monotonic() + 10
            while not ct._adopted_by_live_supervisor(child.pid) \
                    and time.monotonic() < deadline:
                time.sleep(0.05)
            assert ct._adopted_by_live_supervisor(child.pid) is True
        finally:
            child.kill()
            child.wait()

    def test_dead_supervisor_or_no_marker_is_killable(self, tmp_path):
        ct = self._conftest()
        path = str(tmp_path / "j.json")
        dead = 2 ** 22 + 7919  # beyond default pid_max: never alive
        obj = {"v": 1, "body": {"supervisor_pid": dead}}
        open(path, "w").write(json.dumps(obj))
        child = self._marked_child(path)
        unmarked = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(60)"])
        try:
            assert ct._adopted_by_live_supervisor(child.pid) is False
            assert ct._adopted_by_live_supervisor(unmarked.pid) \
                is False
        finally:
            for p in (child, unmarked):
                p.kill()
                p.wait()


# ---------------------------------------------------------------------------
# Journal env markers on spawned replicas
# ---------------------------------------------------------------------------

class TestJournalEnvMarkers:
    def test_scan_finds_marked_server_lookalike(self, tmp_path):
        # a process whose cmdline matches the server module AND whose
        # env carries our journal marker is found by the scan; the
        # same command without the marker is not
        journal = str(tmp_path / "j.json")
        env = dict(os.environ)
        env["PT_SUPERVISOR_JOURNAL"] = journal
        env["PT_REPLICA_IDX"] = "3"
        code = ("import sys, time; "
                "sys.argv=['paddle_tpu.serving.server']; "
                "time.sleep(60)")
        marked = subprocess.Popen(
            [sys.executable, "-c", code, "paddle_tpu.serving.server",
             "--port", "8899"], env=env)
        try:
            deadline = time.monotonic() + 10
            found = {}
            while time.monotonic() < deadline:
                found = scan_marked_replicas(journal)
                if found:
                    break
                time.sleep(0.1)
            assert found == {3: {"pid": marked.pid, "port": 8899}}
            assert scan_marked_replicas(
                str(tmp_path / "other.json")) == {}
        finally:
            marked.kill()
            marked.wait()


# ---------------------------------------------------------------------------
# Integration (slow lane): live fleet, bit-identical across scale
# events; chaos INVARIANT 7
# ---------------------------------------------------------------------------

def _replica_env(cache_dir):
    env = {"JAX_PLATFORMS": "cpu", "TPU_SKIP_MDS_QUERY": "true",
           "JAX_COMPILATION_CACHE_DIR": cache_dir}
    return env


@pytest.mark.slow
class TestAutoscalerLive:
    def test_bit_identical_keyed_tokens_across_scale_events(
            self, tmp_path):
        """Satellite 3 (integration half): keyed greedy outputs from
        a live autoscaled fleet are bit-identical before a scale-up,
        after it, and after the scale-down that follows — chains
        either stay where the rendezvous put them or are handed to a
        survivor, never corrupted."""
        import numpy as np

        from paddle_tpu.serving.server import client_request
        from paddle_tpu.serving.supervisor import FailoverRouter

        chaos = _load_tool("chaos_serving")
        rng = np.random.default_rng(0)
        prompts = [np.asarray(rng.integers(1, 100, size=20), np.int32)
                   for _ in range(4)]
        expected = chaos._reference_outputs("gpt_tiny", prompts,
                                            [5] * 4, 8, 96)
        cache = str(tmp_path / "cache")
        sup = Supervisor(
            model="gpt_tiny", replicas=1,
            server_args=["--page-size", "8", "--max-seq-len", "96",
                         "--num-slots", "2"],
            replica_env=_replica_env(cache),
            probe_interval_s=0.3, backoff_base_s=0.5,
            log_dir=str(tmp_path / "logs"))
        asc = Autoscaler(sup, AutoscaleConfig(
            min_replicas=1, max_replicas=2, cooldown_up_s=0.0,
            cooldown_down_s=0.0),
            journal_path=str(tmp_path / "j.json"))
        router = None
        try:
            sup.start(wait_ready=True)
            router = FailoverRouter(sup, port=0)
            port = router.start()

            def run_all():
                outs = []
                for i, p in enumerate(prompts):
                    r = client_request(
                        "127.0.0.1", port,
                        {"op": "generate",
                         "prompt": [int(t) for t in p],
                         "max_new_tokens": 5,
                         "key": f"asl-{i}"}, timeout_s=180.0)
                    assert not r.get("error"), r
                    outs.append(r["generated"])
                return outs

            assert run_all() == expected
            up = asc.scale_up(reason="test", force=True)
            assert up["ok"] is True, up
            assert len(sup.replicas) == 2
            assert run_all() == expected
            down = asc.scale_down(reason="test", force=True)
            assert down["ok"] is True, down
            assert len(sup.replicas) == 1
            # survivors serve every key: handed-off chains or
            # re-prefill-on-first-use, identical tokens either way
            assert run_all() == expected
            # journal reflects the full story and lints strictly
            fin = _load_tool("flight_inspect")
            obj = json.loads(open(asc.journal.path).read())
            assert fin.lint_fleet_journal(obj,
                                          allow_open_tail=0) == []
            kinds = [a["action"] for a in asc.journal.tail(99)
                     if a.get("phase") == "begin"]
            assert kinds == ["spawn", "drain"]
        finally:
            if router is not None:
                router.stop()
            sup.stop()

    def test_chaos_invariant7_supervisor_sigkill_recovery(self):
        """ISSUE r21 acceptance: the full invariant-7 chaos run —
        SIGKILL the supervisor mid-spawn and mid-scale-down under
        keyed traffic, restart from the journal, assert no stranded
        processes, no lost chains, zero leaked pages, 100% typed
        termination, journal + flight bundles lint clean."""
        chaos = _load_tool("chaos_serving")
        report = chaos.run_autoscale_chaos(requests=6, seed=0)
        assert report.ok, report.to_dict()
        assert report.recoveries == 2
        assert report.stranded_processes == 0
        assert report.journal_lint_failures == 0
        assert report.mismatches == 0
        assert report.hangs == 0
        assert report.completed + report.typed_errors == 6
