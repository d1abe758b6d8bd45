"""Seeded chaos harness for the crash-safe serving stack (r9).

Drives a real workload through the full serving topology — failover
router → supervised replica processes → SLO scheduler → paged decode
engine — while a DETERMINISTIC fault schedule (distributed/
fault_inject.py, seeded) fires at every layer below the client:

- ``engine.step`` bursts inside each replica push the server past
  ``max_engine_errors`` and force an engine RESURRECTION with
  in-flight replay (serving/server.py);
- ``alloc.page`` makes page allocation transiently fail (admission
  unwinds and requeues);
- ``net.recv`` tears connections both inside the replicas (server
  reader) and inside the router's backend reader (failover path);
- one replica is SIGKILLed mid-run; the supervisor restarts it with
  backoff while the router resubmits its keyed in-flight requests to
  the survivor.

The three invariants asserted (the r9 acceptance contract):

1. **Termination** — every request ends in a full result or a TYPED
   error reply; a hang (no reply within the timeout) fails the run.
2. **Zero leaks** — after drain, every replica's ``leak_check`` op
   (engine-thread page-accounting audit) comes back clean.
3. **Bit-identical recovery** — every SUCCESSFUL greedy completion,
   including those that rode an engine resurrection or a router
   failover, equals the fault-free reference output computed in-proc
   before any fault is armed.

Usage (CPU fast lane)::

    python tools/chaos_serving.py --replicas 2 --requests 12 --seed 0

Exit code 0 = all invariants held; the JSON report lands on stdout.
Tests load this file as a module and call ``run_chaos`` directly
(tests/test_crash_safe_serving.py).
"""

from __future__ import annotations

import dataclasses
import json
import os
import sys
import tempfile
import threading
import time
from typing import Dict, List, Optional

_TOOLS = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(_TOOLS)
for _p in (_REPO, _TOOLS):
    if _p not in sys.path:
        sys.path.insert(0, _p)

# default replica fault schedule: an engine.step burst long enough to
# breach --max-engine-errors 3 (forcing one resurrection per replica
# process), scattered transient allocation failures, and a couple of
# torn server-side receives. Deterministic per PT_FAULT_SEED.
DEFAULT_REPLICA_FAULTS = ("engine.step:at=4|5|6,max=3;"
                          "alloc.page:p=0.05,max=3;"
                          "net.recv:p=0.02,max=2")


@dataclasses.dataclass
class ChaosReport:
    """Outcome of one seeded chaos run."""

    requests: int = 0
    completed: int = 0            # full results
    typed_errors: int = 0         # DeadlineExceeded / ReplicaFailed / ...
    hangs: int = 0                # no reply within timeout (INVARIANT 1)
    mismatches: int = 0           # greedy output != reference (INV. 3)
    leak_failures: int = 0        # replica leak_check not ok (INV. 2)
    # crash flight recorder (r17, INVARIANT 4): every survivor bundle
    # lints clean and each replica's retention ring held its budget
    flight_bundles: int = 0
    flight_lint_failures: int = 0
    flight_errors: List[str] = dataclasses.field(default_factory=list)
    # page ledger (r18, INVARIANT 5): after drain every replica's
    # ledger RECONCILES — the event-derived ownership shadow matches
    # the allocator's books exactly (each alloc/reserve had its
    # matching release/free), alongside the existing leak_check
    ledger_failures: int = 0
    ledger_errors: List[str] = dataclasses.field(default_factory=list)
    # autoscaler crash-safety (r21, INVARIANT 7): after SIGKILLing the
    # supervisor mid-scale-action and restarting it from the journal —
    # no serving process left carrying our journal marker after the
    # final graceful stop, and the fleet-state journal lints clean
    # (crc, monotonic seqs, every begin resolved). Default 0 so pre-r21
    # runs are unaffected.
    stranded_processes: int = 0
    journal_lint_failures: int = 0
    # fleet-cache crash safety (r23, INVARIANT 8): the run must have
    # actually exercised the lane under test (router fleet-cache hints
    # observed before the SIGKILL) — a run where the fault never races
    # the behaviour proves nothing and fails loudly instead of
    # greenly. Default 0 so pre-r23 runs are unaffected.
    arming_failures: int = 0
    # rolling weight upgrade (r24, INVARIANT 9): after SIGKILLing the
    # supervisor mid-roll and a replica mid-swap, the fleet must
    # converge to EXACTLY ONE weight generation (never mixed, never
    # weightless), a corrupt checkpoint must be refused typed with
    # zero replicas changed, and post-convergence outputs must be
    # bit-identical to the converged generation's reference. Default 0
    # so pre-r24 runs are unaffected.
    generation_failures: int = 0
    recoveries: int = 0           # supervisor SIGKILL->restart cycles
    error_kinds: Dict[str, int] = dataclasses.field(default_factory=dict)
    details: List[Dict] = dataclasses.field(default_factory=list)
    engine_restarts: int = 0      # scraped from surviving replicas
    replayed_requests: int = 0
    supervisor_restarts: int = 0  # replica process respawns
    router_failovers: int = 0
    replicas_checked: int = 0
    wall_s: float = 0.0

    @property
    def ok(self) -> bool:
        return (self.hangs == 0 and self.mismatches == 0
                and self.leak_failures == 0
                and self.flight_lint_failures == 0
                and self.ledger_failures == 0
                and self.stranded_processes == 0
                and self.journal_lint_failures == 0
                and self.arming_failures == 0
                and self.generation_failures == 0
                and self.completed + self.typed_errors == self.requests)

    def to_dict(self) -> Dict:
        out = dataclasses.asdict(self)
        out["ok"] = self.ok
        return out


def _reference_outputs(model_name: str, prompts, max_new,
                       page_size: int, max_seq_len: int):
    """Fault-free greedy outputs, computed in-process BEFORE any fault
    is armed — the bit-identity oracle for every replayed/failed-over
    request (batching never changes greedy outputs; the serving suite
    pins that)."""
    from paddle_tpu.inference import create_decode_engine
    from paddle_tpu.serving.server import _build_model

    model = _build_model(model_name)
    eng = create_decode_engine(model, num_slots=2, page_size=page_size,
                               max_seq_len=max_seq_len)
    rids = [eng.submit(p, mnt) for p, mnt in zip(prompts, max_new)]
    results = eng.run()
    eng.close()
    return [[int(t) for t in results[r][len(p):]]
            for r, p in zip(rids, prompts)]


def _scrape_counters(host: str, port: int) -> Dict[str, float]:
    from paddle_tpu.serving.supervisor import _rpc
    try:
        snap = _rpc(host, port, {"op": "stats"}, timeout_s=10.0)
        return dict(snap["stats"]["counters"])
    except Exception:
        return {}


def run_chaos(replicas: int = 2, requests: int = 12, seed: int = 0,
              model: str = "gpt_tiny", page_size: int = 8,
              max_seq_len: int = 96, num_slots: int = 2,
              max_new_tokens: int = 6,
              replica_faults: Optional[str] = DEFAULT_REPLICA_FAULTS,
              router_fault_p: float = 0.08,
              router_fault_max: int = 3,
              kill_replica: bool = True,
              deadline_doomed: int = 2,
              unkeyed: int = 2,
              request_timeout_s: float = 300.0,
              drain_timeout_s: float = 120.0,
              platform: str = "cpu",
              log_dir: Optional[str] = None,
              flight_budget_mb: int = 4,
              extra_server_args: Optional[List[str]] = None
              ) -> ChaosReport:
    """One seeded chaos run; see module docstring for the invariants.

    ``deadline_doomed`` requests carry a 1 ms deadline (guaranteed
    typed DeadlineExceeded), ``unkeyed`` requests omit the idempotency
    key (a mid-request replica loss costs them a typed ReplicaFailed
    instead of transparent failover) — both are TYPED outcomes, so
    invariant 1 still covers them.

    ``extra_server_args`` appends raw server CLI flags to every
    replica (``["--speculate", "4", "--prefill-chunk", "8"]``) so the
    UNCHANGED fault sites fire against that engine: resurrections
    rebuild it, replay rides it, and the leak/ledger audits cover its
    exit paths."""
    import numpy as np

    from paddle_tpu.distributed import fault_inject as fi
    from paddle_tpu.serving.server import client_request
    from paddle_tpu.serving.supervisor import (FailoverRouter,
                                               Supervisor, _rpc)

    t_start = time.monotonic()
    rng = np.random.default_rng(seed)
    prompts = [np.asarray(rng.integers(1, 100,
                                       size=int(rng.integers(4, 20))),
                          np.int32)
               for _ in range(requests)]
    max_new = [max_new_tokens] * requests

    # the oracle MUST precede any arming: it runs in this process
    expected = _reference_outputs(model, prompts, max_new,
                                  page_size, max_seq_len)

    log_dir = log_dir or tempfile.mkdtemp(prefix="pt-chaos-")
    replica_env = {
        # CPU fast lane: the chaos contract is about control flow, not
        # the accelerator; replicas must not fight over a TPU
        "JAX_PLATFORMS": platform,
        "TPU_SKIP_MDS_QUERY": "true",
        "PT_FAULT_SEED": str(seed),
    }
    if replica_faults:
        replica_env["PT_FAULT_INJECT"] = replica_faults

    # crash flight recorder (r17): every replica writes black-box
    # bundles on resurrection/EngineFailed/stall. The engine.step
    # fault burst forces a resurrection in each replica process, so a
    # successful run leaves lint-clean bundles behind — the SIGKILLed
    # replica's SURVIVORS (and its own respawn) are exactly the
    # postmortem artifacts a real incident would need.
    flight_root = os.path.join(log_dir, "flight")
    server_args = ["--page-size", str(page_size),
                   "--max-seq-len", str(max_seq_len),
                   "--num-slots", str(num_slots),
                   "--max-engine-errors", "3",
                   "--stall-timeout-s", "120",
                   "--flight-dir",
                   os.path.join(flight_root, "replica{replica}"),
                   "--flight-budget-mb", str(flight_budget_mb)]
    if extra_server_args:
        # these knobs never change a greedy output, so the in-process
        # oracle above stays the reference verbatim
        server_args += list(extra_server_args)
    sup = Supervisor(model=model, replicas=replicas,
                     server_args=server_args, replica_env=replica_env,
                     probe_interval_s=0.3, backoff_base_s=0.5,
                     log_dir=log_dir)
    report = ChaosReport(requests=requests)
    outcomes: List[Optional[Dict]] = [None] * requests
    route_trace: List[Dict] = []
    try:
        sup.start(wait_ready=True)
        router = FailoverRouter(sup, max_failover=replicas + 2)
        router.trace = route_trace.append
        rport = router.start()
        if router_fault_p > 0:
            # router-side net.recv: armed in THIS process, after the
            # oracle ran (fault_point is process-global)
            fi.get_injector().arm("net.recv", probability=router_fault_p,
                                  max_faults=router_fault_max,
                                  seed=seed + 1)

        first_result = threading.Event()

        def client(i: int) -> None:
            payload = {"op": "generate",
                       "prompt": [int(t) for t in prompts[i]],
                       "max_new_tokens": max_new[i],
                       "stream": bool(i % 2)}
            if i >= unkeyed:
                payload["key"] = f"chaos-{seed}-{i}"
            if i < deadline_doomed:
                payload["deadline_ms"] = 1
            else:
                # enforced WELL before the client transport timeout:
                # whatever goes wrong below the socket, the reply is a
                # typed DeadlineExceeded, never a client-side timeout
                payload["deadline_ms"] = int(request_timeout_s * 500)
            t0 = time.monotonic()
            try:
                outcomes[i] = client_request("127.0.0.1", rport, payload,
                                             timeout_s=request_timeout_s)
            except Exception as e:
                outcomes[i] = {"_transport_error":
                               f"{type(e).__name__}: {e}"}
            outcomes[i]["_elapsed_s"] = round(time.monotonic() - t0, 2)
            outcomes[i]["_i"] = i
            first_result.set()

        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True)
                   for i in range(requests)]
        for t in threads:
            t.start()
        if kill_replica:
            # SIGKILL one replica mid-run, once traffic is flowing
            first_result.wait(timeout=request_timeout_s)
            time.sleep(0.5)
            sup.kill_replica(0)
        for t in threads:
            t.join(timeout=request_timeout_s)

        # -- invariant 1: termination, typed ------------------------------
        for i, out in enumerate(outcomes):
            if isinstance(out, dict):
                report.details.append(
                    {"i": i, "elapsed_s": out.get("_elapsed_s"),
                     "kind": out.get("error")
                     or out.get("_transport_error", "ok")})
            if out is None or not isinstance(out, dict):
                report.hangs += 1
                continue
            if "_transport_error" in out:
                # the router owns typed delivery; a torn ROUTER client
                # connection counts as a hang-class failure
                report.hangs += 1
                kind = out["_transport_error"].split(":")[0]
                report.error_kinds[kind] = \
                    report.error_kinds.get(kind, 0) + 1
                continue
            if out.get("error"):
                report.typed_errors += 1
                kind = out["error"]
                report.error_kinds[kind] = \
                    report.error_kinds.get(kind, 0) + 1
                continue
            report.completed += 1
            # -- invariant 3: bit-identical greedy output --------------
            if out.get("generated") != expected[i]:
                report.mismatches += 1

        # -- invariant 2: zero leaks on every replica after drain ----------
        fi.get_injector().disarm("net.recv")
        deadline = time.monotonic() + drain_timeout_s
        sup.wait_ready()  # the killed replica must be back first
        for rep in sup.replicas:
            # the REPLICA-side net.recv faults (PT_FAULT_INJECT in
            # replica_env) stay armed for the replica's whole life, so
            # this very RPC can be torn like any other — a transient
            # the harness itself injects, not a leak. Retry inside the
            # drain deadline exactly like the leak_check loop below
            # (drain is idempotent: stop admitting, finish in-flight);
            # only a replica that never accepts the drain counts as a
            # failure. (Found when the r13 fused-step timing shift
            # moved the seeded fault budget onto the drain RPC.)
            drained = False
            while True:  # do-while: EVERY replica gets >= 1 attempt
                try:
                    _rpc(sup.host, rep.port, {"op": "drain"},
                         timeout_s=10.0)
                    drained = True
                    break
                except Exception:
                    # retries (not first attempts) are bounded by the
                    # shared drain deadline: an earlier replica's slow
                    # drain must not zero out a later one's budget
                    if time.monotonic() >= deadline:
                        break
                    time.sleep(0.5)
            if not drained:
                report.leak_failures += 1
                continue
            ok = False
            chk: Dict = {}
            while time.monotonic() < deadline:
                try:
                    chk = _rpc(sup.host, rep.port, {"op": "leak_check"},
                               timeout_s=10.0)
                except Exception:
                    time.sleep(0.5)
                    continue
                if chk.get("ok"):
                    ok = True
                    break
                if not chk.get("busy"):
                    break  # audit FAILED (not just in-flight work)
                time.sleep(0.5)
            if ok:
                report.replicas_checked += 1
            else:
                report.leak_failures += 1
            # -- invariant 5: ledger reconciliation (r18) ---------------
            # the leak_check reply carries the page-ledger reconcile:
            # the event-derived ownership shadow must match the
            # allocator's books (every alloc/reserve matched by a
            # release/free). A replica without a ledger reports
            # enabled=False and passes vacuously.
            led = chk.get("ledger")
            if isinstance(led, dict) and not led.get("ok", True):
                report.ledger_failures += 1
                report.ledger_errors.extend(
                    f"replica {rep.idx}: {m}"
                    for m in (led.get("mismatches") or
                              ["reconcile failed"])[:4])
            counters = _scrape_counters(sup.host, rep.port)
            report.engine_restarts += \
                int(counters.get("engine_restarts_total", 0))
            report.replayed_requests += \
                int(counters.get("replayed_requests_total", 0))
        # -- invariant 4: lint-clean flight bundles under budget -----------
        # (r17) the engine.step bursts forced resurrections, so each
        # replica process left black-box bundles; every one must lint
        # clean (closed spans, monotonic timeline, consistent metrics
        # export) and each retention ring must hold its byte budget.
        import flight_inspect
        budget = flight_budget_mb << 20
        for rep in sup.replicas:
            rep_dir = os.path.join(flight_root, f"replica{rep.idx}")
            if not os.path.isdir(rep_dir):
                continue
            bundles, errors = flight_inspect.lint_dir(
                rep_dir, budget_bytes=budget)
            report.flight_bundles += len(bundles)
            if errors:
                report.flight_lint_failures += 1
                report.flight_errors.extend(errors[:8])
        if report.flight_bundles == 0 and replica_faults:
            # the fault schedule guarantees resurrections; zero
            # bundles means the recorder silently failed
            report.flight_lint_failures += 1
            report.flight_errors.append(
                f"no flight bundles under {flight_root} despite the "
                f"engine.step fault schedule")
        report.supervisor_restarts = sup.restarts_total
        report.router_failovers = router.failovers_total
        router.stop()
    finally:
        try:
            fi.get_injector().disarm("net.recv")
        except Exception:
            pass
        sup.stop()
    report.wall_s = round(time.monotonic() - t_start, 3)
    if not report.ok:
        # postmortem breadcrumbs: the router's routing history and the
        # replica log locations (subprocess tracebacks live there)
        report.details.append({"route_trace": route_trace,
                               "log_dir": log_dir})
    return report


def run_disagg_chaos(requests: int = 8, seed: int = 0,
                     model: str = "gpt_tiny", page_size: int = 8,
                     max_seq_len: int = 96, num_slots: int = 2,
                     max_new_tokens: int = 6,
                     prompt_len_range=(18, 34),
                     request_timeout_s: float = 300.0,
                     drain_timeout_s: float = 120.0,
                     platform: str = "cpu",
                     log_dir: Optional[str] = None) -> ChaosReport:
    """INVARIANT 6 (r20 disaggregated serving): SIGKILL the
    prefill-class replica MID-HANDOFF. A 1-prefill + 1-decode fleet
    serves keyed long-prompt requests through the router's
    prefill-first dispatch while the prefill replica is killed once
    traffic is flowing — so some requests are mid prefill-hop, some
    mid fetch_pages pull, some already spliced. The contract:

    - every request terminates in a full result or a TYPED error —
      the decode side either completes the handoff, falls back to
      local prefill (bit-identical greedy output), or surfaces a
      typed reply; NEVER a hang;
    - zero leaked pages and a clean page-ledger reconcile on every
      survivor (and on the respawned prefill replica) after drain.

    Reported through the same ChaosReport as the r9 harness; handoff
    accounting lands in ``details``."""
    import numpy as np

    from paddle_tpu.serving.server import client_request
    from paddle_tpu.serving.supervisor import (FailoverRouter,
                                               Supervisor, _rpc)

    t_start = time.monotonic()
    rng = np.random.default_rng(seed)
    lo, hi = prompt_len_range
    # long keyed prompts: every one has shareable full pages, so every
    # request is handoff-eligible (the path under test)
    prompts = [np.asarray(rng.integers(1, 100,
                                       size=int(rng.integers(lo, hi))),
                          np.int32)
               for _ in range(requests)]
    max_new = [max_new_tokens] * requests
    expected = _reference_outputs(model, prompts, max_new,
                                  page_size, max_seq_len)

    log_dir = log_dir or tempfile.mkdtemp(prefix="pt-chaos-disagg-")
    replica_env = {
        "JAX_PLATFORMS": platform,
        "TPU_SKIP_MDS_QUERY": "true",
    }
    server_args = ["--page-size", str(page_size),
                   "--max-seq-len", str(max_seq_len),
                   "--num-slots", str(num_slots),
                   "--stall-timeout-s", "120"]
    sup = Supervisor(model=model, replicas=2,
                     roles=["prefill", "decode"],
                     server_args=server_args, replica_env=replica_env,
                     probe_interval_s=0.3, backoff_base_s=0.5,
                     log_dir=log_dir)
    report = ChaosReport(requests=requests)
    outcomes: List[Optional[Dict]] = [None] * requests
    route_trace: List[Dict] = []
    try:
        sup.start(wait_ready=True)
        router = FailoverRouter(sup, max_failover=4)
        router.trace = route_trace.append
        rport = router.start()

        first_result = threading.Event()

        def client(i: int) -> None:
            payload = {"op": "generate",
                       "prompt": [int(t) for t in prompts[i]],
                       "max_new_tokens": max_new[i],
                       "stream": bool(i % 2),
                       "key": f"disagg-{seed}-{i}",
                       "deadline_ms": int(request_timeout_s * 500)}
            t0 = time.monotonic()
            try:
                outcomes[i] = client_request("127.0.0.1", rport, payload,
                                             timeout_s=request_timeout_s)
            except Exception as e:
                outcomes[i] = {"_transport_error":
                               f"{type(e).__name__}: {e}"}
            outcomes[i]["_elapsed_s"] = round(time.monotonic() - t0, 2)
            first_result.set()

        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True)
                   for i in range(requests)]
        for t in threads:
            t.start()
        # SIGKILL the PREFILL replica MID-HANDOFF: the first wave of
        # requests is inside its prefill hop / fetch_pages pull about
        # one second in (not waiting for a completion — by then the
        # whole wave can be past the handoff). In-flight prefill hops
        # die (router counts a prefill failure -> plain dispatch),
        # in-flight fetch_pages pulls die (decode counts
        # handoff_failures_total -> local prefill) — both typed paths.
        first_result.wait(timeout=1.0)
        sup.kill_replica(0)
        for t in threads:
            t.join(timeout=request_timeout_s)

        for i, out in enumerate(outcomes):
            if isinstance(out, dict):
                report.details.append(
                    {"i": i, "elapsed_s": out.get("_elapsed_s"),
                     "kind": out.get("error")
                     or out.get("_transport_error", "ok")})
            if out is None or not isinstance(out, dict):
                report.hangs += 1
                continue
            if "_transport_error" in out:
                report.hangs += 1
                kind = out["_transport_error"].split(":")[0]
                report.error_kinds[kind] = \
                    report.error_kinds.get(kind, 0) + 1
                continue
            if out.get("error"):
                report.typed_errors += 1
                kind = out["error"]
                report.error_kinds[kind] = \
                    report.error_kinds.get(kind, 0) + 1
                continue
            report.completed += 1
            if out.get("generated") != expected[i]:
                report.mismatches += 1

        # -- zero leaks + ledger reconcile on EVERY replica -----------
        deadline = time.monotonic() + drain_timeout_s
        # the killed prefill replica must be RESPAWNED and ready (not
        # just still flagged ready because the monitor hasn't probed
        # the corpse yet — sup.wait_ready alone races that window)
        while time.monotonic() < deadline:
            if sup.restarts_total >= 1 and \
                    all(r.ready and r.alive() for r in sup.replicas):
                break
            time.sleep(0.3)
        sup.wait_ready()
        for rep in sup.replicas:
            try:
                _rpc(sup.host, rep.port, {"op": "drain"},
                     timeout_s=10.0)
            except Exception:
                report.leak_failures += 1
                continue
            ok = False
            chk: Dict = {}
            while time.monotonic() < deadline:
                try:
                    chk = _rpc(sup.host, rep.port,
                               {"op": "leak_check"}, timeout_s=10.0)
                except Exception:
                    time.sleep(0.5)
                    continue
                if chk.get("ok"):
                    ok = True
                    break
                if not chk.get("busy"):
                    break
                time.sleep(0.5)
            if ok:
                report.replicas_checked += 1
            else:
                report.leak_failures += 1
            led = chk.get("ledger")
            if isinstance(led, dict) and not led.get("ok", True):
                report.ledger_failures += 1
                report.ledger_errors.extend(
                    f"replica {rep.idx}: {m}"
                    for m in (led.get("mismatches") or
                              ["reconcile failed"])[:4])
        report.supervisor_restarts = sup.restarts_total
        report.router_failovers = router.failovers_total
        report.details.append(
            {"handoffs_total": router.handoffs_total,
             "handoff_prefill_failures_total":
                 router.handoff_prefill_failures_total})
        router.stop()
    finally:
        sup.stop()
    report.wall_s = round(time.monotonic() - t_start, 3)
    if not report.ok:
        report.details.append({"route_trace": route_trace,
                               "log_dir": log_dir})
    return report


def run_fleet_cache_chaos(requests: int = 8, seed: int = 0,
                          model: str = "gpt_tiny", page_size: int = 8,
                          max_seq_len: int = 96, num_slots: int = 2,
                          max_new_tokens: int = 6,
                          request_timeout_s: float = 300.0,
                          drain_timeout_s: float = 120.0,
                          platform: str = "cpu",
                          log_dir: Optional[str] = None) -> ChaosReport:
    """INVARIANT 8 (r23 fleet cache): SIGKILL the ADVERTISING PEER
    mid-fleet-cache-fetch under keyed traffic.

    An all-mixed 2-replica fleet (host spill tiers armed, chunked
    prefill on so concurrent same-prefix admissions exercise the r23
    dedup fold). Replica 0 is warmed with a shared-prefix chain and
    advertises it; the harness router deterministically steers every
    pick OFF replica 0 (a stand-in for a forecast-placement pressure
    steer — the routing heuristic is not what's under test), so every
    keyed request dispatches to replica 1 with a fleet-cache
    ``fetch_from`` hint naming replica 0. Once hints are observed,
    replica 0 is SIGKILLed: the first wave's fetch_pages pulls die
    mid-pull, the second wave dispatches against a stale
    advertisement. The contract:

    - every request terminates in a full result or a TYPED error —
      the fetching side's typed PageFetchFailed degrades to LOCAL
      prefill with bit-identical greedy output; NEVER a hang;
    - zero leaked pages and a clean DEDUP-AWARE page-ledger reconcile
      on every survivor (and the respawned peer) after drain —
      folded pages under ``dedup`` owners with ``dedup_hit`` ledger
      reasons must reconcile exactly;
    - the lane actually armed: fleet-cache hints observed before the
      kill, else ``arming_failures`` fails the run loudly."""
    import numpy as np

    from paddle_tpu.serving.prefix_cache import _block_hash
    from paddle_tpu.serving.server import client_request
    from paddle_tpu.serving.supervisor import (FailoverRouter,
                                               Supervisor, _rpc)

    t_start = time.monotonic()
    rng = np.random.default_rng(seed)
    # every prompt shares a 2-full-page prefix (the chain the fleet
    # cache ships) with a distinct random tail
    base = rng.integers(1, 100, size=2 * page_size)
    prompts = [np.asarray(np.concatenate(
                   [base, rng.integers(1, 100,
                                       size=int(rng.integers(2, 17)))]),
               np.int32)
               for _ in range(requests)]
    max_new = [max_new_tokens] * requests
    expected = _reference_outputs(model, prompts, max_new,
                                  page_size, max_seq_len)

    log_dir = log_dir or tempfile.mkdtemp(prefix="pt-chaos-fleet-")
    replica_env = {
        "JAX_PLATFORMS": platform,
        "TPU_SKIP_MDS_QUERY": "true",
    }
    # --spill-mb: both sides of the lane need tiers (the peer exports
    # blobs from them, the fetcher lands blobs into them);
    # --prefill-chunk keeps concurrent same-prefix requests in flight
    # past each other's admission match, forcing the dedup fold
    server_args = ["--page-size", str(page_size),
                   "--max-seq-len", str(max_seq_len),
                   "--num-slots", str(num_slots),
                   "--stall-timeout-s", "120",
                   "--spill-mb", "64",
                   "--prefill-chunk", str(page_size)]
    sup = Supervisor(model=model, replicas=2,
                     server_args=server_args, replica_env=replica_env,
                     probe_interval_s=0.3, backoff_base_s=0.5,
                     log_dir=log_dir)
    report = ChaosReport(requests=requests)
    outcomes: List[Optional[Dict]] = [None] * requests
    route_trace: List[Dict] = []

    class _SteeredRouter(FailoverRouter):
        """Keep picks off the warmed holder (replica 0) so keyed
        requests MUST take the fleet-cache lane to reuse its chain."""

        def _pick(self, exclude, affinity_key=None, keyed=False,
                  exclude_prefill=False):
            return super()._pick(set(exclude) | {0}, affinity_key,
                                 keyed, exclude_prefill)

    try:
        sup.start(wait_ready=True)
        # warm the shared chain onto replica 0 DIRECTLY (the router is
        # not up yet), then wait for its advertisement to reach the
        # supervisor's probe loop — the hint source
        warm = client_request(
            sup.host, sup.replicas[0].port,
            {"op": "generate", "prompt": [int(t) for t in prompts[0]],
             "max_new_tokens": 2, "key": f"fleet-warm-{seed}"},
            timeout_s=request_timeout_s)
        key_hex = _block_hash(None, np.asarray(base[:page_size],
                                               np.int32)).hex()
        adv_deadline = time.monotonic() + 30.0
        while time.monotonic() < adv_deadline and \
                key_hex not in sup.replicas[0].prefix_keys:
            time.sleep(0.2)
        if warm.get("error") or \
                key_hex not in sup.replicas[0].prefix_keys:
            report.arming_failures += 1
            report.details.append(
                {"arming": "warm/advertisement failed",
                 "warm_error": warm.get("error"),
                 "advertised": sorted(sup.replicas[0].prefix_keys)[:4]})
            return report

        router = _SteeredRouter(sup, max_failover=4)
        router.trace = route_trace.append
        rport = router.start()

        def client(i: int) -> None:
            payload = {"op": "generate",
                       "prompt": [int(t) for t in prompts[i]],
                       "max_new_tokens": max_new[i],
                       "stream": bool(i % 2),
                       "key": f"fleet-{seed}-{i}",
                       "deadline_ms": int(request_timeout_s * 500)}
            t0 = time.monotonic()
            try:
                outcomes[i] = client_request(sup.host, rport, payload,
                                             timeout_s=request_timeout_s)
            except Exception as e:
                outcomes[i] = {"_transport_error":
                               f"{type(e).__name__}: {e}"}
            outcomes[i]["_elapsed_s"] = round(time.monotonic() - t0, 2)

        threads = [threading.Thread(target=client, args=(i,),
                                    daemon=True)
                   for i in range(requests)]
        n1 = max(1, requests // 2)
        for t in threads[:n1]:
            t.start()
        # arm check THEN kill: wait until the router has attached at
        # least one fleet-cache hint (the first wave is inside its
        # fetch_pages pull from replica 0 right about now), then
        # SIGKILL the advertising peer mid-pull
        arm_deadline = time.monotonic() + 10.0
        while time.monotonic() < arm_deadline and \
                router.fleet_cache_hints_total == 0:
            time.sleep(0.05)
        hints_pre_kill = router.fleet_cache_hints_total
        if hints_pre_kill == 0:
            report.arming_failures += 1
        time.sleep(0.2)
        sup.kill_replica(0)
        # second wave: dispatched against a stale advertisement — the
        # hint (if any) names a corpse; the typed fetch failure falls
        # back to local prefill on replica 1
        for t in threads[n1:]:
            t.start()
        for t in threads:
            t.join(timeout=request_timeout_s)

        for i, out in enumerate(outcomes):
            if isinstance(out, dict):
                report.details.append(
                    {"i": i, "elapsed_s": out.get("_elapsed_s"),
                     "kind": out.get("error")
                     or out.get("_transport_error", "ok")})
            if out is None or not isinstance(out, dict):
                report.hangs += 1
                continue
            if "_transport_error" in out:
                report.hangs += 1
                kind = out["_transport_error"].split(":")[0]
                report.error_kinds[kind] = \
                    report.error_kinds.get(kind, 0) + 1
                continue
            if out.get("error"):
                report.typed_errors += 1
                kind = out["error"]
                report.error_kinds[kind] = \
                    report.error_kinds.get(kind, 0) + 1
                continue
            report.completed += 1
            if out.get("generated") != expected[i]:
                report.mismatches += 1

        # -- zero leaks + DEDUP-AWARE ledger reconcile everywhere -----
        deadline = time.monotonic() + drain_timeout_s
        while time.monotonic() < deadline:
            if sup.restarts_total >= 1 and \
                    all(r.ready and r.alive() for r in sup.replicas):
                break
            time.sleep(0.3)
        sup.wait_ready()
        for rep in sup.replicas:
            try:
                _rpc(sup.host, rep.port, {"op": "drain"},
                     timeout_s=10.0)
            except Exception:
                report.leak_failures += 1
                continue
            ok = False
            chk: Dict = {}
            while time.monotonic() < deadline:
                try:
                    chk = _rpc(sup.host, rep.port,
                               {"op": "leak_check"}, timeout_s=10.0)
                except Exception:
                    time.sleep(0.5)
                    continue
                if chk.get("ok"):
                    ok = True
                    break
                if not chk.get("busy"):
                    break
                time.sleep(0.5)
            if ok:
                report.replicas_checked += 1
            else:
                report.leak_failures += 1
            led = chk.get("ledger")
            if isinstance(led, dict) and not led.get("ok", True):
                report.ledger_failures += 1
                report.ledger_errors.extend(
                    f"replica {rep.idx}: {m}"
                    for m in (led.get("mismatches") or
                              ["reconcile failed"])[:4])
        report.supervisor_restarts = sup.restarts_total
        report.router_failovers = router.failovers_total
        # survivor-side lane accounting: how the fetches actually
        # ended (pulled vs typed-fallback) plus the dedup fold counts
        surv = _scrape_counters(sup.host, sup.replicas[1].port)
        report.details.append(
            {"fleet_cache_hints_total": router.fleet_cache_hints_total,
             "hints_pre_kill": hints_pre_kill,
             "handoffs_total": router.handoffs_total,
             "survivor_counters":
                 {k: v for k, v in surv.items()
                  if "handoff" in k or "dedup" in k}})
        router.stop()
    finally:
        sup.stop()
    report.wall_s = round(time.monotonic() - t_start, 3)
    if not report.ok:
        report.details.append({"route_trace": route_trace,
                               "log_dir": log_dir})
    return report


def run_autoscale_chaos(requests: int = 8, seed: int = 0,
                        model: str = "gpt_tiny", page_size: int = 8,
                        max_seq_len: int = 96, num_slots: int = 2,
                        max_new_tokens: int = 6,
                        hold_s: float = 3.0,
                        request_timeout_s: float = 300.0,
                        drain_timeout_s: float = 120.0,
                        platform: str = "cpu",
                        log_dir: Optional[str] = None) -> ChaosReport:
    """INVARIANT 7 (r21 autoscaling actuator): SIGKILL the SUPERVISOR
    ITSELF mid-scale-action — once mid-SPAWN (journal ``begin`` +
    process launched, not yet committed) and once mid-SCALE-DOWN
    (victim marked draining, drain not yet run) — under keyed
    traffic, restart it against the same journal, and assert the
    crash-safety contract end to end:

    - **no stranded replica**: after the final graceful stop, zero
      serving processes carry our journal's env marker;
    - **no lost chains**: every keyed request (including those whose
      front door died mid-flight and retried) and a post-recovery
      re-issue of EVERY key return bit-identical greedy tokens;
    - **zero leaked pages**: drain + leak_check + ledger reconcile
      clean on every fleet member at the end;
    - **100% typed termination**: full result or typed error for
      every request — transport retries are bounded by the deadline;
    - the fleet journal lints STRICTLY clean after recovery (crc,
      monotonic seqs, every ``begin`` resolved), and the supervisor's
      autoscale flight bundles lint clean.

    The deterministic kill window comes from ``PT_AUTOSCALE_HOLD_S``:
    every scale action sleeps that long between its journal
    begin/launch record and the commit path, so a kill issued half a
    hold after forcing an action lands inside the
    journaled-but-uncommitted span."""
    import signal as sig
    import subprocess

    import numpy as np

    import flight_inspect
    from paddle_tpu.core.place import refuse_chip_contention
    from paddle_tpu.serving.autoscaler import scan_marked_replicas
    from paddle_tpu.serving.server import client_request
    from paddle_tpu.serving.supervisor import _free_port, _rpc

    t_start = time.monotonic()
    rng = np.random.default_rng(seed)
    # long keyed prompts (>= 2 full pages): every chain has shareable
    # pages, so the scale-down drain's handoff path actually carries
    # state the "no lost chains" assertion depends on
    prompts = [np.asarray(rng.integers(1, 100,
                                       size=int(rng.integers(18, 34))),
                          np.int32)
               for _ in range(requests)]
    max_new = [max_new_tokens] * requests
    expected = _reference_outputs(model, prompts, max_new,
                                  page_size, max_seq_len)

    log_dir = log_dir or tempfile.mkdtemp(prefix="pt-chaos-autoscale-")
    os.makedirs(log_dir, exist_ok=True)
    journal = os.path.join(log_dir, "fleet-journal.json")
    flight_root = os.path.join(log_dir, "flight")
    rport = _free_port()
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": platform,
        "TPU_SKIP_MDS_QUERY": "true",
        "PT_AUTOSCALE_HOLD_S": str(hold_s),
    })
    cmd = [sys.executable, "-m", "paddle_tpu.serving.supervisor",
           "--replicas", "1", "--model", model,
           "--port", str(rport),
           "--probe-interval-s", "0.3", "--backoff-base-s", "0.5",
           "--log-dir", log_dir,
           "--flight-dir", flight_root,
           "--autoscale", "--min-replicas", "1",
           "--max-replicas", "3", "--cooldown-s", "0.5",
           "--autoscale-interval-s", "0.3", "--journal", journal,
           "--",
           "--page-size", str(page_size),
           "--max-seq-len", str(max_seq_len),
           "--num-slots", str(num_slots),
           "--stall-timeout-s", "120"]
    sup_log = open(os.path.join(log_dir, "supervisor-cli.log"), "ab")

    report = ChaosReport(requests=requests)
    outcomes: List[Optional[Dict]] = [None] * requests

    def launch() -> subprocess.Popen:
        # this process ran the reference outputs through JAX: the
        # fleet's replicas must not need the chip it may hold
        refuse_chip_contention(env, "the supervised fleet")
        return subprocess.Popen(cmd, stdout=sup_log,
                                stderr=subprocess.STDOUT, env=env)

    def op(payload: Dict, timeout_s: float = 10.0) -> Dict:
        try:
            return client_request("127.0.0.1", rport, payload,
                                  timeout_s=timeout_s)
        except Exception as e:
            return {"_transport_error": f"{type(e).__name__}: {e}"}

    def wait_router(min_live: int = 1, timeout_s: float = 300.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            h = op({"op": "health"}, timeout_s=5.0)
            if h.get("live", 0) >= min_live:
                return h
            time.sleep(0.3)
        raise RuntimeError(f"router not serving {min_live} live "
                           f"replica(s) within {timeout_s}s "
                           f"(logs: {log_dir})")

    def client(i: int) -> None:
        # the front door DIES when the supervisor is SIGKILLed:
        # transport errors and retryable typed errors are retried
        # (same key — greedy determinism makes that free) until the
        # deadline; only the final outcome is judged
        payload = {"op": "generate",
                   "prompt": [int(t) for t in prompts[i]],
                   "max_new_tokens": max_new[i],
                   "stream": bool(i % 2),
                   "key": f"autoscale-{seed}-{i}",
                   "deadline_ms": int(request_timeout_s * 500)}
        deadline = time.monotonic() + request_timeout_s
        t0 = time.monotonic()
        while True:
            try:
                out = client_request("127.0.0.1", rport, payload,
                                     timeout_s=request_timeout_s)
            except Exception as e:
                out = {"_transport_error":
                       f"{type(e).__name__}: {e}"}
            if "_transport_error" in out or (
                    out.get("error") and out.get("retryable")):
                if time.monotonic() < deadline:
                    time.sleep(0.5)
                    continue
            break
        out["_elapsed_s"] = round(time.monotonic() - t0, 2)
        outcomes[i] = out

    proc = launch()
    try:
        wait_router(min_live=1)

        # ---- phase A: SIGKILL mid-SPAWN under keyed traffic ----------
        wave1 = [threading.Thread(target=client, args=(i,),
                                  daemon=True)
                 for i in range(requests // 2)]
        for t in wave1:
            t.start()
        forcer = threading.Thread(
            target=op, args=({"op": "autoscale",
                              "action": "scale_up"},),
            kwargs={"timeout_s": 60.0}, daemon=True)
        forcer.start()
        # half a hold after forcing: the journal holds begin+launched
        # for the spawn, the commit has not happened
        time.sleep(hold_s * 0.5)
        proc.send_signal(sig.SIGKILL)
        proc.wait(timeout=30)
        report.recoveries += 1
        proc = launch()
        wait_router(min_live=1)
        for t in wave1:
            t.join(timeout=request_timeout_s)

        # ensure >= 2 members before the scale-down phase (the phase-A
        # spawn may have been adopted+committed OR rolled back; a
        # refusal like at_max is fine as long as 2 end up live)
        op({"op": "autoscale", "action": "scale_up"}, timeout_s=240.0)
        wait_router(min_live=2)

        # ---- phase B: SIGKILL mid-SCALE-DOWN under keyed traffic -----
        wave2 = [threading.Thread(target=client, args=(i,),
                                  daemon=True)
                 for i in range(requests // 2, requests)]
        for t in wave2:
            t.start()
        forcer = threading.Thread(
            target=op, args=({"op": "autoscale",
                              "action": "scale_down"},),
            kwargs={"timeout_s": 60.0}, daemon=True)
        forcer.start()
        time.sleep(hold_s * 0.5)
        proc.send_signal(sig.SIGKILL)
        proc.wait(timeout=30)
        report.recoveries += 1
        proc = launch()
        wait_router(min_live=1)
        # wait for the RESUMED drain to resolve: recovery queues the
        # half-finished action; done when nothing is pending/in flight
        # and the journal has no open action left
        deadline = time.monotonic() + drain_timeout_s
        resolved = False
        while time.monotonic() < deadline:
            st = op({"op": "autoscale"}, timeout_s=10.0)
            asc = st.get("autoscaler") or {}
            if asc and asc.get("pending_resumes") == 0 \
                    and not asc.get("action_in_flight"):
                try:
                    with open(journal, encoding="utf-8") as f:
                        jobj = json.load(f)
                    if not flight_inspect.lint_fleet_journal(
                            jobj, allow_open_tail=0):
                        resolved = True
                        break
                except OSError:
                    pass
            time.sleep(0.5)
        if not resolved:
            report.journal_lint_failures += 1
            report.details.append(
                {"journal": "open actions never resolved after "
                            "recovery"})
        for t in wave2:
            t.join(timeout=request_timeout_s)

        # ---- invariant: typed termination + bit-identical outputs ----
        for i, out in enumerate(outcomes):
            if isinstance(out, dict):
                report.details.append(
                    {"i": i, "elapsed_s": out.get("_elapsed_s"),
                     "kind": out.get("error")
                     or out.get("_transport_error", "ok")})
            if out is None or not isinstance(out, dict):
                report.hangs += 1
                continue
            if "_transport_error" in out:
                report.hangs += 1
                kind = out["_transport_error"].split(":")[0]
                report.error_kinds[kind] = \
                    report.error_kinds.get(kind, 0) + 1
                continue
            if out.get("error"):
                report.typed_errors += 1
                kind = out["error"]
                report.error_kinds[kind] = \
                    report.error_kinds.get(kind, 0) + 1
                continue
            report.completed += 1
            if out.get("generated") != expected[i]:
                report.mismatches += 1

        # ---- no lost chains: re-issue EVERY key post-recovery --------
        # chains handed to survivors during the resumed drain (or
        # re-prefilled on first use) must still decode bit-identically
        for i in range(requests):
            rdl = time.monotonic() + request_timeout_s
            while True:
                out = op({"op": "generate",
                          "prompt": [int(t) for t in prompts[i]],
                          "max_new_tokens": max_new[i],
                          "key": f"autoscale-{seed}-{i}"},
                         timeout_s=request_timeout_s)
                if ("_transport_error" in out or (
                        out.get("error") and out.get("retryable"))) \
                        and time.monotonic() < rdl:
                    time.sleep(0.5)
                    continue
                break
            if out.get("generated") != expected[i]:
                report.mismatches += 1
                report.details.append(
                    {"reissue": i,
                     "kind": out.get("error")
                     or out.get("_transport_error", "mismatch")})

        # ---- zero leaks + ledger reconcile on every member -----------
        h = op({"op": "health"}, timeout_s=10.0)
        deadline = time.monotonic() + drain_timeout_s
        for rinfo in (h.get("replicas") or ()):
            port = rinfo.get("port")
            if port is None or not rinfo.get("alive"):
                continue
            try:
                _rpc("127.0.0.1", port, {"op": "drain"},
                     timeout_s=10.0)
            except Exception:
                report.leak_failures += 1
                continue
            ok = False
            chk: Dict = {}
            while time.monotonic() < deadline:
                try:
                    chk = _rpc("127.0.0.1", port,
                               {"op": "leak_check"}, timeout_s=10.0)
                except Exception:
                    time.sleep(0.5)
                    continue
                if chk.get("ok"):
                    ok = True
                    break
                if not chk.get("busy"):
                    break
                time.sleep(0.5)
            if ok:
                report.replicas_checked += 1
            else:
                report.leak_failures += 1
            led = chk.get("ledger")
            if isinstance(led, dict) and not led.get("ok", True):
                report.ledger_failures += 1
                report.ledger_errors.extend(
                    f"replica {rinfo.get('idx')}: {m}"
                    for m in (led.get("mismatches") or
                              ["reconcile failed"])[:4])

        # ---- autoscaler flight bundles + final journal lint ----------
        asup_dir = os.path.join(flight_root, "supervisor")
        if os.path.isdir(asup_dir):
            bundles, errors = flight_inspect.lint_dir(asup_dir)
            report.flight_bundles += len(bundles)
            if errors:
                report.flight_lint_failures += 1
                report.flight_errors.extend(errors[:8])
        try:
            with open(journal, encoding="utf-8") as f:
                jobj = json.load(f)
            errs = flight_inspect.lint_fleet_journal(
                jobj, name="fleet-journal", allow_open_tail=0)
        except Exception as e:
            errs = [f"journal unreadable: {type(e).__name__}: {e}"]
        if errs:
            report.journal_lint_failures += 1
            report.details.append({"journal_lint": errs[:8]})

        # ---- graceful stop, then the stranded-process scan -----------
        proc.send_signal(sig.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            try:
                proc.wait(timeout=30)
            except Exception:
                pass
        sup_log.close()
    time.sleep(1.0)  # let SIGTERMed replicas finish exiting
    stranded = scan_marked_replicas(journal)
    report.stranded_processes = len(stranded)
    if stranded:
        report.details.append({"stranded": stranded})
        for info in stranded.values():  # never leave them behind
            try:
                os.kill(info["pid"], sig.SIGKILL)
            except OSError:
                pass
    report.wall_s = round(time.monotonic() - t_start, 3)
    if not report.ok:
        report.details.append({"log_dir": log_dir})
    return report


def run_roll_chaos(requests: int = 8, seed: int = 0,
                   model: str = "gpt_tiny", page_size: int = 8,
                   max_seq_len: int = 96, num_slots: int = 2,
                   max_new_tokens: int = 6,
                   hold_s: float = 4.0,
                   request_timeout_s: float = 300.0,
                   drain_timeout_s: float = 120.0,
                   converge_timeout_s: float = 300.0,
                   platform: str = "cpu",
                   log_dir: Optional[str] = None) -> ChaosReport:
    """INVARIANT 9 (r24 rolling weight upgrade): interrupt a live
    rolling weight upgrade every way the journal must survive, under
    keyed traffic, and assert the crash-safety contract end to end:

    - **phase A — SIGKILL the SUPERVISOR mid-roll**: force
      ``roll_fleet`` toward a new checkpoint, kill the supervisor
      inside the journaled-but-uncommitted span (``PT_AUTOSCALE_HOLD_S``
      holds every roll action between its journal begin and the swap),
      restart it on the same journal, and require the recovered fleet
      to converge to EXACTLY ONE weight generation — forward if the
      canary proved the checkpoint (``swapped`` record or a committed
      sibling roll), rolled back to the journal's committed config
      otherwise. Never a mixed fleet, never a weightless replica.
    - **phase B — corrupt checkpoint**: a roll whose checkpoint fails
      its crc manifest must be refused TYPED (``canary_swap_failed``)
      with ZERO replicas changed — old weights keep serving.
    - **phase C — SIGKILL a REPLICA mid-swap**: roll again and kill a
      non-canary replica during the roll window; the roll must still
      converge the whole fleet (respawn from the new committed config)
      and report ok.
    - throughout: 100% typed termination; completed mid-roll outputs
      bit-identical to SOME generation's reference (old or new, never
      a cross-spliced hybrid); post-convergence re-issue of EVERY key
      bit-identical to the CONVERGED generation's reference; zero
      leaked pages + clean dedup-aware ledger reconcile on every
      member; journal and flight bundles lint clean; no stranded
      processes."""
    import signal as sig
    import subprocess

    import numpy as np

    import flight_inspect
    from paddle_tpu.core.place import refuse_chip_contention
    from paddle_tpu.distributed.resilience import \
        ResilientCheckpointManager
    from paddle_tpu.inference import create_decode_engine
    from paddle_tpu.models.gpt import checkpoint_state, perturbed_state
    from paddle_tpu.serving.autoscaler import scan_marked_replicas
    from paddle_tpu.serving.server import _build_model, client_request
    from paddle_tpu.serving.supervisor import _free_port, _rpc

    t_start = time.monotonic()
    rng = np.random.default_rng(seed)
    # long keyed prompts: every chain has shareable pages so the
    # pre-swap handoff actually carries state, and generation-salted
    # chain keys are exercised against real cached prefixes
    prompts = [np.asarray(rng.integers(1, 100,
                                       size=int(rng.integers(18, 34))),
                          np.int32)
               for _ in range(requests)]
    max_new = [max_new_tokens] * requests

    log_dir = log_dir or tempfile.mkdtemp(prefix="pt-chaos-roll-")
    os.makedirs(log_dir, exist_ok=True)
    journal = os.path.join(log_dir, "fleet-journal.json")
    flight_root = os.path.join(log_dir, "flight")

    # ---- two real weight generations + a torn third, on disk -------
    # generation 0 == the deterministic boot build, so replicas
    # spawned WITHOUT a checkpoint and replicas restored from ckpt_a
    # serve bit-identical outputs
    base = _build_model(model)
    state_a = checkpoint_state(base)
    state_b = perturbed_state(state_a, scale=1e-3, seed=seed + 1)
    ckpt_a = os.path.join(log_dir, "ckpt-a")
    ckpt_b = os.path.join(log_dir, "ckpt-b")
    ckpt_bad = os.path.join(log_dir, "ckpt-bad")
    ResilientCheckpointManager(ckpt_a).save(1, state_a)
    ResilientCheckpointManager(ckpt_b).save(1, state_b)
    ResilientCheckpointManager(ckpt_bad).save(1, state_b)
    # tear one shard AFTER its crc was manifested: the swap's
    # validate-before-apply must refuse this checkpoint typed
    step_dir = os.path.join(ckpt_bad, "step_00000001")
    shard = sorted(f for f in os.listdir(step_dir)
                   if f.endswith(".npy"))[0]
    with open(os.path.join(step_dir, shard), "r+b") as f:
        f.seek(max(0, os.path.getsize(f.name) // 2))
        f.write(b"\xff" * 16)

    def ref_outputs(state) -> List[List[int]]:
        mm = _build_model(model)
        mm.set_state_dict(state)
        eng = create_decode_engine(mm, num_slots=2,
                                   page_size=page_size,
                                   max_seq_len=max_seq_len)
        rids = [eng.submit(p, mnt)
                for p, mnt in zip(prompts, max_new)]
        results = eng.run()
        eng.close()
        return [[int(t) for t in results[r][len(p):]]
                for r, p in zip(rids, prompts)]

    refs: Dict[int, List[List[int]]] = {0: ref_outputs(state_a),
                                        1: ref_outputs(state_b)}

    rport = _free_port()
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": platform,
        "TPU_SKIP_MDS_QUERY": "true",
        "PT_AUTOSCALE_HOLD_S": str(hold_s),
    })
    # cooldown parked high AND min == the boot size: a pressure-driven
    # scale-down must not eat a fleet member mid-run (a 1-replica
    # fleet converges to one generation trivially — proving nothing),
    # so every journal entry in this run is recovery or a roll
    cmd = [sys.executable, "-m", "paddle_tpu.serving.supervisor",
           "--replicas", "2", "--model", model,
           "--port", str(rport),
           "--checkpoint", ckpt_a,
           "--probe-interval-s", "0.3", "--backoff-base-s", "0.5",
           "--log-dir", log_dir,
           "--flight-dir", flight_root,
           "--autoscale", "--min-replicas", "2",
           "--max-replicas", "3", "--cooldown-s", "3600",
           "--autoscale-interval-s", "0.3", "--journal", journal,
           "--",
           "--page-size", str(page_size),
           "--max-seq-len", str(max_seq_len),
           "--num-slots", str(num_slots),
           "--stall-timeout-s", "120"]
    sup_log = open(os.path.join(log_dir, "supervisor-cli.log"), "ab")

    report = ChaosReport(requests=requests)
    outcomes: List[Optional[Dict]] = [None] * requests

    def launch() -> subprocess.Popen:
        # this process ran the reference outputs through JAX: the
        # fleet's replicas must not need the chip it may hold
        refuse_chip_contention(env, "the supervised fleet")
        return subprocess.Popen(cmd, stdout=sup_log,
                                stderr=subprocess.STDOUT, env=env)

    def op(payload: Dict, timeout_s: float = 10.0) -> Dict:
        try:
            return client_request("127.0.0.1", rport, payload,
                                  timeout_s=timeout_s)
        except Exception as e:
            return {"_transport_error": f"{type(e).__name__}: {e}"}

    def wait_router(min_live: int = 1, timeout_s: float = 300.0):
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            h = op({"op": "health"}, timeout_s=5.0)
            if h.get("live", 0) >= min_live:
                return h
            time.sleep(0.3)
        raise RuntimeError(f"router not serving {min_live} live "
                           f"replica(s) within {timeout_s}s "
                           f"(logs: {log_dir})")

    def client(i: int) -> None:
        payload = {"op": "generate",
                   "prompt": [int(t) for t in prompts[i]],
                   "max_new_tokens": max_new[i],
                   "stream": bool(i % 2),
                   "key": f"roll-{seed}-{i}",
                   "deadline_ms": int(request_timeout_s * 500)}
        deadline = time.monotonic() + request_timeout_s
        t0 = time.monotonic()
        while True:
            try:
                out = client_request("127.0.0.1", rport, payload,
                                     timeout_s=request_timeout_s)
            except Exception as e:
                out = {"_transport_error":
                       f"{type(e).__name__}: {e}"}
            if "_transport_error" in out or (
                    out.get("error") and out.get("retryable")):
                if time.monotonic() < deadline:
                    time.sleep(0.5)
                    continue
            break
        out["_elapsed_s"] = round(time.monotonic() - t0, 2)
        outcomes[i] = out

    def wait_converged(label: str,
                       timeout_s: float) -> Optional[int]:
        """Poll until every live replica reports ONE generation, no
        recovery resume is pending, and the journal lints with zero
        open actions. Returns the converged generation, or None."""
        deadline = time.monotonic() + timeout_s
        last: Dict = {}
        while time.monotonic() < deadline:
            st = op({"op": "fleet_stats"}, timeout_s=10.0)
            fl = st.get("fleet") or {}
            gens = fl.get("weight_generations")
            live = op({"op": "health"}, timeout_s=5.0).get("live", 0)
            asc = (op({"op": "autoscale"},
                      timeout_s=10.0).get("autoscaler") or {})
            last = {"gens": gens, "live": live,
                    "pending": asc.get("pending_resumes"),
                    "in_flight": asc.get("action_in_flight")}
            # >= 2 live: a one-member fleet is single-generation
            # trivially — convergence must mean the whole fleet
            if (isinstance(gens, list) and len(gens) == 1
                    and live >= 2
                    and asc.get("pending_resumes") == 0
                    and not asc.get("action_in_flight")):
                try:
                    with open(journal, encoding="utf-8") as f:
                        jobj = json.load(f)
                    if not flight_inspect.lint_fleet_journal(
                            jobj, allow_open_tail=0):
                        return int(gens[0])
                except OSError:
                    pass
            time.sleep(0.5)
        report.generation_failures += 1
        report.details.append({"converge": label, "state": last})
        return None

    proc = launch()
    try:
        wait_router(min_live=2)

        # ---- phase A: SIGKILL the supervisor mid-roll ---------------
        wave1 = [threading.Thread(target=client, args=(i,),
                                  daemon=True)
                 for i in range(requests // 2)]
        for t in wave1:
            t.start()
        forcer = threading.Thread(
            target=op, args=({"op": "roll", "checkpoint": ckpt_b,
                              "generation": 1},),
            kwargs={"timeout_s": 600.0}, daemon=True)
        forcer.start()
        # half a hold after forcing: the canary's roll action is
        # journaled (begin, maybe handoff) but the swap has not run
        time.sleep(hold_s * 0.5)
        proc.send_signal(sig.SIGKILL)
        proc.wait(timeout=30)
        report.recoveries += 1
        proc = launch()
        wait_router(min_live=2)
        for t in wave1:
            t.join(timeout=request_timeout_s)
        g1 = wait_converged("phase_a", converge_timeout_s)
        if g1 is not None and g1 not in refs:
            report.generation_failures += 1
            report.details.append({"phase_a_generation": g1})
            g1 = None

        # ---- phase B: corrupt checkpoint refused typed --------------
        if g1 is not None:
            rr = (op({"op": "roll", "checkpoint": ckpt_bad,
                      "generation": 9},
                     timeout_s=600.0).get("roll") or {})
            st = op({"op": "fleet_stats"}, timeout_s=10.0)
            gens = (st.get("fleet") or {}).get("weight_generations")
            if (rr.get("ok") is not False
                    or rr.get("refused") != "canary_swap_failed"
                    or gens != [g1]):
                report.generation_failures += 1
                report.details.append(
                    {"corrupt_roll": {"report": rr, "gens": gens}})

        # ---- phase C: SIGKILL a replica mid-swap --------------------
        g2 = None
        if g1 is not None:
            ckpt_c = ckpt_b if g1 == 0 else ckpt_a
            refs[2] = refs[1] if g1 == 0 else refs[0]
            wave2 = [threading.Thread(target=client, args=(i,),
                                      daemon=True)
                     for i in range(requests // 2, requests)]
            for t in wave2:
                t.start()
            roller = threading.Thread(
                target=op, args=({"op": "roll", "checkpoint": ckpt_c,
                                  "generation": 2},),
                kwargs={"timeout_s": 600.0}, daemon=True)
            roller.start()
            # 1.5 holds in: the canary has (usually) committed and a
            # follower sits in its journaled pre-swap window — kill
            # the HIGHEST-idx marked replica (the canary is the
            # lowest live idx), forcing the respawn-forward path
            time.sleep(hold_s * 1.5)
            marked = scan_marked_replicas(journal)
            if marked:
                victim = marked[max(marked)]
                try:
                    os.kill(victim["pid"], sig.SIGKILL)
                except OSError:
                    pass
            roller.join(timeout=600.0)
            for t in wave2:
                t.join(timeout=request_timeout_s)
            g2 = wait_converged("phase_c", converge_timeout_s)
            if g2 is not None and g2 != 2:
                report.generation_failures += 1
                report.details.append({"phase_c_generation": g2})
                g2 = None

        # ---- typed termination + per-generation bit-identity --------
        # a request completed mid-roll may carry EITHER generation's
        # weights; what it must never carry is a cross-spliced hybrid
        for i, out in enumerate(outcomes):
            if isinstance(out, dict):
                report.details.append(
                    {"i": i, "elapsed_s": out.get("_elapsed_s"),
                     "kind": out.get("error")
                     or out.get("_transport_error", "ok")})
            if out is None or not isinstance(out, dict):
                report.hangs += 1
                continue
            if "_transport_error" in out:
                report.hangs += 1
                kind = out["_transport_error"].split(":")[0]
                report.error_kinds[kind] = \
                    report.error_kinds.get(kind, 0) + 1
                continue
            if out.get("error"):
                report.typed_errors += 1
                kind = out["error"]
                report.error_kinds[kind] = \
                    report.error_kinds.get(kind, 0) + 1
                continue
            report.completed += 1
            got = out.get("generated")
            if not any(got == r[i] for r in refs.values()):
                report.mismatches += 1
                report.details.append({"hybrid_output": i})

        # ---- post-convergence: every key re-issued must be
        # bit-identical to the CONVERGED generation (old-generation
        # cached prefixes miss by construction, never splice) --------
        if g2 is not None:
            for i in range(requests):
                rdl = time.monotonic() + request_timeout_s
                while True:
                    out = op({"op": "generate",
                              "prompt": [int(t) for t in prompts[i]],
                              "max_new_tokens": max_new[i],
                              "key": f"roll-{seed}-{i}"},
                             timeout_s=request_timeout_s)
                    if ("_transport_error" in out or (
                            out.get("error") and out.get("retryable"))
                            ) and time.monotonic() < rdl:
                        time.sleep(0.5)
                        continue
                    break
                if out.get("generated") != refs[2][i]:
                    report.mismatches += 1
                    report.details.append(
                        {"reissue": i,
                         "kind": out.get("error")
                         or out.get("_transport_error", "mismatch")})

        # ---- zero leaks + ledger reconcile on every member ----------
        h = op({"op": "health"}, timeout_s=10.0)
        deadline = time.monotonic() + drain_timeout_s
        for rinfo in (h.get("replicas") or ()):
            port = rinfo.get("port")
            if port is None or not rinfo.get("alive"):
                continue
            try:
                _rpc("127.0.0.1", port, {"op": "drain"},
                     timeout_s=10.0)
            except Exception:
                report.leak_failures += 1
                continue
            ok = False
            chk: Dict = {}
            while time.monotonic() < deadline:
                try:
                    chk = _rpc("127.0.0.1", port,
                               {"op": "leak_check"}, timeout_s=10.0)
                except Exception:
                    time.sleep(0.5)
                    continue
                if chk.get("ok"):
                    ok = True
                    break
                if not chk.get("busy"):
                    break
                time.sleep(0.5)
            if ok:
                report.replicas_checked += 1
            else:
                report.leak_failures += 1
            led = chk.get("ledger")
            if isinstance(led, dict) and not led.get("ok", True):
                report.ledger_failures += 1
                report.ledger_errors.extend(
                    f"replica {rinfo.get('idx')}: {m}"
                    for m in (led.get("mismatches") or
                              ["reconcile failed"])[:4])

        # ---- flight bundles + final journal lint --------------------
        asup_dir = os.path.join(flight_root, "supervisor")
        if os.path.isdir(asup_dir):
            bundles, errors = flight_inspect.lint_dir(asup_dir)
            report.flight_bundles += len(bundles)
            if errors:
                report.flight_lint_failures += 1
                report.flight_errors.extend(errors[:8])
        try:
            with open(journal, encoding="utf-8") as f:
                jobj = json.load(f)
            errs = flight_inspect.lint_fleet_journal(
                jobj, name="fleet-journal", allow_open_tail=0)
        except Exception as e:
            errs = [f"journal unreadable: {type(e).__name__}: {e}"]
        if errs:
            report.journal_lint_failures += 1
            report.details.append({"journal_lint": errs[:8]})

        # ---- graceful stop, then the stranded-process scan ----------
        proc.send_signal(sig.SIGTERM)
        try:
            proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            proc.kill()
            proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
            try:
                proc.wait(timeout=30)
            except Exception:
                pass
        sup_log.close()
    time.sleep(1.0)  # let SIGTERMed replicas finish exiting
    stranded = scan_marked_replicas(journal)
    report.stranded_processes = len(stranded)
    if stranded:
        report.details.append({"stranded": stranded})
        for info in stranded.values():  # never leave them behind
            try:
                os.kill(info["pid"], sig.SIGKILL)
            except OSError:
                pass
    report.wall_s = round(time.monotonic() - t_start, 3)
    if not report.ok:
        report.details.append({"log_dir": log_dir})
    return report


def main(argv=None) -> int:
    import argparse
    parser = argparse.ArgumentParser(
        description="seeded chaos run against the crash-safe serving "
                    "stack; exit 0 iff all three invariants held")
    parser.add_argument("--replicas", type=int, default=2)
    parser.add_argument("--requests", type=int, default=12)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--model", default="gpt_tiny")
    parser.add_argument("--no-kill", action="store_true",
                        help="skip the replica SIGKILL")
    parser.add_argument("--faults", default=DEFAULT_REPLICA_FAULTS,
                        help="PT_FAULT_INJECT schedule for replicas "
                             "('' = none)")
    parser.add_argument("--platform", default="cpu")
    parser.add_argument("--log-dir", default=None)
    parser.add_argument(
        "--speculate", type=int, default=0, metavar="K",
        help="arm every replica with ngram speculative decoding at "
             "draft k=K; 0 = off")
    parser.add_argument(
        "--prefill-chunk", type=int, default=0, metavar="TOKENS",
        help="arm every replica with chunked prefill; 0 = off")
    parser.add_argument(
        "--disagg", action="store_true",
        help="run INVARIANT 6 instead (r20): 1 prefill + 1 decode "
             "replica, keyed long-prompt handoff traffic, SIGKILL the "
             "prefill replica mid-handoff — typed termination or "
             "local-prefill fallback everywhere, zero leaks + clean "
             "ledger reconcile on every survivor")
    parser.add_argument(
        "--fleet-cache-chaos", action="store_true",
        help="run INVARIANT 8 instead (r23): all-mixed fleet, keyed "
             "shared-prefix traffic riding fleet-cache fetch_from "
             "hints, SIGKILL the ADVERTISING PEER mid-fetch — typed "
             "fallback to local prefill everywhere, zero leaks, "
             "dedup-aware ledger reconcile clean on every survivor")
    parser.add_argument(
        "--roll-chaos", action="store_true",
        help="run INVARIANT 9 instead (r24): SIGKILL the supervisor "
             "mid-rolling-weight-upgrade and a replica mid-swap "
             "under keyed traffic, plus a corrupt-checkpoint roll — "
             "the fleet converges to exactly one weight generation, "
             "outputs stay bit-identical per generation, typed "
             "termination, zero leaks, journal lints clean")
    parser.add_argument(
        "--autoscale-chaos", action="store_true",
        help="run INVARIANT 7 instead (r21): SIGKILL the SUPERVISOR "
             "mid-spawn and mid-scale-down under keyed traffic, "
             "restart it from the fleet journal — no stranded "
             "replicas, no lost chains, zero leaks, typed "
             "termination, journal lints clean")
    args = parser.parse_args(argv)

    if args.fleet_cache_chaos:
        report = run_fleet_cache_chaos(requests=args.requests,
                                       seed=args.seed,
                                       model=args.model,
                                       platform=args.platform,
                                       log_dir=args.log_dir)
        print(json.dumps(report.to_dict(), indent=2))
        return 0 if report.ok else 1

    if args.roll_chaos:
        report = run_roll_chaos(requests=args.requests,
                                seed=args.seed, model=args.model,
                                platform=args.platform,
                                log_dir=args.log_dir)
        print(json.dumps(report.to_dict(), indent=2))
        return 0 if report.ok else 1

    if args.autoscale_chaos:
        report = run_autoscale_chaos(requests=args.requests,
                                     seed=args.seed, model=args.model,
                                     platform=args.platform,
                                     log_dir=args.log_dir)
        print(json.dumps(report.to_dict(), indent=2))
        return 0 if report.ok else 1

    if args.disagg:
        report = run_disagg_chaos(requests=args.requests,
                                  seed=args.seed, model=args.model,
                                  platform=args.platform,
                                  log_dir=args.log_dir)
        print(json.dumps(report.to_dict(), indent=2))
        return 0 if report.ok else 1

    extra = []
    if args.speculate > 0:
        extra += ["--speculate", str(args.speculate)]
    if args.prefill_chunk > 0:
        extra += ["--prefill-chunk", str(args.prefill_chunk)]
    report = run_chaos(replicas=args.replicas, requests=args.requests,
                       seed=args.seed, model=args.model,
                       replica_faults=args.faults or None,
                       kill_replica=not args.no_kill,
                       platform=args.platform, log_dir=args.log_dir,
                       extra_server_args=extra or None)
    print(json.dumps(report.to_dict(), indent=2))
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
