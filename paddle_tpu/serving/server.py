"""Threaded socket front-end over the continuous-batching engine.

Newline-JSON protocol (one JSON object per line, both directions):

    -> {"op": "generate", "prompt": [1, 2, 3], "max_new_tokens": 8,
        "priority": "interactive", "stream": true, "eos": 7,
        "deadline_ms": 5000, "key": "req-42"}
    <- {"rid": 0, "token": 17, "done": false}          # per token (stream)
    <- {"rid": 0, "done": true, "tokens": [...], "stats": {...}}
    -> {"op": "health"}
    <- {"status": "ok", "active": 1, "queued": 0, "free_pages": 9, ...}
    -> {"op": "stats"}     # metrics snapshot (JSON)
    -> {"op": "metrics"}   # Prometheus text page (in "text")
    -> {"op": "export"}    # structured metrics export (r17): exact
                           # counters + bucket-exact histogram counts
                           # + SLO window counts — what the
                           # supervisor's fleet collector scrapes
    -> {"op": "slo"}       # read / retarget the live SLO monitor
                           # ({"ttft_ms": 50, "tpot_ms": 10} sets and
                           # resets the rolling window)
    -> {"op": "trace"}     # finished span trees + engine step
                           # timeline (r16); {"format": "chrome"}
                           # returns chrome://tracing JSON mergeable
                           # with jax.profiler via tools/merge_traces
    -> {"op": "capacity"}  # memory observatory (r18): pool occupancy
                           # by owner class (inflight/prefix-device/
                           # reserved/free, summing to the pool), spill-
                           # tier residency, the page-ledger tail, and
                           # an EWMA time-to-exhaustion forecast over
                           # step-timeline ring deltas
    -> {"op": "profile"}   # on-demand device profiling: live per-
                           # device HBM accounting (device.memory_stats
                           # where the backend provides it; chip-pending
                           # gauges on CPU) and, with {"ms": N}, a
                           # jax.profiler capture window server-side —
                           # the engine keeps stepping, so the dump
                           # holds real serving steps (merge with span
                           # dumps via tools/merge_traces.py)
    -> {"op": "drain"}     # stop admitting, finish in-flight, close
    -> {"op": "leak_check"}  # engine-thread page-accounting audit
                             # (+ page-ledger reconciliation, r18)
    -> {"op": "fetch_pages"}  # disaggregated serving (r20): serve
                              # chain-page KV blobs (base64, crc32
                              # inside) to a peer replica by exact
                              # chain key and/or chain head (heads
                              # are expanded server-side); keys this
                              # replica cannot produce come back in
                              # "missing" — absence is never an error
    -> {"op": "prefetch"}  # pull a PEER's chains into this replica's
                           # spill tiers (the drain-handoff receiving
                           # side): {"host","port","heads":[hex...]}
                           # — fetch on the conn thread, crc-verified
                           # import on the engine thread

Disaggregated roles (r20): ``--role prefill`` serves prefill_only
requests (admission + chunked prefill; the finished chain parks in
its cache/tiers, the reply is a prefill-ack with the chain keys) and
rejects plain generates typed (WrongRole); ``--role decode`` accepts
a router-supplied ``"fetch_from": {"host", "port"}`` hint on generate
— the conn thread pulls the prompt's chain blobs from that peer
(fetch_pages), the engine imports them into the spill tiers, and
admission SPLICES them in instead of re-prefilling (greedy outputs
bit-identical handoff-vs-local; any fetch failure is a counted,
typed-internal PageFetchFailed fall-back to local prefill, never a
hang). ``--role mixed`` (default) is byte-for-byte the pre-r20
replica.

End-to-end tracing (r16): ``--trace-sample R`` samples a fraction R of
requests into per-request span trees (serving/tracing.py) covering
queue → admit → prefill chunks → decode/verify steps → complete,
stitched across engine resurrection and router failover; an incoming
``"trace": {"id": ..., "parent": ...}`` context (set by the failover
router) forces sampling so one trace id spans router and replica.
Dump via the ``trace`` op; validate with tools/trace_lint.py.

``deadline_ms`` is a completion budget measured from arrival: a
request that cannot finish in time is never admitted (shed from the
queue), and one already decoding is evicted mid-flight with its pages
(and any speculative reservation) returned — either way the client
gets a typed ``{"error": "DeadlineExceeded"}``, never a hang.
``key`` marks the request idempotent for the failover router
(serving/supervisor.py): greedy decoding is deterministic, so a keyed
request that dies with its replica is safely resubmitted to another.

Typed failures are structured replies, never hangs: an overloaded
queue answers ``{"error": "ServerOverloaded", "retry_after_ms": ...}``
(serving/scheduler.py), a prefill whose retries exhausted answers
``{"error": "PrefillFailed"}``, a drain answers in-flight requests
normally and rejects new ones with ``{"error": "ServerDraining"}``, a
slot that stops emitting answers ``{"error": "RequestStalled"}``
(``stall_timeout_s`` watchdog), an expired budget answers
``{"error": "DeadlineExceeded"}``.

Engine resurrection: when ``max_engine_errors`` consecutive step
failures mark the engine dead, the server does NOT fail its clients —
it tears the engine down (pages returned and audited), rebuilds it
(the persistent compile cache makes the re-compiles cache reads),
and replays every in-flight request from its prompt + already-emitted
tokens as one chained greedy prefill. Greedy continuations are
bit-identical to the uninterrupted run, so clients just see a pause.
Only after ``max_engine_restarts`` resurrections does the server fail
typed (EngineFailed) and stop admitting.

Threading model: the ENGINE THREAD exclusively owns the engine (it is
not thread-safe) — connection threads parse requests and hand them
over through an inbox queue. A streaming generate's tokens and final
reply flow back through ONE stream writer thread: the engine appends
each token to the writer's queue (no lock, no wake-up) and wakes the
writer once after each step; the writer formats the lines as bytes
and sends each connection's share without blocking, keeping what a
socket does not take until a selector reports it writable, so a slow
client delays itself alone — never the decode step nor another
stream. Every other reply flows back through the request's outbox
queue to its connection thread. Graceful drain: stop admitting,
finish in-flight work, return every page, `engine.close()` (which
asserts ``check_no_leak``).

Fault sites (distributed/fault_inject.py): ``serving.request`` fires
in the connection thread per request (clients get a retryable typed
error); ``serving.prefill`` fires inside engine admission and is
retried per the ``serving.prefill`` resilience policy; ``engine.step``
fires at the top of the decode step (persistent firing drives the
resurrection path); ``alloc.page`` fires in the page allocator
(admission requeues); ``net.recv`` tears the connection down like a
half-open socket (the failover router resubmits keyed requests).

Run it: ``python -m paddle_tpu.serving.server --model gpt_125m``.
Chunked prefill: ``--prefill-chunk 256`` admits long prompts without
stalling in-flight streams — each engine step prefills at most one
page-aligned 256-token chunk of one admitted prompt before the decode
step (the TTFT-vs-TPOT head-of-line fix; greedy outputs stay
bit-identical to whole prefill, and the ``serving_prefill_debt_tokens``
gauge tracks the outstanding work).
Speculative decoding: ``--speculate 4`` (n-gram/prompt-lookup draft,
no second model) or ``--speculate 4 --draft-model gpt_tiny`` (a small
model drafts; its greedy guesses are verified in one multi-token
forward, so greedy outputs stay bit-identical to the vanilla engine
while each accepted draft amortizes the weight/KV stream). Per-request
acceptance rate and tokens-per-step land in the ``stats`` reply and
the Prometheus ``metrics`` page.

Reference analog: the C serving API / AnalysisPredictor server loop
(SURVEY §1 rows 7/12), TPU-native over one jitted decode step.
"""

from __future__ import annotations

import collections
import json
import queue as queue_mod
import selectors
import socket
import threading
import time
from typing import Any, Dict, Optional

import numpy as np

from ..core.profiler import host_phase
from .fleet_metrics import FlightRecorder
from .metrics import ServingMetrics, SLOAttainment
from .prefix_cache import PrefixCache
from .scheduler import Priority, ServerOverloaded, SLOScheduler
from .tracing import SpanTracer, stderr_span_sink

__all__ = ["ServingServer", "client_request", "PageFetchFailed",
           "fetch_page_blobs"]

import os as _os

# PT_SERVING_DEBUG=1: request-lifecycle tracing on stderr. Since r16
# this IS the span tracer at sample_rate=1.0 with the stderr span sink
# (serving/tracing.py) — one event vocabulary for live debugging, the
# ``trace`` op, and chrome-trace export, replacing the old ad-hoc
# print sites. The chaos harness's postmortems lean on it: it is how
# a request that vanishes between layers is localized.

_PRIORITIES = {"batch": Priority.BATCH, "normal": Priority.NORMAL,
               "interactive": Priority.INTERACTIVE}

_ROLES = ("mixed", "prefill", "decode")


class PageFetchFailed(ConnectionError):
    """A cross-replica page fetch (the r20 ``fetch_pages`` wire op)
    could not deliver usable blobs: peer dead, transport torn, typed
    peer error, or a malformed payload. ALWAYS recoverable — the
    caller falls back to local (chained) prefill, so the client sees
    identical greedy tokens, never a hang; the socket timeout bounds
    the wait and ``handoff_failures_total`` counts the fallback."""


def fetch_page_blobs(host: str, port: int, keys=None, heads=None,
                     timeout_s: float = 30.0):
    """Client side of the ``fetch_pages`` wire op: pull chain-page
    blobs from a peer replica. ``keys`` are exact chain keys (bytes or
    hex); ``heads`` are chain heads the PEER expands to their full
    chains (device subtree + spilled members — the drain-handoff
    path). Returns ``(blobs: {key_bytes: blob_bytes}, missing_hex,
    bytes_total)``; raises :class:`PageFetchFailed` on any transport
    or protocol failure. Blob integrity is NOT checked here — the
    importer re-verifies every crc32 before a blob can ever reach a
    splice (serving/prefix_cache.py ``import_blobs``).

    Chains longer than the peer's FETCH_PAGES_CAP page through the
    reply's ``next_cursor`` (r23): this client keeps pulling bounded
    windows until the peer stops returning one, so a long chain hands
    off WHOLE. Each window is its own timeout-bounded RPC."""
    import base64

    def hexes(ks):
        return [k.hex() if isinstance(k, bytes) else str(k)
                for k in ks]

    base: Dict[str, Any] = {"op": "fetch_pages"}
    if keys:
        base["keys"] = hexes(keys)
    if heads:
        base["heads"] = hexes(heads)
    blobs: Dict[bytes, bytes] = {}
    missing: List[str] = []
    total = 0
    cursor = 0
    # hard bound on pagination rounds: a buggy/malicious peer echoing
    # a never-advancing cursor must not spin this thread forever
    for _round in range(256):
        payload = dict(base)
        if cursor:
            payload["cursor"] = cursor
        try:
            reply = client_request(host, int(port), payload,
                                   timeout_s=timeout_s)
        except Exception as e:
            raise PageFetchFailed(f"{type(e).__name__}: {e}")
        if not isinstance(reply, dict) or reply.get("error"):
            raise PageFetchFailed(
                f"{reply.get('error')}: {reply.get('reason')}"
                if isinstance(reply, dict) else "non-object reply")
        try:
            for khex, b64 in (reply.get("blobs") or {}).items():
                blob = base64.b64decode(b64)
                blobs[bytes.fromhex(khex)] = blob
                total += len(blob)
        except Exception as e:
            raise PageFetchFailed(f"malformed blob payload: "
                                  f"{type(e).__name__}: {e}")
        missing.extend(reply.get("missing") or ())
        nxt = reply.get("next_cursor")
        if not isinstance(nxt, int) or nxt <= cursor:
            break
        cursor = nxt
    return blobs, missing, total


class _Pending:
    """Engine-side record of one in-flight client request.

    A streaming generate's messages (tokens, then the final reply) go
    through the server's `_StreamWriter`, in the order they were
    produced, straight to ``sock``; every other request's go through
    ``outbox`` to its connection thread, which sends them. Either way
    ``None`` on the outbox releases the connection thread: for a
    streaming request the writer puts it there once the last byte is
    sent (or the peer is gone)."""

    __slots__ = ("outbox", "writer", "sock", "taken",
                 "wbuf", "closing", "dead")

    def __init__(self, writer: Optional["_StreamWriter"] = None,
                 sock: Optional[socket.socket] = None):
        self.outbox: "queue_mod.Queue[Optional[Dict]]" = queue_mod.Queue()
        self.writer = writer
        self.sock = sock
        # set once the engine thread took the request off the inbox:
        # from then on it owes the request its end
        self.taken = False
        # the writer's own: bytes not yet sent, the end seen, the peer gone
        self.wbuf = bytearray()
        self.closing = False
        self.dead = False

    @property
    def stream(self) -> bool:
        return self.writer is not None

    def put(self, msg: Optional[Dict]) -> None:
        """Hand ``msg`` (a reply, or None: the end of the request) on
        toward the client, behind everything handed on before it."""
        if self.writer is None:
            self.outbox.put(msg)
        else:
            self.writer.queue.append((self, None, msg, None))

    def finish(self, msg: Dict) -> None:
        """The final reply, then the end of the request."""
        self.put(msg)
        self.put(None)

    def on_token(self):
        """The engine's per-token callback: for a streaming request one
        append to the writer's queue (no lock, no wake-up: the engine
        loop wakes the writer once a step), else None."""
        if self.writer is None:
            return None
        append = self.writer.queue.append

        def on_token(rid, tok, done, _p=self):
            append((_p, rid, tok, done))
        return on_token


class _StreamWriter:
    """The one thread that writes every streaming generate's lines.

    The engine thread appends ``(pending, rid, token, done)`` for a
    token and ``(pending, None, reply_or_None, None)`` for a final
    reply or the end of a request to ``queue`` (a deque: an append
    needs no lock) and calls `kick` once after each engine step. The
    writer formats a token line as bytes (the fields and values of
    ``json.dumps``, without it), appends it to its connection's buffer
    and sends with ``MSG_DONTWAIT`` (the connection thread's blocking
    reads are left alone); what a socket does not take waits in its
    buffer for a selector to report it writable, so a client that
    stops reading delays itself alone. Once a request's end is reached
    and its last byte sent, the writer puts ``None`` into the
    pending's outbox. A socket error drops that connection's bytes,
    releases its connection thread and is counted; it never raises
    here. Sockets must be blocking (no timeout): a socket with a
    timeout waits inside ``send`` whatever the flags.

    Counters (``metrics``): ``stream_writer_wakes_total`` (the engine's
    wakes), ``stream_lines_total`` (token lines), ``stream_deferred_
    sends_total`` (sends that left bytes for the selector),
    ``stream_dropped_lines_total`` (lines, tokens or replies, of a
    connection that died)."""

    def __init__(self, metrics: ServingMetrics):
        self.queue: "collections.deque[tuple]" = collections.deque()
        self._metrics = metrics
        self._wake_r, self._wake_w = socket.socketpair()
        self._wake_r.setblocking(False)
        self._wake_w.setblocking(False)
        self._sel = selectors.DefaultSelector()
        self._sel.register(self._wake_r, selectors.EVENT_READ, None)
        self._blocked: set = set()  # pendings waiting to be writable
        self._stop_at: Optional[float] = None
        self._thread: Optional[threading.Thread] = None
        # counts not yet added to the metrics, by counter name
        self._n = dict.fromkeys(
            ("stream_writer_wakes_total", "stream_lines_total",
             "stream_deferred_sends_total", "stream_dropped_lines_total"),
            0)

    def start(self) -> None:
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="pt-serving-stream-writer")
        self._thread.start()

    def kick(self) -> None:
        """Wake the writer if anything is queued (engine thread)."""
        if self.queue:
            try:
                self._wake_w.send(b"\0")
            except OSError:
                pass  # the pair is full (a wake is waiting) or closed

    def stop(self, timeout_s: float = 5.0) -> None:
        """Send what is queued for up to ``timeout_s``, drop the rest
        (releasing each connection thread), and end the thread."""
        self._stop_at = time.monotonic() + float(timeout_s)
        try:
            self._wake_w.send(b"\0")
        except OSError:
            pass
        self._thread.join(timeout=float(timeout_s) + 5.0)

    def _run(self) -> None:
        try:
            while True:
                timeout = None
                if self._stop_at is not None:
                    timeout = max(0.0, self._stop_at - time.monotonic())
                for key, _ev in self._sel.select(timeout):
                    if key.data is None:
                        self._read_wake()
                    else:
                        self._flush(key.data)
                self._take()
                self._count()
                if self._stop_at is not None and not self.queue and (
                        not self._blocked
                        or time.monotonic() >= self._stop_at):
                    break
            for p in list(self._blocked):
                self._drop(p)
            self._count()
        finally:
            self._close()

    def _read_wake(self) -> None:
        self._n["stream_writer_wakes_total"] += 1
        try:
            while self._wake_r.recv(4096):
                pass
        except OSError:
            pass  # drained

    def _take(self) -> None:
        """Every queued item into its connection's buffer, then one
        send for each connection that got bytes."""
        q, touched = self.queue, {}
        lines = dropped = 0
        while q:
            p, rid, tok, done = q.popleft()
            if p.dead:
                dropped += tok is not None  # an end marker is no line
                continue
            if rid is not None:
                p.wbuf += b'{"rid": %d, "token": %d, "done": %s}\n' % (
                    rid, tok, b"true" if done else b"false")
                lines += 1
            elif tok is not None:
                p.wbuf += json.dumps(tok).encode() + b"\n"
            else:
                p.closing = True
            touched[p] = None
        self._n["stream_lines_total"] += lines
        self._n["stream_dropped_lines_total"] += dropped
        for p in touched:
            self._flush(p)

    def _flush(self, p: _Pending) -> None:
        if p.wbuf:
            try:
                sent = p.sock.send(p.wbuf, socket.MSG_DONTWAIT)
            except (BlockingIOError, InterruptedError):
                sent = 0
            except OSError:  # reset, closed: this peer alone is gone
                self._drop(p)
                return
            del p.wbuf[:sent]
            if p.wbuf:
                self._n["stream_deferred_sends_total"] += 1
                if p not in self._blocked:
                    self._sel.register(p.sock, selectors.EVENT_WRITE, p)
                    self._blocked.add(p)
                return
        if p in self._blocked:
            self._unregister(p)
        if p.closing:
            p.outbox.put(None)

    def _drop(self, p: _Pending) -> None:
        self._n["stream_dropped_lines_total"] += p.wbuf.count(b"\n")
        p.wbuf.clear()
        p.dead = True
        if p in self._blocked:
            self._unregister(p)
        p.outbox.put(None)

    def _unregister(self, p: _Pending) -> None:
        self._blocked.discard(p)
        self._sel.unregister(p.sock)

    def _count(self) -> None:
        for name, n in self._n.items():
            if n:
                self._metrics.counter(name).add(n)
                self._n[name] = 0

    def _close(self) -> None:
        for s in (self._wake_r, self._wake_w):
            try:
                s.close()
            except OSError:
                pass
        self._sel.close()


class ServingServer:
    """In-process serving front-end (tests construct it directly; the
    CLI entry below wraps it).

    ``engine_kwargs`` pass through to `create_decode_engine`
    (num_slots, page_size, num_pages, ...). ``prefix_cache=True``
    builds a `PrefixCache` sized to the engine's page_size;
    ``scheduler=None`` defaults to an `SLOScheduler` with stock
    SLOConfig. ``prefill_retry=None`` resolves the ``serving.prefill``
    site policy from distributed/resilience.py."""

    def __init__(self, model, host: str = "127.0.0.1", port: int = 0,
                 scheduler=None, prefix_cache: bool = True,
                 metrics: Optional[ServingMetrics] = None,
                 prefill_retry="site", max_new_tokens_cap: int = 512,
                 poll_interval_s: float = 0.02,
                 max_engine_errors: int = 32,
                 max_engine_restarts: int = 2,
                 spill_bytes: Optional[int] = None,
                 spill_dir: Optional[str] = None,
                 spill_disk_bytes: Optional[int] = None,
                 trace_sample: float = 0.0,
                 trace_max: int = 64,
                 tracer: Optional[SpanTracer] = None,
                 slo_ttft_ms: Optional[float] = None,
                 slo_tpot_ms: Optional[float] = None,
                 slo_window_s: float = 120.0,
                 flight_dir: Optional[str] = None,
                 flight_budget_bytes: int = 64 << 20,
                 role: str = "mixed",
                 handoff_timeout_s: float = 30.0,
                 blob_format: str = "raw",
                 dedup: bool = True,
                 checkpoint: Optional[str] = None,
                 weight_generation: int = 0,
                 **engine_kwargs):
        from ..distributed.resilience import get_retry_policy

        # disaggregated serving (r20): "mixed" (the default) is
        # byte-for-byte the pre-r20 replica. "prefill" runs admission
        # + (chunked) prefill only — plain generate ops get a typed
        # WrongRole; finished chains park in its cache/tiers and are
        # advertised for peers to fetch. "decode" serves streams and
        # pulls advertised chains over fetch_pages instead of
        # re-prefilling. Both non-mixed roles need a spill tier (the
        # parking lot / wire landing zone), so one is defaulted when
        # the caller configured none.
        if role not in _ROLES:
            raise ValueError(f"role must be one of {_ROLES}; got "
                             f"{role!r}")
        self.role = role
        self.handoff_timeout_s = float(handoff_timeout_s)
        if role != "mixed" and prefix_cache and spill_bytes is None \
                and spill_dir is None:
            spill_bytes = 64 << 20

        # end-to-end tracing (r16): one tracer shared by the server
        # and its (resurrected) engines so a request's span tree spans
        # the whole stack. PT_SERVING_DEBUG=1 forces sample_rate=1.0
        # with the stderr span sink — the unified debug mode.
        if tracer is not None:
            self.tracer = tracer
        else:
            rate, sink = float(trace_sample), None
            if _os.environ.get("PT_SERVING_DEBUG"):
                rate, sink = 1.0, stderr_span_sink
            self.tracer = SpanTracer(sample_rate=rate,
                                     max_traces=int(trace_max),
                                     on_span=sink)
        self.host = host
        self._requested_port = port
        self.scheduler = scheduler if scheduler is not None \
            else SLOScheduler()
        if metrics is not None:
            # the caller owns the SLOAttainment (window size included);
            # constructor kwargs overlay per-target, preserving any
            # target already configured there — the same partial-
            # retarget rule as the runtime "slo" op
            self.metrics = metrics
            if slo_ttft_ms is not None or slo_tpot_ms is not None:
                slo = self.metrics.slo
                slo.set_targets(
                    slo_ttft_ms if slo_ttft_ms is not None
                    else slo.ttft_ms,
                    slo_tpot_ms if slo_tpot_ms is not None
                    else slo.tpot_ms)
        else:
            # live SLO monitor (r17): targets from the CLI (or the
            # runtime "slo" op); without targets the tracker is inert
            # and exports no attainment gauges
            self.metrics = ServingMetrics(
                slo=SLOAttainment(ttft_ms=slo_ttft_ms,
                                  tpot_ms=slo_tpot_ms,
                                  window_s=slo_window_s))
        # crash flight recorder (r17): black-box bundles on engine
        # resurrection / terminal failure / stall — postmortems stop
        # depending on having had stderr attached
        self.flight = (FlightRecorder(flight_dir,
                                      budget_bytes=flight_budget_bytes)
                       if flight_dir else None)
        self._use_prefix_cache = bool(prefix_cache)
        # hierarchical prefix cache (r15): spill-tier config is part of
        # the resurrection recipe — a rebuilt engine gets the same
        # host-RAM/disk tiers (contents start empty; blobs reference
        # nothing outside themselves, but the old cache's books died
        # with the old allocator and clear() scrubbed its blobs)
        self._spill_bytes = spill_bytes
        self._spill_dir = spill_dir
        self._spill_disk_bytes = spill_disk_bytes
        # KV byte substrate (r23): the blob transport codec and the
        # cross-request dedup switch are resurrection-recipe state
        # like the tiers — a rebuilt cache packs/folds identically
        self._blob_format = str(blob_format)
        self._dedup = bool(dedup)
        self._page_size = int(engine_kwargs.get("page_size", 64))
        if prefill_retry == "site":
            prefill_retry = get_retry_policy("serving.prefill")
        # everything a rebuild needs, captured once: engine resurrection
        # constructs a bit-equivalent engine from these after a terminal
        # step failure (fresh allocator, fresh pools, fresh prefix
        # cache — the old one's books die with the old allocator)
        self._model = model
        self._prefill_retry = prefill_retry
        self._engine_kwargs = dict(engine_kwargs)
        pb = self._engine_kwargs.get("prompt_buckets")
        if pb:
            # resurrection replays prompt + already-emitted tokens as
            # ONE chained prefill, so every length up to max_seq_len
            # must be representable as a prompt — a custom bucket
            # ladder that stops short would turn a transparent replay
            # into ReplayFailed. Extend it; prefill jits retrace per
            # shape lazily, so the extra bucket costs nothing until a
            # replay (or a long prompt) first uses it.
            msl = int(self._engine_kwargs.get("max_seq_len")
                      or model.config.max_seq_len)
            self._engine_kwargs["prompt_buckets"] = sorted(
                set(int(x) for x in pb) | {msl})
        # weight hot-swap (r24): the CURRENT generation is part of the
        # resurrection recipe — a rebuilt engine and prefix cache come
        # back salted to the generation that was serving, and replicas
        # (re)spawned mid-roll join the fleet at the right generation
        # via --checkpoint/--weight-generation. A boot checkpoint is
        # applied to the model BEFORE the engine captures its
        # functional state; a missing/corrupt boot checkpoint fails
        # construction (the supervisor's ready probe owns recovery).
        self._weight_generation = int(weight_generation)
        self._checkpoint_dir = checkpoint
        if checkpoint:
            _step, state = self._load_checkpoint_state(checkpoint)
            missing = model.set_state_dict(state)
            if missing:
                raise ValueError(
                    f"boot checkpoint {checkpoint!r} is missing "
                    f"{len(missing)} weight leaves (e.g. "
                    f"{missing[0]!r})")
        self.prefix_cache: Optional[PrefixCache] = None
        self.engine = self._build_engine()
        self.max_new_tokens_cap = int(max_new_tokens_cap)
        self.poll_interval_s = float(poll_interval_s)
        self.max_engine_errors = int(max_engine_errors)
        self.max_engine_restarts = int(max_engine_restarts)
        self._consec_errors = 0
        self._restarts = 0
        # replay ledger: new req_id -> (original prompt, tokens already
        # delivered before the crash, the original request's stats);
        # _on_complete stitches the full sequence — and the telemetry —
        # back together for the final reply
        self._replay: Dict[int, tuple] = {}
        self.metrics.set_gauge_fn(self._gauges)

        # pending weight swap (engine thread): (ctl payload, _Pending,
        # drain deadline). While set, engine admission is paused so
        # active slots can drain to zero — queued and newly-arriving
        # generates WAIT in the engine queue (zero drops, a TTFT dip)
        self._swap_pending: Optional[tuple] = None
        self._inbox: "queue_mod.Queue[tuple]" = queue_mod.Queue()
        self._admission_lock = threading.Lock()
        self._pending: Dict[int, _Pending] = {}  # engine thread only
        self._wake = threading.Event()
        self._engine_done = threading.Event()
        self._draining = False
        self._stopping = False
        self._started = False
        self._listen_sock: Optional[socket.socket] = None
        self._threads = []
        # the one writer of streamed lines, built and started by start()
        self._stream_writer: Optional[_StreamWriter] = None
        self._conn_threads = []
        self._conns = []
        self._conns_lock = threading.Lock()
        self._t0 = time.monotonic()
        # step-histogram scrape marker: (engine identity, last step
        # observed) — resurrection swaps the engine and resets it
        self._tl_seen: tuple = (None, -1)
        # one jax.profiler capture at a time (r18 profile op)
        self._profile_lock = threading.Lock()
        self.port: Optional[int] = None

    def _build_engine(self):
        """(Re)build the decode engine from the captured construction
        recipe. The prefix cache is rebuilt too: its books reference
        pages in the engine's allocator, so a cache may never outlive
        its engine. The persistent compile cache (core/compile_cache,
        enabled inside the engine constructor) turns the rebuilt
        engine's prefill/decode/verify compiles into cache reads — the
        warm-resurrection lane."""
        from ..inference import create_decode_engine
        self.prefix_cache = (
            PrefixCache(self._page_size,
                        spill_bytes=self._spill_bytes,
                        spill_dir=self._spill_dir,
                        disk_bytes=self._spill_disk_bytes,
                        blob_format=self._blob_format,
                        dedup=self._dedup,
                        generation=self._weight_generation)
            if self._use_prefix_cache else None)
        return create_decode_engine(
            self._model, scheduler=self.scheduler,
            prefix_cache=self.prefix_cache,
            prefill_retry=self._prefill_retry,
            weight_generation=self._weight_generation,
            on_complete=self._on_complete,
            # the SAME tracer across resurrections: a replayed
            # request's spans land on its original tree. Program-cost
            # capture is on for served engines — the scrape gauges
            # (serving_program_*) are this server's to export.
            tracer=self.tracer, capture_costs=True,
            **self._engine_kwargs)

    # -- lifecycle ---------------------------------------------------------

    def start(self) -> int:
        """Bind, listen, and start the accept + engine threads.
        Returns the bound port (OS-assigned when constructed with
        port=0)."""
        if self._started:
            return self.port
        self._listen_sock = socket.socket(socket.AF_INET,
                                          socket.SOCK_STREAM)
        self._listen_sock.setsockopt(socket.SOL_SOCKET,
                                     socket.SO_REUSEADDR, 1)
        self._listen_sock.bind((self.host, self._requested_port))
        self._listen_sock.listen(64)
        self.port = self._listen_sock.getsockname()[1]
        self._started = True
        self._stream_writer = _StreamWriter(self.metrics)
        self._stream_writer.start()
        for name, fn in (("engine", self._engine_loop),
                         ("accept", self._accept_loop)):
            t = threading.Thread(target=fn, daemon=True,
                                 name=f"pt-serving-{name}")
            t.start()
            self._threads.append(t)
        return self.port

    def drain(self) -> None:
        """Stop admitting new requests; in-flight and already-queued
        work finishes normally."""
        self._draining = True
        self._wake.set()

    def stop(self, timeout_s: float = 60.0) -> None:
        """Graceful shutdown: drain, finish in-flight, return pages
        (engine.close() asserts check_no_leak), close sockets."""
        self._draining = True
        self._stopping = True
        self._wake.set()
        for t in self._threads:
            if t is threading.current_thread():
                continue
            t.join(timeout=timeout_s)
        if self._stream_writer is not None:
            # the engine has put every request's end on the writer's
            # queue: let it send what it holds before the conn threads
            # are waited for
            self._stream_writer.stop()
        if self._listen_sock is not None:
            try:
                self._listen_sock.close()
            except OSError:
                pass
        with self._conns_lock:
            conns = list(self._conns)
            threads = list(self._conn_threads)
        # let conn threads FLUSH first: the engine thread has exited,
        # so every pending outbox resolves (result or ServerEvicted)
        # within one poll tick — tearing the sockets down before that
        # relay races the final reply and a graceful client sees EOF
        # mid-request instead of its typed answer. Clients that close
        # after the reply release their conn thread immediately; idle
        # keep-alive readers hold readline open, so the wait is
        # bounded and stragglers are force-closed below.
        flush_deadline = time.monotonic() + 5.0
        for t in threads:
            if t is threading.current_thread():
                continue
            t.join(timeout=max(0.0,
                               flush_deadline - time.monotonic()))
        for c in conns:
            try:
                c.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                c.close()
            except OSError:
                pass
        for t in threads:
            if t is threading.current_thread():
                continue
            t.join(timeout=5.0)

    def __enter__(self) -> "ServingServer":
        self.start()
        return self

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- engine thread -----------------------------------------------------

    def _engine_loop(self) -> None:
        """Engine-thread entry: the no-hang contract is STRUCTURAL —
        whatever escapes the serving loop below (it should handle
        everything itself) becomes a typed EngineFailed broadcast plus
        ``_engine_done``, never a silently dead thread with clients
        spinning on their outboxes forever."""
        try:
            self._engine_loop_inner()
        except Exception:
            try:
                self._fail_engine()
            finally:
                self._engine_done.set()

    def _engine_loop_inner(self) -> None:
        while True:
            # re-read self.engine every iteration: resurrection swaps
            # the instance mid-loop
            eng = self.engine
            # the `loop` host phase (core/profiler.py): what this
            # thread does between two engine steps. Its time is the
            # next step record's `gap_us`, taken by the engine from the
            # end of one commit to the start of the next step; here it
            # is only named for a profiler session.
            with host_phase("loop"):
                with host_phase("inbox"):
                    self._drain_inbox()
                self._maybe_apply_swap(eng)
                has_work = eng.num_queued or eng.num_active
            if has_work:
                try:
                    before = eng.num_queued + eng.num_active
                    eng.step()
                    after = eng.num_queued + eng.num_active
                    self._consec_errors = 0
                    # the step's tokens and replies: one wake-up for all
                    self._stream_writer.kick()
                    if after and after == before and not eng.num_active:
                        # queued but nothing admissible and nothing
                        # decoding: don't hot-spin on the free list
                        time.sleep(self.poll_interval_s)
                except Exception:
                    # a failed prefill already unwound inside the
                    # engine (request requeued, or FAILED with a typed
                    # reply via on_complete) — the serving loop must
                    # outlive it either way. A PERSISTENT step failure
                    # (decode jit broken, pools consumed) must not
                    # wedge clients forever: past the consecutive-error
                    # cap the engine is RESURRECTED — torn down, pages
                    # audited, rebuilt, and every in-flight request
                    # replayed from its token history (clients see a
                    # pause, not an error); only when restarts are
                    # exhausted too does the server fail typed and
                    # stop admitting.
                    self.metrics.counter("engine_errors_total").add()
                    self._consec_errors += 1
                    # a failing step never reaches its own deadline /
                    # stall sweeps — run them here so a broken engine
                    # still sheds doomed work typed instead of letting
                    # requests ride the outage into a hang
                    try:
                        self.engine.expire_deadlines()
                        self.engine.evict_stalled()
                    except Exception:
                        pass
                    if self._consec_errors >= self.max_engine_errors:
                        if self._restarts < self.max_engine_restarts:
                            try:
                                self._resurrect_engine()
                            except Exception:
                                # the rebuild/replay failed too —
                                # almost certainly the same root cause
                                # that broke the engine. Terminal and
                                # TYPED, never a dead thread.
                                self.metrics.counter(
                                    "engine_resurrect_failures_total"
                                ).add()
                                self._fail_engine()
                        else:
                            self._fail_engine()
                    self._stream_writer.kick()
                    time.sleep(self.poll_interval_s)
                continue
            self._stream_writer.kick()  # replies the inbox gave
            if self._stopping and self._inbox.empty():
                self._resolve_swap_pending(
                    {"error": "ServerEvicted",
                     "reason": "server shutting down"})
                try:
                    eng.close()
                finally:
                    # unblock any conn thread still waiting on a
                    # pending outbox (evicted replies already sent by
                    # close() -> on_complete)
                    for p in self._pending.values():
                        p.put(None)
                    self._pending.clear()
                    self._stream_writer.kick()
                    self._engine_done.set()
                return
            self._wake.wait(timeout=self.poll_interval_s)
            self._wake.clear()

    def _flight_record(self, reason: str, inflight=None,
                       **extra) -> None:
        """Crash flight recorder (r17): assemble + atomically write
        one black-box bundle (engine thread; bounded structures only —
        timeline ring, finished-trace ring, slot-count inflight dump).
        Never raises: a postmortem artifact must not create the next
        incident."""
        if self.flight is None:
            return
        eng = self.engine

        def collect() -> Dict:
            reqs = (inflight if inflight is not None
                    else eng.dump_inflight())
            return {
                # v2 bundles (r18) carry the page-ledger tail and a
                # capacity snapshot; tools/flight_inspect.py requires
                # and lints both at this version
                "v": 2,
                "page_ledger": getattr(eng, "ledger_tail",
                                       lambda n: [])(256),
                "capacity": self._capacity(),
                "model": type(self._model).__name__,
                # weight hot-swap (r24): which generation was serving
                # when the bundle was cut (flight_inspect lints it)
                "weight_generation": self._weight_generation,
                "engine": getattr(eng, "flight_summary",
                                  lambda: {})(),
                "recipe": dict(self._engine_kwargs),
                "restarts": self._restarts,
                "consec_errors": self._consec_errors,
                "step_timeline": getattr(eng, "step_timeline",
                                         lambda: [])(),
                "traces": self.tracer.finished(),
                "events": self.tracer.events(),
                "metrics": self.metrics.export(),
                "inflight": [{"req_id": int(r.req_id),
                              "state": r.state,
                              "prompt_len": int(len(r.prompt)),
                              "generated": int(len(r.generated)),
                              "priority": int(r.priority)}
                             for r in reqs],
                **extra,
            }

        self.flight.record(reason, collect)

    def _resurrect_engine(self) -> None:
        """Terminal engine-step failure, recoverable edition (engine
        thread): snapshot every request the dead engine still owes an
        answer for, tear the engine down (pages returned and audited by
        ``close()``), rebuild it from the captured recipe, and REPLAY
        each in-flight request — its prompt plus already-emitted tokens
        resubmitted as one chained greedy prefill, so the continuation
        is bit-identical to the uninterrupted run and the client sees a
        pause instead of an error. Requests still in the server inbox
        are untouched: the next ``_drain_inbox`` submits them to the
        new engine."""
        self._restarts += 1
        self.metrics.counter("engine_restarts_total").add()
        old = self.engine
        snapshot = old.dump_inflight()
        # flight bundle BEFORE teardown: the dying engine's timeline
        # ring and in-flight set are exactly what the postmortem needs
        self._flight_record("resurrect", inflight=snapshot)
        self.tracer.annotate(
            "resurrect",
            rids=[(r.req_id, len(r.prompt), len(r.generated), r.state)
                  for r in snapshot],
            pending=sorted(self._pending), inbox=self._inbox.qsize(),
            restarts=self._restarts)
        # detach each in-flight request's TRACE before teardown: the
        # close() below evicts every slot, and the engine's terminal
        # path would otherwise FINISH the tree — the replayed request
        # must keep appending to it (one tree across the stitch, the
        # r16 contract). The open stage span is closed typed here.
        saved_traces: Dict[int, Any] = {}
        for req in snapshot:
            tr = req.trace
            if tr is None:
                continue
            if req.span is not None:
                tr.end(req.span, state="resurrect")
                req.span = None
            tr.event("resurrect_replay", parent=tr.anchor,
                     restarts=self._restarts,
                     pre_tokens=len(req.generated))
            saved_traces[req.req_id] = tr
            req.trace = None
        # detach the completion hook BEFORE close(): teardown evictions
        # are an implementation detail of the restart, not terminal
        # replies the clients should see
        old.set_on_complete(None)
        try:
            old.close()
        except Exception:
            # a torn allocator is possible when the failure hit
            # half-applied host state; the old engine (and its pools)
            # are dropped wholesale either way — count it, don't die
            self.metrics.counter("engine_teardown_leaks_total").add()
        self.engine = self._build_engine()
        if self._swap_pending is not None:
            # a swap was draining when the engine died: the rebuilt
            # engine must keep the admission gate down or the replays
            # below pin slots forever against the pending swap
            self.engine.pause_admission = True
        for req in snapshot:
            pending = self._pending.pop(req.req_id, None)
            # compose across repeated resurrections: the snapshot's
            # prompt may itself be a replay prompt
            prior = self._replay.pop(req.req_id, None)
            if prior is not None:
                orig_prompt, pre, orig_stats = prior
                pre = list(pre) + [int(t) for t in req.generated]
            else:
                orig_prompt = [int(t) for t in req.prompt]
                pre = [int(t) for t in req.generated]
                orig_stats = req.stats
            replay_prompt = np.concatenate(
                [req.prompt, np.asarray(req.generated, np.int32)])
            remaining = req.max_new_tokens - len(req.generated)
            on_token = None if pending is None else pending.on_token()
            try:
                new_rid = self.engine.submit(
                    replay_prompt, max_new_tokens=remaining,
                    eos_token=req.eos_token, priority=req.priority,
                    deadline_t=req.deadline_t, on_token=on_token,
                    # a handoff-blocking prefill job keeps its boost
                    # across resurrection — a decode replica is still
                    # waiting on the chain (r20)
                    handoff=getattr(req, "handoff", False),
                    # continue the original span tree on the rebuilt
                    # engine — queue/admit/prefill/decode spans of the
                    # replay append after the resurrect_replay marker
                    trace=saved_traces.get(req.req_id))
            except Exception as e:
                self.tracer.annotate(
                    "replay_failed", old_rid=req.req_id,
                    error=f"{type(e).__name__}: {e}")
                tr = saved_traces.get(req.req_id)
                if tr is not None:
                    tr.event("complete", parent=tr.anchor,
                             state="replay_failed")
                    self.tracer.finish(tr, state="replay_failed")
                if pending is not None:
                    pending.finish({"error": "ReplayFailed",
                                    "reason": f"{type(e).__name__}: {e}"})
                continue
            self.metrics.counter("replayed_requests_total").add()
            self.tracer.annotate(
                "replay", old_rid=req.req_id, new_rid=new_rid,
                pending=pending is not None)
            self._replay[new_rid] = (orig_prompt, pre, orig_stats)
            if pending is not None:
                self._pending[new_rid] = pending
        self._consec_errors = 0
        self._wake.set()

    def _fail_engine(self) -> None:
        """Terminal engine failure (engine thread): every in-flight and
        inboxed client gets a typed EngineFailed reply, the engine's
        pages are torn down best-effort, and the server stops admitting
        (health keeps answering with status "draining")."""
        self._draining = True
        self._flight_record("engine_failed")
        self._resolve_swap_pending(
            {"error": "SwapFailed",
             "reason": "engine failed terminally before the swap "
                       "could apply"})
        err = {"error": "EngineFailed",
               "reason": f"decode engine failed "
                         f"{self._consec_errors} consecutive steps; "
                         f"server stopped admitting"}
        try:
            self.engine.close()  # sends ServerEvicted via on_complete
        except Exception:
            pass
        for p in self._pending.values():
            p.finish(dict(err))
        self._pending.clear()
        while True:
            item = self._take_inbox()
            if item is None:
                break
            item[1].finish(dict(err))
        self._stream_writer.kick()

    def _take_inbox(self) -> Optional[tuple]:
        """The next ``(payload, pending)`` off the inbox, or None. From
        here the engine thread owes the pending its end."""
        try:
            payload, pending = self._inbox.get_nowait()
        except queue_mod.Empty:
            return None
        pending.taken = True
        return payload, pending

    def _drain_inbox(self) -> None:
        while True:
            item = self._take_inbox()
            if item is None:
                return
            payload, pending = item
            if payload.get("ctl") == "leak_check":
                # page-accounting audit, answered ON the engine thread
                # so it never races a step's allocator mutations (the
                # chaos harness's per-replica invariant probe)
                pending.finish(self._leak_check())
                continue
            if payload.get("ctl") == "fetch_pages":
                # r20 handoff serving side: pack/serve chain blobs ON
                # the engine thread — device reads (pack_page_blob via
                # _read_page) and tier index walks must not race a
                # step's pool donation or LRU mutation
                pending.finish(self._serve_fetch_pages(payload))
                continue
            if payload.get("ctl") == "import_blobs":
                # r20 handoff/prefetch receiving side: tier puts are
                # engine-thread state (the conn thread already did the
                # network pull; this is dict inserts + crc checks)
                pending.finish(self._import_blobs(payload))
                continue
            if payload.get("ctl") == "swap":
                # weight hot-swap (r24): the conn thread already
                # loaded + crc-validated the checkpoint; park the
                # apply until active slots drain (admission pauses,
                # nothing is dropped — _maybe_apply_swap finishes it)
                if self._swap_pending is not None:
                    pending.finish(
                        {"error": "SwapFailed",
                         "reason": "another weight swap is already "
                                   "pending on this replica"})
                    continue
                self.engine.pause_admission = True
                deadline = time.monotonic() + float(
                    payload.get("timeout_s") or 120.0)
                self._swap_pending = (payload, pending, deadline)
                continue

            # r20: a generate that rode a wire handoff carries the
            # fetched blobs — import them into the cache tiers NOW so
            # this request's admission restores+splices them instead
            # of re-prefilling (corrupt blobs are dropped counted by
            # the crc re-verify; missing ones fall to chained prefill)
            handoff_info = None
            ho = payload.pop("_handoff", None)
            if ho is not None:
                pc = self.prefix_cache
                if pc is not None and getattr(pc, "tiers", None):
                    rep = pc.import_blobs(ho["blobs"],
                                          heads=ho.get("heads", ()))
                    handoff_info = {"ms": ho["ms"], "bytes": ho["bytes"],
                                    "imported": rep["imported"],
                                    "corrupt": rep["corrupt"]}
                    if rep["corrupt"] and not rep["imported"]:
                        # every fetched blob failed its crc re-verify:
                        # the handoff delivered nothing — a counted
                        # fallback to local prefill
                        self.metrics.counter(
                            "handoff_failures_total").add()
            try:
                rid = self.engine.submit(
                    np.asarray(payload["prompt"], np.int32),
                    max_new_tokens=payload["max_new_tokens"],
                    eos_token=payload.get("eos"),
                    priority=payload.get("priority", Priority.NORMAL),
                    deadline_t=payload.get("deadline_t"),
                    on_token=pending.on_token(),
                    # a prefill_only job is handoff-blocking: the
                    # router is mid-handoff and a decode replica waits
                    # on this chain (scheduler boost, r20)
                    handoff=bool(payload.get("handoff")),
                    handoff_info=handoff_info,
                    # upstream trace context (the failover router's
                    # forward span) forces sampling and links this
                    # replica's tree under the router's; without it
                    # the engine's own sampler decides
                    trace_ctx=payload.get("trace_ctx"))
            except Exception as e:
                # broad on purpose: this runs on the ENGINE thread, and
                # one malformed payload (e.g. prompt [null] -> numpy
                # TypeError) must cost that client a BadRequest, never
                # the thread every other client depends on
                pending.finish({"error": "BadRequest",
                                "reason": f"{type(e).__name__}: {e}"})
                continue
            self._pending[rid] = pending

    # -- weight hot-swap (r24) ----------------------------------------------

    def _resolve_swap_pending(self, reply: Dict) -> None:
        """Answer (and clear) a parked swap with ``reply`` — the
        shutdown / terminal-failure escape so the swapping client can
        never hang on its outbox (engine thread)."""
        if self._swap_pending is None:
            return
        _payload, pending, _deadline = self._swap_pending
        self._swap_pending = None
        self.metrics.counter("weight_swaps_failed_total").add()
        pending.finish(dict(reply))

    def _maybe_apply_swap(self, eng) -> None:
        """Engine-thread gate of a parked swap: once active slots
        drain to zero (admission is paused, so they only ever shrink),
        apply it between steps; past the drain deadline, fail it typed
        with the old weights still serving."""
        if self._swap_pending is None:
            return
        payload, pending, deadline = self._swap_pending
        if eng.num_active and time.monotonic() < deadline:
            return  # active slots still finishing on the old weights
        self._swap_pending = None
        reply = self._apply_swap(eng, payload)
        eng.pause_admission = False
        self._wake.set()
        pending.finish(reply)

    def _apply_swap(self, eng, payload: Dict) -> Dict:
        """Apply a drained, pre-validated swap (engine thread). Any
        failure is a typed SwapFailed reply — the engine refused
        before touching live state, so the old generation keeps
        serving, pinned."""
        from ..inference.continuous_batching import SwapFailed
        outcome = ("rolled_back" if payload.get("rollback")
                   else "committed")
        if eng.num_active:
            self.metrics.counter("weight_swaps_failed_total").add()
            return {"error": "SwapFailed",
                    "reason": f"engine did not drain its "
                              f"{eng.num_active} active slot(s) "
                              f"within the swap timeout"}
        try:
            info = eng.swap_weights(payload["state"],
                                    generation=payload.get("generation"))
        except SwapFailed as e:
            self.metrics.counter("weight_swaps_failed_total").add()
            self._flight_record("swap_failed", swap_error=str(e))
            return {"error": "SwapFailed", "reason": str(e)}
        except Exception as e:
            self.metrics.counter("weight_swaps_failed_total").add()
            self._flight_record(
                "swap_failed",
                swap_error=f"{type(e).__name__}: {e}")
            return {"error": "SwapFailed",
                    "reason": f"{type(e).__name__}: {e}"}
        self._weight_generation = int(info["generation"])
        self.metrics.counter(f"weight_swaps_{outcome}_total").add()
        self.metrics.swap_ms.observe(float(info["swap_ms"]))
        self.tracer.annotate("weight_swap", outcome=outcome,
                             generation=info["generation"],
                             swap_ms=info["swap_ms"],
                             checkpoint_step=payload.get("step"))
        return {"ok": True, "outcome": outcome, **info}

    @staticmethod
    def _load_checkpoint_state(directory: str):
        """Load + crc-validate the newest valid checkpoint under
        ``directory`` (ResilientCheckpointManager manifest layout) on
        the CALLING thread — the live engine is never touched. The
        ``checkpoint.load`` fault site fires per attempt and transient
        faults retry per its builtin policy; a directory with no valid
        checkpoint raises a typed SwapFailed. Returns (step, state)."""
        from ..distributed.fault_inject import fault_point
        from ..distributed.resilience import (
            ResilientCheckpointManager, get_retry_policy)
        from ..inference.continuous_batching import SwapFailed

        def load_once():
            fault_point("checkpoint.load")
            mgr = ResilientCheckpointManager(directory)
            got = mgr.restore_latest_valid()
            if got is None:
                raise SwapFailed(
                    f"no valid checkpoint under {directory!r} "
                    f"(skipped corrupt/partial steps: "
                    f"{mgr.last_skipped})")
            return got

        policy = get_retry_policy("checkpoint.load")
        return policy.call(load_once, site="checkpoint.load")

    def _swap(self, msg: Dict, send) -> None:
        """The ``swap`` op (conn thread): load-and-validate the new
        checkpoint fully BEFORE the engine hears about it — a torn or
        corrupt checkpoint is a typed SwapFailed with the old weights
        still serving — then hand the host-side state to the engine
        thread, which drains active slots and applies it between
        steps. Queued and newly-arriving generates wait (zero drops);
        the reply carries the new generation and swap_ms."""
        from ..distributed.resilience import RetryExhausted
        from ..inference.continuous_batching import SwapFailed
        ckpt = msg.get("checkpoint")
        if not isinstance(ckpt, str) or not ckpt:
            send({"error": "BadRequest",
                  "reason": "swap needs 'checkpoint': a checkpoint-"
                            "manager directory path"})
            return
        gen = msg.get("generation")
        if gen is not None and (isinstance(gen, bool)
                                or not isinstance(gen, int)
                                or gen < 0):
            send({"error": "BadRequest",
                  "reason": "generation must be a non-negative int"})
            return
        timeout_s = msg.get("timeout_s")
        if timeout_s is not None and (
                isinstance(timeout_s, bool)
                or not isinstance(timeout_s, (int, float))
                or timeout_s <= 0):
            send({"error": "BadRequest",
                  "reason": "timeout_s must be a positive number of "
                            "seconds"})
            return
        try:
            step, state = self._load_checkpoint_state(ckpt)
        except (SwapFailed, RetryExhausted) as e:
            self.metrics.counter("weight_swaps_failed_total").add()
            send({"error": "SwapFailed", "reason": str(e)})
            return
        except Exception as e:
            self.metrics.counter("weight_swaps_failed_total").add()
            send({"error": "SwapFailed",
                  "reason": f"{type(e).__name__}: {e}"})
            return
        payload: Dict[str, Any] = {"ctl": "swap", "state": state,
                                   "step": step}
        if gen is not None:
            payload["generation"] = gen
        if msg.get("rollback"):
            payload["rollback"] = True
        if timeout_s is not None:
            payload["timeout_s"] = float(timeout_s)
        pending = _Pending()
        self._inbox.put((payload, pending))
        self._wake.set()
        self._await_outbox(pending, send)

    def _on_complete(self, req) -> None:
        """Engine callback: terminal state for a request (any state)."""
        replay = self._replay.pop(req.req_id, None)
        if replay is not None:
            # telemetry must describe the request the CLIENT
            # experienced — one generation from the original submit,
            # every pre-crash token included — not the
            # post-resurrection slice (which would undercount
            # tokens_generated_total and report replay-relative
            # latencies)
            orig_prompt, pre, orig_stats = replay
            st = req.stats
            st.tokens_out = len(req.generated) + len(pre)
            st.prompt_len = len(orig_prompt)
            st.submit_t = orig_stats.submit_t
            if orig_stats.admit_t:
                st.admit_t = orig_stats.admit_t
            if orig_stats.first_token_t:
                st.first_token_t = orig_stats.first_token_t
            if orig_stats.prefill_ms:
                st.prefill_ms = orig_stats.prefill_ms
        self.metrics.observe_request(req)
        # the reply below is the server's result delivery — drop the
        # engine's retained copy or a long-lived server accumulates
        # every DecodeRequest (and its outbox closure) ever finished
        self.engine.result(req.req_id, pop=True)
        pending = self._pending.pop(req.req_id, None)
        if pending is None:
            return  # engine used without the server front-end
        if req.state == "done":
            tokens = [int(t) for t in req.tokens]
            generated = [int(t) for t in req.generated]
            stats = _json_stats(req.stats)
            if replay is not None:
                # a resurrected engine served the tail of this request;
                # the reply must read as ONE uninterrupted generation:
                # original prompt, pre-crash tokens stitched back in
                # front of the replayed continuation
                orig_prompt, pre, _orig_stats = replay
                generated = list(pre) + generated
                tokens = list(orig_prompt) + generated
                stats["tokens_out"] = len(generated)
                stats["replayed"] = True
            msg: Dict[str, Any] = {
                "rid": req.req_id, "done": True,
                "tokens": tokens, "generated": generated,
                "stats": stats}
        elif req.state == "deadline":
            msg = {"rid": req.req_id, "error": "DeadlineExceeded",
                   "reason": "deadline_ms elapsed before completion",
                   "tokens_out": int(req.stats.tokens_out)}
            fors = getattr(req, "page_forensics", None)
            if fors:
                # memory observatory (r18): the unwound request's page
                # ownership history rides the typed reply (bounded)
                msg["page_forensics"] = fors[-8:]
        elif req.state == "stalled":
            # a stall is the third black-box trigger: something below
            # the engine stopped making progress without erroring —
            # the rate-limited bundle captures the step timeline that
            # explains the silence (r17)
            fors = getattr(req, "page_forensics", None)
            self._flight_record("stall", stalled_rid=int(req.req_id),
                                page_forensics=fors or [])
            msg = {"rid": req.req_id, "error": "RequestStalled",
                   "reason": f"no token for "
                             f"{self.engine.stall_timeout_s}s; evicted",
                   "tokens_out": int(req.stats.tokens_out)}
            if fors:
                msg["page_forensics"] = fors[-8:]
        elif req.state == "shed":
            cfg = getattr(self.scheduler, "cfg", None)
            msg = {"rid": req.req_id, "error": "ServerOverloaded",
                   "reason": "queued past SLO shed_after_s",
                   "retry_after_ms": getattr(cfg, "retry_after_ms", 1000)}
        elif req.state == "failed":
            msg = {"rid": req.req_id, "error": "PrefillFailed",
                   "attempts": req.stats.prefill_attempts}
        else:  # evicted (drain/close)
            msg = {"rid": req.req_id, "error": "ServerEvicted",
                   "reason": "server shutting down"}
        pending.finish(msg)

    # -- connection threads ------------------------------------------------

    def _accept_loop(self) -> None:
        while not self._stopping:
            try:
                self._listen_sock.settimeout(0.2)
                conn, _addr = self._listen_sock.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 daemon=True, name="pt-serving-conn")
            with self._conns_lock:
                self._conns.append(conn)
                # prune finished threads so a long-lived server doesn't
                # accumulate one Thread object per connection ever seen
                self._conn_threads = [x for x in self._conn_threads
                                      if x.is_alive()]
                self._conn_threads.append(t)
            t.start()

    def _serve_conn(self, conn: socket.socket) -> None:
        rfile = conn.makefile("r", encoding="utf-8")
        wfile = conn.makefile("w", encoding="utf-8")

        def send(obj: Dict) -> None:
            wfile.write(json.dumps(obj) + "\n")
            wfile.flush()

        from ..distributed.fault_inject import InjectedFault, fault_point

        try:
            for line in rfile:
                try:
                    # chaos site: a torn receive. The connection dies
                    # exactly like a real half-open TCP teardown — the
                    # failover router (serving/supervisor.py) resubmits
                    # keyed requests to a live replica; unkeyed clients
                    # see a clean close, never a hang.
                    fault_point("net.recv")
                except InjectedFault:
                    self.metrics.counter("net_recv_drops_total").add()
                    # the peer must see the teardown NOW: shutdown()
                    # sends the FIN even while rfile/wfile still hold
                    # references to the socket (close() alone defers
                    # to their refcounts — a GC-timing hang, not a
                    # torn connection)
                    try:
                        conn.shutdown(socket.SHUT_RDWR)
                    except OSError:
                        pass
                    break
                line = line.strip()
                if not line:
                    continue
                try:
                    msg = json.loads(line)
                except json.JSONDecodeError as e:
                    send({"error": "BadRequest", "reason": str(e)})
                    continue
                try:
                    self._handle(msg, send, conn)
                except ServerOverloaded as e:
                    # submit-gate rejections get their own counter:
                    # engine-side sheds count under requests_total +
                    # shed_total, and mixing the two would let
                    # shed/requests ratios exceed 100%
                    self.metrics.counter("rejected_total").add()
                    send({"error": "ServerOverloaded",
                          "reason": e.reason,
                          "retry_after_ms": e.retry_after_ms})
                except Exception as e:  # typed reply, never a hang
                    send({"error": type(e).__name__, "reason": str(e)})
        except (OSError, ValueError):
            pass  # client went away / socket torn down by stop()
        finally:
            try:
                conn.close()
            except OSError:
                pass
            with self._conns_lock:
                if conn in self._conns:
                    self._conns.remove(conn)

    def _handle(self, msg: Dict, send, conn: socket.socket) -> None:
        from ..distributed.fault_inject import InjectedFault, fault_point

        op = msg.get("op", "generate")
        if op == "health":
            send(self._health())
            return
        if op == "stats":
            eng = self.engine
            send({"stats": self.metrics.snapshot(),
                  "prefix_cache": self._cache_stats(),
                  # step timeline tail (r16) — the full ring rides the
                  # "trace" op; program launch totals by kind
                  "step_timeline": getattr(
                      eng, "step_timeline", lambda: [])()[-16:],
                  "programs_launched": dict(
                      getattr(eng, "programs_launched", {}) or {}),
                  # weight hot-swap (r24)
                  "weight_generation": self._weight_generation,
                  "weight_swaps": getattr(eng, "weight_swaps", 0)})
            return
        if op == "metrics":
            send({"text": self.metrics.prometheus_text()})
            return
        if op == "export":
            # fleet telemetry (r17): the STRUCTURED metrics export the
            # supervisor's collector scrapes — exact counters,
            # bucket-exact histogram counts, SLO window counts. The
            # fleet plane merges these; it never parses exposition
            # text.
            send({"export": self.metrics.export()})
            return
        if op == "slo":
            # runtime SLO retargeting: {"op": "slo", "ttft_ms": 50,
            # "tpot_ms": 10} sets (resetting the window — attainment
            # against old targets is not attainment against new);
            # omitting both fields just reads the current state. The
            # fleet_goodput bench calibrates targets this way without
            # a replica restart.
            if "ttft_ms" in msg or "tpot_ms" in msg:
                for k in ("ttft_ms", "tpot_ms"):
                    v = msg.get(k)
                    if v is not None and (isinstance(v, bool)
                                          or not isinstance(
                                              v, (int, float))
                                          or v <= 0):
                        send({"error": "BadRequest",
                              "reason": f"{k} must be a positive "
                                        f"number of ms or null"})
                        return
                # an ABSENT key preserves the current target (partial
                # retarget must not silently drop the other SLO); an
                # explicit null clears it
                slo = self.metrics.slo
                slo.set_targets(
                    msg["ttft_ms"] if "ttft_ms" in msg
                    else slo.ttft_ms,
                    msg["tpot_ms"] if "tpot_ms" in msg
                    else slo.tpot_ms)
            send({"slo": {"ttft_ms": self.metrics.slo.ttft_ms,
                          "tpot_ms": self.metrics.slo.tpot_ms,
                          "window_s": self.metrics.slo.window_s,
                          "attainment":
                              self.metrics.slo.attainment()}})
            return
        if op == "trace":
            # r16: finished span trees + tracer annotations + the
            # engine step-timeline ring. format=chrome returns a
            # chrome://tracing JSON mergeable with jax.profiler output
            # via tools/merge_traces.py.
            eng = self.engine
            if msg.get("format") == "chrome":
                send({"chrome": self.tracer.to_chrome()})
                return
            n = msg.get("n")
            if msg.get("drain") is True:
                # consume the finished ring (r17): phase-scoped trace
                # collection — the fleet_goodput bench reads each
                # swept rate's traces without earlier phases bleeding
                # into its attainment computation
                traces = self.tracer.drain()
            else:
                traces = self.tracer.finished(
                    n if isinstance(n, int) and not isinstance(
                        n, bool) else None)
            send({"traces": traces,
                  "events": self.tracer.events(),
                  "step_timeline": getattr(
                      eng, "step_timeline", lambda: [])(),
                  "program_costs": getattr(
                      eng, "program_costs", lambda: {})(),
                  "sample_rate": self.tracer.sample_rate})
            return
        if op == "capacity":
            # memory observatory (r18): occupancy + forecast + ledger
            # tail — the capacity/headroom signal the supervisor
            # scrapes per probe cycle and the autoscaler actuator
            # consumes (ROADMAP 3a, memory half)
            send(self._capacity(
                ledger_tail=msg.get("ledger_tail")))
            return
        if op == "profile":
            send(self._profile(msg))
            return
        if op == "drain":
            self.drain()
            send({"ok": True, "status": "draining"})
            return
        if op == "leak_check":
            # answered on the ENGINE thread via the inbox so the audit
            # can't race a step; same outbox plumbing as generate
            pending = _Pending()
            self._inbox.put(({"ctl": "leak_check"}, pending))
            self._wake.set()
            self._await_outbox(pending, send)
            return
        if op == "fetch_pages":
            # disaggregated serving (r20): serve chain-page blobs to a
            # peer replica. Keys/heads are hex chain keys; answered on
            # the ENGINE thread (device reads + tier walks must not
            # race a step).
            keys = self._parse_hex_keys(msg.get("keys"))
            heads = self._parse_hex_keys(msg.get("heads"))
            if keys is None or heads is None or not (keys or heads):
                send({"error": "BadRequest",
                      "reason": "fetch_pages needs 'keys' and/or "
                                "'heads' as lists of hex chain keys"})
                return
            try:
                cursor = max(0, int(msg.get("cursor") or 0))
            except (TypeError, ValueError):
                send({"error": "BadRequest",
                      "reason": "fetch_pages cursor must be an int"})
                return
            pending = _Pending()
            self._inbox.put(({"ctl": "fetch_pages", "keys": keys,
                              "heads": heads, "cursor": cursor},
                             pending))
            self._wake.set()
            self._await_outbox(pending, send)
            return
        if op == "prefetch":
            # disaggregated serving (r20): pull a PEER's chains into
            # this replica's tiers — the drain-handoff receiving side.
            # The network fetch runs on THIS conn thread (decode never
            # waits on the wire); the tier import lands on the engine
            # thread.
            self._prefetch(msg, send)
            return
        if op == "swap":
            # weight hot-swap (r24): load/validate on THIS conn
            # thread, apply on the engine thread between steps.
            # Allowed while draining — the supervisor's roll path
            # drains a replica, then swaps it.
            self._swap(msg, send)
            return
        if op != "generate":
            send({"error": "BadRequest", "reason": f"unknown op {op!r}"})
            return
        if self._draining:
            send({"error": "ServerDraining",
                  "reason": "server is draining; not admitting"})
            return
        try:
            # per-request fault site: a transient front-end failure is
            # a retryable typed reply, not a dropped connection
            fault_point("serving.request")
        except InjectedFault as e:
            send({"error": "TransientServerError", "reason": str(e),
                  "retryable": True})
            return
        prompt = msg.get("prompt")
        if not isinstance(prompt, list) or not prompt:
            send({"error": "BadRequest",
                  "reason": "prompt must be a non-empty token list"})
            return
        prefill_only = bool(msg.get("prefill_only"))
        if self.role == "prefill" and not prefill_only:
            # prefill-class replicas run admission + chunked prefill
            # only; decode streams belong on a decode/mixed replica
            # (the role-aware router never sends them here)
            send({"error": "WrongRole", "retryable": True,
                  "reason": "replica role is 'prefill'; route decode "
                            "streams through a role-aware router or "
                            "send prefill_only requests"})
            return
        if prefill_only and self.prefix_cache is None:
            send({"error": "BadRequest",
                  "reason": "prefill_only needs a prefix cache to "
                            "park the finished chain in"})
            return
        mnt = int(msg.get("max_new_tokens", 16))
        if prefill_only:
            # the prefill IS the work: one generated token (the
            # minimum submit) proves the chain complete; the reply is
            # a prefill-ack carrying the chain keys, not a stream
            mnt = 1
        if mnt < 1 or mnt > self.max_new_tokens_cap:
            send({"error": "BadRequest",
                  "reason": f"max_new_tokens must be in [1, "
                            f"{self.max_new_tokens_cap}]"})
            return
        prio = msg.get("priority", "normal")
        if prio not in _PRIORITIES:
            send({"error": "BadRequest",
                  "reason": f"priority must be one of "
                            f"{sorted(_PRIORITIES)}"})
            return
        deadline_t = None
        if msg.get("deadline_ms") is not None:
            dl = msg["deadline_ms"]
            # bool is an int subclass: "deadline_ms": true must be a
            # BadRequest, not a surprise 1 ms budget
            if isinstance(dl, bool) or \
                    not isinstance(dl, (int, float)) or dl <= 0:
                send({"error": "BadRequest",
                      "reason": "deadline_ms must be a positive "
                                "number of milliseconds"})
                return
            # the budget starts at ARRIVAL: queueing, prefill, decode
            # and any engine resurrection all spend from it
            deadline_t = time.monotonic() + float(dl) / 1e3
        # disaggregated handoff (r20): a fetch_from hint names the
        # peer holding this prompt's chain — pull its blobs on THIS
        # conn thread before enqueueing (the engine never waits on the
        # wire; a failed fetch is a counted fall-back to local prefill)
        handoff = None
        if not prefill_only and msg.get("fetch_from") is not None:
            # advisory overload pre-check BEFORE the wire pull: a
            # request the depth gate will shed must not first spend
            # up to handoff_timeout_s of peer RPC and churn the spill
            # tiers with an import it never uses. The authoritative
            # gate still runs under the admission lock below.
            check = getattr(self.scheduler, "check_admission", None)
            if check is not None:
                check(self.engine.num_queued + self._inbox.qsize())
            handoff = self._handoff_fetch(msg.get("fetch_from"), prompt)
        # a streamed generate's lines go out through the stream writer,
        # straight to this connection's socket
        stream = bool(msg.get("stream", False)) and not prefill_only
        pending = (_Pending(self._stream_writer, conn) if stream
                   else _Pending())
        with self._admission_lock:
            # submit-time overload gate, atomic with the enqueue so
            # concurrent connections can't all slip under the depth
            # bound (raises ServerOverloaded -> typed reply upstream)
            check = getattr(self.scheduler, "check_admission", None)
            if check is not None:
                check(self.engine.num_queued + self._inbox.qsize())
            tctx = msg.get("trace")
            if not (isinstance(tctx, dict) and
                    isinstance(tctx.get("id"), str)):
                tctx = None  # malformed/absent: engine sampler decides
            payload = {"prompt": prompt, "max_new_tokens": mnt,
                       "eos": msg.get("eos"),
                       "priority": int(_PRIORITIES[prio]),
                       "deadline_t": deadline_t,
                       "trace_ctx": tctx}
            if prefill_only:
                payload["handoff"] = True
            if handoff is not None:
                payload["_handoff"] = handoff
            self._inbox.put((payload, pending))
        self._wake.set()
        self._await_outbox(pending, send,
                           transform=(self._prefill_ack(prompt)
                                      if prefill_only else None))

    def _await_outbox(self, pending: _Pending, send,
                      transform=None) -> None:
        """Relay one request's outbox to the client until the None
        sentinel (``transform``, when given, rewrites each message —
        the prefill-ack path); a streaming request's outbox holds the
        sentinel alone, put there by the stream writer after the last
        line. Closes the submit-vs-shutdown race: if the engine thread
        has fully EXITED (mere stop() intent is not enough — graceful
        shutdown still finishes in-flight work and delivers real
        results), the request can never complete, so answer a typed
        ServerEvicted instead of hanging — unless the engine took a
        streaming request, whose end the writer still owes and
        sends."""
        while True:
            try:
                out = pending.outbox.get(timeout=1.0)
            except queue_mod.Empty:
                if self._engine_done.is_set() and not (
                        pending.stream and pending.taken):
                    send({"error": "ServerEvicted",
                          "reason": "server shutting down"})
                    return
                continue
            if out is None:
                return
            send(out if transform is None else transform(out))

    # -- disaggregated serving (r20) ----------------------------------------

    @staticmethod
    def _parse_hex_keys(val) -> Optional[list]:
        """[] for absent, None for malformed, else decoded key bytes."""
        if val is None:
            return []
        if not isinstance(val, list):
            return None
        out = []
        for k in val:
            if not isinstance(k, str):
                return None
            try:
                out.append(bytes.fromhex(k))
            except ValueError:
                return None
        return out

    def _prefill_ack(self, prompt):
        """Reply transform for prefill_only requests: the engine's
        done-reply (tokens included) becomes a prefill-ack naming the
        parked chain — the router hands the KEYS (well, the peer
        address; the decode side re-derives keys from its own prompt)
        to the decode hop. Typed errors pass through untouched."""
        def transform(reply: Dict) -> Dict:
            if not reply.get("done"):
                return reply
            pc = self.prefix_cache
            chain = []
            if pc is not None:
                try:
                    chain = [k.hex() for k in pc.chain_keys_for(
                        np.asarray(prompt, np.int32))]
                except Exception:
                    chain = []
            return {"rid": reply.get("rid"), "done": True,
                    "prefilled": True, "keys": chain,
                    "page_size": self._page_size, "role": self.role,
                    "stats": reply.get("stats")}
        return transform

    def _handoff_fetch(self, ff, prompt) -> Optional[Dict]:
        """Conn-thread wire pull for a generate carrying a
        ``fetch_from`` hint: compute the prompt's chain keys (pure
        hashing), fetch their blobs from the peer, and return the
        bundle the engine thread imports before submit. ANY failure —
        malformed hint, dead peer, typed peer error — is a counted
        fall-back to local prefill (return None), never a hang (the
        socket timeout bounds the wait) and never a client error."""
        pc = self.prefix_cache
        if pc is None or not getattr(pc, "tiers", None) \
                or not isinstance(ff, dict):
            return None
        try:
            host = str(ff.get("host") or self.host)
            port = int(ff["port"])
        except (KeyError, TypeError, ValueError):
            return None
        # cross-generation guard (r24): a hint stamped with a peer
        # generation other than ours is skipped typed-and-counted
        # BEFORE any wire traffic — the peer's pages were computed
        # under different weights and must never splice (the
        # generation-salted chain keys would miss anyway; this makes
        # the skip explicit and free)
        peer_gen = ff.get("generation")
        if peer_gen is not None:
            try:
                peer_gen = int(peer_gen)
            except (TypeError, ValueError):
                return None
            if peer_gen != self._weight_generation:
                self.metrics.counter(
                    "cross_generation_skips_total").add()
                self.tracer.annotate(
                    "handoff_skipped_cross_generation",
                    peer_generation=peer_gen,
                    generation=self._weight_generation)
                return None
        t0 = time.perf_counter()
        try:
            chain = pc.chain_keys_for(np.asarray(prompt, np.int32))
            if not chain:
                return None  # no full shareable block: nothing to pull
            blobs, _missing, nbytes = fetch_page_blobs(
                host, port, keys=chain,
                timeout_s=self.handoff_timeout_s)
        except PageFetchFailed as e:
            self.metrics.counter("handoff_failures_total").add()
            self.tracer.annotate("handoff_fetch_failed",
                                 peer=f"{ff.get('host')}:{ff.get('port')}",
                                 error=str(e)[:200])
            return None
        except Exception as e:
            # chain hashing on a malformed prompt etc: the engine's
            # BadRequest path owns the reply; no handoff
            self.metrics.counter("handoff_failures_total").add()
            self.tracer.annotate("handoff_fetch_failed",
                                 error=f"{type(e).__name__}: {e}"[:200])
            return None
        if not blobs:
            return None  # peer no longer holds the chain: local prefill
        self.metrics.counter("handoff_bytes_total").add(nbytes)
        return {"blobs": blobs, "heads": chain[:1],
                "ms": (time.perf_counter() - t0) * 1e3,
                "bytes": nbytes}

    def _prefetch(self, msg: Dict, send) -> None:
        """The ``prefetch`` op: fetch a peer's chains (by head) into
        this replica's tiers — the drain-handoff receiving side. Fetch
        on this conn thread, import on the engine thread; every
        failure is a typed reply."""
        keys = self._parse_hex_keys(msg.get("keys"))
        heads = self._parse_hex_keys(msg.get("heads"))
        if keys is None or heads is None or not (keys or heads):
            send({"error": "BadRequest",
                  "reason": "prefetch needs 'keys' and/or 'heads' as "
                            "lists of hex chain keys, plus the peer's "
                            "'host'/'port'"})
            return
        pc = self.prefix_cache
        if pc is None or not getattr(pc, "tiers", None):
            send({"error": "PageFetchFailed",
                  "reason": "replica has no spill tier to land "
                            "fetched pages in"})
            return
        try:
            host = str(msg.get("host") or self.host)
            port = int(msg["port"])
        except (KeyError, TypeError, ValueError):
            send({"error": "BadRequest",
                  "reason": "prefetch needs the peer's 'port'"})
            return
        # cross-generation guard (r24): same rule as fetch_from hints
        # — a prefetch stamped with a different weight generation is
        # skipped typed-and-counted, never spliced
        peer_gen = msg.get("generation")
        if peer_gen is not None and not isinstance(peer_gen, bool) \
                and isinstance(peer_gen, int) \
                and peer_gen != self._weight_generation:
            self.metrics.counter("cross_generation_skips_total").add()
            send({"error": "StaleGeneration",
                  "reason": f"prefetch stamped generation {peer_gen} "
                            f"but this replica serves generation "
                            f"{self._weight_generation}; "
                            f"cross-generation pages never splice",
                  "generation": self._weight_generation})
            return
        t0 = time.perf_counter()
        try:
            blobs, missing, nbytes = fetch_page_blobs(
                host, port, keys=keys, heads=heads,
                timeout_s=self.handoff_timeout_s)
        except PageFetchFailed as e:
            self.metrics.counter("handoff_failures_total").add()
            send({"error": "PageFetchFailed", "reason": str(e)})
            return
        self.metrics.counter("handoff_bytes_total").add(nbytes)
        ms = (time.perf_counter() - t0) * 1e3
        pending = _Pending()
        self._inbox.put(({"ctl": "import_blobs", "blobs": blobs,
                          "heads": heads}, pending))
        self._wake.set()

        def add_fetch_info(reply: Dict) -> Dict:
            if reply.get("ok"):
                reply = dict(reply)
                reply["fetch_ms"] = round(ms, 3)
                reply["missing"] = missing
            return reply

        self._await_outbox(pending, send, transform=add_fetch_info)

    # -- introspection -----------------------------------------------------

    def _health(self) -> Dict:
        eng = self.engine
        pc = self.prefix_cache
        mesh_info = getattr(eng, "mesh_info", lambda: None)()

        def racy(fn, fallback=-1):
            # conn-thread reads of dicts the engine thread mutates
            # (allocator reservations, prefix-cache books) can hit
            # "dictionary changed size during iteration" under load. A
            # health probe must degrade to a stale/-1 number — a typed
            # RuntimeError reply here reads as a failed probe to the
            # supervisor, which would kill a healthy replica after
            # max_probe_failures of them.
            for _ in range(3):
                try:
                    return fn()
                except RuntimeError:
                    continue
            return fallback

        adv = (racy(lambda: pc.advertised_keys_info(),
                    {"keys": [], "truncated": False})
               if pc is not None else {"keys": [], "truncated": False})
        return {"status": "draining" if self._draining else "ok",
                # autoscaler adoption (r21): a restarted supervisor
                # verifies a journal-recorded replica is really THIS
                # process (not a recycled pid) by matching this
                "pid": _os.getpid(),
                "active": eng.num_active,
                "queued": eng.num_queued,
                # disaggregated serving (r20): the replica's class —
                # the router's role-aware dispatch input
                "role": self.role,
                # cache-affinity routing (r15): the replica's page size
                # plus the chain-head prefix keys it can serve (device
                # entries AND spill-tier blobs) — the FailoverRouter
                # steers keyed requests whose first-block hash matches.
                # truncated=True tells the router "not advertised" may
                # still be resident (r20 satellite: a capped list must
                # not read as a miss)
                "page_size": eng.page_size,
                # weight hot-swap (r24): the generation this replica
                # serves — the supervisor's roll ready-probe and the
                # router's generation-aware affinity read it here
                "weight_generation": self._weight_generation,
                "weight_swaps": getattr(eng, "weight_swaps", 0),
                "prefix_keys": adv["keys"],
                "prefix_keys_truncated": adv["truncated"],
                "free_pages": eng.free_pages,
                "reserved_pages": racy(
                    lambda: eng.allocator.reserved_total),
                "cached_pages": racy(
                    lambda: pc.total_pages()) if pc is not None else 0,
                "num_pages": eng.num_pages,
                "steps": eng.steps,
                # tensor-parallel serving (r10): None = single-device,
                # else {"axes": {...}, "model_parallel": N, ...} — the
                # supervisor and dashboards see the replica's mesh
                # layout without a separate query
                "mesh": mesh_info,
                "engine_restarts": self._restarts,
                # r11 split the EMAs: step_ema_ms stays as the decode
                # alias for existing probes/dashboards
                "step_ema_ms": (None if eng.decode_ema_s is None
                                else round(eng.decode_ema_s * 1e3, 3)),
                "prefill_chunk_ema_ms": (
                    None if eng.prefill_chunk_ema_s is None
                    else round(eng.prefill_chunk_ema_s * 1e3, 3)),
                # chunked prefill: outstanding prefill tokens (half-
                # prefilled slots + queue) and the configured chunk
                "prefill_debt_tokens": eng.prefill_debt_tokens,
                "prefill_chunk_tokens": eng.prefill_chunk_tokens,
                # fused decode hot path (r13): whether the engine
                # traces fused programs, and the per-program traced-op
                # launch counts ({"decode": N, ...} — populated as
                # each program kind first traces)
                "fused_step": getattr(eng, "fused_step", None),
                "step_programs": dict(
                    getattr(eng, "step_programs", {}) or {}),
                # end-to-end tracing (r16): the sampling rate and how
                # many span trees the finished ring holds
                "trace_sample": self.tracer.sample_rate,
                "traces_finished": self.tracer.finished_total,
                "uptime_s": round(time.monotonic() - self._t0, 3)}

    def _gauges(self) -> Dict[str, float]:
        """Engine-occupancy gauge source for the Prometheus page
        (serving/metrics.py): live reads of host-side ints — benign
        against the engine thread, same as the health op."""
        eng = self.engine
        pc = self.prefix_cache
        g = {"inflight_slots": eng.num_active,
             # weight hot-swap (r24): the serving generation as a
             # gauge (serving_weight_generation on the scrape page;
             # the supervisor rolls it up per fleet)
             "weight_generation": float(self._weight_generation),
             # num_slots rides along so the fleet plane can compute
             # occupancy (inflight/slots) for the pressure verdict
             "num_slots": eng.num_slots,
             "queued_requests": eng.num_queued,
             "free_pages": eng.free_pages,
             "reserved_pages": eng.allocator.reserved_total,
             "prefix_cache_pages":
                 pc.total_pages() if pc is not None else 0,
             "num_pages": eng.num_pages,
             # chunked prefill (r11): un-stored prompt tokens across
             # half-prefilled slots + the queue — the head-of-line
             # pressure a dashboard watches against TPOT
             "prefill_debt_tokens": eng.prefill_debt_tokens}
        # memory observatory (r18): pool occupancy by owner class —
        # the same breakdown the capacity op and the step-timeline
        # ring carry, scraped into the fleet plane where the pressure
        # verdict's memory input reads it (pages_used/num_pages)
        occ = getattr(eng.allocator, "occupancy", lambda: None)()
        if occ:
            g["pages_inflight"] = occ["inflight"]
            g["pages_prefix_device"] = occ["prefix_device"]
            g["pages_used"] = eng.num_pages - occ["free"]
            # the PRESSURE input: used minus reclaimable-on-demand
            # (refcount-0 cache pages) — a warm inclusive cache fills
            # the pool by design and must not read as exhaustion
            evictable = 0
            if pc is not None:
                try:
                    evictable = int(pc.evictable_pages())
                except RuntimeError:
                    pass  # racy read: skip this scrape's refinement
            g["pages_unreclaimable"] = max(
                0, eng.num_pages - occ["free"] - evictable)
        led = getattr(eng, "ledger", None)
        if led is not None:
            g["ledger_events"] = led.seq
            g["ledger_dropped"] = led.dropped_total
        # hierarchical prefix cache (r15): per-tier occupancy so a
        # dashboard sees how much evicted KV is restorable (bytes and
        # blob counts per spill tier)
        if pc is not None and getattr(pc, "tiers", None):
            for t in pc.tiers:
                g[f"spill_{t.name}_bytes"] = t.occupancy_bytes
                # r23: raw-equivalent bytes of the stored blobs — with
                # a coded blob_format the physical figure undersells
                # restorable KV, so capacity/hit-rate math reads this
                g[f"spill_{t.name}_logical_bytes"] = t.logical_bytes
                g[f"spill_{t.name}_blobs"] = t.blob_count
                g[f"spill_{t.name}_capacity_bytes"] = t.capacity_bytes
        # fused decode (r13): ops traced into the decode-step program
        # (the launch counter) — exported as serving_step_programs so
        # the fused launch-count win is visible on a live server; 0
        # until the decode step first traces
        sp = getattr(eng, "step_programs", None)
        if sp is not None:
            g["step_programs"] = sp.get("decode", 0)
        # step timeline (r16): per-kind program LAUNCH totals, the
        # engine step count, and the latest step's wall ms; new
        # entries since the last scrape feed the serving_step_ms
        # histogram (ServingMetrics.step_ms)
        for kind, n in dict(getattr(eng, "programs_launched", {})
                            or {}).items():
            g[f"programs_launched_{kind}"] = n
        g["engine_steps"] = getattr(eng, "steps", 0)
        tl = getattr(eng, "step_timeline", lambda: [])()
        if tl:
            g["step_last_ms"] = tl[-1].get("ms", 0.0)
            self._feed_step_histogram(tl)
        # program-cost gauges (r16 satellite): flops / bytes-accessed
        # per program kind from jit cost_analysis at build time
        for kind, cost in getattr(eng, "program_costs",
                                  lambda: {})().items():
            if "flops" in cost:
                g[f"program_{kind}_flops"] = cost["flops"]
                g[f"program_{kind}_bytes_accessed"] = \
                    cost["bytes_accessed"]
        # tracing counters (r16): tracer lifetime totals synced into
        # the registry at scrape (monotonic, so the counter contract
        # holds)
        for cname, val in (
                ("traces_sampled_total", self.tracer.sampled_total),
                ("traces_finished_total", self.tracer.finished_total),
                ("trace_spans_dropped_total",
                 self.tracer.spans_dropped_total)):
            self.metrics.counter(cname).set(val)
        mi = getattr(eng, "mesh_info", lambda: None)()
        if mi is not None:
            # tensor-parallel serving (r10/r16): mesh layout on the
            # scrape page. mesh_collective_bytes was a STUB pinned 0
            # through r15; it now carries the engine's per-decode-step
            # ESTIMATE (ring-allreduce traffic of the row-parallel
            # reductions — see mesh_collective_bytes_estimate, with
            # the per-program flops/bytes from cost_analysis exported
            # above). The chip-MEASURED value still needs an on-chip
            # profiler session (xprof collective stats) — chip-pending,
            # same convention as bench_all's cpu_smoke markers.
            g["mesh_model_parallel"] = mi["model_parallel"]
            g["mesh_devices"] = mi["devices"]
            est = getattr(eng, "mesh_collective_bytes_estimate",
                          lambda: None)()
            g["mesh_collective_bytes"] = est if est is not None else 0.0
        return g

    def _feed_step_histogram(self, tl) -> None:
        """Observe ring entries newer than the last scrape into the
        serving_step_ms histogram. The marker keys on the RESTART
        COUNT (monotonic) — id(eng) could be reused by a later engine
        allocated at a freed one's address, silently inheriting a
        stale high-water step."""
        key, seen = self._tl_seen
        if key != self._restarts:
            key, seen = self._restarts, -1
        for entry in tl:
            s = entry.get("step", 0)
            if s > seen:
                self.metrics.step_ms.observe(entry.get("ms", 0.0))
                seen = s
        self._tl_seen = (key, seen)

    # max chain pages served per fetch_pages reply: bounds one reply's
    # size (a page blob is small — page*H*D*2*itemsize per layer — but
    # an unbounded key list would let one peer RPC occupy the engine
    # thread arbitrarily long between steps)
    FETCH_PAGES_CAP = 512

    def _serve_fetch_pages(self, payload: Dict) -> Dict:
        """Engine-thread half of the ``fetch_pages`` wire op (r20):
        expand requested chain heads, pack device-resident pages /
        read tier blobs, and base64 them for the reply. A key this
        replica cannot produce is listed in ``missing`` — the peer's
        chained-prefill fallback covers it, so this op never errors
        on absence.

        Cursor pagination (r23): each reply serves at most
        FETCH_PAGES_CAP keys starting at ``payload["cursor"]`` (an
        offset into the deterministic expanded key list) and carries
        ``next_cursor`` while more remain — so a chain longer than
        one page's cap hands off WHOLE across several bounded RPCs
        instead of silently degrading its tail to missing. The
        legacy ``truncated`` flag stays for pre-r23 clients."""
        import base64
        pc = self.prefix_cache
        if pc is None:
            return {"error": "PageFetchFailed",
                    "reason": "replica has no prefix cache"}
        keys = list(payload.get("keys") or ())
        heads = list(payload.get("heads") or ())
        if heads:
            seen = set(keys)
            keys += [k for k in pc.expand_heads(heads)
                     if k not in seen]
        cursor = max(0, int(payload.get("cursor") or 0))
        window = keys[cursor:cursor + self.FETCH_PAGES_CAP]
        remaining = len(keys) - (cursor + len(window))
        truncated = len(keys) > self.FETCH_PAGES_CAP
        blobs, missing = pc.export_blobs(window)
        reply = {"blobs": {k.hex(): base64.b64encode(b).decode("ascii")
                           for k, b in blobs.items()},
                 "missing": [k.hex() for k in missing],
                 "count": len(blobs),
                 "bytes": sum(len(b) for b in blobs.values()),
                 "truncated": truncated,
                 "role": self.role,
                 # r24: the generation these blobs were computed under
                 # (cross-generation requests miss by key construction;
                 # this makes the provenance explicit on the wire)
                 "generation": self._weight_generation}
        if remaining > 0:
            reply["next_cursor"] = cursor + len(window)
        return reply

    def _import_blobs(self, payload: Dict) -> Dict:
        """Engine-thread half of the ``prefetch`` op (r20 drain
        handoff): land already-fetched blobs in the cache tiers (crc
        re-verified per blob by ``import_blobs``)."""
        pc = self.prefix_cache
        if pc is None or not getattr(pc, "tiers", None):
            return {"error": "PageFetchFailed",
                    "reason": "replica has no spill tier to land "
                              "fetched pages in"}
        rep = pc.import_blobs(payload.get("blobs") or {},
                              heads=payload.get("heads") or ())
        rep["ok"] = True
        return rep

    def _leak_check(self) -> Dict:
        """Engine-thread page audit: with no in-flight work, the
        allocator must balance (cache-less: everything free; cached:
        free + cache-owned == pool, no other owners). The reply also
        carries the page-ledger RECONCILIATION (r18): the event-derived
        ownership shadow must match the allocator's books exactly —
        the chaos harness's invariant 5."""
        eng = self.engine
        led = getattr(eng, "ledger", None)
        ledger_info = ({"ok": True, "enabled": False} if led is None
                       else led.reconcile(eng.allocator))
        if eng.num_active or eng.num_queued:
            return {"ok": False, "busy": True,
                    "active": eng.num_active, "queued": eng.num_queued}
        try:
            if self.prefix_cache is not None:
                self.prefix_cache.check_consistent(eng.allocator)
            else:
                eng.allocator.check_no_leak()
        except Exception as e:
            return {"ok": False, "busy": False,
                    "error": type(e).__name__, "reason": str(e),
                    "ledger": ledger_info}
        return {"ok": True, "busy": False,
                "free_pages": eng.free_pages,
                "reserved_pages": eng.allocator.reserved_total,
                "cached_pages": (self.prefix_cache.total_pages()
                                 if self.prefix_cache is not None else 0),
                "num_pages": eng.num_pages,
                "ledger": ledger_info}

    def _capacity(self, ledger_tail=None) -> Dict:
        """The ``capacity`` op payload: the engine's occupancy card,
        an EWMA exhaustion forecast over step-timeline ring deltas,
        and (on request) the ledger ring tail. Conn-thread reads of
        host ints/dicts — the same benign-race contract as health."""
        from ..inference.page_ledger import forecast_exhaustion
        eng = self.engine
        snap = getattr(eng, "capacity_snapshot", lambda: {})()
        snap["forecast"] = forecast_exhaustion(
            getattr(eng, "step_timeline", lambda: [])())
        n = ledger_tail
        if isinstance(n, int) and not isinstance(n, bool) and n > 0:
            snap["ledger_tail"] = getattr(
                eng, "ledger_tail", lambda _n: [])(n)
        snap["engine_restarts"] = self._restarts
        return snap

    def _profile(self, msg: Dict) -> Dict:
        """The ``profile`` op (r18): live per-device HBM accounting
        plus an optional ``jax.profiler`` device capture window. The
        capture runs on THIS connection thread while the engine thread
        keeps stepping, so the dump holds real serving programs (the
        jit bodies' pt.* named_scopes); ``{"ms": N, "dir": PATH}``
        captures N ms into PATH (tensorboard layout — the
        *.trace.json.gz inside merges with span dumps via
        tools/merge_traces.py). One capture at a time: a concurrent
        request gets a typed ProfileBusy, never a corrupted trace."""
        import jax
        out: Dict[str, Any] = {"devices": [], "chip_pending": True}
        for d in jax.devices():
            stats = None
            fn = getattr(d, "memory_stats", None)
            if callable(fn):
                try:
                    raw = fn()
                    if raw:
                        stats = {str(k): int(v)
                                 for k, v in raw.items()
                                 if isinstance(v, (int, float))}
                except Exception:
                    stats = None
            if stats:
                # a backend that accounts HBM makes the gauges real;
                # CPU reports none — the numbers stay chip-pending
                out["chip_pending"] = False
            out["devices"].append({"id": int(d.id),
                                   "platform": str(d.platform),
                                   "memory_stats": stats})
        ms = msg.get("ms")
        if ms is not None:
            if isinstance(ms, bool) or not isinstance(ms, (int, float)) \
                    or ms <= 0 or ms > 30_000:
                return {"error": "BadRequest",
                        "reason": "ms must be a capture window in "
                                  "(0, 30000] milliseconds"}
            if not self._profile_lock.acquire(blocking=False):
                return {"error": "ProfileBusy",
                        "reason": "a profiler capture is already "
                                  "running; retry after it finishes"}
            try:
                import tempfile
                trace_dir = msg.get("dir") or tempfile.mkdtemp(
                    prefix="pt-profile-")
                jax.profiler.start_trace(trace_dir)
                try:
                    time.sleep(float(ms) / 1e3)
                finally:
                    jax.profiler.stop_trace()
                out["trace_dir"] = trace_dir
                out["ms"] = float(ms)
            except Exception as e:
                return {"error": "ProfileFailed",
                        "reason": f"{type(e).__name__}: {e}"}
            finally:
                self._profile_lock.release()
        return out

    def _cache_stats(self) -> Optional[Dict]:
        pc = self.prefix_cache
        if pc is None:
            return None
        return {"pages": pc.total_pages(), "hit_pages": pc.hit_pages,
                "miss_pages": pc.miss_pages,
                "inserted_pages": pc.inserted_pages,
                "evicted_pages": pc.evicted_pages,
                "hit_rate": pc.hit_rate(),
                # hierarchical tiers (r15): per-tier hit/occupancy
                # breakdown plus spill/restore lifetime counters
                "tiers": pc.tier_stats(),
                "spilled_pages": pc.spilled_pages,
                "restored_pages": pc.restored_pages,
                "restore_corrupt": pc.restore_corrupt,
                "spill_failed": pc.spill_failed,
                # disaggregated handoff (r20): blobs served to /
                # accepted from peer replicas over fetch_pages
                "exported_pages": getattr(pc, "exported_pages", 0),
                "imported_pages": getattr(pc, "imported_pages", 0),
                "import_corrupt": getattr(pc, "import_corrupt", 0),
                # KV byte substrate (r23): transport codec + dedup
                # accounting. codec_stats is non-empty only on a lossy
                # blob_format — max_abs_err is the REPORTED accuracy
                # delta, never silent
                "blob_format": getattr(pc, "blob_format", "raw"),
                "dedup": getattr(pc, "dedup", False),
                "dedup_hits": getattr(pc, "dedup_hits", 0),
                "codec_stats": dict(getattr(pc, "codec_stats", {}))}


def _json_stats(stats) -> Dict:
    out = stats.to_dict()
    return {k: (round(v, 6) if isinstance(v, float) else v)
            for k, v in out.items() if v is not None}


def client_request(host: str, port: int, payload: Dict,
                   timeout_s: float = 120.0, on_token=None) -> Dict:
    """Minimal blocking client: send one request, collect streamed
    tokens through ``on_token(token)``, return the final reply."""
    with socket.create_connection((host, port), timeout=timeout_s) as s:
        rfile = s.makefile("r", encoding="utf-8")
        wfile = s.makefile("w", encoding="utf-8")
        wfile.write(json.dumps(payload) + "\n")
        wfile.flush()
        for line in rfile:
            msg = json.loads(line)
            if "token" in msg:  # streamed chunk (its "done" flag marks
                if on_token is not None:  # the LAST token, not the
                    on_token(msg["token"])  # final summary message)
                continue
            return msg  # final reply: summary, admin reply, or error
    raise ConnectionError("server closed the connection mid-request")


def _build_model(name: str):
    import paddle_tpu as pt
    from ..models.falcon_h1 import (FalconH1ForCausalLM, falcon_h1_34b,
                                    falcon_h1_tiny)
    from ..models.glm4_moe_lite import (Glm4MoeLiteForCausalLM,
                                        glm4_7_flash, glm4_moe_lite_tiny)
    from ..models.glm5_next import (Glm5NextForCausalLM, glm5_3_flash,
                                    glm5_next_tiny)
    from ..models.gpt import (GPTForCausalLM, gpt_125m, gpt_1p3b,
                              gpt_350m, gpt_tiny)
    from ..models.smallthinker import (SmallThinkerForCausalLM,
                                       smallthinker_21b_a3b,
                                       smallthinker_tiny)
    from ..models.solar_open2 import (SolarOpen2ForCausalLM,
                                      solar_open2_250b, solar_open2_tiny)
    configs = {"gpt_tiny": (GPTForCausalLM, gpt_tiny),
               "gpt_125m": (GPTForCausalLM, gpt_125m),
               "gpt_350m": (GPTForCausalLM, gpt_350m),
               "gpt_1p3b": (GPTForCausalLM, gpt_1p3b),
               "smallthinker_tiny": (SmallThinkerForCausalLM,
                                     smallthinker_tiny),
               "smallthinker_21b_a3b": (SmallThinkerForCausalLM,
                                        smallthinker_21b_a3b),
               "solar_open2_tiny": (SolarOpen2ForCausalLM,
                                    solar_open2_tiny),
               # one period of the published widths, one chip's share
               # of eight: 40 of 320 experts, an eighth of the head
               "solar_open2_250b_cut": (
                   SolarOpen2ForCausalLM, lambda: solar_open2_250b(
                       4, experts_held=(0, 40), vocab_size=24576,
                       dtype="bfloat16")),
               "glm4_moe_lite_tiny": (Glm4MoeLiteForCausalLM,
                                      glm4_moe_lite_tiny),
               # the first of eight pipeline stages at the published
               # widths: the dense layer and five expert layers
               "glm4_7_flash_cut": (
                   Glm4MoeLiteForCausalLM,
                   lambda: glm4_7_flash(6, dtype="bfloat16")),
               "falcon_h1_tiny": (FalconH1ForCausalLM, falcon_h1_tiny),
               # the first of twelve pipeline stages at the published
               # widths, an eighth of the vocabulary
               "falcon_h1_34b_cut": (
                   FalconH1ForCausalLM, lambda: falcon_h1_34b(
                       6, vocab_size=32640, prefill_segment=2048,
                       dtype="bfloat16")),
               "glm5_next_tiny": (Glm5NextForCausalLM, glm5_next_tiny),
               # the first pipeline stage at the published widths
               # (layers 0 and 3-6; 1 and 2 are of layer 0's kind), one
               # chip's share of eight: 36 of 288 experts, an eighth of
               # the head
               "glm5_3_flash_cut": (
                   Glm5NextForCausalLM, lambda: glm5_3_flash(
                       experts_held=(0, 36), vocab_size=19360,
                       dtype="bfloat16"))}
    if name not in configs:
        raise SystemExit(f"unknown --model {name!r}; choose from "
                         f"{sorted(configs)}")
    pt.seed(0)
    cls, preset = configs[name]
    model = cls(preset())
    model.eval()
    return model


def main(argv=None) -> None:
    import argparse
    parser = argparse.ArgumentParser(
        description="paddle_tpu serving front-end (newline-JSON)")
    parser.add_argument("--model", default="gpt_125m")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=8765)
    parser.add_argument("--num-slots", type=int, default=4)
    parser.add_argument("--page-size", type=int, default=64)
    parser.add_argument("--num-pages", type=int, default=None)
    parser.add_argument("--max-seq-len", type=int, default=None)
    parser.add_argument("--no-prefix-cache", action="store_true")
    parser.add_argument(
        "--role", default="mixed", choices=list(_ROLES),
        help="disaggregated serving (r20): 'mixed' (default) is the "
             "full replica, byte-for-byte the pre-r20 behavior. "
             "'prefill' runs admission + (chunked) prefill only — it "
             "answers prefill_only requests, parks finished KV chains "
             "in its cache/spill tiers, advertises them via health "
             "prefix_keys, and serves them to peers over the "
             "fetch_pages op (plain generates get a typed WrongRole). "
             "'decode' serves token streams and, when the router "
             "supplies a fetch_from hint, pulls the prompt's chain "
             "from the prefill peer and splices it in instead of "
             "re-prefilling (greedy outputs bit-identical either "
             "way). Non-mixed roles default a 64 MB host spill tier "
             "when none is configured")
    parser.add_argument(
        "--handoff-timeout-s", type=float, default=30.0, metavar="S",
        help="socket timeout of cross-replica fetch_pages pulls; on "
             "expiry the request falls back to local prefill typed "
             "(PageFetchFailed is counted, never a hang)")
    parser.add_argument(
        "--spill-mb", type=int, default=None, metavar="MB",
        help="hierarchical prefix cache (r15): add a host-RAM spill "
             "tier of this many MB — refcount-0 prefix pages evicted "
             "from the device pool are kept as content-hashed blobs "
             "and restored on a later hit via one device_put + "
             "page-table splice instead of a re-prefill (greedy "
             "outputs stay bit-identical; default: evictions are "
             "dropped)")
    parser.add_argument(
        "--spill-dir", default=None, metavar="DIR",
        help="add a disk spill tier under DIR behind the host tier "
             "(host-tier LRU evictions demote here; blobs are "
             "crc32-checked on restore and scrubbed on shutdown)")
    parser.add_argument(
        "--spill-disk-mb", type=int, default=1024, metavar="MB",
        help="byte budget of the --spill-dir disk tier (default 1024)")
    parser.add_argument(
        "--max-engine-errors", type=int, default=32,
        help="consecutive engine-step failures before the engine is "
             "resurrected (torn down, rebuilt, in-flight replayed)")
    parser.add_argument(
        "--max-engine-restarts", type=int, default=2,
        help="engine resurrections before the server gives up and "
             "fails everything with a typed EngineFailed")
    parser.add_argument(
        "--stall-timeout-s", type=float, default=None,
        help="evict a slot that emits no token for this long with a "
             "typed RequestStalled reply (default: watchdog off)")
    parser.add_argument(
        "--prefill-chunk", type=int, default=None, metavar="TOKENS",
        help="chunked prefill: admit long prompts without stalling "
             "in-flight streams by prefilling at most this many "
             "page-aligned tokens per decode step (must be a multiple "
             "of --page-size; default: whole-prompt prefill). Greedy "
             "outputs stay bit-identical; smaller chunks protect "
             "interactive TPOT, larger chunks finish batch prefills "
             "sooner")
    parser.add_argument(
        "--no-fused-step", action="store_true",
        help="disable the fused decode hot path (r13: attention + "
             "out-projection folded into one kernel, sampling streamed "
             "through the lm_head so [B, vocab] logits never hit HBM). "
             "The fused path is the default; greedy outputs are "
             "bit-identical either way on the CPU reference lane "
             "(on-chip Mosaic-kernel parity is chip-pending "
             "validation), and this escape hatch restores the "
             "byte-for-byte pre-r13 programs")
    parser.add_argument(
        "--speculate", type=int, default=0, metavar="K",
        help="draft K tokens per decode step and verify them in one "
             "forward (0 = off); greedy outputs stay bit-identical")
    parser.add_argument(
        "--draft-model", default="ngram",
        help="draft source for --speculate: 'ngram' (prompt lookup, "
             "no second model) or a model name (e.g. gpt_tiny)")
    parser.add_argument(
        "--draft-window", type=int, default=64,
        help="context window of a --draft-model draft")
    parser.add_argument(
        "--mesh", default=None, metavar="model=N",
        help="tensor-parallel serving mesh: shard weights and KV "
             "pages over N devices along the model axis "
             "(distributed/topology.py make_serving_mesh). Greedy "
             "outputs stay bit-identical to the single-device engine; "
             "omit for the single-device default")
    parser.add_argument(
        "--trace-sample", type=float, default=0.0, metavar="R",
        help="end-to-end request tracing (r16): sample this fraction "
             "of requests into span trees (queue -> admit -> prefill "
             "chunks -> decode steps -> complete, stitched across "
             "resurrection/failover). 0 = off (the default; tracing "
             "off costs ~zero on the hot path), 1.0 = every request. "
             "Dump via the 'trace' op; greedy outputs are "
             "bit-identical tracing on/off")
    parser.add_argument(
        "--slo-ttft-ms", type=float, default=None, metavar="MS",
        help="fleet telemetry (r17): TTFT target for the live "
             "SLO-attainment monitor — the rolling-window fraction of "
             "finished requests meeting it surfaces per class as "
             "serving_slo_attainment gauges and in the supervisor's "
             "fleet_stats (retargetable at runtime via the 'slo' op)")
    parser.add_argument(
        "--slo-tpot-ms", type=float, default=None, metavar="MS",
        help="TPOT target for the live SLO monitor (see --slo-ttft-ms)")
    parser.add_argument(
        "--slo-window-s", type=float, default=120.0, metavar="S",
        help="rolling window of the live SLO monitor (default 120)")
    parser.add_argument(
        "--flight-dir", default=None, metavar="DIR",
        help="crash flight recorder (r17): write black-box bundles "
             "(step-timeline ring, sampled traces, metrics export, "
             "inflight dump, engine recipe) to DIR on engine "
             "resurrection, terminal EngineFailed, or a stalled "
             "request — atomic tmp+rename writes, byte-budgeted "
             "retention; inspect with tools/flight_inspect.py")
    parser.add_argument(
        "--flight-budget-mb", type=int, default=64, metavar="MB",
        help="retention byte budget of --flight-dir (oldest bundles "
             "pruned first, the newest always kept; default 64)")
    parser.add_argument(
        "--no-page-ledger", action="store_true",
        help="disable the page ledger (r18: every page event appended "
             "to a bounded ring with owner/step/reason — leak "
             "forensics, ledger reconciliation, capacity-op event "
             "tail). On by default at ~1.0x ms/step; greedy outputs "
             "are bit-identical either way")
    parser.add_argument(
        "--blob-format", default="raw", choices=["raw", "int8", "int4"],
        help="KV byte substrate (r23): transport codec for spill/"
             "handoff/prefetch page blobs. 'raw' (default) is the r22 "
             "byte layout. 'int8' moves ~2x fewer bytes — LOSSLESS "
             "(bit-identical greedy) when the engine already runs "
             "int8 KV pages, the pinned quantize_kv round trip when "
             "it runs float pages. 'int4' moves ~4x fewer bytes and "
             "is always lossy (pinned nibble decode). Lossy formats "
             "report their max_abs_err in cache_stats codec_stats — "
             "the accuracy delta is never silent")
    parser.add_argument(
        "--no-dedup", action="store_true",
        help="disable cross-request page dedup (r23: content-identical "
             "FULL pages from unrelated requests fold onto one "
             "physical page, proven by the chained blake2b keys; "
             "greedy outputs are bit-identical on/off). "
             "--blob-format raw plus --no-dedup restores the r22 "
             "byte layout exactly")
    parser.add_argument(
        "--checkpoint", default=None, metavar="DIR",
        help="weight hot-swap (r24): boot from the newest valid "
             "checkpoint under DIR (ResilientCheckpointManager "
             "manifest layout, crc-validated) instead of the seeded "
             "init — how replicas (re)spawned mid-roll join the fleet "
             "on the rolled weights. Swap a LIVE replica via the "
             "'swap' op; a corrupt/missing checkpoint fails startup "
             "typed")
    parser.add_argument(
        "--weight-generation", type=int, default=0, metavar="N",
        help="weight generation this replica serves (salts the KV "
             "chain keys so pages from other generations miss by "
             "construction; the supervisor threads it through "
             "respawns so a re-role never reverts a rolled replica)")
    parser.add_argument(
        "--forecast-admission", action="store_true",
        help="byte-planning admission (r23): _fits also charges the "
             "fleet's forecast page burn (r18 EWMA exhaustion "
             "forecast) over the request's expected lifetime, so a "
             "request lands only when the pool's FUTURE accommodates "
             "it (default: instant-occupancy gate only)")
    args = parser.parse_args(argv)

    model = _build_model(args.model)
    speculative = None
    if args.speculate > 0:
        from ..inference import SpeculativeConfig
        draft = args.draft_model
        if draft != "ngram":
            draft = _build_model(draft)
        speculative = SpeculativeConfig(k=args.speculate, draft=draft,
                                        draft_window=args.draft_window)
    engine_kwargs = {}
    if args.num_pages is not None:
        engine_kwargs["num_pages"] = args.num_pages
    if args.max_seq_len is not None:
        engine_kwargs["max_seq_len"] = args.max_seq_len
    if args.prefill_chunk is not None:
        # rides in engine_kwargs, so the resurrection recipe rebuilds
        # a chunked engine too
        engine_kwargs["prefill_chunk_tokens"] = args.prefill_chunk
    if args.no_fused_step:
        # rides in engine_kwargs, so a resurrected engine honors the
        # escape hatch too (fused is the engine default)
        engine_kwargs["fused_step"] = False
    if args.no_page_ledger:
        engine_kwargs["page_ledger"] = False
    if args.forecast_admission:
        # rides in engine_kwargs, so a resurrected engine keeps the
        # byte-planning admission gate
        engine_kwargs["forecast_admission"] = True
    mesh_desc = "single-device"
    if args.mesh is not None:
        from ..distributed.topology import (make_serving_mesh,
                                            parse_mesh_spec)
        try:
            mp = parse_mesh_spec(args.mesh)
            # mesh= rides in engine_kwargs, so the resurrection recipe
            # (ServingServer._build_engine) rebuilds onto the SAME mesh
            engine_kwargs["mesh"] = make_serving_mesh(mp)
        except ValueError as e:
            raise SystemExit(f"--mesh: {e}")
        mesh_desc = f"mesh model={mp}"
    server = ServingServer(model, host=args.host, port=args.port,
                           prefix_cache=not args.no_prefix_cache,
                           role=args.role,
                           handoff_timeout_s=args.handoff_timeout_s,
                           blob_format=args.blob_format,
                           dedup=not args.no_dedup,
                           checkpoint=args.checkpoint,
                           weight_generation=args.weight_generation,
                           num_slots=args.num_slots,
                           page_size=args.page_size,
                           max_engine_errors=args.max_engine_errors,
                           max_engine_restarts=args.max_engine_restarts,
                           stall_timeout_s=args.stall_timeout_s,
                           spill_bytes=(None if args.spill_mb is None
                                        else args.spill_mb << 20),
                           spill_dir=args.spill_dir,
                           spill_disk_bytes=(
                               None if args.spill_dir is None
                               else args.spill_disk_mb << 20),
                           trace_sample=args.trace_sample,
                           slo_ttft_ms=args.slo_ttft_ms,
                           slo_tpot_ms=args.slo_tpot_ms,
                           slo_window_s=args.slo_window_s,
                           flight_dir=args.flight_dir,
                           flight_budget_bytes=(
                               args.flight_budget_mb << 20),
                           speculative=speculative, **engine_kwargs)
    port = server.start()
    print(f"[paddle_tpu.serving] listening on {args.host}:{port} "
          f"(model {args.model}, {mesh_desc}); newline-JSON, see "
          f"module docstring", flush=True)
    try:
        while True:
            time.sleep(3600)
    except KeyboardInterrupt:
        print("[paddle_tpu.serving] draining ...", flush=True)
        server.stop()


if __name__ == "__main__":
    main()
