"""Continuous-batching decode engine over the paged KV cache.

The serving-side half of the paged decode subsystem (the kernel half is
`ops/pallas/paged_attention.py`, the model half `models/gpt.py`
PagedKVCache): a fixed-slot decode batch that admits and evicts
sequences MID-FLIGHT, recycling completed sequences' KV pages to newly
admitted ones. This is what the paging buys beyond ragged bandwidth —
the dense StaticKVCache path must run every co-batched request for the
longest request's duration (or re-prefill), while here a finished slot
is refilled on the next step without touching the other slots' compiled
program.

Design (TPU-native fixed shapes; paper basis: *Ragged Paged Attention*,
PAPERS.md — the same pool/page-table layout its kernel consumes):

- DEVICE state is fully static-shaped: per-layer page pools and the
  decode step's inputs, ONE packed int32 array ``[num_slots,
  max_pages + 2]`` (page table | ``seq_lens`` | current token). The
  decode program returns the next step's packed inputs and the engine
  feeds them back: a decode step that follows no host write uploads
  nothing and fetches the sampled tokens alone. ONE compiled decode
  step serves the engine's whole lifetime; prefill compiles once per
  prompt bucket.
- HOST state is the scheduler: a free-list `PageAllocator`, the wait
  queue, per-slot request bookkeeping, and the mirrors of the packed
  inputs (``_table``, ``_lens``, ``_cur``: views of one array). The
  mirrors are the truth: every host write goes through
  ``_write_slot``, which marks the device's copy stale, and the next
  decode step sends the mirrors in one transfer (``decode_h2d`` in
  the step timeline says which kind a step was). Admission allocates
  ceil(capacity/page) pages and runs a bucket-padded prefill whose
  right padding is redirected to the pool's reserved scratch page
  (models/gpt.py paged_kv_append valid_len), so padded prompts never
  touch real pages; eviction returns the pages to the free list and
  parks the slot on the scratch page at length 0 (an empty slot
  attends nothing and produces defined zeros — see
  paged_attention_reference), so a freed page can be handed to the
  next request without any cross-slot read hazard.
- Inactive slots still ride through the fixed-shape decode step (their
  writes land on the scratch page and the program returns their
  length as it came in, 0); that is the fixed-slot contract that
  keeps the hot loop at one compiled program.

Serving hooks (the `paddle_tpu/serving/` subsystem rides on these;
each defaults OFF so the bare engine behaves exactly as before):

- ``scheduler``: admission-order policy object (duck-typed
  ``select(queue, fits, now)`` / ``shed(queue, now)``) replacing the
  built-in blocking FIFO — serving/scheduler.py's SLO-aware policy.
- ``prefix_cache``: refcounted full-page sharing across requests
  (serving/prefix_cache.py). Admission reuses cached prefix pages and
  prefills only the suffix (models/gpt.py ``prefill_chained``);
  completed prompts' full pages transfer ownership into the cache.
- ``prefill_retry``: a resilience.RetryPolicy retrying transient
  prefill failures at the ``serving.prefill`` fault site.
- per-request ``RequestStats`` (admit/prefill/first-token/finish
  timestamps) surfaced through ``on_token`` / ``on_complete``
  callbacks — the records serving/metrics.py aggregates.

Request lifecycle: queued → prefill → decoding → done, with the
off-ramps evicted (close()), shed (scheduler overload) and failed
(prefill attempts exhausted). Chunked-prefill engines
(``prefill_chunk_tokens``) replace the prefill stage with
prefill_partial: admission binds pages without prefilling, and each
step() advances at most one half-prefilled slot by one page-aligned
chunk through the chained-prefill jit BEFORE the decode step — so
in-flight decode streams keep ticking while a long prompt trickles in
(the TTFT-vs-TPOT head-of-line fix; greedy outputs stay bit-identical
to whole prefill).

Reference analog: the inference engine's multi-stream serving loop
(`inference/api/analysis_predictor.cc` + TensorRT's enqueue batching),
rebuilt as a scheduler over one jitted step instead of a stream pool.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import re
import threading
import time
from typing import (Any, Callable, Dict, Hashable, List, Optional,
                    Sequence, Tuple)

import numpy as np

from ..core.profiler import HostPhases

__all__ = ["PageAllocator", "DecodeRequest", "RequestStats",
           "ContinuousBatchingEngine", "create_decode_engine",
           "SwapFailed", "HOST_PHASES"]

# The host phases inside an engine step, in the order the single-step
# path runs them (ContinuousBatchingEngine._tl_commit documents each).
# With `commit` (the timeline's own cost) and `loop` (the caller
# between two steps, `gap_us`) they partition the stepping thread's
# time from one step's start to the next.
HOST_PHASES = ("admit", "upload", "launch", "wait", "emit")


def _raw(t):
    """The array under a Tensor (or the array itself)."""
    from ..tensor import Tensor
    return t.value if isinstance(t, Tensor) else t


class SwapFailed(RuntimeError):
    """A weight hot-swap was refused or could not be applied (r24).

    Raised BEFORE any live state is touched: a torn/corrupt/mismatched
    checkpoint, or an engine that is not at a swappable boundary,
    leaves the old weights serving and the old generation pinned —
    never a half-applied state dict, never mixed tensors."""


class PageAllocator:
    """Host-side free-list allocator over the shared page pool.

    Pages are plain ints in [0, num_pages); the pool's reserved scratch
    page (index num_pages in the device arrays) is never handed out.
    `alloc` is all-or-nothing so a request that does not fit leaves the
    free list untouched (no partial reservations to unwind). Owners are
    arbitrary hashables: requests own by req_id (int), the prefix cache
    owns by ("prefix", key) tuples.

    Reservations (the speculative-decoding discipline): ``reserve``
    claims CAPACITY without binding physical pages; ``alloc_reserved``
    later converts reservation into pages (guaranteed to succeed), and
    ``release_pages(..., rereserve=True)`` converts pages back into
    reservation. ``free_count`` excludes reserved capacity, so
    admission-fit checks and the prefix cache's eviction pressure see
    only genuinely available pages. This is what lets a speculative
    slot grow its page set token-by-token and RETURN wholly-unused
    pages on rejection rollback while its future growth stays
    deadlock-free (capacity was committed at admission).

    ``ledger`` (r18, inference/page_ledger.py): an optional PageLedger
    that every successful mutation appends to — the memory-forensics
    plane. With a ledger attached, ``check_no_leak`` failures dump the
    dangling pages' ownership history instead of bare counts. None
    (the default for direct construction) is byte-for-byte the
    pre-r18 allocator."""

    def __init__(self, num_pages: int, ledger=None):
        self.num_pages = num_pages
        self._free: List[int] = list(range(num_pages))
        self._owned: Dict[Hashable, List[int]] = {}
        self._reserved: Dict[Hashable, int] = {}
        self.ledger = ledger

    @property
    def free_count(self) -> int:
        return len(self._free) - self.reserved_total

    @property
    def reserved_total(self) -> int:
        return sum(self._reserved.values())

    def alloc(self, owner: Hashable, n: int) -> Optional[List[int]]:
        from ..distributed.fault_inject import fault_point
        # chaos site: a transient allocation failure (the host-side
        # analog of an HBM allocator hiccup). Admission treats it like
        # a no-fit and requeues — never a leak, never a wedge.
        fault_point("alloc.page")
        if n > self.free_count:
            return None
        pages = [self._free.pop() for _ in range(n)]
        self._owned.setdefault(owner, []).extend(pages)
        if self.ledger is not None:
            self.ledger.record("alloc", owner, pages)
        return pages

    def reserve(self, owner: Hashable, n: int) -> bool:
        """All-or-nothing capacity claim (no physical pages bound)."""
        from ..distributed.fault_inject import fault_point
        fault_point("alloc.page")  # same chaos regime as alloc()
        if n > self.free_count:
            return False
        if n:
            self._reserved[owner] = self._reserved.get(owner, 0) + n
            if self.ledger is not None:
                self.ledger.record("reserve", owner, n=n)
        return True

    def reserved(self, owner: Hashable) -> int:
        return self._reserved.get(owner, 0)

    def alloc_reserved(self, owner: Hashable, n: int) -> List[int]:
        """Convert ``n`` pages of ``owner``'s reservation into physical
        pages. Never fails: reserve() bounded the claim against the
        free list, and only alloc/alloc_reserved consume it."""
        held = self._reserved.get(owner, 0)
        if n > held:
            raise RuntimeError(
                f"{owner!r} asked for {n} reserved pages but holds a "
                f"reservation of {held}")
        pages = [self._free.pop() for _ in range(n)]
        self._owned.setdefault(owner, []).extend(pages)
        if held == n:
            self._reserved.pop(owner, None)
        else:
            self._reserved[owner] = held - n
        if self.ledger is not None and pages:
            self.ledger.record("alloc_reserved", owner, pages)
        return pages

    def release_pages(self, owner: Hashable, pages: Sequence[int],
                      rereserve: bool = False) -> None:
        """Return SPECIFIC pages to the free list (rollback of rejected
        speculation). ``rereserve`` converts them back into reservation
        so the owner's growth guarantee is preserved."""
        held = self._owned.get(owner, [])
        for p in pages:
            if p not in held:
                raise RuntimeError(
                    f"release of page {p} not owned by {owner!r}")
            held.remove(p)
            self._free.append(p)
        if not held:
            self._owned.pop(owner, None)
        if rereserve and pages:
            self._reserved[owner] = (self._reserved.get(owner, 0) +
                                     len(pages))
        if self.ledger is not None and pages:
            self.ledger.record("release", owner, pages,
                               rereserve=rereserve)

    def free(self, owner: Hashable) -> int:
        pages = self._owned.pop(owner, [])
        for p in pages:
            if p in self._free:  # double free = scheduler bug
                raise RuntimeError(f"page {p} double-freed")
        self._free.extend(pages)
        res_held = self._reserved.pop(owner, None) or 0
        if self.ledger is not None and (pages or res_held):
            self.ledger.record("free", owner, pages,
                               reserved_freed=res_held)
        return len(pages)

    def transfer(self, owner: Hashable, new_owner: Hashable,
                 pages: Sequence[int]) -> None:
        """Move specific pages between owners (no free-list round trip:
        the pages stay live — this is how a finished prefill's full
        prompt pages become prefix-cache property instead of being
        recycled with the request)."""
        held = self._owned.get(owner, [])
        for p in pages:
            if p not in held:
                raise RuntimeError(
                    f"transfer of page {p} not owned by {owner!r}")
            held.remove(p)
        if not held:
            self._owned.pop(owner, None)
        self._owned.setdefault(new_owner, []).extend(pages)
        if self.ledger is not None and pages:
            self.ledger.record("transfer", owner, pages,
                               new_owner=new_owner)

    def owners(self) -> Dict[Hashable, Tuple[int, ...]]:
        """Snapshot of live ownership (diagnostics / cache audits)."""
        return {k: tuple(v) for k, v in self._owned.items()}

    def occupancy(self) -> Dict[str, int]:
        """Pool breakdown by owner class (r18 capacity timeline):
        ``inflight`` (request-owned) / ``prefix_device`` (prefix-cache
        chains) / ``dedup`` (cross-request content-shared pages, r23)
        / ``reserved`` (speculative capacity) / ``free``.
        Sums to ``num_pages`` by construction — the invariant
        tools/flight_inspect.py lints. Scrape/conn threads read this
        while the engine thread mutates; retry the benign
        dict-iteration race (the health-op discipline) — a class
        count pinned between retries stays self-consistent because it
        is recomputed whole."""
        infl = pfx = dedup = reserved = 0
        for attempt in range(3):
            infl = pfx = dedup = reserved = 0
            try:
                for owner, pages in list(self._owned.items()):
                    if isinstance(owner, tuple) and owner \
                            and owner[0] == "prefix":
                        pfx += len(pages)
                    elif isinstance(owner, tuple) and owner \
                            and owner[0] == "dedup":
                        dedup += len(pages)
                    else:
                        infl += len(pages)
                # inside the retry: summing _reserved.values() races
                # the same engine-thread mutations the _owned walk does
                reserved = self.reserved_total
                break
            except RuntimeError:
                continue
        # free NORMALIZED from the other classes (not read separately):
        # engine-thread reads are exact either way, and a scrape-side
        # racy read then still satisfies sum-to-pool instead of
        # presenting classes torn across two snapshots
        free = max(0, self.num_pages - infl - pfx - dedup - reserved)
        return {"inflight": infl, "prefix_device": pfx,
                "dedup": dedup, "reserved": reserved, "free": free}

    def check_no_leak(self) -> None:
        if self._owned or self._reserved or \
                len(self._free) != self.num_pages:
            msg = (
                f"page leak: {sum(map(len, self._owned.values()))} owned "
                f"by {sorted(self._owned, key=str)}, "
                f"{self.reserved_total} reserved by "
                f"{sorted(self._reserved, key=str)} with "
                f"{len(self._free)}/{self.num_pages} free")
            if self.ledger is not None:
                # forensics, not counts (r18): each dangling page's
                # retained ownership history — who alloc'd it, on
                # which step, why, and every transfer since
                msg += "\nledger forensics:\n" + self.ledger.forensics(
                    self._owned, self._reserved)
            raise RuntimeError(msg)


@dataclasses.dataclass
class RequestStats:
    """Per-request serving telemetry (time.monotonic timestamps).

    Filled by the engine across the request lifecycle and exposed on
    completion (the record serving/metrics.py aggregates — the
    per-request granularity VERDICT weak #5 asked for). Derived
    latencies return None until their inputs exist."""

    submit_t: float = 0.0
    admit_t: float = 0.0
    prefill_ms: float = 0.0
    first_token_t: float = 0.0
    finish_t: float = 0.0
    tokens_out: int = 0
    prompt_len: int = 0
    cached_pages: int = 0          # prefix-cache pages reused at admit
    cached_tokens: int = 0         # = cached_pages * page_size
    # hierarchical prefix cache (r15): pages restored from spill tiers
    # at admission (a subset of cached_pages — restored pages skip
    # their prefill exactly like device hits, at the cost of one
    # device_put + page-table splice, whose wall time is restore_ms)
    restored_pages: int = 0
    restored_host_pages: int = 0
    restored_disk_pages: int = 0
    restore_corrupt: int = 0       # corrupt blobs hit (fell back typed)
    restore_ms: float = 0.0
    # disaggregated serving (r20): pages spliced in whose blobs were
    # FETCHED from a peer replica over the wire (a subset of
    # restored_pages — the fetched-vs-restored split), and the wall
    # time the server's connection thread spent on the fetch RPC
    # (off the engine thread; decode never waits on the wire)
    handoff_pages: int = 0
    handoff_ms: float = 0.0
    prompt_pages: int = 0          # shareable full pages in the prompt
    cache_enabled: bool = False    # a prefix cache was configured
    prefill_attempts: int = 0      # 1 = first try succeeded
    prefill_chunks: int = 0        # prefill launches (1 = whole prefill)
    spec_steps: int = 0            # verify steps this request rode
    spec_drafted: int = 0          # draft tokens offered to verify
    spec_accepted: int = 0         # draft tokens accepted
    # memory observatory (r18): per-request page attribution — the
    # high-water mark of privately-owned pages (shared prefix pages
    # are the cache's) and the time integral of pages held (page *
    # seconds), maintained by the engine at admission, each step, and
    # final free. The serving_request_peak_pages histogram aggregates
    # the former.
    peak_pages: int = 0
    page_seconds: float = 0.0

    @property
    def acceptance_rate(self) -> Optional[float]:
        """Accepted / drafted over the request's verify steps."""
        if self.spec_drafted:
            return self.spec_accepted / self.spec_drafted
        return None

    @property
    def tokens_per_step(self) -> Optional[float]:
        """Decode tokens emitted per verify step (the speculative win:
        > 1 means the weight/KV stream amortized). The prefill-produced
        first token is excluded — it predates any verify step."""
        if self.spec_steps and self.tokens_out > 1:
            return (self.tokens_out - 1) / self.spec_steps
        return None

    @property
    def queue_delay_s(self) -> Optional[float]:
        if self.admit_t and self.submit_t:
            return self.admit_t - self.submit_t
        return None

    @property
    def ttft_s(self) -> Optional[float]:
        """Submit → first generated token (includes queueing)."""
        if self.first_token_t and self.submit_t:
            return self.first_token_t - self.submit_t
        return None

    @property
    def tpot_s(self) -> Optional[float]:
        """Mean per-output-token time after the first token."""
        if self.finish_t and self.first_token_t and self.tokens_out > 1:
            return ((self.finish_t - self.first_token_t)
                    / (self.tokens_out - 1))
        return None

    def to_dict(self) -> Dict[str, Any]:
        out = dataclasses.asdict(self)
        out["queue_delay_s"] = self.queue_delay_s
        out["ttft_s"] = self.ttft_s
        out["tpot_s"] = self.tpot_s
        out["acceptance_rate"] = self.acceptance_rate
        out["tokens_per_step"] = self.tokens_per_step
        return out


@dataclasses.dataclass
class DecodeRequest:
    """One generation request in the engine."""
    req_id: int
    prompt: np.ndarray                # [len] int32
    max_new_tokens: int
    eos_token: Optional[int] = None
    priority: int = 1                 # serving/scheduler.py Priority
    generated: List[int] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    done: bool = False
    # queued|prefill|prefill_partial|decoding|done|evicted|shed|failed
    # |deadline|stalled — prefill_partial is the chunked-prefill stage:
    # the slot holds pages and a PARTIAL prompt KV (prefill_done_len
    # tokens stored); it rides decode steps masked to the scratch page
    # until its last chunk lands
    state: str = "queued"
    # chunked prefill: prompt tokens whose KV is already stored
    # (prefix-cache hits count — shared pages and prior chunks are the
    # same "already stored" case); meaningful in prefill_partial
    prefill_done_len: int = 0
    # consecutive engine steps this request's next prefill chunk was
    # deferred by higher-class decode work (scheduler starvation bound)
    chunk_deferrals: int = 0
    stats: RequestStats = dataclasses.field(default_factory=RequestStats)
    on_token: Optional[Callable[[int, int, bool], None]] = None
    cache_keys: Tuple[Hashable, ...] = ()   # prefix-cache chain refs held
    bypass_count: int = 0             # times a later request jumped us
    # absolute time.monotonic() deadline (None = no deadline); carried
    # from the protocol's deadline_ms through admission, decode steps
    # and eviction so an expired request never holds pages
    deadline_t: Optional[float] = None
    # last time a token was delivered (stall watchdog input)
    last_emit_t: float = 0.0
    # end-to-end tracing (r16): the request's span tree
    # (serving/tracing.py RequestTrace; None = unsampled — the hot
    # path's only cost is this attribute check) and the currently open
    # lifecycle-stage span (queue -> prefill -> decode)
    trace: Any = None
    span: Any = None
    # disaggregated serving (r20): True marks a handoff-blocking
    # prefill job (a prefill-class replica's prefill_only request —
    # a decode replica is waiting on its chain), which the SLO
    # scheduler boosts by cfg.handoff_boost priority levels
    handoff: bool = False

    @property
    def tokens(self) -> np.ndarray:
        return np.concatenate([self.prompt,
                               np.asarray(self.generated, np.int32)])


class ContinuousBatchingEngine:
    """Fixed-slot continuous batching over one jitted paged decode step.

    ``num_pages`` sizes the shared pool; with
    num_pages < num_slots * max_pages_per_seq the engine oversubscribes
    slots against real memory and admission blocks on the free list —
    the page-recycling regime the tests pin. Greedy decoding (the
    deterministic serving mode; sampling belongs to generate())."""

    def __init__(self, model, num_slots: int = 4, page_size: int = 64,
                 max_seq_len: Optional[int] = None,
                 num_pages: Optional[int] = None,
                 kv_int8: bool = False,
                 prompt_buckets: Sequence[int] = (),
                 scheduler=None, prefix_cache=None,
                 prefill_retry=None,
                 on_complete: Optional[Callable[["DecodeRequest"],
                                                None]] = None,
                 max_prefill_attempts: int = 3,
                 speculative=None, verify_retry="site",
                 stall_timeout_s: Optional[float] = None,
                 mesh=None,
                 prefill_chunk_tokens: Optional[int] = None,
                 fused_step: bool = True,
                 tracer=None, timeline_steps: int = 256,
                 capture_costs: bool = False,
                 page_ledger: bool = True,
                 ledger_events: int = 1024,
                 forecast_admission: bool = False,
                 weight_generation: int = 0):
        import jax.numpy as jnp

        from ..core.compile_cache import enable_compile_cache
        from ..models.cache_layout import (UnsupportedCacheLayout,
                                           create_pools,
                                           create_state_pools, ring_pages)

        # persistent compile cache (core/compile_cache.py places it):
        # the engine's prefill-per-bucket + decode/verify programs are
        # exactly the compiles a restarted server pays again cold
        enable_compile_cache()
        self.model = model
        model.eval()
        # weight hot-swap (r24): the generation of the weights this
        # engine currently serves. swap_weights bumps it; the prefix
        # cache salts chain roots with it so KV from different
        # generations never splices.
        self.weight_generation = int(weight_generation)
        self.weight_swaps = 0
        # swap drain gate: while True, _admit is a no-op — active
        # slots finish and free, queued requests WAIT (nothing is
        # dropped), and a pending swap can reach num_active == 0
        # under continuous traffic. Owned by the serving layer.
        self.pause_admission = False
        cfg = model.config
        self.cfg = cfg
        # what the model keeps per layer (models/cache_layout.py): KV
        # heads, head size, a window or none, or a state of fixed size
        # a sequence. Layers that keep every position share the
        # allocator's pages and the one page table; a window layer
        # holds a ring of `ring_pages` pages a slot in a pool of its
        # own; a state layer a row a slot of its state pool, which no
        # page table addresses; a latent layer one row a position for
        # all heads, in the allocator's pages: ONE pool (under "k") and
        # no V pool. Everything below that differs between
        # decoders comes from here, never from the model's class.
        self._layout = list(model.cache_layout())
        self._has_state = any(lc.state is not None for lc in self._layout)
        self._rings = [None if lc.window is None
                       else ring_pages(lc.window, page_size)
                       for lc in self._layout]
        self._has_rings = any(r is not None for r in self._rings)
        # layers whose memory is a row a SLOT: their programs are told
        # which slot each batch row is
        self._slot_rows = self._has_rings or self._has_state
        self._plain_layout = all(lc.plain for lc in self._layout)
        if not self._plain_layout:
            # a ring holds a window's worth of ONE sequence's own keys,
            # a state layer what the whole sequence left (no snapshot
            # of an earlier position), and a heads-major page or a
            # latent page (one pool, no heads) is not the page the
            # spill codecs, the int8 scales, a mesh's head sharding and
            # the verify path read; a prompt over latent pages attends
            # to its own positions alone. What would have to restore,
            # share, rewind or
            # re-enter such a cache is refused here, typed, until it
            # has a parity test. (Spill and handoff move a prefix
            # cache's pages: refused with it. Resurrection replays
            # prompt + tokens through a fresh prefill and needs none.)
            refused = [
                ("a prefix cache (a hit cannot restore a window "
                 "layer's ring, nor a state layer's state at the "
                 "prefix's end)", prefix_cache is not None),
                ("a serving mesh", mesh is not None),
                ("int8 KV pages", bool(kv_int8)),
                ("speculative decoding (a rejected draft cannot be "
                 "rewound out of a ring or a state)",
                 speculative is not None),
                ("chunked prefill (a chunk would have to continue a "
                 "ring or a state)", prefill_chunk_tokens is not None)]
            for what, asked in refused:
                if asked:
                    raise UnsupportedCacheLayout(
                        f"a cache layout with window layers, grouped "
                        f"heads, state layers or latent pages does not "
                        f"support {what} yet")
        # tensor-parallel serving (mesh=None = single-device, the
        # byte-for-byte pre-r10 behavior): weights shard per their
        # mp_layers pspecs, KV pools shard over heads, page table and
        # seq_lens stay replicated host state, and the one compiled
        # decode/verify/prefill step runs under GSPMD with the paged-
        # attention op head-sharded via shard_map. The allocator and
        # every host-side page-accounting invariant are untouched: a
        # page is a page on every shard.
        self.mesh = mesh
        self._mesh_axis = None
        self._kv_sharding = None
        # where the decode step's packed inputs live on a mesh: on
        # every device, whole (None: the default device)
        self._replicated = None
        self._state_shardings = None
        # identity cache for sharded weights: (kind, name) -> (source
        # array, its device_put result). An unchanged leaf transfers to
        # the mesh ONCE per engine lifetime; per-admission state
        # refreshes then cost dict lookups, not host->mesh copies.
        self._shard_cache: Dict = {}
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec
            from ..distributed.topology import SERVING_MODEL_AXIS
            axis = SERVING_MODEL_AXIS
            if axis not in mesh.axis_names:
                raise ValueError(
                    f"serving mesh must carry a {axis!r} axis "
                    f"(distributed.topology.make_serving_mesh); got "
                    f"axes {mesh.axis_names}")
            extra = [a for a in mesh.axis_names
                     if a != axis and mesh.shape[a] != 1]
            if extra:
                raise ValueError(
                    f"serving mesh axes {extra} must have size 1 "
                    f"(only {axis!r} shards the decode engine)")
            n = int(mesh.shape[axis])
            kv_heads = sorted({lc.kv_heads for lc in self._layout})
            if cfg.num_heads % n or any(h % n for h in kv_heads):
                raise ValueError(
                    f"num_heads {cfg.num_heads} (KV heads {kv_heads}) "
                    f"not divisible by mesh {axis}={n}")
            if cfg.vocab_size % n:
                raise ValueError(
                    f"vocab_size {cfg.vocab_size} not divisible by "
                    f"mesh {axis}={n} (VocabParallelEmbedding shards "
                    f"the vocab dim)")
            self._mesh_axis = axis
            # one spec serves pools ([P+1, page, H, D]) and scales
            # ([P+1, page, H]): dim 2 is the head dim in both
            self._kv_sharding = NamedSharding(
                mesh, PartitionSpec(None, None, axis))
            self._replicated = NamedSharding(mesh, PartitionSpec())
        self.page_size = int(page_size)
        self.num_slots = int(num_slots)
        self.max_seq_len = int(max_seq_len or cfg.max_seq_len)
        if self.max_seq_len > int(cfg.max_seq_len):
            # the GPT position table (wpe) has exactly cfg.max_seq_len
            # rows: positions past it are an out-of-bounds gather whose
            # jnp fill-mode NaNs poison the shared scratch page and,
            # through the attention row max, every co-resident slot's
            # stream — fail typed at construction instead
            raise ValueError(
                f"max_seq_len={self.max_seq_len} exceeds the model's "
                f"position-embedding capacity "
                f"(cfg.max_seq_len={cfg.max_seq_len}); positions past "
                f"it would read garbage embeddings. Use a config with "
                f"a larger max_seq_len")
        self.max_pages = -(-self.max_seq_len // self.page_size)
        self.num_pages = int(num_pages if num_pages is not None
                             else num_slots * self.max_pages)
        self.kv_int8 = bool(kv_int8)
        if not prompt_buckets:
            bucket, prompt_buckets = self.page_size, []
            while bucket < self.max_seq_len:
                prompt_buckets.append(bucket)
                bucket *= 2
            prompt_buckets.append(self.max_seq_len)
        self.prompt_buckets = sorted(set(int(x) for x in prompt_buckets))

        # page ledger (r18, inference/page_ledger.py): every allocator
        # mutation appended to a bounded ring with owner/step/reason —
        # the memory-forensics plane. Default ON (host-side dict
        # appends next to jit launches; the memory_observatory bench
        # A/Bs it at ~1.0x ms/step); page_ledger=False is the
        # byte-for-byte pre-r18 allocator.
        if page_ledger:
            from .page_ledger import PageLedger
            self.ledger: Optional["PageLedger"] = PageLedger(
                capacity=int(ledger_events))
        else:
            self.ledger = None
        self.allocator = PageAllocator(self.num_pages,
                                       ledger=self.ledger)
        # byte-planning admission (r23): when True, _fits also charges
        # the forecast page-burn of the already-admitted fleet over
        # this request's expected lifetime (the r18 exhaustion
        # forecast over the step timeline) — a request is admitted
        # only when the POOL'S FUTURE, not just its instant free
        # count, accommodates it. Default False: byte-for-byte the
        # instant-occupancy gate.
        self.forecast_admission = bool(forecast_admission)
        self.forecast_denials = 0
        self._scratch = self.num_pages  # reserved page index
        dt = self._layout[0].dtype
        self._nl = len(self._layout)
        # one DISTINCT pool per layer (not nl references to one array:
        # the jitted step donates the pool buffers, and donating the
        # same buffer for two arguments is an error); a window layer's
        # pool is its rings, `num_slots * ring` pages and the scratch;
        # a state layer has no pages: a row a slot of `state` and `tail`
        per_layer = [
            (None,) * 4 + create_state_pools(lc, self.num_slots)
            if lc.state is not None else create_pools(
                lc,
                self.num_pages if ring is None else self.num_slots * ring,
                self.page_size, self.max_pages, quantized=self.kv_int8,
                kv_sharding=self._kv_sharding) + (None, None)
            for lc, ring in zip(self._layout, self._rings)]
        self._pools = {
            name: [p[j] for p in per_layer] for j, name in
            enumerate(("k", "v", "ks", "vs", "state", "tail"))}
        def nbytes(xs):
            return sum(int(x.size) * x.dtype.itemsize
                       for x in xs if x is not None)

        self.state_pool_bytes = nbytes(self._pools["state"]
                                       + self._pools["tail"])
        # the latent layers' pools as they lie in memory: a row's
        # padding to whole lane tiles counts
        self.latent_pool_bytes = nbytes(
            x for lc, x in zip(self._layout, self._pools["k"])
            if lc.latent is not None)
        # host-owned scheduler state. The three mirrors of the decode
        # step's inputs are views of ONE packed int32 array
        # ``[num_slots, max_pages + 2]`` (page table | length | current
        # token), so a stale step sends them in one transfer (see
        # _write_slot / _decode_step). They are written in place,
        # never rebound.
        self._packed = np.zeros((self.num_slots, self.max_pages + 2),
                                np.int32)
        self._table = self._packed[:, :self.max_pages]
        self._lens = self._packed[:, self.max_pages]
        self._cur = self._packed[:, self.max_pages + 1]
        self._table[:] = self._scratch
        # the device's copy of ``_packed`` as the NEWEST decode program
        # launched returns it (what the mirrors will read once every
        # launched step is settled), or None where a host write (or a
        # failed step) has made it stale: the next decode step then
        # uploads
        self._resident = None
        # the decode step launched and not yet fetched (_launch_decode's
        # record), at most one: the look-ahead of depth one. Its tokens
        # are handed out by the next step(), under the step launched
        # from its outputs, or by whoever has to write a slot first
        # (_settle_inflight)
        self._inflight: Optional[Dict[str, Any]] = None
        self._settled_t = 0.0  # end of the newest settle (decode EMA)
        # this step's decode launch for the record: (decode_h2d,
        # decode_ahead)
        self._tl_decode: Optional[Tuple[int, int]] = None
        # counters the model's own programs report (a routed model: the
        # experts its rows touched), packed behind the tokens in the
        # step's one fetch: names by program kind as traced, this
        # step's values for the timeline record, running totals
        self._traced_stats = None
        self._stat_names: Dict[str, List[Tuple[str, str]]] = {}
        self._tl_stats: Dict[str, Dict[str, Any]] = {}
        self.model_counters: Dict[str, Dict[str, float]] = {}
        self.decode_steps_resident = 0
        self.decode_steps_uploaded = 0
        self.decode_steps_ahead = 0
        # rows a look-ahead step computed for a request that finished
        # in the step before it: dropped at the settle, never handed out
        self.decode_rows_dropped = 0
        # of those, the rows whose step moved a state layer's row of
        # the finished slot in place (a KV append for such a row lands
        # on a page nobody reads; this lands in the slot's own row).
        # Harmless only because nothing reads a slot's state between
        # its finish and the next admission, and a prompt writes the
        # whole row from zero (tests/test_solar_open2.py pins it)
        self.state_rows_overwritten = 0
        # what whole-prompt prefills ran: the prompts' own positions
        # and the buckets they were padded to (their quotient is the
        # share of a prefill program's rows that are a prompt's; the
        # flash forward skips the rest where a model hands it the
        # lengths, the matmuls run them)
        self.prefill_positions = 0
        self.prefill_positions_padded = 0
        self._slots: List[Optional[DecodeRequest]] = \
            [None] * self.num_slots
        self._queue: List[DecodeRequest] = []
        self._finished: Dict[int, DecodeRequest] = {}
        self._next_id = 0
        self._jnp = jnp
        self._decode_jit = None
        self._prefill_jits: Dict[bool, Any] = {}
        self._state_cache = None
        self.steps = 0
        # serving hooks (all optional; None = bare-engine behavior)
        self._scheduler = scheduler
        cache_ps = getattr(prefix_cache, "page_size", None)
        if cache_ps is not None and int(cache_ps) != self.page_size:
            # fail at construction, not as a page leak after the first
            # successful prefill's insert()
            raise ValueError(
                f"prefix_cache.page_size {cache_ps} != engine "
                f"page_size {self.page_size}")
        self._prefix_cache = prefix_cache
        # hierarchical prefix cache (r15): a cache carrying spill tiers
        # needs device IO — how to copy an evicted page's KV to host
        # (spill) and splice a restored blob into a fresh page. The
        # splice is one jitted donate-in-place scatter per restore
        # (models/gpt.py paged_page_splice), compiled once; the spill
        # read is one jitted stacked gather (same discipline).
        self._splice_jit = None
        self._gather_jit = None
        if getattr(prefix_cache, "tiers", None):
            prefix_cache.attach_device_io(self._read_page,
                                          self._splice_page)
        self._prefill_retry = prefill_retry
        self._on_complete = on_complete
        self.max_prefill_attempts = int(max_prefill_attempts)
        # stall watchdog: a slot that delivers no token for this long
        # is evicted with the typed "stalled" state instead of holding
        # its pages forever (None = off). Healthy engines emit a token
        # per active slot per step, so a stall only ever means the
        # step itself is failing or pathologically slow.
        self.stall_timeout_s = (None if stall_timeout_s is None
                                else float(stall_timeout_s))
        # chunked prefill (r11): None = whole-prefill admission (the
        # byte-for-byte pre-r11 behavior). A positive multiple of
        # page_size makes admission bind pages WITHOUT prefilling and
        # each step() advance at most one half-prefilled slot by one
        # page-aligned chunk of this many tokens (one fixed chunk
        # bucket -> one prefill compile) before the decode step — so
        # in-flight streams keep ticking while a long prompt trickles
        # in instead of stalling behind its whole suffix prefill.
        self.prefill_chunk_tokens: Optional[int] = None
        if prefill_chunk_tokens is not None:
            c = int(prefill_chunk_tokens)
            if c < self.page_size or c % self.page_size:
                raise ValueError(
                    f"prefill_chunk_tokens {c} must be a positive "
                    f"multiple of page_size {self.page_size} (chunks "
                    f"are page-aligned so every chunk boundary lands "
                    f"on a page boundary)")
            self.prefill_chunk_tokens = c
        # split EMAs (r11): the deadline admission gate's estimates.
        # decode_ema_s tracks ONLY the decode/verify jit call;
        # prefill_chunk_ema_s tracks one fixed-bucket prefill chunk
        # (constant-cost by construction), so a prefill-heavy step
        # can't poison the per-token estimate short requests are
        # gated on. step_ema_s remains as a back-compat alias.
        self.decode_ema_s: Optional[float] = None
        self.prefill_chunk_ema_s: Optional[float] = None
        # chunk-EMA warmup guard (the analog of decode's skip-first-
        # step rule): the first launch of each chunk-jit variant
        # (fresh / chained) is compile-dominated — recording it would
        # make _deadline_hopeless estimate seconds per chunk and shed
        # every deadline-carrying long prompt until the EMA decayed
        self._chunk_warm = {False: False, True: False}
        # engine-wide last-chunk-progress timestamp: the stall
        # watchdog's liveness signal for half-prefilled slots WAITING
        # their turn for the single per-step chunk budget (see
        # evict_stalled)
        self._last_chunk_t = 0.0
        # fused decode hot path (r13): True (the default) traces the
        # decode/prefill/verify programs through the fused kernels —
        # attention + out-projection folded into ONE op per layer
        # (models/gpt.py fused_decode -> ops paged_attention_fused)
        # and sampling streamed through the lm_head from the final
        # hidden row (nn/decode.py fused_sample_token), so the
        # [B, vocab] logits tensor never materializes in HBM. Greedy
        # outputs are BIT-IDENTICAL either way where the fused
        # REFERENCES run (the CPU lane — pinned); on TPU the Mosaic
        # fused kernels mimic the unfused lowering's precision but
        # cross-mode bit-parity there is chip-pending validation
        # (ops/pallas/paged_attention.py paged_attention_fused).
        # False is byte-for-byte the pre-r13 trace — the same
        # escape-hatch pattern as mesh=None / prefill_chunk_tokens=None.
        self.fused_step = bool(fused_step)
        # page-growth discipline of a speculative engine: admission
        # binds only the prefill-covering pages and RESERVES the rest,
        # and a verify step grows each slot's page set out of that
        # reservation (guaranteed to succeed)
        self._reserve_growth = speculative is not None
        # traced-program op counts per jitted step kind (the launch
        # counter: dispatch.count_op_calls around each jit call counts
        # the ops traced into the program on a (re)trace, zero on the
        # compiled fast path) — the fused_decode A/B's currency and
        # the serving_step_programs gauge's source
        self.step_programs: Dict[str, int] = {}
        # end-to-end tracing (r16): a serving/tracing.py SpanTracer
        # (None = off, the default — every hook degrades to one
        # attribute check; sampling happens once per request at
        # submit, so there is NO per-token cost for unsampled work)
        self._tracer = tracer
        # step timeline (r16): a fixed-size ring of per-step records —
        # programs launched by kind, the engine thread's host phases
        # (`host_us`, see _tl_commit), slot occupancy and page
        # pressure. Always on: one small dict per ENGINE STEP (never
        # per token) next to a jit launch.
        self.timeline: "collections.deque" = collections.deque(
            maxlen=max(1, int(timeline_steps)))
        # host phases of the thread that steps the engine
        # (core/profiler.py): every `with self._phase(name)` adds its
        # own time to `_host.us` and is a `pt.host.<name>` event of a
        # running profiler session. The names are HOST_PHASES and
        # `commit`; a phase entered inside another pauses the outer
        # one. step() empties the accumulator, _tl_commit stores it.
        self._host = HostPhases()
        self._phase = self._host.phase
        # where the previous record's commit ended (monotonic) and the
        # stepping thread's CPU clock there: `gap_us` and `cpu_us`
        self._tl_end: Optional[float] = None
        self._tl_cpu: Tuple[int, int] = (0, 0)
        # cumulative program launches by kind (every jit call — 1 per
        # launch, unlike step_programs which records traced-op counts)
        self.programs_launched: Dict[str, int] = {}
        self._tl_programs: Dict[str, int] = {}
        self._tl_ms: Dict[str, float] = {}
        # program cost capture (r16 satellite): at each program kind's
        # first (re)trace, run jit.lower(...).cost_analysis() on stub
        # avals — flops / bytes-accessed estimates for the
        # serving_program_* gauges (replacing the r10 collective-bytes
        # stub). Engine-thread only (bind_state tracing is not
        # thread-safe) and OFF by default: the extra abstract trace
        # per kind (~decode-trace cost) is only worth paying where the
        # numbers are scraped — the server enables it.
        self._capture_costs = bool(capture_costs)
        self._program_costs: Dict[str, Dict] = {}
        self._kv_dtype = dt
        # speculative decoding (inference/speculative.py): draft k
        # tokens per step, verify all k+1 in ONE forward, emit the
        # longest accepted prefix + 1. Greedy stays bit-identical to
        # the vanilla engine; OFF by default.
        self._spec_cfg = None
        self._spec_draft = None
        self._verify_jit = None
        self._spec_key = None
        if speculative is not None:
            from .speculative import as_spec_config
            self._spec_cfg = as_spec_config(speculative)
            self._spec_draft = self._spec_cfg.build_draft()
            if verify_retry == "site":
                from ..distributed.resilience import get_retry_policy
                verify_retry = get_retry_policy("serving.verify")
            self._verify_retry = verify_retry
        else:
            self._verify_retry = None

    # -- request lifecycle -------------------------------------------------

    def submit(self, prompt, max_new_tokens: int,
               eos_token: Optional[int] = None, priority: int = 1,
               on_token: Optional[Callable[[int, int, bool], None]] = None,
               deadline_t: Optional[float] = None,
               trace=None, trace_ctx: Optional[Dict] = None,
               handoff: bool = False,
               handoff_info: Optional[Dict] = None) -> int:
        """``trace``: an existing RequestTrace to CONTINUE (resurrection
        replay resubmits the in-flight request onto the same tree);
        ``trace_ctx``: a wire context from an upstream hop (the
        failover router) that forces sampling and links this request's
        root under the upstream span. With neither, the engine's own
        tracer (if any) makes the sampling decision.

        Disaggregated serving (r20): ``handoff=True`` marks a
        handoff-blocking prefill job (scheduler boost);
        ``handoff_info={"ms": ..., "bytes": ...}`` records the wire
        fetch the server's connection thread already performed for
        this request (the fetched blobs sit in the prefix cache's
        tiers; admission splices them via restore_from_spill)."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) + max_new_tokens > self.max_seq_len:
            raise ValueError(
                f"prompt {len(prompt)} + max_new {max_new_tokens} exceeds "
                f"max_seq_len {self.max_seq_len}")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (the prefill "
                             "itself produces the first token)")
        if len(prompt) > self.prompt_buckets[-1]:
            raise ValueError(
                f"prompt length {len(prompt)} exceeds the largest "
                f"prompt bucket {self.prompt_buckets[-1]}")
        need = -(-(len(prompt) + max_new_tokens) // self.page_size)
        if need > self.num_pages:
            # would block the FIFO head forever — no amount of
            # recycling frees pages that never existed
            raise ValueError(
                f"request needs {need} pages but the pool has only "
                f"{self.num_pages}; raise num_pages or shrink the "
                f"request")
        req = DecodeRequest(self._next_id, prompt, int(max_new_tokens),
                            eos_token, priority=int(priority),
                            on_token=on_token,
                            deadline_t=(None if deadline_t is None
                                        else float(deadline_t)),
                            handoff=bool(handoff))
        req.stats.submit_t = time.monotonic()
        req.stats.prompt_len = len(prompt)
        if handoff_info:
            req.stats.handoff_ms = float(handoff_info.get("ms", 0.0))
        self._next_id += 1
        tr = trace
        if tr is None and self._tracer is not None:
            if trace_ctx is not None:
                tr = self._tracer.start(
                    "request", ctx=trace_ctx, req_id=req.req_id,
                    prompt_len=len(prompt),
                    max_new=int(max_new_tokens))
            elif self._tracer.sample():
                tr = self._tracer.start(
                    "request", sampled=True, req_id=req.req_id,
                    prompt_len=len(prompt),
                    max_new=int(max_new_tokens))
        if tr is not None:
            req.trace = tr
            req.span = tr.begin("queue", parent=tr.anchor,
                                req_id=req.req_id,
                                priority=int(priority),
                                prompt_len=len(prompt))
        self._queue.append(req)
        return req.req_id

    def result(self, req_id: int, pop: bool = False
               ) -> Optional[np.ndarray]:
        req = (self._finished.pop(req_id, None) if pop
               else self._finished.get(req_id))
        return None if req is None else req.tokens

    @property
    def num_active(self) -> int:
        return sum(r is not None for r in self._slots)

    @property
    def num_queued(self) -> int:
        return len(self._queue)

    @property
    def free_pages(self) -> int:
        return self.allocator.free_count

    @property
    def step_ema_s(self) -> Optional[float]:
        """Back-compat alias: r11 split the old blended step EMA into
        ``decode_ema_s`` (decode/verify jit only) and
        ``prefill_chunk_ema_s`` (one fixed-bucket prefill chunk)."""
        return self.decode_ema_s

    @step_ema_s.setter
    def step_ema_s(self, value: Optional[float]) -> None:
        self.decode_ema_s = value

    @property
    def prefill_debt_tokens(self) -> int:
        """Outstanding prefill work in tokens: the un-stored prompt
        suffix of every half-prefilled slot plus every queued prompt
        (an upper bound — future prefix-cache hits may shrink it).
        The serving layer exports this as the
        ``serving_prefill_debt_tokens`` gauge."""
        debt = sum(len(r.prompt) - r.prefill_done_len
                   for r in self._slots
                   if r is not None and r.state == "prefill_partial")
        debt += sum(len(r.prompt) for r in self._queue)
        return debt

    # -- the host mirrors of the decode step's inputs ----------------------

    def _write_slot(self, slot: int, table=None, lens=None,
                    cur=None) -> None:
        """The one writer of the host mirrors (``_table``, ``_lens``,
        ``_cur``): sets what is given in ``slot``'s row and marks the
        device's copy stale, so the next decode step uploads the
        mirrors instead of feeding the program its own outputs. Only
        the settle of a decode step writes past it: it advances the
        mirrors by what the device computed, which is what keeps the
        copy current (see _decode_step). A caller that computes what
        it writes from the mirrors settles the step in flight first
        (_settle_inflight); a finish inside a settle is the one write
        that may land under a step in flight, and that step's row for
        the slot is dropped when it is settled."""
        if table is not None:
            self._table[slot] = table
        if lens is not None:
            self._lens[slot] = lens
        if cur is not None:
            self._cur[slot] = cur
        self._resident = None

    def _place_resident(self, packed: np.ndarray):
        """The decode step's one host-to-device transfer: the packed
        mirrors as a device array, replicated over the serving mesh
        where there is one (where ``jnp.asarray`` of the three arrays
        ended up, now said outright so that the program's own output
        and an upload are one signature to the jit)."""
        if self._replicated is None:
            return self._jnp.asarray(packed)
        import jax
        return jax.device_put(packed, self._replicated)

    # -- jitted device programs -------------------------------------------

    def _caches(self, pools, table, lens, rows=None):
        """One cache a layer over the pools. A window layer's table is
        its slots' rings (``rows``: the slot of each batch row; the
        decode step's rows are the slots in order)."""
        from ..models.cache_layout import (LatentCache, StateCache,
                                           ring_table)
        from ..models.gpt import PagedKVCache
        if rows is None and self._slot_rows:
            rows = self._jnp.arange(table.shape[0], dtype=self._jnp.int32)
        return [StateCache(pools["state"][i], pools["tail"][i], rows, lens)
                if lc.state is not None
                else LatentCache(pools["k"][i], table, lens)
                if lc.latent is not None
                else PagedKVCache(pools["k"][i], pools["v"][i],
                                  pools["ks"][i], pools["vs"][i],
                                  table if ring is None
                                  else ring_table(rows, ring), lens)
                for i, (lc, ring) in enumerate(zip(self._layout,
                                                   self._rings))]

    def _take_stats(self):
        """At trace time, after the model's call: the counters of the
        forward just traced (``pop_step_stats``: ``{group: {name: int32
        scalar}}``), or None from a model that reports none."""
        pop = getattr(self.model, "pop_step_stats", None)
        self._traced_stats = None if pop is None else pop()

    def _pack_stats(self, kind: str, nxt):
        """The program's first result with the traced counters behind
        the tokens, so that they come back in the fetch the step makes
        anyway; ``nxt`` itself where the model reports none (then the
        program is the one it always was)."""
        stats, self._traced_stats = self._traced_stats, None
        if not stats:
            return nxt
        jnp = self._jnp
        names = [(g, k) for g in sorted(stats) for k in sorted(stats[g])]
        self._stat_names[kind] = names
        return jnp.concatenate(
            [nxt.reshape(-1).astype(jnp.int32),
             jnp.stack([stats[g][k].astype(jnp.int32)
                        for g, k in names])])

    def _fold_stats(self, kind: str, values) -> None:
        """Counters fetched behind a program's tokens: into this step's
        record (the largest where a step ran the program twice) and
        the running totals. A name that ends in ``_x1000`` carries
        thousandths."""
        for (group, key), v in zip(self._stat_names.get(kind, ()), values):
            v = float(v)
            if key.endswith("_x1000"):
                key, v = key[:-6], v / 1000.0
            elif v == int(v):
                v = int(v)
            rec = self._tl_stats.setdefault(group, {})
            rec[key] = max(v, rec.get(key, v))
            tot = self.model_counters.setdefault(
                f"{group}.{key}", {"n": 0, "sum": 0.0, "max": v})
            tot["n"] += 1
            tot["sum"] += v
            tot["max"] = max(tot["max"], v)

    def _fresh_state(self, refresh: bool = False):
        """Model functional state (params AND buffers — converted
        layers hold int8 weights as buffers) for the jitted calls.
        Re-read at every ADMISSION (refresh=True) so post-construction
        weight mutation (set_state_dict, convert_to_weight_only_int8)
        is served, not silently ignored — a structural change simply
        retraces via the new argument pytree (the r5 stale-cache
        lesson). The per-token decode step reuses the cached dict:
        rebuilding hundreds of entries per generated token is pure
        host overhead on the hot path."""
        if refresh or self._state_cache is None:
            from ..nn.layer import functional_state
            self._state_cache = self._shard_state(
                functional_state(self.model))
        return self._state_cache

    def _shard_state(self, state):
        """Place the functional state on the serving mesh per each
        weight's mp_layers pspec (mesh=None: passthrough). Transfers
        are identity-cached, so only leaves that actually changed since
        the last refresh (set_state_dict, int8 conversion) move; a
        structural change (new buffer names) recomputes the sharding
        tree — the same retrace-don't-stale contract `_fresh_state`
        documents."""
        if self.mesh is None:
            return state
        import jax

        from ..nn.layer import functional_state_shardings
        if self._state_shardings is None:
            self._state_shardings = functional_state_shardings(
                self.model, self.mesh)
        out: Dict[str, Dict] = {}
        missed: List = []  # (kind, name, val, sharding)
        for kind in ("params", "buffers"):
            grp = {}
            for name, val in state[kind].items():
                hit = self._shard_cache.get((kind, name))
                if hit is not None and hit[0] is val:
                    grp[name] = hit[1]
                    continue
                sh = self._state_shardings[kind].get(name)
                if sh is None:  # structural change: new leaf appeared
                    self._state_shardings = functional_state_shardings(
                        self.model, self.mesh)
                    sh = self._state_shardings[kind][name]
                missed.append((kind, name, val, sh))
                grp[name] = None  # filled from the batched transfer
            out[kind] = grp
        if missed:
            # ONE batched transfer for every cache miss: on engine
            # build/resurrection all leaves miss, and per-leaf
            # device_put dispatch is serial host overhead
            puts = jax.device_put([v for _, _, v, _ in missed],
                                  [s for _, _, _, s in missed])
            for (kind, name, val, _), put in zip(missed, puts):
                self._shard_cache[(kind, name)] = (val, put)
                out[kind][name] = put
        # prune leaves that vanished from the state (e.g. fp32 params
        # replaced by int8 buffers when convert_to_weight_only_int8
        # swaps layers): a stale entry pins BOTH the host array and its
        # on-mesh copy for the engine lifetime — roughly a full dead
        # model of HBM on exactly the deployments mesh= targets
        live = {(k, n) for k in ("params", "buffers") for n in out[k]}
        for stale in [k for k in self._shard_cache if k not in live]:
            del self._shard_cache[stale]
        return out

    def swap_weights(self, state_dict,
                     generation: Optional[int] = None
                     ) -> Dict[str, Any]:
        """Weight hot-swap (r24): replace the model's weights between
        steps with a fully-validated state dict, bump the weight
        generation, and re-salt the prefix-cache chain keys so KV from
        the old weights misses by construction.

        Validate-then-swap is ATOMIC: the incoming tree is checked
        against the model's own state dict (exact key set, exact
        shapes, exact dtypes) BEFORE any tensor is touched —
        ``set_state_dict`` raises mid-apply on a shape mismatch and
        silently coerces dtypes, so the only safe swap is one that
        cannot hit either path. Any validation failure, and any
        in-flight work (active slots), is a typed :class:`SwapFailed`
        with the old weights still serving and the old generation
        pinned. Queued-but-unadmitted requests
        survive the swap: their memoized chain keys are invalidated so
        their prefills insert under the NEW generation's keys.

        Returns ``{"generation", "leaves", "swap_ms"}`` on success."""
        from ..distributed.fault_inject import fault_point
        t0 = time.monotonic()
        gen = int(generation) if generation is not None \
            else self.weight_generation + 1
        if gen == self.weight_generation:
            raise SwapFailed(
                f"generation {gen} is already serving; a swap must "
                f"move to a new weight generation")
        # a decode step in flight still reads the OLD weights: settle it
        # so the swap lands between launches, never under one
        self._settle_inflight()
        if self.num_active:
            raise SwapFailed(
                f"engine busy: {self.num_active} active slot(s) — "
                f"drain in-flight requests before swapping (old "
                f"requests finish on old weights)")
        own = self.model.state_dict(include_non_persistable_buffer=True)
        got = dict(state_dict)
        missing = [k for k in own if k not in got]
        extra = [k for k in got if k not in own]
        if missing or extra:
            raise SwapFailed(
                f"state-dict structure mismatch: missing "
                f"{sorted(missing)[:8]}, unexpected "
                f"{sorted(extra)[:8]} — a partial apply would serve "
                f"mixed tensors")
        bad = []
        for name, target in own.items():
            arr = np.asarray(getattr(got[name], "value", got[name]))
            if tuple(arr.shape) != tuple(target.shape):
                bad.append(f"{name}: shape {tuple(arr.shape)} vs "
                           f"{tuple(target.shape)}")
            elif np.dtype(arr.dtype) != np.dtype(target.dtype):
                bad.append(f"{name}: dtype {arr.dtype} vs "
                           f"{target.dtype}")
        if bad:
            raise SwapFailed(
                f"state-dict tree mismatch ({len(bad)} leaves): "
                f"{bad[:4]}")
        # the apply fault site fires AFTER validation and BEFORE the
        # first tensor write: an injected abort here proves the
        # all-or-nothing contract (no tensor touched yet)
        fault_point("swap.apply")
        self.model.set_state_dict(got)
        # identity cache: only changed leaves re-transfer to the mesh
        self._fresh_state(refresh=True)
        self.weight_generation = gen
        self.weight_swaps += 1
        if self._prefix_cache is not None:
            with self._led("swap"):
                self._prefix_cache.set_generation(gen, self.allocator)
        # queued requests memoized their chain keys under the OLD
        # generation's salt (match() caches on the request); drop the
        # memos so post-swap admission hashes fresh
        for req in self._queue:
            if hasattr(req, "_pfx_chain"):
                del req._pfx_chain
        return {"generation": gen,
                "leaves": len(own),
                "swap_ms": round((time.monotonic() - t0) * 1e3, 3)}

    def _head_ctx(self):
        """Trace-time mesh routing for the jitted programs: under a
        mesh, every `paged_attention` call inside the traced body
        dispatches head-sharded via shard_map (each device runs the
        standard kernel-selection path on its H/N-head slice), and the
        mp_layers ACTIVATION constraints are disabled — they pin to the
        global hybrid (training) mesh, which is a different device set
        than the serving mesh whenever a fleet group is live in the
        process (the PR-1 leaked-mesh failure mode: "incompatible
        devices" at trace time). The serving mesh carries only mp, so
        GSPMD infers the activation layouts from the weight and KV-pool
        shardings instead.

        mesh=None traces ALSO disable the constraints: the single-device
        engine never wants hybrid-mesh activation constraints either,
        and a live fleet group in the same process (training + serving,
        or a group leaked by an earlier test module) otherwise pins the
        decode traces to the training mesh — observed as WRONG decode
        outputs, not a trace error. In a clean process hcg is None and
        _constrain is already a no-op, so single-device behavior is
        unchanged."""
        from ..distributed.mp_layers import no_sharding_constraints
        if self.mesh is None:
            return no_sharding_constraints()
        from ..ops.pallas.paged_attention import head_sharding
        ctx = contextlib.ExitStack()
        ctx.enter_context(head_sharding(self.mesh, self._mesh_axis))
        ctx.enter_context(no_sharding_constraints())
        return ctx

    def _fuse_ctx(self):
        """Trace-time fused-kernel routing (r13): under ``fused_step``
        the traced body's paged-attention calls fold their epilogue
        into `paged_attention_fused` (models/gpt.py fused_decode);
        fused_step=False returns a null context so the trace is
        byte-for-byte the pre-r13 program."""
        if not self.fused_step:
            return contextlib.nullcontext()
        from ..models.gpt import fused_decode
        return fused_decode()

    def _fused_head(self):
        """``(weight, transpose_y, bias)`` of a streamable lm_head, or
        None when fusion is off or the model's head is not a plain fp
        matmul (callers then keep the exact unfused logits path).
        Evaluated INSIDE the traced body under bind_state, so the
        weights are the jit's ARGUMENTS, never closure constants, and
        a post-construction conversion (int8) re-decides at the
        retrace the new state pytree forces."""
        if not self.fused_step:
            return None
        if not hasattr(self.model, "decode_hidden"):
            return None
        hp = getattr(self.model, "head_params", None)
        return None if hp is None else hp()

    def _record_programs(self, kind: str, count: int) -> None:
        """Record a (re)trace's program op count; the compiled fast
        path counts zero and keeps the last traced figure. Every call
        is also one program LAUNCH of ``kind`` — the step timeline's
        per-kind launch currency (r16)."""
        if count:
            self.step_programs[kind] = count
        self.programs_launched[kind] = \
            self.programs_launched.get(kind, 0) + 1
        self._tl_programs[kind] = self._tl_programs.get(kind, 0) + 1

    # -- end-to-end tracing hooks (r16) -------------------------------------
    #
    # Every hook is a `req.trace is None` check when tracing is off —
    # the ~zero-cost contract. Stage spans (queue -> prefill -> decode)
    # live on req.span; per-step work is appended as pre-timed closed
    # spans (RequestTrace.add), so the per-slot cost of a traced step
    # is one list append, with no extra clock reads per slot.

    def _tr_end(self, req: DecodeRequest, **args) -> None:
        """Close the request's current lifecycle-stage span (no-op for
        unsampled requests); stage OPENS stay at their sites, where
        the stage-specific args live."""
        tr = req.trace
        if tr is not None and req.span is not None:
            tr.end(req.span, **args)
            req.span = None

    # -- page ledger + per-request page attribution (r18) -------------------

    def _led(self, reason: str, req_id: Optional[int] = None):
        """Ledger reason context for a page-moving code path (no-op
        null context with the ledger off)."""
        if self.ledger is None:
            return contextlib.nullcontext()
        return self.ledger.why(reason, req_id)

    def ledger_tail(self, n: int = 256) -> List[Dict[str, Any]]:
        """The ledger ring's most recent events (flight bundles and
        the server's ``capacity`` op); [] with the ledger off."""
        return [] if self.ledger is None else self.ledger.tail(n)

    def _account_req_pages(self, req: DecodeRequest,
                           now: Optional[float] = None) -> None:
        """Fold the request's CURRENT private page holding into its
        peak-pages / page-seconds attribution. Called at admission,
        once per engine step (_tl_commit), and right before the final
        free, so one-step requests still record their peak."""
        owned = len(self.allocator._owned.get(req.req_id, ()))
        st = req.stats
        if owned > st.peak_pages:
            st.peak_pages = owned
        now = time.monotonic() if now is None else now
        last = getattr(req, "_pages_t", None)
        if last is not None and owned:
            st.page_seconds += owned * max(0.0, now - last)
        req._pages_t = now

    def capacity_snapshot(self) -> Dict[str, Any]:
        """Point-in-time capacity card (the server's ``capacity`` op
        and flight bundles): pool occupancy by owner class (sums to
        num_pages), spill-tier residency, and ledger stats. Host-side
        ints only — safe from any thread, like the health gauges."""
        occ = self.allocator.occupancy()
        out: Dict[str, Any] = {
            "num_pages": int(self.num_pages),
            "page_size": int(self.page_size),
            "occupancy": occ,
            "used_fraction": round(
                1.0 - occ["free"] / self.num_pages, 4)
            if self.num_pages else 0.0,
            "steps": int(self.steps),
            "forecast_admission": bool(self.forecast_admission),
            "forecast_denials": int(self.forecast_denials),
        }
        pc = self._prefix_cache
        evictable = 0
        if pc is not None:
            # refcount-0 cache pages are reclaimed on demand at every
            # admission (evict_until) — a warm inclusive cache
            # legitimately fills the pool, so the PRESSURE-relevant
            # figure is the unreclaimable remainder, not raw used
            for _ in range(3):  # conn-thread read vs engine mutation
                try:
                    evictable = int(pc.evictable_pages())
                    break
                except RuntimeError:
                    continue
        out["evictable_pages"] = evictable
        out["unreclaimable_pages"] = max(
            0, self.num_pages - occ["free"] - evictable)
        out["unreclaimable_fraction"] = round(
            out["unreclaimable_pages"] / self.num_pages, 4) \
            if self.num_pages else 0.0
        if pc is not None and getattr(pc, "tiers", None):
            for t in pc.tiers:
                out[f"{t.name}_tier_pages"] = int(t.blob_count)
                out[f"{t.name}_tier_bytes"] = int(t.occupancy_bytes)
        if self.ledger is not None:
            out["ledger"] = self.ledger.stats()
        return out

    # -- step timeline + program cost capture (r16) -------------------------

    def _tl_commit(self, t_step: float) -> None:
        """Append one fixed-size step-timeline record (bounded ring).

        Besides occupancy and page pressure a record says where the
        stepping thread's time went, all on ``time.monotonic``:

        - ``ms``: the whole step, from its start (``t_us``) to the
          start of this commit.
        - ``host_us``: ``{phase: µs}`` of the phases inside ``ms``
          (HOST_PHASES: ``admit``, ``upload``, ``launch``, ``wait``,
          ``emit``) and ``other``, what they leave of ``ms``. Phases
          never overlap. ``launch`` is every jit CALL (it returns
          futures), ``wait`` every blocking read of a device result.
        - ``phases``: the same phases as the stretches they ran in, in
          order: ``[name, start_us, us]`` with ``start_us`` counted
          from ``t_us``, one entry for every stretch (a phase paused
          by an inner one leaves two), inside ``[0, ms]``, disjoint,
          and summed by name equal to ``host_us`` to the rounding
          (``other`` is what they leave). A ``launch`` entry carries
          a fourth element, the kind of program it dispatched, under
          the name ``programs`` counts it by (``prefill``,
          ``prefill_chained`` — a chunk is one of the two —,
          ``decode``, ``verify``, ``restore``; ``spill`` for the
          gather of a page on its way to a spill tier, which
          ``programs`` does not count), and a ``wait`` entry the kind
          it fetched. ``commit`` and ``loop`` need no entry: they are
          ``[t_us + ms, + commit_us]`` and ``[t_us - gap_us, t_us]``.
          A sum says how long; only a place on the clock can be laid
          against the device's idle gaps (benchmarks/host_clock.py
          ties the trace's clock to this one from the ``launch`` and
          ``wait`` entries and the programs they bracket).
        - ``commit_us``: this method itself (page accounting for every
          slot, ``occupancy()``, the record) — what the always-on
          timeline costs. It runs after ``ms`` is taken.
        - ``gap_us``: end of the previous record's commit to this
          step's start — the caller's loop between two steps (the
          server's inbox drain and swap check; also its sleep when
          the previous step left nothing to do).
        - ``cpu_us``: the stepping thread's CPU time
          (``time.thread_time_ns``) over gap + step + commit; left out
          when the previous step ran on another thread.

        - ``decode_h2d``: on a record whose step LAUNCHED the
          single-step decode program, the host-to-device transfers of
          its inputs: 0 where the program was fed its own outputs, 1
          where a host write (admission, first token, finish,
          eviction), a failed step or a half-prefilled slot made the
          step send the mirrors. ``flight_summary()`` keeps the totals
          (``decode_steps_resident``, ``decode_steps_uploaded``). The
          speculative and chunk programs build their arguments from
          the mirrors on every launch and record no such key.
        - ``decode_ahead``: beside ``decode_h2d``, 1 where that program
          was launched while the previous one's tokens were still
          unfetched (the look-ahead: the device goes from one program
          to the next with no gap), else 0; the total is
          ``decode_steps_ahead``.

        **A step launched in one ``step()`` call and settled in the
        next** (_decode_step): a record describes one CALL. Its
        ``programs``, ``decode_h2d``, ``decode_ahead`` and
        ``decode_ms`` belong to the program the call launched; its
        ``wait`` and ``emit``, the tokens it handed out, ``step`` and
        the model's counters belong to the program it settled, which
        the call before it launched (the same program only on a masked
        step, which is settled where it is launched). ``ms`` is the
        call, whatever it held: in the steady state one launch and one
        settle, so its median is still the time from one handed-out
        token to the next less commit and loop. A traced request's
        ``decode_step`` span carries the launch stamps of the program
        that computed its token. ``decode_ema_s`` averages, per
        settled step, the time from its launch (or from the end of the
        settle before it, where that is later: a step launched ahead)
        to the end of its own settle: the cadence at which a slot
        receives tokens, which is what the deadline gate multiplies.

        The older ``_tl_ms`` keys are fed from the same stamps:
        ``decode_ms`` is the decode program's ``launch`` phase on the
        single-step path — the DISPATCH, not the decode (on a device
        the call returns before the program has run; the device's
        time is in ``wait``); ``prefill_ms`` is upload + launch + the
        blocking read of the first token; ``chunk_ms`` / ``verify_ms``
        are upload + launch of those programs, ``splice_ms`` the
        launch."""
        with self._phase("commit") as commit:
            entry = self._tl_record(t_step, commit.t0)
        end = commit.t1
        entry["commit_us"] = round((end - commit.t0) * 1e6, 1)
        ident, cpu = threading.get_ident(), time.thread_time_ns()
        if self._tl_cpu[0] == ident:
            entry["cpu_us"] = round((cpu - self._tl_cpu[1]) * 1e-3, 1)
        self._tl_end, self._tl_cpu = end, (ident, cpu)

    def _tl_record(self, t_step: float, now: float) -> Dict[str, Any]:
        # per-request page attribution (r18): one pass over the slots
        # per STEP (never per token) keeps peak-pages/page-seconds
        # current for long-running requests
        for r in self._slots:
            if r is not None:
                self._account_req_pages(r, now)
        us, segs = self._host.take()
        host = {k: round(v * 1e6, 1) for k, v in us.items()}
        host["other"] = round((now - t_step) * 1e6 - sum(host.values()), 1)
        # the same stretches with their places: both ends rounded on
        # the step's own scale, so neighbours that share a stamp still
        # touch and none overlaps
        phases = []
        for name, a, b, kind in segs:
            a = round((a - t_step) * 1e6, 1)
            us = round(round((b - t_step) * 1e6, 1) - a, 1)
            phases.append((name, a, us) if kind is None
                          else (name, a, us, kind))
        entry: Dict[str, Any] = {
            "step": self.steps,
            "t_us": t_step * 1e6,
            "ms": round((now - t_step) * 1e3, 4),
            "host_us": host,
            "phases": phases,
            "gap_us": 0.0 if self._tl_end is None
            else round((t_step - self._tl_end) * 1e6, 1),
            "programs": self._tl_programs,
            "slots_active": self.num_active,
            "slots_decoding": sum(
                1 for r in self._slots
                if r is not None and r.state == "decoding"),
            "queued": len(self._queue),
            "free_pages": self.allocator.free_count,
            "reserved_pages": self.allocator.reserved_total,
            # capacity timeline (r18): pool breakdown by owner class
            # (inflight/prefix_device/reserved/free — sums to the pool
            # size); the capacity op's forecast reads the free deltas
            "occupancy": self.allocator.occupancy(),
        }
        pc = self._prefix_cache
        if pc is not None and getattr(pc, "tiers", None):
            for t in pc.tiers:
                entry[f"{t.name}_tier_pages"] = int(t.blob_count)
        for k, v in self._tl_ms.items():
            entry[k] = round(v, 4)
        if self._tl_decode is not None:
            entry["decode_h2d"], entry["decode_ahead"] = self._tl_decode
        if not self._plain_layout:
            # pages in use by kind of layer, a layer of each: the
            # allocator's (every position kept: K/V pages or latent
            # rows) and the rings' (a sequence never holds more than
            # its ring)
            entry["kv_pages"] = {
                "global": self.num_pages - entry["free_pages"]}
            if self._has_rings:
                entry["kv_pages"]["window"] = self.window_pages_in_use()
        if self._has_state:
            # rows of the state pool that hold a live sequence
            entry["state_slots"] = entry["slots_active"]
        # the model's own counters of this step's programs
        entry.update(self._tl_stats)
        self.timeline.append(entry)
        return entry

    def window_pages_in_use(self) -> int:
        """Ring pages that hold a live sequence's keys, in one window
        layer: ``min(ring, ceil(len / page))`` over the active slots."""
        ring = max((r for r in self._rings if r is not None), default=0)
        return sum(min(ring, -(-int(self._lens[i]) // self.page_size))
                   for i, r in enumerate(self._slots) if r is not None)

    def step_timeline(self) -> List[Dict[str, Any]]:
        """Snapshot of the per-step ring (oldest first) — the server's
        ``trace``/``stats`` ops and the goodput bench read this."""
        return list(self.timeline)

    def flight_summary(self) -> Dict[str, Any]:
        """JSON-safe engine state card for the crash flight recorder
        (r17): the numbers a postmortem wants next to the timeline
        ring — occupancy, page pressure, EMAs, launch totals, and the
        feature flags that shaped the traced programs. Host-side ints
        and floats only; safe to call from a dying engine."""
        return {
            "steps": int(self.steps),
            "num_slots": int(self.num_slots),
            "num_active": int(self.num_active),
            "num_queued": int(self.num_queued),
            "num_pages": int(self.num_pages),
            "free_pages": int(self.free_pages),
            "reserved_pages": int(self.allocator.reserved_total),
            "page_size": int(self.page_size),
            "max_seq_len": int(self.max_seq_len),
            "decode_ema_ms": (None if self.decode_ema_s is None
                              else round(self.decode_ema_s * 1e3, 3)),
            "prefill_chunk_ema_ms": (
                None if self.prefill_chunk_ema_s is None
                else round(self.prefill_chunk_ema_s * 1e3, 3)),
            "prefill_debt_tokens": int(self.prefill_debt_tokens),
            "prefill_chunk_tokens": self.prefill_chunk_tokens,
            "fused_step": bool(self.fused_step),
            "decode_steps_resident": int(self.decode_steps_resident),
            "decode_steps_uploaded": int(self.decode_steps_uploaded),
            "decode_steps_ahead": int(self.decode_steps_ahead),
            "decode_rows_dropped": int(self.decode_rows_dropped),
            "state_pool_bytes": int(self.state_pool_bytes),
            "latent_pool_bytes": int(self.latent_pool_bytes),
            "state_rows_overwritten": int(self.state_rows_overwritten),
            "prefill_positions": int(self.prefill_positions),
            "prefill_positions_padded": int(self.prefill_positions_padded),
            "model_counters": {k: dict(v) for k, v in
                               self.model_counters.items()},
            "window_ring_pages": max(
                (r for r in self._rings if r is not None), default=None),
            "speculative": self._spec_cfg is not None,
            "mesh": self.mesh_info(),
            "programs_launched": dict(self.programs_launched),
            "step_programs": dict(self.step_programs),
            "ledger_events": (None if self.ledger is None
                              else int(self.ledger.seq)),
        }

    def _tl_add_ms(self, key: str, seconds: float) -> None:
        self._tl_ms[key] = self._tl_ms.get(key, 0.0) + seconds * 1e3

    def _phase_seconds(self, *names: str) -> float:
        """Seconds this step has spent in the named phases so far: a
        difference of two readings is an interval on the phases' own
        stamps, with no clock read of its own."""
        us = self._host.us
        return sum(us.get(n, 0.0) for n in names)

    def _capture_cost(self, kind: str, jitfn, args: Tuple) -> None:
        """Capture flops / bytes-accessed estimates for ``kind`` from
        ``jit.lower(...).cost_analysis()`` on stub avals (no compile,
        no execution), and which Pallas kernels the lowered program
        holds (``pallas_kernels``: name -> call sites; empty where
        every gate picked its reference) — once per program kind, at
        (re)trace time, on
        the ENGINE thread (bind_state substitution is process-global,
        so a scrape thread must never trace the model concurrently).
        These feed the serving_program_* gauges that replace the r10
        ``serving_mesh_collective_bytes`` 0-stub; the chip-MEASURED
        collective traffic still needs an on-chip profiler session
        (chip-pending, as before)."""
        if not self._capture_costs or kind in self._program_costs:
            return
        import jax

        def stub(x):
            if hasattr(x, "shape") and hasattr(x, "dtype"):
                sh = getattr(x, "sharding", None)
                if sh is not None and self.mesh is not None:
                    # host-side args (page table, lens, tokens) land
                    # on ONE device in the live call and jax replicates
                    # them; an abstract lower() has no auto-placement,
                    # so stub them replicated over the mesh or the
                    # mixed device sets fail the lowering
                    try:
                        if len(sh.device_set) == 1:
                            from jax.sharding import (NamedSharding,
                                                      PartitionSpec)
                            sh = NamedSharding(self.mesh,
                                               PartitionSpec())
                    except Exception:
                        sh = None
                try:
                    return jax.ShapeDtypeStruct(x.shape, x.dtype,
                                                sharding=sh)
                except TypeError:
                    return jax.ShapeDtypeStruct(x.shape, x.dtype)
            return x

        try:
            stubs = jax.tree_util.tree_map(stub, args)
            lowered = jitfn.lower(*stubs)
            ca = lowered.cost_analysis()
            if isinstance(ca, (list, tuple)):
                ca = ca[0] if ca else {}
            cost: Dict[str, Any] = {
                "pallas_kernels": dict(collections.Counter(re.findall(
                    r'kernel_name\s*=\s*"([^"]+)"',
                    lowered.as_text())))}
            if ca:  # None on the TPU backend: no cost model there
                # for a module that is not compiled yet
                cost["flops"] = float(ca.get("flops") or 0.0)
                cost["bytes_accessed"] = float(
                    ca.get("bytes accessed") or 0.0)
            self._program_costs[kind] = cost
            if self.mesh is not None:
                # the partitioner inserts collectives at COMPILE time;
                # the launch that just ran left this program in the
                # compile cache, so this is a cache read
                from ..distributed.topology import collectives_in
                self._program_costs[kind]["collectives"] = \
                    collectives_in(lowered.compile().as_text())
        except Exception as e:  # cost capture must never break a step
            self._program_costs[kind] = {
                "error": f"{type(e).__name__}: {e}"}

    def program_costs(self) -> Dict[str, Dict]:
        """Per-program-kind cost estimates captured so far (empty
        until the first traced launch, or with capture off)."""
        return dict(self._program_costs)

    def mesh_collective_bytes_estimate(self) -> Optional[float]:
        """Estimated per-decode-step collective traffic under the
        serving mesh (None = single-device): the mp-partitioned
        contractions all-reduce their partial sums — 2 row-parallel
        reductions per layer (attention out-projection + MLP
        down-projection) plus the sampled-head reduction — and a ring
        all-reduce moves ``2 * (mp-1)/mp`` of the tensor bytes per
        device. The per-program flops/bytes figures come from
        ``program_costs`` (cost_analysis); the chip-MEASURED value
        remains chip-pending (xprof collective stats)."""
        if self.mesh is None:
            return None
        mp = int(self.mesh.shape[self._mesh_axis])
        if mp <= 1:
            return 0.0
        import numpy as _np
        itemsize = _np.dtype(self._kv_dtype).itemsize
        act = self.num_slots * int(self.cfg.hidden_size) * itemsize
        return float((2 * self._nl + 1) * act * 2 * (mp - 1) / mp)

    def _constrain_pools(self, pools):
        """Pin the returned pools to the engine's KV sharding (heads
        over the model axis; scales drop the trailing head-dim axis).
        Without this GSPMD is free to pick a different output layout,
        which would make the next step's donated inputs mismatch the
        compiled program and ping-pong the jit cache."""
        if self.mesh is None:
            return pools
        import jax
        # ONE definition of the KV layout: the same sharding the pools
        # were created under in __init__ (heads over the model axis —
        # P(None, None, mp) hits dim 2, the head dim of both the 4-D
        # pools and the 3-D scale pools)
        spec = self._kv_sharding

        def pin(xs):
            return [None if x is None
                    else jax.lax.with_sharding_constraint(x, spec)
                    for x in xs]

        return dict(pools, k=pin(pools["k"]), v=pin(pools["v"]),
                    ks=pin(pools["ks"]), vs=pin(pools["vs"]))

    # -- spill-tier device IO (r15) -----------------------------------------

    def _read_page(self, page: int) -> List[Tuple]:
        """Copy one pool page device→host for the prefix cache's spill
        tier: per layer (k, v, k_scale, v_scale) numpy blocks. Runs at
        eviction time on the engine thread; indexing the live pools is
        a read, so the donated buffers are untouched. The per-layer
        slices are stacked in ONE jitted gather so the spill costs one
        launch plus one transfer per pool KIND — not 2-4 sequential
        device round-trips per LAYER (the batched-splice discipline,
        applied to the read side)."""
        import jax

        jnp = self._jnp
        if self._gather_jit is None:
            def gather(pools, pg):
                k = jnp.stack([p[pg] for p in pools["k"]])
                v = jnp.stack([p[pg] for p in pools["v"]])
                ks = vs = None
                if self.kv_int8:
                    ks = jnp.stack([p[pg] for p in pools["ks"]])
                    vs = jnp.stack([p[pg] for p in pools["vs"]])
                return k, v, ks, vs

            self._gather_jit = jax.jit(gather)
        if self.ledger is not None:
            # spill-side device IO: the page's KV is leaving the
            # device for a spill tier (the cache decides which)
            self.ledger.record("spill", None, pages=[int(page)])
        with self._phase("launch", "spill"):
            k, v, ks, vs = self._gather_jit(
                self._pools, jnp.asarray(page, jnp.int32))
        with self._phase("wait", "spill"):
            k, v = np.asarray(k), np.asarray(v)
            ks = None if ks is None else np.asarray(ks)
            vs = None if vs is None else np.asarray(vs)
        return [(k[i], v[i],
                 None if ks is None else ks[i],
                 None if vs is None else vs[i])
                for i in range(self._nl)]

    def _splice_page(self, pages: Sequence[int],
                     layers_list: Sequence[Sequence[Tuple]]) -> None:
        """Restore a run of spilled pages in ONE batched device call:
        stack the per-page/per-layer host blocks and scatter them into
        every pool through a single jitted donate-in-place program
        (models/gpt.py paged_page_splice). The page indices are
        traced, and the batch is padded to a power-of-two bucket
        targeting the SCRATCH page (whose content is garbage by
        contract — masked writes land there every step), so the jit
        compiles once per bucket size, not once per restore shape.
        This is the whole restore-vs-reprefill trade: one device_put
        plus one scatter launch against the suffix prefill it
        replaces."""
        import jax

        jnp = self._jnp
        n = len(pages)
        nb = 1
        while nb < n:
            nb *= 2
        pad = nb - n

        def stack(idx):
            blocks = [np.stack([layers[i][idx] for layers in
                                layers_list]) for i in range(self._nl)]
            out = np.stack(blocks)            # [nl, n, page, ...]
            if pad:
                z = np.zeros(out.shape[:1] + (pad,) + out.shape[2:],
                             out.dtype)
                out = np.concatenate([out, z], axis=1)
            return out

        k, v = stack(0), stack(1)
        ks = vs = None
        if self.kv_int8:
            ks, vs = stack(2), stack(3)
        page_idx = np.asarray(list(pages) + [self._scratch] * pad,
                              np.int32)
        if self._splice_jit is None:
            from ..models.gpt import paged_page_splice

            def splice(pools, pg, kb, vb, ksb, vsb):
                with jax.named_scope("pt.page_splice"):
                    # the K/V pools of a plain layout; what else the
                    # pools hold (no state layer here) passes through
                    spliced = paged_page_splice(pools, pg, kb, vb, ksb, vsb)
                    return self._constrain_pools(dict(pools, **spliced))

            self._splice_jit = jax.jit(splice, donate_argnums=(0,))
        from ..dispatch import count_op_calls
        if self.ledger is not None:
            # restore-side device IO: one batched splice writes the
            # whole contiguous run (padding targets scratch, excluded)
            self.ledger.record("splice", None,
                               pages=[int(p) for p in pages])
        with self._phase("upload"):
            args = (self._pools, jnp.asarray(page_idx), k, v, ks, vs)
        with self._phase("launch", "restore") as launch:
            with count_op_calls() as c:
                self._pools = self._splice_jit(*args)
        self._tl_add_ms("splice_ms", launch.t1 - launch.t0)
        self._record_programs("restore", c.count)
        if c.count:
            self._capture_cost("restore", self._splice_jit, args)

    def mesh_info(self) -> Optional[Dict[str, Any]]:
        """Mesh observability record (server stats / Prometheus):
        None when single-device, else axis sizes + device count."""
        if self.mesh is None:
            return None
        info = {"axes": {str(a): int(self.mesh.shape[a])
                         for a in self.mesh.axis_names},
                "model_parallel": int(self.mesh.shape[self._mesh_axis]),
                "devices": int(self.mesh.size),
                "model_axis": self._mesh_axis}
        if self._state_cache is not None:
            # where the weights really are (filled at first admission):
            # `split` counts leaves that are NOT fully replicated,
            # `pspec_split` those whose pspec names the model axis —
            # equal when the placement follows the annotations
            placed = [(v, self._state_shardings[kind][name])
                      for kind, grp in self._state_cache.items()
                      for name, v in grp.items()]
            info["weights"] = {
                "leaves": len(placed),
                "split": sum(not v.sharding.is_fully_replicated
                             for v, _ in placed),
                "pspec_split": sum(not want.is_fully_replicated
                                   for _, want in placed),
                "on_all_devices": all(
                    len(v.sharding.device_set) == self.mesh.size
                    for v, _ in placed)}
        return info

    def _new_pools(self, nc):
        """The pools a traced program hands back, from the caches the
        model returned."""
        def take(name, only=True):
            return [_raw(getattr(c, name)) if only and hasattr(c, name)
                    else None for c in nc]
        # a latent layer's one pool rides under "k"
        k = [a if a is not None else b
             for a, b in zip(take("k_pages"), take("pages"))]
        pools = self._constrain_pools({
            "k": k, "v": take("v_pages"),
            "ks": take("k_scale", self.kv_int8),
            "vs": take("v_scale", self.kv_int8)})
        return dict(pools, state=take("state"), tail=take("tail"))

    def _build_decode(self):
        """The per-token decode program over the packed inputs
        ``[num_slots, max_pages + 2]`` (page table | length | current
        token; the layout of ``_packed``). It returns the tokens, the
        pools and the NEXT step's packed inputs, so a step the host did
        not touch feeds the program its own output and uploads nothing
        (_launch_decode). The model returns ``lens + 1`` for every row,
        and a row that came in empty (length 0: an empty or masked
        slot, writing to the scratch page) goes out empty, or its
        length would creep from step to step. The token such a row
        yields is an in-range argmax nobody reads; admission
        overwrites it."""
        import jax
        import jax.numpy as jnp

        from ..autograd.engine import no_grad
        from ..nn.decode import sample_token
        from ..nn.layer import bind_state
        from ..tensor import Tensor

        mp = self.max_pages
        replicated = self._replicated

        def step(state, pools, packed):
            table, lens = packed[:, :mp], packed[:, mp]
            tokens = packed[:, mp + 1]
            caches = self._caches(pools, table, lens)
            # named_scope: metadata-only, UNCONDITIONAL (never keyed on
            # tracing state, so programs are identical tracing on/off)
            # — serving steps show up inside jax.profiler device traces
            with jax.named_scope("pt.decode_step"), self._head_ctx(), \
                    self._fuse_ctx(), \
                    bind_state(self.model, state), no_grad():
                hp = self._fused_head()
                if hp is not None:
                    # fused hot path (r13): hidden -> streaming lm_head
                    # argmax; the [B, vocab] logits never materialize
                    from ..nn.decode import fused_sample_token
                    hidden, nc = self.model.decode_hidden(
                        Tensor(tokens[:, None]), caches)
                    w, ty, bias = hp
                    nxt, _ = fused_sample_token(
                        _raw(hidden)[:, -1], _raw(w), 0.0,
                        transpose_y=ty,
                        bias=None if bias is None else _raw(bias))
                else:
                    logits, nc = self.model.forward(
                        Tensor(tokens[:, None]), caches=caches)
                    # greedy serving mode through the ONE shared
                    # sampler (nn/decode.py) — the same call generate()
                    # and the speculative verify make
                    nxt, _ = sample_token(_raw(logits)[:, -1], 0.0)
                self._take_stats()
            pools = self._new_pools(nc)
            lens_new = jnp.where(lens > 0, _raw(nc[0].seq_lens), 0)
            packed = jnp.concatenate(
                [table, lens_new[:, None], nxt[:, None]], axis=1)
            if replicated is not None:
                # the layout _place_resident uploads in: what goes
                # back in hits the same compiled program
                packed = jax.lax.with_sharding_constraint(packed,
                                                          replicated)
            return self._pack_stats("decode", nxt), pools, packed

        # donate the pools: the append scatters then update the pool
        # buffers IN PLACE instead of materializing a fresh copy of
        # every per-layer pool each token (~GBs/step at serving scale,
        # plus 2x peak KV memory); the engine always adopts the
        # returned pools, so the donated buffers are never reused.
        # (On CPU donation is ignored with a warning — harmless.)
        return jax.jit(step, donate_argnums=(1,))

    def _build_prefill(self, chained: bool):
        """One jitted prefill; jax.jit's shape-keyed cache compiles it
        once per prompt bucket (the bucket IS the ids shape). The
        ``chained`` variant starts from a non-empty slot (seq_lens =
        the prefix-cache hit length) and attends the stored prefix
        through the paged-attention reference (models/gpt.py
        prefill_chained); the fresh variant keeps the exact dense
        chunk-attention program the bit-identical tests pin."""
        import jax

        from ..autograd.engine import no_grad
        from ..nn.decode import sample_token
        from ..nn.layer import bind_state
        from ..tensor import Tensor

        def prefill(state, pools, trow, slens, plen, ids, rows=None):
            caches = self._caches(pools, trow, slens, rows)
            with jax.named_scope(
                    "pt.prefill_chained" if chained else "pt.prefill"), \
                    self._head_ctx(), self._fuse_ctx(), \
                    bind_state(self.model, state), no_grad():
                hp = self._fused_head()
                if hp is not None:
                    # fused (r13): sample the first token straight from
                    # the last VALID hidden row — the [1, bucket, vocab]
                    # prefill logits tensor never materializes
                    from ..nn.decode import fused_sample_token
                    hidden, nc = self.model.decode_hidden(
                        Tensor(ids), caches, prefill_lens=plen,
                        prefill_chained=chained)
                    w, ty, bias = hp
                    nxt, _ = fused_sample_token(
                        _raw(hidden)[:1, plen[0] - 1], _raw(w), 0.0,
                        transpose_y=ty,
                        bias=None if bias is None else _raw(bias))
                else:
                    logits, nc = self.model.forward(
                        Tensor(ids), caches=caches, prefill_lens=plen,
                        prefill_chained=chained)
                    nxt, _ = sample_token(_raw(logits)[:1, plen[0] - 1],
                                          0.0)
                self._take_stats()
            nxt = self._pack_stats(
                "prefill_chained" if chained else "prefill", nxt[0])
            return nxt, self._new_pools(nc)

        return jax.jit(prefill, donate_argnums=(1,))

    def _get_prefill(self, chained: bool):
        if self._prefill_jits.get(chained) is None:
            self._prefill_jits[chained] = self._build_prefill(chained)
        return self._prefill_jits[chained]

    def _build_verify(self):
        """ONE jitted speculative verify step for the engine's whole
        lifetime (fixed [num_slots, k+1] shape): append the pending
        token + k drafts through the page tables (ragged per-slot
        valid counts park the tail on the scratch page), score all
        k+1 positions via models/gpt.py ``verify_step`` (the chained-
        prefill q_offsets paged-attention path), and compute the
        accept/resample decisions with nn/decode.py's shared sampler
        math. Lengths stay host-owned: the host rolls back past the
        longest accepted prefix, so rejected positions are simply
        never attended again."""
        import jax

        from ..autograd.engine import no_grad
        from ..nn.decode import speculative_verify_tokens
        from ..nn.layer import bind_state
        from ..tensor import Tensor

        temp = float(self._spec_cfg.temperature)
        tk = self._spec_cfg.top_k

        def verify(state, pools, table, lens, tokens, valid, key):
            caches = self._caches(pools, table, lens)
            with jax.named_scope("pt.verify_step"), self._head_ctx(), \
                    self._fuse_ctx(), \
                    bind_state(self.model, state), no_grad():
                hp = self._fused_head()
                if hp is not None:
                    # one-program fused verify (r13): the k+1-position
                    # scoring runs through the fused attention epilogue
                    # and the accept/resample decisions stream through
                    # the lm_head per position (nn/decode.py
                    # fused_verify_tokens) — draft scoring AND
                    # acceptance in the same fused program, with no
                    # [B, k+1, vocab] logits tensor on the greedy path
                    from ..nn.decode import fused_verify_tokens
                    hidden, nc = self.model.decode_hidden(
                        Tensor(tokens), caches, prefill_lens=valid,
                        prefill_chained=True)
                    w, ty, bias = hp
                    accept, resid, full, _ = fused_verify_tokens(
                        _raw(hidden), tokens[:, 1:], _raw(w), temp, tk,
                        key, transpose_y=ty,
                        bias=None if bias is None else _raw(bias))
                else:
                    logits, nc = self.model.verify_step(Tensor(tokens),
                                                        caches, valid)
                    accept, resid, full, _ = speculative_verify_tokens(
                        _raw(logits), tokens[:, 1:], temp, tk, key)
            return accept, resid, full, self._new_pools(nc)

        return jax.jit(verify, donate_argnums=(1,))

    def _unwind_prefill_failure(self, slot: int, req: DecodeRequest
                                ) -> None:
        """Shared unwind for a FAILED prefill launch — the whole
        prefill at admission or any chunk of a chunked prefill: free
        the pages and any speculative reservation, drop the
        prefix-cache pins, park the slot, and requeue at the head for
        a from-scratch retry — or FAIL typed once max_prefill_attempts
        accumulated, so a persistent fault can't wedge the queue head
        forever. A strict superset of what the whole-prefill path
        needs (its slot was never committed: lens/cur are still 0 and
        the _slots entry still None — re-clearing them is a no-op), so
        both leak-critical paths stay in sync by construction."""
        with self._led("prefill_unwind", req.req_id):
            self.allocator.free(req.req_id)
            if self._prefix_cache is not None and req.cache_keys:
                self._prefix_cache.release(req.cache_keys)
        req.cache_keys = ()
        req.prefill_done_len = 0
        self._write_slot(slot, table=self._scratch, lens=0, cur=0)
        self._slots[slot] = None
        req.slot = None
        req.stats.prefill_attempts += 1
        if req.stats.prefill_attempts >= self.max_prefill_attempts:
            req.state = "failed"
            req.done = True
            req.stats.finish_t = time.monotonic()
            self._notify_complete(req)
        else:
            req.state = "queued"
            # a requeued request is queued again: close any stage span
            # (the chunked-mode "prefill") and reopen "queue" so the
            # tree mirrors the real lifecycle
            self._tr_end(req, state="prefill_failed")
            if req.trace is not None:
                req.span = req.trace.begin(
                    "queue", parent=req.trace.anchor,
                    retry=req.stats.prefill_attempts)
            self._queue.insert(0, req)

    def _check_pools_live(self, what: str) -> None:
        """Donated-buffer guard shared by every retrying jit call site
        (prefill, chunk prefill, verify): if an earlier attempt failed
        AFTER execution began, the donated pools are gone — a retry
        would feed the jit dead buffers. Surface a terminal
        (non-transient) error instead of a confusing backend one."""
        k0 = next(x for kind in ("k", "state") for x in self._pools[kind]
                  if x is not None)
        if getattr(k0, "is_deleted", None) is not None \
                and k0.is_deleted():
            raise RuntimeError(
                f"KV pool buffers were consumed by a failed donating "
                f"{what}; engine state is unrecoverable — rebuild "
                f"the engine")

    # -- scheduler ---------------------------------------------------------

    def _bucket(self, n: int) -> int:
        for b in self.prompt_buckets:
            if n <= b:
                return b
        return self.prompt_buckets[-1]

    def _fits(self, req: DecodeRequest) -> bool:
        """Could this request be admitted right now? Free pages plus
        whatever the prefix cache could evict — EXCLUDING the entries
        this request's own prefix match would pin (counting those as
        evictable made _fits optimistic: admission then pinned them,
        the allocation failed, and the scheduler charged phantom
        bypasses for an admission that never happened). ``match`` memoizes
        the chain hash on the request, so per-step fits checks cost
        dict lookups, not re-hashing the prompt."""
        capacity = len(req.prompt) + req.max_new_tokens
        need = -(-capacity // self.page_size)
        avail = self.allocator.free_count
        if self._prefix_cache is not None:
            keys, shared = self._prefix_cache.match(req.prompt, memo=req)
            need -= len(shared)
            avail += self._prefix_cache.evictable_pages(excluding=keys)
        if need <= avail and self.forecast_admission:
            # byte planning (r23): also charge the fleet's forecast
            # page burn over this request's expected lifetime. The
            # r18 EWMA over the step-timeline's free_pages deltas
            # gives pages/s; the horizon is how long this request
            # will realistically hold its pages (max_new_tokens at
            # the decode EMA). A positive burn rate shrinks avail by
            # the pages the ALREADY-ADMITTED load will take in that
            # window — landing a request the instant books accept but
            # the forecast cannot carry is how pools thrash.
            from .page_ledger import forecast_exhaustion
            fc = forecast_exhaustion(self.step_timeline())
            rate = fc.get("rate_pages_per_s")
            if rate is not None and rate > 0 and \
                    self.decode_ema_s is not None:
                horizon_s = req.max_new_tokens * self.decode_ema_s
                burn = int(rate * horizon_s)
                if need > avail - burn:
                    self.forecast_denials += 1
                    return False
        return need <= avail

    def _partial_debt_by_class(self) -> Dict[int, int]:
        """In-flight prefill debt (un-stored suffix tokens of admitted
        half-prefilled slots) per priority class — the chunk-budget
        admission gate's input."""
        out: Dict[int, int] = {}
        for r in self._slots:
            if r is not None and r.state == "prefill_partial":
                rem = len(r.prompt) - r.prefill_done_len
                out[r.priority] = out.get(r.priority, 0) + rem
        return out

    def _debt_allows(self, req: DecodeRequest) -> bool:
        """Per-class prefill-debt admission gate (chunked mode with an
        SLO scheduler carrying ``max_prefill_debt_tokens``): don't turn
        every slot into half-prefilled work of one class — a stream of
        long BATCH prompts is admitted only while the class's in-flight
        debt stays under the cap. A class with ZERO in-flight debt is
        always admissible (the cap bounds concurrency, it must never
        lock a class out entirely)."""
        if self.prefill_chunk_tokens is None:
            return True
        cfg = getattr(self._scheduler, "cfg", None)
        cap = getattr(cfg, "max_prefill_debt_tokens", None)
        if cap is None:
            return True
        cur = self._partial_debt_by_class().get(req.priority, 0)
        if cur == 0:
            return True
        add = len(req.prompt)
        if self._prefix_cache is not None:
            _keys, shared = self._prefix_cache.match(req.prompt,
                                                     memo=req)
            add -= len(shared) * self.page_size
        return cur + add <= cap

    def _admissible(self, req: DecodeRequest) -> bool:
        return self._fits(req) and self._debt_allows(req)

    def _select_next(self) -> Optional[DecodeRequest]:
        if not self._queue:
            return None
        if self._scheduler is not None:
            idx = self._scheduler.select(self._queue, self._admissible,
                                         time.monotonic())
            return self._queue.pop(idx) if idx is not None else None
        # built-in FIFO: head or nothing (don't starve the head)
        if self._admissible(self._queue[0]):
            return self._queue.pop(0)
        return None

    def _shed_overloaded(self) -> List[DecodeRequest]:
        """Let the scheduler shed queued requests past their SLO (the
        typed-overload path); returns what was shed so callers (the
        server) can answer those clients."""
        if self._scheduler is None or not self._queue:
            return []
        doomed = self._scheduler.shed(self._queue, time.monotonic())
        now = time.monotonic()
        for req in doomed:
            self._queue.remove(req)
            req.state = "shed"
            req.done = True
            req.stats.finish_t = now
            self._notify_complete(req)
        return doomed

    def set_on_complete(self, fn: Optional[Callable[["DecodeRequest"],
                                                    None]]) -> None:
        """Swap the completion hook (e.g. attach metrics only after a
        warm-up batch so compile time doesn't pollute TTFT)."""
        self._on_complete = fn

    def _notify_complete(self, req: DecodeRequest) -> None:
        tr = req.trace
        if tr is not None:
            # EVERY terminal path funnels through here, so this is the
            # one place open stage spans close and the tree finishes —
            # the zero-leaked-open-spans contract. Resurrection
            # detaches req.trace BEFORE teardown, so a replayed
            # request's tree survives to be continued, not finished.
            self._tr_end(req, state=req.state)
            tr.event("complete", parent=tr.anchor, state=req.state,
                     tokens_out=len(req.generated),
                     req_id=req.req_id)
            tr._tracer.finish(tr, state=req.state)
        if self._on_complete is not None:
            self._on_complete(req)

    def _emit_token(self, req: DecodeRequest, tok: int) -> None:
        # fires BEFORE _maybe_finish so streamed tokens always precede
        # the completion notification; callbacks run on the engine
        # thread and must not raise — the server's callback catches
        # its own socket errors
        req.last_emit_t = time.monotonic()
        if req.on_token is not None:
            req.on_token(req.req_id, tok, self._finish_due(req))

    # -- typed mid-flight eviction (deadline / stall / replay) -------------

    def _evict_slot(self, slot: int, state: str) -> DecodeRequest:
        """Tear one active slot down with a typed terminal ``state``:
        return its pages AND any outstanding speculative reservation
        (`PageAllocator.free` drops both — the same unwinding the
        rejection-rollback machinery relies on), drop the prefix-cache
        pins, park the slot on the scratch page, and notify."""
        req = self._slots[slot]
        self._account_req_pages(req)
        if self.ledger is not None and state in ("stalled", "deadline"):
            # the stall/deadline unwind forensics (r18): snapshot the
            # pages' event history BEFORE the free below rewrites it —
            # the server's stall flight bundle and typed reply carry it
            req.page_forensics = self.ledger.history_for_owner(
                req.req_id)
        with self._led(state, req.req_id):
            self.allocator.free(req.req_id)
            if self._prefix_cache is not None and req.cache_keys:
                # for a half-prefilled slot these are the matched chain
                # pins acquired at admission (insert() never ran); for a
                # decoding slot, the full inserted chain — release() is
                # the right unwind for both
                self._prefix_cache.release(req.cache_keys)
                req.cache_keys = ()
        req.prefill_done_len = 0
        req.state = state
        req.done = True
        req.stats.finish_t = time.monotonic()
        req.stats.tokens_out = len(req.generated)
        self._write_slot(slot, table=self._scratch, lens=0, cur=0)
        self._slots[slot] = None
        self._notify_complete(req)
        return req

    def _terminate_queued(self, req: DecodeRequest, state: str) -> None:
        self._queue.remove(req)
        req.state = state
        req.done = True
        req.stats.finish_t = time.monotonic()
        self._notify_complete(req)

    def _deadline_hopeless(self, req: DecodeRequest, now: float) -> bool:
        """Admission gate: True when the request provably cannot finish
        before its deadline — already expired, or even the BEST-case
        remaining work times the observed step cadence overshoots it.
        Best-case, not expected: ``max_new_tokens`` is a cap (an
        ``eos_token`` can legally end the generation after one token)
        and a speculative step emits up to k+1 tokens — overestimating
        here would shed feasible work. Without an EMA yet (cold engine)
        only hard expiry counts: guessing would shed work a fast engine
        could still serve.

        Chunked mode additionally counts the queued prompt's REMAINING
        prefill chunks (after its actual memoized prefix-cache match)
        at the per-chunk EMA — sound because the fixed chunk bucket
        makes every chunk the same compiled program, so its cost is a
        constant the EMA tracks, unlike whole prefills whose cost
        scales with prompt length (which is why the unchunked gate
        never charged prefill time at all)."""
        if req.deadline_t is None:
            return False
        if now >= req.deadline_t:
            return True
        if self.decode_ema_s is not None:
            need = 1 if req.eos_token is not None else req.max_new_tokens
            # decode_ema_s is per LAUNCH: one token for the per-token
            # engine, up to k+1 for a speculative verify
            per_step = 1 if self._spec_cfg is None else self._spec_cfg.k + 1
            est = -(-need // per_step) * self.decode_ema_s
            if self.prefill_chunk_tokens is not None:
                cached = 0
                if self._prefix_cache is not None:
                    _keys, shared = self._prefix_cache.match(req.prompt,
                                                             memo=req)
                    cached = len(shared) * self.page_size
                chunks = -(-(len(req.prompt) - cached)
                           // self.prefill_chunk_tokens)
                if self.prefill_chunk_ema_s is not None:
                    est += chunks * self.prefill_chunk_ema_s
            return now + est > req.deadline_t
        return False

    def expire_deadlines(self, now: Optional[float] = None
                         ) -> List[DecodeRequest]:
        """Terminate everything past its deadline with the typed
        "deadline" state: queued requests are shed before prefill,
        active slots are evicted mid-flight with their pages (and any
        speculative reservation) returned. Runs at the top of every
        step and is safe to call from the serving loop even when the
        step itself is failing (host state only)."""
        now = time.monotonic() if now is None else now
        expired: List[DecodeRequest] = []
        for req in [r for r in self._queue
                    if r.deadline_t is not None and now >= r.deadline_t]:
            self._terminate_queued(req, "deadline")
            expired.append(req)
        for slot, req in enumerate(self._slots):
            if req is not None and req.deadline_t is not None \
                    and now >= req.deadline_t:
                # an eviction writes the slot: the step in flight is
                # folded first, and the request may have finished in it
                self._settle_inflight()
                if self._slots[slot] is req:
                    expired.append(self._evict_slot(slot, "deadline"))
        return expired

    def evict_stalled(self, now: Optional[float] = None
                      ) -> List[DecodeRequest]:
        """Stall watchdog: evict active slots that have delivered no
        token for ``stall_timeout_s`` with the typed "stalled" state
        instead of holding pages forever. No-op when the watchdog is
        off. Like `expire_deadlines` this touches host state only, so
        the serving loop calls it even mid engine failure."""
        if self.stall_timeout_s is None:
            return []
        now = time.monotonic() if now is None else now
        stalled: List[Tuple[int, DecodeRequest]] = []
        for slot, req in enumerate(self._slots):
            if req is None:
                continue
            last = max(req.last_emit_t, req.stats.admit_t)
            if req.state == "prefill_partial":
                # a half-prefilled slot may be healthily WAITING its
                # turn for the single per-step chunk budget while
                # another slot's chunks land — engine-wide chunk
                # progress is its liveness signal. A broken step stops
                # landing chunks ANYWHERE, so the timestamp goes stale
                # and the waiting slot still stalls out typed.
                last = max(last, self._last_chunk_t)
            if now - last > self.stall_timeout_s:
                stalled.append((slot, req))
        if not stalled:
            return []
        # judged on what was DELIVERED by ``now``: a token still in a
        # step in flight was not. An eviction writes the slot, so that
        # step is folded first (its token goes out before the typed
        # completion), and a request that finished in it is done
        self._settle_inflight()
        return [self._evict_slot(slot, "stalled")
                for slot, req in stalled if self._slots[slot] is req]

    def dump_inflight(self) -> List[DecodeRequest]:
        """Snapshot every request the engine still owes an answer for
        (active slots + wait queue) in submission order — the engine-
        resurrection input: each request's prompt plus already-emitted
        tokens is everything needed to rebuild its KV state on a fresh
        engine via a chained greedy prefill (bit-identical continuation
        is the paged design's recovery dividend). Does NOT release
        anything; callers tear down via close()."""
        # fold the step in flight into the snapshot first: its tokens
        # were never delivered, so on a failed settle the state before
        # the launch is equally gapless to replay from
        # (a computation that died with the engine: its tokens were
        # never generated as far as any client knows)
        with contextlib.suppress(Exception):
            self._settle_inflight()
        live = [r for r in self._slots if r is not None]
        return sorted(live + list(self._queue), key=lambda r: r.req_id)

    def _admit(self) -> None:
        if self.pause_admission:
            # swap drain gate (r24): hold the queue — a request
            # admitted now would pin active slots and starve the
            # pending weight swap of its num_active == 0 window
            return
        self._shed_overloaded()
        if self._queue and any(r is None for r in self._slots):
            # an admission writes a slot, and what is admissible
            # depends on the pages a finish gives back: the step in
            # flight is folded before the queue is looked at (whether
            # the head then fits or not: the conservative side)
            self._settle_inflight()
        for slot in range(self.num_slots):
            if self._slots[slot] is not None:
                continue
            while True:
                req = self._select_next()
                if req is None:
                    return
                if self._deadline_hopeless(req, time.monotonic()):
                    # never admit a request that can't finish: prefill
                    # compute spent on it is pure waste and its pages
                    # would be clawed back next step anyway
                    req.state = "deadline"
                    req.done = True
                    req.stats.finish_t = time.monotonic()
                    self._notify_complete(req)
                    continue
                break
            committed = self._admit_into(slot, req)
            if committed is False:
                return
            if committed is None:
                # deadline expired mid-prefill: the admission was
                # unwound typed and the slot is free again. No queue
                # jump happened, so fall through WITHOUT the fairness
                # charge — phantom bypass charges from a stream of
                # deadline-tight requests could otherwise starve the
                # queue (note_admitted is for COMMITTED admissions
                # only). The next step's _admit refills the slot.
                continue
            # fairness accounting happens only on COMMITTED admissions
            # (a failed/unwound admission must not charge bypasses)
            note = getattr(self._scheduler, "note_admitted", None)
            if note is not None:
                note(req, self._queue, time.monotonic())

    def _admit_into(self, slot: int, req: DecodeRequest
                    ) -> Optional[bool]:
        """Admit ``req`` into ``slot``. Returns True on a committed
        admission, False when it doesn't fit (stop admitting this
        step), None when the deadline expired mid-prefill and the
        admission was unwound typed (slot is free again; caller must
        not charge fairness accounting)."""
        jnp = self._jnp
        cache = self._prefix_cache
        tr = req.trace
        sp_admit = (tr.begin("admit", parent=tr.anchor, slot=slot)
                    if tr is not None else None)
        keys: Tuple[Hashable, ...] = ()
        shared: List[int] = []
        if cache is not None:
            keys, shared = cache.match(req.prompt, memo=req)
            # a device hit is a device hit; the DISTINCTION from
            # restored pages matters for the per-tier counters, so
            # remember where the device chain ended (insert() and the
            # stats below use it)
            req._pfx_device_hits = len(keys)
            # pin the matched chain BEFORE restore/allocation: both
            # the restore's own eviction pressure and the fallback
            # below must never reclaim pages we are about to point
            # this slot's table row at
            cache.acquire(keys)
            if getattr(cache, "spill_enabled", False):
                # hierarchical tiers (r15): extend the device match by
                # restoring spilled blobs into fresh pages (device_put
                # + page-table splice) — each restored page is one
                # prefix page this request does NOT re-prefill. A tier
                # miss mid-chain just stops here; the chained-prefill
                # suffix path below covers the rest, so outputs are
                # bit-identical either way.
                rsp = (tr.begin("restore", parent=sp_admit)
                       if tr is not None else None)
                with self._led("restore", req.req_id):
                    rkeys, rpages, rinfo = cache.restore_from_spill(
                        req.prompt, keys, self.allocator, memo=req)
                if rkeys and self.ledger is not None:
                    self.ledger.record("restore", req.req_id,
                                       pages=rpages)
                if tr is not None:
                    # fetched-vs-restored split (r20): how many of the
                    # restored pages arrived over the wire vs from a
                    # local eviction's blob
                    tr.end(rsp, pages=len(rkeys),
                           fetched=rinfo.get("fetched", 0),
                           corrupt=rinfo.get("corrupt", 0))
                if rkeys:
                    cache.acquire(rkeys)
                    keys = tuple(keys) + rkeys
                    shared = list(shared) + rpages
                if rkeys or rinfo.get("corrupt"):
                    st = req.stats
                    st.restored_pages += len(rkeys)
                    st.restored_host_pages += rinfo.get("host", 0)
                    st.restored_disk_pages += rinfo.get("disk", 0)
                    st.restore_corrupt += rinfo.get("corrupt", 0)
                    st.restore_ms += rinfo.get("ms", 0.0)
                    st.handoff_pages += rinfo.get("fetched", 0)
        cached_len = len(shared) * self.page_size
        capacity = len(req.prompt) + req.max_new_tokens
        need = -(-capacity // self.page_size)
        private_need = need - len(shared)

        def grab():
            if not self._reserve_growth:
                return self.allocator.alloc(req.req_id, private_need)
            # speculative AND multi-step modes bind only the
            # prefill-covering pages and RESERVE the rest of the
            # capacity: decode grows the page set on demand
            # (_ensure_pages, per spec step) and speculative
            # rollback returns wholly-unused pages (_rollback_pages)
            # without ever risking a mid-decode allocation failure
            prefill_need = (-(-len(req.prompt) // self.page_size)
                            - len(shared))
            if not self.allocator.reserve(req.req_id, private_need):
                return None
            return self.allocator.alloc_reserved(req.req_id,
                                                 prefill_need)

        from ..distributed.fault_inject import InjectedFault
        try:
            with self._led("admit", req.req_id):
                pages = grab()
                if pages is None and cache is not None:
                    if cache.evict_until(self.allocator, private_need):
                        pages = grab()
        except InjectedFault:
            # armed alloc.page site: a transient allocation failure is
            # the same outcome as not fitting — unwind and requeue;
            # the next step retries admission (alloc/reserve raise
            # BEFORE mutating the free list, so there is nothing to
            # roll back)
            pages = None
        if pages is None:
            if tr is not None:
                tr.end(sp_admit, admitted=False, reason="no_fit")
            if cache is not None:
                cache.release(keys)
            self._queue.insert(0, req)
            return False
        req.stats.admit_t = time.monotonic()
        # page-attribution baseline (r18): peak starts at the admitted
        # holding, page-seconds integrate from here
        self._account_req_pages(req, req.stats.admit_t)
        if tr is not None:
            # the queue stage ends at the committed admission; the
            # scheduler's explain() (duck-typed) attributes WHY the
            # request waited (class, promotion, bypasses)
            exp = {}
            explain = getattr(self._scheduler, "explain", None)
            if explain is not None:
                try:
                    exp = dict(explain(req, req.stats.admit_t))
                except Exception:
                    exp = {}
            self._tr_end(req, bypass_count=req.bypass_count, **exp)
        req.stats.cached_pages = len(shared)
        req.stats.cached_tokens = cached_len
        req.stats.prompt_pages = (len(req.prompt) - 1) // self.page_size
        req.stats.cache_enabled = cache is not None
        req.cache_keys = keys
        req.state = "prefill"
        row = np.full((self.max_pages,), self._scratch, np.int32)
        row[:len(shared)] = shared
        row[len(shared):len(shared) + len(pages)] = pages
        self._write_slot(slot, table=row)
        if self.prefill_chunk_tokens is not None:
            # chunked admission (r11): bind the pages, store NOTHING
            # yet — the suffix is enqueued as page-aligned chunks that
            # _advance_prefill_chunk trickles in across decode steps.
            # The slot's stored length is exactly the prefix-cache hit
            # (shared pages already hold valid KV); matched cache pins
            # stay on req.cache_keys so every eviction path releases
            # them, and insert() runs only when the LAST chunk lands.
            req.state = "prefill_partial"
            req.prefill_done_len = cached_len
            req.slot = slot
            self._write_slot(slot, lens=cached_len, cur=0)
            self._slots[slot] = req
            if tr is not None:
                tr.end(sp_admit, cached_pages=len(shared),
                       restored_pages=req.stats.restored_pages)
                # chunked mode: the prefill STAGE stays open across
                # chunks; each chunk appends a child span
                req.span = tr.begin(
                    "prefill", parent=tr.anchor, chunked=True,
                    remaining=len(req.prompt) - cached_len)
            return True
        suffix = req.prompt[cached_len:]
        bucket = self._bucket(len(suffix))
        ids = np.zeros((1, bucket), np.int32)
        ids[0, :len(suffix)] = suffix
        chained = cached_len > 0
        kind = "prefill_chained" if chained else "prefill"
        jit = self._get_prefill(chained)
        if tr is not None:
            tr.end(sp_admit, cached_pages=len(shared),
                   restored_pages=req.stats.restored_pages)
        sp_pref = (tr.begin("prefill", parent=tr.anchor, bucket=bucket,
                            fill=round(len(suffix) / bucket, 4),
                            chained=chained)
                   if tr is not None else None)

        def run_prefill():
            from ..dispatch import count_op_calls
            from ..distributed.fault_inject import fault_point
            self._check_pools_live("prefill")
            fault_point("serving.prefill")
            with self._phase("upload"):
                args = (self._fresh_state(refresh=True), self._pools,
                        jnp.asarray(row[None]),
                        jnp.asarray([cached_len], jnp.int32),
                        jnp.asarray([len(suffix)], jnp.int32),
                        jnp.asarray(ids))
                if self._slot_rows:
                    # whose rings the window layers write, whose row
                    # the state layers
                    args += (jnp.asarray([slot], jnp.int32),)
            with self._phase("launch", kind):
                with count_op_calls() as c:
                    out = jit(*args)
            self._record_programs(kind, c.count)
            if c.count:
                self._capture_cost(kind, jit, args)
            return out

        spent = self._phase_seconds("upload", "launch", "wait")
        try:
            if self._prefill_retry is not None:
                nxt, pools = self._prefill_retry.call(
                    run_prefill, site="serving.prefill")
            else:
                nxt, pools = run_prefill()
        except Exception:
            # unwind the half-applied admission so a prefill failure
            # (e.g. a remote-compile transport error on a new prompt
            # bucket, or an exhausted serving.prefill retry) is
            # retryable instead of losing the request and leaking its
            # pages, then surface the error. (If the failure hit AFTER
            # execution began, the donated pool buffers may be gone
            # with it — compile-time failures, the documented class,
            # leave them untouched.)
            if tr is not None:
                tr.end(sp_pref, error=True)
            self._unwind_prefill_failure(slot, req)
            raise
        self._pools = pools
        self.prefill_positions += len(suffix)
        self.prefill_positions_padded += bucket
        with self._phase("wait", kind):
            # blocks until the prefill program has run; the model's
            # counters, where it reports any, ride behind the token
            got = np.asarray(nxt).reshape(-1)
            tok = int(got[0])
            if got.size > 1:
                self._fold_stats(kind, got[1:])
        # the first token exists from here: `now` is the end of that
        # wait, and prefill_ms the argument build, the dispatch and
        # the wait together, from the phases' own stamps
        now = self._host.t
        dt = self._phase_seconds("upload", "launch", "wait") - spent
        req.stats.prefill_ms = dt * 1e3
        self._tl_add_ms("prefill_ms", dt)
        if tr is not None:
            tr.end(sp_pref, ms=round(req.stats.prefill_ms, 3))
        req.stats.prefill_attempts += 1
        req.stats.prefill_chunks = 1  # whole prefill = one launch
        with self._phase("emit"):
            if req.deadline_t is not None and now >= req.deadline_t:
                # deadline expired MID-PREFILL: the forward pass is
                # paid for, but delivering a token past the deadline
                # breaks the contract — unwind the admission typed
                # instead (pools were adopted above, so device state
                # stays coherent)
                self._account_req_pages(req, now)
                if self.ledger is not None:
                    # same forensics contract as _evict_slot's
                    # deadline path: snapshot the page history BEFORE
                    # free rewrites it so the typed reply can carry it
                    req.page_forensics = self.ledger.history_for_owner(
                        req.req_id)
                with self._led("deadline", req.req_id):
                    self.allocator.free(req.req_id)
                    if cache is not None:
                        cache.release(keys)
                        req.cache_keys = ()
                self._write_slot(slot, table=self._scratch)
                req.state = "deadline"
                req.done = True
                req.stats.finish_t = now
                self._notify_complete(req)
                return None
            req.stats.first_token_t = now
            self._write_slot(slot, lens=len(req.prompt), cur=tok)
            req.slot = slot
            req.state = "decoding"
            req.generated.append(tok)
            req.stats.tokens_out = 1
            if cache is not None:
                # the slot's full prompt pages now hold valid KV — hand
                # them to the cache (ownership transfer, refcount held
                # by this request until it finishes)
                req.cache_keys = cache.insert(
                    req.prompt, row, self.allocator, req.req_id,
                    self.page_size, keys,
                    device_hits=getattr(req, "_pfx_device_hits", None))
            self._slots[slot] = req
            if tr is not None:
                tr.event("first_token", parent=tr.anchor, token=tok)
                req.span = tr.begin("decode", parent=tr.anchor)
            self._emit_token(req, tok)
            self._maybe_finish(slot)
        return True

    # -- chunked prefill (r11) ---------------------------------------------

    def _select_chunk_slot(self, partial: List[Tuple[int, DecodeRequest]]
                           ) -> Optional[int]:
        """Which half-prefilled slot gets this step's chunk budget.
        With a scheduler exposing ``select_chunk`` (serving/
        scheduler.py's chunk-budget policy: INTERACTIVE decode preempts
        lower-class prefill chunks, bounded deferrals), defer to it;
        the built-in policy advances the oldest admission (FIFO by
        req_id). When nothing is decoding there is nothing to preempt,
        so the scheduler contract requires a pick — the engine would
        otherwise spin without progress."""
        sel = getattr(self._scheduler, "select_chunk", None)
        if sel is not None:
            decoding = [r for r in self._slots
                        if r is not None and r.state == "decoding"]
            return sel(partial, decoding, time.monotonic())
        return min(partial, key=lambda sr: sr[1].req_id)[0]

    def _advance_prefill_chunk(self) -> bool:
        """Spend this step's prefill budget: advance AT MOST ONE
        half-prefilled slot by one page-aligned chunk of
        ``prefill_chunk_tokens`` tokens through the chained-prefill jit
        (``cached_len`` = tokens stored so far — shared prefix pages
        and prior chunks are the same "already stored" case, so the
        chunk attends everything before it through the paged-attention
        q_offsets path). The chunk ids are ALWAYS padded to the one
        fixed chunk bucket, so the engine pays one prefill compile per
        chained-ness, not one per suffix length. The final chunk's
        logits produce the first generated token, exactly like a whole
        prefill. Returns True when a chunk ran."""
        partial = [(i, r) for i, r in enumerate(self._slots)
                   if r is not None and r.state == "prefill_partial"]
        if not partial:
            return False
        slot = self._select_chunk_slot(partial)
        if slot is None:
            return False  # scheduler deferred: decode preempts
        jnp = self._jnp
        req = self._slots[slot]
        cache = self._prefix_cache
        chunk = self.prefill_chunk_tokens
        done = req.prefill_done_len
        suffix = req.prompt[done:done + chunk]
        final = done + len(suffix) == len(req.prompt)
        ids = np.zeros((1, chunk), np.int32)
        ids[0, :len(suffix)] = suffix
        # chunk 1 of an uncached prompt keeps the exact dense fresh-
        # prefill program (chained=False), so a suffix that fits in one
        # chunk is byte-for-byte the whole-prefill admission
        chained = done > 0
        kind = "prefill_chained" if chained else "prefill"
        jit = self._get_prefill(chained)
        row = self._table[slot]  # the live row: insert() retargets it

        def run_chunk():
            from ..dispatch import count_op_calls
            from ..distributed.fault_inject import fault_point
            self._check_pools_live("prefill")
            fault_point("serving.prefill")
            with self._phase("upload"):
                # a copy of the row: the upload may alias what it is
                # given (a CPU device does), and the mirrors are written
                # in place while the chunk is still in flight
                # (_launch_decode sends a copy for the same reason)
                args = (self._fresh_state(refresh=True), self._pools,
                        jnp.asarray(row[None].copy()),
                        jnp.asarray([done], jnp.int32),
                        jnp.asarray([len(suffix)], jnp.int32),
                        jnp.asarray(ids))
                if self._slot_rows:
                    # whose rings the window layers write, whose row
                    # the state layers
                    args += (jnp.asarray([slot], jnp.int32),)
            with self._phase("launch", kind):
                with count_op_calls() as c:
                    out = jit(*args)
            self._record_programs(kind, c.count)
            if c.count:
                self._capture_cost(kind, jit, args)
            return out

        tr = req.trace
        sp_chunk = (tr.begin("prefill_chunk", parent=req.span,
                             idx=req.stats.prefill_chunks,
                             done_tokens=done)
                    if tr is not None else None)
        spent = self._phase_seconds("upload", "launch")
        try:
            if self._prefill_retry is not None:
                nxt, pools = self._prefill_retry.call(
                    run_chunk, site="serving.prefill")
            else:
                nxt, pools = run_chunk()
        except Exception:
            # unwind the WHOLE half-prefilled admission (not just this
            # chunk) — shared with the whole-prefill failure path
            if tr is not None:
                tr.end(sp_chunk, error=True)
            self._unwind_prefill_failure(slot, req)
            raise
        self._pools = pools
        # the chunk's argument build and dispatch (no blocking read: a
        # chunk's result is read only after the last one, below)
        now = self._host.t
        dt = self._phase_seconds("upload", "launch") - spent
        self._tl_add_ms("chunk_ms", dt)
        if tr is not None:
            tr.end(sp_chunk, tokens=len(suffix))
        req.stats.prefill_ms += dt * 1e3
        req.stats.prefill_chunks += 1
        if self._chunk_warm[chained]:
            self.prefill_chunk_ema_s = dt \
                if self.prefill_chunk_ema_s is None \
                else 0.8 * self.prefill_chunk_ema_s + 0.2 * dt
        else:
            # first launch of this variant: compile-dominated, skip
            self._chunk_warm[chained] = True
        req.prefill_done_len = done + len(suffix)
        self._write_slot(slot, lens=req.prefill_done_len)
        # chunk progress is liveness for the stall watchdog: a long
        # prompt legitimately emits nothing while its chunks land, but
        # a slot whose chunks stopped landing (step failures) still
        # stalls out and is evicted typed. The engine-wide timestamp
        # additionally protects OTHER half-prefilled slots waiting
        # their turn for the per-step chunk budget.
        req.last_emit_t = now
        self._last_chunk_t = now
        req.chunk_deferrals = 0
        if req.deadline_t is not None and now >= req.deadline_t:
            # expired mid-prefill: the chunk is paid for, but delivering
            # a token past the deadline breaks the contract — evict
            # typed (pages, reservations and cache pins all return)
            self._evict_slot(slot, "deadline")
            return True
        if not final:
            return True
        # last chunk: its logits ARE the whole prefill's logits — emit
        # the first token and promote the slot to the decode batch
        with self._phase("wait", kind):
            tok = int(nxt)
        with self._phase("emit"):
            req.stats.prefill_attempts += 1
            req.stats.first_token_t = now
            self._write_slot(slot, cur=tok)
            req.state = "decoding"
            req.generated.append(tok)
            req.stats.tokens_out = 1
            if tr is not None:
                # close the chunked "prefill" stage, mark the first
                # token, and open the decode stage — same shape as
                # whole prefill
                self._tr_end(req, chunks=req.stats.prefill_chunks)
                tr.event("first_token", parent=tr.anchor, token=tok)
                req.span = tr.begin("decode", parent=tr.anchor)
            if cache is not None:
                # the slot's full prompt pages now hold valid KV — hand
                # them to the cache (ownership transfer; the matched
                # keys from admission are the already-acquired chain
                # head)
                req.cache_keys = cache.insert(
                    req.prompt, row, self.allocator, req.req_id,
                    self.page_size, req.cache_keys,
                    device_hits=getattr(req, "_pfx_device_hits", None))
            self._emit_token(req, tok)
            self._maybe_finish(slot)
        return True

    def _finish_due(self, req: DecodeRequest) -> bool:
        hit_eos = (req.eos_token is not None and req.generated and
                   req.generated[-1] == req.eos_token)
        return len(req.generated) >= req.max_new_tokens or hit_eos

    def _maybe_finish(self, slot: int) -> None:
        req = self._slots[slot]
        if req is None:
            return
        if self._finish_due(req):
            self._finish_slot(slot)

    def _finish_slot(self, slot: int) -> None:
        """Terminal "done" teardown for one slot: free pages, release
        cache pins, park on scratch, notify."""
        req = self._slots[slot]
        req.done = True
        req.state = "done"
        req.stats.finish_t = time.monotonic()
        req.stats.tokens_out = len(req.generated)
        self._finished[req.req_id] = req
        self._account_req_pages(req)
        with self._led("done", req.req_id):
            self.allocator.free(req.req_id)
            if self._prefix_cache is not None and req.cache_keys:
                self._prefix_cache.release(req.cache_keys)
                req.cache_keys = ()
        # park on the scratch page
        self._write_slot(slot, table=self._scratch, lens=0, cur=0)
        self._slots[slot] = None
        self._notify_complete(req)

    # -- speculative decoding ----------------------------------------------

    def _ensure_pages(self, slot: int, req: DecodeRequest,
                      need_len: int) -> None:
        """Grow the slot's page set to cover positions [0, need_len)
        out of the request's reservation (guaranteed: capacity was
        committed at admission). Speculative engines only — vanilla
        per-token admission binds every page up front."""
        row = self._table[slot].copy()
        want = -(-need_len // self.page_size)
        missing = [j for j in range(want) if row[j] == self._scratch]
        if not missing:
            return
        with self._led("spec_grow", req.req_id):
            pages = self.allocator.alloc_reserved(req.req_id,
                                                  len(missing))
        row[missing] = pages
        self._write_slot(slot, table=row)

    def _rollback_pages(self, slot: int, req: DecodeRequest,
                        new_len: int) -> int:
        """Rejection rollback: pages whose EVERY position sits at or
        beyond the accepted length hold only rejected-draft KV —
        return them to the allocator (capacity goes back into the
        request's reservation, so later growth still cannot fail).
        The page containing position ``new_len`` (the next append
        target) is kept even when partially stale: stale positions are
        never attended (host seq_lens were rewound) and the next
        append overwrites them. Shared prefix pages sit strictly below
        ``new_len`` and are never touched."""
        row = self._table[slot]
        keep = -(-(new_len + 1) // self.page_size)
        victims = [int(row[j]) for j in range(keep, self.max_pages)
                   if row[j] != self._scratch]
        if victims:
            with self._led("spec_rollback", req.req_id):
                self.allocator.release_pages(req.req_id, victims,
                                             rereserve=True)
            row = row.copy()
            row[keep:] = self._scratch
            self._write_slot(slot, table=row)
        return len(victims)

    def _spec_step(self) -> int:
        """One draft-and-verify step over every active slot: propose k
        tokens per slot (host/draft-model), score all k+1 positions in
        ONE target forward, emit each slot's longest accepted prefix
        plus its correction/bonus token, rewind ``seq_lens`` past the
        rejections and return wholly-unused pages. Greedy emission is
        bit-identical to the vanilla per-token loop (pinned)."""
        import jax

        jnp = self._jnp
        cfg = self._spec_cfg
        k = cfg.k
        vocab = self.cfg.vocab_size
        # half-prefilled slots (chunked mode) are NOT verified: their
        # valid count stays 0, parking their writes on the scratch page
        # exactly like empty slots, and the draft source sees no
        # history for them
        active = [i for i, r in enumerate(self._slots)
                  if r is not None and r.state == "decoding"]
        hist = [None if (r is None or r.state != "decoding")
                else r.tokens for r in self._slots]
        drafts = np.asarray(self._spec_draft.propose(hist, k), np.int32)
        if drafts.shape != (self.num_slots, k):
            raise ValueError(
                f"draft source returned shape {drafts.shape}, expected "
                f"{(self.num_slots, k)}")
        # defensive clip: a draft over a larger vocab must not feed the
        # target an out-of-range id (wrong guesses are free, OOB isn't)
        drafts = np.clip(drafts, 0, vocab - 1).astype(np.int32)
        tokens = np.zeros((self.num_slots, k + 1), np.int32)
        tokens[:, 0] = self._cur
        tokens[:, 1:] = drafts
        valid = np.zeros((self.num_slots,), np.int32)
        old_lens = self._lens.copy()
        for i in active:
            req = self._slots[i]
            rem = req.max_new_tokens - len(req.generated)
            k_eff = min(k, rem - 1)  # emit at most rem tokens
            valid[i] = 1 + k_eff
            self._ensure_pages(i, req, int(old_lens[i]) + int(valid[i]))
        if self._verify_jit is None:
            self._verify_jit = self._build_verify()
        with self._phase("upload"):  # the key is a device argument too
            if cfg.temperature and self._spec_key is None:
                self._spec_key = jax.random.PRNGKey(cfg.seed)
            if cfg.temperature:
                self._spec_key, key = jax.random.split(self._spec_key)
            else:
                key = jax.random.PRNGKey(0)  # unused on the greedy path

        stamps: List[float] = []  # the start of each attempt's upload

        def run_verify():
            from ..dispatch import count_op_calls
            from ..distributed.fault_inject import fault_point
            self._check_pools_live("verify")
            fault_point("serving.verify")
            with self._phase("upload") as upload:
                args = (self._fresh_state(), self._pools,
                        jnp.asarray(self._table), jnp.asarray(self._lens),
                        jnp.asarray(tokens), jnp.asarray(valid), key)
            stamps.append(upload.t0)
            with self._phase("launch", "verify"):
                with count_op_calls() as c:
                    out = self._verify_jit(*args)
            self._record_programs("verify", c.count)
            if c.count:
                self._capture_cost("verify", self._verify_jit, args)
            return out

        if self._verify_retry is not None:
            accept, resid, full, pools = self._verify_retry.call(
                run_verify, site="serving.verify")
        else:
            accept, resid, full, pools = run_verify()
        # first argument build to the end of the last dispatch
        t0v, t1v = stamps[0], self._host.t
        self._tl_add_ms("verify_ms", t1v - t0v)
        self._pools = pools
        with self._phase("wait", "verify"):
            accept = np.asarray(accept)
            resid = np.asarray(resid)
            full = np.asarray(full)
        with self._phase("emit"):
            self.steps += 1
            for i in active:
                req = self._slots[i]
                k_eff = int(valid[i]) - 1
                n = 0
                while n < k_eff and accept[i, n]:
                    n += 1
                req.stats.spec_steps += 1
                req.stats.spec_drafted += k_eff
                req.stats.spec_accepted += n
                if req.trace is not None:
                    req.trace.add("verify_step", t0v * 1e6, t1v * 1e6,
                                  parent=req.span, step=self.steps,
                                  drafted=k_eff, accepted=n)
                nxt = int(resid[i, n]) if n < k_eff else int(full[i, k_eff])
                emitted = [int(t) for t in tokens[i, 1:1 + n]] + [nxt]
                finished = False
                for tok in emitted:
                    req.generated.append(tok)
                    req.stats.tokens_out = len(req.generated)
                    self._write_slot(i, cur=tok)
                    self._emit_token(req, tok)
                    if self._finish_due(req):
                        finished = True
                        break  # EOS inside the accepted run: stop emitting
                if finished:
                    # _maybe_finish frees the slot wholesale (pages AND
                    # remaining reservation) — no rollback bookkeeping
                    self._maybe_finish(i)
                    continue
                # KV now validly covers cur + the n accepted drafts; the
                # last emitted token's KV is written by the NEXT step
                new_len = int(old_lens[i]) + n + 1
                self._write_slot(i, lens=new_len)
                self._rollback_pages(i, req, new_len)
        return self.num_active

    def step(self) -> int:
        """Admit what fits, spend the chunked-prefill budget (at most
        one slot's next chunk), LAUNCH one fixed-shape decode step for
        every slot past prefill and hand out the tokens of the decode
        step launched by the call before (or run one draft-and-verify
        speculative step), evict what finished.
        Returns the number of still-active slots.

        **One decode step stays in flight ahead of the host**
        (_decode_step). A call that finds a step in flight, its
        outputs usable and no slot write due launches the next step
        from those outputs FIRST and only then blocks on the tokens of
        the one in flight, so the device goes from program to program
        while the host fetches, emits, finishes and commits. Whatever
        writes a slot settles the step in flight before it (admission,
        eviction, swap; every outside reader: _settle_inflight), and
        the call then runs the synchronous sequence: the case "nothing
        in flight", not a second path. A call that launches where
        nothing was in flight hands out no decode token; its step is
        settled by the next call. A call that leaves no slot active
        drops what is still in flight: every row of it belongs to a
        request that is gone.

        The ``engine.step`` fault site fires FIRST — before admission
        and before the donating jit. A step that raises, there or
        later (in a launch, at the fetch of the step in flight), drops
        the result in flight together with the device's copy of the
        decode inputs (``_resident``): those tokens were never handed
        out, the mirrors are the truth, and the next decode step
        uploads them and computes the same position again. So after
        an injected step failure host state is exactly as the last
        HANDED-OUT step left it (the precondition for the serving
        layer's resurrection replay)."""
        from ..distributed.fault_inject import fault_point
        try:
            fault_point("engine.step")
            # r16 step timeline: reset per-step accumulators, commit
            # one ring entry per step attempt (a dict per STEP — never
            # per token — next to at least one jit launch)
            self._tl_programs = {}
            self._tl_ms = {}
            self._tl_decode = None
            self._tl_stats = {}
            self._host.take()  # phases outside a step: no record's
            if self.ledger is not None:
                self.ledger.step = self.steps
            t_step = time.monotonic()
            try:
                active = self._step_inner()
                if not active:
                    self._inflight = None
                return active
            finally:
                self._tl_commit(t_step)
        except BaseException:
            # whatever failed, the step after it sends the mirrors
            self._drop_inflight()
            raise

    def _drop_inflight(self) -> None:
        """After a failed step: forget the device's copy of the decode
        inputs and the step in flight. Its KV appends are computed
        again into the same places by the step that follows; a state
        layer's row it has already MOVED, and a second pass would move
        it twice, so there the step in flight is settled instead (its
        tokens are real: the program ran) and dropped only if its
        fetch fails too."""
        if self._has_state:
            with contextlib.suppress(Exception):
                self._settle_inflight()
        self._resident = self._inflight = None

    def _step_inner(self) -> int:
        if self._resident is None:
            # a finish at the last settle wrote its slot under the step
            # in flight: that step's outputs cannot feed another, and
            # the slot it freed is about to be looked at
            self._settle_inflight()
        with self._phase("admit"):
            # each of the three settles the step in flight before it
            # writes a slot, and leaves it alone where it writes none
            self.expire_deadlines()
            self.evict_stalled()
            self._admit()
            if self.num_active == 0:
                return 0
        if self.prefill_chunk_tokens is not None:
            self._advance_prefill_chunk()
            if not any(r is not None and r.state == "decoding"
                       for r in self._slots):
                # everything active is still mid-prefill (only chunked
                # admission leaves a slot so): no decode step to run;
                # the next step() advances the next chunk. num_active
                # keeps run() looping.
                return self.num_active
        if self._spec_cfg is None:
            return self._decode_step()
        # the verify EMA is fed from the phases' stamps: the last one
        # before the call, the last one inside it
        t0 = self._host.t
        try:
            return self._spec_step()
        finally:
            self._feed_decode_ema(self._host.t - t0)

    def _feed_decode_ema(self, dt: float) -> None:
        """One more decode (or verify) step of ``dt`` seconds into the
        deadline gate's estimate. The first step is skipped: its wall
        time is dominated by the one-off decode/prefill compiles and
        would poison the estimate for the engine's whole warmup. Chunk
        prefills have their own EMA (_advance_prefill_chunk), so a
        prefill-heavy step can't poison the per-token estimate."""
        if self.steps > 1:
            self.decode_ema_s = dt if self.decode_ema_s is None \
                else 0.8 * self.decode_ema_s + 0.2 * dt

    def _decode_step(self) -> int:
        """Launch one decode step over the device's own copy of its
        inputs, and settle the one launched before it.

        The decode program returns the next step's packed inputs
        (_build_decode) and ``_resident`` holds those of the newest
        launch. The host mirrors stay the truth: every host write goes
        through ``_write_slot``, which drops ``_resident``, and the
        decode step after it sends the mirrors in ONE transfer. A step
        the host did not touch sends nothing and fetches the tokens
        alone; the length mirror advances by the host's own ``+ 1``.

        1. Ahead. With a step k in flight (whoever had to write a slot
           has settled it by now, _step_inner), k+1 is launched from
           k's device outputs (``pools``, ``packed``) first; then k is
           settled: its tokens fetched, the mirrors advanced, tokens
           emitted, slots finished. Fetch latency, ``emit``, ``commit``
           and the caller's loop pass under k+1's device time.
        2. Settle before any host write. A writer that finds a step in
           flight folds it first and the call runs upload (once, the
           mirrors as of that step), launch: the pipeline's bubble.
        3. A slot that finishes in k while k+1 is in flight, by
           ``eos_token`` or by count alike (a count-known finish RIDES
           the look-ahead: the other rows' step is not held back for
           it): the row k+1 computed for it is dropped at k+1's settle
           and reaches no ``generated``, ``on_token``, trace or
           counter. Its KV append landed in a page the slot still
           owned when k+1 was launched, or on the scratch page; the
           pages are freed, cached or re-bound by host code that runs
           after that launch, so any program that writes them again is
           ordered behind it. The finish drops ``_resident``, so the
           next call settles k+1 and uploads.
        4. Outside readers (expire_deadlines, evict_stalled,
           dump_inflight, swap_weights, close) settle the step in
           flight before they change a slot.
        5. A step that fails leaves copy and step in flight dropped
           (``step``). A masked step (a half-prefilled slot) is
           settled in the call that launches it: its outputs are not
           the mirrors' next state."""
        # nothing counts as in flight while a settle runs: a callback
        # that comes back in through a public method finds settled
        # state, and a settle that raises drops both steps (``step``)
        pend, self._inflight = self._inflight, None
        try:
            new = self._launch_decode(ahead=pend is not None)
        except BaseException:
            self._inflight = pend  # whoever handles the failure decides
            raise
        if pend is not None:
            self._settle_decode(pend)
        if new["masked"]:
            self._settle_decode(new)
        else:
            self._inflight = new
        return self.num_active

    def _launch_decode(self, ahead: bool) -> Dict[str, Any]:
        """Launch the decode program over ``_resident`` (or, where that
        is stale, over one upload of the mirrors) and return the record
        of the step now in flight; does not block. ``ahead``: the step
        before it is still unfetched."""
        from ..dispatch import count_op_calls
        held = self._resident
        with self._phase("upload") as upload:
            if self._decode_jit is None:
                self._decode_jit = self._build_decode()
            states = [None if r is None else r.state for r in self._slots]
            rows = [(slot, self._slots[slot])
                    for slot, s in enumerate(states) if s == "decoding"]
            masked = "prefill_partial" in states
            send = None
            if masked or held is None:
                # a copy: the mirrors are written in place, and a
                # transfer may alias host memory until it completes
                send = self._packed.copy()
            if masked:
                # half-prefilled slots ride the fixed-shape step MASKED
                # to the scratch page at length 0: their pages hold a
                # partial prompt whose next position the NEXT chunk
                # owns — the decode append must not touch it (writes
                # land on scratch, attention over an empty slot is
                # defined zeros). The mirrors keep the real values and
                # only the device call sees the mask, so what the
                # program returns is not the mirrors' next state:
                # every such step uploads.
                idle = np.array([s != "decoding" for s in states])
                send[idle, :self.max_pages] = self._scratch
                send[idle, self.max_pages] = 0
            if send is not None:
                held = self._place_resident(send)
            args = (self._fresh_state(), self._pools, held)
        with self._phase("launch", "decode") as launch:
            with count_op_calls() as c:
                nxt, self._pools, held = self._decode_jit(*args)
            # the newest launch's outputs; a masked step leaves nothing
            # to reuse
            self._resident = None if masked else held
            if c.count:
                self._capture_cost("decode", self._decode_jit, args)
            # the arguments and the donated pools' handles are let go
            # here, in a phase (some hundred objects at 24 layers)
            del args, held
            # counted once the program is launched: 0, fed its own
            # outputs
            self._tl_decode = (int(send is not None), int(ahead))
            if send is None:
                self.decode_steps_resident += 1
            else:
                self.decode_steps_uploaded += 1
            self.decode_steps_ahead += int(ahead)
            self._record_programs("decode", c.count)
        # the DISPATCH of the decode program, not the decode: on a
        # device the call returns futures, and the program's own time
        # passes until its settle's `wait` ends
        self._tl_add_ms("decode_ms", launch.t1 - launch.t0)
        return {"nxt": nxt, "rows": rows, "masked": masked,
                "t0": upload.t0, "t0d": launch.t0, "t1d": launch.t1}

    def _settle_inflight(self) -> None:
        """Fold the decode step in flight, if there is one, into host
        state: what everything that writes a slot, and every outside
        reader, does first. The step is forgotten BEFORE the blocking
        read: a failed computation raises there, and its handles would
        only raise again."""
        pend, self._inflight = self._inflight, None
        if pend is None:
            return
        try:
            self._settle_decode(pend)
        except BaseException:
            self._resident = None  # its outputs, which never came
            raise

    def _settle_decode(self, pend: Dict[str, Any]) -> None:
        """The host half of a launched decode step: fetch its tokens
        (the step's one fetch), advance the mirrors, emit, finish.
        Rows of requests that left their slot since the launch (a
        finish in the step before, under this one) are dropped."""
        with self._phase("wait", "decode"):
            nxt = np.asarray(pend.pop("nxt"))
            if nxt.size > self.num_slots:
                # the model's counters came back behind the tokens
                self._fold_stats("decode", nxt[self.num_slots:])
                nxt = nxt[:self.num_slots]
        with self._phase("emit"):
            rows = [(slot, req) for slot, req in pend["rows"]
                    if self._slots[slot] is req]
            dropped = len(pend["rows"]) - len(rows)
            self.decode_rows_dropped += dropped
            if self._has_state:
                self.state_rows_overwritten += dropped
            if rows:
                # the mirrors follow the device: a decoding slot's
                # length grew by the token appended, its current token
                # is the one sampled. Other slots wrote to the scratch
                # page and keep their host values (0 for an empty slot,
                # prefill_done_len for a half-prefilled one). With
                # every launched step settled, mirrors and
                # ``_resident`` agree; the finishes below go through
                # _write_slot and drop it.
                idx = [slot for slot, _ in rows]
                self._lens[idx] += 1
                self._cur[idx] = nxt[idx]
                self.steps += 1
            t0d, t1d = pend["t0d"] * 1e6, pend["t1d"] * 1e6
            for slot, req in rows:
                tok = int(nxt[slot])
                req.generated.append(tok)
                req.stats.tokens_out = len(req.generated)
                if req.trace is not None:
                    # pre-timed closed span: one list append per traced
                    # in-flight request, no extra clock reads per slot
                    req.trace.add("decode_step", t0d, t1d,
                                  parent=req.span, step=self.steps,
                                  token=tok)
                self._emit_token(req, tok)
                self._maybe_finish(slot)
            # the fetched result goes here, in the phase that read it:
            # letting a device result's buffer go is not free, and at
            # this function's return it would fall in no phase
            del nxt
        end = self._host.t
        if rows:
            self._feed_decode_ema(end - max(pend["t0"], self._settled_t))
        self._settled_t = end

    def run(self, max_steps: int = 100000) -> Dict[int, np.ndarray]:
        """Drive until queue and slots drain; returns {req_id: tokens}
        for everything finished so far and DRAINS the finished store
        (a long-running engine must not accumulate past results —
        callers polling step() themselves use result(id, pop=True))."""
        steps = 0
        while self._queue or self.num_active:
            before = (len(self._queue), self.num_active)
            self.step()
            steps += 1
            if steps > max_steps:
                raise RuntimeError(f"engine did not drain in {max_steps} "
                                   f"steps (state {before})")
        if self._prefix_cache is None:
            self.allocator.check_no_leak()
        else:
            # cached prefix pages legitimately outlive their requests;
            # audit the cache's books against the allocator instead
            self._prefix_cache.check_consistent(self.allocator)
        out = {rid: req.tokens for rid, req in self._finished.items()}
        self._finished.clear()
        return out

    def close(self) -> None:
        """Terminal teardown: evict every active slot, drop every
        queued request, return their pages, clear the prefix cache, and
        assert nothing leaked. After close() the engine holds no pages
        — the graceful-drain endpoint bench/tests call on every exit
        path (a drained `run()` followed by close() is the clean
        shutdown; close() mid-flight is the hard stop)."""
        # settle the step in flight so teardown evictions see current
        # state and streamed tokens precede every eviction
        # notification. A failed settle means its tokens never existed
        # for any client: drop it.
        with contextlib.suppress(Exception):
            self._settle_inflight()
        for slot, req in enumerate(self._slots):
            if req is not None:
                self._evict_slot(slot, "evicted")
        for req in list(self._queue):
            self._terminate_queued(req, "evicted")
        if self._prefix_cache is not None:
            with self._led("close"):
                self._prefix_cache.clear(self.allocator)
        self.allocator.check_no_leak()


def create_decode_engine(model, **kwargs) -> ContinuousBatchingEngine:
    """Serving-path entry (mirrors inference.create_predictor): build a
    continuous-batching decode engine over a causal-LM layer."""
    return ContinuousBatchingEngine(model, **kwargs)
