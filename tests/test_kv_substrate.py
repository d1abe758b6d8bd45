"""KV bytes as the fleet substrate (r23): quantized spill/handoff
blob codecs, cross-request page dedup, byte-aware accounting, and
the router's fleet-cache / byte-planning lanes.

The contracts pinned here (ISSUE r23 acceptance):

- per-format blob roundtrips are PINNED: raw is the r22 byte layout
  unchanged (the ``--blob-format raw`` escape hatch), int8 on an int8
  pool is a lossless byte-equal passthrough, lossy int8/int4 decode
  by exactly the declared quant.py math and report their error —
  never silently;
- a corrupt coded blob is the same typed SpillCorrupt miss as a
  corrupt raw blob;
- cross-request dedup folds content-identical FULL pages: refcounts
  rise, the duplicate page returns to the free list under a
  ``dedup_hit`` ledger reason, the shared page moves to a
  ("dedup", key) owner, eviction happens at refcount 0 only, and the
  deadline/close paths stay zero-leak with a clean dedup-aware
  ledger reconcile;
- greedy outputs are BIT-IDENTICAL with dedup on vs off and with
  losslessly-packed blobs vs raw, across chunked x speculative x
  mesh;
- fetch_pages pages through cursor/next_cursor so chains longer than
  FETCH_PAGES_CAP hand off whole;
- spill tiers export logical (raw-equivalent) bytes next to physical
  occupancy;
- the router's fleet-cache lane hints a non-holder pick at the
  least-loaded advertising peer, and forecast placement steers
  around replicas whose fresh capacity forecast is pressed.
"""

import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.distributed.topology import make_serving_mesh
from paddle_tpu.inference import (PageAllocator, SpeculativeConfig,
                                  create_decode_engine)
from paddle_tpu.inference.page_ledger import PageLedger
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.quantization.quant import (dequantize_kv_int4_np,
                                           dequantize_kv_np,
                                           quantize_kv_int4_np,
                                           quantize_kv_np)
from paddle_tpu.serving import (HostSpillTier, PrefixCache,
                                ServingMetrics, ServingServer,
                                SpillCorrupt, client_request)
from paddle_tpu.serving.prefix_cache import (BLOB_FORMATS,
                                             blob_logical_bytes,
                                             pack_page_blob,
                                             unpack_page_blob)
from paddle_tpu.serving.server import fetch_page_blobs
from paddle_tpu.serving.supervisor import FailoverRouter


@pytest.fixture(scope="module", autouse=True)
def _compile_cache(module_compile_cache):
    """Engine-heavy file: reuse XLA compiles across tests."""
    yield


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


ENGINE_KW = dict(num_slots=2, page_size=8, max_seq_len=96, num_pages=12)


def _engine(m, **kw):
    merged = dict(ENGINE_KW)
    merged.update(kw)
    return create_decode_engine(m, **merged)


# 19 tokens = 2 full shareable blocks at page_size 8
PROMPT = np.arange(3, 22, dtype=np.int32)
OTHER = np.arange(40, 61, dtype=np.int32)
MNT = 6


def _layers(int8=False, nl=3, shape=(8, 2, 4), seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for _ in range(nl):
        if int8:
            k = rng.integers(-128, 127, shape).astype(np.int8)
            v = rng.integers(-128, 127, shape).astype(np.int8)
            ks = rng.random(shape[:2]).astype(np.float32)
            vs = rng.random(shape[:2]).astype(np.float32)
        else:
            k = rng.standard_normal(shape).astype(np.float32)
            v = rng.standard_normal(shape).astype(np.float32)
            ks = vs = None
        out.append((k, v, ks, vs))
    return out


def _assert_layers_byte_equal(a, b):
    assert len(a) == len(b)
    for la, lb in zip(a, b):
        for x, y in zip(la, lb):
            if x is None:
                assert y is None
                continue
            assert x.dtype == y.dtype and x.shape == y.shape
            assert x.tobytes() == y.tobytes()


# ---------------------------------------------------------------------------
# Blob codecs (no jax): per-format roundtrip pins
# ---------------------------------------------------------------------------

class TestBlobCodecs:
    def test_raw_fmt_is_the_r22_byte_layout(self):
        """The escape hatch: fmt="raw" produces byte-for-byte the blob
        the default (pre-r23) call produces — 4-field meta, no format
        marker anywhere in the frame."""
        for int8 in (False, True):
            layers = _layers(int8=int8)
            assert pack_page_blob(layers, fmt="raw") == \
                pack_page_blob(layers)

    def test_bad_format_rejected(self):
        with pytest.raises(ValueError):
            pack_page_blob(_layers(), fmt="int2")
        assert BLOB_FORMATS == ("raw", "int8", "int4")

    def test_int8_on_int8_pool_is_lossless_passthrough(self):
        """int8 pages ARE the int8 encoding: packing an int8 pool as
        fmt="int8" must be BYTE-EQUAL to raw (no stats, no error)."""
        layers = _layers(int8=True)
        stats = {}
        blob = pack_page_blob(layers, fmt="int8", stats=stats)
        assert blob == pack_page_blob(layers, fmt="raw")
        assert stats == {}  # lossless: nothing to report
        _assert_layers_byte_equal(unpack_page_blob(blob), layers)

    def test_int8_fp_decode_math_pinned_and_reported(self):
        """Lossy int8 on a float pool: decode is exactly
        ``dequantize_kv_np(quantize_kv_np(x))`` and the encode reports
        lossy_pages / max_abs_err — never silent."""
        layers = _layers(int8=False)
        stats = {}
        back = unpack_page_blob(
            pack_page_blob(layers, fmt="int8", stats=stats))
        assert stats["lossy_pages"] == 1 and stats["max_abs_err"] > 0
        exp_err = 0.0
        for (k, v, _ks, _vs), (bk, bv, bks, bvs) in zip(layers, back):
            assert bks is None and bvs is None
            for x, y in ((k, bk), (v, bv)):
                exp = dequantize_kv_np(*quantize_kv_np(x))
                assert np.array_equal(y, exp)
                exp_err = max(exp_err,
                              float(np.max(np.abs(x - exp))))
        assert stats["max_abs_err"] == pytest.approx(exp_err)

    @pytest.mark.parametrize("head_dim", [4, 5])  # even + odd nibbles
    def test_int4_decode_math_pinned(self, head_dim):
        layers = _layers(int8=False, shape=(8, 2, head_dim))
        stats = {}
        back = unpack_page_blob(
            pack_page_blob(layers, fmt="int4", stats=stats))
        assert stats["lossy_pages"] == 1
        for (k, v, _ks, _vs), (bk, bv, _a, _b) in zip(layers, back):
            for x, y in ((k, bk), (v, bv)):
                exp = dequantize_kv_int4_np(*quantize_kv_int4_np(x),
                                            head_dim)
                assert np.array_equal(y, exp)

    def test_int4_on_int8_pool_requantizes_to_pool_layout(self):
        """Coded blob over an int8 pool decodes back to the POOL's
        layout (int8 q + scales) by exactly the declared math:
        dequant pool -> int4 roundtrip -> re-quantize via the same
        quantizer the append path uses."""
        layers = _layers(int8=True, shape=(8, 2, 4))
        back = unpack_page_blob(pack_page_blob(layers, fmt="int4"))
        for (k, v, ks, vs), (bk, bv, bks, bvs) in zip(layers, back):
            for q, s, bq, bs in ((k, ks, bk, bks), (v, vs, bv, bvs)):
                assert bq.dtype == np.int8 and bs is not None
                x = dequantize_kv_np(q, s)
                x4 = dequantize_kv_int4_np(*quantize_kv_int4_np(x),
                                           x.shape[-1])
                eq, es = quantize_kv_np(x4)
                assert np.array_equal(bq, eq)
                assert np.array_equal(bs, es.astype(bs.dtype))

    def test_coded_blobs_shrink_the_wire(self):
        """The point of the exercise: 2-4x fewer bytes than raw fp."""
        layers = _layers(int8=False, shape=(8, 2, 16))
        raw = pack_page_blob(layers, fmt="raw")
        i8 = pack_page_blob(layers, fmt="int8")
        i4 = pack_page_blob(layers, fmt="int4")
        assert len(i8) < 0.5 * len(raw)
        assert len(i4) < len(i8)

    def test_corrupt_coded_blob_is_typed(self):
        for fmt in ("int8", "int4"):
            blob = pack_page_blob(_layers(), fmt=fmt)
            with pytest.raises(SpillCorrupt):
                unpack_page_blob(blob[:-1] +
                                 bytes([blob[-1] ^ 0xFF]))
            with pytest.raises(SpillCorrupt):
                unpack_page_blob(blob[: len(blob) // 2])

    def test_blob_logical_bytes_is_raw_equivalent(self):
        for int8 in (False, True):
            layers = _layers(int8=int8, shape=(8, 2, 16))
            raw = pack_page_blob(layers, fmt="raw")
            logical = blob_logical_bytes(raw)
            # raw: logical == payload bytes exactly
            expected = sum(
                sum(a.nbytes for a in lay if a is not None)
                for lay in layers)
            assert logical == expected
            # coded: logical unchanged (same page), physical smaller
            coded = pack_page_blob(layers, fmt="int4")
            assert blob_logical_bytes(coded) == expected
            assert len(coded) < logical
        # unparseable input falls back to physical size
        assert blob_logical_bytes(b"junk") == 4


# ---------------------------------------------------------------------------
# Spill tiers: logical vs physical byte accounting
# ---------------------------------------------------------------------------

class TestTierLogicalBytes:
    def test_logical_bytes_follow_put_remove_evict(self):
        layers = _layers(int8=False, shape=(8, 2, 16))
        coded = pack_page_blob(layers, fmt="int4")
        logical = blob_logical_bytes(coded)
        t = HostSpillTier(1 << 20)
        t.put(b"a", coded)
        t.put(b"b", coded)
        assert t.logical_bytes == 2 * logical
        assert t.occupancy_bytes == 2 * len(coded)
        assert t.stats()["logical_bytes"] == 2 * logical
        t.check_consistent()
        t.remove(b"a")
        assert t.logical_bytes == logical
        # byte-budget eviction drops the logical share too
        t2 = HostSpillTier(int(len(coded) * 1.5))
        t2.put(b"a", coded)
        t2.put(b"b", coded)  # evicts a
        assert t2.blob_count == 1 and t2.logical_bytes == logical
        t2.check_consistent()


# ---------------------------------------------------------------------------
# Cross-request dedup: refcount lifecycle (no jax)
# ---------------------------------------------------------------------------

class TestDedupUnit:
    def _two_requests(self, dedup=True, led=None):
        """Two unrelated requests with the same 2-block prompt, both
        prefilled privately (the concurrent-prefill race): request 2's
        insert collides with request 1's entries."""
        pc = PrefixCache(4, dedup=dedup)
        alloc = PageAllocator(10, ledger=led)
        prompt = np.arange(9, dtype=np.int32)  # 2 full blocks + 1
        rows = {}
        keys = {}
        for rid in (1, 2):
            pages = alloc.alloc(rid, 3)
            rows[rid] = np.array(pages, dtype=np.int32)
            keys[rid] = pc.insert(prompt, rows[rid], alloc, rid, 4, ())
        return pc, alloc, rows, keys

    def test_fold_refcounts_and_frees_duplicates(self):
        pc, alloc, rows, keys = self._two_requests()
        assert keys[2] == keys[1]
        assert pc.dedup_hits == 2
        # request 2's table row was retargeted at the shared pages
        assert list(rows[2][:2]) == list(rows[1][:2])
        # the duplicate pages went back to the free list: 10 total,
        # 2 shared + 1 tail each = 4 held
        assert alloc.free_count == 6
        # shared pages live under ("dedup", key) owners
        owners = alloc.owners()
        for k in keys[1]:
            assert ("dedup", k) in owners
            assert ("prefix", k) not in owners
        for ent in pc._entries.values():
            assert ent.refcount == 2 and ent.dedup
        # drained audit: request owners freed, cache books balance
        pc.release(keys[1])
        pc.release(keys[2])
        alloc.free(1)
        alloc.free(2)
        pc.check_consistent(alloc)

    def test_eviction_at_refcount_zero_only(self):
        pc, alloc, rows, keys = self._two_requests()
        alloc.free(1)
        alloc.free(2)
        # both requests still hold references: nothing evictable
        assert not pc.evict_until(alloc, alloc.num_pages)
        pc.release(keys[1])
        assert not pc.evict_until(alloc, alloc.num_pages)
        pc.release(keys[2])
        # refcount 0: entries stay cached (dedup flag persists) until
        # pressure evicts them, then the dedup owners free cleanly
        assert all(e.refcount == 0 and e.dedup
                   for e in pc._entries.values())
        assert pc.evict_until(alloc, alloc.num_pages)
        assert not pc._entries
        alloc.check_no_leak()

    def test_ledger_reconcile_clean_with_dedup_reason(self):
        led = PageLedger()
        pc, alloc, rows, keys = self._two_requests(led=led)
        rec = led.reconcile(alloc)
        assert rec["ok"], rec
        reasons = [e.get("reason") for e in led.tail(16)]
        assert "dedup_hit" in reasons
        pc.release(keys[1])
        pc.release(keys[2])
        alloc.free(1)
        alloc.free(2)
        pc.clear(alloc)
        alloc.check_no_leak()
        rec = led.reconcile(alloc)
        assert rec["ok"] and rec["live_owners"] == 0

    def test_dedup_off_keeps_private_pages(self):
        """The escape hatch: dedup=False is the pre-r23 collision
        behavior — refcount rises but request 2 keeps its own pages."""
        pc, alloc, rows, keys = self._two_requests(dedup=False)
        assert pc.dedup_hits == 0
        assert list(rows[2][:2]) != list(rows[1][:2])
        assert alloc.free_count == 4  # nothing returned
        assert not any(e.dedup for e in pc._entries.values())
        pc.release(keys[1])
        pc.release(keys[2])
        alloc.free(1)
        alloc.free(2)
        pc.clear(alloc)
        alloc.check_no_leak()

    def test_occupancy_reports_dedup_class(self):
        """allocator.occupancy() splits cross-request shared pages
        into their own class and the books still sum to the pool."""
        pc = PrefixCache(4, dedup=True)
        alloc = PageAllocator(10)
        prompt = np.arange(9, dtype=np.int32)
        for rid in (1, 2):
            row = np.array(alloc.alloc(rid, 3), dtype=np.int32)
            pc.insert(prompt, row, alloc, rid, 4, ())
        occ = alloc.occupancy()
        assert occ["dedup"] == 2
        assert occ["inflight"] == 2  # each request's private tail
        assert occ["prefix_device"] == 0
        assert occ["free"] == 6
        assert (occ["inflight"] + occ["prefix_device"] + occ["dedup"]
                + occ["reserved"] + occ["free"]) == 10


# ---------------------------------------------------------------------------
# Engine-level dedup: deterministic fold, bit-identity, zero leak
# ---------------------------------------------------------------------------

def _run_engine(model, prompts, mnt=MNT, **kw):
    eng = _engine(model, **kw)
    try:
        rids = [eng.submit(p, max_new_tokens=mnt) for p in prompts]
        done = eng.run()
        return [done[r] for r in rids], eng
    except Exception:
        eng.close()
        raise


class TestDedupEngine:
    def test_chunked_concurrent_prefill_folds_deterministically(
            self, model):
        """Chunked prefill keeps both same-prompt requests in flight
        past each other's admission match, so the second insert always
        takes the collision branch: dedup_hits counts the 2 full
        blocks, occupancy reports them, books balance after close."""
        pc = PrefixCache(8, dedup=True)
        outs, eng = _run_engine(model, [PROMPT, PROMPT, OTHER],
                                prefix_cache=pc,
                                prefill_chunk_tokens=8)
        try:
            assert pc.dedup_hits == 2
            occ = eng.allocator.occupancy()
            assert occ["dedup"] == 2
            ts = pc.tier_stats()["device"]
            assert ts["dedup_pages"] == 2 and ts["dedup_hits"] == 2
            rec = eng.ledger.reconcile(eng.allocator)
            assert rec["ok"], rec
        finally:
            eng.close()  # asserts check_no_leak internally

    @pytest.mark.parametrize("mode_kw", [
        {},
        {"prefill_chunk_tokens": 8},
        {"speculative": SpeculativeConfig(k=2)},
    ], ids=["plain", "chunked", "spec"])
    def test_bit_identical_dedup_on_vs_off(self, model, mode_kw):
        base, eng0 = _run_engine(
            model, [PROMPT, PROMPT, OTHER],
            prefix_cache=PrefixCache(8, dedup=False), **mode_kw)
        eng0.close()
        outs, eng1 = _run_engine(
            model, [PROMPT, PROMPT, OTHER],
            prefix_cache=PrefixCache(8, dedup=True), **mode_kw)
        eng1.close()
        for a, b in zip(base, outs):
            assert np.array_equal(a, b)

    def test_bit_identical_dedup_on_vs_off_mesh2(self, model):
        base, eng0 = _run_engine(
            model, [PROMPT, PROMPT, OTHER],
            prefix_cache=PrefixCache(8, dedup=False),
            mesh=make_serving_mesh(2))
        eng0.close()
        outs, eng1 = _run_engine(
            model, [PROMPT, PROMPT, OTHER],
            prefix_cache=PrefixCache(8, dedup=True),
            mesh=make_serving_mesh(2))
        eng1.close()
        for a, b in zip(base, outs):
            assert np.array_equal(a, b)

    def test_deadline_mid_decode_zero_leak_with_dedup(self, model):
        """A request whose pages were folded onto shared entries dies
        by deadline mid-decode: its pins release, the shared pages
        stay cache-owned, reconcile is clean."""
        pc = PrefixCache(8, dedup=True)
        eng = _engine(model, prefix_cache=pc,
                      prefill_chunk_tokens=8)
        try:
            eng.submit(PROMPT, max_new_tokens=4)
            r2 = eng.submit(PROMPT, max_new_tokens=50,
                            deadline_t=time.monotonic() + 60.0)
            for _ in range(8):  # both prefills complete + fold
                eng.step()
            assert pc.dedup_hits == 2
            expired = eng.expire_deadlines(
                now=time.monotonic() + 61.0)
            assert [r.req_id for r in expired] == [r2]
            eng.run()
            pc.check_consistent(eng.allocator)
            rec = eng.ledger.reconcile(eng.allocator)
            assert rec["ok"], rec
        finally:
            eng.close()


# ---------------------------------------------------------------------------
# Engine-level blob formats: lossless pins + reported lossy deltas
# ---------------------------------------------------------------------------

class TestBlobFormatEngine:
    def _spill_all(self, eng):
        pc = eng._prefix_cache
        assert pc.evict_until(eng.allocator, eng.allocator.num_pages)
        return pc

    def test_int8_pool_blobs_lossless_and_bit_identical(self, model):
        """paged_int8 engines pack int8 bytes losslessly: the int8
        blob format produces byte-equal blobs and bit-identical
        restored greedy output vs raw."""
        results = {}
        for fmt in ("raw", "int8"):
            pc = PrefixCache(8, spill_bytes=1 << 20, blob_format=fmt)
            eng = _engine(model, prefix_cache=pc, kv_int8=True)
            try:
                rid = eng.submit(PROMPT, max_new_tokens=MNT)
                first = eng.run()[rid]
                self._spill_all(eng)
                blobs = {k: pc.tiers[0]._load(k)
                         for k in list(pc.tiers[0]._index)}
                rid = eng.submit(PROMPT, max_new_tokens=MNT)
                again = eng.run()[rid]
                assert pc.restored_pages > 0
                assert np.array_equal(first, again)
                results[fmt] = (first, blobs)
                assert pc.codec_stats == {}  # lossless: no deltas
            finally:
                eng.close()
        assert np.array_equal(results["raw"][0], results["int8"][0])
        # the int8 "encoding" of an int8 pool IS the raw layout
        assert results["raw"][1] == results["int8"][1]

    def test_fp_lossy_format_reports_never_silent(self, model):
        """A float engine opting into int8 blobs trades exactness for
        bytes: restore still works, and the accuracy delta is in
        codec_stats — the never-silent rule."""
        pc = PrefixCache(8, spill_bytes=1 << 20, blob_format="int8")
        eng = _engine(model, prefix_cache=pc)
        try:
            rid = eng.submit(PROMPT, max_new_tokens=MNT)
            base = eng.run()[rid]
            self._spill_all(eng)
            assert pc.codec_stats["lossy_pages"] >= 2
            assert pc.codec_stats["max_abs_err"] > 0
            rid = eng.submit(PROMPT, max_new_tokens=MNT)
            out = eng.run()[rid]
            assert pc.restored_pages > 0
            assert len(out) == len(base)
            pc.check_consistent(eng.allocator)
        finally:
            eng.close()

    def test_escape_hatch_raw_plus_no_dedup_is_r22(self, model):
        """blob_format="raw" + dedup=False: blobs byte-identical to
        the pre-r23 packer and greedy output identical to a bare
        engine."""
        eng0 = _engine(model)
        rid = eng0.submit(PROMPT, max_new_tokens=MNT)
        base = eng0.run()[rid]
        eng0.close()
        pc = PrefixCache(8, spill_bytes=1 << 20, blob_format="raw",
                         dedup=False)
        eng = _engine(model, prefix_cache=pc)
        try:
            rid = eng.submit(PROMPT, max_new_tokens=MNT)
            assert np.array_equal(eng.run()[rid], base)
            self._spill_all(eng)
            import struct
            for k in list(pc.tiers[0]._index):
                blob = pc.tiers[0]._load(k)
                meta_len, _pl = struct.unpack("<HI", blob[4:10])
                meta = blob[10:10 + meta_len].decode("ascii")
                # 4-field meta: no format marker on the wire at all
                assert meta.count(";") == 3
                # and the DEFAULT (pre-r23 signature) packer
                # reproduces the stored bytes exactly
                assert pack_page_blob(unpack_page_blob(blob)) == blob
        finally:
            eng.close()


# ---------------------------------------------------------------------------
# fetch_pages cursor pagination
# ---------------------------------------------------------------------------

class TestFetchPagesPagination:
    def test_cursor_windows_hand_off_whole_chain(self, model,
                                                 monkeypatch):
        monkeypatch.setattr(ServingServer, "FETCH_PAGES_CAP", 1)
        srv = ServingServer(model, role="prefill",
                            metrics=ServingMetrics(
                                registry=StatRegistry()),
                            **ENGINE_KW)
        srv.start()
        try:
            ack = client_request(
                "127.0.0.1", srv.port,
                {"op": "generate", "prompt": PROMPT.tolist(),
                 "max_new_tokens": 1, "prefill_only": True},
                timeout_s=120)
            assert ack.get("prefilled") and len(ack["keys"]) == 2
            # raw wire: first window carries next_cursor, second ends
            r1 = client_request("127.0.0.1", srv.port,
                               {"op": "fetch_pages",
                                "heads": [ack["keys"][0]]})
            assert len(r1["blobs"]) == 1 and r1["truncated"]
            assert r1["next_cursor"] == 1
            r2 = client_request("127.0.0.1", srv.port,
                               {"op": "fetch_pages",
                                "heads": [ack["keys"][0]],
                                "cursor": r1["next_cursor"]})
            assert len(r2["blobs"]) == 1
            assert "next_cursor" not in r2
            assert set(r1["blobs"]) | set(r2["blobs"]) == \
                set(ack["keys"])
            # the client loops the cursor transparently
            blobs, missing, nbytes = fetch_page_blobs(
                "127.0.0.1", srv.port, heads=[ack["keys"][0]])
            assert len(blobs) == 2 and not missing and nbytes > 0
            # malformed cursor is a typed BadRequest
            r = client_request("127.0.0.1", srv.port,
                              {"op": "fetch_pages",
                               "keys": [ack["keys"][0]],
                               "cursor": "zz"})
            assert r["error"] == "BadRequest"
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Router: fleet-cache lane + forecast placement (stub supervisor)
# ---------------------------------------------------------------------------

class _StubReplica:
    def __init__(self, idx, port=0, role="mixed", keys=(), load=0):
        self.idx = idx
        self.port = port
        self.role = role
        self.ready = True
        self.restarts = 0
        self.page_size = 8
        self.load = load
        self.prefix_keys = frozenset(keys)
        self.prefix_truncated = False
        self.capacity = None
        self.capacity_t = 0.0

    def alive(self):
        return True


class _StubSup:
    def __init__(self, reps, host="127.0.0.1"):
        self.replicas = reps
        self.host = host
        self.probe_interval_s = 0.5

    def live(self):
        return [r for r in self.replicas if r.ready]


class TestFleetCacheRouting:
    KEY = "ab" * 16

    def test_hint_names_least_loaded_advertising_peer(self):
        reps = [_StubReplica(0, port=7001),
                _StubReplica(1, port=7002, keys=[self.KEY], load=3),
                _StubReplica(2, port=7003, keys=[self.KEY], load=1)]
        router = FailoverRouter(_StubSup(reps))
        hint = router._fleet_cache_hint(reps[0], self.KEY)
        assert hint == {"host": "127.0.0.1", "port": 7003}
        assert router.fleet_cache_hints_total == 1

    def test_no_hint_when_pick_holds_or_no_peer_or_lane_off(self):
        reps = [_StubReplica(0, port=7001, keys=[self.KEY]),
                _StubReplica(1, port=7002, keys=[self.KEY])]
        router = FailoverRouter(_StubSup(reps))
        # the pick already holds the chain
        assert router._fleet_cache_hint(reps[0], self.KEY) is None
        # unkeyed request
        assert router._fleet_cache_hint(reps[0], None) is None
        # no live peer advertises it
        solo = [_StubReplica(0, port=7001)]
        router = FailoverRouter(_StubSup(solo))
        assert router._fleet_cache_hint(solo[0], self.KEY) is None
        # lane disabled
        router = FailoverRouter(_StubSup(reps), fleet_cache=False)
        assert router._fleet_cache_hint(reps[0], self.KEY) is None
        assert router.fleet_cache_hints_total == 0

    def test_forecast_placement_steers_off_pressed_replica(self):
        reps = [_StubReplica(0, port=7001), _StubReplica(1, port=7002)]
        router = FailoverRouter(_StubSup(reps),
                                forecast_placement=True)
        # replica 0's FRESH forecast says exhaustion in 1s
        reps[0].capacity = {"forecast": {"tte_s": 1.0}}
        reps[0].capacity_t = time.monotonic()
        assert router._forecast_pressed(reps[0])
        for _ in range(4):
            assert router._pick(set()).idx == 1
        assert router.forecast_steers_total == 4
        # a stale forecast is advisory only: no steering
        reps[0].capacity_t = time.monotonic() - 3600.0
        assert not router._forecast_pressed(reps[0])
        # never filter-to-empty: both pressed -> plain routing
        for r in reps:
            r.capacity = {"forecast": {"tte_s": 0.5}}
            r.capacity_t = time.monotonic()
        assert router._pick(set()) is not None

    def test_forecast_placement_default_off(self):
        reps = [_StubReplica(0), _StubReplica(1)]
        router = FailoverRouter(_StubSup(reps))
        reps[0].capacity = {"forecast": {"tte_s": 0.1}}
        reps[0].capacity_t = time.monotonic()
        picked = {router._pick(set()).idx for _ in range(4)}
        assert picked == {0, 1}  # round-robin untouched
        assert router.forecast_steers_total == 0


# ---------------------------------------------------------------------------
# Forecast-aware byte admission (engine)
# ---------------------------------------------------------------------------

class TestForecastAdmission:
    def test_default_off_and_snapshot_surface(self, model):
        eng = _engine(model)
        try:
            snap = eng.capacity_snapshot()
            assert snap["forecast_admission"] is False
            assert snap["forecast_denials"] == 0
        finally:
            eng.close()

    def test_burn_charged_against_instant_fit(self, model):
        """With forecast admission on, a request that fits the
        instant free count but not the projected burn over its
        lifetime is denied (counted), then admitted once pressure
        clears."""
        eng = _engine(model, forecast_admission=True)
        try:
            assert eng.capacity_snapshot()["forecast_admission"]
            rid = eng.submit(PROMPT, max_new_tokens=MNT)
            out = eng.run()
            assert len(out[rid]) == len(PROMPT) + MNT
            # steady state: no spurious denials on an idle pool
            snap = eng.capacity_snapshot()
            assert snap["forecast_denials"] == 0

            class _Req:
                prompt = np.arange(9, dtype=np.int32)
                max_new_tokens = 4

            # synthetic pressure: a positive burn rate and a known
            # decode cadence force the projected-burn branch
            eng.decode_ema_s = 1.0
            free0 = eng.allocator.free_count

            def fake_forecast(entries, alpha=0.3):
                return {"samples": 8, "free_pages": free0,
                        "rate_pages_per_s": float(free0),
                        "tte_s": 1.0}
            from paddle_tpu.inference import page_ledger as pl
            orig = pl.forecast_exhaustion
            pl.forecast_exhaustion = fake_forecast
            try:
                assert not eng._fits(_Req())
            finally:
                pl.forecast_exhaustion = orig
            assert eng.capacity_snapshot()["forecast_denials"] == 1
        finally:
            eng.close()
