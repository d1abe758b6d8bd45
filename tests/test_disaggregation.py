"""Disaggregated prefill/decode serving (r20): prefill-class replicas
ship finished KV pages to decode replicas over the wire.

The contracts pinned here (ISSUE r20 acceptance):

- greedy outputs are BIT-IDENTICAL handoff-vs-local-prefill across
  the feature matrix (fp, paged_int8, chunked prefill, speculative,
  their combination, and a 2-way mesh), and ``role="mixed"`` is the
  pre-r20 replica (no default spill tier, no handoff accounting);
- every handoff failure — dead peer, typed peer error, corrupt blob,
  partial chain — is a COUNTED fallback to local prefill with the
  same greedy tokens, never a hang, and every new exit path leaves
  zero leaked pages on both sides;
- ``advertised_keys_info`` orders chain heads by the most recent
  touch anywhere in the chain and surfaces ``truncated`` so a capped
  advertisement cannot read as "not resident";
- the drain handoff (``handoff_chains`` / ``Supervisor.drain_replica``)
  ships a victim's chains to survivors by the same rendezvous the
  router steers with;
- the engine rejects ``max_seq_len`` beyond the model's position
  table TYPED (the silent-NaN corruption the r20 bench surfaced).
"""

import socket
import threading
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.inference import (PageAllocator, SpeculativeConfig,
                                  create_decode_engine)
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import (PrefixCache, ServingMetrics,
                                ServingServer, client_request)
from paddle_tpu.serving.metrics import merge_exports
from paddle_tpu.serving.prefix_cache import _block_hash, pack_page_blob
from paddle_tpu.serving.server import PageFetchFailed, fetch_page_blobs
from paddle_tpu.serving.supervisor import (FailoverRouter,
                                           handoff_chains,
                                           rendezvous_owner)


@pytest.fixture(scope="module", autouse=True)
def _compile_cache(module_compile_cache):
    """Engine-heavy file: reuse XLA compiles across tests."""
    yield


def _model():
    pt.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def model():
    return _model()


ENGINE_KW = dict(num_slots=2, page_size=8, max_seq_len=96)

# 19 tokens = 2 full shareable blocks at page_size 8: a handoff moves
# exactly 2 pages and chained prefill covers the 3-token suffix
PROMPT = list(range(3, 22))
MNT = 6


def _free_dead_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _reference(mode_kw, prompt=PROMPT, mnt=MNT):
    """Greedy tokens from a bare engine with the same config — the
    handoff runs must reproduce these bit-exactly."""
    eng = create_decode_engine(_model(), **ENGINE_KW, **mode_kw)
    try:
        rid = eng.submit(np.asarray(prompt, np.int32), mnt)
        return [int(t) for t in eng.run()[rid]][len(prompt):]
    finally:
        eng.close()


def _server(role, mode_kw=None, **kw):
    srv = ServingServer(
        _model(), role=role,
        metrics=ServingMetrics(registry=StatRegistry()),
        **{**ENGINE_KW, **(mode_kw or {}), **kw})
    srv.start()
    return srv


def _leak_ok(*srvs):
    for s in srvs:
        chk = client_request("127.0.0.1", s.port, {"op": "leak_check"})
        assert chk.get("ok"), chk


def _handoff_pair(mode_kw):
    """(prefill server, decode server) with identical weights/config."""
    return _server("prefill", mode_kw), _server("decode", mode_kw)


def _do_handoff(pf, dec, prompt=PROMPT, mnt=MNT, fetch_port=None):
    """Run the two-hop handoff by hand (what the role-aware router
    does): prefill_only on the prefill replica, then generate on the
    decode replica with a fetch_from hint naming it."""
    ack = client_request("127.0.0.1", pf.port,
                         {"op": "generate", "prompt": prompt,
                          "max_new_tokens": 1, "prefill_only": True},
                         timeout_s=120)
    assert ack.get("prefilled"), ack
    out = client_request(
        "127.0.0.1", dec.port,
        {"op": "generate", "prompt": prompt, "max_new_tokens": mnt,
         "fetch_from": {"host": "127.0.0.1",
                        "port": fetch_port or pf.port}},
        timeout_s=120)
    assert "error" not in out, out
    return ack, out


# ---------------------------------------------------------------------------
# advertised_keys_info: recency + truncation (satellite 1)
# ---------------------------------------------------------------------------

class TestAdvertisedKeys:
    def _cache_with_chains(self, n_chains, blocks=2, page=4):
        pc = PrefixCache(page)
        alloc = PageAllocator(4 * n_chains * blocks)
        chains = []
        for c in range(n_chains):
            prompt = np.asarray([100 * c + i
                                 for i in range(page * blocks + 1)],
                                np.int32)
            pages = alloc.alloc(("req", c), blocks + 1)
            row = np.array(pages, dtype=np.int32)
            keys = pc.insert(prompt, row, alloc, ("req", c), page, ())
            pc.release(keys)
            alloc.free(("req", c))
            chains.append((prompt, keys))
        return pc, alloc, chains

    def test_truncation_flag_and_cap(self):
        pc, _a, chains = self._cache_with_chains(6)
        info = pc.advertised_keys_info(limit=4)
        assert len(info["keys"]) == 4 and info["truncated"] is True
        info = pc.advertised_keys_info(limit=16)
        assert len(info["keys"]) == 6 and info["truncated"] is False
        # back-compat wrapper returns the bare list
        assert pc.advertised_keys(limit=16) == info["keys"]

    def test_deep_touch_refreshes_head_recency(self):
        """The r20 fix: traffic touching only a DEEP block of chain 0
        must keep chain 0's HEAD at the front of a truncated
        advertisement (the head entry's own last_used goes stale)."""
        pc, _a, chains = self._cache_with_chains(3)
        # whole-chain traffic on chains 1 then 2, then a DEEP-only
        # touch on chain 0 (what an insert() extending the chain, or a
        # partial re-acquire, does): chain 0's head entry keeps its old
        # tick, but the chain's RECENCY is its deepest touch
        for c in (1, 2):
            keys, _ = pc.match(chains[c][0])
            pc.acquire(keys)
            pc.release(keys)
        keys0, _ = pc.match(chains[0][0])
        pc.acquire(keys0[1:])  # leaf only: head last_used stays stale
        pc.release(keys0[1:])
        info = pc.advertised_keys_info(limit=1)
        assert info["truncated"] is True
        # pre-r20 ordering (head's own last_used) would advertise
        # chain 2 here and drop the hottest chain off the cap
        assert info["keys"] == [chains[0][1][0].hex()]


# ---------------------------------------------------------------------------
# Cache-level wire export/import
# ---------------------------------------------------------------------------

class _FakeIO:
    def __init__(self):
        self.spliced = {}

    def read_page(self, page):
        return [(np.full((4, 2, 3), page * 10 + l, np.float32),
                 np.full((4, 2, 3), page * 10 + l, np.float32),
                 None, None) for l in range(2)]

    def splice_page(self, pages, layers_list):
        for p, layers in zip(pages, layers_list):
            self.spliced[p] = float(layers[0][0].flat[0])


def _unit_cache(**kw):
    pc = PrefixCache(4, **kw)
    io = _FakeIO()
    pc.attach_device_io(io.read_page, io.splice_page)
    return pc, io


def _seed_chain(pc, alloc, prompt, owner="req"):
    n = pc._shareable_blocks(prompt)
    pages = alloc.alloc(owner, n + 1)
    row = np.array(pages, dtype=np.int32)
    keys = pc.insert(prompt, row, alloc, owner, pc.page_size, ())
    pc.release(keys)
    alloc.free(owner)
    return keys


class TestCacheWireOps:
    def test_chain_keys_are_pure_hashing(self):
        pc, _ = _unit_cache()
        prompt = np.arange(13, dtype=np.int32)
        keys = pc.chain_keys_for(prompt)
        assert len(keys) == 3  # (13-1)//4 full blocks
        # stateless: same prompt, same keys, no entries created
        assert pc.chain_keys_for(prompt) == keys
        assert not pc._entries

    def test_export_device_and_tier_blobs(self):
        pc, io = _unit_cache(spill_bytes=1 << 20)
        alloc = PageAllocator(8)
        prompt = np.arange(13, dtype=np.int32)
        keys = _seed_chain(pc, alloc, prompt)
        # spill one leaf; the rest stay device-resident
        assert pc.evict_until(alloc, alloc.free_count + 1)
        blobs, missing = pc.export_blobs(list(keys) + [b"\x00" * 8])
        assert set(blobs) == set(keys)
        assert missing == [b"\x00" * 8]
        assert pc.exported_pages == 3
        # every exported blob re-verifies (device pages were packed
        # fresh through pack_page_blob; tier blobs travel as stored)
        from paddle_tpu.serving.prefix_cache import unpack_page_blob
        for b in blobs.values():
            unpack_page_blob(b)

    def test_expand_heads_covers_device_and_spilled(self):
        pc, _io = _unit_cache(spill_bytes=1 << 20)
        alloc = PageAllocator(8)
        prompt = np.arange(13, dtype=np.int32)
        keys = _seed_chain(pc, alloc, prompt)
        # spill the whole chain (leaf-first)
        assert pc.evict_until(alloc, alloc.num_pages)
        assert set(pc.expand_heads([keys[0]])) == set(keys)
        # partially restore: device subtree + spilled members merge
        pc.restore_from_spill(prompt, (), alloc)
        assert set(pc.expand_heads([keys[0]])) == set(keys)

    def test_import_blobs_crc_and_skip(self):
        src, _ = _unit_cache(spill_bytes=1 << 20)
        alloc = PageAllocator(8)
        prompt = np.arange(13, dtype=np.int32)
        keys = _seed_chain(src, alloc, prompt)
        src.evict_until(alloc, alloc.num_pages)
        blobs, _ = src.export_blobs(keys)

        dst, dio = _unit_cache(spill_bytes=1 << 20)
        bad = dict(blobs)
        k_corrupt = keys[1]
        bad[k_corrupt] = bad[k_corrupt][:-1] + \
            bytes([bad[k_corrupt][-1] ^ 0xFF])
        rep = dst.import_blobs(bad, heads=keys[:1])
        assert rep["imported"] == 2 and rep["corrupt"] == 1
        assert dst.import_corrupt == 1
        assert rep["bytes"] > 0
        # head advertised from the tier
        assert keys[0].hex() in dst.advertised_keys_info()["keys"]
        # re-import: tier-resident keys land again (inclusive tiers
        # overwrite identical content), device-resident keys skip
        dalloc = PageAllocator(8)
        rkeys, rpages, info = dst.restore_from_spill(prompt, (), dalloc)
        assert rkeys == keys[:1]  # corrupt k2 broke the chain walk
        assert info["fetched"] == 1  # wire-fetched split reported
        rep2 = dst.import_blobs(blobs)
        assert rep2["skipped"] == 1  # restored key now device-resident
        assert rep2["imported"] == 2

    def test_import_without_tiers_skips_all(self):
        dst, _ = _unit_cache()  # no spill tier configured
        rep = dst.import_blobs({b"k": b"blob"})
        assert rep == {"imported": 0, "corrupt": 0, "skipped": 1,
                       "dropped": 0, "bytes": 0}

    def test_import_blob_too_big_for_tier_counts_dropped(self):
        src, _ = _unit_cache(spill_bytes=1 << 20)
        alloc = PageAllocator(8)
        prompt = np.arange(13, dtype=np.int32)
        keys = _seed_chain(src, alloc, prompt)
        src.evict_until(alloc, alloc.num_pages)
        blobs, _ = src.export_blobs(keys)
        # destination tier smaller than ONE blob: nothing can land —
        # the reply must say dropped, not imported (the drain-handoff
        # ack must never claim pages that are not resident), and the
        # dropped keys must not linger in the fetched-split record
        dst, _ = _unit_cache(spill_bytes=16)
        rep = dst.import_blobs(blobs, heads=keys[:1])
        assert rep["imported"] == 0 and rep["bytes"] == 0
        assert rep["dropped"] == len(blobs)
        assert dst.imported_pages == 0
        assert not dst._fetched_keys
        # the head never landed either: not advertised
        assert keys[0].hex() not in dst.advertised_keys_info()["keys"]


# ---------------------------------------------------------------------------
# fetch_pages / prefetch wire ops
# ---------------------------------------------------------------------------

class TestWireOps:
    def test_fetch_pages_roundtrip_and_missing(self, model):
        srv = _server("prefill")
        try:
            ack = client_request(
                "127.0.0.1", srv.port,
                {"op": "generate", "prompt": PROMPT,
                 "max_new_tokens": 1, "prefill_only": True},
                timeout_s=120)
            assert ack.get("prefilled") and len(ack["keys"]) == 2
            blobs, missing, nbytes = fetch_page_blobs(
                "127.0.0.1", srv.port, keys=ack["keys"] + ["ab" * 8])
            assert len(blobs) == 2 and nbytes > 0
            assert missing == ["ab" * 8]
            # heads expand server-side to the full chain
            blobs2, _m, _b = fetch_page_blobs(
                "127.0.0.1", srv.port, heads=[ack["keys"][0]])
            assert set(blobs2) == set(blobs)
            _leak_ok(srv)
        finally:
            srv.stop()

    def test_fetch_pages_bad_request_and_dead_peer(self, model):
        srv = _server("mixed")
        try:
            r = client_request("127.0.0.1", srv.port,
                               {"op": "fetch_pages"})
            assert r["error"] == "BadRequest"
            r = client_request("127.0.0.1", srv.port,
                               {"op": "fetch_pages", "keys": ["zz"]})
            assert r["error"] == "BadRequest"
        finally:
            srv.stop()
        with pytest.raises(PageFetchFailed):
            fetch_page_blobs("127.0.0.1", _free_dead_port(),
                             keys=["ab" * 8], timeout_s=2.0)

    def test_prefetch_lands_peer_chain_in_tiers(self, model):
        pf, dec = _handoff_pair({})
        try:
            ack = client_request(
                "127.0.0.1", pf.port,
                {"op": "generate", "prompt": PROMPT,
                 "max_new_tokens": 1, "prefill_only": True},
                timeout_s=120)
            rep = client_request(
                "127.0.0.1", dec.port,
                {"op": "prefetch", "host": "127.0.0.1",
                 "port": pf.port, "heads": [ack["keys"][0]]},
                timeout_s=120)
            assert rep.get("ok") and rep["imported"] == 2, rep
            assert rep["fetch_ms"] >= 0 and rep["missing"] == []
            # the prefetched chain is advertised and then SPLICED on
            # the next keyed generate — no fetch_from hint needed
            h = client_request("127.0.0.1", dec.port, {"op": "health"})
            assert ack["keys"][0] in h["prefix_keys"]
            ref = _reference({})
            out = client_request(
                "127.0.0.1", dec.port,
                {"op": "generate", "prompt": PROMPT,
                 "max_new_tokens": MNT}, timeout_s=120)
            assert out["generated"] == ref
            assert out["stats"]["restored_pages"] == 2
            assert out["stats"]["handoff_pages"] == 2
            _leak_ok(pf, dec)
        finally:
            pf.stop()
            dec.stop()

    def test_prefetch_typed_failures(self, model):
        dec = _server("decode")
        try:
            r = client_request("127.0.0.1", dec.port,
                               {"op": "prefetch", "heads": ["ab" * 8]})
            assert r["error"] == "BadRequest"  # no port
            r = client_request(
                "127.0.0.1", dec.port,
                {"op": "prefetch", "port": _free_dead_port(),
                 "heads": ["ab" * 8]}, timeout_s=120)
            assert r["error"] == "PageFetchFailed"
            assert dec.metrics.counter(
                "handoff_failures_total").get() == 1
        finally:
            dec.stop()


# ---------------------------------------------------------------------------
# Handoff-vs-local bit-identity across the feature matrix
# ---------------------------------------------------------------------------

MODES = {
    "fp": {},
    "int8": {"kv_int8": True},
    "chunked": {"prefill_chunk_tokens": 8},
    "spec": {"speculative": SpeculativeConfig(k=3)},
    "spec_int8_chunked": {"kv_int8": True,
                          "prefill_chunk_tokens": 8,
                          "speculative": SpeculativeConfig(k=3)},
}


class TestHandoffBitIdentity:
    @pytest.mark.parametrize("mode", sorted(MODES))
    def test_handoff_matches_local(self, mode):
        mode_kw = MODES[mode]
        ref = _reference(mode_kw)
        pf, dec = _handoff_pair(mode_kw)
        try:
            _ack, out = _do_handoff(pf, dec)
            assert out["generated"] == ref, mode
            st = out["stats"]
            assert st["handoff_pages"] == 2 and \
                st["restored_pages"] == 2, st
            assert st["handoff_ms"] > 0
            m = dec.metrics
            assert m.counter("handoff_pages_total").get() == 2
            assert m.counter("handoff_bytes_total").get() > 0
            assert m.counter("handoff_failures_total").get() == 0
            assert m.handoff_ms.snapshot()["count"] == 1
            assert "serving_handoff_ms_bucket" in m.prometheus_text()
            _leak_ok(pf, dec)
        finally:
            pf.stop()
            dec.stop()

    def test_handoff_matches_local_mesh2(self):
        from paddle_tpu.distributed.topology import make_serving_mesh
        mode_kw = {"mesh": make_serving_mesh(2)}
        ref = _reference(mode_kw)
        pf, dec = _handoff_pair(mode_kw)
        try:
            _ack, out = _do_handoff(pf, dec)
            assert out["generated"] == ref
            assert out["stats"]["handoff_pages"] == 2
            _leak_ok(pf, dec)
        finally:
            pf.stop()
            dec.stop()


# ---------------------------------------------------------------------------
# Handoff failure paths: counted typed fallbacks, zero leaks
# ---------------------------------------------------------------------------

class TestHandoffFallbacks:
    def test_dead_peer_falls_back_local(self, model):
        ref = _reference({})
        dec = _server("decode", handoff_timeout_s=2.0)
        try:
            out = client_request(
                "127.0.0.1", dec.port,
                {"op": "generate", "prompt": PROMPT,
                 "max_new_tokens": MNT,
                 "fetch_from": {"host": "127.0.0.1",
                                "port": _free_dead_port()}},
                timeout_s=120)
            assert out["generated"] == ref
            assert out["stats"]["handoff_pages"] == 0
            assert dec.metrics.counter(
                "handoff_failures_total").get() == 1
            _leak_ok(dec)
        finally:
            dec.stop()

    def test_corrupt_blobs_fall_back_local(self, model, monkeypatch):
        ref = _reference({})
        pf, dec = _handoff_pair({})
        try:
            import paddle_tpu.serving.server as server_mod
            real = server_mod.fetch_page_blobs

            def corrupting(*a, **kw):
                blobs, missing, nb = real(*a, **kw)
                return ({k: b[:-1] + bytes([b[-1] ^ 0xFF])
                         for k, b in blobs.items()}, missing, nb)

            monkeypatch.setattr(server_mod, "fetch_page_blobs",
                                corrupting)
            _ack, out = _do_handoff(pf, dec)
            assert out["generated"] == ref
            st = out["stats"]
            # nothing spliced from the wire; local prefill covered it
            assert st["handoff_pages"] == 0 and \
                st["restored_pages"] == 0
            assert dec.prefix_cache.import_corrupt == 2
            # all-corrupt import counts as a handoff failure
            assert dec.metrics.counter(
                "handoff_failures_total").get() == 1
            _leak_ok(pf, dec)
        finally:
            pf.stop()
            dec.stop()

    def test_partial_chain_splices_prefix(self, model, monkeypatch):
        """The peer delivers only the chain HEAD: restore splices what
        arrived and chained prefill covers the rest — bit-identical."""
        ref = _reference({})
        pf, dec = _handoff_pair({})
        try:
            import paddle_tpu.serving.server as server_mod
            real = server_mod.fetch_page_blobs

            def dropping(host, port, keys=None, heads=None, **kw):
                blobs, missing, nb = real(host, port, keys=keys,
                                          heads=heads, **kw)
                kept = dict(list(blobs.items())[:1])
                return kept, missing, sum(len(b) for b in kept.values())

            monkeypatch.setattr(server_mod, "fetch_page_blobs",
                                dropping)
            _ack, out = _do_handoff(pf, dec)
            assert out["generated"] == ref
            st = out["stats"]
            assert st["handoff_pages"] == 1 and \
                st["restored_pages"] == 1
            assert dec.metrics.counter(
                "handoff_failures_total").get() == 0
            _leak_ok(pf, dec)
        finally:
            pf.stop()
            dec.stop()

    def test_wrong_role_and_prefill_only_validation(self, model):
        pf = _server("prefill")
        try:
            r = client_request("127.0.0.1", pf.port,
                               {"op": "generate", "prompt": PROMPT,
                                "max_new_tokens": 4}, timeout_s=120)
            assert r["error"] == "WrongRole" and r["retryable"]
        finally:
            pf.stop()
        srv = ServingServer(model, prefix_cache=False,
                            metrics=ServingMetrics(
                                registry=StatRegistry()),
                            **ENGINE_KW)
        srv.start()
        try:
            r = client_request("127.0.0.1", srv.port,
                               {"op": "generate", "prompt": PROMPT,
                                "max_new_tokens": 1,
                                "prefill_only": True}, timeout_s=120)
            assert r["error"] == "BadRequest"
        finally:
            srv.stop()

    def test_bad_role_rejected_at_construction(self, model):
        with pytest.raises(ValueError, match="role"):
            ServingServer(model, role="verifier", **ENGINE_KW)


# ---------------------------------------------------------------------------
# role="mixed" is the pre-r20 replica
# ---------------------------------------------------------------------------

class TestMixedUnchanged:
    def test_no_default_tier_no_handoff_accounting(self, model):
        ref = _reference({})
        srv = _server("mixed")
        try:
            # no spill tier was defaulted (mixed = pre-r20 config)
            assert not srv.prefix_cache.tiers
            h = client_request("127.0.0.1", srv.port, {"op": "health"})
            assert h["role"] == "mixed"
            assert h["prefix_keys_truncated"] is False
            out = client_request(
                "127.0.0.1", srv.port,
                {"op": "generate", "prompt": PROMPT,
                 "max_new_tokens": MNT}, timeout_s=120)
            assert out["generated"] == ref
            # a fetch_from hint on a tier-less replica is ignored (no
            # failure counted — there is nowhere to land blobs)
            out = client_request(
                "127.0.0.1", srv.port,
                {"op": "generate", "prompt": PROMPT,
                 "max_new_tokens": MNT,
                 "fetch_from": {"port": _free_dead_port()}},
                timeout_s=120)
            assert out["generated"] == ref
            m = srv.metrics
            assert m.counter("handoff_pages_total").get() == 0
            assert m.counter("handoff_failures_total").get() == 0
            assert m.counter("handoff_bytes_total").get() == 0
            _leak_ok(srv)
        finally:
            srv.stop()


# ---------------------------------------------------------------------------
# Router role-aware dispatch (stub supervisor)
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

    def alive(self):
        return True


class _StubSup:
    def __init__(self, reps, host="127.0.0.1"):
        self.replicas = reps
        self.host = host

    def live(self):
        return [r for r in self.replicas if r.ready]


def _first_block_key(prompt, page_size=8):
    return _block_hash(None, np.asarray(prompt[:page_size],
                                        np.int32)).hex()


_NOTRACE = lambda ev, **kw: None  # noqa: E731


class TestRouterRoleDispatch:
    def test_pick_excludes_prefill_for_streams(self):
        reps = [_StubReplica(0, role="prefill"),
                _StubReplica(1, role="decode")]
        router = FailoverRouter(_StubSup(reps))
        for _ in range(4):
            assert router._pick(set(), exclude_prefill=True).idx == 1
        # prefill-only fleet: no decode-capable replica
        reps[1].ready = False
        assert router._pick(set(), exclude_prefill=True) is None

    def test_plan_handoff_decision_table(self):
        key = _first_block_key(PROMPT)
        msg = {"prompt": PROMPT, "key": "k"}
        # all-mixed fleet: no hint (pre-r20 routing byte-for-byte)
        router = FailoverRouter(_StubSup(
            [_StubReplica(0), _StubReplica(1)]))
        assert router._plan_handoff(msg, key, None, _NOTRACE) is None
        # chain already resident on a decode-capable replica: no hint
        reps = [_StubReplica(0, role="prefill", port=1),
                _StubReplica(1, role="decode", keys=[key])]
        router = FailoverRouter(_StubSup(reps))
        assert router._plan_handoff(msg, key, None, _NOTRACE) is None
        # a prefill replica advertises it: hint WITHOUT a prefill hop
        reps = [_StubReplica(0, role="prefill", port=7777, keys=[key]),
                _StubReplica(1, role="decode")]
        router = FailoverRouter(_StubSup(reps))
        hint = router._plan_handoff(msg, key, None, _NOTRACE)
        assert hint == {"host": "127.0.0.1", "port": 7777}
        assert router.handoffs_total == 1
        # disaggregate=False: no hint even with roles present
        router = FailoverRouter(_StubSup(reps), disaggregate=False)
        assert router.disaggregate is False

    def test_failed_prefill_hop_degrades_to_plain(self):
        key = _first_block_key(PROMPT)
        reps = [_StubReplica(0, role="prefill",
                             port=_free_dead_port()),
                _StubReplica(1, role="decode")]
        router = FailoverRouter(_StubSup(reps), backend_timeout_s=2.0)
        hint = router._plan_handoff({"prompt": PROMPT, "key": "k"},
                                    key, None, _NOTRACE)
        assert hint is None
        assert router.handoff_prefill_failures_total == 1

    def test_exhausted_budget_skips_prefill_hop(self):
        """A request whose deadline budget is already spent must not
        pay a prefill hop (the dispatch loop answers DeadlineExceeded
        from the SAME budget) — and a hopeless hop is not counted as
        a prefill failure."""
        key = _first_block_key(PROMPT)
        reps = [_StubReplica(0, role="prefill",
                             port=_free_dead_port()),
                _StubReplica(1, role="decode")]
        router = FailoverRouter(_StubSup(reps), backend_timeout_s=2.0)
        t0 = time.monotonic()
        hint = router._plan_handoff(
            {"prompt": PROMPT, "key": "k"}, key, None, _NOTRACE,
            budget_ms=50.0, arrival=time.monotonic() - 1.0)
        assert hint is None
        # no RPC was attempted: well under the 2 s backend timeout
        assert time.monotonic() - t0 < 1.0
        assert router.handoff_prefill_failures_total == 0

    def test_router_e2e_prefill_first_dispatch(self, model):
        """Live two-server fleet behind a real router socket: a keyed
        request routes prefill-first, the decode replica splices the
        fetched chain, greedy output matches the bare-engine
        reference."""
        ref = _reference({})
        pf, dec = _handoff_pair({})
        reps = [_StubReplica(0, port=pf.port, role="prefill"),
                _StubReplica(1, port=dec.port, role="decode")]
        router = FailoverRouter(_StubSup(reps))
        port = router.start()
        try:
            out = client_request(
                "127.0.0.1", port,
                {"op": "generate", "prompt": PROMPT,
                 "max_new_tokens": MNT, "key": "k"}, timeout_s=120)
            assert out["generated"] == ref
            assert out["stats"]["handoff_pages"] == 2
            assert router.handoffs_total == 1
            assert router.handoff_prefill_failures_total == 0
            # the router's health op surfaces the accounting + roles
            st = client_request("127.0.0.1", port, {"op": "health"})
            assert st["handoffs_total"] == 1
            assert st["disaggregate"] is True
            roles = {r["idx"]: r["role"] for r in st["replicas"]}
            assert roles == {0: "prefill", 1: "decode"}
            _leak_ok(pf, dec)
        finally:
            router.stop()
            pf.stop()
            dec.stop()


# ---------------------------------------------------------------------------
# Drain handoff (ROADMAP 3(a) prefix-affinity-aware drain)
# ---------------------------------------------------------------------------

class TestDrainHandoff:
    def test_rendezvous_owner_stable(self):
        reps = [_StubReplica(i) for i in range(4)]
        owners = {}
        for i in range(16):
            key = _first_block_key(list(range(i, i + 20)))
            o1 = rendezvous_owner(key, reps).idx
            assert rendezvous_owner(key, reps).idx == o1
            owners.setdefault(o1, 0)
            owners[o1] += 1
        assert len(owners) >= 2  # spreads

    def test_handoff_chains_ships_to_survivors(self, model):
        """The drain path over live servers: the victim's advertised
        heads are prefetched by the survivor (rendezvous share), and a
        later keyed request on the survivor splices instead of
        re-prefilling."""
        ref = _reference({})
        victim = _server("mixed", spill_bytes=1 << 20)
        survivor = _server("mixed", spill_bytes=1 << 20)
        try:
            out = client_request(
                "127.0.0.1", victim.port,
                {"op": "generate", "prompt": PROMPT,
                 "max_new_tokens": MNT}, timeout_s=120)
            assert out["generated"] == ref
            heads = client_request("127.0.0.1", victim.port,
                                   {"op": "health"})["prefix_keys"]
            assert heads
            rep = handoff_chains(
                "127.0.0.1", victim.port, heads,
                [_StubReplica(1, port=survivor.port)])
            assert rep["failures"] == [], rep
            assert rep["imported_pages"] == 2 and rep["bytes"] > 0
            # victim drains clean; survivor serves from the handoff
            client_request("127.0.0.1", victim.port, {"op": "drain"})
            out = client_request(
                "127.0.0.1", survivor.port,
                {"op": "generate", "prompt": PROMPT,
                 "max_new_tokens": MNT}, timeout_s=120)
            assert out["generated"] == ref
            assert out["stats"]["restored_pages"] == 2
            assert out["stats"]["handoff_pages"] == 2
            _leak_ok(survivor)
        finally:
            victim.stop()
            survivor.stop()

    def test_handoff_chains_dead_survivor_recorded(self):
        rep = handoff_chains(
            "127.0.0.1", _free_dead_port(), ["ab" * 8],
            [_StubReplica(0, port=_free_dead_port())], timeout_s=2.0)
        assert rep["imported_pages"] == 0
        assert len(rep["failures"]) == 1

    @pytest.mark.slow
    def test_drain_replica_e2e_live_supervisor(self, tmp_path):
        """Supervisor.drain_replica on a LIVE 2-replica fleet: the
        victim's hot chain lands on the survivor through prefetch,
        the victim drains, and the survivor then serves the keyed
        prompt bit-identically from the spliced pages."""
        from paddle_tpu.serving.supervisor import Supervisor, _rpc
        env = {"JAX_PLATFORMS": "cpu", "TPU_SKIP_MDS_QUERY": "true",
               "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "cc")}
        sup = Supervisor(
            model="gpt_tiny", replicas=2,
            server_args=["--page-size", "8", "--max-seq-len", "96",
                         "--num-slots", "2", "--spill-mb", "16"],
            replica_env=env, probe_interval_s=0.3,
            backoff_base_s=3600)
        try:
            sup.start(wait_ready=True)
            v, s = sup.replicas
            out = client_request(
                "127.0.0.1", v.port,
                {"op": "generate", "prompt": PROMPT,
                 "max_new_tokens": MNT}, timeout_s=120)
            assert "error" not in out, out
            ref_tokens = out["generated"]
            # wait for the monitor to refresh the advertisement
            deadline = time.monotonic() + 20.0
            while time.monotonic() < deadline and not v.prefix_keys:
                time.sleep(0.2)
            assert v.prefix_keys
            rep = sup.drain_replica(0)
            assert rep["drained"], rep
            assert rep["handoff"]["imported_pages"] == 2, rep
            out = client_request(
                "127.0.0.1", s.port,
                {"op": "generate", "prompt": PROMPT,
                 "max_new_tokens": MNT}, timeout_s=120)
            assert out["generated"] == ref_tokens
            assert out["stats"]["handoff_pages"] == 2
            chk = _rpc("127.0.0.1", s.port, {"op": "leak_check"},
                       timeout_s=30.0)
            assert chk.get("ok"), chk
        finally:
            sup.stop()


# ---------------------------------------------------------------------------
# Scheduler boost, trace split, fleet rollup, engine validation
# ---------------------------------------------------------------------------

class TestSatellites:
    def test_scheduler_handoff_boost(self):
        from paddle_tpu.inference.continuous_batching import \
            DecodeRequest
        from paddle_tpu.serving.scheduler import (Priority, SLOConfig,
                                                  SLOScheduler)
        now = time.monotonic()

        def req(handoff):
            r = DecodeRequest(0, np.asarray([1, 2], np.int32), 2,
                              priority=int(Priority.BATCH),
                              handoff=handoff)
            r.stats.submit_t = now
            return r

        sched = SLOScheduler(SLOConfig())
        assert sched.effective_priority(req(False), now) == \
            int(Priority.BATCH)
        assert sched.effective_priority(req(True), now) == \
            int(Priority.BATCH) + 1
        assert sched.explain(req(True), now)["handoff"] is True
        assert "handoff" not in sched.explain(req(False), now)
        # capped at INTERACTIVE; 0 restores the pre-r20 ordering
        big = SLOScheduler(SLOConfig(handoff_boost=99))
        assert big.effective_priority(req(True), now) == \
            int(Priority.INTERACTIVE)
        off = SLOScheduler(SLOConfig(handoff_boost=0))
        assert off.effective_priority(req(True), now) == \
            int(Priority.BATCH)

    def test_trace_reports_fetched_split(self, model):
        pf, dec = _handoff_pair({})
        dec.tracer.sample_rate = 1.0
        try:
            _do_handoff(pf, dec)
            tr = client_request("127.0.0.1", dec.port, {"op": "trace"})
            restores = [s for t in tr["traces"]
                        for s in t["spans"]
                        if s["name"] == "restore"]
            assert restores, tr["traces"]
            args = restores[-1].get("args", {})
            assert args.get("fetched") == 2
            assert args.get("pages") == 2
        finally:
            pf.stop()
            dec.stop()

    def test_fleet_rollup_merges_handoff_telemetry(self):
        mets = []
        for pages in (2, 3):
            m = ServingMetrics(registry=StatRegistry())
            m.counter("handoff_pages_total").add(pages)
            m.counter("handoff_bytes_total").add(100 * pages)
            m.handoff_ms.observe(float(pages))
            mets.append(m)
        exps = [m.export() for m in mets]
        for e in exps:
            assert "handoff_ms" in e["histograms"]
        merged = merge_exports([e["histograms"]["handoff_ms"]
                                for e in exps])
        assert merged["total"] == 2
        assert sum(e["counters"]["handoff_pages_total"]
                   for e in exps) == 5

    def test_engine_rejects_oversized_max_seq_len(self, model):
        """The r20 root-cause fix: positions past the model's wpe
        table read out-of-bounds embeddings whose NaNs poison the
        shared scratch page — construction must fail typed."""
        with pytest.raises(ValueError, match="position-embedding"):
            create_decode_engine(model, num_slots=2, page_size=8,
                                 max_seq_len=256)
        # at exactly the table size it builds fine
        eng = create_decode_engine(model, num_slots=2, page_size=8,
                                   max_seq_len=128)
        eng.close()
