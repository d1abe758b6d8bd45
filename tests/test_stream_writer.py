"""Streamed token delivery (serving/server.py ``_StreamWriter``): one
writer thread owns every socket write of streaming generates.

- through a live server: every token line reaches its client before the
  request's final reply and equals the reply's ``generated``, with 16
  clients at once; a client that closes mid-stream costs the others
  nothing and its connection thread is released; the engine wakes the
  writer at most once a call, and ``stream_lines_total`` counts the
  streamed tokens; a connection answers its next request after a
  streamed one;
- the writer alone, over socket pairs with a small send buffer: a peer
  that stops reading delays itself alone and later gets every line in
  order; a peer that dies with lines queued drops them counted; no
  wake is lost under a tiny switch interval.
"""

import json
import socket
import sys
import threading
import time

import pytest

import paddle_tpu as pt
from paddle_tpu.core.monitor import StatRegistry
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import ServingMetrics, ServingServer, client_request
from paddle_tpu.serving.server import _Pending, _StreamWriter


@pytest.fixture(scope="module", autouse=True)
def _compile_cache(module_compile_cache):
    yield


@pytest.fixture(scope="module")
def model():
    pt.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


def _server(model, **kw):
    kw.setdefault("num_slots", 4)
    kw.setdefault("page_size", 8)
    kw.setdefault("max_seq_len", 96)
    kw.setdefault("num_pages", 48)
    kw.setdefault("metrics", ServingMetrics(registry=StatRegistry()))
    return ServingServer(model, **kw)


def _wait_for(cond, timeout_s=60.0):
    deadline = time.monotonic() + timeout_s
    while not cond():
        if time.monotonic() > deadline:
            return False
        time.sleep(0.01)
    return True


def _counter(metrics, name):
    return metrics.counter(name).get()


def _stream(port, prompt, n):
    """One streaming generate: (final reply, token lines in order)."""
    toks = []
    rep = client_request("127.0.0.1", port, {
        "op": "generate", "prompt": prompt, "max_new_tokens": n,
        "stream": True}, timeout_s=120.0, on_token=toks.append)
    return rep, toks


# ---------------------------------------------------------------------------
# Through a live server
# ---------------------------------------------------------------------------

def test_sixteen_concurrent_streams_get_every_token_before_the_reply(model):
    srv = _server(model)
    port = srv.start()
    results = {}

    def client(i):
        results[i] = _stream(port, [(7 * i + j) % 90 + 1
                                    for j in range(3 + i % 5)], 6 + i % 4)

    ts = [threading.Thread(target=client, args=(i,)) for i in range(16)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=180)
    try:
        assert not any(t.is_alive() for t in ts)
        assert len(results) == 16
        for i, (rep, toks) in sorted(results.items()):
            assert "error" not in rep, rep
            # client_request returns at the final reply: lines that
            # came after it would be missing here
            assert toks == rep["generated"], i
            assert len(toks) == 6 + i % 4
        m = srv.metrics
        streamed = sum(len(rep["generated"]) for rep, _ in results.values())
        assert _wait_for(lambda: _counter(m, "stream_lines_total")
                         == streamed)
        assert _counter(m, "stream_dropped_lines_total") == 0
    finally:
        srv.stop()
    srv.engine.allocator.check_no_leak()


def test_engine_wakes_the_writer_at_most_once_a_call(model):
    srv = _server(model)
    eng = srv.engine
    calls = [0]
    step = eng.step

    def counted_step():
        calls[0] += 1
        return step()

    eng.step = counted_step
    port = srv.start()
    results = {}

    def client(i):
        results[i] = _stream(port, [i + 1, i + 2, i + 3], 24)

    ts = [threading.Thread(target=client, args=(i,)) for i in range(4)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=180)
    try:
        assert len(results) == 4
        streamed = sum(len(toks) for _, toks in results.values())
        assert streamed == 4 * 24
        m = srv.metrics
        assert _wait_for(lambda: _counter(m, "stream_lines_total")
                         == streamed)
        wakes = _counter(m, "stream_writer_wakes_total")
        # at most one wake a step() call, plus one for each request's
        # end where it came on its own; never one a token
        assert 0 < wakes <= calls[0] + len(results)
        assert wakes < streamed
    finally:
        srv.stop()
    srv.engine.allocator.check_no_leak()


def test_a_client_that_closes_mid_stream_costs_the_others_nothing(
        model, monkeypatch):
    escaped = []
    monkeypatch.setattr(threading, "excepthook", escaped.append)
    srv = _server(model)
    port = srv.start()
    quitter = socket.create_connection(("127.0.0.1", port), timeout=60)
    quitter.sendall((json.dumps({
        "op": "generate", "prompt": [5, 6, 7], "max_new_tokens": 80,
        "stream": True}) + "\n").encode())
    first = quitter.makefile("r", encoding="utf-8").readline()
    assert "token" in json.loads(first)
    quitter.close()
    results = {}

    def client(i):
        results[i] = _stream(port, [i + 1, i + 2], 10)

    ts = [threading.Thread(target=client, args=(i,)) for i in range(3)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=180)
    try:
        assert len(results) == 3
        for rep, toks in results.values():
            assert "error" not in rep, rep
            assert toks == rep["generated"]
        # every connection is closed by its client: each connection
        # thread, the quitter's included, must have been released
        assert _wait_for(lambda: not any(
            t.is_alive() for t in list(srv._conn_threads)))
        assert srv._stream_writer._thread.is_alive()
        assert not escaped
    finally:
        srv.stop()
    srv.engine.allocator.check_no_leak()


def test_a_connection_answers_its_next_request_after_a_streamed_one(model):
    srv = _server(model)
    port = srv.start()
    try:
        with socket.create_connection(("127.0.0.1", port), timeout=60) as s:
            rfile = s.makefile("r", encoding="utf-8")

            def ask(payload):
                s.sendall((json.dumps(payload) + "\n").encode())
                toks = []
                for line in rfile:
                    msg = json.loads(line)
                    if "token" not in msg:
                        return msg, toks
                    toks.append(msg["token"])
                raise ConnectionError("closed mid-request")

            rep, toks = ask({"op": "generate", "prompt": [1, 2, 3],
                             "max_new_tokens": 5, "stream": True})
            assert "error" not in rep and toks == rep["generated"]
            rep2, toks2 = ask({"op": "generate", "prompt": [4, 5],
                               "max_new_tokens": 3, "stream": True})
            assert "error" not in rep2 and toks2 == rep2["generated"]
            rep3, toks3 = ask({"op": "generate", "prompt": [4, 5],
                               "max_new_tokens": 3})
            assert toks3 == [] and rep3["generated"] == rep2["generated"]
            assert ask({"op": "health"})[0]["status"] == "ok"
    finally:
        srv.stop()
    srv.engine.allocator.check_no_leak()


# ---------------------------------------------------------------------------
# The writer alone
# ---------------------------------------------------------------------------

def _writer():
    metrics = ServingMetrics(registry=StatRegistry())
    w = _StreamWriter(metrics)
    w.start()
    return w, metrics


@pytest.fixture
def pair():
    """``pair(sndbuf)``: (the writer's side with a small send buffer,
    the peer's side); every socket is closed at teardown."""
    made = []

    def make(sndbuf=4096):
        mine, peer = socket.socketpair()
        mine.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, sndbuf)
        peer.settimeout(60)
        made.extend((mine, peer))
        return mine, peer

    yield make
    for s in made:
        s.close()


def _read_lines(sock, n):
    """``n`` lines from ``sock`` (its timeout bounds each read)."""
    buf, lines = b"", []
    while len(lines) < n:
        data = sock.recv(1 << 16)
        if not data:
            raise ConnectionError(f"closed after {len(lines)} lines")
        buf += data
        *done, buf = buf.split(b"\n")
        lines += done
    assert buf == b"" and len(lines) == n
    return lines


def test_writer_a_peer_that_stops_reading_delays_itself_alone(pair):
    w, metrics = _writer()
    try:
        (a, a_peer), (b, b_peer) = pair(), pair()
        pa, pb = _Pending(w, a), _Pending(w, b)
        tok_a, tok_b = pa.on_token(), pb.on_token()
        n = 4000  # ~170 KB a peer, far past a 4 KB send buffer
        for i in range(n):
            tok_a(1, i, i == n - 1)
            tok_b(2, i, i == n - 1)
            if i % 250 == 249:
                w.kick()
        pa.finish({"rid": 1, "done": True})
        pb.finish({"rid": 2, "done": True})
        w.kick()
        got_b = _read_lines(b_peer, n + 1)
        assert got_b[0] == json.dumps(
            {"rid": 2, "token": 0, "done": False}).encode()
        assert got_b[n - 1] == json.dumps(
            {"rid": 2, "token": n - 1, "done": True}).encode()
        assert [json.loads(x)["token"] for x in got_b[:n]] == list(range(n))
        assert json.loads(got_b[n]) == {"rid": 2, "done": True}
        assert pb.outbox.get(timeout=60) is None
        assert _wait_for(lambda: _counter(
            metrics, "stream_deferred_sends_total") > 0)
        # the peer that never read still holds its connection thread
        assert pa.outbox.empty()
        got_a = _read_lines(a_peer, n + 1)
        assert [json.loads(x)["token"] for x in got_a[:n]] == list(range(n))
        assert json.loads(got_a[n]) == {"rid": 1, "done": True}
        assert pa.outbox.get(timeout=60) is None
        assert _wait_for(lambda: _counter(
            metrics, "stream_lines_total") == 2 * n)
        assert _counter(metrics, "stream_dropped_lines_total") == 0
    finally:
        w.stop()
    assert not w._thread.is_alive()


def test_writer_drops_a_dead_peers_lines_counted(pair):
    w, metrics = _writer()
    try:
        mine, peer = pair()
        p = _Pending(w, mine)
        tok = p.on_token()
        for i in range(2000):
            tok(3, i, False)
        w.kick()
        assert _wait_for(lambda: _counter(
            metrics, "stream_deferred_sends_total") > 0)
        peer.close()
        # the selector reports the dead socket; the send fails; the
        # connection thread is released without the end marker
        assert p.outbox.get(timeout=60) is None
        assert _wait_for(lambda: _counter(
            metrics, "stream_dropped_lines_total") > 0)
        before = _counter(metrics, "stream_dropped_lines_total")
        tok(3, 2000, True)
        p.finish({"rid": 3, "done": True})
        w.kick()
        assert _wait_for(lambda: _counter(
            metrics, "stream_dropped_lines_total") == before + 2)
        assert w._thread.is_alive()
        # a live peer beside it is still served
        mine2, peer2 = pair()
        p2 = _Pending(w, mine2)
        p2.on_token()(4, 9, True)
        p2.finish({"rid": 4, "done": True})
        w.kick()
        assert len(_read_lines(peer2, 2)) == 2
        assert p2.outbox.get(timeout=60) is None
    finally:
        w.stop()
    assert not w._thread.is_alive()


def test_writer_loses_no_wake_under_a_tiny_switch_interval(pair):
    """More reader threads than cores, the interpreter switching every
    microsecond, the engine's side kicking once a step: a lost wake
    would leave a stream short and its reader timing out."""
    streams, steps = 24, 150
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    w, metrics = _writer()
    try:
        pairs = [pair(1 << 16) for _ in range(streams)]
        pend = [_Pending(w, mine) for mine, _ in pairs]
        got = {}

        def reader(i):
            try:
                got[i] = _read_lines(pairs[i][1], steps + 1)
            except Exception as e:  # reported by the assert below
                got[i] = e

        rs = [threading.Thread(target=reader, args=(i,))
              for i in range(streams)]
        for r in rs:
            r.start()
        toks = [p.on_token() for p in pend]
        for s in range(steps):
            for i, tok in enumerate(toks):
                tok(i, s, s == steps - 1)
            w.kick()
        for i, p in enumerate(pend):
            p.finish({"rid": i, "done": True})
        w.kick()
        for r in rs:
            r.join(timeout=120)
        assert not any(r.is_alive() for r in rs)
        for i in range(streams):
            assert not isinstance(got[i], Exception), got[i]
            assert [json.loads(x)["token"] for x in got[i][:steps]] \
                == list(range(steps))
        for p in pend:
            assert p.outbox.get(timeout=60) is None
        assert _counter(metrics, "stream_writer_wakes_total") <= steps + 1
    finally:
        sys.setswitchinterval(old)
        w.stop()
