"""What delivering streamed tokens costs the engine thread, on the CPU:
the serving loop's host side modelled with the server's own delivery
objects (``paddle_tpu/serving/server.py``: ``_Pending``,
``_StreamWriter``) and nothing of the engine.

Each step of the model engine thread: a sleep stands in for the device
fetch (it lets the interpreter lock go, as the blocking read does), a
fixed amount of pure Python calibrated to ``--launch-ms`` on an idle
interpreter stands in for the jit dispatch, then one ``on_token`` call a
stream, as the engine's per-slot loop makes them. A process of its own
(this file with ``--reader``, without JAX) plays the load generator: one
connection a stream, every line parsed, every token checked in order.

Two deliveries, the same steps:

- ``thread``: the server's delivery before the stream writer: a
  connection thread a stream relays its ``_Pending`` outbox (one
  ``queue.Queue.put`` of a dict a token from the engine thread, then
  ``json.dumps``, a write and a flush on the connection thread);
- ``writer``: the stream writer: one append a token, one wake a step,
  one thread that formats the lines and sends without blocking.

Per case and delivery it prints the engine thread's wall time a step,
the parts of it in the sleep (``wait``: the sleep and getting the lock
back after it), the calibrated Python (``launch``) and the ``on_token``
loop with the wake (``emit``), the thread's CPU time over its wall time,
and for the writer its wakes and token lines a wake. The first
``--skip`` steps are left out.

    python3 tools/stream_delivery_report.py
    python3 tools/stream_delivery_report.py --cases 128:1,16:15 --steps 200
"""

from __future__ import annotations

import argparse
import json
import os
import selectors
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def reader(port: int, streams: int, steps: int) -> dict:
    """The load generator's part: ``streams`` connections, read to each
    one's final reply; counts lines and tokens out of order."""
    sel = selectors.DefaultSelector()
    socks = []
    for i in range(streams):
        s = socket.create_connection(("127.0.0.1", port), timeout=60)
        s.sendall(b"%8d" % i)
        s.setblocking(False)
        sel.register(s, selectors.EVENT_READ, [i, b"", 0])
        socks.append(s)
    open_, lines, bad = streams, 0, 0
    while open_:
        events = sel.select(timeout=60)
        if not events:
            raise TimeoutError(f"{open_} streams silent for 60 s")
        for key, _ in events:
            st = key.data
            data = key.fileobj.recv(1 << 16)
            if not data:
                raise ConnectionError(f"stream {st[0]} closed early")
            *done, st[1] = (st[1] + data).split(b"\n")
            for line in done:
                msg = json.loads(line)
                lines += 1
                if "token" in msg:
                    bad += msg["token"] != st[2]
                    st[2] += 1
                else:
                    bad += st[2] != steps
                    sel.unregister(key.fileobj)
                    open_ -= 1
    for s in socks:
        s.close()
    return {"lines": lines, "bad": bad}


def _spin(n: int) -> int:
    x = 0
    for i in range(n):
        x += i * i
    return x


def calibrate(ms: float) -> int:
    """Iterations of `_spin` that take ``ms`` on an idle interpreter."""
    n = 20000
    best = min(_timed_spin(n) for _ in range(5))
    return max(1, int(n * ms * 1e-3 / best))


def _timed_spin(n: int) -> float:
    t0 = time.perf_counter()
    _spin(n)
    return time.perf_counter() - t0


def run_case(delivery: str, streams: int, device_ms: float, spin: int,
             steps: int, skip: int) -> dict:
    from paddle_tpu.core.monitor import StatRegistry
    from paddle_tpu.serving.metrics import ServingMetrics
    from paddle_tpu.serving.server import _Pending, _StreamWriter

    lsock = socket.create_server(("127.0.0.1", 0))
    port = lsock.getsockname()[1]
    child = subprocess.Popen(
        [sys.executable, os.path.abspath(__file__), "--reader",
         f"{port}:{streams}:{steps}"], stdout=subprocess.PIPE)
    conns = [None] * streams
    for _ in range(streams):
        c, _addr = lsock.accept()
        i = int(c.recv(8, socket.MSG_WAITALL))
        conns[i] = c
    lsock.close()

    metrics = ServingMetrics(registry=StatRegistry())
    writer, relays = None, []
    if delivery == "writer":
        writer = _StreamWriter(metrics)
        writer.start()
        pend = [_Pending(writer, c) for c in conns]
        toks = [p.on_token() for p in pend]
    else:
        pend = [_Pending() for _ in conns]

        def relay(p, c):
            with c.makefile("w", encoding="utf-8") as wfile:
                while True:
                    out = p.outbox.get()
                    if out is None:
                        return
                    wfile.write(json.dumps(out) + "\n")
                    wfile.flush()

        def token_sink(p):
            def on_token(rid, tok, done):
                p.put({"rid": rid, "token": int(tok), "done": bool(done)})
            return on_token

        toks = [token_sink(p) for p in pend]
        relays = [threading.Thread(target=relay, args=(p, c), daemon=True)
                  for p, c in zip(pend, conns)]
        for t in relays:
            t.start()

    parts = {"wait": 0.0, "launch": 0.0, "emit": 0.0}
    clock, cpu = time.perf_counter, time.thread_time
    for step in range(steps):
        if step == skip:
            t_start, cpu_start = clock(), cpu()
            for k in parts:
                parts[k] = 0.0
        t0 = clock()
        time.sleep(device_ms * 1e-3)
        t1 = clock()
        _spin(spin)
        t2 = clock()
        for i, tok in enumerate(toks):
            tok(i, step, step == steps - 1)
        if writer is not None:
            writer.kick()
        t3 = clock()
        parts["wait"] += t1 - t0
        parts["launch"] += t2 - t1
        parts["emit"] += t3 - t2
    wall, busy = clock() - t_start, cpu() - cpu_start
    for i, p in enumerate(pend):
        p.finish({"rid": i, "done": True})
    if writer is not None:
        writer.kick()
        for p in pend:
            p.outbox.get(timeout=120)
        writer.stop()
    for t in relays:
        t.join(timeout=120)
    got = json.loads(child.communicate(timeout=120)[0])
    for c in conns:
        c.close()
    n = steps - skip
    out = {"delivery": delivery, "streams": streams, "device_ms": device_ms,
           "steps": n, "step_ms": wall / n * 1e3,
           **{f"{k}_ms": v / n * 1e3 for k, v in parts.items()},
           "engine_cpu_pct": busy / wall * 100,
           "lines": got["lines"], "bad": got["bad"]}
    if writer is not None:
        wakes = metrics.counter("stream_writer_wakes_total").get()
        out["wakes"] = wakes
        out["lines_per_wake"] = (
            metrics.counter("stream_lines_total").get() / max(1, wakes))
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    p.add_argument("--cases", default="128:1,16:15",
                   help="streams:device wait ms, comma separated")
    p.add_argument("--deliveries", default="thread,writer")
    p.add_argument("--steps", type=int, default=200)
    p.add_argument("--skip", type=int, default=20)
    p.add_argument("--launch-ms", type=float, default=1.2)
    p.add_argument("--reader", default=None, help=argparse.SUPPRESS)
    opts = p.parse_args(argv)
    if opts.reader:
        port, streams, steps = (int(x) for x in opts.reader.split(":"))
        print(json.dumps(reader(port, streams, steps)), flush=True)
        return 0
    sys.path.insert(0, ROOT)
    spin = calibrate(opts.launch_ms)
    print(f"# {os.cpu_count()} cores, Python {sys.version.split()[0]}, "
          f"launch stand-in {spin} iterations = {opts.launch_ms} ms idle")
    print(f"{'delivery':>8} {'streams':>7} {'device':>6} | {'step ms':>8} "
          f"{'wait':>6} {'launch':>6} {'emit':>6} {'cpu %':>6} "
          f"{'lines/wake':>10}")
    rows = []
    for case in opts.cases.split(","):
        streams, device_ms = case.split(":")
        for delivery in opts.deliveries.split(","):
            r = run_case(delivery, int(streams), float(device_ms), spin,
                         opts.steps, opts.skip)
            rows.append(r)
            lpw = r.get("lines_per_wake")
            print(f"{delivery:>8} {r['streams']:>7} {r['device_ms']:>6g} | "
                  f"{r['step_ms']:>8.3f} {r['wait_ms']:>6.3f} "
                  f"{r['launch_ms']:>6.3f} {r['emit_ms']:>6.3f} "
                  f"{r['engine_cpu_pct']:>6.1f} "
                  f"{'-' if lpw is None else f'{lpw:.1f}':>10}")
    print(json.dumps(rows))
    return 0 if all(r["bad"] == 0 for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())
