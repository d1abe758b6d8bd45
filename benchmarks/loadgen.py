"""The load generator: a process of its own that never imports JAX.

Reads one JSON object on stdin: ``{"port": p, "t0": monotonic seconds,
"window_s": s, "drain_s": d, "requests": [{"due", "prompt",
"max_new_tokens"}, ...]}``. Sends each ``generate`` (``stream: true``,
no ``eos``) at ``t0 + due`` whether or not earlier ones have finished,
over a connection of its own, and stamps every streamed token with its
own clock as it arrives. After the last due time it waits up to
``drain_s`` past the window's close for the replies still owed, then
writes one JSON object to stdout: per request ``due``, ``sent``,
``token_times`` (seconds from ``t0``), ``tokens`` (as the final reply
gave them), ``error``.

One thread, one selector: nothing here competes with itself for the
interpreter, and CLOCK_MONOTONIC is the machine's, so the server
process reads the same clock.
"""

from __future__ import annotations

import json
import selectors
import socket
import sys
import time


class _Conn:
    __slots__ = ("i", "sock", "buf", "times", "reply", "error")

    def __init__(self, i, sock):
        self.i, self.sock, self.buf = i, sock, b""
        self.times, self.reply, self.error = [], None, None


def run(job: dict) -> dict:
    port, t0 = int(job["port"]), float(job["t0"])
    reqs = job["requests"]
    close_at = t0 + float(job["window_s"]) + float(job["drain_s"])
    sel = selectors.DefaultSelector()
    log = [{"due": r["due"], "sent": None, "token_times": [],
            "tokens": None, "error": None, "prompt_len": len(r["prompt"]),
            "max_new_tokens": r["max_new_tokens"]} for r in reqs]
    open_conns, nxt = {}, 0

    def finish(c, error=None):
        rec = log[c.i]
        rec["token_times"] = c.times
        if c.reply is not None and "error" not in c.reply:
            rec["tokens"] = c.reply.get("generated")
        else:
            rec["error"] = error or json.dumps(c.reply)[:200]
        try:
            sel.unregister(c.sock)
        except (KeyError, ValueError):
            pass
        c.sock.close()
        open_conns.pop(c.i, None)

    while nxt < len(reqs) or open_conns:
        now = time.monotonic()
        if now >= close_at:
            break
        while nxt < len(reqs) and t0 + reqs[nxt]["due"] <= now:
            r = reqs[nxt]
            try:
                s = socket.create_connection(("127.0.0.1", port), timeout=5)
                s.sendall((json.dumps({
                    "op": "generate", "prompt": r["prompt"],
                    "max_new_tokens": r["max_new_tokens"],
                    "stream": True}) + "\n").encode())
                s.setblocking(False)
                c = _Conn(nxt, s)
                open_conns[nxt] = c
                sel.register(s, selectors.EVENT_READ, c)
                log[nxt]["sent"] = time.monotonic() - t0
            except OSError as e:
                log[nxt]["sent"] = time.monotonic() - t0
                log[nxt]["error"] = f"connect: {e}"
            nxt += 1
            now = time.monotonic()
        wait = close_at - now
        if nxt < len(reqs):
            wait = min(wait, t0 + reqs[nxt]["due"] - now)
        for key, _ in sel.select(timeout=max(0.0, min(wait, 0.5))):
            c = key.data
            try:
                data = c.sock.recv(1 << 16)
            except BlockingIOError:
                continue
            except OSError as e:
                finish(c, f"recv: {e}")
                continue
            t = time.monotonic() - t0
            if not data:
                finish(c, "closed mid-request")
                continue
            c.buf += data
            while b"\n" in c.buf:
                line, c.buf = c.buf.split(b"\n", 1)
                if not line.strip():
                    continue
                msg = json.loads(line)
                if "token" in msg:
                    c.times.append(t)
                else:
                    c.reply = msg
            if c.reply is not None:
                finish(c)
    for c in list(open_conns.values()):
        finish(c, "no reply by the drain limit")
    for i in range(nxt, len(reqs)):
        log[i]["error"] = "never sent: generator past the drain limit"
    sel.close()
    return {"requests": log, "ended": time.monotonic() - t0}


if __name__ == "__main__":
    json.dump(run(json.load(sys.stdin)), sys.stdout)
    sys.stdout.flush()
