"""Elastic membership / fault tolerance.

Reference parity: python/paddle/distributed/fleet/elastic.py
(ElasticManager:87 — etcd-registered ranks, membership watch, launcher
restart on scale events, ELASTIC_EXIT_CODE=101 contract:25; recovery is
checkpoint-based). This environment ships no etcd, so the registry is
pluggable:

- TcpMembershipStore: a network registry served by
  ``MembershipServer`` (a tiny threaded TCP service any rank — usually
  the launcher on node 0 — can host). Cross-host with NO shared
  filesystem, the direct etcd analog.
- FileMembershipStore: shared filesystem (GCS-fuse/NFS on TPU pods).
- An etcd store can be registered when the client library is present.
"""

from __future__ import annotations

import json
import os
import socket
import threading
import time
from typing import Callable, Dict, List, Optional

ELASTIC_EXIT_CODE = 101


class MembershipStore:
    """Abstract registry of live ranks."""

    def register(self, job_id: str, rank: int, meta: Dict) -> None:
        raise NotImplementedError

    def deregister(self, job_id: str, rank: int) -> None:
        raise NotImplementedError

    def members(self, job_id: str) -> Dict[int, Dict]:
        raise NotImplementedError

    def heartbeat(self, job_id: str, rank: int) -> None:
        raise NotImplementedError


class FileMembershipStore(MembershipStore):
    """Registry on a shared filesystem (GCS-fuse/NFS on TPU pods)."""

    def __init__(self, root: str, ttl_s: float = 30.0):
        self.root = root
        self.ttl_s = ttl_s
        os.makedirs(root, exist_ok=True)

    def _path(self, job_id: str, rank: int) -> str:
        d = os.path.join(self.root, job_id)
        os.makedirs(d, exist_ok=True)
        return os.path.join(d, f"rank_{rank}.json")

    def register(self, job_id: str, rank: int, meta: Dict) -> None:
        meta = dict(meta, ts=time.time(), host=socket.gethostname())
        self._write(self._path(job_id, rank), meta)

    @staticmethod
    def _write(path: str, meta: Dict) -> None:
        """Whole or not at all: a reader of ``members`` that met the file
        truncated mid-rewrite saw the rank vanish for one observation
        (a watcher whose FIRST observation that was never saw the scale-
        down that followed)."""
        tmp = os.path.join(os.path.dirname(path),
                           f".{os.path.basename(path)}.{os.getpid()}.tmp")
        with open(tmp, "w") as f:
            json.dump(meta, f)
        os.replace(tmp, path)

    def heartbeat(self, job_id: str, rank: int) -> None:
        from .fault_inject import fault_point
        fault_point("membership.heartbeat")
        p = self._path(job_id, rank)
        if os.path.exists(p):
            with open(p) as f:
                meta = json.load(f)
            meta["ts"] = time.time()
            self._write(p, meta)

    def deregister(self, job_id: str, rank: int) -> None:
        try:
            os.remove(self._path(job_id, rank))
        except FileNotFoundError:
            pass

    def members(self, job_id: str) -> Dict[int, Dict]:
        d = os.path.join(self.root, job_id)
        out: Dict[int, Dict] = {}
        if not os.path.isdir(d):
            return out
        now = time.time()
        for fn in os.listdir(d):
            if not fn.startswith("rank_"):
                continue
            try:
                with open(os.path.join(d, fn)) as f:
                    meta = json.load(f)
            except (json.JSONDecodeError, OSError):
                continue
            if now - meta.get("ts", 0) <= self.ttl_s:
                out[int(fn[5:-5])] = meta
        return out


class MembershipServer:
    """Threaded TCP registry: the etcd analog for cross-host elastic
    membership (reference registers ranks in etcd, fleet/elastic.py:87).
    Line protocol, one JSON object per request/response:

        {"op": "reg", "job": j, "rank": r, "meta": {...}}
        {"op": "hb"|"dereg", "job": j, "rank": r}
        {"op": "members", "job": j} -> {"ok": true, "members": {...}}

    Liveness is server-side: entries older than ``ttl_s`` are pruned on
    read, so a killed rank disappears without deregistering."""

    def __init__(self, host: str = "0.0.0.0", port: int = 0,
                 ttl_s: float = 30.0):
        self.ttl_s = ttl_s
        self._jobs: Dict[str, Dict[int, Dict]] = {}
        self._lock = threading.Lock()
        self._srv = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._srv.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._srv.bind((host, port))
        self._srv.listen(64)
        self.port = self._srv.getsockname()[1]
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._accept_loop,
                                        daemon=True)
        self._thread.start()

    def _accept_loop(self) -> None:
        while not self._stop.is_set():
            try:
                conn, _ = self._srv.accept()
            except OSError:
                return
            threading.Thread(target=self._serve, args=(conn,),
                             daemon=True).start()

    def _serve(self, conn: socket.socket) -> None:
        with conn, conn.makefile("rwb") as f:
            for line in f:
                try:
                    req = json.loads(line)
                    resp = self._handle(req)
                except (json.JSONDecodeError, KeyError, TypeError,
                        ValueError) as e:
                    resp = {"ok": False, "error": str(e)}
                f.write(json.dumps(resp).encode() + b"\n")
                f.flush()

    def _handle(self, req: Dict) -> Dict:
        op, job = req["op"], req["job"]
        with self._lock:
            ranks = self._jobs.setdefault(job, {})
            if op == "reg":
                meta = dict(req.get("meta") or {}, ts=time.time())
                ranks[int(req["rank"])] = meta
            elif op == "hb":
                r = int(req["rank"])
                now = time.time()
                entry = ranks.get(r)
                if entry is not None and \
                        now - entry.get("ts", 0) <= self.ttl_s:
                    entry["ts"] = now
                elif entry is not None:
                    # etcd lease semantics: an expired rank cannot be
                    # resurrected by a late heartbeat (a stalled zombie
                    # would mask the relaunched rank under the same
                    # key) — it must re-register.
                    ranks.pop(r, None)
            elif op == "dereg":
                ranks.pop(int(req["rank"]), None)
            elif op == "members":
                now = time.time()
                dead = [r for r, m in ranks.items()
                        if now - m.get("ts", 0) > self.ttl_s]
                for r in dead:
                    ranks.pop(r, None)
                return {"ok": True, "members": dict(ranks)}
            else:
                return {"ok": False, "error": f"unknown op {op!r}"}
        return {"ok": True}

    def close(self) -> None:
        self._stop.set()
        try:
            self._srv.close()
        except OSError:
            pass


class TcpMembershipStore(MembershipStore):
    """Client of MembershipServer — no shared filesystem required. One
    short-lived connection per call keeps the client usable across
    fork/exec (the elastic relaunch path)."""

    def __init__(self, endpoint: str, timeout_s: float = 5.0):
        host, port = endpoint.rsplit(":", 1)
        self.addr = (host, int(port))
        self.timeout_s = timeout_s

    def _call(self, req: Dict) -> Dict:
        with socket.create_connection(self.addr,
                                      timeout=self.timeout_s) as s, \
                s.makefile("rwb") as f:
            f.write(json.dumps(req).encode() + b"\n")
            f.flush()
            line = f.readline()
        if not line:
            raise ConnectionError("membership server closed connection")
        resp = json.loads(line)
        if not resp.get("ok"):
            raise RuntimeError(
                f"membership server error: {resp.get('error')}")
        return resp

    def register(self, job_id: str, rank: int, meta: Dict) -> None:
        meta = dict(meta, host=socket.gethostname())
        self._call({"op": "reg", "job": job_id, "rank": rank,
                    "meta": meta})

    def heartbeat(self, job_id: str, rank: int) -> None:
        from .fault_inject import fault_point
        fault_point("membership.heartbeat")
        self._call({"op": "hb", "job": job_id, "rank": rank})

    def deregister(self, job_id: str, rank: int) -> None:
        try:
            self._call({"op": "dereg", "job": job_id, "rank": rank})
        except (ConnectionError, OSError):
            pass  # best effort: the TTL prunes us anyway

    def members(self, job_id: str) -> Dict[int, Dict]:
        got = self._call({"op": "members", "job": job_id})["members"]
        return {int(r): m for r, m in got.items()}


class ElasticManager:
    """Watches membership; triggers the restart callback when the member
    set changes (scale up/down or failure), mirroring ElasticManager's
    watch loop (reference: fleet/elastic.py:87)."""

    def __init__(self, job_id: str, rank: int, np: int,
                 store: MembershipStore,
                 on_change: Optional[Callable[[Dict[int, Dict]], None]]
                 = None, heartbeat_s: float = 5.0):
        self.job_id = job_id
        self.rank = rank
        self.np = np
        self.store = store
        self.on_change = on_change
        self.heartbeat_s = heartbeat_s
        self._stop = threading.Event()
        self._thread: Optional[threading.Thread] = None
        self._last_members: Optional[List[int]] = None
        self.hb_failures = 0  # consecutive failed heartbeat rounds

    def start(self) -> None:
        self.store.register(self.job_id, self.rank, {"np": self.np})
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def stop(self) -> None:
        self._stop.set()
        if self._thread:
            self._thread.join(timeout=5)
        self.store.deregister(self.job_id, self.rank)

    def _loop(self) -> None:
        from .resilience import get_retry_policy
        policy = get_retry_policy("membership.heartbeat")
        while not self._stop.is_set():
            try:
                policy.call(self.store.heartbeat, self.job_id, self.rank,
                            site="membership.heartbeat")
                member_map = policy.call(
                    self.store.members, self.job_id,
                    site="membership.heartbeat")
            except Exception:  # noqa: BLE001 - a flaky store must not
                # kill the watch thread; the TTL decides liveness
                self.hb_failures += 1
                self._stop.wait(self.heartbeat_s)
                continue
            self.hb_failures = 0
            members = sorted(member_map)
            if self._last_members is None:
                self._last_members = members
            elif members != self._last_members:
                self._last_members = members
                if self.on_change:
                    # hand over the map we just fetched — a second,
                    # unretried store read here could throw and kill
                    # the watch thread
                    self.on_change(member_map)
            self._stop.wait(self.heartbeat_s)

    def healthy(self) -> bool:
        return len(self.store.members(self.job_id)) >= self.np
