"""Native lib, PyLayer, control flow, launcher/elastic, profiler tests."""

import multiprocessing
import os
import subprocess
import sys
import tempfile
import time

import numpy as np
import pytest

import paddle_tpu as pt
from paddle_tpu import nn


# -- native -------------------------------------------------------------------

def test_native_builds():
    from paddle_tpu import native
    assert native.available(), "g++ build of ptnative failed"


def test_crc32c():
    from paddle_tpu import native
    # known crc32c vector: "123456789" -> 0xE3069283
    if native.get_lib() is not None:
        assert native.crc32c(b"123456789") == 0xE3069283
    assert native.crc32c(b"abc") == native.crc32c(b"abc")
    assert native.crc32c(b"abc") != native.crc32c(b"abd")


def test_u8_norm_matches_numpy():
    from paddle_tpu import native
    img = np.random.default_rng(0).integers(0, 256, (3, 8, 8)).astype(
        np.uint8)
    mean = [0.485, 0.456, 0.406]
    std = [0.229, 0.224, 0.225]
    got = native.u8_to_f32_norm(img, mean, std)
    expect = (img.astype(np.float32) / 255.0 -
              np.asarray(mean, np.float32).reshape(3, 1, 1)) / \
        np.asarray(std, np.float32).reshape(3, 1, 1)
    np.testing.assert_allclose(got, expect, rtol=1e-5, atol=1e-6)


def _producer(qname, n):
    from paddle_tpu import native
    q = native.ShmQueue(qname, create=False)
    for i in range(n):
        q.push_array(np.full((64,), i, np.float32))


def test_shm_queue_roundtrip():
    from paddle_tpu import native
    if not native.available():
        pytest.skip("native lib unavailable")
    qname = f"test_{os.getpid()}"
    q = native.ShmQueue(qname, slot_size=1 << 12, n_slots=4)
    try:
        ctx = multiprocessing.get_context("fork")
        p = ctx.Process(target=_producer, args=(qname, 10))
        p.start()
        got = []
        for _ in range(10):
            data = q.pop()
            got.append(np.frombuffer(data, np.float32)[0])
        p.join(timeout=10)
        assert sorted(got) == list(range(10))
    finally:
        q.destroy()


# -- PyLayer ------------------------------------------------------------------

def test_pylayer_custom_backward():
    from paddle_tpu.autograd.py_layer import PyLayer

    class Cube(PyLayer):
        @staticmethod
        def forward(ctx, x):
            ctx.save_for_backward(x)
            return x * x * x

        @staticmethod
        def backward(ctx, g):
            (x,) = ctx.saved_tensor()
            return g * 3.0 * x * x

    x = pt.to_tensor([2.0], stop_gradient=False)
    y = Cube.apply(x)
    y.sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), [12.0])


def test_pylayer_scaled_backward():
    from paddle_tpu.autograd.py_layer import PyLayer

    class TimesTwoGradTen(PyLayer):
        @staticmethod
        def forward(ctx, x):
            return x * 2.0

        @staticmethod
        def backward(ctx, g):
            return g * 10.0

    x = pt.to_tensor([1.0, 2.0], stop_gradient=False)
    TimesTwoGradTen.apply(x).sum().backward()
    np.testing.assert_allclose(x.grad.numpy(), [10.0, 10.0])


# -- control flow ---------------------------------------------------------

def test_cond_and_while_loop():
    from paddle_tpu.ops.control_flow import cond, while_loop

    x = pt.to_tensor([3.0])
    out = cond(pt.to_tensor(True), lambda v: v * 2, lambda v: v * 10, x)
    np.testing.assert_allclose(out.numpy(), [6.0])

    i = pt.to_tensor(0)
    acc = pt.to_tensor(0.0)
    i_f, acc_f = while_loop(lambda i_, a: i_ < 5,
                            lambda i_, a: (i_ + 1, a + 2.0), (i, acc))
    assert int(i_f.numpy()) == 5
    np.testing.assert_allclose(acc_f.numpy(), 10.0)


def test_switch_case_and_scan():
    from paddle_tpu.ops.control_flow import scan, switch_case

    out = switch_case(pt.to_tensor(1),
                      [lambda: pt.to_tensor([1.0]),
                       lambda: pt.to_tensor([2.0]),
                       lambda: pt.to_tensor([3.0])])
    np.testing.assert_allclose(out.numpy(), [2.0])

    xs = pt.to_tensor(np.arange(5, dtype=np.float32))
    carry, ys = scan(lambda c, x: (c + x, c + x), pt.to_tensor(0.0), xs)
    np.testing.assert_allclose(carry.numpy(), 10.0)
    np.testing.assert_allclose(ys.numpy(), [0, 1, 3, 6, 10])


def test_control_flow_inside_jit():
    import jax
    from paddle_tpu.ops.control_flow import while_loop

    def f(n):
        i, s = while_loop(lambda i_, s_: i_ < n,
                          lambda i_, s_: (i_ + 1, s_ + i_),
                          (pt.to_tensor(0), pt.to_tensor(0)))
        return s.value

    out = jax.jit(f)(5)
    assert int(out) == 10


# -- launcher / elastic ---------------------------------------------------

def test_launcher_runs_multiproc():
    from paddle_tpu.distributed.launch import launch_procs, watch_procs

    with tempfile.TemporaryDirectory() as d:
        script = os.path.join(d, "worker.py")
        with open(script, "w") as f:
            f.write(
                "import os\n"
                "print('rank', os.environ['PT_PROCESS_ID'], 'of',\n"
                "      os.environ['PT_NUM_PROCESSES'])\n")
        procs = launch_procs([script], nproc=2,
                             coordinator="127.0.0.1:29500", log_dir=d)
        code = watch_procs(procs, poll_s=0.2)
        assert code == 0
        log0 = open(os.path.join(d, "workerlog.0")).read()
        assert "rank 0 of 2" in log0


def test_launcher_propagates_failure():
    from paddle_tpu.distributed.launch import launch_procs, watch_procs

    with tempfile.TemporaryDirectory() as d:
        script = os.path.join(d, "bad.py")
        with open(script, "w") as f:
            f.write("import os, sys\n"
                    "sys.exit(3 if os.environ['PT_PROCESS_ID']=='1' "
                    "else 0)\n")
        procs = launch_procs([script], nproc=2,
                             coordinator="127.0.0.1:29501", log_dir=d)
        code = watch_procs(procs, poll_s=0.2)
        assert code == 3


def test_elastic_membership():
    from paddle_tpu.distributed.elastic import (ElasticManager,
                                                FileMembershipStore)

    with tempfile.TemporaryDirectory() as d:
        store = FileMembershipStore(d, ttl_s=5.0)
        changes = []
        m0 = ElasticManager("job1", 0, 2, store,
                            on_change=lambda mem: changes.append(len(mem)),
                            heartbeat_s=0.1)
        m1 = ElasticManager("job1", 1, 2, store, heartbeat_s=0.1)
        m0.start()
        m1.start()

        # poll with a deadline instead of one fixed sleep: on a loaded
        # 2-cpu host the 0.1 s heartbeat threads can miss a 0.5 s
        # window (observed flaking under a concurrent test lane); the
        # semantics under test are reach-healthy / notice-scale-down,
        # not heartbeat latency
        def wait_for(cond, timeout_s=10.0):
            deadline = time.time() + timeout_s
            while time.time() < deadline:
                if cond():
                    return True
                time.sleep(0.1)
            return cond()

        assert wait_for(m0.healthy)
        # the watch thread must have taken its FIRST observation (the
        # change-detection baseline) before the scale-down happens: on
        # a 1-cpu host the main thread otherwise reaches stop() before
        # the watch loop ever runs, the baseline is post-scale-down,
        # and on_change can never fire (observed deterministic there)
        # ... and that baseline must hold BOTH ranks: a first observation
        # taken before rank 1 registered is [0], and if rank 1 stops
        # before the next one the scale-down is never seen as a change
        assert wait_for(lambda: m0._last_members == [0, 1])
        m1.stop()  # scale-down event
        assert wait_for(lambda: not m0.healthy())
        # the watch-loop callback runs on its own cadence — poll it too
        assert wait_for(lambda: bool(changes)), \
            "membership change not observed"
        m0.stop()


# -- profiler ----------------------------------------------------------------

def test_profiler_records_and_exports():
    import json
    from paddle_tpu.core import (RecordEvent, disable_profiler,
                                 enable_profiler, export_chrome_trace)
    from paddle_tpu.core.profiler import profiler_events

    enable_profiler()
    with RecordEvent("my_region"):
        pt.matmul(pt.randn((8, 8)), pt.randn((8, 8)))
    disable_profiler()
    events = profiler_events()
    assert any(e.name == "my_region" for e in events)
    with tempfile.TemporaryDirectory() as d:
        path = os.path.join(d, "trace.json")
        export_chrome_trace(path)
        trace = json.load(open(path))
        assert any(ev["name"] == "my_region"
                   for ev in trace["traceEvents"])


def test_benchmark_flag_collects_stats():
    from paddle_tpu.core import GLOBAL_STATS, set_flags

    set_flags({"benchmark": True})
    try:
        pt.add(pt.ones((4,)), pt.ones((4,)))
    finally:
        set_flags({"benchmark": False})
    snap = GLOBAL_STATS.snapshot()
    assert any(k.startswith("op_us/add") for k in snap)


def test_tcp_membership_store():
    """Network membership registry (cross-host, NO shared filesystem):
    same ElasticManager semantics over the TCP store."""
    from paddle_tpu.distributed.elastic import (ElasticManager,
                                                MembershipServer,
                                                TcpMembershipStore)

    srv = MembershipServer(host="127.0.0.1", ttl_s=5.0)
    try:
        ep = f"127.0.0.1:{srv.port}"
        s0 = TcpMembershipStore(ep)
        s1 = TcpMembershipStore(ep)  # independent client, own connection
        changes = []
        m0 = ElasticManager("jobT", 0, 2, s0,
                            on_change=lambda mem: changes.append(len(mem)),
                            heartbeat_s=0.1)
        m1 = ElasticManager("jobT", 1, 2, s1, heartbeat_s=0.1)
        m0.start()
        m1.start()
        time.sleep(0.5)
        assert m0.healthy()
        assert s0.members("jobT")[1]["host"]
        m1.stop()  # deregisters over the wire
        time.sleep(0.5)
        assert not m0.healthy()
        assert changes, "membership change not observed"
        m0.stop()
    finally:
        srv.close()


def test_tcp_membership_ttl_prunes_dead_rank():
    from paddle_tpu.distributed.elastic import (MembershipServer,
                                                TcpMembershipStore)

    srv = MembershipServer(host="127.0.0.1", ttl_s=0.3)
    try:
        st = TcpMembershipStore(f"127.0.0.1:{srv.port}")
        st.register("jobD", 0, {})
        st.register("jobD", 1, {})
        assert sorted(st.members("jobD")) == [0, 1]
        deadline = time.time() + 3.0
        while time.time() < deadline:
            st.heartbeat("jobD", 0)  # rank 1 went silent (killed)
            if sorted(st.members("jobD")) == [0]:
                break
            time.sleep(0.1)
        assert sorted(st.members("jobD")) == [0]
    finally:
        srv.close()


def test_launcher_serves_membership_registry():
    """--membership serve: the launcher hosts the TCP registry and
    exports PT_MEMBER_EP; workers register over the wire only."""
    from paddle_tpu.distributed.launch import main as launch_main

    with tempfile.TemporaryDirectory() as d:
        script = os.path.join(d, "worker.py")
        with open(script, "w") as f:
            f.write(
                "import os, sys\n"
                f"sys.path.insert(0, {os.path.dirname(os.path.dirname(os.path.abspath(__file__)))!r})\n"
                "from paddle_tpu.distributed.elastic import "
                "TcpMembershipStore\n"
                "st = TcpMembershipStore(os.environ['PT_MEMBER_EP'])\n"
                "rank = int(os.environ['PT_PROCESS_ID'])\n"
                "st.register('jobL', rank, {})\n"
                "assert rank in st.members('jobL')\n")
        code = launch_main(["--nproc", "2", "--coordinator",
                            "127.0.0.1:29502", "--log_dir", d,
                            "--membership", "serve", script])
        assert code == 0, open(os.path.join(d, "workerlog.0")).read()
