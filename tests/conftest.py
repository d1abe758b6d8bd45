"""Test configuration: run everything on a virtual 8-device CPU mesh.

Distributed/sharding tests validate multi-chip semantics on fake CPU
devices (the driver's dryrun_multichip does the same). The chip is
reached only through `python chip_smoke.py`; the one test file that
asks the TPU's COMPILER anything is tests/test_tpu_aot_compile.py.
"""

import os

# Compile-only TPU topologies (tests/test_tpu_aot_compile.py) must not
# probe the GCP metadata server: off-cloud, libtpu retries those fetches
# for ~8 minutes before giving up, stalling the whole fast lane.
os.environ.setdefault("TPU_SKIP_MDS_QUERY", "true")

# Must be set before the first backend initialization.
_flag = "--xla_force_host_platform_device_count=8"
if _flag not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " " +
                               _flag).strip()

import jax  # noqa: E402

# Tests run on the host CPU whatever the environment asks for.
jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# ---------------------------------------------------------------------------
# Stray serving-process guard (r13). A paddle_tpu.serving server leaked
# from a PRIOR run (the PR 7 tier-1 hazard: one sat in its poll loop
# and pushed a timed suite past the 870s cap) competes with the timed
# lane for CPU. At session start we scan for serving/supervisor/chaos
# processes that do not belong to this session's process tree:
# detection-only by default (a developer may legitimately run a server
# next to the suite — never kill what we didn't start), and even under
# CI (env CI set) the kill is scoped to ORPHANED matches — processes
# reparented to init, the signature of a survivor whose spawning run
# died. A live concurrent run's server still has its supervisor/pytest
# as parent and is reported but spared, so two jobs sharing a runner
# cannot fratricide each other. Known limit: a concurrent job that
# INTENTIONALLY daemonizes its server (setsid/double-fork reparents it
# to init while the job still uses it) looks exactly like a leak — on
# shared bare-metal runners such jobs should not rely on surviving
# another job's CI-mode session start, or CI should be unset there.
# ---------------------------------------------------------------------------

_SERVING_MARKERS = ("paddle_tpu.serving.server",
                    "paddle_tpu.serving.supervisor",
                    "tools/chaos_serving.py", "chaos_serving.py")


def _proc_ancestors():
    """PIDs of this process and its ancestors (never guard-kill the
    runner's own tree — e.g. a supervisor driving pytest)."""
    pids = set()
    pid = os.getpid()
    for _ in range(64):
        if pid <= 0 or pid in pids:
            break
        pids.add(pid)
        try:
            with open(f"/proc/{pid}/stat") as f:
                pid = int(f.read().split(")")[-1].split()[1])  # ppid
        except (OSError, ValueError, IndexError):
            break
    return pids


def _stray_serving_procs():
    """[(pid, ppid, cmdline)] of serving-marker processes outside this
    session's ancestry. /proc scan (Linux — the CI/test platform);
    empty elsewhere rather than guessing."""
    own = _proc_ancestors()
    found = []
    try:
        pids = [int(p) for p in os.listdir("/proc") if p.isdigit()]
    except OSError:
        return found
    for pid in pids:
        if pid in own:
            continue
        try:
            with open(f"/proc/{pid}/cmdline", "rb") as f:
                cmd = f.read().replace(b"\0", b" ").decode(
                    "utf-8", "replace").strip()
            with open(f"/proc/{pid}/stat") as f:
                ppid = int(f.read().split(")")[-1].split()[1])
        except (OSError, ValueError, IndexError):
            continue  # raced with exit, or not ours to read
        if any(m in cmd for m in _SERVING_MARKERS):
            found.append((pid, ppid, cmd))
    return found


def _adopted_by_live_supervisor(pid: int) -> bool:
    """Autoscaler-managed replicas (r21) carry PT_SUPERVISOR_JOURNAL
    in their environment. An orphaned (ppid==1) replica is NOT a leak
    when the journal it points at names a LIVE supervisor_pid: its
    original parent died, but a restarted supervisor ADOPTED it from
    the journal — killing it would scale down someone's live fleet.
    Any read/parse failure returns False (the pre-r21 kill rule)."""
    import json
    try:
        with open(f"/proc/{pid}/environ", "rb") as f:
            raw = f.read()
        env = dict(p.split(b"=", 1) for p in raw.split(b"\0")
                   if b"=" in p)
        journal = env.get(b"PT_SUPERVISOR_JOURNAL")
        if not journal:
            return False
        with open(journal.decode("utf-8", "replace"),
                  encoding="utf-8") as f:
            body = (json.load(f) or {}).get("body") or {}
        sup_pid = body.get("supervisor_pid")
        return isinstance(sup_pid, int) \
            and os.path.isdir(f"/proc/{sup_pid}")
    except (OSError, ValueError, AttributeError):
        return False


def _handle_stray_serving(kill: bool):
    """Detect stray serving processes; with ``kill=True`` reap the
    ORPHANED ones (ppid == 1: their spawning run is dead — a process
    with a live parent belongs to someone and is only reported).
    Autoscaler-adopted replicas (orphaned by pid but owned by a live
    supervisor through the fleet journal, r21) are spared. Returns
    ``[(pid, ppid, cmdline, killed)]``. Split from the hook so the
    guard's detection-only and orphans-only contracts are directly
    testable."""
    import signal
    out = []
    for pid, ppid, cmd in _stray_serving_procs():
        killed = False
        if kill and ppid == 1 and not _adopted_by_live_supervisor(pid):
            try:
                os.kill(pid, signal.SIGKILL)
                killed = True
            except OSError:
                pass
        out.append((pid, ppid, cmd, killed))
    return out


def pytest_sessionstart(session):
    kill = bool(os.environ.get("CI"))
    for pid, ppid, cmd, killed in _handle_stray_serving(kill=kill):
        if killed:
            action = "killed (CI, orphaned)"
        elif kill and ppid == 1:
            action = "NOT killed (adopted by a live supervisor via " \
                     "its fleet journal)"
        elif kill:
            action = f"NOT killed (live parent {ppid} — belongs to a " \
                     f"concurrent run)"
        else:
            action = "NOT killed (detection-only outside CI; kill it " \
                     "before timed runs)"
        print(f"[conftest] stray serving process pid {pid}: "
              f"{cmd[:120]} — {action}", flush=True)


@pytest.fixture
def rng():
    import numpy as np
    return np.random.default_rng(0)


@pytest.fixture(scope="module")
def module_compile_cache(tmp_path_factory):
    """Module-scoped persistent compile cache (core/compile_cache.py)
    for engine-heavy test files: their tests build fresh engines over
    the same gpt_tiny program shapes, so without a cache each file
    pays the same XLA compiles dozens of times — most of its tier-1
    wall cost. Module scope means one fresh temp-dir cache per
    requesting file (pytest caches per-module), hermetic and fully
    detached on teardown: an explicit path for this process, and
    JAX_COMPILATION_CACHE_DIR for the replica children the file
    spawns. OPT-IN via a module-level autouse fixture."""
    from paddle_tpu.core.compile_cache import (ENV_VAR,
                                               compile_cache_dir,
                                               disable_compile_cache,
                                               enable_compile_cache)
    old_env, old_dir = os.environ.get(ENV_VAR), compile_cache_dir()
    path = str(tmp_path_factory.mktemp("module_compile_cache"))
    os.environ[ENV_VAR] = path
    enable_compile_cache(path)
    yield path
    disable_compile_cache()
    if old_env is None:
        os.environ.pop(ENV_VAR, None)
    else:
        os.environ[ENV_VAR] = old_env
    if old_dir:
        enable_compile_cache(old_dir)


@pytest.fixture
def check_padded_prefill_through_flash(monkeypatch):
    """``check(model, flash_calls)``: two right-padded prompts (300 and
    640 positions in a bucket of 640: with heads of 64 the flash kernel
    in blocks of 128, 3 of the shorter prompt's 5 Q blocks live) through
    a served decoder's prefill, first with the dense attention the CPU
    takes, then with the flash kernel forced through the Pallas
    interpreter. Each of the ``flash_calls`` calls was handed the
    prompts' true lengths, the logits at a prompt's last position are
    the dense path's, and what the rows of the skipped Q blocks (zeros)
    become in the matmuls and norms after is finite."""
    import functools

    import jax.numpy as jnp
    import numpy as np

    def check(model, flash_calls):
        from paddle_tpu.ops.pallas import flash_attention as fa
        lens = jnp.asarray([300, 640], jnp.int32)
        ids = np.random.default_rng(2).integers(
            0, model.config.vocab_size, (2, 640))
        ids[0, 300:] = 0  # right-padded, as the engine's
        ids = jnp.asarray(ids, jnp.int32)

        def logits():
            hidden, _ = model.decode_hidden(ids, None, prefill_lens=lens)
            return np.asarray(model.logits(hidden))

        dense = logits()
        handed = []
        grouped = fa.flash_attention_grouped

        def spy(*args, lengths=None, **kw):
            handed.append(lengths)
            return grouped(*args, lengths=lengths, **kw)

        with monkeypatch.context() as m:
            m.setattr(fa.pl, "pallas_call", functools.partial(
                fa.pl.pallas_call, interpret=True))
            m.setattr(fa, "flash_attention_supported", lambda *a, **k: True)
            m.setattr(fa, "flash_attention_grouped", spy)
            flash = logits()
        assert len(handed) == flash_calls
        for got in handed:
            np.testing.assert_array_equal(np.asarray(got), np.asarray(lens))
        for i, n in enumerate(np.asarray(lens)):
            np.testing.assert_allclose(flash[i, n - 1], dense[i, n - 1],
                                       atol=2e-5, rtol=0)
        assert np.isfinite(flash).all()

    return check


@pytest.fixture
def cpu_mesh_json():
    """Run a mesh payload in a FRESH subprocess pinned to an N-device
    CPU host platform (core/cpu_mesh.py): the child prints its result
    via ``emit_result``; the fixture returns the parsed object. For
    mesh tests that must not share jax state with this process — the
    in-process suite is already 8 fake devices (see module top), but a
    cold subprocess also pins that the XLA_FLAGS plumbing itself works
    outside the conftest's environment (bench_all, production CLIs)."""
    from paddle_tpu.core.cpu_mesh import run_cpu_mesh_json
    return run_cpu_mesh_json
