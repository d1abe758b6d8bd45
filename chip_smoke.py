"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py             one TPU chip: train, then serve
    python chip_smoke.py --chips 4   four chips: the sharded paths only
    python chip_smoke.py --cpu-rehearsal [--chips 4]
                                     tiny presets, children pinned to the CPU

Drives the main path once, through the entry points a user would call,
at the full width of GPT-1.3B (hidden 2048, 24 layers, 16x128 heads;
weights random from a seed):

- kernels: paged decode (f32, bf16, int8 pools, plain and with the
  fused o-projection) and the streaming lm-head argmax against their
  pure-JAX references on a small seeded input.
- train: bench.py's configuration (B2 x S2048, bf16, flash attention)
  through `paddle_tpu.jit.TrainStep.multi_step`: finite, falling loss;
  the timed window closed by `block_until_ready` must agree with one
  closed by a host fetch.
- serve: `python -m paddle_tpu.serving.server --model gpt_1p3b` with
  its defaults, answering `generate` requests of differing prompt
  lengths over the socket (two at once, one streamed, one repeated so
  the prefix cache is hit), then `health`, `stats`, `trace`, `profile`,
  `leak_check`, `drain`. Every reply must carry the requested number of
  tokens and no error, the engine must count no error and no restart,
  the repeated prompt must return identical tokens, and the decode
  program must hold the Pallas paged-decode and sampling kernels.

With ``--chips 4`` only what exists across chips runs, each beside what
it is compared with: `fleet.distributed_jit` (mp2 x sharding2, ZeRO-1)
against the one-device `TrainStep` on the same batch, and the server
with ``--mesh model=4`` against the one-device server on the same
prompts; weights must really be split, every device must hold bytes and
the compiled programs must hold collectives.

One process per chip: THIS process never initialises a JAX backend. It
is a plain subprocess/socket/json driver; every phase is a child that
exits before the next starts (a chip belongs to one process at a time),
and the device facts on the last line are read from a child's output.
Any phase that fails makes the script exit non-zero: no phase's
exception is caught and reported beside a 0. Without a TPU (and without
``--cpu-rehearsal``) the first child raises and nothing below runs.

The last line of stdout is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": N}}``;
loss values, tokens, compile seconds, cache files and memory go on the
lines before it. Children's full output lands in
``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import signal
import socket
import subprocess
import sys
import threading
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out", "chip_smoke")
SEED = 0

# the whole script must end inside the driver's 1200 s
TRAIN_TIMEOUT_S = 700
SERVER_READY_TIMEOUT_S = 420
REQUEST_TIMEOUT_S = 420

# bf16 tolerance of the four-chip comparison: the sharded step reduces
# partial sums in another order than one device does
HYBRID_LOSS_RTOL = 2e-2


def say(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


# ---------------------------------------------------------------------------
# children: the only code here that touches JAX
# ---------------------------------------------------------------------------

def _child_setup(rehearsal: bool, chips: int):
    """Backend, compile cache and device facts of a training child."""
    import jax

    import bench
    from paddle_tpu.core.compile_cache import enable_compile_cache
    if not rehearsal:
        bench.require_tpu()
    if len(jax.devices()) < chips:
        raise RuntimeError(
            f"this phase needs {chips} devices, JAX found "
            f"{len(jax.devices())}")
    cache = enable_compile_cache()
    say(f"device {bench.device_block()} jax {jax.__version__} "
        f"compile cache {cache}")
    return jax, bench


def _peak_bytes(jax) -> list:
    return [(d.memory_stats() or {}).get("peak_bytes_in_use")
            for d in jax.devices()]


def _seeded_batch(cfg, batch: int, seq: int):
    import numpy as np
    rng = np.random.default_rng(SEED)
    return rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)


def _check_losses(losses) -> None:
    """Finite, and falling: the later half's mean under the earlier
    half's. (Step to step the benchmark's recipe — AdamW at 1e-4 with
    no warm-up, bf16 moments — is not monotone: it spikes.)"""
    import math
    if not all(math.isfinite(x) for x in losses):
        raise RuntimeError(f"non-finite loss: {losses}")
    h = max(1, len(losses) // 2)
    if not sum(losses[-h:]) / h < sum(losses[:h]) / h:
        raise RuntimeError(f"loss did not fall: {losses}")


def check_kernels(jax, rehearsal: bool) -> None:
    """The serving kernels against their pure-JAX references, at
    GPT-1.3B widths on a small seeded input: paged decode over f32,
    bf16 and int8 pools, its fused epilogue, and the streaming lm-head
    argmax. On the CPU every gate picks the reference, so a rehearsal
    only walks the code; on a TPU the public entries run the kernels."""
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import fused_sample as fs
    from paddle_tpu.ops.pallas import paged_attention as pa
    from paddle_tpu.quantization.quant import quantize_kv

    h, d, page, vocab = 16, 128, 64, 1024 if rehearsal else 50304
    e = h * d
    draws = iter(range(1 << 30))

    def normal(shape, dtype=jnp.float32):
        key = jax.random.fold_in(jax.random.key(SEED), next(draws))
        return jax.random.normal(key, shape, jnp.float32).astype(dtype)

    kf, vf = normal((9, page, h, d)), normal((9, page, h, d))
    q = normal((2, 1, h, d))
    w, bias = normal((e, e)) * 0.02, normal((e,))
    table = jnp.asarray([[0, 2, 4, 8], [5, 3, 1, 8]], jnp.int32)
    lens = jnp.asarray([150, 70], jnp.int32)  # ragged, mid-page
    kq, ks = quantize_kv(kf)
    vq, vs = quantize_kv(vf)
    bf = jnp.bfloat16
    # tolerances: max abs error over max(1, max |reference|)
    pools = {"f32": (q, kf, vf, None, None, 2e-3),
             "bf16": (q.astype(bf), kf.astype(bf), vf.astype(bf), None,
                      None, 2e-2),
             "int8": (q.astype(bf), kq, vq, ks, vs, 2e-2)}
    errs = {}

    def exact(fn, *args, **kw):
        # a reference's own matmuls must not be the loose side (a TPU's
        # default f32 matmul precision is one bf16 pass); the kernels
        # are traced outside this, at the precision serving runs them
        with jax.default_matmul_precision("highest"):
            return fn(*args, **kw).astype(jnp.float32)

    for name, (qq, kp, vp, sk, sv, tol) in pools.items():
        kw = dict(k_scale=sk, v_scale=sv)
        wq, bq = w.astype(qq.dtype), bias.astype(qq.dtype)
        for tag, got, ref in (
                (name,
                 pa.paged_attention(qq, kp, vp, table, lens, **kw),
                 exact(pa.paged_attention_reference, qq, kp, vp, table,
                       lens, **kw)),
                (name + "+oproj",
                 pa.paged_attention_fused(qq, kp, vp, table, lens, wq,
                                          bq, **kw),
                 exact(pa.paged_attention_fused_reference, qq, kp, vp,
                       table, lens, wq, bq, **kw))):
            err = float(jnp.max(jnp.abs(got.astype(jnp.float32) - ref))
                        / jnp.maximum(1.0, jnp.max(jnp.abs(ref))))
            errs[tag] = float(f"{err:.2g}")
            if not err <= tol:
                raise RuntimeError(f"paged decode {tag}: error {err} > "
                                   f"{tol}")
    for dtype in (jnp.float32, bf):
        hidden, head = normal((4, e), dtype), normal((vocab, e), dtype)
        tok = fs.fused_sample(hidden, head, transpose_y=True)
        logits = exact(jnp.matmul, hidden.astype(jnp.float32),
                       head.astype(jnp.float32).T)
        # the kernel's pick must be the reference's maximum, up to what
        # the operands' precision can tell apart
        short = float(jnp.max(jnp.max(logits, axis=1)
                              - logits[jnp.arange(4), tok]))
        errs[f"argmax {jnp.dtype(dtype).name}"] = round(short, 4)
        if not short <= 0.5:
            raise RuntimeError(f"fused_sample {dtype}: picked a token "
                               f"{short} under the maximum")
    say(f"kernels vs references, error: {errs}")


def child_train(rehearsal: bool, steps: int) -> dict:
    """One device: the serving kernels against their references, then
    bench.py's trainer through TrainStep.multi_step."""
    jax, bench = _child_setup(rehearsal, 1)
    import numpy as np

    check_kernels(jax, rehearsal)

    from paddle_tpu import native
    native.require_lib()  # a failed build is an error here, no fallback
    if native.crc32c(b"chip_smoke") != native._crc32c_py(b"chip_smoke", 0):
        raise RuntimeError("native crc32c disagrees with its reference")
    say(f"native library {native.lib_path()}")

    t0 = time.perf_counter()
    cfg, model, step, batch, seq = bench.gpt1p3b_train_step(rehearsal)
    ids = _seeded_batch(cfg, batch, seq)
    batches = (np.broadcast_to(ids, (steps,) + ids.shape).copy(),) * 2
    t_build = time.perf_counter() - t0

    def window(close):
        t = time.perf_counter()
        out = step.multi_step(batches)
        close(out)
        return time.perf_counter() - t, [float(x) for x in out]

    # first call: trace + compile (or cache read) + `steps` steps
    t_first, losses = window(jax.block_until_ready)
    # the same window closed two ways (bench.py keeps block_until_ready)
    t_bur, losses_b = window(jax.block_until_ready)
    t_fetch, losses_f = window(lambda out: float(out[-1]))
    all_losses = losses + losses_b + losses_f
    say(f"train: {steps} steps/call B{batch} x S{seq} "
        f"layers {cfg.num_layers} hidden {cfg.hidden_size}")
    say(f"train: losses {[round(x, 4) for x in all_losses]}")
    say(f"train: build {t_build:.1f}s first call {t_first:.1f}s "
        f"(compile ~{t_first - t_bur:.1f}s) warm window "
        f"block_until_ready {t_bur:.3f}s host fetch {t_fetch:.3f}s")
    _check_losses(all_losses)
    # (a rehearsal's windows are milliseconds of CPU noise)
    if not rehearsal and abs(t_bur - t_fetch) > 0.25 * t_fetch:
        raise RuntimeError(
            f"block_until_ready ({t_bur:.3f}s) and a host fetch "
            f"({t_fetch:.3f}s) disagree on the same window")
    tok_s = steps * batch * seq / t_bur
    say(f"train: {tok_s:.0f} tokens/s in the warm window (a smoke "
        f"reading, not a benchmark); peak bytes {_peak_bytes(jax)}")
    return {"losses": losses, "device": bench.device_block(),
            "first_call_s": round(t_first, 2),
            "compile_s": round(t_first - t_bur, 2)}


def child_train4(rehearsal: bool, steps: int) -> dict:
    """Four devices, one process: fleet.distributed_jit, mp2 x
    sharding2 with ZeRO-1, on the batch `child_train` uses."""
    jax, bench = _child_setup(rehearsal, 4)
    from paddle_tpu.distributed import DistributedStrategy, fleet
    from paddle_tpu.distributed.topology import collectives_in

    t0 = time.perf_counter()
    strategy = DistributedStrategy()
    strategy.hybrid_configs = {"mp_degree": 2, "sharding_degree": 2}
    strategy.sharding = True
    strategy.sharding_configs = {"stage": 1}
    fleet.init(strategy=strategy)
    # the one-device trainer's model, optimizer and loss
    cfg, batch, seq = bench.gpt1p3b_config(rehearsal)
    model = bench.gpt1p3b_model(cfg, rehearsal)
    opt = fleet.distributed_optimizer(bench.gpt1p3b_optimizer(), strategy)
    step = fleet.distributed_jit(model, opt, bench.train_loss)
    ids = _seeded_batch(cfg, batch, seq)
    t_build = time.perf_counter() - t0

    devices = set(jax.devices()[:4])
    annotated = {n: tuple(getattr(p, "pspec", None) or ())
                 for n, p in model.named_parameters()}
    split = 0
    for name, v in step.params.items():
        if set(v.sharding.device_set) != devices:
            raise RuntimeError(f"{name} is not on the four devices: "
                               f"{v.sharding}")
        # the model's own annotation decides (ZeRO-1 splits no weight)
        wants = any(step.mesh.shape[ax] > 1 for entry in annotated[name]
                    for ax in ([entry] if isinstance(entry, str)
                               else entry or ()))
        if wants == v.sharding.is_fully_replicated:
            raise RuntimeError(f"{name}: annotated {annotated[name]} "
                               f"but placed {v.sharding}")
        split += wants
    slots_split = sum(
        not s.sharding.is_fully_replicated
        for slots in step.opt_state["slots"].values()
        for s in slots.values())
    if not split or not slots_split:
        raise RuntimeError(f"nothing is sharded: {split} params, "
                           f"{slots_split} optimizer slots split")
    say(f"train4: mesh {dict(step.mesh.shape)} {split}/"
        f"{len(step.params)} params split over mp, {slots_split} "
        f"optimizer slots split (ZeRO-1)")

    t = time.perf_counter()
    losses = [float(step((ids, ids))) for _ in range(steps)]
    t_run = time.perf_counter() - t
    say(f"train4: losses {[round(x, 4) for x in losses]} build "
        f"{t_build:.1f}s {steps} steps incl. compile {t_run:.1f}s")
    _check_losses(losses)

    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()[:4]]
    say(f"train4: bytes_in_use per device {in_use} peak "
        f"{_peak_bytes(jax)}")
    if not rehearsal and not all(in_use):  # the CPU reports no stats
        raise RuntimeError(f"a device holds nothing: {in_use}")
    # the program that just ran, read back from the compile cache
    batch_raw = jax.tree_util.tree_map(
        lambda v, s: jax.device_put(v, s), (ids, ids),
        step._batch_sharding((ids, ids)))
    text = step._step.lower(step.params, step.buffers, step.opt_state,
                            step._key, step._lr_device(),
                            batch_raw).compile().as_text()
    found = collectives_in(text)
    say(f"train4: collectives in the compiled step: {found}")
    if not found:
        raise RuntimeError("the sharded step holds no collective")
    return {"losses": losses, "device": bench.device_block()}


# ---------------------------------------------------------------------------
# parent: subprocess / socket / json only
# ---------------------------------------------------------------------------

def child_env(rehearsal: bool, chips: int) -> dict:
    env = dict(os.environ)
    if rehearsal:
        flag = "--xla_force_host_platform_device_count"
        kept = [f for f in env.get("XLA_FLAGS", "").split()
                if not f.startswith(flag)]
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = " ".join(kept + [f"{flag}={chips}"])
    return env


def cache_files() -> int:
    d = os.environ.get("JAX_COMPILATION_CACHE_DIR", "").strip() or \
        os.path.join(ROOT, ".jax_cache")
    return sum(len(fs) for _, _, fs in os.walk(d))


def stop(proc: subprocess.Popen) -> None:
    """End a child and everything it started."""
    if proc.poll() is None:
        for sig in (signal.SIGINT, signal.SIGKILL):
            try:
                os.killpg(proc.pid, sig)
            except ProcessLookupError:
                break
            try:
                proc.wait(timeout=20)
                break
            except subprocess.TimeoutExpired:
                continue
    proc.wait()


def run_child(phase: str, args, timeout_s: float) -> dict:
    """Run one training phase as a child of this script; its last line
    is its result."""
    n0, t0 = cache_files(), time.perf_counter()
    cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
           "--chips", str(args.chips)]
    if args.cpu_rehearsal:
        cmd.append("--cpu-rehearsal")
    log = os.path.join(OUT_DIR, f"{phase}.log")
    with open(log, "wb") as err:
        proc = subprocess.Popen(
            cmd, cwd=ROOT, env=child_env(args.cpu_rehearsal, args.chips),
            stdout=subprocess.PIPE, stderr=err, start_new_session=True)
        try:
            out, _ = proc.communicate(timeout=timeout_s)
        finally:
            stop(proc)
    lines = out.decode("utf-8", "replace").splitlines()
    for line in lines[:-1] if proc.returncode == 0 else lines:
        print(line, flush=True)
    if proc.returncode != 0:
        with open(log, "rb") as f:
            tail = f.read()[-3000:].decode("utf-8", "replace")
        raise RuntimeError(f"phase {phase} exited {proc.returncode}; "
                           f"end of {log}:\n{tail}")
    result = json.loads(lines[-1])
    say(f"{phase}: done in {time.perf_counter() - t0:.1f}s, compile "
        f"cache files {n0} -> {cache_files()}")
    return result


def rpc(port: int, payload: dict, on_token=None) -> dict:
    """One newline-JSON request; streamed tokens go to ``on_token``."""
    with socket.create_connection(("127.0.0.1", port),
                                  timeout=REQUEST_TIMEOUT_S) as s:
        s.sendall((json.dumps(payload) + "\n").encode())
        for line in s.makefile("r", encoding="utf-8"):
            msg = json.loads(line)
            if "token" in msg:
                if on_token is not None:
                    on_token(msg["token"])
                continue
            return msg
    raise ConnectionError("server closed the connection mid-request")


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def run_server(tag: str, args, mesh: int = 0) -> dict:
    """Start the serving CLI with its defaults, drive it over the
    socket, check every reply, drain it and stop it. Returns the tokens
    per request and the device facts the server reported."""
    model = "gpt_tiny" if args.cpu_rehearsal else "gpt_1p3b"
    vocab = 1024 if args.cpu_rehearsal else 50304
    # a repeat must share one full 64-token page to hit the prefix cache
    lens = [12, 40, 100]
    new_tokens = 8
    rng = random.Random(SEED)
    prompts = [[rng.randrange(vocab) for _ in range(n)] for n in lens]

    port = free_port()
    cmd = [sys.executable, "-m", "paddle_tpu.serving.server", "--model",
           model, "--port", str(port)]
    if mesh:
        cmd += ["--mesh", f"model={mesh}"]
    n0, t0 = cache_files(), time.perf_counter()
    log = os.path.join(OUT_DIR, f"{tag}.log")
    with open(log, "wb") as out:
        proc = subprocess.Popen(
            cmd, cwd=ROOT, env=child_env(args.cpu_rehearsal, args.chips),
            stdout=out, stderr=subprocess.STDOUT, start_new_session=True)
    try:
        result = _drive_server(tag, proc, port, prompts, new_tokens,
                               mesh, args.cpu_rehearsal, t0)
    except BaseException:
        with open(log, "rb") as f:
            sys.stderr.write(f"end of {log}:\n" + f.read()[-3000:].decode(
                "utf-8", "replace") + "\n")
        raise
    finally:
        stop(proc)
    say(f"{tag}: done in {time.perf_counter() - t0:.1f}s, compile cache "
        f"files {n0} -> {cache_files()}")
    return result


def _drive_server(tag, proc, port, prompts, new_tokens, mesh, rehearsal,
                  t0) -> dict:
    while True:  # listening means the model is built and placed
        if proc.poll() is not None:
            raise RuntimeError(f"{tag}: server exited {proc.returncode} "
                               f"before listening")
        if time.perf_counter() - t0 > SERVER_READY_TIMEOUT_S:
            raise TimeoutError(f"{tag}: server not listening after "
                               f"{SERVER_READY_TIMEOUT_S}s")
        try:
            socket.create_connection(("127.0.0.1", port), 1).close()
            break
        except OSError:
            time.sleep(0.5)
    say(f"{tag}: listening after {time.perf_counter() - t0:.1f}s")

    def generate(prompt, stream=False):
        streamed = []
        t = time.perf_counter()
        rep = rpc(port, {"op": "generate", "prompt": prompt,
                         "max_new_tokens": new_tokens, "stream": stream},
                  on_token=streamed.append)
        dt = time.perf_counter() - t
        if "error" in rep:
            raise RuntimeError(f"{tag}: generate({len(prompt)}) -> {rep}")
        got = rep["tokens"][len(prompt):]
        if rep["tokens"][:len(prompt)] != prompt or \
                len(got) != new_tokens:
            raise RuntimeError(
                f"{tag}: asked {new_tokens} tokens after a "
                f"{len(prompt)}-token prompt, got {rep['tokens']}")
        if stream and streamed != got:
            raise RuntimeError(f"{tag}: streamed {streamed} != {got}")
        say(f"{tag}: prompt {len(prompt):3d} -> {got} in {dt:.2f}s"
            f"{' (streamed)' if stream else ''}")
        return got

    # two at once (one streamed): the engine batches them across slots
    pair = [None, None]

    def worker(i):
        pair[i] = generate(prompts[i], stream=bool(i))

    threads = [threading.Thread(target=worker, args=(i,)) for i in (0, 1)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    if None in pair:
        raise RuntimeError(f"{tag}: a concurrent request failed")
    first = generate(prompts[2])    # cold: compiles its prompt bucket
    again = generate(prompts[2])    # warm, and shares a cached page
    if again != first:
        raise RuntimeError(f"{tag}: the repeated prompt returned "
                           f"{again}, first {first}")

    health = rpc(port, {"op": "health"})
    stats = rpc(port, {"op": "stats"})
    counters = stats["stats"]["counters"]
    cache = stats["prefix_cache"]
    say(f"{tag}: health status {health['status']} steps "
        f"{health['steps']} engine_restarts {health['engine_restarts']} "
        f"engine_errors_total {counters['engine_errors_total']} "
        f"prefix cache hit pages {cache['hit_pages']}")
    if health["status"] != "ok" or health["engine_restarts"] or \
            counters["engine_errors_total"] or \
            counters["engine_restarts_total"]:
        raise RuntimeError(f"{tag}: engine errors or restarts: {health} "
                           f"{counters}")
    if not cache["hit_pages"]:
        raise RuntimeError(f"{tag}: the repeated prompt missed the "
                           f"prefix cache: {cache}")

    costs = rpc(port, {"op": "trace"})["program_costs"]
    for kind, cost in sorted(costs.items()):
        if "error" in cost:
            raise RuntimeError(f"{tag}: program {kind}: {cost}")
        say(f"{tag}: program {kind}: kernels {cost['pallas_kernels']}"
            + (f" collectives {cost['collectives']}" if mesh else ""))
    kernels = costs["decode"]["pallas_kernels"]
    if rehearsal:
        say(f"{tag}: CPU rehearsal, every kernel gate picked its "
            f"reference")
    else:
        # on a TPU a shape the gates admit must have run the kernels
        walk = [k for k in kernels if k.startswith("paged_decode")]
        if not walk or (not mesh and "fused_argmax" not in kernels):
            raise RuntimeError(f"{tag}: the decode program lacks its "
                               f"Pallas kernels: {kernels}")
        say(f"{tag}: decode attention ran {walk[0]} "
            + ("(o-projection inside the kernel)"
               if walk[0] == "paged_decode_fused" else
               "(fused-epilogue gate closed: the o-projection weight "
               "is over its VMEM budget, XLA runs the matmul)"))
    if mesh:
        weights = health["mesh"]["weights"]
        say(f"{tag}: mesh {health['mesh']['axes']} weights {weights}")
        if health["mesh"]["devices"] != mesh or not weights["split"] or \
                weights["split"] != weights["pspec_split"] or \
                not weights["on_all_devices"]:
            raise RuntimeError(f"{tag}: weights are not spread as their "
                               f"pspecs say: {health['mesh']}")
        if not costs["decode"]["collectives"]:
            raise RuntimeError(f"{tag}: the sharded decode program "
                               f"holds no collective")

    profile = rpc(port, {"op": "profile"})
    devices = profile["devices"][:max(1, mesh)]
    in_use = [(d["memory_stats"] or {}).get("bytes_in_use")
              for d in devices]
    say(f"{tag}: profile platform {devices[0]['platform']} bytes_in_use "
        f"{in_use} peak "
        f"{[(d['memory_stats'] or {}).get('peak_bytes_in_use') for d in devices]}")
    if not rehearsal and (devices[0]["platform"] != "tpu"
                          or not all(in_use)):
        raise RuntimeError(f"{tag}: not every device is a TPU that "
                           f"holds bytes: {profile['devices']}")

    leak = rpc(port, {"op": "leak_check"})
    drain = rpc(port, {"op": "drain"})
    say(f"{tag}: leak_check {leak} drain {drain}")
    if not leak.get("ok") or not drain.get("ok"):
        raise RuntimeError(f"{tag}: leak_check {leak} drain {drain}")
    return {"tokens": pair + [first, again],
            "platform": devices[0]["platform"]}


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4: run only the four-chip paths and what "
                             "they are compared with")
    parser.add_argument("--cpu-rehearsal", action="store_true",
                        help="tiny presets, children pinned to the CPU "
                             "(virtual devices for --chips 4)")
    parser.add_argument("--phase", choices=("train", "train4"),
                        help=argparse.SUPPRESS)  # child mode
    args = parser.parse_args(argv)

    if args.phase:  # a child: the only place JAX is touched
        steps = 3 if args.chips == 4 else 4
        fn = child_train if args.phase == "train" else child_train4
        print(json.dumps(fn(args.cpu_rehearsal, steps)), flush=True)
        return

    os.makedirs(OUT_DIR, exist_ok=True)
    say(f"chips {args.chips} rehearsal {args.cpu_rehearsal} compile "
        f"cache files at start {cache_files()}")
    train = run_child("train", args, TRAIN_TIMEOUT_S)
    if args.chips == 1:
        serve = run_server("serve", args)
        device = train["device"]
    else:
        train4 = run_child("train4", args, TRAIN_TIMEOUT_S)
        for a, b in zip(train["losses"], train4["losses"]):
            if abs(a - b) > HYBRID_LOSS_RTOL * abs(a):
                raise RuntimeError(
                    f"mp2 x sharding2 losses {train4['losses']} differ "
                    f"from one device's {train['losses']} by more than "
                    f"{HYBRID_LOSS_RTOL:g}")
        say(f"train4 vs train: losses agree within {HYBRID_LOSS_RTOL:g}")
        one = run_server("serve", args)
        serve = run_server("serve4", args, mesh=4)
        if serve["tokens"] != one["tokens"]:
            raise RuntimeError(
                f"--mesh model=4 tokens {serve['tokens']} differ from "
                f"the one-device server's {one['tokens']}")
        say("serve4 vs serve: identical greedy tokens")
        device = train4["device"]
    if serve["platform"] != device["platform"] or \
            device["count"] != args.chips:
        raise RuntimeError(f"phases disagree on the device: {device} vs "
                           f"server {serve['platform']}, asked "
                           f"{args.chips} chips")
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    main()
