"""The training driver: ``paddle_tpu.jit.TrainStep.multi_step`` from the
configuration, launches back to back for the whole window.

Set-up builds ONE object (the compiled step with its state), drives it
from the seed through its first ``check_launches`` launches with the
window's own call and feed, reads what `correct` compares (each loss;
the first gradient's norm per leaf, from the optimizer's first moment
after one step; the parameters' change per leaf after the launches) and
hands the same object to the window. The plain reference follows those
steps after the window has closed, the peak of memory has been read and
the program's state is freed.
"""

from __future__ import annotations

import collections
import gc
import math
import sys
import time

import numpy as np

from benchmarks import common, traffic as gen, weights as wts
from benchmarks.model import build_gpt


def build(cell, seed: int):
    """The program's trainer for the configuration, loaded with the
    benchmark's weights."""
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStep

    cfg, hp = cell.config, cell.config["train"]
    model = build_gpt(cfg, seed,
                      use_flash_attention=bool(hp["flash_attention"]),
                      remat=bool(hp["remat"]), loss_chunk_size=0)
    opt = optim.AdamW(learning_rate=hp["learning_rate"], beta1=hp["beta1"],
                      beta2=hp["beta2"], epsilon=hp["epsilon"],
                      weight_decay=hp["weight_decay"])
    return TrainStep(model, opt, lambda m, b: m(b[0], labels=b[1]))


def launch(step, ids):
    return step.multi_step((ids, ids))


def first_gradient_norms(step, beta1: float) -> dict:
    """The gradient the optimizer got at step 1, from its first moment
    after that step (m1 = (1 - beta1) g1): its norm per leaf, and its
    elements at the sampled places of each leaf."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def read(slots):
        g = {k: s["moment1"].astype(jnp.float32) / (1.0 - beta1)
             for k, s in slots.items()}
        return ({k: jnp.sqrt(jnp.sum(jnp.square(v))) for k, v in g.items()},
                {k: v.reshape(-1)[common.sample_index(v.size)]
                 for k, v in g.items()})
    norms, samples = jax.device_get(read(step.opt_state["slots"]))
    return {k: float(v) for k, v in norms.items()}, samples


def change_norms(step, cfg: dict, seed: int) -> dict:
    """Per-leaf norm of (parameters now - parameters from the seed), and
    the change itself for the vector leaves (biases, norms: a few MB)."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def norms(now, start):
        return {k: jnp.sqrt(jnp.sum(jnp.square(
            now[k].astype(jnp.float32) - start[k].astype(jnp.float32))))
            for k in now}
    @jax.jit
    def vectors(now, start):
        return {k: now[k].astype(jnp.float32) - start[k].astype(jnp.float32)
                for k in now if now[k].ndim == 1}
    start = wts.make_weights(cfg, seed)
    out = jax.device_get(norms(step.params, start))
    vec = jax.device_get(vectors(step.params, start))
    del start
    return {k: float(v) for k, v in out.items()}, vec


def first_steps(step, cell, seed: int, feed: list) -> dict:
    """Drive the step through its first launches with the window's own
    call and feed, and read what `correct` compares."""
    import jax
    cfg = cell.config
    prog = {"losses": []}
    for i, ids in enumerate(feed):
        prog["losses"] += [float(x) for x in np.asarray(
            jax.device_get(launch(step, ids)), np.float32)]
        if i == 0 and ids.shape[0] == 1:
            prog["grad_norms"], prog["grad_samples"] = first_gradient_norms(
                step, float(cfg["train"]["beta1"]))
    prog["delta_norms"], prog["delta_vectors"] = change_norms(step, cfg, seed)
    return prog


def run(cell, opts) -> dict:
    import jax

    cfg, tr = cell.config, cell.traffic
    seed, seconds = opts.seed, opts.seconds
    vocab = cfg["vocab_size"]
    k_steps, batch, seq = (int(tr["steps_per_launch"]), int(tr["batch"]),
                           int(tr["seq"]))
    n_check = int(tr["check_launches"])
    compiles = common.Compiles()
    marks = common.Marks()
    pauses = common.GcPauses()

    step = build(cell, seed)
    marks("built")
    feed = [gen.train_batch(tr, vocab, seed, i) for i in range(n_check)]
    prog = first_steps(step, cell, seed, feed)
    marks("first_steps")
    pauses.settle()
    setup_done = time.monotonic()
    compiled_in_setup = compiles.n

    # -- the window: launches back to back, `depth` in flight -------------
    depth = int(tr.get("launches_in_flight", 2))
    n_launch = 0

    done_at = []  # when each launch's losses reached the host

    def drive(until):
        """Launch until ``until()`` says stop; returns the losses."""
        nonlocal n_launch
        pending, got = collections.deque(), []

        def fetch():
            got.append(jax.device_get(pending.popleft()))
            done_at.append(time.monotonic())
        while not until():
            ids = gen.train_batch(tr, vocab, seed, n_check + n_launch)
            pending.append(launch(step, ids))
            n_launch += 1
            if len(pending) > depth:
                fetch()
        while pending:
            fetch()
        return got

    t0 = time.monotonic()
    losses = drive(lambda: time.monotonic() - t0 >= seconds)
    window = time.monotonic() - t0
    marks("window")
    gaps = np.diff(np.asarray(done_at)) * 1e3
    compiled_in_window = compiles.n - compiled_in_setup
    flat = np.concatenate([np.asarray(x, np.float32).ravel() for x in losses])
    failed = int(np.sum(~np.isfinite(flat)))
    n_window = n_launch
    tokens = n_window * k_steps * batch * seq
    peak = common.memory_peak_bytes()

    # -- traced: a few more seconds of the same launches, after the
    # window has closed, so that the window's rate is the untraced one
    events, trace_win = None, None
    if opts.trace:
        tlen = float(tr.get("trace_seconds", 3.0))
        tw0 = time.monotonic()
        events, (a, b) = common.trace(
            lambda: drive(lambda: time.monotonic() - tw0 >= tlen))
        trace_win = (a - t0, b - t0)

    # -- free the program's state, then the reference ---------------------
    del step, losses
    pauses.release()
    gc.collect()
    numbers = check(cell, seed, feed, prog)
    marks("checked")
    return {
        "setup_done": setup_done, "attempted": n_window * k_steps,
        "failed": failed, "memory_peak_bytes": peak,
        "end_to_end": {"train_tokens_per_s": tokens / window},
        "numbers": numbers,
        "notes": {"launches": n_window, "window_s": window,
                  "compiles_in_setup": compiled_in_setup,
                  "compiles_in_window": compiled_in_window,
                  "phases_s": marks.since(), "gc": pauses.notes(),
                  "launch_ms_p50_max": [float(np.median(gaps)),
                                        float(np.max(gaps))]
                  if len(gaps) else None,
                  "launches_over_1p5_p50": int(np.sum(
                      gaps > 1.5 * np.median(gaps))) if len(gaps) else 0,
                  "longest_gap_at_s": float(
                      done_at[int(np.argmax(gaps))] - t0) if len(gaps) else None,
                  "last_loss": float(flat[-1]) if len(flat) else math.nan},
        "artifacts": {"events": events, "trace_window": trace_win,
                      "window_s": window},
    }


def reference(cell, seed: int, feed: list, **how) -> dict:
    """The plain reference's readings of the steps of ``feed``; ``how``
    makes it the control (``lowp``) or plants a ``fault``."""
    cfg = cell.config
    ref_mod = cell.load_module("references", cfg["reference"])
    batches = [ids[j] for ids in feed for j in range(ids.shape[0])]
    return ref_mod.train_steps(cfg, wts.make_weights(cfg, seed), batches,
                               cfg["train"],
                               sample_index=common.sample_index, **how)


def check(cell, seed: int, feed: list, prog: dict) -> dict:
    """The numbers `correct` compares: the program's readings of its
    first steps against the plain reference's of the same steps."""
    return compare(prog, reference(cell, seed, feed))


def compare(prog: dict, ref: dict) -> dict:
    out = {"loss_gap": max(
        (abs(p - r) / abs(r) if math.isfinite(p) else math.inf)
        for p, r in zip(prog["losses"], ref["losses"]))}
    if len(prog["losses"]) != len(ref["losses"]):
        out["loss_gap"] = math.inf
    if "grad_norms" in prog:
        out["grad_gap"], _ = common.norm_gap(prog["grad_norms"],
                                             ref["grad_norms"])
    if "grad_samples" in prog and ref.get("grad_samples"):
        out["grad_diff"], _ = common.diff_gap(prog["grad_samples"],
                                              ref["grad_samples"])
    # leaves whose gradient is nought to rounding in the reference move
    # under Adam by round-off alone: left out of the change by a rule on
    # the reference's gradient (under a thousandth of the median leaf's)
    # The same rule element by element inside a vector leaf: the fused
    # QKV bias holds the key's bias, whose gradient is nought under
    # softmax, as a third of its elements.
    g = ref["grad_norms"]
    med = sorted(g.values())[len(g) // 2]
    keep = {k for k, v in g.items() if v >= 1e-3 * med}
    pd, rd = dict(prog["delta_norms"]), dict(ref["delta_norms"])
    for k, gv in ref.get("grad_vectors", {}).items():
        live = np.asarray(gv) >= 1e-3 * np.median(gv)
        pd[k] = float(np.linalg.norm(np.asarray(prog["delta_vectors"][k])[live]))
        rd[k] = float(np.linalg.norm(np.asarray(ref["delta_vectors"][k])[live]))
    prog, ref = dict(prog, delta_norms=pd), dict(ref, delta_norms=rd)
    out["update_gap"], _ = common.norm_gap(pd, rd, keep)
    for what in ("grad_norms", "delta_norms"):
        if what in prog:
            rows = sorted(((abs(prog[what][k] - ref[what][k])
                            / max(ref[what][k], 1e-30), k) for k in keep),
                          reverse=True)[:4]
            print(f"[bench] worst {what}: " + "; ".join(
                f"{k} prog {prog[what][k]:.4g} ref {ref[what][k]:.4g}"
                for _, k in rows), file=sys.stderr)
    return out
