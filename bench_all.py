"""Staged benchmark sweep — BASELINE.md configs 1, 2 and 5.

Emits one JSON object with a result per staged config:
  - resnet50: dygraph-style train step, imgs/s + MFU (config 1)
  - bert_base: traced-program pretrain step, tokens/s + MFU (config 2)
  - inference: AOT predictor serving latency p50/p99 for ResNet-50 and
    BERT-base (config 5)

The GPT-1.3B number (config 3) stays in bench.py. The 10B config 4 is
proven by AOT compilation instead (tools/scale_proof.py ->
SCALE_PROOF.json).

A measurement needs the chip: without a TPU this fails, it does not
fall back. ``--cpu-rehearsal`` runs every phase at its tiny CPU size
(metrics named ``*_cpu_smoke``); a phase that raises ends the run.

Reference analog: tools/test_model_benchmark.sh:1 (whole-model CI
benchmark gate) — the reference ships the gate but no numbers
(BASELINE.md); these are the numbers for the TPU stack.
"""

from __future__ import annotations

import json
import os
import time
from typing import Dict

import numpy as np

GIB = 1024 ** 3


def _peak_flops() -> float:
    from bench import peak_flops
    return peak_flops()


# parameter-name tokens that stay fp32 under the bf16 recipe (norm
# statistics); shared with tools/scale_proof.py's abstract variant
BF16_KEEP_TOKENS = ("bn", "norm", "ln_")


def _to_bf16_except_norms(model):
    """bf16 weights with fp32 norm params/buffers (the GPT bench recipe:
    MXU runs bf16; layernorm/batchnorm statistics stay fp32)."""
    import jax.numpy as jnp
    model.to(dtype="bfloat16")
    for name, p in model.named_parameters():
        if any(t in name for t in BF16_KEEP_TOKENS):
            p.value = p.value.astype(jnp.float32)
    for name, b in model.named_buffers():
        if b is not None and hasattr(b, "value") and \
                np.issubdtype(np.asarray(b.value).dtype, np.floating):
            b.value = b.value.astype(jnp.float32)


def _timed_windows(run, n_windows: int = 3):
    """Median-of-windows wall time; run() must end with a host sync."""
    times = []
    for _ in range(n_windows):
        t0 = time.perf_counter()
        run()
        times.append(time.perf_counter() - t0)
    return float(np.median(times)), times


def bench_resnet50(on_tpu: bool) -> Dict:
    """Config 1: ResNet-50 ImageNet-shape training throughput (dygraph
    API surface, one fused step under the hood)."""
    import paddle_tpu as pt
    import paddle_tpu.optimizer as optim
    from paddle_tpu import nn  # noqa: F401
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.vision.models import resnet50, resnet18

    pt.seed(0)
    if on_tpu:
        # 16 steps/window: the ~50 ms resnet step needs more launch
        # amortization than the ~330 ms GPT step
        model, batch, hw, steps = resnet50(), 128, 224, 16
        _to_bf16_except_norms(model)
        img_dtype = "bfloat16"
    else:
        model, batch, hw, steps = resnet18(num_classes=10), 2, 64, 2
        img_dtype = "float32"

    import paddle_tpu.dispatch as dispatch
    F = dispatch.wrapped_ops

    def train_fn(m, b):
        logits = m(b[0])
        return F["mean"](F["cross_entropy"](
            F["cast"](logits, "float32"), b[1]))

    opt = optim.Momentum(learning_rate=0.1, momentum=0.9)
    step = TrainStep(model, opt, train_fn)

    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    x = rng.standard_normal((batch, 3, hw, hw)).astype(np.float32)
    if img_dtype != "float32":
        x = x.astype(jnp.bfloat16)
    y = rng.integers(0, 10, (batch,)).astype(np.int64)
    # stage the epoch's batches on device OUTSIDE the timed window (what
    # the prefetching dataloader does in a real loop)
    xs = jnp.asarray(np.broadcast_to(x, (steps,) + x.shape).copy())
    ys = jnp.asarray(np.broadcast_to(y, (steps,) + y.shape).copy())

    losses = step.multi_step((xs, ys))
    final = float(losses[-1])  # hard sync
    assert np.isfinite(final), final

    def run():
        float(step.multi_step((xs, ys))[-1])

    dt, _ = _timed_windows(run)
    imgs_s = batch * steps / dt
    # 4.09 GFLOP fwd per 224x224 image (public ResNet-50 figure), x3 for
    # fwd+bwd
    flops_img = 3 * 4.09e9 if hw == 224 else 0.0
    mfu = imgs_s * flops_img / _peak_flops() if on_tpu else 0.0
    return {"metric": "resnet50_train_imgs_per_sec_chip" if on_tpu
            else "resnet18_train_imgs_per_sec_cpu_smoke",
            "value": round(imgs_s, 1), "unit": "imgs/s",
            "mfu_pct": round(100 * mfu, 2),
            "batch": batch, "image": hw, "dtype": img_dtype,
            "steps_per_window": steps}


def bench_bert_base(on_tpu: bool) -> Dict:
    """Config 2: BERT-base MLM pretrain step through the traced-program
    path (whole step compiled by XLA — the Executor->XLA analog)."""
    import paddle_tpu as pt
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.bert import (BertForPretraining, bert_base,
                                        bert_tiny)

    pt.seed(0)
    if on_tpu:
        cfg = bert_base(hidden_dropout_prob=0.0,
                        attention_probs_dropout_prob=0.0)
        # r5 sweep (pre-round record, FOLDED
        # layout-native Pallas attention — [B,S,E] column groups, no
        # [B,H,S,D] transposes, lse-free fused recompute backward —
        # executed-FLOPs MFU): b64 gathered-head 213.8k tokens/s at
        # ~63.9% MFU (r4: 164.6k / 49.2% on the transposing kernel;
        # the r4 "~50% h=768 ceiling" was the transpose tax, now gone)
        batch, seq, steps = 64, 512, 16
        # reference pretrain data format: max_predictions_per_seq
        # masked slots per sequence; the MLM head runs only on them
        max_preds = 76
    else:
        cfg = bert_tiny()
        batch, seq, steps = 2, 32, 2
        max_preds = 0  # cover the full-sequence-head path on CPU
    model = BertForPretraining(cfg)
    if on_tpu:
        _to_bf16_except_norms(model)

    if max_preds:
        def train_fn(m, b):
            return m(b[0], masked_positions=b[1], labels=b[2])
    else:
        def train_fn(m, b):
            return m(b[0], labels=b[1])

    opt = optim.AdamW(learning_rate=1e-4)
    step = TrainStep(model, opt, train_fn)

    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    if max_preds:
        pos = np.stack([rng.choice(seq, max_preds, replace=False)
                        for _ in range(batch)]).astype(np.int32)
        labels = np.take_along_axis(ids, pos, 1).astype(np.int64)
        batch_np = (ids, pos, labels)
    else:
        labels = np.where(rng.random((batch, seq)) < 0.15, ids,
                          -100).astype(np.int64)
        batch_np = (ids, labels)
    staged = tuple(jnp.asarray(np.broadcast_to(a, (steps,) + a.shape)
                               .copy()) for a in batch_np)

    final = float(step.multi_step(staged)[-1])
    assert np.isfinite(final), final

    def run():
        float(step.multi_step(staged)[-1])

    dt, _ = _timed_windows(run)
    tok_s = batch * seq * steps / dt
    flops_tok = bert_executed_flops_per_token(model, cfg, seq,
                                              max_preds or seq)
    mfu = tok_s * flops_tok / _peak_flops() if on_tpu else 0.0
    return {"metric": "bert_base_pretrain_tokens_per_sec_chip" if on_tpu
            else "bert_tiny_pretrain_tokens_per_sec_cpu_smoke",
            "value": round(tok_s, 1), "unit": "tokens/s",
            "mfu_pct": round(100 * mfu, 2),
            "batch": batch, "seq": seq,
            "max_predictions_per_seq": max_preds or seq,
            "mfu_note": "MFU counts EXECUTED matmul+attention FLOPs "
                        "(embedding lookups and the head's skipped "
                        "positions are not credited); the gathered MLM "
                        "head raises tokens/s, not MFU",
            "steps_per_window": steps}


def bert_executed_flops_per_token(model, cfg, seq: int,
                                  head_positions: int) -> float:
    """Honest per-token training FLOPs for the BERT pretrain step:
    6x the matmul params actually traversed (encoder + MLM transform +
    the tied vocab head scaled by the fraction of positions it runs on)
    plus the attention score/value term. Embedding LOOKUPS carry no
    matmul FLOPs — unlike the LLM-style 6N-total-params convention,
    which for BERT-base would credit 22% phantom FLOPs."""
    emb_names = ("embeddings.word_embeddings",
                 "embeddings.position_embeddings",
                 "embeddings.token_type_embeddings",
                 "pooler")  # pooler runs on ONE token per sequence
    n_body = sum(int(np.prod(p.shape))
                 for name, p in model.named_parameters()
                 if not name.startswith(("mlm_", "nsp_")) and
                 not any(t in name for t in emb_names))
    h = cfg.hidden_size
    n_transform = h * h + h  # mlm_transform
    n_head = cfg.vocab_size * h  # tied decoder matmul (executed!)
    frac = head_positions / seq
    return (6.0 * n_body + 6.0 * (n_transform + n_head) * frac +
            12.0 * cfg.num_hidden_layers * h * seq)


def bench_long_context(on_tpu: bool) -> Dict:
    """Staged long-context config: GPT-1.3B at S=8192 on one chip —
    the shape where the Pallas flash kernel is the only compiling path
    (XLA attention's S^2 scores exceed HBM). Config from the r4 sweep:
    chunked CE 512 + remat_every=3 + remat_save_attention (save the
    flash out+lse residuals so backward recompute skips the flash
    forward; remat4/6 fail to compile on 16G HBM)."""
    import jax.numpy as jnp

    import paddle_tpu as pt
    import paddle_tpu.optimizer as optim
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models import GPTConfig, GPTForCausalLM, gpt_tiny

    if on_tpu:
        cfg = GPTConfig(vocab_size=32768, hidden_size=2048,
                        num_layers=24, num_heads=16, max_seq_len=8192,
                        dropout=0.0, attn_dropout=0.0, dtype="bfloat16",
                        loss_chunk_size=512, remat=True, remat_every=3,
                        remat_save_attention=True)
        batch, seq, steps = 1, 8192, 4
    else:
        cfg = gpt_tiny(remat=True, remat_save_attention=True)
        batch, seq, steps = 1, 64, 2

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        _to_bf16_except_norms(model)
    step = TrainStep(model, optim.AdamW(learning_rate=1e-4),
                     lambda m, b: m(b[0], labels=b[1]))
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq)).astype(np.int32)
    xs = jnp.asarray(np.broadcast_to(ids, (steps,) + ids.shape).copy())

    final = float(step.multi_step((xs, xs))[-1])
    assert np.isfinite(final), final

    def run():
        float(step.multi_step((xs, xs))[-1])

    dt, _ = _timed_windows(run)
    tok_s = batch * seq * steps / dt
    n_params = sum(int(np.prod(p.shape)) for p in model.parameters())
    flops_tok = 6.0 * n_params + 12.0 * cfg.num_layers * \
        cfg.hidden_size * seq
    mfu = tok_s * flops_tok / _peak_flops() if on_tpu else 0.0
    return {"metric": "gpt1p3b_s8192_train_tokens_per_sec_chip"
            if on_tpu else "gpt_tiny_longctx_train_cpu_smoke",
            "value": round(tok_s, 1), "unit": "tokens/s",
            "mfu_pct": round(100 * mfu, 2),
            "batch": batch, "seq": seq,
            "config": "flash attention (Pallas) + chunked CE 512 + "
                      "remat every 3 + remat_save_attention (save the "
                      "flash out+lse residuals; backward recompute "
                      "skips the flash forward)",
            "note": "the configuration that REQUIRES the flash kernel: "
                    "XLA attention + full logits fails to compile at "
                    "this shape (S^2 scores / [B,S,V] logits exceed "
                    "HBM); remat4/6 fail to compile on 16G HBM even "
                    "with the saved residuals",
            "steps_per_window": steps}


def _decode_1p3b_cfg():
    """The shared GPT-1.3B decode-bench config (decode, paged_decode and
    ragged_serving must measure the SAME model or their numbers stop
    being comparable)."""
    from paddle_tpu.models import GPTConfig
    return GPTConfig(vocab_size=32768, hidden_size=2048,
                     num_layers=24, num_heads=16, max_seq_len=2048,
                     dropout=0.0, attn_dropout=0.0, dtype="bfloat16",
                     use_flash_attention=False, loss_chunk_size=0)


def bench_decode(on_tpu: bool) -> Dict:
    """Generation decode throughput: GPT-1.3B greedy decode through the
    jitted StaticKVCache scan (one launch for prefill + all decode
    steps), batch-swept. Decode is weight-bandwidth-bound, so tokens/s
    scales with batch until HBM runs out of KV room (r3 verdict weak
    #6: the serving entry had latency only, no decode tokens/s)."""
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.models import GPTConfig, GPTForCausalLM, gpt_tiny

    if on_tpu:
        cfg = _decode_1p3b_cfg()
        # r4 sweep: decode is weights-bound and keeps scaling with
        # batch (b32 4.6k -> b128 7.5k tok/s); b256's KV at S=192 still
        # fits but prefill compile cost grows — 128 is the sweet spot
        batches, prompt, new_toks = (1, 8, 32, 64, 128), 128, 64
    else:
        cfg = gpt_tiny()
        batches, prompt, new_toks = (1,), 8, 4

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        _to_bf16_except_norms(model)
    model.eval()

    rng = np.random.default_rng(0)
    out: Dict = {"metric": "gpt1p3b_decode_tokens_per_sec_chip" if on_tpu
                 else "gpt_tiny_decode_tokens_per_sec_cpu_smoke",
                 "unit": "tokens/s", "prompt_len": prompt,
                 "new_tokens": new_toks,
                 "by_batch": {}}
    for b in batches:
        ids = jnp.asarray(rng.integers(
            0, cfg.vocab_size, (b, prompt)).astype(np.int32))

        def run_n(n):
            got = model.generate(pt.Tensor(ids), max_new_tokens=n,
                                 temperature=0.0, use_jit=True)
            v = got.value if hasattr(got, "value") else got
            np.asarray(v[:, -1])  # host fetch = hard sync

        if on_tpu:
            # two scan lengths; the difference isolates the per-token
            # decode rate (prefill + launch cancel in the subtraction)
            n_short = max(1, new_toks // 8)
            run_n(n_short)
            run_n(new_toks)  # compile + warm both
            dt_short, _ = _timed_windows(lambda: run_n(n_short))
            dt_full, _ = _timed_windows(lambda: run_n(new_toks))
            if dt_full <= dt_short:  # a host stall inverted the pair
                dt_short, _ = _timed_windows(lambda: run_n(n_short))
                dt_full, _ = _timed_windows(lambda: run_n(new_toks))
            if dt_full <= dt_short:
                raise RuntimeError(
                    f"decode b{b}: timing inverted twice (full "
                    f"{dt_full:.4f}s <= short {dt_short:.4f}s)")
            per_tok = (dt_full - dt_short) / (new_toks - n_short)
        else:  # CPU smoke: sub-ms noise swamps the subtraction
            run_n(new_toks)
            dt, _ = _timed_windows(lambda: run_n(new_toks))
            per_tok = dt / new_toks
        out["by_batch"][str(b)] = {
            "tokens_per_s": round(b / per_tok, 1),
            "ms_per_token": round(per_tok * 1e3, 3)}
    out["value"] = max(v["tokens_per_s"]
                       for v in out["by_batch"].values())

    # weight-only int8 decode (r4 verdict weak #4: the int8 path was
    # never wired where weight streaming dominates). Same harness at
    # the best fp batch; weights stream at half the bytes.
    from paddle_tpu.quantization.quant import convert_to_weight_only_int8
    best_b = max((v["tokens_per_s"], int(k))
                 for k, v in out["by_batch"].items())[1]
    n_conv = convert_to_weight_only_int8(model)
    # two regimes: at the big swept batch the KV-cache bytes are ~2x
    # the weight bytes so int8 buys ~12%; at small batch the 2.56 GB of
    # weights dominate and int8 approaches 2x — measure both
    int8_batches = [best_b] if not on_tpu else sorted({8, best_b})
    out["int8_weight_only"] = {"layers_converted": n_conv,
                               "by_batch": {}}
    for b8 in int8_batches:
        ids8 = jnp.asarray(rng.integers(
            0, cfg.vocab_size, (b8, prompt)).astype(np.int32))

        def run8(n):
            got = model.generate(pt.Tensor(ids8), max_new_tokens=n,
                                 temperature=0.0, use_jit=True)
            v = got.value if hasattr(got, "value") else got
            np.asarray(v[:, -1])

        n_short = max(1, new_toks // 8)
        run8(n_short)
        run8(new_toks)
        if on_tpu:
            dt_short, _ = _timed_windows(lambda: run8(n_short))
            dt_full, _ = _timed_windows(lambda: run8(new_toks))
            if dt_full <= dt_short:
                raise RuntimeError(
                    f"int8 decode b{b8}: timing inverted (full "
                    f"{dt_full:.4f}s <= short {dt_short:.4f}s)")
            per_tok = (dt_full - dt_short) / (new_toks - n_short)
            fp = out["by_batch"][str(b8)]["tokens_per_s"] \
                if str(b8) in out["by_batch"] else None
            entry = {"tokens_per_s": round(b8 / per_tok, 1),
                     "ms_per_token": round(per_tok * 1e3, 3),
                     "vs_bf16_same_batch": round(
                         (b8 / per_tok) / fp, 3) if fp else None}
        else:
            dt, _ = _timed_windows(lambda: run8(new_toks))
            entry = {"tokens_per_s": round(b8 * new_toks / dt, 1)}
        out["int8_weight_only"]["by_batch"][str(b8)] = entry
    return out


def bench_paged_decode(on_tpu: bool) -> Dict:
    """Paged-vs-static decode step time (the tentpole's A/B): the SAME
    model, prompts and scan harness, dense StaticKVCache vs the
    block-paged PagedKVCache (ragged paged-attention kernel on TPU,
    its reference on cpu) — plus the int8-KV variant, which halves the
    KV bytes that dominate the b128 step (5.5 GB of the 8.4 GB/step in
    the pre-round decode trace). Full-length equal-size sequences, so on-chip
    this isolates the kernel/layout cost; the RAGGED win (skip unused
    pages + mid-flight admission) is bench_ragged_serving's number."""
    import jax.numpy as jnp

    import paddle_tpu as pt
    from paddle_tpu.models import GPTConfig, GPTForCausalLM, gpt_tiny

    if on_tpu:
        cfg = _decode_1p3b_cfg()
        batch, prompt, new_toks, page = 128, 128, 64, 64
    else:
        cfg = gpt_tiny()
        batch, prompt, new_toks, page = 2, 8, 8, 8

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        _to_bf16_except_norms(model)
    model.eval()
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(
        0, cfg.vocab_size, (batch, prompt)).astype(np.int32))

    def run_n(n, mode):
        got = model.generate(pt.Tensor(ids), max_new_tokens=n,
                             temperature=0.0, use_jit=True,
                             kv_cache=mode, page_size=page)
        v = got.value if hasattr(got, "value") else got
        np.asarray(v[:, -1])

    out: Dict = {"metric": "gpt1p3b_paged_decode_ms_per_step_chip"
                 if on_tpu else "gpt_tiny_paged_decode_cpu_smoke",
                 "batch": batch, "prompt_len": prompt,
                 "new_tokens": new_toks, "page_size": page,
                 "by_mode": {}}
    for mode in ("static", "paged", "paged_int8"):
        if on_tpu:
            n_short = max(1, new_toks // 8)
            run_n(n_short, mode)
            run_n(new_toks, mode)
            dt_s, _ = _timed_windows(lambda: run_n(n_short, mode))
            dt_f, _ = _timed_windows(lambda: run_n(new_toks, mode))
            if dt_f <= dt_s:
                dt_s, _ = _timed_windows(lambda: run_n(n_short, mode))
                dt_f, _ = _timed_windows(lambda: run_n(new_toks, mode))
            if dt_f <= dt_s:
                out["by_mode"][mode] = {"error": "timing inverted twice"}
                continue
            per_step = (dt_f - dt_s) / (new_toks - n_short)
        else:
            run_n(new_toks, mode)
            dt, _ = _timed_windows(lambda: run_n(new_toks, mode))
            per_step = dt / new_toks
        out["by_mode"][mode] = {
            "ms_per_step": round(per_step * 1e3, 3),
            "tokens_per_s": round(batch / per_step, 1)}
    st = out["by_mode"].get("static", {}).get("ms_per_step")
    pg = out["by_mode"].get("paged", {}).get("ms_per_step")
    if st and pg:
        out["paged_vs_static"] = round(pg / st, 3)
    return out


def bench_ragged_serving(on_tpu: bool) -> Dict:
    """Continuous-batching ragged serving throughput: a mixed-length
    request stream through the fixed-slot paged decode engine
    (inference/continuous_batching.py) — admission, eviction and page
    recycling all on the hot path. tokens/s counts GENERATED tokens
    only. This is the workload the paging opens: the dense scan cannot
    admit a new request mid-flight at all."""
    import paddle_tpu as pt
    from paddle_tpu.inference import create_decode_engine
    from paddle_tpu.models import GPTConfig, GPTForCausalLM, gpt_tiny

    if on_tpu:
        cfg = _decode_1p3b_cfg()
        slots, page, max_seq = 32, 64, 1024
        lens = [64, 96, 128, 192, 256, 384, 512, 640]
        n_req, new_toks = 64, 64
    else:
        cfg = gpt_tiny()
        slots, page, max_seq = 2, 8, 64
        lens = [5, 9, 13]
        n_req, new_toks = 4, 8

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        _to_bf16_except_norms(model)
    model.eval()
    rng = np.random.default_rng(0)
    eng = create_decode_engine(model, num_slots=slots, page_size=page,
                               max_seq_len=max_seq)
    prompts = [rng.integers(0, cfg.vocab_size,
                            (lens[i % len(lens)],)).astype(np.int32)
               for i in range(n_req)]
    # warm THE MEASURED ENGINE's compiles (jitted prefill/decode are
    # per-instance closures, so a throwaway engine would compile its
    # own programs and discard them): run one short request per
    # distinct prompt bucket + the shared decode step through `eng`
    # itself, then let it drain — slots and pages all return to free
    for p in prompts[:len(lens)]:
        eng.submit(p, max_new_tokens=2)
    eng.run()

    steps_before = eng.steps
    t0 = time.perf_counter()
    rids = [eng.submit(p, max_new_tokens=new_toks) for p in prompts]
    try:
        results = eng.run()
    finally:
        eng.close()  # every exit path returns the pages (r7 contract)
    wall = time.perf_counter() - t0
    # the engine's host-driven loop pays one launch+fetch round trip
    # PER decode step and PER prefill (unlike the scanned decode's
    # single launch)
    timed_steps = eng.steps - steps_before
    dt = wall
    # run() drains per call, so results holds exactly the timed batch
    gen_tokens = sum(len(results[rid]) - len(p)
                     for rid, p in zip(rids, prompts))
    return {"metric": "gpt1p3b_ragged_serving_tokens_per_sec_chip"
            if on_tpu else "gpt_tiny_ragged_serving_cpu_smoke",
            "value": round(gen_tokens / dt, 1), "unit": "tokens/s",
            "requests": n_req, "prompt_lens": lens,
            "new_tokens_per_req": new_toks, "num_slots": slots,
            "page_size": page, "decode_steps": timed_steps,
            "generated_tokens": gen_tokens,
            "note": "mixed-length batch through admit/evict + page "
                    "recycling; tokens/s counts generated tokens only"}


def bench_fused_decode(on_tpu: bool) -> Dict:
    """Fused decode hot path A/B (r13, ROADMAP item 3): the
    ragged_serving request stream through the SAME engine twice —
    ``fused_step=True`` (attention + out-projection folded into one
    kernel per layer, sampling streamed through the lm_head so the
    [B, vocab] logits never hit HBM) vs ``False`` (the pre-r13
    programs). Reports tokens/s for both, programs-per-step from the
    dispatch launch counter (ops traced into each step program — the
    count the fusion exists to shrink), and the bit_identical flag
    over the full greedy token streams."""
    import paddle_tpu as pt
    from paddle_tpu.inference import create_decode_engine
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny

    if on_tpu:
        cfg = _decode_1p3b_cfg()
        slots, page, max_seq = 32, 64, 1024
        lens = [64, 96, 128, 192, 256, 384, 512, 640]
        n_req, new_toks = 64, 64
    else:
        cfg = gpt_tiny()
        slots, page, max_seq = 2, 8, 64
        lens = [5, 9, 13]
        n_req, new_toks = 4, 8

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        _to_bf16_except_norms(model)
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size,
                            (lens[i % len(lens)],)).astype(np.int32)
               for i in range(n_req)]

    def run_mode(fused: bool) -> Dict:
        eng = create_decode_engine(model, num_slots=slots,
                                   page_size=page, max_seq_len=max_seq,
                                   fused_step=fused)
        # warm THE MEASURED ENGINE's compiles (per-instance closures;
        # see bench_ragged_serving) — one request per distinct bucket
        for p in prompts[:len(lens)]:
            eng.submit(p, max_new_tokens=2)
        eng.run()
        steps_before = eng.steps
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new_tokens=new_toks) for p in prompts]
        try:
            results = eng.run()
        finally:
            eng.close()
        wall = time.perf_counter() - t0
        timed_steps = eng.steps - steps_before
        dt = wall
        gen = sum(len(results[rid]) - len(p)
                  for rid, p in zip(rids, prompts))
        return {"tokens_per_s": round(gen / dt, 1),
                "decode_steps": timed_steps,
                "programs_per_step": dict(eng.step_programs),
                "tokens": {rid: results[rid].tolist() for rid in rids}}

    fused = run_mode(True)
    unfused = run_mode(False)
    bit_identical = fused.pop("tokens") == unfused.pop("tokens")
    fp = fused["programs_per_step"].get("decode")
    up = unfused["programs_per_step"].get("decode")
    return {"metric": "gpt1p3b_fused_decode_ab_chip" if on_tpu
            else "gpt_tiny_fused_decode_ab_cpu_smoke",
            "unit": "tokens/s (A/B) + programs/step",
            "fused": fused, "unfused": unfused,
            "bit_identical": bool(bit_identical),
            "decode_programs_fused": fp,
            "decode_programs_unfused": up,
            "decode_programs_reduction": (
                None if not (fp and up)
                else round(1.0 - fp / up, 3)),
            "requests": n_req, "prompt_lens": lens,
            "new_tokens_per_req": new_toks, "num_slots": slots,
            "page_size": page,
            "note": "programs_per_step counts ops traced into each "
                    "step program (dispatch.count_op_calls); the HBM "
                    "round-trip win (no [B,vocab] logits, fused "
                    "epilogue) needs the chip's Mosaic kernels — on "
                    "cpu both modes run the pure-JAX references, so "
                    "tokens/s measures host overhead, not the fusion"}


# ONE set of workload constants, interpolated into both the subprocess
# payload and the result-dict metadata below — the result entry
# must describe the workload that was actually measured
_MESH_DECODE_CPU = {"lens": [5, 9, 13], "n_req": 4, "new_toks": 8,
                    "num_slots": 2, "page_size": 8, "devices": 8}

_MESH_DECODE_PAYLOAD = """
import time
import numpy as np
import paddle_tpu as pt
from paddle_tpu.core.cpu_mesh import emit_result
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.inference import create_decode_engine
from paddle_tpu.distributed.topology import make_serving_mesh

pt.seed(0)
model = GPTForCausalLM(gpt_tiny())
model.eval()
rng = np.random.default_rng(0)
lens, n_req, new_toks = {lens}, {n_req}, {new_toks}
prompts = [rng.integers(0, 1024, (lens[i % len(lens)],)).astype(
    np.int32) for i in range(n_req)]


def run(mp):
    mesh = None if mp == 1 else make_serving_mesh(mp)
    eng = create_decode_engine(model, num_slots={num_slots},
                               page_size={page_size},
                               max_seq_len=64, mesh=mesh)
    for p in prompts[:len(lens)]:  # warm THIS engine's compiles
        eng.submit(p, max_new_tokens=2)
    eng.run()
    steps0 = eng.steps
    t0 = time.perf_counter()
    rids = [eng.submit(p, max_new_tokens=new_toks) for p in prompts]
    try:
        results = eng.run()
    finally:
        eng.close()
    wall = time.perf_counter() - t0
    gen = sum(len(results[r]) - len(p) for r, p in zip(rids, prompts))
    return {"tokens_per_s": round(gen / wall, 1),
            "decode_steps": eng.steps - steps0,
            "generated_tokens": gen,
            "tokens": {str(r): [int(t) for t in results[r]]
                       for r in rids}}


by_mp = {str(mp): run(mp) for mp in (1, 2, 4)}
base = by_mp["1"].pop("tokens")
bit_identical = all(v.pop("tokens") == base
                    for k, v in by_mp.items() if k != "1")
emit_result({"by_model_parallel": by_mp,
             "bit_identical": bit_identical})
"""


def bench_mesh_decode(on_tpu: bool) -> Dict:
    """Tensor-parallel serving (r10) A/B: the mesh-sharded engine
    (weights per their mp_layers pspecs, KV pools head-sharded,
    paged attention under shard_map) vs the single-device engine on
    the SAME ragged request stream as bench_ragged_serving. On the CPU
    lane the mesh is a cold-subprocess 8-fake-device host platform
    (core/cpu_mesh.py) — it measures GSPMD overhead and pins
    bit-identical outputs, NOT a speedup (N fake devices time-share
    one CPU; the tensor-parallel win is HBM capacity + per-chip
    bandwidth, which only a real multi-chip session can show). On
    chip, the mesh spans the session's real devices."""
    if not on_tpu:
        from paddle_tpu.core.cpu_mesh import run_cpu_mesh_json
        w = _MESH_DECODE_CPU
        payload = _MESH_DECODE_PAYLOAD
        for k in ("lens", "n_req", "new_toks", "num_slots",
                  "page_size"):
            payload = payload.replace("{%s}" % k, repr(w[k]))
        res = run_cpu_mesh_json(payload, device_count=w["devices"],
                                timeout_s=900.0)
        return {"metric": "gpt_tiny_mesh_decode_cpu_smoke",
                "unit": "tokens/s", "requests": w["n_req"],
                "prompt_lens": w["lens"],
                "new_tokens_per_req": w["new_toks"],
                "num_slots": w["num_slots"],
                "page_size": w["page_size"],
                "host_platform_devices": w["devices"],
                "by_model_parallel": res["by_model_parallel"],
                "bit_identical": res["bit_identical"],
                "note": "cpu_smoke of the real GSPMD path in a cold "
                        "subprocess; fake devices time-share one CPU "
                        "so tokens/s measures collective/partition "
                        "overhead, not the capacity win — chip A/B "
                        "pending"}
    # chip path: shard over the session's real devices
    import jax

    import paddle_tpu as pt
    from paddle_tpu.distributed.topology import make_serving_mesh
    from paddle_tpu.inference import create_decode_engine
    from paddle_tpu.models import GPTForCausalLM

    cfg = _decode_1p3b_cfg()
    ndev = len(jax.devices())
    mp = 1
    while mp * 2 <= ndev and cfg.num_heads % (mp * 2) == 0 and \
            cfg.vocab_size % (mp * 2) == 0:
        mp *= 2
    pt.seed(0)
    model = GPTForCausalLM(cfg)
    _to_bf16_except_norms(model)
    model.eval()
    rng = np.random.default_rng(0)
    lens = [64, 96, 128, 192, 256, 384, 512, 640]
    n_req, new_toks = 64, 64
    prompts = [rng.integers(0, cfg.vocab_size,
                            (lens[i % len(lens)],)).astype(np.int32)
               for i in range(n_req)]
    out: Dict = {"metric": "gpt1p3b_mesh_decode_tokens_per_sec_chip",
                 "unit": "tokens/s", "devices": ndev,
                 "by_model_parallel": {}}
    for deg in sorted({1, mp}):
        mesh = None if deg == 1 else make_serving_mesh(deg)
        eng = create_decode_engine(model, num_slots=32, page_size=64,
                                   max_seq_len=1024, mesh=mesh)
        for p in prompts[:len(lens)]:
            eng.submit(p, max_new_tokens=2)
        eng.run()
        steps0 = eng.steps
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new_tokens=new_toks)
                for p in prompts]
        try:
            results = eng.run()
        finally:
            eng.close()
        wall = time.perf_counter() - t0
        timed_steps = eng.steps - steps0
        dt = wall
        gen = sum(len(results[r]) - len(p)
                  for r, p in zip(rids, prompts))
        out["by_model_parallel"][str(deg)] = {
            "tokens_per_s": round(gen / dt, 1),
            "decode_steps": timed_steps}
    return out


def bench_chunked_prefill(on_tpu: bool) -> Dict:
    """Chunked-prefill A/B (r11 tentpole artifact): an ADVERSARIAL
    arrival trace — steady short INTERACTIVE streams decoding while
    long BATCH prompts arrive mid-flight — through the same engine
    with chunked prefill on vs off. Whole-prefill admission runs the
    long prompt's entire suffix synchronously inside one step, so
    every in-flight stream sees one giant inter-token gap (the
    TTFT-vs-TPOT head-of-line stall); chunked admission trickles the
    prefill in page-aligned chunks between decode steps. Reported:
    short-stream TPOT p99 (the headline — this is a SCHEDULING
    property, so the A/B is real on the CPU lane, not chip-pending),
    TTFT p50/p99 for both classes, and bit_identical across modes
    (greedy outputs must not change with the schedule). The arrival
    trace is step-indexed (submissions keyed to completion counts),
    so both modes see the same schedule."""
    import paddle_tpu as pt
    from paddle_tpu.inference import create_decode_engine
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import Priority, SLOScheduler

    if on_tpu:
        cfg = _decode_1p3b_cfg()
        slots, page, max_seq = 16, 64, 2048
        chunk = 256
        short_len, short_new, n_short = 32, 32, 48
        long_len, long_new, n_long = 1536, 8, 3
        inject_at = (8, 20, 32)   # short completions triggering a long
        concurrency = slots - 1
    else:
        cfg = gpt_tiny()
        slots, page, max_seq = 4, 8, 128
        chunk = 16
        short_len, short_new, n_short = 6, 16, 18
        long_len, long_new, n_long = 96, 4, 2
        inject_at = (4, 10)
        concurrency = 3

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        _to_bf16_except_norms(model)
    model.eval()
    rng = np.random.default_rng(0)
    shorts = [rng.integers(0, cfg.vocab_size,
                           (short_len,)).astype(np.int32)
              for _ in range(n_short)]
    longs = [rng.integers(0, cfg.vocab_size,
                          (long_len,)).astype(np.int32)
             for _ in range(n_long)]

    def run_trace(chunk_tokens):
        from paddle_tpu.serving import SLOConfig
        # shed_after_s=None: the default 30s shed could terminate a
        # queued long prompt on the chip config — a shed/failed request
        # never enters the result store this driver polls, which would
        # wedge the drain loop (it is also not the property under test)
        eng = create_decode_engine(
            model, num_slots=slots, page_size=page,
            max_seq_len=max_seq,
            scheduler=SLOScheduler(SLOConfig(shed_after_s=None)),
            prefill_chunk_tokens=chunk_tokens)
        # warm THIS engine's compiles: one request per distinct
        # prefill shape (short bucket / long bucket or chunk bucket)
        # plus the shared decode step, then drain
        eng.submit(shorts[0][:short_len], max_new_tokens=2)
        eng.submit(longs[0][:long_len], max_new_tokens=2)
        eng.run()
        tok_t: Dict[int, list] = {}
        submit_t: Dict[int, float] = {}

        def on_token(rid, tok, done):
            tok_t.setdefault(rid, []).append(time.perf_counter())

        short_rids, long_rids = [], []

        def submit_short(i):
            rid = eng.submit(shorts[i], max_new_tokens=short_new,
                             priority=int(Priority.INTERACTIVE),
                             on_token=on_token)
            submit_t[rid] = time.perf_counter()
            short_rids.append(rid)

        def submit_long(j):
            rid = eng.submit(longs[j], max_new_tokens=long_new,
                             priority=int(Priority.BATCH),
                             on_token=on_token)
            submit_t[rid] = time.perf_counter()
            long_rids.append(rid)

        t0 = time.perf_counter()
        for i in range(concurrency):
            submit_short(i)
        next_short, next_long = concurrency, 0
        outputs: Dict[int, list] = {}
        done_shorts = 0
        steps = 0
        while len(outputs) < n_short + n_long:
            eng.step()
            steps += 1
            if steps > 100000:  # engine.run()'s own drain bound
                raise RuntimeError(
                    f"trace did not drain: {len(outputs)} of "
                    f"{n_short + n_long} finished")
            for rid in short_rids + long_rids:
                if rid in outputs:
                    continue
                res = eng.result(rid, pop=True)
                if res is None:
                    continue
                outputs[rid] = [int(t) for t in res]
                if rid in short_rids:
                    done_shorts += 1
                    # steady stream: a finished short is replaced
                    if next_short < n_short:
                        submit_short(next_short)
                        next_short += 1
                    # adversarial arrivals keyed to the completion
                    # count, so both modes see the same trace
                    while next_long < n_long and \
                            next_long < len(inject_at) and \
                            done_shorts >= inject_at[next_long]:
                        submit_long(next_long)
                        next_long += 1
        wall = time.perf_counter() - t0
        eng.close()  # every exit path returns the pages (r7 contract)

        def pctl(vals, p):
            # np.percentile for consistency with _serve_latency's
            # wall-latency stats
            return float(np.percentile(vals, p))

        gaps = []
        for rid in short_rids:
            ts = tok_t.get(rid, [])
            gaps.extend(b - a for a, b in zip(ts, ts[1:]))
        ttft_s = [tok_t[r][0] - submit_t[r]
                  for r in short_rids if tok_t.get(r)]
        ttft_l = [tok_t[r][0] - submit_t[r]
                  for r in long_rids if tok_t.get(r)]
        ordered = [outputs[r] for r in short_rids + long_rids]
        return {
            "short_tpot_p50_ms": round(pctl(gaps, 50) * 1e3, 3),
            "short_tpot_p99_ms": round(pctl(gaps, 99) * 1e3, 3),
            "short_tpot_max_ms": round(max(gaps) * 1e3, 3),
            "short_ttft_p50_ms": round(pctl(ttft_s, 50) * 1e3, 3),
            "short_ttft_p99_ms": round(pctl(ttft_s, 99) * 1e3, 3),
            "long_ttft_p50_ms": round(pctl(ttft_l, 50) * 1e3, 3),
            "wall_s": round(wall, 3),
        }, ordered

    whole, out_whole = run_trace(None)
    chunked, out_chunked = run_trace(chunk)
    bit_identical = out_whole == out_chunked
    better = chunked["short_tpot_p99_ms"] < whole["short_tpot_p99_ms"]
    return {"metric": "gpt1p3b_chunked_prefill_tpot_chip" if on_tpu
            else "gpt_tiny_chunked_prefill_cpu_smoke",
            "unit": "ms", "num_slots": slots, "page_size": page,
            "prefill_chunk_tokens": chunk,
            "short": {"len": short_len, "new": short_new,
                      "count": n_short, "concurrency": concurrency},
            "long": {"len": long_len, "new": long_new,
                     "count": n_long, "inject_at": list(inject_at)},
            "whole_prefill": whole, "chunked_prefill": chunked,
            "bit_identical": bit_identical,
            "tpot_p99_improved": better,
            "note": "scheduling A/B on one engine config: short "
                    "INTERACTIVE streams decode while long BATCH "
                    "prompts arrive mid-flight; chunked admission "
                    "interleaves page-aligned prefill chunks between "
                    "decode steps instead of stalling every stream "
                    "behind one whole suffix prefill. TPOT p99 is the "
                    "headline; greedy outputs pinned bit-identical "
                    "across modes"}


def bench_serving_prefix(on_tpu: bool) -> Dict:
    """Serving-layer A/B (r7 tentpole artifact): a shared-system-prompt
    request stream through the full serving stack — SLO scheduler +
    refcounted prefix cache + per-request metrics — with the prefix
    cache ON vs OFF. Every request carries the same system prompt, so
    with the cache on, all its full KV pages prefill ONCE and every
    later request's prefill shrinks to the per-request tail
    (models/gpt.py prefill_chained). Reported: generated tokens/s,
    TTFT p50/p99 and prefill-ms p50 per mode, plus the cache hit rate
    and shed counters from serving/metrics.py."""
    import paddle_tpu as pt
    from paddle_tpu.core.monitor import StatRegistry
    from paddle_tpu.inference import create_decode_engine
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import (PrefixCache, ServingMetrics,
                                    SLOConfig, SLOScheduler)

    if on_tpu:
        cfg = _decode_1p3b_cfg()
        slots, page, max_seq = 16, 64, 1024
        sys_len, tails, n_req, new_toks = 512, (7, 23, 41, 61), 32, 32
    else:
        cfg = gpt_tiny()
        slots, page, max_seq = 4, 8, 96
        sys_len, tails, n_req, new_toks = 40, (3, 5, 7, 9), 16, 8

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        _to_bf16_except_norms(model)
    model.eval()
    rng = np.random.default_rng(0)
    system = rng.integers(0, cfg.vocab_size, (sys_len,)).astype(np.int32)
    prompts = [np.concatenate([
        system, rng.integers(0, cfg.vocab_size,
                             (tails[i % len(tails)],)).astype(np.int32)])
        for i in range(n_req)]
    num_pages = slots * (-(-max_seq // page))

    def run_mode(cache_on: bool) -> Dict:
        metrics = ServingMetrics(registry=StatRegistry())
        eng = create_decode_engine(
            model, num_slots=slots, page_size=page, max_seq_len=max_seq,
            num_pages=num_pages,
            prefix_cache=PrefixCache(page) if cache_on else None,
            # shedding disabled for the measured run: a slow machine
            # shedding a tail request must not turn the throughput
            # number into a partial-batch artifact (the shed COUNTER
            # still reports, and the shed path is pinned in tests)
            scheduler=SLOScheduler(SLOConfig(shed_after_s=None)))
        # warm the compiles through THE MEASURED ENGINE (per-instance
        # jit closures), then drain so pages return before timing;
        # metrics attach AFTER the warm-up so jit compile time never
        # pollutes the TTFT/prefill histograms
        for p in prompts[:len(tails)]:
            eng.submit(p, max_new_tokens=2)
        eng.run()
        eng.set_on_complete(metrics.observe_request)
        steps_before = eng.steps
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new_tokens=new_toks) for p in prompts]
        try:
            results = eng.run()
        except Exception:
            eng.close()  # every exit path returns the pages
            raise
        wall = time.perf_counter() - t0
        gen = sum(len(results[r]) - len(p)
                  for r, p in zip(rids, prompts) if r in results)
        dt = wall
        pc = eng._prefix_cache
        out = {"tokens_per_s": round(gen / dt, 1),
               "ttft_ms_p50": metrics.ttft_ms.percentile(50),
               "ttft_ms_p99": metrics.ttft_ms.percentile(99),
               "prefill_ms_p50": metrics.prefill_ms.percentile(50),
               "queue_delay_ms_p50":
                   metrics.queue_delay_ms.percentile(50),
               "shed": metrics.counter("shed_total").get(),
               "requests": metrics.counter("requests_total").get()}
        if pc is not None:
            out["cache"] = {
                "hit_pages": pc.hit_pages, "miss_pages": pc.miss_pages,
                "hit_rate": round(pc.hit_rate() or 0.0, 4),
                "evicted_pages": pc.evicted_pages}
        eng.close()
        return out

    off = run_mode(False)
    on = run_mode(True)
    out: Dict = {"metric": "gpt1p3b_serving_prefix_cache_chip" if on_tpu
                 else "gpt_tiny_serving_prefix_cache_cpu_smoke",
                 "requests": n_req, "system_prompt_len": sys_len,
                 "tail_lens": list(tails),
                 "new_tokens_per_req": new_toks, "num_slots": slots,
                 "page_size": page,
                 "cache_off": off, "cache_on": on}
    if off["tokens_per_s"] and on["tokens_per_s"]:
        out["throughput_gain"] = round(
            on["tokens_per_s"] / off["tokens_per_s"], 3)
    if off["prefill_ms_p50"] and on["prefill_ms_p50"]:
        out["prefill_p50_speedup"] = round(
            off["prefill_ms_p50"] / on["prefill_ms_p50"], 3)
    return out


def bench_prefix_tiers(on_tpu: bool) -> Dict:
    """Hierarchical prefix cache A/B (r15 tentpole artifact): a
    RE-VISITED shared-system-prompt stream at cache depth >> the
    device pool. N distinct system prompts are cycled for several
    rounds with the pool sized so the chains cannot all stay resident:
    every revisit finds its prefix EVICTED. With the spill tier OFF
    the prefix re-prefills from scratch; with it ON the evicted pages
    restore via one device_put + page-table splice each
    (serving/prefix_cache.py spill tiers). Reported per mode: TTFT
    p50/p99, prefill-ms p50, tokens actually prefilled (prompt minus
    cached/restored — the re-prefill compute the tiers exist to
    kill), restored pages and restore-ms."""
    import paddle_tpu as pt
    from paddle_tpu.inference import create_decode_engine
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import PrefixCache

    if on_tpu:
        cfg = _decode_1p3b_cfg()
        slots, page, max_seq = 4, 64, 1024
        sys_len, tail, new_toks = 512, 16, 16
        n_prefix, rounds = 8, 3
        num_pages = 24          # << n_prefix chains of 8 pages
        spill = 1 << 32
    else:
        # a beefed-up tiny config: enough per-token prefill compute
        # that the A/B measures restore-vs-reprefill, not just CPU
        # launch overhead (at stock gpt_tiny scale every prefill is
        # ~one dispatch, so there is nothing for a restore to save)
        from paddle_tpu.models.gpt import GPTConfig
        cfg = GPTConfig(vocab_size=1024, hidden_size=256, num_layers=4,
                        num_heads=4, max_seq_len=256, dropout=0.0,
                        attn_dropout=0.0)
        slots, page, max_seq = 2, 16, 256
        sys_len, tail, new_toks = 200, 8, 8
        n_prefix, rounds = 6, 3
        num_pages = 20          # << 6 chains x 12 full prompt pages
        spill = 1 << 27

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        _to_bf16_except_norms(model)
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [np.concatenate([
        rng.integers(0, cfg.vocab_size, (sys_len,)).astype(np.int32),
        rng.integers(0, cfg.vocab_size, (tail,)).astype(np.int32)])
        for _ in range(n_prefix)]

    def run_mode(spill_on: bool) -> Dict:
        pc = PrefixCache(page, spill_bytes=spill if spill_on else None)
        eng = create_decode_engine(
            model, num_slots=slots, page_size=page,
            max_seq_len=max_seq, num_pages=num_pages, prefix_cache=pc)
        finished = []
        # warm the compiles (fresh + CHAINED prefill, decode, splice)
        # through the measured engine, then drain — metrics attach
        # after so compile time never pollutes TTFT. prompts[0] twice:
        # the second admission hits the cache and compiles the chained
        # suffix-prefill program both modes use on every revisit.
        for p in (prompts[0], prompts[1], prompts[0]):
            eng.submit(p, max_new_tokens=2)
            eng.run()
        if spill_on:
            pc.evict_until(eng.allocator, eng.allocator.num_pages)
            eng.submit(prompts[0], max_new_tokens=2)
            eng.run()  # pays the splice-jit bucket compile
        eng.set_on_complete(lambda req: finished.append(req.stats))
        t0 = time.perf_counter()
        # SERIAL revisit stream: one request in flight at a time, so
        # TTFT is queue-free and measures exactly the prefill-vs-
        # restore difference the A/B is about
        for _ in range(rounds):
            for p in prompts:
                eng.submit(p, max_new_tokens=new_toks)
                eng.run()
        wall = time.perf_counter() - t0
        ttfts = [(s.ttft_s or 0) * 1e3 for s in finished]
        prefills = [s.prefill_ms for s in finished]

        def pctl(vals, q):
            # np.percentile like every other serving bench entry, so
            # cross-entry TTFT comparisons share one basis
            return round(float(np.percentile(vals, q)), 3)

        out = {"requests": len(finished),
               "wall_s": round(wall, 3),
               "ttft_ms_p50": pctl(ttfts, 50),
               "ttft_ms_p99": pctl(ttfts, 99),
               "prefill_ms_p50": pctl(prefills, 50),
               # the number the tiers exist to shrink: tokens whose
               # prefill actually ran (cached/restored pages skip it)
               "prefilled_tokens": int(sum(
                   s.prompt_len - s.cached_tokens for s in finished)),
               "cache": {"hit_rate": round(pc.hit_rate() or 0.0, 4),
                         "spilled_pages": pc.spilled_pages,
                         "restored_pages": pc.restored_pages,
                         "tier_stats": pc.tier_stats()}}
        # measured-only: pc.restored_pages includes warmup restores,
        # so gate on the per-request stats actually collected
        rms = [s.restore_ms for s in finished if s.restored_pages]
        if rms:
            out["restore_ms_p50"] = pctl(rms, 50)
        eng.close()
        return out

    off = run_mode(False)
    on = run_mode(True)
    out: Dict = {"metric": "gpt1p3b_prefix_tiers_ab_chip" if on_tpu
                 else "gpt_tiny_prefix_tiers_ab_cpu_smoke",
                 "distinct_prefixes": n_prefix, "rounds": rounds,
                 "system_prompt_len": sys_len, "tail_len": tail,
                 "num_pages": num_pages, "page_size": page,
                 "spill_off": off, "spill_on": on}
    if off["ttft_ms_p50"] and on["ttft_ms_p50"]:
        out["ttft_p50_speedup"] = round(
            off["ttft_ms_p50"] / on["ttft_ms_p50"], 3)
    if off["prefilled_tokens"]:
        out["reprefill_tokens_saved"] = (off["prefilled_tokens"]
                                         - on["prefilled_tokens"])
    return out


def bench_kv_substrate(on_tpu: bool) -> Dict:
    """KV byte substrate A/B (r23 tentpole artifact): the spill-heavy
    shared-prefix stream of bench_prefix_tiers swept over the
    blob-format x dedup grid, plus a paged-int8 lossless pair. The
    three numbers the substrate exists to move:

    - WIRE bytes per spilled KV token (spill/handoff blobs ride the
      same ``pack_page_blob`` codecs): int8 blobs carry ~4x fewer
      bytes than raw fp32, int4 ~8x — reported as
      ``wire_bytes_per_token`` per format with the raw-equivalent
      ``logical_bytes`` alongside;
    - effective context tokens per HBM megabyte (cross-request page
      dedup): two concurrent same-prefix admissions under chunked
      prefill fold their duplicate FULL pages onto one physical copy;
    - greedy bit-identity: every LOSSLESS config (raw anywhere, int8
      blobs over a paged-int8 pool, dedup on or off) must report
      ``bit_identical`` true vs the r22 escape hatch (raw +
      dedup-off); lossy fp formats report ``codec_stats`` (pages,
      max abs dequant error) instead — never silently."""
    import paddle_tpu as pt
    from paddle_tpu.inference import create_decode_engine
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.serving import PrefixCache

    if on_tpu:
        cfg = _decode_1p3b_cfg()
        slots, page, max_seq = 4, 64, 1024
        sys_len, tail, new_toks = 512, 16, 16
        n_prefix, rounds = 6, 2
        num_pages, dedup_pages = 24, 48
        spill = 1 << 32
    else:
        # the beefed-up tiny config bench_prefix_tiers uses: enough KV
        # bytes per page that codec ratios measure payload, not header
        from paddle_tpu.models.gpt import GPTConfig
        cfg = GPTConfig(vocab_size=1024, hidden_size=256, num_layers=4,
                        num_heads=4, max_seq_len=256, dropout=0.0,
                        attn_dropout=0.0)
        slots, page, max_seq = 2, 16, 256
        sys_len, tail, new_toks = 200, 8, 8
        n_prefix, rounds = 4, 2
        num_pages, dedup_pages = 20, 32
        spill = 1 << 27

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        _to_bf16_except_norms(model)
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [np.concatenate([
        rng.integers(0, cfg.vocab_size, (sys_len,)).astype(np.int32),
        rng.integers(0, cfg.vocab_size, (tail,)).astype(np.int32)])
        for _ in range(n_prefix)]
    full_pages = (len(prompts[0]) - 1) // page
    # fp32 KV page in HBM: K+V per layer, hidden floats per token
    page_hbm_bytes = 2 * cfg.num_layers * page * cfg.hidden_size * 4

    def run_mode(fmt: str, dedup: bool, kv_int8: bool = False) -> Dict:
        # -- phase A: serial spill/restore stream (codec wire bytes) --
        pc = PrefixCache(page, spill_bytes=spill, blob_format=fmt,
                         dedup=dedup)
        eng = create_decode_engine(
            model, num_slots=slots, page_size=page, max_seq_len=max_seq,
            num_pages=num_pages, prefix_cache=pc, kv_int8=kv_int8)
        outputs = []
        for p in (prompts[0], prompts[1], prompts[0]):  # warm compiles
            eng.submit(p, max_new_tokens=2)
            eng.run()
        pc.evict_until(eng.allocator, eng.allocator.num_pages)
        eng.submit(prompts[0], max_new_tokens=2)
        eng.run()  # pays the splice-jit bucket compile
        t0 = time.perf_counter()
        for _ in range(rounds):
            for p in prompts:
                rid = eng.submit(p, max_new_tokens=new_toks)
                res = eng.run()
                outputs.append([int(t) for t in res[rid][len(p):]])
        wall = time.perf_counter() - t0
        tier = pc.tiers[0]
        wire, logical = tier.occupancy_bytes, tier.logical_bytes
        tokens_spilled = tier.blob_count * page
        out = {"requests": len(outputs), "wall_s": round(wall, 3),
               "outputs": outputs,
               "wire_bytes": wire, "logical_bytes": logical,
               "wire_bytes_per_token": (round(wire / tokens_spilled, 1)
                                        if tokens_spilled else None),
               "spilled_pages": pc.spilled_pages,
               "restored_pages": pc.restored_pages,
               "codec_stats": dict(pc.codec_stats)}
        eng.close()

        # -- phase B: concurrent same-prefix admissions (dedup HBM) ---
        pc2 = PrefixCache(page, dedup=dedup)
        eng2 = create_decode_engine(
            model, num_slots=2, page_size=page, max_seq_len=max_seq,
            num_pages=dedup_pages, prefix_cache=pc2, kv_int8=kv_int8,
            prefill_chunk_tokens=page)
        r1 = eng2.submit(prompts[0], max_new_tokens=new_toks)
        r2 = eng2.submit(prompts[0], max_new_tokens=new_toks)
        res2 = eng2.run()
        out["outputs"] = out["outputs"] + [
            [int(t) for t in res2[r][len(prompts[0]):]]
            for r in (r1, r2)]
        ctx_tokens = 2 * full_pages * page
        pages_used = 2 * full_pages - pc2.dedup_hits
        out["dedup_hits"] = pc2.dedup_hits
        out["hbm_ctx_pages"] = pages_used
        out["effective_ctx_tokens_per_hbm_mb"] = round(
            ctx_tokens / (pages_used * page_hbm_bytes / (1 << 20)), 1)
        eng2.close()
        return out

    grid: Dict = {}
    for fmt in ("raw", "int8"):
        for dedup in (False, True):
            grid[f"{fmt}|dedup_{'on' if dedup else 'off'}"] = \
                run_mode(fmt, dedup)
    # paged-int8 pool: int8 blobs are a lossless passthrough of the
    # pool layout — the codec rewrites them to raw framing, so wire
    # bytes AND greedy outputs must match exactly
    i8_raw = run_mode("raw", True, kv_int8=True)
    i8_coded = run_mode("int8", True, kv_int8=True)

    baseline = grid["raw|dedup_off"]["outputs"]
    for mode in grid.values():
        mode["bit_identical"] = mode.pop("outputs") == baseline
    i8_pair = {"bit_identical":
               i8_raw.pop("outputs") == i8_coded.pop("outputs"),
               "wire_bytes_raw": i8_raw["wire_bytes"],
               "wire_bytes_int8": i8_coded["wire_bytes"],
               "codec_stats": i8_coded["codec_stats"]}

    out: Dict = {"metric": "gpt1p3b_kv_substrate_ab_chip" if on_tpu
                 else "gpt_tiny_kv_substrate_ab_cpu_smoke",
                 "distinct_prefixes": n_prefix, "rounds": rounds,
                 "system_prompt_len": sys_len, "page_size": page,
                 "num_pages": num_pages, "grid": grid,
                 "paged_int8": i8_pair}
    raw_w = grid["raw|dedup_off"]["wire_bytes_per_token"]
    i8_w = grid["int8|dedup_off"]["wire_bytes_per_token"]
    if raw_w and i8_w:
        out["wire_shrink_int8_vs_raw"] = round(raw_w / i8_w, 2)
    out["effective_ctx_tokens_per_hbm_mb"] = {
        "dedup_off": grid["raw|dedup_off"]
        ["effective_ctx_tokens_per_hbm_mb"],
        "dedup_on": grid["raw|dedup_on"]
        ["effective_ctx_tokens_per_hbm_mb"]}
    out["hbm_pages_saved_by_dedup"] = \
        grid["raw|dedup_on"]["dedup_hits"]
    return out


def bench_memory_observatory(on_tpu: bool) -> Dict:
    """memory_observatory (r18): ledger-overhead A/B on a page-CHURN
    stream — a revisited shared-prefix workload over a pool smaller
    than the working set, so every round drives admit / evict / spill
    / restore traffic (the event mix the ledger records). Reported:
    ms/step with the page ledger on vs off (the behavior-neutrality
    claim: ~1.0x), ledger event totals by kind, the occupancy
    timeline's tail (owner-class breakdown per step) and the EWMA
    exhaustion forecast over it. Outputs are asserted BIT-IDENTICAL
    ledger on/off. On CPU this measures the host-side dict-append
    cost next to real jit launches; HBM gauges (the profile op's
    device.memory_stats) need a real device — chip pending."""
    import paddle_tpu as pt
    from paddle_tpu.inference import create_decode_engine
    from paddle_tpu.inference.page_ledger import forecast_exhaustion
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import PrefixCache

    if on_tpu:
        cfg = _decode_1p3b_cfg()
        slots, page, max_seq = 4, 64, 1024
        sys_len, tail, new_toks = 256, 16, 8
        n_prefix, rounds, num_pages = 8, 3, 24
    else:
        cfg = gpt_tiny()
        slots, page, max_seq = 2, 8, 128
        sys_len, tail, new_toks = 48, 8, 6
        n_prefix, rounds, num_pages = 6, 4, 16

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        _to_bf16_except_norms(model)
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [np.concatenate([
        rng.integers(0, cfg.vocab_size, (sys_len,)).astype(np.int32),
        rng.integers(0, cfg.vocab_size, (tail,)).astype(np.int32)])
        for _ in range(n_prefix)]

    def prepare(ledger: bool):
        pc = PrefixCache(page, spill_bytes=1 << 26)
        eng = create_decode_engine(
            model, num_slots=slots, page_size=page,
            max_seq_len=max_seq, num_pages=num_pages,
            prefix_cache=pc, page_ledger=ledger)
        # warm every compile (fresh + chained prefill, decode, splice)
        # through the measured engine before timing
        for p in (prompts[0], prompts[1], prompts[0]):
            eng.submit(p, max_new_tokens=2)
            eng.run()
        pc.evict_until(eng.allocator, eng.allocator.num_pages)
        eng.submit(prompts[0], max_new_tokens=2)
        eng.run()
        return eng

    def one_pass(eng, outputs=None):
        steps0 = eng.steps
        t0 = time.perf_counter()
        for _ in range(rounds):
            for p in prompts:
                eng.submit(p, max_new_tokens=new_toks)
                res = [int(t) for t in list(eng.run().values())[0]]
                if outputs is not None:
                    outputs.append(res)
        return time.perf_counter() - t0, eng.steps - steps0

    # both engines built and warmed BEFORE any timing, passes
    # INTERLEAVED on/off/on/... with min-of-passes per mode — at
    # ~1.5 ms/step on a shared CPU host the A/B would otherwise
    # measure process warmup drift, not the ledger (the cache is
    # inclusive, so every pass sees the same hit/spill/restore mix)
    eng_on, eng_off = prepare(True), prepare(False)
    out_on: list = []
    out_off: list = []
    walls = {True: [], False: []}
    steps = 0
    for p_idx in range(4):
        for led, eng, sink in ((True, eng_on, out_on),
                               (False, eng_off, out_off)):
            w, steps = one_pass(
                eng, sink if p_idx == 0 else None)
            walls[led].append(w)

    def mode_out(eng, wall_list) -> Dict:
        wall = min(wall_list)
        tl = eng.step_timeline()
        out = {"wall_s": round(wall, 3), "steps": steps,
               "ms_per_step": round(wall * 1e3 / max(1, steps), 4),
               "occupancy_tail": [e.get("occupancy") for e in tl[-8:]],
               "forecast": forecast_exhaustion(tl)}
        if eng.ledger is not None:
            st = eng.ledger.stats()
            out["ledger_events_total"] = st["events_total"]
            out["ledger_events_by_kind"] = st["by_kind"]
            out["ledger_dropped"] = st["dropped_total"]
            out["ledger_reconcile_ok"] = \
                eng.ledger.reconcile(eng.allocator)["ok"]
        eng.close()
        return out

    on = mode_out(eng_on, walls[True])
    off = mode_out(eng_off, walls[False])
    bit_identical = out_on == out_off
    out: Dict = {"metric": "gpt1p3b_memory_observatory_ab_chip"
                 if on_tpu else
                 "gpt_tiny_memory_observatory_ab_cpu_smoke",
                 "distinct_prefixes": n_prefix, "rounds": rounds,
                 "num_pages": num_pages, "page_size": page,
                 "bit_identical": bit_identical,
                 "ledger_on": on, "ledger_off": off}
    if off["ms_per_step"]:
        out["ms_per_step_ratio"] = round(
            on["ms_per_step"] / off["ms_per_step"], 4)
    return out


def bench_serving_goodput(on_tpu: bool) -> Dict:
    """serving_goodput (r16, ROADMAP item 3c): open-loop Poisson
    arrivals swept over request rates, reporting SLO-ATTAINMENT curves
    (% of requests meeting TTFT/TPOT targets vs offered load) computed
    FROM THE REQUEST TRACES (serving/tracing.py at sample 1.0) — the
    number a capacity planner uses, rather than peak tokens/s. Open
    loop: submission times are drawn from a seeded exponential
    inter-arrival process and never wait on completions, so an
    overloaded engine shows up as queueing delay (TTFT attainment
    collapse past capacity), exactly like real traffic. Also carries
    the tracing-overhead A/B the r16 acceptance requires: the same
    closed-loop workload with the tracer off vs sample 1.0."""
    import paddle_tpu as pt
    from paddle_tpu.inference import create_decode_engine
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny
    from paddle_tpu.serving import SLOConfig, SLOScheduler
    from paddle_tpu.serving.tracing import SpanTracer, request_latencies

    if on_tpu:
        cfg = _decode_1p3b_cfg()
        slots, page, max_seq = 16, 64, 1024
        lens, new_toks = (64, 128, 256), 32
        n_ref, n_cal, n_req = 6, 24, 48
    else:
        cfg = gpt_tiny()
        slots, page, max_seq = 4, 8, 96
        lens, new_toks = (6, 10, 14), 8
        n_ref, n_cal, n_req = 6, 16, 24

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        _to_bf16_except_norms(model)
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size,
                            (lens[i % len(lens)],)).astype(np.int32)
               for i in range(max(n_cal, n_req))]

    def build(tracer):
        eng = create_decode_engine(
            model, num_slots=slots, page_size=page,
            max_seq_len=max_seq,
            scheduler=SLOScheduler(SLOConfig(shed_after_s=None)),
            tracer=tracer)
        # warm THE MEASURED ENGINE's compiles (per-instance jit
        # closures): one request per distinct prompt bucket + decode
        for p in prompts[:len(lens)]:
            eng.submit(p, max_new_tokens=2)
        eng.run()
        if tracer is not None:
            tracer.drain()  # warmup traces are not measurements
        return eng

    def lat_list(tracer):
        out = []
        for t in tracer.drain():
            if t.get("state") != "done":
                continue
            lt = request_latencies(t)
            if lt is not None and lt.get("ttft_s") is not None:
                out.append(lt)
        return out

    # -- unloaded reference (serial, queue-free): the SLO targets ----------
    tracer = SpanTracer(sample_rate=1.0, max_traces=n_req + 8)
    eng = build(tracer)
    for i in range(n_ref):
        eng.submit(prompts[i], max_new_tokens=new_toks)
        eng.run()
    ref = lat_list(tracer)
    ttft_ref = float(np.percentile([r["ttft_s"] for r in ref], 50))
    tpot_ref = float(np.percentile(
        [r["tpot_s"] for r in ref if r["tpot_s"]], 50))
    # targets: a healthy deployment holds TTFT within 5x and TPOT
    # within 3x of its unloaded medians; self-calibrating, so the
    # curve's SHAPE (attainment collapsing past capacity) is the
    # portable result across hosts/chips
    slo_ttft = 5.0 * ttft_ref
    slo_tpot = 3.0 * tpot_ref

    # -- capacity calibration (closed loop) --------------------------------
    t0 = time.perf_counter()
    for i in range(n_cal):
        eng.submit(prompts[i], max_new_tokens=new_toks)
    eng.run()
    cap_rps = n_cal / (time.perf_counter() - t0)
    tracer.drain()
    eng.close()

    # -- open-loop sweep ---------------------------------------------------
    def run_rate(rate_rps: float) -> Dict:
        tr = SpanTracer(sample_rate=1.0, max_traces=n_req + 8)
        e = build(tr)
        arrivals = np.cumsum(
            np.random.default_rng(1).exponential(1.0 / rate_rps,
                                                 n_req))
        done = []
        e.set_on_complete(lambda req: done.append(req.req_id))
        start = time.monotonic()
        submitted = 0
        while len(done) < n_req:
            now = time.monotonic() - start
            while submitted < n_req and arrivals[submitted] <= now:
                e.submit(prompts[submitted],
                         max_new_tokens=new_toks)
                submitted += 1
            if e.num_queued or e.num_active:
                e.step()
            elif submitted < n_req:
                # open loop: idle until the next scheduled arrival
                time.sleep(min(0.002, max(
                    0.0, arrivals[submitted]
                    - (time.monotonic() - start))))
        wall = time.monotonic() - start
        lats = lat_list(tr)
        e.close()
        n = len(lats)
        ok_ttft = sum(1 for l in lats if l["ttft_s"] <= slo_ttft)
        ok_tpot = sum(1 for l in lats
                      if l["tpot_s"] is None
                      or l["tpot_s"] <= slo_tpot)
        ok_both = sum(1 for l in lats
                      if l["ttft_s"] <= slo_ttft
                      and (l["tpot_s"] is None
                           or l["tpot_s"] <= slo_tpot))
        return {"offered_rps": round(rate_rps, 2),
                "completed": n,
                "wall_s": round(wall, 3),
                "ttft_p50_ms": round(float(np.percentile(
                    [l["ttft_s"] for l in lats], 50)) * 1e3, 3),
                "ttft_p99_ms": round(float(np.percentile(
                    [l["ttft_s"] for l in lats], 99)) * 1e3, 3),
                "ttft_attainment": round(ok_ttft / n, 4),
                "tpot_attainment": round(ok_tpot / n, 4),
                "slo_attainment": round(ok_both / n, 4),
                "goodput_rps": round(ok_both / wall, 3)}

    # >= 3 swept rates straddling the calibrated capacity: the curve
    # must show attainment holding under capacity and collapsing past
    sweep = {f"{f:g}x": run_rate(f * cap_rps)
             for f in (0.5, 1.0, 1.5)}

    # -- tracing-overhead A/B (r16 acceptance: off adds ~nothing) ----------
    def closed_loop(tracer) -> Dict:
        e = build(tracer)
        steps0 = e.steps
        t0 = time.perf_counter()
        for i in range(n_cal):
            e.submit(prompts[i], max_new_tokens=new_toks)
        e.run()
        wall = time.perf_counter() - t0
        steps = e.steps - steps0
        e.close()
        return {"wall_s": round(wall, 4), "steps": steps,
                "ms_per_step": round(wall / max(1, steps) * 1e3, 4)}

    off = closed_loop(None)
    on = closed_loop(SpanTracer(sample_rate=1.0,
                                max_traces=n_cal + 8))
    return {"metric": "gpt1p3b_serving_goodput_chip" if on_tpu
            else "gpt_tiny_serving_goodput_cpu_smoke",
            "unit": "SLO-attainment fraction vs offered rps",
            "num_slots": slots, "page_size": page,
            "prompt_lens": list(lens), "new_tokens_per_req": new_toks,
            "requests_per_rate": n_req,
            "capacity_rps_closed_loop": round(cap_rps, 2),
            "slo": {"ttft_ms": round(slo_ttft * 1e3, 3),
                    "tpot_ms": round(slo_tpot * 1e3, 3),
                    "basis": "5x / 3x the unloaded serial medians "
                             f"(ttft {ttft_ref * 1e3:.3f} ms, tpot "
                             f"{tpot_ref * 1e3:.3f} ms)"},
            "by_rate": sweep,
            "trace_overhead": {
                "tracer_off": off, "tracer_on_sample_1": on,
                "ms_per_step_ratio": round(
                    on["ms_per_step"] / max(off["ms_per_step"], 1e-9),
                    3)},
            "note": "open-loop Poisson arrivals (seeded), latencies "
                    "computed from the request SPAN TREES (sample "
                    "1.0); attainment holds under the calibrated "
                    "capacity and collapses past it — the queueing "
                    "regime a closed-loop bench cannot show. "
                    "trace_overhead A/Bs the same closed-loop "
                    "workload tracer-off vs sample-1.0"}


def bench_fleet_goodput(on_tpu: bool) -> Dict:
    """fleet_goodput (r17 fleet telemetry): the serving_goodput
    open-loop sweep run through the FULL topology — supervisor, 2
    replica processes, failover router — with the fleet plane live,
    asserting the LIVE SLO monitor's rolling-window attainment
    (replica-side SLOAttainment merged by the supervisor's collector)
    agrees with the TRACE-computed attainment (request_latencies over
    each replica's span trees — the offline-bench path) within ±0.05
    at every swept rate. Also A/Bs the collector's scrape overhead:
    the same closed-loop workload with the per-probe export scrape on
    vs off, as a fleet ms/step ratio.

    Replicas are pinned to JAX_PLATFORMS=cpu in BOTH lanes: N
    replica processes sharing one TPU would serialize on the chip and
    measure contention, not the plane — the chip rerun needs
    per-replica device assignment (ROADMAP 3(b)) and stays pending."""
    import tempfile
    import threading

    from paddle_tpu.serving.server import client_request
    from paddle_tpu.serving.supervisor import (FailoverRouter,
                                               Supervisor, _rpc)
    from paddle_tpu.serving.tracing import request_latencies

    replicas, page, slots, max_seq = 2, 8, 4, 96
    lens, new_toks = (6, 10, 14), 8
    n_ref, n_cal, n_req = 6, 16, 24
    rng = np.random.default_rng(0)
    vocab = 1000
    prompts = [rng.integers(1, vocab,
                            (lens[i % len(lens)],)).astype(int).tolist()
               for i in range(max(n_cal, n_req))]

    log_dir = tempfile.mkdtemp(prefix="pt-fleet-goodput-")
    replica_env = {"JAX_PLATFORMS": "cpu",
                   "TPU_SKIP_MDS_QUERY": "true"}
    server_args = ["--page-size", str(page), "--num-slots", str(slots),
                   "--max-seq-len", str(max_seq),
                   "--trace-sample", "1.0"]
    sup = Supervisor(model="gpt_tiny", replicas=replicas,
                     server_args=server_args, replica_env=replica_env,
                     probe_interval_s=0.25, log_dir=log_dir)

    def replica_rpc(payload):
        return [_rpc(sup.host, rep.port, payload, timeout_s=30.0)
                for rep in sup.replicas]

    def drain_traces():
        out = []
        for reply in replica_rpc({"op": "trace", "drain": True}):
            out.extend(reply.get("traces") or [])
        return out

    def router_request(port, i, outcomes, idx):
        try:
            outcomes[idx] = client_request(
                "127.0.0.1", port,
                {"op": "generate", "prompt": prompts[i],
                 "max_new_tokens": new_toks}, timeout_s=300.0)
        except Exception as e:
            outcomes[idx] = {"error": f"{type(e).__name__}: {e}"}

    router = None
    try:
        sup.start(wait_ready=True)
        router = FailoverRouter(sup)
        rport = router.start()

        # -- unloaded reference (serial through the router) --------------
        for i in range(len(lens)):  # warm every prompt bucket
            client_request("127.0.0.1", rport,
                           {"op": "generate", "prompt": prompts[i],
                            "max_new_tokens": 2}, timeout_s=300.0)
        drain_traces()
        for i in range(n_ref):
            client_request("127.0.0.1", rport,
                           {"op": "generate", "prompt": prompts[i],
                            "max_new_tokens": new_toks},
                           timeout_s=300.0)
        ref = [lt for t in drain_traces()
               if t.get("state") == "done"
               for lt in [request_latencies(t)]
               if lt is not None and lt.get("ttft_s") is not None]
        ttft_ref = float(np.percentile([r["ttft_s"] for r in ref], 50))
        tpot_ref = float(np.percentile(
            [r["tpot_s"] for r in ref if r["tpot_s"]], 50))
        slo_ttft_ms = 5.0 * ttft_ref * 1e3
        slo_tpot_ms = 3.0 * tpot_ref * 1e3

        # -- capacity calibration (closed loop, concurrent clients) ------
        t0 = time.perf_counter()
        outs: list = [None] * n_cal
        th = [threading.Thread(target=router_request,
                               args=(rport, i, outs, i), daemon=True)
              for i in range(n_cal)]
        for t in th:
            t.start()
        for t in th:
            t.join()
        cap_rps = n_cal / (time.perf_counter() - t0)
        drain_traces()

        def set_slo():
            # (re)target + RESET the rolling windows on both replicas
            # so each swept rate's live attainment covers exactly its
            # own requests
            replica_rpc({"op": "slo", "ttft_ms": slo_ttft_ms,
                         "tpot_ms": slo_tpot_ms})

        def fleet_attainment():
            # wait for the collector to scrape post-completion exports
            time.sleep(3 * sup.probe_interval_s + 0.2)
            fs = client_request("127.0.0.1", rport,
                               {"op": "fleet_stats"})["fleet"]
            return fs["slo"]["attainment"].get("all"), fs

        def run_rate(rate_rps: float) -> Dict:
            set_slo()
            arrivals = np.cumsum(np.random.default_rng(1).exponential(
                1.0 / rate_rps, n_req))
            outcomes: list = [None] * n_req
            threads = []
            start = time.monotonic()
            for i in range(n_req):
                wait = arrivals[i] - (time.monotonic() - start)
                if wait > 0:
                    time.sleep(wait)
                t = threading.Thread(target=router_request,
                                     args=(rport, i, outcomes, i),
                                     daemon=True)
                t.start()
                threads.append(t)
            for t in threads:
                t.join(timeout=300.0)
            wall = time.monotonic() - start
            live, fs = fleet_attainment()
            lats = [lt for t in drain_traces()
                    if t.get("state") == "done"
                    for lt in [request_latencies(t)]
                    if lt is not None and lt.get("ttft_s") is not None]
            n = len(lats)
            ok_both = sum(
                1 for l in lats
                if l["ttft_s"] * 1e3 <= slo_ttft_ms
                and (l["tpot_s"] is None
                     or l["tpot_s"] * 1e3 <= slo_tpot_ms))
            trace_att = (ok_both / n) if n else None
            delta = (None if live is None or trace_att is None
                     else abs(live - trace_att))
            return {"offered_rps": round(rate_rps, 2),
                    "completed": sum(1 for o in outcomes
                                     if isinstance(o, dict)
                                     and o.get("done")),
                    "wall_s": round(wall, 3),
                    "traced": n,
                    "live_attainment": (None if live is None
                                        else round(live, 4)),
                    "trace_attainment": (None if trace_att is None
                                         else round(trace_att, 4)),
                    "agreement_delta": (None if delta is None
                                        else round(delta, 4)),
                    "pressure": fs["pressure"]["verdict"]}

        # straddle capacity WIDE: the closed-loop calibration includes
        # connection/thread overhead the warm open-loop path doesn't
        # pay, so the true knee sits above 1x — the high multiples are
        # what drive attainment into the interesting middle where
        # live-vs-trace agreement is a real check, not 1.0 == 1.0
        sweep = {f"{f:g}x": run_rate(f * cap_rps)
                 for f in (0.5, 2.0, 8.0)}
        deltas = [r["agreement_delta"] for r in sweep.values()
                  if r["agreement_delta"] is not None]
        agree = bool(deltas) and max(deltas) <= 0.05

        # -- collector scrape-overhead A/B (fleet ms/step ratio) ---------
        def fleet_steps():
            return sum(
                s["stats"]["gauges"].get("engine_steps", 0)
                for s in replica_rpc({"op": "stats"}))

        def closed_loop(collect: bool, rounds: int = 3) -> Dict:
            # several rounds: one warm closed loop is ~0.1 s on this
            # host — too small for a stable ms/step ratio
            sup.collect_metrics = collect
            s0 = fleet_steps()
            t0 = time.perf_counter()
            for _ in range(rounds):
                outs: list = [None] * n_cal
                th = [threading.Thread(target=router_request,
                                       args=(rport, i, outs, i),
                                       daemon=True)
                      for i in range(n_cal)]
                for t in th:
                    t.start()
                for t in th:
                    t.join()
            wall = time.perf_counter() - t0
            steps = max(1, fleet_steps() - s0)
            return {"wall_s": round(wall, 4), "steps": int(steps),
                    "ms_per_step": round(wall / steps * 1e3, 4)}

        scrape_off = closed_loop(False)
        scrape_on = closed_loop(True)
    finally:
        # every exit path: the router thread/socket must not outlive
        # the bench inside a long run_staged process, and the scrape
        # toggle must not leak into later phases
        sup.collect_metrics = True
        if router is not None:
            router.stop()
        sup.stop()

    return {"metric": "gpt_tiny_fleet_goodput_cpu_smoke",
            "unit": "fleet SLO-attainment fraction vs offered rps",
            "replicas": replicas, "num_slots": slots,
            "page_size": page, "requests_per_rate": n_req,
            "capacity_rps_closed_loop": round(cap_rps, 2),
            "slo": {"ttft_ms": round(slo_ttft_ms, 3),
                    "tpot_ms": round(slo_tpot_ms, 3),
                    "basis": "5x / 3x unloaded serial medians via "
                             "router"},
            "by_rate": sweep,
            "live_trace_agreement_within_0p05": agree,
            "scrape_overhead": {
                "scrape_off": scrape_off, "scrape_on": scrape_on,
                "ms_per_step_ratio": round(
                    scrape_on["ms_per_step"]
                    / max(scrape_off["ms_per_step"], 1e-9), 3)},
            "note": "open-loop Poisson sweep through supervisor + "
                    "failover router with the fleet telemetry plane "
                    "live; live_attainment is the collector-merged "
                    "rolling-window SLO monitor, trace_attainment is "
                    "the offline path over the same requests' span "
                    "trees — the ±0.05 agreement is the r17 "
                    "acceptance pin. Replicas run JAX_PLATFORMS=cpu "
                    "in both lanes (N processes sharing one chip "
                    "would measure contention, not the plane); the "
                    "chip rerun rides ROADMAP 3(b) per-replica "
                    "device assignment — chip pending."}


def bench_autoscale_goodput(on_tpu: bool) -> Dict:
    """Autoscaling actuator A/B (r21 tentpole artifact): the SAME
    bursty trace — quiet, a hard arrival burst, quiet again — through
    two fleets behind a real FailoverRouter:

    - **static**: 2 replicas for the whole run (the operator's
      overprovision-for-the-burst answer);
    - **auto**: 1 replica + the Autoscaler (min 1 / max 3, short
      cooldowns) consuming the live PressureMonitor verdict — spawns
      into the burst, drains back down in the tail.

    The comparison is normalized to REPLICA-SECONDS (live replica
    count integrated over the wall clock, sampled at 10 Hz): goodput
    per replica-second is what an operator pays for. The autoscaled
    lane spends quiet-phase seconds at 1 replica, so equal goodput at
    fewer replica-seconds — or more goodput at equal replica-seconds
    — is the win the actuator claims.

    Replicas are pinned to JAX_PLATFORMS=cpu in BOTH lanes (N
    processes sharing one chip would measure contention, not the
    actuator); the chip rerun rides ROADMAP 3(b) per-replica device
    assignment — chip pending."""
    import tempfile
    import threading

    from paddle_tpu.serving.autoscaler import (AutoscaleConfig,
                                               Autoscaler)
    from paddle_tpu.serving.fleet_metrics import (FleetMetrics,
                                                  PressureMonitor)
    from paddle_tpu.serving.server import client_request
    from paddle_tpu.serving.supervisor import (FailoverRouter,
                                               Supervisor)

    page, slots, max_seq, new_toks = 8, 2, 128, 64
    deadline_ms = 15000
    lens = (22, 28, 34)
    rng = np.random.default_rng(0)
    vocab = 1000
    # the bursty trace: quiet 0.8 rps, then a burst pinned ABOVE one
    # replica's open-loop service rate (~20 rps for these 64-token
    # requests on cpu — the burst must outrun a replica or no queue
    # ever builds and the actuator correctly never fires), then a
    # quiet tail for the drain-down
    arrivals = []
    t = 0.0
    for n, rate in ((4, 0.8), (280, 45.0), (6, 0.5)):
        for _ in range(n):
            t += float(rng.exponential(1.0 / rate))
            arrivals.append(t)
    prompts = [rng.integers(1, vocab,
                            (lens[i % len(lens)],)).astype(int).tolist()
               for i in range(len(arrivals))]

    bench_dir = tempfile.mkdtemp(prefix="pt-autoscale-goodput-")
    # both lanes share the one compile cache (core/compile_cache.py):
    # the auto lane's mid-burst spawn pays process start, not XLA
    replica_env = {"JAX_PLATFORMS": "cpu",
                   "TPU_SKIP_MDS_QUERY": "true"}
    server_args = ["--page-size", str(page), "--num-slots", str(slots),
                   "--max-seq-len", str(max_seq)]

    def lane(auto: bool) -> Dict:
        log_dir = os.path.join(bench_dir, "auto" if auto else "static")
        fleet = FleetMetrics(
            pressure=PressureMonitor(hysteresis=2, queue_high=3.0),
            pressure_interval_s=0.5)
        sup = Supervisor(model="gpt_tiny",
                         replicas=1 if auto else 2,
                         server_args=server_args,
                         replica_env=replica_env,
                         probe_interval_s=0.25, backoff_base_s=0.5,
                         log_dir=log_dir, fleet=fleet)
        asc = None
        if auto:
            asc = Autoscaler(sup, AutoscaleConfig(
                min_replicas=1, max_replicas=3,
                cooldown_up_s=2.0, cooldown_down_s=3.0,
                interval_s=0.25))
        outcomes: list = [None] * len(arrivals)

        def client(i):
            try:
                outcomes[i] = client_request(
                    "127.0.0.1", rport,
                    {"op": "generate", "prompt": prompts[i],
                     "max_new_tokens": new_toks,
                     "deadline_ms": deadline_ms}, timeout_s=120.0)
            except Exception as e:
                outcomes[i] = {"error": f"{type(e).__name__}: {e}"}

        replica_seconds = 0.0
        peak = 0
        sampling = threading.Event()

        def sampler():
            nonlocal replica_seconds, peak
            last = time.monotonic()
            while not sampling.is_set():
                time.sleep(0.1)
                now = time.monotonic()
                n = len(sup.replicas)
                replica_seconds += n * (now - last)
                peak = max(peak, n)
                last = now

        router = None
        try:
            sup.start(wait_ready=True)
            router = FailoverRouter(sup)
            rport = router.start()
            # warm every prompt bucket before the clock starts
            for ln in lens:
                client_request("127.0.0.1", rport,
                               {"op": "generate",
                                "prompt": prompts[
                                    [len(p) for p in prompts]
                                    .index(ln)],
                                "max_new_tokens": 2}, timeout_s=300.0)
            if asc is not None:
                asc.start()
            sth = threading.Thread(target=sampler, daemon=True)
            sth.start()
            start = time.monotonic()
            threads = []
            for i, at in enumerate(arrivals):
                wait = at - (time.monotonic() - start)
                if wait > 0:
                    time.sleep(wait)
                th = threading.Thread(target=client, args=(i,),
                                      daemon=True)
                th.start()
                threads.append(th)
            for th in threads:
                th.join(timeout=120.0)
            # let the auto lane's drain-down show up in the bill
            tail_until = start + arrivals[-1] + 12.0
            while time.monotonic() < tail_until:
                time.sleep(0.2)
            wall = time.monotonic() - start
            sampling.set()
            sth.join(timeout=5.0)
            actions = None
            if asc is not None:
                st = asc.status()
                actions = {k: v for k, v in
                           st["actions_total"].items()
                           if not k.split("|")[1]
                           .startswith("refused_")}
        finally:
            if asc is not None:
                asc.stop()
            if router is not None:
                router.stop()
            sup.stop()
        done = sum(1 for o in outcomes
                   if isinstance(o, dict) and o.get("done"))
        expired = sum(1 for o in outcomes
                      if isinstance(o, dict)
                      and o.get("error") == "DeadlineExceeded")
        out = {"completed_in_deadline": done,
               "expired": expired,
               "other_failures": len(arrivals) - done - expired,
               "wall_s": round(wall, 2),
               "replica_seconds": round(replica_seconds, 1),
               "peak_replicas": peak,
               "goodput_per_replica_second": round(
                   done / max(replica_seconds, 1e-9), 4)}
        if actions is not None:
            out["autoscale_actions"] = actions
        return out

    static = lane(auto=False)
    auto = lane(auto=True)
    return {"metric": "gpt_tiny_autoscale_goodput_cpu_smoke",
            "unit": "requests completed in deadline per "
                    "replica-second",
            "requests": len(arrivals),
            "deadline_ms": deadline_ms,
            "trace": "bursty: ~5s @0.8rps, ~6s @45rps, ~12s @0.5rps",
            "num_slots": slots, "page_size": page,
            "static_2_replicas": static,
            "autoscaled_1_to_3": auto,
            "replica_second_savings_fraction": round(
                1.0 - auto["replica_seconds"]
                / max(static["replica_seconds"], 1e-9), 3),
            "note": "same bursty open-loop trace through a static "
                    "2-replica fleet vs a 1..3 autoscaled fleet "
                    "(PressureMonitor verdict -> journaled spawn/"
                    "drain); goodput normalized to sampled "
                    "replica-seconds — the autoscaled lane buys its "
                    "burst capacity only while the burst lasts. "
                    "Replicas run JAX_PLATFORMS=cpu in both lanes; "
                    "chip rerun pending ROADMAP 3(b) per-replica "
                    "device assignment."}


def bench_rolling_update(on_tpu: bool) -> Dict:
    """Rolling weight upgrade A/B (r24 tentpole artifact): the SAME
    steady open-loop trace through a 2-replica fleet behind a real
    FailoverRouter while the fleet is upgraded to a new checkpoint
    mid-trace, two ways:

    - **hot_swap_roll**: `Supervisor.roll_fleet` — per replica, hand
      hot chains to the survivor, pause admission while active slots
      drain, apply the validated state through the engine's identity
      cache, verify the health probe reports the new generation;
    - **drain_respawn**: the pre-r24 operator answer — kill each
      replica and respawn it on the new checkpoint (full process
      boot + model build + warm compile per replica).

    Reported per lane: requests completed within deadline (the hot
    lane's claim is ZERO drops — every request completes, none
    expires), the upgrade's wall time, the slowest in-flight request
    while the upgrade ran, and the final fleet generation. Replicas
    are pinned to JAX_PLATFORMS=cpu in both lanes; chip magnitudes
    pending like every cpu_smoke entry."""
    import tempfile
    import threading

    import paddle_tpu as pt
    from paddle_tpu.distributed.resilience import \
        ResilientCheckpointManager
    from paddle_tpu.models.gpt import (GPTForCausalLM, checkpoint_state,
                                       gpt_tiny, perturbed_state)
    from paddle_tpu.serving.server import client_request
    from paddle_tpu.serving.supervisor import FailoverRouter, Supervisor

    page, slots, max_seq, new_toks = 8, 2, 96, 32
    deadline_ms = 30000
    rate_rps, n_requests, upgrade_at_s = 4.0, 80, 5.0
    rng = np.random.default_rng(0)
    arrivals = np.cumsum(rng.exponential(1.0 / rate_rps,
                                         n_requests)).tolist()
    prompts = [rng.integers(1, 1000, (int(rng.integers(16, 30)),))
               .astype(int).tolist() for _ in range(n_requests)]

    bench_dir = tempfile.mkdtemp(prefix="pt-rolling-update-")
    # the new generation's checkpoint: the boot weights perturbed —
    # a real weight delta, saved through the crc-manifested manager
    # exactly as a trainer would publish it
    pt.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    ResilientCheckpointManager(os.path.join(bench_dir, "ckpt")).save(
        1, perturbed_state(checkpoint_state(m), scale=1e-3, seed=1))
    ckpt = os.path.join(bench_dir, "ckpt")
    del m

    replica_env = {"JAX_PLATFORMS": "cpu",
                   "TPU_SKIP_MDS_QUERY": "true"}
    server_args = ["--page-size", str(page), "--num-slots", str(slots),
                   "--max-seq-len", str(max_seq)]

    def lane(hot: bool) -> Dict:
        sup = Supervisor(model="gpt_tiny", replicas=2,
                         server_args=server_args,
                         replica_env=replica_env,
                         probe_interval_s=0.25, backoff_base_s=0.5,
                         log_dir=os.path.join(
                             bench_dir, "hot" if hot else "respawn"))
        outcomes: list = [None] * n_requests
        elapsed: list = [None] * n_requests

        def client(i):
            t0 = time.monotonic()
            try:
                outcomes[i] = client_request(
                    "127.0.0.1", rport,
                    {"op": "generate", "prompt": prompts[i],
                     "max_new_tokens": new_toks,
                     "deadline_ms": deadline_ms}, timeout_s=120.0)
            except Exception as e:
                outcomes[i] = {"error": f"{type(e).__name__}: {e}"}
            elapsed[i] = time.monotonic() - t0

        upgrade: Dict = {}

        def do_upgrade():
            t0 = time.monotonic()
            if hot:
                roll = sup.roll_fleet(ckpt, generation=1,
                                      canary_window_s=0.5)
                upgrade["roll"] = {
                    "ok": roll.get("ok"),
                    "canary": roll.get("canary"),
                    "swapped": len(roll.get("swapped") or ()),
                    "respawned": len(roll.get("respawned") or ())}
            else:
                # the cold path: new committed config, then each
                # replica pays a full process respawn sequentially
                sup.checkpoint = ckpt
                sup.weight_generation = 1
                for rep in sorted(sup.live(), key=lambda r: r.idx):
                    sup._respawn_with_config(rep)
            upgrade["upgrade_s"] = round(time.monotonic() - t0, 2)

        router = None
        try:
            sup.start(wait_ready=True)
            router = FailoverRouter(sup)
            rport = router.start()
            client_request("127.0.0.1", rport,
                           {"op": "generate", "prompt": prompts[0],
                            "max_new_tokens": 2}, timeout_s=300.0)
            start = time.monotonic()
            threads, upth = [], None
            for i, at in enumerate(arrivals):
                if upth is None and at >= upgrade_at_s:
                    upth = threading.Thread(target=do_upgrade,
                                            daemon=True)
                    upth.start()
                wait = at - (time.monotonic() - start)
                if wait > 0:
                    time.sleep(wait)
                th = threading.Thread(target=client, args=(i,),
                                      daemon=True)
                th.start()
                threads.append(th)
            for th in threads:
                th.join(timeout=120.0)
            if upth is not None:
                upth.join(timeout=300.0)
            final_gen = sup.weight_generation
        finally:
            if router is not None:
                router.stop()
            sup.stop()
        done = sum(1 for o in outcomes
                   if isinstance(o, dict) and o.get("done"))
        expired = sum(1 for o in outcomes
                      if isinstance(o, dict)
                      and o.get("error") == "DeadlineExceeded")
        out = {"completed_in_deadline": done,
               "expired": expired,
               "dropped_or_failed": n_requests - done - expired,
               "slowest_request_s": round(
                   max(e for e in elapsed if e is not None), 2),
               "final_generation": final_gen}
        out.update(upgrade)
        return out

    hot = lane(hot=True)
    cold = lane(hot=False)
    return {"metric": "gpt_tiny_rolling_update_cpu_smoke",
            "unit": "requests completed in deadline during a live "
                    "weight upgrade",
            "requests": n_requests,
            "deadline_ms": deadline_ms,
            "trace": f"steady ~{rate_rps:.0f} rps, fleet upgraded to "
                     f"a new checkpoint at t={upgrade_at_s:.0f}s",
            "num_slots": slots, "page_size": page,
            "hot_swap_roll": hot,
            "drain_respawn": cold,
            "note": "same steady open-loop trace through a 2-replica "
                    "fleet upgraded mid-trace: roll_fleet hot-swap "
                    "(handoff + admission pause + validated in-place "
                    "apply) vs kill-and-respawn on the new "
                    "checkpoint. The hot lane's contract is zero "
                    "drops and zero expiries; the cold lane pays two "
                    "full process boots and rides on router "
                    "failover. Replicas run JAX_PLATFORMS=cpu in "
                    "both lanes; chip rerun pending ROADMAP 3(b) "
                    "per-replica device assignment."}


def bench_disaggregated_serving(on_tpu: bool) -> Dict:
    """Disaggregated prefill/decode A/B (r20 tentpole artifact): the
    SAME adversarial trace — steady short unkeyed token streams while
    DISTINCT keyed long prompts arrive mid-flight — through two fleet
    shapes behind a real FailoverRouter: two mixed replicas (the
    pre-r20 fleet) vs one prefill-class + one decode-class replica.
    In the mixed fleet every long prompt's WHOLE prefill runs on a
    replica that is also serving short streams (the head-of-line
    TPOT hit); in the disaggregated fleet the router routes the long
    prompt prefill-first, the prefill replica parks the finished KV
    chain, and the decode replica pulls it over fetch_pages and
    SPLICES it in — the stream-serving side prefills only the
    sub-page suffix. Reported: short-stream TPOT p99 (must be no
    worse), decode-side prefilled tokens (must be strictly reduced),
    the new serving_handoff_ms histogram, and bit_identical across
    fleets (greedy outputs must not change with the topology).
    Replicas are in-process servers (CPU lane: the A/B is a
    scheduling/placement property, real on this lane; chip magnitudes
    pending like every cpu_smoke entry)."""
    import threading

    import paddle_tpu as pt
    from paddle_tpu.core.monitor import StatRegistry
    from paddle_tpu.models import GPTForCausalLM
    from paddle_tpu.serving import ServingMetrics, client_request
    from paddle_tpu.serving.server import ServingServer
    from paddle_tpu.serving.supervisor import FailoverRouter

    # stock gpt_tiny's position table stops at 128 — a 240-token
    # prompt would read out-of-bounds position embeddings (the engine
    # now rejects max_seq_len past cfg.max_seq_len typed), so the
    # trace runs on a tiny config with a 256-position table
    from paddle_tpu.models.gpt import GPTConfig
    cfg = GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                    num_heads=4, max_seq_len=256, dropout=0.0,
                    attn_dropout=0.0)
    # long prompts sized so their WHOLE prefill visibly dents a
    # co-resident stream's inter-token gaps (the interference under
    # test), well above this host's decode-step noise floor
    # interference density: enough long arrivals that the whole-prefill
    # stall lands INSIDE the short gaps' p99 (one outlier among 200+
    # gaps only moves the max — seen as mixed max ~940ms vs p99 ~8ms)
    slots, page, max_seq = 2, 8, 256
    short_len, short_new, n_short, lanes = 6, 16, 10, 2
    long_len, long_new, n_long = 240, 4, 6
    inject_at = (1, 2, 4, 5, 7, 8)

    def make_model():
        # one model INSTANCE per in-process replica: engines sharing a
        # model object cannot trace concurrently (the per-model state
        # refresh races another engine's jit trace — real replicas are
        # separate processes and never share one). Same seed -> same
        # weights, so outputs stay comparable across fleets.
        pt.seed(0)
        m = GPTForCausalLM(cfg)
        m.eval()
        return m

    rng = np.random.default_rng(0)
    shorts = [rng.integers(1, cfg.vocab_size,
                           (short_len,)).astype(int).tolist()
              for _ in range(n_short)]
    longs = [rng.integers(1, cfg.vocab_size,
                          (long_len,)).astype(int).tolist()
             for _ in range(n_long)]

    class _Rep:
        def __init__(self, idx, port, role):
            self.idx, self.port, self.role = idx, port, role
            self.ready, self.restarts = True, 0
            self.page_size, self.load = page, 0
            self.prefix_keys = frozenset()
            self.prefix_truncated = False

        def alive(self):
            return True

    class _Sup:
        def __init__(self, reps):
            self.replicas, self.host = reps, "127.0.0.1"

        def live(self):
            return [r for r in self.replicas if r.ready]

    kw = dict(num_slots=slots, page_size=page, max_seq_len=max_seq)

    def run_fleet(roles):
        srvs = [ServingServer(make_model(), role=role,
                              metrics=ServingMetrics(
                                  registry=StatRegistry()), **kw)
                for role in roles]
        reps = []
        for i, s in enumerate(srvs):
            s.start()
            reps.append(_Rep(i, s.port, roles[i]))
        router = FailoverRouter(_Sup(reps))
        rport = router.start()
        try:
            # warm every compile lane on every replica: short + long
            # prefill buckets, the decode step, and (disagg) the
            # handoff hop + splice path
            for s in srvs:
                client_request("127.0.0.1", s.port,
                               {"op": "generate",
                                "prompt": shorts[0][:short_len],
                                "max_new_tokens": 2}
                               if s.role != "prefill" else
                               {"op": "generate", "prompt": longs[0],
                                "max_new_tokens": 1,
                                "prefill_only": True},
                               timeout_s=300.0)
            client_request("127.0.0.1", rport,
                           {"op": "generate", "prompt": longs[0],
                            "max_new_tokens": 2, "key": "warm-long"},
                           timeout_s=300.0)

            tok_t: Dict[str, list] = {}
            submit_t: Dict[str, float] = {}
            results: Dict[str, Dict] = {}
            done_shorts = [0]
            next_long = [0]
            lock = threading.Lock()
            long_threads = []

            def run_short(tag, i):
                submit_t[tag] = time.perf_counter()
                ts = tok_t.setdefault(tag, [])
                out = client_request(
                    "127.0.0.1", rport,
                    {"op": "generate", "prompt": shorts[i],
                     "max_new_tokens": short_new, "stream": True},
                    timeout_s=300.0,
                    on_token=lambda t: ts.append(time.perf_counter()))
                results[tag] = out

            def run_long(tag, j):
                submit_t[tag] = time.perf_counter()
                results[tag] = client_request(
                    "127.0.0.1", rport,
                    {"op": "generate", "prompt": longs[j],
                     "max_new_tokens": long_new,
                     "key": f"long-{j}"}, timeout_s=300.0)

            def short_lane(lane):
                while True:
                    # claim the next short index under the lock
                    with lock:
                        i = short_lane.next
                        if i >= n_short:
                            return
                        short_lane.next += 1
                    run_short(f"s{i}", i)
                    with lock:
                        done_shorts[0] += 1
                        # adversarial arrivals keyed to completion
                        # counts so both fleets see the same schedule
                        while next_long[0] < n_long and \
                                next_long[0] < len(inject_at) and \
                                done_shorts[0] >= \
                                inject_at[next_long[0]]:
                            j = next_long[0]
                            next_long[0] += 1
                            th = threading.Thread(
                                target=run_long, args=(f"l{j}", j),
                                daemon=True)
                            th.start()
                            long_threads.append(th)

            short_lane.next = 0
            t0 = time.perf_counter()
            lanes_th = [threading.Thread(target=short_lane,
                                         args=(k,), daemon=True)
                        for k in range(lanes)]
            for t in lanes_th:
                t.start()
            for t in lanes_th:
                t.join(timeout=600.0)
            for t in long_threads:
                t.join(timeout=600.0)
            wall = time.perf_counter() - t0

            gaps = []
            for tag, ts in tok_t.items():
                gaps.extend(b - a for a, b in zip(ts, ts[1:]))
            ttft_s = [tok_t[t][0] - submit_t[t]
                      for t in tok_t if tok_t[t]]
            long_out = [results.get(f"l{j}", {}).get("generated")
                        for j in range(n_long)]
            short_out = [results.get(f"s{i}", {}).get("generated")
                         for i in range(n_short)]
            errors = {t: r.get("error") for t, r in results.items()
                      if r.get("error")}
            # decode-side prefilled tokens: what the STREAM-SERVING
            # replica had to prefill for each long prompt (whole
            # prompt when mixed; sub-page suffix after a spliced
            # handoff)
            decode_prefilled = sum(
                results[f"l{j}"]["stats"]["prompt_len"]
                - results[f"l{j}"]["stats"].get("cached_tokens", 0)
                for j in range(n_long) if f"l{j}" in results
                and results[f"l{j}"].get("stats"))
            handoff_pages = sum(
                results[f"l{j}"]["stats"].get("handoff_pages", 0)
                for j in range(n_long) if f"l{j}" in results
                and results[f"l{j}"].get("stats"))
            # handoff telemetry from the decode-capable replicas
            hist = {}
            counters = {}
            for s in srvs:
                if s.role == "prefill":
                    continue
                snap = s.metrics.handoff_ms.snapshot()
                if snap["count"]:
                    hist = {k: (round(v, 3)
                                if isinstance(v, float) else v)
                            for k, v in snap.items()}
                for c in ("handoff_pages_total",
                          "handoff_bytes_total",
                          "handoff_failures_total"):
                    counters[c] = counters.get(c, 0) + \
                        s.metrics.counter(c).get()
            leak_ok = all(
                client_request("127.0.0.1", s.port,
                               {"op": "leak_check"},
                               timeout_s=60.0).get("ok")
                for s in srvs)

            def pctl(vals, p):
                return float(np.percentile(vals, p)) if vals else 0.0

            return {
                "short_tpot_p50_ms": round(pctl(gaps, 50) * 1e3, 3),
                "short_tpot_p99_ms": round(pctl(gaps, 99) * 1e3, 3),
                "short_tpot_max_ms": round(max(gaps) * 1e3, 3)
                if gaps else 0.0,
                "short_ttft_p50_ms": round(pctl(ttft_s, 50) * 1e3, 3),
                "decode_side_prefilled_tokens": int(decode_prefilled),
                "handoff_pages": int(handoff_pages),
                "handoff_ms": hist or None,
                "handoff_counters": counters,
                "router_handoffs": router.handoffs_total,
                "leak_check_ok": bool(leak_ok),
                "errors": errors,
                "wall_s": round(wall, 3),
            }, long_out, short_out
        finally:
            router.stop()
            for s in srvs:
                s.stop()

    # interleaved multi-trial A/B (the memory_observatory lesson one
    # level up): a single trial's TPOT p99 rides scheduling luck — in
    # the mixed fleet the long prefill only dents a short's gaps when
    # it lands on a replica with a stream mid-decode. Medians across
    # interleaved trials keep the comparison honest; bit-identity must
    # hold across EVERY trial of BOTH topologies.
    trials = 3
    mixed_runs, disagg_runs = [], []
    outs: List = []
    for _ in range(trials):
        mixed_runs.append(run_fleet(["mixed", "mixed"]))
        disagg_runs.append(run_fleet(["prefill", "decode"]))
        outs.extend((mixed_runs[-1][1:], disagg_runs[-1][1:]))
    long_m, short_m = outs[0]
    bit_identical = (all(o == (long_m, short_m) for o in outs)
                     and all(o is not None for o in long_m))
    mismatched = sorted({f"l{j}" for lo, _so in outs
                         for j, x in enumerate(lo) if x != long_m[j]}
                        | {f"s{i}" for _lo, so in outs
                           for i, x in enumerate(so) if x != short_m[i]})

    def med(runs, key):
        return float(np.median([r[0][key] for r in runs]))

    mixed = dict(sorted(mixed_runs,
                        key=lambda r: r[0]["short_tpot_p99_ms"])
                 [trials // 2][0])
    disagg = dict(sorted(disagg_runs,
                         key=lambda r: r[0]["short_tpot_p99_ms"])
                  [trials // 2][0])
    for runs, rep in ((mixed_runs, mixed), (disagg_runs, disagg)):
        rep["tpot_p99_trials_ms"] = [
            r[0]["short_tpot_p99_ms"] for r in runs]
    mixed_p99 = med(mixed_runs, "short_tpot_p99_ms")
    disagg_p99 = med(disagg_runs, "short_tpot_p99_ms")
    return {"metric": "gpt_tiny_disaggregated_serving_cpu_smoke",
            "unit": "ms",
            "num_slots": slots, "page_size": page, "trials": trials,
            "short": {"len": short_len, "new": short_new,
                      "count": n_short, "lanes": lanes},
            "long": {"len": long_len, "new": long_new,
                     "count": n_long, "inject_at": list(inject_at)},
            "mixed_fleet": mixed,
            "disaggregated_fleet": disagg,
            "bit_identical": bit_identical,
            "mismatched_requests": mismatched,
            "reprefill_strictly_reduced": (
                med(disagg_runs, "decode_side_prefilled_tokens")
                < med(mixed_runs, "decode_side_prefilled_tokens")),
            "tpot_p99_no_worse": disagg_p99 <= mixed_p99 * 1.05,
            "note": "same completion-keyed adversarial trace through "
                    "two fleet shapes behind a real FailoverRouter "
                    "(in-process replicas): 2 mixed vs 1 prefill + 1 "
                    "decode, interleaved median-of-3 per topology. "
                    "Keyed long prompts route prefill-first and the "
                    "decode replica splices the fetched chain; short "
                    "streams are unkeyed. TPOT p99 and decode-side "
                    "prefilled tokens are the headline pair; greedy "
                    "outputs pinned bit-identical across every trial "
                    "of both fleets. cpu_smoke: scheduling/placement "
                    "property is real here, wire+splice magnitudes "
                    "vs chip prefill FLOPs are chip-pending"}


def bench_speculative_decode(on_tpu: bool) -> Dict:
    """Speculative-decoding A/B (r8 tentpole artifact): the SAME
    request stream through the continuous-batching engine vanilla vs
    draft-and-verify at k in {2, 4, 8}, draft = n-gram prompt lookup
    (no second model) and a small draft model. Greedy outputs are
    bit-identical by contract (tests/test_speculative.py pins it), so
    the entire delta is engine steps saved: each verify step emits
    1..k+1 tokens for ONE weight/KV stream pass. Reported per mode:
    generated tokens/s, measured acceptance rate, decode tokens per
    verify step, and engine steps vs the vanilla baseline."""
    import paddle_tpu as pt
    from paddle_tpu.inference import (ModelDraft, SpeculativeConfig,
                                      create_decode_engine)
    from paddle_tpu.models import GPTForCausalLM, gpt_tiny

    if on_tpu:
        cfg = _decode_1p3b_cfg()
        slots, page, max_seq = 16, 64, 1024
        lens = [64, 128, 256, 384]
        n_req, new_toks = 16, 64
        draft_cfg = gpt_tiny(vocab_size=cfg.vocab_size, dtype=cfg.dtype,
                             use_flash_attention=False, max_seq_len=256)
    else:
        cfg = gpt_tiny()
        slots, page, max_seq = 4, 8, 128
        lens = [14, 20, 26, 32]
        n_req, new_toks = 8, 24
        draft_cfg = None  # self-draft: gpt_tiny drafting for gpt_tiny

    pt.seed(0)
    model = GPTForCausalLM(cfg)
    if on_tpu:
        _to_bf16_except_norms(model)
    model.eval()
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size,
                            (lens[i % len(lens)],)).astype(np.int32)
               for i in range(n_req)]

    if draft_cfg is not None:
        pt.seed(0)
        draft_model = GPTForCausalLM(draft_cfg)
        if on_tpu:
            _to_bf16_except_norms(draft_model)
        draft_model.eval()
    else:
        draft_model = model

    def run_mode(spec) -> Dict:
        done = []
        eng = create_decode_engine(
            model, num_slots=slots, page_size=page, max_seq_len=max_seq,
            speculative=spec, on_complete=done.append)
        # warm the measured engine's compiles (prefill buckets +
        # decode/verify + any draft jit), then drain before timing
        for p in prompts[:len(lens)]:
            eng.submit(p, max_new_tokens=2)
        eng.run()
        done.clear()
        steps_before = eng.steps
        t0 = time.perf_counter()
        rids = [eng.submit(p, max_new_tokens=new_toks) for p in prompts]
        try:
            results = eng.run()
        finally:
            eng.close()
        wall = time.perf_counter() - t0
        timed_steps = eng.steps - steps_before
        dt = wall
        gen = sum(len(results[r]) - len(p)
                  for r, p in zip(rids, prompts))
        out = {"tokens_per_s": round(gen / dt, 1),
               "engine_steps": timed_steps,
               "generated_tokens": gen}
        drafted = sum(r.stats.spec_drafted for r in done)
        accepted = sum(r.stats.spec_accepted for r in done)
        vsteps = sum(r.stats.spec_steps for r in done)
        if vsteps:
            out["acceptance_rate"] = round(accepted / max(1, drafted), 4)
            out["tokens_per_step"] = round(
                sum(r.stats.tokens_out - 1 for r in done) / vsteps, 3)
        return out

    vanilla = run_mode(None)
    by_mode: Dict = {}
    for label, draft in (("ngram", "ngram"), ("draft_model",
                                              draft_model)):
        for k in (2, 4, 8):
            spec = SpeculativeConfig(k=k, draft=draft, draft_window=64)
            entry = run_mode(spec)
            if vanilla["tokens_per_s"]:
                entry["vs_vanilla"] = round(
                    entry["tokens_per_s"] / vanilla["tokens_per_s"], 3)
            by_mode[f"{label}_k{k}"] = entry
    return {"metric": "gpt1p3b_speculative_decode_chip" if on_tpu
            else "gpt_tiny_speculative_decode_cpu_smoke",
            "requests": n_req, "prompt_lens": lens,
            "new_tokens_per_req": new_toks, "num_slots": slots,
            "page_size": page,
            "draft_model": ("gpt_tiny" if on_tpu else
                            "gpt_tiny (self-draft)"),
            "vanilla": vanilla, "by_mode": by_mode,
            "note": "greedy outputs bit-identical across all modes "
                    "(pinned); n-gram acceptance on a RANDOM-weight "
                    "cpu_smoke model is ~0 by construction (its greedy "
                    "stream is aperiodic — prompt lookup pays off on "
                    "trained models' self-repeating text), so the "
                    "draft_model rows carry the amortization result"}


def bench_moe_dispatch(on_tpu: bool) -> Dict:
    """MoE dispatch microbench (VERDICT "do this" #4b): forward
    tokens/s for a 4-expert capacity-dispatch GPT (top-2, every block
    MoE) vs an equal-FLOPs dense-FFN GPT (ffn mult doubled to match
    the k=2 expert compute per token). Measures the DISPATCH overhead
    — gate, capacity scatter/gather, drops — against the dense oracle
    at matched arithmetic."""
    import jax

    import paddle_tpu as pt
    from paddle_tpu.models import GPTConfig, GPTForCausalLM
    from paddle_tpu.tensor import Tensor

    if on_tpu:
        base = dict(vocab_size=50304, hidden_size=2048, num_layers=4,
                    num_heads=16, max_seq_len=1024, dropout=0.0,
                    attn_dropout=0.0)
        batch, seq = 8, 1024
    else:
        base = dict(vocab_size=1024, hidden_size=128, num_layers=2,
                    num_heads=4, max_seq_len=128, dropout=0.0,
                    attn_dropout=0.0)
        batch, seq = 2, 64

    moe_cfg = GPTConfig(moe_experts=4, moe_every=1, moe_top_k=2,
                        ffn_hidden_mult=4, **base)
    dense_cfg = GPTConfig(moe_experts=0, ffn_hidden_mult=8, **base)

    rng = np.random.default_rng(0)
    ids = rng.integers(0, base["vocab_size"],
                       (batch, seq)).astype(np.int32)

    def measure(cfg) -> float:
        pt.seed(0)
        model = GPTForCausalLM(cfg)
        if on_tpu:
            _to_bf16_except_norms(model)
        model.eval()

        def run():
            out = model.forward(Tensor(ids))
            jax.block_until_ready(
                out.value if hasattr(out, "value") else out)

        run()  # compile/warm
        dt, _ = _timed_windows(run)
        return dt

    dt_moe = measure(moe_cfg)
    dt_dense = measure(dense_cfg)
    toks = batch * seq
    out: Dict = {"metric": "gpt_moe_dispatch_tokens_per_s_chip"
                 if on_tpu else "gpt_moe_dispatch_cpu_smoke",
                 "batch": batch, "seq": seq,
                 "experts": 4, "top_k": 2,
                 "moe_capacity_dispatch": {
                     "ms_per_fwd": round(dt_moe * 1e3, 3),
                     "tokens_per_s": round(toks / dt_moe, 1)},
                 "dense_equal_flops": {
                     "ms_per_fwd": round(dt_dense * 1e3, 3),
                     "tokens_per_s": round(toks / dt_dense, 1)},
                 "moe_vs_dense": round(dt_moe / dt_dense, 3),
                 "note": "same FLOPs/token by construction (top-2 of "
                         "mult-4 experts vs mult-8 dense); the ratio "
                         "is the dispatch machinery's cost"}
    return out


def _serve_latency(prefix, example_inputs, n_runs: int) -> Dict:
    """Serving metrics through the AOT predictor:

    - p50/p99_wall_ms: per-request wall latency incl. the launch round
      trip;
    - pipelined_requests_per_s / pipelined_ms_per_req: N zero-copy
      handle-pattern launches in flight, blocked once. This is the
      serving-throughput figure to compare;
    - device_ms_per_req (r5 verdict item 5 — reconcile the two serving
      numbers): per-request DEVICE execution time, measured as the
      steady-state per-launch time of a long saturated pipeline (3x
      the pipelined window, one block at the end). With launches
      continuously in flight the device is the bottleneck, so elapsed
      / N converges on device execution per request."""
    from paddle_tpu.inference import Config, create_predictor

    import jax
    import jax.numpy as jnp

    cfg = Config(prefix)
    cfg.disable_gpu()
    pred = create_predictor(cfg)
    # device-staged inputs (share_external_data serving pattern): the
    # timed region is the model launch, not the host->device copy
    example_inputs = [jnp.asarray(a) for a in example_inputs]
    pred.run(example_inputs)  # compile + warm
    lat = []
    for _ in range(n_runs):
        t0 = time.perf_counter()
        pred.run(example_inputs)
        lat.append((time.perf_counter() - t0) * 1e3)
    lat = np.asarray(lat)

    # pipelined: inputs pre-bound to handles, run() without per-call
    # host fetch (outputs stay device-side), block on the last one
    for n, a in zip(pred.get_input_names(), example_inputs):
        pred.get_input_handle(n).copy_from_cpu(a)
    pred.run()  # warm the no-fetch path
    n_pipe = max(32, n_runs)
    t0 = time.perf_counter()
    for _ in range(n_pipe):
        pred.run()
    jax.block_until_ready(pred._outputs)
    dt = time.perf_counter() - t0
    # device execution per request: a 3x-longer saturated window so the
    # single end-of-window block and the warmup launch are amortized to
    # <1% — steady-state per-launch time == device time when the queue
    # never drains
    n_dev = 3 * n_pipe
    t0 = time.perf_counter()
    for _ in range(n_dev):
        pred.run()
    jax.block_until_ready(pred._outputs)
    dt_dev = time.perf_counter() - t0
    return {"p50_wall_ms": round(float(np.percentile(lat, 50)), 3),
            "p99_wall_ms": round(float(np.percentile(lat, 99)), 3),
            "pipelined_requests_per_s": round(n_pipe / dt, 1),
            "pipelined_ms_per_req": round(dt / n_pipe * 1e3, 3),
            "device_ms_per_req": round(dt_dev / n_dev * 1e3, 3),
            "runs": n_runs, "pipelined_runs": n_pipe,
            "device_window_runs": n_dev}


def bench_inference(on_tpu: bool) -> Dict:
    """Config 5: AOT predictor serving latency, ResNet + BERT."""
    import tempfile
    with tempfile.TemporaryDirectory(prefix="pt_bench_infer_") as workdir:
        return _bench_inference(on_tpu, workdir)


def _bench_inference(on_tpu: bool, workdir: str) -> Dict:
    import paddle_tpu as pt
    from paddle_tpu import static
    from paddle_tpu.models.bert import (BertForSequenceClassification,
                                        bert_base, bert_tiny)
    from paddle_tpu.vision.models import resnet50, resnet18

    n_runs = 100 if on_tpu else 10
    rng = np.random.default_rng(0)
    out: Dict = {}

    pt.seed(0)
    rmodel = resnet50() if on_tpu else resnet18(num_classes=10)
    rmodel.eval()
    hw = 224 if on_tpu else 64
    rprefix = os.path.join(workdir, "resnet")
    static.save_inference_model(
        rprefix, [static.InputSpec((1, 3, hw, hw), "float32", "x")],
        layer=rmodel)
    rx = rng.standard_normal((1, 3, hw, hw)).astype(np.float32)
    out["resnet"] = _serve_latency(rprefix, [rx], n_runs)

    pt.seed(0)
    bcfg = (bert_base(hidden_dropout_prob=0.0,
                      attention_probs_dropout_prob=0.0)
            if on_tpu else bert_tiny())
    bmodel = BertForSequenceClassification(bcfg)
    bmodel.eval()
    seq = 128 if on_tpu else 32
    bprefix = os.path.join(workdir, "bert")
    static.save_inference_model(
        bprefix, [static.InputSpec((1, seq), "int32", "input_ids")],
        layer=bmodel)
    bx = rng.integers(0, bcfg.vocab_size, (1, seq)).astype(np.int32)
    out["bert"] = _serve_latency(bprefix, [bx], n_runs)

    out["metric"] = ("predictor_serving_latency_chip" if on_tpu
                     else "predictor_serving_latency_cpu_smoke")
    out["unit"] = "ms"
    return out


def run_staged(on_tpu: bool) -> Dict:
    """All staged configs, in order; a phase that raises ends the run
    (no phase's failure is reported beside a zero exit)."""
    import sys
    staged: Dict = {}
    for name, fn in (("resnet50", bench_resnet50),
                     ("bert_base", bench_bert_base),
                     ("long_context", bench_long_context),
                     ("decode", bench_decode),
                     ("paged_decode", bench_paged_decode),
                     ("ragged_serving", bench_ragged_serving),
                     ("fused_decode", bench_fused_decode),
                     ("chunked_prefill", bench_chunked_prefill),
                     ("mesh_decode", bench_mesh_decode),
                     ("serving_prefix", bench_serving_prefix),
                     ("prefix_tiers", bench_prefix_tiers),
                     ("kv_substrate", bench_kv_substrate),
                     ("disaggregated_serving",
                      bench_disaggregated_serving),
                     ("serving_goodput", bench_serving_goodput),
                     ("fleet_goodput", bench_fleet_goodput),
                     ("autoscale_goodput", bench_autoscale_goodput),
                     ("rolling_update", bench_rolling_update),
                     ("memory_observatory", bench_memory_observatory),
                     ("speculative_decode", bench_speculative_decode),
                     ("moe_dispatch", bench_moe_dispatch),
                     ("inference", bench_inference)):
        t0 = time.time()
        staged[name] = fn(on_tpu)
        print(f"[bench_all] {name}: {staged[name]} "
              f"({time.time() - t0:.0f}s)", file=sys.stderr, flush=True)
    return staged


def main(argv=None) -> None:
    import argparse

    from bench import device_block, require_tpu
    from paddle_tpu.core.compile_cache import enable_compile_cache

    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument(
        "--cpu-rehearsal", action="store_true",
        help="run every phase at its tiny CPU size (paths and control "
             "flow only; metrics are named *_cpu_smoke and the device "
             "block names the CPU)")
    args = parser.parse_args(argv)

    import jax
    if args.cpu_rehearsal:
        jax.config.update("jax_platforms", "cpu")
    else:
        require_tpu()
    # a re-run of the sweep skips every unchanged compile
    enable_compile_cache()
    staged = run_staged(on_tpu=not args.cpu_rehearsal)
    staged["device"] = device_block()
    print(json.dumps(staged))


if __name__ == "__main__":
    main()
