"""Layout-native ("folded") flash attention for single-K-block shapes.

The BERT-shape fix for the [B,S,H,D] -> [B,H,S,D] transpose tax
(pre-round BERT device trace, r4: ~27 ms/step of "data
formatting" around the flash custom-calls — pure overhead created by
the kernel's calling convention, named by the r4 verdict as the #2
perf item). Reference analog: the fused CUDA attention
paddle/fluid/operators/fused/multihead_matmul_op.cu, which likewise
reads the projection's natural [B, S, 3*H*D] layout directly.

Design: q/k/v stay in the projection's natural [B, S, E] layout
(E = H*D; the model-side [B,S,H,D] reshape is a free bitcast). The
grid tiles E into 128-lane column groups — exactly 2 heads at d=64,
1 head at d=128 — so every block DMA is lane-aligned on the native
row-major layout and NO transpose is ever materialized. Heads inside
a group are separated by in-kernel lane slicing (measured: Mosaic
lowers the 64-lane slices fine; the whole fwd+bwd runs ~19% faster
than the transposing BHSD path on the isolated b64 h12 s512 d64
microbench, and the win compounds in the full model where the
transposes also break XLA fusion).

Single-K-block only (sq == sk == one block <= 1024): at these shapes
the whole score matrix fits in VMEM, so
- the forward is a plain softmax (no online-softmax streaming state);
- the backward RECOMPUTES the softmax from q/k and derives
  delta = rowsum(p_hat * dp) in-register — no saved lse, no delta
  prepass, no out residual. Residuals are (q, k, v) alone, in the
  fused single pass dQ/dK/dV kernel.
Longer sequences stay on the streaming BHSD kernels in
flash_attention.py (GPT S>=2048 causal), where online softmax is
actually needed.
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from .naming import named_pallas_call

# whole-S score blocks: [S, S] f32 intermediates in VMEM. 1024 keeps
# the backward's live set (~4 x 4 MB) inside the scoped-vmem budget.
MAX_SINGLE_BLOCK = 1024
_NEG_INF = -1e30


def _heads_per_group(d: int) -> int:
    return 128 // d


def _causal_mask(s):
    q_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    k_pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    return jnp.where(q_pos >= k_pos, s, _NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, *, scale, causal, d, grp):
    outs = []
    for hh in range(grp):
        sl = slice(hh * d, (hh + 1) * d)
        qh = q_ref[0][:, sl]
        kh = k_ref[0][:, sl]
        vh = v_ref[0][:, sl]
        s = jax.lax.dot_general(qh, kh, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)
        acc = jax.lax.dot_general(p.astype(vh.dtype), vh,
                                  (((1,), (0,)), ((), ())),
                                  preferred_element_type=jnp.float32)
        outs.append(acc / l)
    o_ref[0] = jnp.concatenate(outs, axis=1).astype(o_ref.dtype)


def _bwd_kernel(q_ref, k_ref, v_ref, do_ref, dq_ref, dk_ref, dv_ref,
                *, scale, causal, d, grp):
    """Fused dQ/dK/dV with in-kernel softmax recompute: p_hat is rebuilt
    from q/k (no saved lse) and delta = rowsum(p_hat * dp) replaces the
    separate rowsum(do * o) prepass — identical by substitution:
    o = p_hat @ v  =>  rowsum(do * o) = rowsum(p_hat * (do @ v^T))."""
    dqs, dks, dvs = [], [], []
    for hh in range(grp):
        sl = slice(hh * d, (hh + 1) * d)
        qh = q_ref[0][:, sl]
        kh = k_ref[0][:, sl]
        vh = v_ref[0][:, sl]
        doh = do_ref[0][:, sl]
        s = jax.lax.dot_general(qh, kh, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if causal:
            s = _causal_mask(s)
        m = jnp.max(s, axis=1, keepdims=True)
        p = jnp.exp(s - m)
        l = jnp.maximum(jnp.sum(p, axis=1, keepdims=True), 1e-30)
        phat = p / l  # [S, S] f32, normalized
        dp = jax.lax.dot_general(doh, vh, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        delta = jnp.sum(phat * dp, axis=1, keepdims=True)
        ds = phat * (dp - delta) * scale
        dsc = ds.astype(qh.dtype)
        dqs.append(jax.lax.dot_general(
            dsc, kh, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
        dks.append(jax.lax.dot_general(
            dsc, qh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
        dvs.append(jax.lax.dot_general(
            phat.astype(doh.dtype), doh, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))
    dq_ref[0] = jnp.concatenate(dqs, axis=1).astype(dq_ref.dtype)
    dk_ref[0] = jnp.concatenate(dks, axis=1).astype(dk_ref.dtype)
    dv_ref[0] = jnp.concatenate(dvs, axis=1).astype(dv_ref.dtype)


def _col_spec(s):
    """[B, S, E] block: full batch-element rows, one 128-lane column
    group — lane-aligned strided DMA on the native layout."""
    return pl.BlockSpec((1, s, 128), lambda b, g: (b, 0, g),
                        memory_space=pltpu.VMEM)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _folded_core(q, k, v, head_dim, scale, causal):
    return _folded_fwd(q, k, v, head_dim, scale, causal)


def _folded_fwd(q, k, v, head_dim, scale, causal):
    b, s, e = q.shape
    grp = _heads_per_group(head_dim)
    h = e // head_dim
    return named_pallas_call(
        "folded_fwd",
        functools.partial(_fwd_kernel, scale=scale, causal=causal,
                          d=head_dim, grp=grp),
        grid=(b, e // 128),
        in_specs=[_col_spec(s)] * 3,
        out_specs=_col_spec(s),
        out_shape=jax.ShapeDtypeStruct((b, s, e), q.dtype),
        cost_estimate=pl.CostEstimate(
            flops=4 * b * h * s * s * head_dim,
            bytes_accessed=4 * q.size * q.dtype.itemsize,
            transcendentals=b * h * s * s),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(q, k, v)


def _folded_vjp_fwd(q, k, v, head_dim, scale, causal):
    # selective-remat hook (remat_save_attention): this kernel's ONLY
    # backward residuals are q/k/v themselves (the softmax recompute is
    # in-kernel by design — there is no out/lse to buy back), so the
    # named-save policy tags them: under jax.checkpoint the projections
    # feeding attention are then saved instead of recomputed, the
    # closest analog of the BHSD path's saved out+lse.
    from ...core.offload import ATTN_OUT_NAME, name_activation
    q = name_activation(q, ATTN_OUT_NAME)
    k = name_activation(k, ATTN_OUT_NAME)
    v = name_activation(v, ATTN_OUT_NAME)
    return _folded_fwd(q, k, v, head_dim, scale, causal), (q, k, v)


def _folded_vjp_bwd(head_dim, scale, causal, res, g):
    q, k, v = res
    b, s, e = q.shape
    grp = _heads_per_group(head_dim)
    h = e // head_dim
    dq, dk, dv = named_pallas_call(
        "folded_bwd",
        functools.partial(_bwd_kernel, scale=scale, causal=causal,
                          d=head_dim, grp=grp),
        grid=(b, e // 128),
        in_specs=[_col_spec(s)] * 4,
        out_specs=[_col_spec(s)] * 3,
        out_shape=[jax.ShapeDtypeStruct((b, s, e), q.dtype)] * 3,
        cost_estimate=pl.CostEstimate(
            # s, dp, dq, dk, dv matmuls over every (q, k) pair
            flops=10 * b * h * s * s * head_dim,
            bytes_accessed=7 * q.size * q.dtype.itemsize,
            transcendentals=b * h * s * s),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(q, k, v, g)
    return dq, dk, dv


_folded_core.defvjp(_folded_vjp_fwd, _folded_vjp_bwd)


def folded_attention_supported(q_shape, k_shape, causal: bool = False,
                               backend: Optional[str] = None) -> bool:
    """Gate for the [B, S, H, D]-layout entry: same-length single-block
    self-attention with head groups that tile 128 lanes exactly.

    Causal caps: the single block pays the full S^2 while the
    streaming kernel skips fully-masked blocks, but at d=64 the
    streaming kernel's half-lane matmuls are inefficient enough that
    folded wins anyway — measured v5e causal fwd+bwd scanned:
    S=512 b64 h12 folded 5.68 vs streaming 6.62 ms/iter, S=1024 b8
    h12 folded 4.33 vs 5.25 — so d=64 causal runs folded through the
    whole single-block range. d=128 causal caps at one 256-block
    (r6, tools/folded_crossover_sweep.py -> FOLDED_CROSSOVER.json,
    replacing r5's unmeasured-conservative 512): calibrating the
    streaming kernel's non-MXU cost from those d=64 measurements and
    halving only its MAC term for full-lane d=128 puts folded at
    ~1.6x streaming's time at S=512 and ~1.5x at S=1024 — the 2x
    causal-pair skip dominates once streaming's contractions are
    full-lane — while S=256 stays folded because streaming is below
    its own measured XLA crossover there (_FLASH_MIN_SEQ). The sweep
    tool re-derives the cap from on-chip data when a chip is
    reachable; FOLDED_CROSSOVER.json records on_chip_pending until
    then."""
    from .flash_attention import _FORCE_DEPTH
    if backend is None:
        backend = jax.default_backend()
    if backend != "tpu" and _FORCE_DEPTH == 0:
        return False
    b, sq, h, d = q_shape
    sk = k_shape[1]
    if causal and sq > (MAX_SINGLE_BLOCK if d == 64 else 256):
        return False
    return (sq == sk and sq <= MAX_SINGLE_BLOCK and sq % 128 == 0 and
            d in (64, 128) and (h * d) % 128 == 0)


def folded_attention(q, k, v, causal: bool = False,
                     scale: Optional[float] = None):
    """Public entry, layout [B, S, H, D] (matching
    scaled_dot_product_attention); the [B, S, E] fold is a free
    reshape of the projection output — no transpose is materialized
    anywhere on the path."""
    b, s, h, d = q.shape
    e = h * d
    scale = float(scale if scale is not None else 1.0 / math.sqrt(d))
    out = _folded_core(q.reshape(b, s, e), k.reshape(b, s, e),
                       v.reshape(b, s, e), d, scale, bool(causal))
    return out.reshape(b, s, h, d)
