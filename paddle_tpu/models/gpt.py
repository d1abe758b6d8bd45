"""GPT/ERNIE-class decoder-only transformer — the flagship model family.

Reference parity: the fleet-era GPT implementations the reference's hybrid
parallelism was built to train (Megatron-style TP layers
distributed/fleet/meta_parallel/parallel_layers/mp_layers.py + PP segments
pp_layers.py + sharding). Architecture choices follow the GPT-3/ERNIE 3.0
configs in BASELINE.md.

TPU-first: bf16 compute with fp32 layernorm/softmax, attention through
scaled_dot_product_attention (Pallas flash kernel on TPU), uniform blocks
so pipeline stages stack into a scanned [n_layer, ...] pytree, and every
parameter annotated with its hybrid-mesh PartitionSpec (dp×mp×pp×sp).
"""

from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
import threading
from typing import Any, NamedTuple, Optional

import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .. import dispatch
from ..nn import functional as NF
from ..nn.common import Dropout, Embedding, Linear
from ..nn.container import LayerList
from ..nn.initializer import Normal
from ..nn.layer import Layer
from ..nn.norm import LayerNorm
from ..tensor import Tensor
from ..distributed.mp_layers import (ColumnParallelLinear,
                                     ParallelCrossEntropy,
                                     RowParallelLinear,
                                     VocabParallelEmbedding, _constrain)

F = dispatch.wrapped_ops


@dataclasses.dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_seq_len: int = 1024
    ffn_hidden_mult: int = 4
    dropout: float = 0.1
    attn_dropout: float = 0.1
    initializer_range: float = 0.02
    layer_norm_epsilon: float = 1e-5
    tie_word_embeddings: bool = True
    use_flash_attention: bool = True
    # None | "ring" | "ulysses" | "zigzag" (balanced causal ring: the
    # model permutes the sequence into the zigzag layout once at the
    # embedding boundary and back after the final norm)
    seq_parallel_mode: Optional[str] = None
    dtype: str = "float32"
    # MoE (beyond-reference): every `moe_every`-th block uses an
    # expert-parallel MoE FFN when moe_experts > 0
    moe_experts: int = 0
    moe_every: int = 2
    moe_top_k: int = 2
    # Chunked LM loss: compute logits+CE over sequence chunks of this many
    # positions under jax.checkpoint, so the [B, S, vocab] logits tensor
    # never materializes (peak activation drops from S*V to chunk*V per
    # example). 0 = off. Memory-saving analog of the reference's fused
    # c_softmax_with_cross_entropy (which also avoids a separate softmax
    # tensor); here it additionally avoids the full logits.
    loss_chunk_size: int = 0
    # Rematerialize each transformer block in backward (jax.checkpoint):
    # O(L) -> O(1) per-layer activation memory at ~33% extra FLOPs.
    # Single-chip analog of the reference's RecomputeOptimizer
    # (python/paddle/fluid/optimizer.py:5288). MoE blocks are NOT
    # rematerialized (their aux-loss side channel cannot escape
    # jax.checkpoint), so with moe_experts>0 only the dense blocks
    # drop out of the activation footprint.
    remat: bool = False
    # With remat on, rematerialize only blocks where
    # layer_idx % remat_every == 0: trades activation memory back for
    # fewer recomputed FLOPs when HBM has headroom (selective
    # checkpointing; remat_every=1 = every block).
    remat_every: int = 1
    # Selective remat: SAVE each attention mix's output so backward
    # recompute skips the flash forward — the block's dominant
    # recompute cost at long S — for only [B, S, H] of residual memory
    # per layer. Process-global (sets core.offload's remat saved names
    # at model build, consulted by the jax.checkpoint policy). DENSE
    # flash path only: ring/ulysses/zigzag sequence parallelism wraps
    # its hops in its own custom_vjp, and jax.checkpoint's
    # named-residual policy cannot see inside a custom_vjp — measured
    # bit-identical compiled memory on the S=32k zigzag scale proof
    # (SCALE_PROOF_LONGCTX.json variant_remat_save_attention).
    remat_save_attention: bool = False

    def __post_init__(self):
        if self.remat and self.remat_every < 1:
            raise ValueError(
                "remat_every must be >= 1 (1 = remat every block); to "
                "disable rematerialization set remat=False")

    @property
    def head_dim(self):
        return self.hidden_size // self.num_heads


# staged baseline configs (BASELINE.md: GPT-3 1.3B, ERNIE-3.0 10B)
def gpt_tiny(**kw):
    return GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2,
                     num_heads=4, max_seq_len=128, dropout=0.0,
                     attn_dropout=0.0, **kw)


def gpt_125m(**kw):
    return GPTConfig(hidden_size=768, num_layers=12, num_heads=12, **kw)


def gpt_350m(**kw):
    """GPT-3 350M (BASELINE.md family; Brown et al. 2020, table 2.1:
    24 layers, hidden 1024, 16 heads)."""
    return GPTConfig(hidden_size=1024, num_layers=24, num_heads=16,
                     max_seq_len=2048, **kw)


def gpt_1p3b(**kw):
    return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16,
                     max_seq_len=2048, **kw)


def ernie_10b(**kw):
    return GPTConfig(hidden_size=4096, num_layers=48, num_heads=64,
                     max_seq_len=4096, **kw)


# -- fused decode hot path (r13) --------------------------------------------
#
# Trace-time switch, the same pattern as ops/pallas/paged_attention.py
# `head_sharding`: while active, the paged decode/verify paths fold
# their epilogues into fused ops — `paged_attention_fused` (attention +
# out-projection, one launch) inside GPTAttention, and callers sample
# through nn/decode.py `fused_sample_token` over `decode_hidden` so the
# [B, vocab] logits never materialize. THREAD-LOCAL because jit traces
# run on the calling thread and a fused serving engine may trace
# concurrently with an unfused one (two server threads). The switch
# changes the op composition, never the math: greedy outputs stay
# bit-identical to the unfused trace (pinned in
# tests/test_fused_decode.py).

_FUSED_DECODE = threading.local()


@contextlib.contextmanager
def fused_decode(enable: bool = True):
    """Route paged decode/verify traces through the fused kernels for
    the duration (wrap the jit-traced call, not the runtime one)."""
    prev = getattr(_FUSED_DECODE, "value", False)
    _FUSED_DECODE.value = bool(enable)
    try:
        yield
    finally:
        _FUSED_DECODE.value = prev


def fused_decode_active() -> bool:
    return bool(getattr(_FUSED_DECODE, "value", False))


class StaticKVCache(NamedTuple):
    """Preallocated per-layer KV buffer for fixed-shape decode.

    ``k``/``v``: [B, max_len, H, D] buffers; ``pos``: number of valid
    positions already written. Shapes never change across decode steps,
    so the whole generate loop compiles into one lax.scan (the serving
    analog of the reference inference engine's fused decoder kernels,
    e.g. operators/fused/multihead_matmul_op.cu's cache path)."""

    k: Any
    v: Any
    pos: Any


class PagedKVCache(NamedTuple):
    """Block-paged per-layer KV cache for ragged fixed-shape decode.

    KV lives in a pool of fixed-size pages (``k_pages``/``v_pages``:
    [num_pages + 1, page_size, H, D]; the LAST page is a reserved
    scratch page that masked/inactive writes land on, so recycled pages
    are never touched by slots that don't own them). ``page_table``
    ([B, max_pages] int32) maps each sequence's logical page index to a
    pool page; ``seq_lens`` ([B] int32) is each sequence's valid
    length. All shapes are static, so prefill + decode compile into one
    scanned program exactly like StaticKVCache — but attention walks
    only ceil(len/page) pages per sequence (ops/pallas/
    paged_attention.py), and a host-side allocator can hand pages from
    completed sequences to newly admitted ones mid-flight
    (inference/continuous_batching.py). int8 mode stores pages as int8
    with per-(position, head) abs-max scales (``k_scale``/``v_scale``:
    [num_pages + 1, page_size, H]; quantization/quant.py quantize_kv),
    halving the dominant decode HBM category."""

    k_pages: Any
    v_pages: Any
    k_scale: Any  # None when pages are float
    v_scale: Any
    page_table: Any
    seq_lens: Any

    @property
    def page_size(self) -> int:
        return self.k_pages.shape[1]


@functools.lru_cache(maxsize=None)
def _sharded_zeros_fn(sharding):
    """One jitted zeros-under-out_shardings program per output sharding
    (shape/dtype static, so jax's jit cache dedups repeated layers): a
    mesh engine creates 2*num_layers identically-shaped pools per
    build/resurrection, which must not each pay their own trace. Every
    EXECUTION still returns a fresh buffer — callers donate the pools,
    so the executable is shared, never the arrays."""
    import jax
    return jax.jit(jnp.zeros, static_argnums=(0, 1),
                   out_shardings=sharding)


def paged_cache_create(batch: int, num_pages: int, page_size: int,
                       num_heads: int, head_dim: int, dtype,
                       max_pages_per_seq: int, quantized: bool = False,
                       page_table=None, seq_lens=None,
                       kv_sharding=None) -> PagedKVCache:
    """Zero-filled pool (+1 reserved scratch page) with an optional
    pre-assigned page table; the default table hands sequence ``i``
    pages ``[i*mp, (i+1)*mp)`` contiguously (the single-request
    generate() layout — the continuous-batching engine supplies its
    allocator-managed table instead).

    ``kv_sharding``: an optional NamedSharding for the KV pools (the
    mesh-sharded engine passes heads-over-``mp``). The pools are
    created DIRECTLY under it via jit out_shardings — a serving-scale
    pool is sized for the whole mesh's HBM, so materializing it
    replicated first and resharding after would OOM the very
    deployments the mesh exists for. Scale pools (one rank lower)
    derive their sharding by dropping the trailing head-dim axis."""
    kv_dtype = jnp.int8 if quantized else dtype
    shape = (num_pages + 1, page_size, num_heads, head_dim)
    if kv_sharding is None:
        zeros = jnp.zeros
        scale_zeros = jnp.zeros
    else:
        from jax.sharding import NamedSharding, PartitionSpec
        spec3 = PartitionSpec(*tuple(kv_sharding.spec)[:3])
        scale_sharding = NamedSharding(kv_sharding.mesh, spec3)
        zeros = _sharded_zeros_fn(kv_sharding)
        scale_zeros = _sharded_zeros_fn(scale_sharding)

    k_pages = zeros(shape, kv_dtype)
    v_pages = zeros(shape, kv_dtype)
    if quantized:
        k_scale = scale_zeros(shape[:3], jnp.float32)
        v_scale = scale_zeros(shape[:3], jnp.float32)
    else:
        k_scale = v_scale = None
    if page_table is None:
        page_table = jnp.arange(
            batch * max_pages_per_seq,
            dtype=jnp.int32).reshape(batch, max_pages_per_seq)
    if seq_lens is None:
        seq_lens = jnp.zeros((batch,), jnp.int32)
    return PagedKVCache(k_pages, v_pages, k_scale, v_scale,
                        page_table, seq_lens)


def paged_kv_append(cache: PagedKVCache, k, v, valid_len=None):
    """Write ``s`` new tokens per sequence at positions seq_lens ..
    seq_lens+s-1 through the page table (one scatter per pool — fixed
    shapes, jit/scan-safe) and advance the lengths.

    ``valid_len`` ([B] int32, optional): ragged prefill — only the
    first valid_len[i] of the s tokens are real; the rest (right
    padding) are redirected to the reserved scratch page and the
    length advances by valid_len, so padded prompts never pollute a
    sequence's pages."""
    b, s = k.shape[:2]
    page = cache.page_size
    mp = cache.page_table.shape[1]
    scratch = cache.k_pages.shape[0] - 1
    pos = cache.seq_lens[:, None] + jnp.arange(s, dtype=jnp.int32)[None]
    if valid_len is None:
        valid = None
        new_lens = cache.seq_lens + s
    else:
        valid = jnp.arange(s, dtype=jnp.int32)[None] < valid_len[:, None]
        new_lens = cache.seq_lens + valid_len.astype(jnp.int32)
    pidx = jnp.clip(pos // page, 0, mp - 1)
    off = pos % page
    pages = jnp.take_along_axis(cache.page_table, pidx, axis=1)
    # over-capacity positions (pos beyond the table's mp*page) go to
    # the scratch page instead of silently overwriting the last real
    # page; lengths clamp below so attention never reads past what was
    # actually stored. In-tree callers size pools so this never fires
    # (generate: total = prompt + max_new; engine: admission checks
    # capacity) — this bounds the public-API failure mode.
    overflow = pos >= mp * page
    pages = jnp.where(overflow, scratch, pages)
    off = jnp.where(overflow, 0, off)
    if valid is not None:
        pages = jnp.where(valid, pages, scratch)
        off = jnp.where(valid, off, 0)
    new_lens = jnp.minimum(new_lens, mp * page)

    def put(pool, scales, val):
        if scales is None:
            return pool.at[pages, off].set(val.astype(pool.dtype)), None
        from ..quantization.quant import quantize_kv
        qv, sc = quantize_kv(val)
        return (pool.at[pages, off].set(qv),
                scales.at[pages, off].set(sc))

    k_pages, k_scale = put(cache.k_pages, cache.k_scale, k)
    v_pages, v_scale = put(cache.v_pages, cache.v_scale, v)
    return PagedKVCache(k_pages, v_pages, k_scale, v_scale,
                        cache.page_table, new_lens)


def paged_page_splice(pools, page, k_blocks, v_blocks,
                      ks_blocks=None, vs_blocks=None):
    """Restore spilled prefix pages into the engine's per-layer pools
    (r15 hierarchical prefix cache): write layer i's KV blocks
    (``k_blocks``/``v_blocks`` [nl, n, page, H, D], plus
    [nl, n, page, H] scales for int8 pools) into every pool at the n
    page indices ``page`` ([n] int32 — or a scalar with unbatched
    [nl, page, ...] blocks). ``pools`` is the engine's ``{"k": [...],
    "v": [...], "ks": [...], "vs": [...]}`` per-layer dict; returns
    the same structure. jit-friendly with ``page`` traced — one
    compile per batch bucket serves every restore — and pure, so the
    engine donates the pools for an in-place scatter exactly like the
    decode step's appends (inference/continuous_batching.py
    ``_splice_page``).

    Blocks always arrive in the POOL's layout: the r23 blob codecs
    (serving/prefix_cache.py ``pack_page_blob``/``unpack_page_blob``)
    decode wire formats (raw/int8/int4, quantization/quant.py
    ``KV_QMAX_*`` scale math) back to pool dtype on the host before
    this splice runs, so spill format never leaks into the jitted
    program — one compile serves every blob format."""
    from ..ops.nn_functional import paged_page_splice as _splice_one

    def put(pool_list, blocks):
        return [_splice_one(pool, blocks[i], page)
                for i, pool in enumerate(pool_list)]

    return {
        "k": put(pools["k"], k_blocks),
        "v": put(pools["v"], v_blocks),
        "ks": (list(pools["ks"]) if ks_blocks is None
               else put(pools["ks"], ks_blocks)),
        "vs": (list(pools["vs"]) if vs_blocks is None
               else put(pools["vs"], vs_blocks)),
    }


def _remat_block(block, x):
    """Run ``block`` under jax.checkpoint as ONE taped op: the pure kernel
    takes (hidden, *param_values) so the eager tape differentiates through
    it (and recomputes block activations in backward instead of storing
    them), while under jit capture it reduces to a plain checkpointed call.
    Analog of the reference's RecomputeFunction PyLayer
    (distributed/fleet/utils/recompute.py:63)."""
    import jax

    from ..nn.layer import functional_call

    named = list(block.named_parameters())
    names = [n for n, _ in named]
    params = [p for _, p in named]

    def kernel(h, *pvals):
        from ..core.offload import name_block_input, remat_policy
        state = {"params": dict(zip(names, pvals)), "buffers": {}}
        return jax.checkpoint(
            lambda s, hh: functional_call(
                block, s, Tensor(name_block_input(hh))),
            policy=remat_policy())(state, h)

    return dispatch.call_fn(kernel, "remat_block", True, (x, *params), {})


class GPTAttention(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_heads
        self.head_dim = c.head_dim
        self.seq_mode = c.seq_parallel_mode
        init = Normal(std=c.initializer_range)
        self.qkv_proj = ColumnParallelLinear(
            c.hidden_size, 3 * c.hidden_size, gather_output=False)
        self.out_proj = RowParallelLinear(
            c.hidden_size, c.hidden_size, input_is_parallel=True)
        self.attn_dropout_p = c.attn_dropout
        self.use_flash = c.use_flash_attention

    def forward(self, x, cache=None, use_cache=False, prefill_len=None,
                prefill_chained=False):
        b, s, h = x.shape
        qkv = self.qkv_proj(x)  # [b, s, 3h] sharded over mp on last dim
        qkv = F["reshape"](qkv, (b, s, 3, self.num_heads, self.head_dim))
        q = qkv[:, :, 0]
        k = qkv[:, :, 1]
        v = qkv[:, :, 2]
        new_cache = None
        if use_cache and isinstance(cache, PagedKVCache):
            # Ragged paged decode path: append through the page table,
            # attend over only the pages each sequence owns.
            return self._decode_paged(q, k, v, cache, b, s, prefill_len,
                                      prefill_chained)
        if use_cache and isinstance(cache, StaticKVCache):
            # Fixed-shape decode path (scan/jit-able): write the new k/v
            # at pos into the preallocated buffers and attend over the
            # whole buffer with a validity mask.
            return self._decode_static(q, k, v, cache, b, s)
        if use_cache:
            if cache is not None:
                k = F["concat"]([cache[0], k], axis=1)
                v = F["concat"]([cache[1], v], axis=1)
            new_cache = (k, v)
        if self.seq_mode in ("ring", "ulysses", "zigzag") and \
                not use_cache:
            from ..distributed.sp import sequence_parallel_attention
            out = dispatch.call_fn(
                lambda qq, kk, vv: sequence_parallel_attention(
                    qq, kk, vv, mode=self.seq_mode, causal=True),
                "seq_parallel_attention", True, (q, k, v), {})
        else:
            # explicit both ways (the flag was silently ignored before
            # r4 — every earlier benched config actually ran flash):
            # True forces the flash kernel, False forces XLA attention
            out = F["scaled_dot_product_attention"](
                q, k, v, is_causal=True, dropout_p=self.attn_dropout_p,
                training=self.training, use_flash=bool(self.use_flash))
        # selective remat (config.remat_save_attention) is tagged at
        # the flash kernel's vjp residuals (out AND lse — see
        # pallas/flash_attention._flash_lse_vjp_fwd), not here: saving
        # out alone would still recompute the flash forward for lse
        out = F["reshape"](out, (b, s, self.num_heads * self.head_dim))
        out = self.out_proj(out)
        if use_cache:
            return out, new_cache
        return out

    def _decode_static(self, q, k, v, cache, b, s):
        """Single/multi-token decode against a preallocated KV buffer:
        k/v written at cache.pos via dynamic_update_slice, attention over
        the full buffer masked to positions < pos + s. Fixed shapes
        throughout — the building block of the jitted generate loop."""
        import jax

        def upd(buf, val, p):
            return jax.lax.dynamic_update_slice(
                buf, val.astype(buf.dtype), (0, p, 0, 0))

        k_buf = dispatch.call_fn(upd, "kv_cache_update", True,
                                 (cache.k, k, cache.pos), {})
        v_buf = dispatch.call_fn(upd, "kv_cache_update", True,
                                 (cache.v, v, cache.pos), {})
        total = k_buf.shape[1]

        def attend(qq, kk, vv, p):
            # causal over absolute positions: query i sits at p + i;
            # shared sdpa does the fp32-softmax attention under the mask
            kpos = jnp.arange(total)[None, None, None, :]
            qpos = p + jnp.arange(qq.shape[1])[None, None, :, None]
            from .. import ops
            return ops.nn_functional.scaled_dot_product_attention(
                qq, kk, vv, attn_mask=kpos <= qpos, use_flash=False)

        out = dispatch.call_fn(attend, "kv_cache_attention", True,
                               (q, k_buf, v_buf, cache.pos), {})
        out = F["reshape"](out, (b, s, self.num_heads * self.head_dim))
        out = self.out_proj(out)
        return out, StaticKVCache(k_buf, v_buf, cache.pos + s)

    def _decode_paged(self, q, k, v, cache, b, s, prefill_len=None,
                      prefill_chained=False):
        """Paged decode/prefill: k/v append through the page table
        (ragged right-padding redirected to the scratch page), then

        - s == 1 (decode): the ragged paged-attention op — the Pallas
          page-walk kernel on TPU, its dense-gather reference on the
          CPU fast lane (ops/pallas/paged_attention.py);
        - s > 1 with ``prefill_len`` (scheduler/generate prefill, which
          guarantees a FRESH slot — seq_lens == 0 before the chunk):
          dense causal attention over THIS chunk's k/v only. Causal +
          right padding means valid tokens attend exactly their own
          prefix; padded tokens' outputs are discarded by the caller
          and their KV never reaches a real page.
        - s > 1 with ``prefill_len`` AND ``prefill_chained`` (the
          prefix-cache suffix prefill, serving/prefix_cache.py, AND
          every non-first chunk of the engine's chunked prefill,
          inference/continuous_batching.py ``prefill_chunk_tokens``):
          the slot STARTS at seq_lens > 0 — page-table entries below
          that length hold already-populated KV, whether shared prefix
          pages or this request's own prior chunks (the same "already
          stored" case) — so the ragged right-padded chunk is appended
          via valid_len and attends the stored prefix PLUS itself
          through the reference paged attention with q_offsets = old
          seq_lens. Right-padded query rows produce garbage that the
          caller discards; their KV lands on the scratch page, never
          on a shared page.
        - s > 1 without ``prefill_len`` (public forward() continuation
          against a possibly NON-empty cache): the reference paged
          attention with per-sequence q_offsets — it attends the full
          stored prefix plus the chunk, so multi-chunk appends are
          correct instead of silently chunk-local.

        Prefill attends the un-quantized k/v even in int8 mode (exact,
        and free — the dense path already has them in registers);
        decode reads back the quantized pages, which is the lossy step
        the int8 parity tests bound. The chained prefill reads the
        prefix back from pages, so in int8 mode its prefix keys are
        the quantized ones — the same values decode would have read."""
        old_lens = cache.seq_lens
        if prefill_len is None:
            new_cache = dispatch.call_fn(
                lambda c, kk, vv: tuple(paged_kv_append(c, kk, vv)),
                "paged_kv_append", True, (cache, k, v), {})
        else:
            new_cache = dispatch.call_fn(
                lambda c, kk, vv, pl_: tuple(paged_kv_append(
                    c, kk, vv, valid_len=pl_)),
                "paged_kv_append", True, (cache, k, v, prefill_len), {})
        new_cache = PagedKVCache(*new_cache)
        # fused epilogue (r13): under an active fused_decode() trace,
        # the paged-attention branches fold softmax-normalize +
        # head-concat + out-projection into ONE op and return the
        # attention block's output directly — same math, one launch
        # (the dense fresh-prefill branch keeps its exact pre-r13
        # program; it is not the decode hot path)
        fw = (self._fused_epilogue_params() if fused_decode_active()
              else None)
        if s == 1:
            if fw is not None:
                out = F["paged_attention_fused"](
                    q, new_cache.k_pages, new_cache.v_pages,
                    new_cache.page_table, new_cache.seq_lens,
                    fw[0], fw[1], k_scale=new_cache.k_scale,
                    v_scale=new_cache.v_scale)
                return out, new_cache
            out = F["paged_attention"](
                q, new_cache.k_pages, new_cache.v_pages,
                new_cache.page_table, new_cache.seq_lens,
                k_scale=new_cache.k_scale, v_scale=new_cache.v_scale)
        elif prefill_len is not None and not prefill_chained:
            out = F["scaled_dot_product_attention"](
                q, k, v, is_causal=True, dropout_p=0.0,
                training=False, use_flash=bool(self.use_flash))
        else:
            if fw is not None:
                out = F["paged_attention_fused"](
                    q, new_cache.k_pages, new_cache.v_pages,
                    new_cache.page_table, new_cache.seq_lens,
                    fw[0], fw[1], k_scale=new_cache.k_scale,
                    v_scale=new_cache.v_scale, q_offsets=old_lens)
                return out, new_cache
            out = F["paged_attention"](
                q, new_cache.k_pages, new_cache.v_pages,
                new_cache.page_table, new_cache.seq_lens,
                k_scale=new_cache.k_scale, v_scale=new_cache.v_scale,
                q_offsets=old_lens)
        out = F["reshape"](out, (b, s, self.num_heads * self.head_dim))
        out = self.out_proj(out)
        return out, new_cache

    def _fused_epilogue_params(self):
        """(weight, bias) of a FUSABLE out-projection, else None: the
        epilogue folds only a plain fp matmul head ([E, E] weight, the
        RowParallelLinear layout). A converted projection (e.g.
        quantization's WeightOnlyInt8Linear, whose weight lives in
        int8 buffers with an output-scale epilogue of its own) keeps
        the unfused composition — correctness over fusion."""
        import jax.numpy as _jnp
        w = getattr(self.out_proj, "weight", None)
        if w is None:
            return None
        wv = w.value if isinstance(w, Tensor) else w
        if wv is None or not _jnp.issubdtype(wv.dtype, _jnp.floating):
            return None
        if wv.shape[0] != self.num_heads * self.head_dim:
            return None
        return w, getattr(self.out_proj, "bias", None)


class GPTMLP(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        c = config
        inner = c.ffn_hidden_mult * c.hidden_size
        self.fc_in = ColumnParallelLinear(c.hidden_size, inner,
                                          gather_output=False)
        self.fc_out = RowParallelLinear(inner, c.hidden_size,
                                        input_is_parallel=True)

    def forward(self, x):
        return self.fc_out(F["gelu"](self.fc_in(x), True))


class GPTBlock(Layer):
    """Pre-norm transformer block; uniform across the stack so pipeline
    stages can scan a stacked params pytree."""

    def __init__(self, config: GPTConfig, layer_idx: int = 0):
        super().__init__()
        self.ln_1 = LayerNorm(config.hidden_size,
                              epsilon=config.layer_norm_epsilon)
        self.attn = GPTAttention(config)
        self.ln_2 = LayerNorm(config.hidden_size,
                              epsilon=config.layer_norm_epsilon)
        if (config.moe_experts > 0 and
                layer_idx % config.moe_every == config.moe_every - 1):
            from ..distributed.moe import MoELayer
            self.mlp = MoELayer(
                config.hidden_size,
                config.ffn_hidden_mult * config.hidden_size,
                num_experts=config.moe_experts, top_k=config.moe_top_k)
        else:
            self.mlp = GPTMLP(config)
        self.dropout = Dropout(config.dropout)

    def forward(self, x, cache=None, use_cache=False, prefill_len=None,
                prefill_chained=False):
        if use_cache:
            a, new_cache = self.attn(self.ln_1(x), cache, use_cache=True,
                                     prefill_len=prefill_len,
                                     prefill_chained=prefill_chained)
            x = x + self.dropout(a)
            x = x + self.dropout(self.mlp(self.ln_2(x)))
            return x, new_cache
        x = x + self.dropout(self.attn(self.ln_1(x)))
        x = x + self.dropout(self.mlp(self.ln_2(x)))
        return x


class GPTModel(Layer):
    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        c = config
        # Selective remat is scoped to THIS model's forward trace
        # (override_remat_saved_names around forward): a model that
        # never opted in neither clears nor inherits another model's
        # selection, and a direct set_remat_saved_names() call stays in
        # force for models built with remat_save_attention=False.
        from ..core.offload import ATTN_OUT_NAME
        self._remat_names = ((ATTN_OUT_NAME,) if c.remat_save_attention
                             else None)
        init = Normal(std=c.initializer_range)
        self.wte = VocabParallelEmbedding(c.vocab_size, c.hidden_size)
        self.wpe = Embedding(c.max_seq_len, c.hidden_size)
        self.wpe.weight.pspec = P()
        self.drop = Dropout(c.dropout)
        self.h = LayerList([GPTBlock(c, i)
                            for i in range(c.num_layers)])
        self.ln_f = LayerNorm(c.hidden_size, epsilon=c.layer_norm_epsilon)

    def forward(self, input_ids, position_ids=None, caches=None,
                use_cache=False, prefill_lens=None,
                prefill_chained=False):
        if self._remat_names is not None:
            from ..core.offload import override_remat_saved_names
            with override_remat_saved_names(self._remat_names):
                return self._forward(input_ids, position_ids, caches,
                                     use_cache, prefill_lens,
                                     prefill_chained)
        return self._forward(input_ids, position_ids, caches, use_cache,
                             prefill_lens, prefill_chained)

    def _forward(self, input_ids, position_ids=None, caches=None,
                 use_cache=False, prefill_lens=None,
                 prefill_chained=False):
        use_cache = use_cache or caches is not None
        b, s = input_ids.shape
        if position_ids is None:
            position_ids = F["arange"](s, dtype="int32")
            offset = 0
            if caches is not None and caches[0] is not None:
                c0 = caches[0]
                if isinstance(c0, StaticKVCache):
                    offset = c0.pos
                elif isinstance(c0, PagedKVCache):
                    # ragged: each sequence continues from ITS length
                    lens = c0.seq_lens
                    offset = F["unsqueeze"](
                        lens if isinstance(lens, Tensor) else Tensor(lens),
                        1)
                else:
                    offset = c0[0].shape[1]
                position_ids = position_ids + offset
            if len(position_ids.shape) == 1:
                position_ids = F["expand"](
                    F["unsqueeze"](position_ids, 0), (b, s))
        x = self.wte(input_ids) + self.wpe(position_ids)
        # shard activations: batch over dp(+sharding), seq over sep
        x = _constrain(x, ("dp", "sharding"), "sep", None)
        x = self.drop(x)
        zig = (self.config.seq_parallel_mode == "zigzag" and
               not use_cache and self._sep_degree() > 1)
        if zig:
            x = self._zigzag(x, s)
        if caches is None and use_cache:
            caches = [None] * len(self.h)
        new_caches = [] if use_cache else None
        for i, block in enumerate(self.h):
            if use_cache:
                x, nc = block(x, caches[i], use_cache=True,
                              prefill_len=prefill_lens,
                              prefill_chained=prefill_chained)
                new_caches.append(nc)
            elif self.config.remat and not hasattr(block.mlp, "aux_loss") \
                    and i % self.config.remat_every == 0:
                x = _remat_block(block, x)
            else:
                x = block(x)
        x = self.ln_f(x)
        if zig:
            x = self._zigzag(x, s, inverse=True)
        if use_cache:
            return x, new_caches
        return x

    def _sep_degree(self) -> int:
        from ..distributed.topology import get_hybrid_communicate_group
        hcg = get_hybrid_communicate_group()
        return dict(hcg.mesh.shape).get("sep", 1) if hcg is not None else 1

    def _zigzag(self, x, s, inverse=False):
        """One boundary re-layout puts the WHOLE block stack in the
        zigzag sequence layout (every non-attention op is positionwise;
        attention runs the balanced zigzag ring); the inverse after the
        final norm restores the public order, so the LM loss shift is
        untouched. Chunk-level split+concat (not a gather — shard-
        aligned slices lower to collective-permutes under GSPMD; a
        sharded-S gather trips the TPU SPMD partitioner), two per step
        instead of per-layer re-layouts."""
        from ..distributed.sp import zigzag_reorder
        n = self._sep_degree()
        x = dispatch.call_fn(
            lambda h: zigzag_reorder(h, n, axis=1, inverse=inverse),
            "zigzag_permute", True, (x,), {})
        return _constrain(x, ("dp", "sharding"), "sep", None)


class GPTForCausalLM(Layer):
    """GPT with a (vocab-sharded) LM head + parallel CE loss."""

    def __init__(self, config: GPTConfig):
        super().__init__()
        self.config = config
        self.gpt = GPTModel(config)
        if config.tie_word_embeddings:
            self.lm_head = None
        else:
            self.lm_head = ColumnParallelLinear(
                config.hidden_size, config.vocab_size, has_bias=False,
                gather_output=False)
        self.loss_fn = ParallelCrossEntropy()

    def logits(self, hidden):
        if self.lm_head is not None:
            return self.lm_head(hidden)
        return F["matmul"](hidden, self.gpt.wte.weight, transpose_y=True)

    def head_params(self):
        """``(weight, transpose_y, bias)`` of the lm_head for the fused
        streaming sampler (nn/decode.py ``fused_sample_token``), or
        None when the head is not a plain fp matmul (e.g. an
        int8-converted lm_head) — callers then fall back to
        :meth:`logits`. Tied embeddings expose the [V, D] wte weight
        with ``transpose_y=True``, exactly the :meth:`logits` math."""
        import jax.numpy as _jnp
        if self.lm_head is None:
            return self.gpt.wte.weight, True, None
        w = getattr(self.lm_head, "weight", None)
        if w is None:
            return None
        wv = w.value if isinstance(w, Tensor) else w
        if wv is None or not _jnp.issubdtype(wv.dtype, _jnp.floating):
            return None
        return w, False, getattr(self.lm_head, "bias", None)

    def cache_layout(self):
        """What the serving engine builds its pools from
        (models/cache_layout.py): every layer alike, one KV head a
        query head, every position kept."""
        from .cache_layout import LayerCache
        c = self.config
        return [LayerCache(c.num_heads, c.head_dim, None,
                           self.gpt.wte.weight.dtype)] * c.num_layers

    def decode_hidden(self, input_ids, caches, prefill_lens=None,
                      prefill_chained=False):
        """Cached forward returning FINAL HIDDEN STATES instead of
        logits — the fused decode hot path's model entry: callers
        sample straight from the hidden row via the streaming lm_head
        (``fused_sample_token``), so the [B, S, vocab] logits tensor
        never materializes. Returns ``(hidden [B, S, D],
        new_caches)``."""
        return self.gpt(input_ids, None, caches,
                        prefill_lens=prefill_lens,
                        prefill_chained=prefill_chained)

    def _chunked_lm_loss(self, hidden, labels, chunk):
        """Mean next-token CE without materializing full logits: scan over
        sequence chunks; each chunk's logits+CE run under jax.checkpoint,
        so backward recomputes the chunk logits instead of storing them.
        Dispatched as ONE taped op over (hidden, labels, head params) so
        eager backward differentiates through it."""
        import jax

        from ..autograd.engine import no_grad
        from ..nn.layer import bind_state

        head = self.lm_head
        if head is not None:
            hp = list(head.named_parameters())
            names = [n for n, _ in hp]
            params = [p for _, p in hp]
        else:
            names = None
            params = [self.gpt.wte.weight]

        def kernel(hid, lab, *pvals):
            lab = lab[:, 1:].astype(jnp.int32)
            hid = hid[:, :-1]
            b, s, d = hid.shape
            pad = (-s) % chunk
            if pad:
                hid = jnp.pad(hid, ((0, 0), (0, pad), (0, 0)))
                lab = jnp.pad(lab, ((0, 0), (0, pad)),
                              constant_values=-100)  # ignore_index
            nc = hid.shape[1] // chunk
            hid = hid.reshape(b, nc, chunk, d).swapaxes(0, 1)  # [nc,B,C,D]
            lab = lab.reshape(b, nc, chunk).swapaxes(0, 1)

            def apply_head(h):
                if head is None:
                    return h @ pvals[0].T
                with bind_state(head, {"params": dict(zip(names, pvals)),
                                       "buffers": {}}):
                    out = head(Tensor(h))
                return out.value if isinstance(out, Tensor) else out

            @jax.checkpoint
            def chunk_fn(h, l):  # noqa: E741
                per = self.loss_fn(Tensor(apply_head(h)), Tensor(l))
                per = per.value if isinstance(per, Tensor) else per
                # zero the scan-padding slots; user ignore_index positions
                # are already zeroed by the loss (and, like the full-logits
                # F["mean"] path, still count in the denominator)
                return jnp.where(l != -100, per, 0.0).sum()

            def body(tot, inp):
                return tot + chunk_fn(*inp), None

            with no_grad():
                tot, _ = jax.lax.scan(
                    body, jnp.asarray(0.0, jnp.float32), (hid, lab))
            return tot / (b * s)

        return dispatch.call_fn(kernel, "chunked_lm_loss", True,
                                (hidden, labels, *params), {})

    def forward(self, input_ids, labels=None, position_ids=None,
                caches=None, prefill_lens=None, prefill_chained=False):
        if caches is not None:
            hidden, new_caches = self.gpt(input_ids, position_ids, caches,
                                          prefill_lens=prefill_lens,
                                          prefill_chained=prefill_chained)
            return self.logits(hidden), new_caches
        hidden = self.gpt(input_ids, position_ids)
        if labels is None:
            return self.logits(hidden)
        # next-token LM loss
        if self.config.loss_chunk_size:
            loss = self._chunked_lm_loss(hidden, labels,
                                         self.config.loss_chunk_size)
        else:
            logits = self.logits(hidden)
            shift_logits = logits[:, :-1]
            shift_labels = labels[:, 1:]
            loss = F["mean"](self.loss_fn(shift_logits, shift_labels))
        # MoE load-balancing aux losses, if any blocks are MoE
        for block in self.gpt.h:
            aux = getattr(block.mlp, "aux_loss", None)
            if aux is not None:
                a = block.mlp.aux_loss()
                if a is not None:
                    loss = loss + a
        return loss

    def verify_step(self, input_ids, caches, valid_len):
        """Speculative-decoding verify forward over paged slots.

        ``input_ids``: [B, s] = ``[cur, d_0, .., d_{s-2}]`` per
        sequence — the pending token plus ``s-1`` draft tokens.
        ``caches``: per-layer PagedKVCache whose ``seq_lens`` are the
        PRE-verify lengths. ``valid_len``: [B] int32, how many of the
        ``s`` tokens are real for each sequence (ragged draft windows;
        0 parks an inactive slot — its writes land on the reserved
        scratch page).

        One forward scores ALL ``s`` positions: the chunk is appended
        through ``paged_kv_append`` (valid_len redirects the ragged
        tail to the scratch page, so rejected-draft KV never lands
        outside the sequence's own pages) and attends the stored
        prefix plus itself through the chained-prefill paged-attention
        path (``q_offsets`` = old seq_lens). Position ``j``'s logits
        are therefore exactly the vanilla decode logits after
        ``cur, d_0..d_{j-1}`` — the bit-identical greedy contract the
        speculative engine pins. Returns ``(logits [B, s, V],
        new_caches)``; the caller keeps host-side lengths and rolls
        back past the longest accepted prefix (rejected positions are
        simply never attended and are overwritten by the next
        append)."""
        return self.forward(input_ids, caches=caches,
                            prefill_lens=valid_len,
                            prefill_chained=True)

    def generate(self, input_ids, max_new_tokens: int = 20,
                 temperature: float = 1.0, top_k: Optional[int] = None,
                 key=None, use_jit: bool = False,
                 kv_cache: str = "static", page_size: int = 64,
                 compile_mode: str = "whole"):
        """Greedy/top-k sampling with kv cache. ``use_jit`` compiles the
        WHOLE generation (prefill + lax.scan decode over a StaticKVCache)
        into one device launch — the serving hot path; the eager loop
        stays as the debuggable reference.

        ``kv_cache``: "static" (dense preallocated buffers), "paged"
        (block-paged pool + page table — the ragged decode path,
        identical greedy tokens, pinned in tests/test_paged_attention),
        or "paged_int8" (int8 KV pages, half the streamed KV bytes).
        Paged modes require ``use_jit``. ``compile_mode``: "whole" (one
        program) or "chunked" — compile ONE per-block decode function
        (the uniform blocks share it) plus small embed/head programs,
        for a model whose whole-generate program is too large to
        compile in one piece; slower to launch, but every component
        program is ~num_layers x smaller."""
        import jax
        from ..core.rng import next_key
        from ..tensor import Tensor

        if kv_cache not in ("static", "paged", "paged_int8"):
            raise ValueError(f"unknown kv_cache mode {kv_cache!r}")
        if compile_mode not in ("whole", "chunked"):
            raise ValueError(f"unknown compile_mode {compile_mode!r}")
        if kv_cache != "static" and not use_jit:
            raise ValueError("paged kv_cache requires use_jit=True")
        if compile_mode == "chunked" and not use_jit:
            raise ValueError("compile_mode='chunked' requires "
                             "use_jit=True (it IS a compile strategy)")
        if kv_cache != "static" and compile_mode == "chunked":
            raise ValueError(
                "compile_mode='chunked' decodes over the dense "
                "StaticKVCache only (its per-block programs exist to "
                "shrink compiles, not to change the cache layout)")
        if use_jit and compile_mode == "chunked" and max_new_tokens > 0:
            return self._generate_chunked(input_ids, max_new_tokens,
                                          temperature, top_k, key)
        if use_jit and max_new_tokens > 0:
            return self._generate_jit(input_ids, max_new_tokens,
                                      temperature, top_k, key,
                                      kv_cache=kv_cache,
                                      page_size=page_size)
        if max_new_tokens <= 0:
            return input_ids
        self.eval()
        # the eager loop samples through the ONE shared sampler
        # (nn/decode.py sample_token — r13 consolidation: the same
        # call the jitted scan, the chunked generate and the serving
        # engine make; previously these four lines lived here inline
        # with their own key-split order)
        from ..nn.decode import sample_token
        caches = [None] * self.config.num_layers
        ids = input_ids
        logits, caches = self.forward(ids, caches=caches)
        out_ids = [ids]
        cur = logits[:, -1]
        key_raw = key.value if isinstance(key, Tensor) else key
        if temperature != 0.0 and key_raw is None:
            key_raw = next_key()
        for _ in range(max_new_tokens):
            tok, key_raw = sample_token(
                cur.value if isinstance(cur, Tensor) else cur,
                float(temperature), top_k, key_raw)
            nxt = Tensor(tok[:, None].astype(jnp.int32))
            out_ids.append(nxt)
            logits, caches = self.forward(nxt, caches=caches)
            cur = logits[:, -1]
        return F["concat"](out_ids, axis=1)

    def _generate_jit(self, input_ids, max_new_tokens, temperature, top_k,
                      key, kv_cache: str = "static", page_size: int = 64):
        """One-launch generation: prefill writes the prompt's KV into
        preallocated buffers (dense or block-paged), then lax.scan runs
        fixed-shape decode steps (TPU-native replacement for the
        reference inference engine's decoder loop — no Python between
        tokens)."""
        import jax

        from ..autograd.engine import no_grad
        from ..core.rng import next_key
        from ..nn.layer import bind_state, functional_state

        self.eval()
        ids_raw = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        b, s = ids_raw.shape
        total = s + max_new_tokens
        cfg = self.config
        nh, hd, nl = cfg.num_heads, cfg.head_dim, cfg.num_layers
        state = functional_state(self)
        dt = state["params"]["gpt.wte.weight"].dtype
        key_raw = key.value if isinstance(key, Tensor) else key
        if key_raw is None:
            key_raw = next_key()
        temp, tk = float(temperature), top_k
        pages_per_seq = -(-total // page_size)

        def raw(t):
            return t.value if isinstance(t, Tensor) else t

        def raw_cache(c):
            if isinstance(c, StaticKVCache):
                return StaticKVCache(raw(c.k), raw(c.v), raw(c.pos))
            return PagedKVCache(*[None if f is None else raw(f)
                                  for f in c])

        def make_caches():
            if kv_cache == "static":
                return [StaticKVCache(jnp.zeros((b, total, nh, hd), dt),
                                      jnp.zeros((b, total, nh, hd), dt),
                                      jnp.asarray(0, jnp.int32))
                        for _ in range(nl)]
            return [paged_cache_create(
                b, b * pages_per_seq, page_size, nh, hd, dt,
                pages_per_seq, quantized=(kv_cache == "paged_int8"))
                for _ in range(nl)]

        # fused decode hot path (r13): when the lm_head is a plain fp
        # matmul, every step samples STRAIGHT from the final hidden row
        # through the streaming lm_head (nn/decode.py
        # fused_sample_token — greedy tokens bit-identical to
        # argmax(logits) by the first-index tie rule), and paged traces
        # additionally fold the attention epilogue (fused_decode()).
        # A non-fusable head (e.g. int8-converted lm_head) keeps the
        # exact pre-r13 logits path.
        use_fused = self.head_params() is not None

        def fwd_tok(params, ids, caches, k):
            # paged prefill chunks (s > 1) pass an explicit full-length
            # prefill_lens: generate() always starts from a FRESH pool,
            # so the chunk-local dense fast path applies (forward()
            # without it assumes a possibly non-empty cache and takes
            # the general full-prefix path)
            plens = None
            if kv_cache != "static" and ids.shape[1] > 1:
                plens = jnp.full((ids.shape[0],), ids.shape[1],
                                 jnp.int32)
            with bind_state(self, {"params": params, "buffers": {}}), \
                    no_grad():
                if use_fused:
                    from ..nn.decode import fused_sample_token
                    hidden, nc = self.decode_hidden(Tensor(ids), caches,
                                                    prefill_lens=plens)
                    w, ty, bias = self.head_params()
                    nxt, k = fused_sample_token(
                        raw(hidden)[:, -1], raw(w), temp, tk, k,
                        transpose_y=ty,
                        bias=None if bias is None else raw(bias))
                else:
                    from ..nn.decode import sample_token
                    logits, nc = self.forward(Tensor(ids), caches=caches,
                                              prefill_lens=plens)
                    nxt, k = sample_token(raw(logits)[:, -1], temp, tk, k)
            return nxt, [raw_cache(c) for c in nc], k

        def run(params, ids, k):
            # single-device program: hybrid-mesh activation constraints
            # must not leak into this trace. With a fleet group live in
            # the process they hand the GSPMD partitioner mp/dp
            # annotations with no in_shardings to anchor them, and it
            # has been observed to insert an all-reduce over mp on the
            # REPLICATED token output — emitted ids came back exactly
            # mp-times too large while the scan carry stayed correct.
            from ..distributed.mp_layers import no_sharding_constraints
            fuse_attn = (fused_decode() if use_fused and
                         kv_cache != "static"
                         else contextlib.nullcontext())
            with no_sharding_constraints(), fuse_attn:
                caches = make_caches()
                nxt, caches, k = fwd_tok(params, ids, caches, k)

                def body(carry, _):
                    cur, cs, kk = carry
                    nxt2, cs, kk = fwd_tok(params, cur[:, None], cs, kk)
                    return (nxt2, cs, kk), cur

                (last, _, _), toks = jax.lax.scan(
                    body, (nxt, caches, k), None,
                    length=max_new_tokens - 1)
                # toks: [N-1, B] tokens fed at each step; `last` is
                # token N
                all_new = jnp.concatenate(
                    [toks, last[None]], axis=0).swapaxes(0, 1)  # [B, N]
                return jnp.concatenate([ids, all_new], axis=1)

        sig = (b, s, max_new_tokens, temp, tk, kv_cache, page_size)
        cache = getattr(self, "_gen_jit_cache", None)
        if cache is None:
            cache = self._gen_jit_cache = {}
        if sig not in cache:
            cache[sig] = jax.jit(run)
        out = cache[sig](state["params"], ids_raw, key_raw)
        return Tensor(out)

    def _generate_chunked(self, input_ids, max_new_tokens, temperature,
                          top_k, key):
        """Chunked-compile generation: instead of one whole-program
        compile (prefill + scanned decode), compile THREE small
        programs: embed, ONE per-block step (the uniform blocks share
        the compiled function — per-layer params are just different
        arguments), and the LM head. Each program is ~num_layers x
        smaller than the monolith; compiles are wrapped in a transient-
        error RetryPolicy (distributed/resilience.py). The price is a
        Python-level launch per layer per token — this path exists to
        get past a compile limit, not to win the latency race (whether
        any compile on this runtime needs it is ROADMAP S4/D3's
        question). Greedy/top-k token stream matches use_jit=True
        bit-for-bit at temperature 0 (tested)."""
        import jax

        from ..autograd.engine import no_grad
        from ..core.rng import next_key
        from ..distributed.resilience import RetryPolicy
        from ..nn.layer import bind_state, functional_state

        self.eval()
        cfg = self.config
        if cfg.moe_experts > 0:
            raise ValueError("chunked compile supports dense blocks only")
        ids_raw = input_ids.value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        b, s = ids_raw.shape
        total = s + max_new_tokens
        nh, hd, nl = cfg.num_heads, cfg.head_dim, cfg.num_layers
        state = functional_state(self)
        dt = state["params"]["gpt.wte.weight"].dtype
        temp, tk = float(temperature), top_k
        key_raw = key.value if isinstance(key, Tensor) else key
        if key_raw is None:
            key_raw = next_key()
        # transport errors only: deterministic compile failures (JAX
        # RuntimeErrors — including the reproducible 1.3B broken-pipe
        # this path works around by SHRINKING programs) propagate
        # immediately instead of burning 3 multi-minute attempts
        retry = RetryPolicy(max_attempts=3, base_delay_s=0.5,
                            retry_on=(ConnectionError, OSError))

        def raw(t):
            return t.value if isinstance(t, Tensor) else t

        blk0 = self.gpt.h[0]
        # params AND buffers: converted layers (WeightOnlyInt8Linear)
        # carry their quantized weights as buffers — binding params
        # alone would run every layer on blk0's closed-over buffers
        pnames = [n for n, _ in blk0.named_parameters()]
        bnames = [n for n, _ in blk0.named_buffers()]
        n_p = len(pnames)

        def layer_vals(blk):
            ps = dict(blk.named_parameters())
            bs = dict(blk.named_buffers())
            return ([raw(ps[n]) for n in pnames] +
                    [None if bs[n] is None else raw(bs[n])
                     for n in bnames])

        layer_params = [layer_vals(blk) for blk in self.gpt.h]

        # jit objects are cached on the model (state/params flow in as
        # ARGUMENTS): repeated calls — e.g. bench timing windows — hit
        # the per-shape compile cache instead of rebuilding the jits
        # and recompiling every window through the very transport this
        # path exists to spare
        cache = getattr(self, "_chunked_jit_cache", None)
        if cache is None:
            cache = self._chunked_jit_cache = {}
        # the parameter-name tuple keys STRUCTURE: an in-place layer
        # swap (e.g. convert_to_weight_only_int8) changes the names,
        # so the cached closure over the old structure is not reused
        # against new-layout params (the r5 stale-pack-cache lesson)
        sig = (temp, tk, tuple(pnames), tuple(bnames))
        if sig not in cache:
            # same single-device-trace guard as _generate_jit: a live
            # fleet group's activation constraints must not reach these
            # per-block programs
            from ..distributed.mp_layers import no_sharding_constraints

            def embed_fn(st, ids, pos0):
                with bind_state(self, st), no_grad(), \
                        no_sharding_constraints():
                    pos = pos0 + jnp.arange(ids.shape[1],
                                            dtype=jnp.int32)[None]
                    pos = jnp.broadcast_to(pos, ids.shape)
                    x = self.gpt.wte(Tensor(ids)) + \
                        self.gpt.wpe(Tensor(pos))
                return raw(x)

            def block_fn(x, k_buf, v_buf, pos, *vals):
                st = {"params": dict(zip(pnames, vals[:n_p])),
                      "buffers": dict(zip(bnames, vals[n_p:]))}
                with bind_state(blk0, st), no_grad(), \
                        no_sharding_constraints():
                    out, nc = blk0(Tensor(x),
                                   StaticKVCache(k_buf, v_buf, pos),
                                   use_cache=True)
                return raw(out), raw(nc.k), raw(nc.v)

            def head_fn(st, x):
                with bind_state(self, st), no_grad(), \
                        no_sharding_constraints():
                    lg = self.logits(self.gpt.ln_f(Tensor(x)))
                return raw(lg)[:, -1]

            def sample_fn(last, k):
                from ..nn.decode import sample_token
                return sample_token(last, temp, tk, k)

            cache[sig] = tuple(
                jax.jit(f) for f in (embed_fn, block_fn, head_fn,
                                     sample_fn))
        embed_j, block_j, head_j, sample_j = cache[sig]
        kvs = [(jnp.zeros((b, total, nh, hd), dt),
                jnp.zeros((b, total, nh, hd), dt)) for _ in range(nl)]

        def run_stack(ids, pos):
            x = retry.call(embed_j, state, ids, pos,
                           site="jit.compile.embed")
            for i in range(nl):
                x, kb, vb = retry.call(
                    block_j, x, kvs[i][0], kvs[i][1], pos,
                    *layer_params[i], site="jit.compile.block")
                kvs[i] = (kb, vb)
            return retry.call(head_j, state, x, site="jit.compile.head")

        pos = jnp.asarray(0, jnp.int32)
        last = run_stack(ids_raw, pos)
        pos = pos + s
        nxt, key_raw = sample_j(last, key_raw)
        out = [ids_raw, nxt[:, None]]
        for _ in range(max_new_tokens - 1):
            last = run_stack(nxt[:, None], pos)
            pos = pos + 1
            nxt, key_raw = sample_j(last, key_raw)
            out.append(nxt[:, None])
        return Tensor(jnp.concatenate(out, axis=1))


# -- checkpoint-state helpers (r24 weight hot-swap) -------------------------

def checkpoint_state(model: Layer) -> dict:
    """The model's full weight tree as plain host numpy arrays keyed by
    structured name — the form ``ResilientCheckpointManager`` saves and
    a swap/restore applies back through ``set_state_dict``. Buffers are
    included (converted layers hold int8 weights there), so a restored
    tree is the COMPLETE serving state, never a partial apply."""
    import numpy as np
    return {name: np.asarray(t.value)
            for name, t in model.state_dict(
                include_non_persistable_buffer=True).items()}


def perturbed_state(state: dict, scale: float = 1e-3,
                    seed: int = 0) -> dict:
    """A deterministic variant of ``state`` with every float leaf
    nudged by ``scale`` — how tests/benches/chaos manufacture a "new
    checkpoint" that is structurally identical but produces different
    logits (so a hot-swap's generation isolation is observable) without
    training anything. Integer/bool leaves pass through untouched."""
    import numpy as np
    rng = np.random.default_rng(seed)
    out = {}
    for name, arr in state.items():
        arr = np.asarray(arr)
        if np.issubdtype(arr.dtype, np.floating):
            out[name] = (arr + scale * rng.standard_normal(
                arr.shape).astype(arr.dtype)).astype(arr.dtype)
        else:
            out[name] = arr
    return out
