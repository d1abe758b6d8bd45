"""Flash-attention kernel correctness in Pallas interpreter mode (CPU) —
the same ref-vs-optimized contract the reference uses for its JIT kernels
(paddle/fluid/operators/jit: refer/ scalar versions vs gen/ optimized)."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu.ops.nn_functional import scaled_dot_product_attention
from paddle_tpu.ops.pallas import flash_attention as fa


@pytest.fixture(autouse=True)
def _interpret_mode(monkeypatch):
    # run the Mosaic kernels via the Pallas interpreter on CPU
    orig = fa.pl.pallas_call
    monkeypatch.setattr(fa.pl, "pallas_call",
                        functools.partial(orig, interpret=True))
    yield


def _rand(b, s, h, d, seed=0):
    rng = np.random.default_rng(seed)
    return (rng.standard_normal((b, s, h, d)).astype(np.float32),
            rng.standard_normal((b, s, h, d)).astype(np.float32),
            rng.standard_normal((b, s, h, d)).astype(np.float32))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_forward_matches_reference(causal):
    q, k, v = _rand(1, 256, 2, 64)
    ref = scaled_dot_product_attention(jnp.asarray(q), jnp.asarray(k),
                                       jnp.asarray(v), is_causal=causal,
                                       use_flash=False)
    out = fa.flash_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
                             causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3,
                               atol=2e-3)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_backward_matches_reference(causal):
    q, k, v = _rand(1, 128, 1, 64, seed=1)

    def loss_flash(q_, k_, v_):
        return jnp.sum(fa.flash_attention(q_, k_, v_, causal=causal) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(scaled_dot_product_attention(
            q_, k_, v_, is_causal=causal, use_flash=False) ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=5e-3, atol=5e-3,
                                   err_msg=f"d{name} mismatch")


def test_supported_gate():
    assert not fa.flash_attention_supported((1, 100, 2, 64), (1, 100, 2, 64),
                                            backend="tpu")
    assert fa.flash_attention_supported((1, 256, 2, 64), (1, 256, 2, 64),
                                        backend="tpu")
    assert not fa.flash_attention_supported((1, 256, 2, 64), (1, 256, 2, 64),
                                            backend="cpu")


def test_resolve_blocks_divisor_fallback():
    """S=640 (multiple of 128, not of 512) must stay on the flash path."""
    from paddle_tpu.ops.pallas.flash_attention import (
        _resolve_blocks, flash_attention_supported)

    assert _resolve_blocks(640, 640, 512, 512) == (128, 128)
    assert _resolve_blocks(1024, 1024, 512, 512) == (512, 512)
    assert _resolve_blocks(256, 1024, 512, 512) == (256, 512)
    assert flash_attention_supported((2, 640, 4, 64), (2, 640, 4, 64),
                                     backend="tpu")
    assert not flash_attention_supported((2, 100, 4, 64), (2, 100, 4, 64),
                                         backend="tpu")


def test_flash_bf16_matches_f32_reference():
    """bf16 operands (MXU full-rate path): forward + grads must stay
    within bf16 tolerance of the f32 reference — guards the
    preferred_element_type=f32 accumulation contract."""
    q, k, v = _rand(1, 256, 2, 64, seed=5)
    qb, kb, vb = (jnp.asarray(a, jnp.bfloat16) for a in (q, k, v))

    out_b = fa.flash_attention(qb, kb, vb, causal=True)
    out_f = scaled_dot_product_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        is_causal=True, use_flash=False)
    np.testing.assert_allclose(np.asarray(out_b, np.float32),
                               np.asarray(out_f), atol=2e-2, rtol=2e-2)

    def loss_b(q_, k_, v_):
        return fa.flash_attention(q_, k_, v_, causal=True).astype(
            jnp.float32).sum()

    def loss_f(q_, k_, v_):
        return scaled_dot_product_attention(
            q_, k_, v_, is_causal=True, use_flash=False).sum()

    gb = jax.grad(loss_b, argnums=(0, 1, 2))(qb, kb, vb)
    gf = jax.grad(loss_f, argnums=(0, 1, 2))(jnp.asarray(q),
                                             jnp.asarray(k),
                                             jnp.asarray(v))
    for got, exp, name in zip(gb, gf, "qkv"):
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(exp),
            atol=0.25, rtol=0.08,
            err_msg=f"d{name} diverged beyond bf16 tolerance")


@pytest.mark.parametrize("causal", [False, True])
def test_flash_multiblock_matches_reference(causal):
    """The streaming path with REAL multi-block grids (nq=nk=4): scratch
    init/carry/finish, cross-block causal skip, and the clamped masked-
    step index maps all execute (single-block shapes collapse them)."""
    q, k, v = _rand(2, 512, 2, 64, seed=3)

    def loss_flash(q_, k_, v_):
        return jnp.sum(fa.flash_attention(q_, k_, v_, causal=causal,
                                          block_q=128, block_k=128) ** 2)

    def loss_ref(q_, k_, v_):
        return jnp.sum(scaled_dot_product_attention(
            q_, k_, v_, is_causal=causal, use_flash=False) ** 2)

    out = fa.flash_attention(jnp.asarray(q), jnp.asarray(k),
                             jnp.asarray(v), causal=causal,
                             block_q=128, block_k=128)
    ref = scaled_dot_product_attention(jnp.asarray(q), jnp.asarray(k),
                                       jnp.asarray(v), is_causal=causal,
                                       use_flash=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=5e-3, atol=5e-3,
                                   err_msg=f"d{name} mismatch")


def test_flash_lse_block_merge_matches_dense():
    """flash_attention_lse + merge_attention_blocks over a K/V split
    equals one dense attention — the ring-attention hop contract —
    including gradients THROUGH the differentiable lse. (The causal
    schedule is covered by test_ring_flash_matches_dense.)"""
    from paddle_tpu.distributed.sp import merge_attention_blocks

    b, s, h, d = 1, 512, 2, 64
    q, k, v = _rand(b, s, h, d, seed=7)
    qj, kj, vj = map(jnp.asarray, (q, k, v))
    nblk = 4
    blk = s // nblk

    def merged(q_, k_, v_):
        acc = jnp.zeros(q_.shape, jnp.float32)
        lse = jnp.full((b, s, h), -jnp.inf, jnp.float32)
        for i in range(nblk):
            kb = k_[:, i * blk:(i + 1) * blk]
            vb = v_[:, i * blk:(i + 1) * blk]
            ob, lb = fa.flash_attention_lse(q_, kb, vb, causal=False)
            acc, lse = merge_attention_blocks(acc, lse, ob, lb)
        return acc.astype(q_.dtype)

    out = merged(qj, kj, vj)
    ref = scaled_dot_product_attention(qj, kj, vj, is_causal=False,
                                       use_flash=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)

    g_m = jax.grad(lambda a, b_, c: jnp.sum(merged(a, b_, c) ** 2),
                   argnums=(0, 1, 2))(qj, kj, vj)
    g_r = jax.grad(lambda a, b_, c: jnp.sum(scaled_dot_product_attention(
        a, b_, c, is_causal=False, use_flash=False) ** 2),
        argnums=(0, 1, 2))(qj, kj, vj)
    for gm, gr, name in zip(g_m, g_r, "qkv"):
        np.testing.assert_allclose(np.asarray(gm), np.asarray(gr),
                                   rtol=5e-3, atol=5e-3,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("causal", [False, True])
def test_ring_flash_matches_dense(causal):
    """Ring attention with the flash hop (use_flash=True) over a 4-way
    sequence shard matches dense attention, fwd and grads."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.distributed.sp import ring_attention

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.array(jax.devices()[:4]), ("sep",))
    b, s, h, d = 1, 512, 2, 64
    q, k, v = _rand(b, s, h, d, seed=9)
    qj, kj, vj = map(jnp.asarray, (q, k, v))
    spec = P(None, "sep")

    def ring(q_, k_, v_):
        return shard_map(
            lambda a, b_, c: ring_attention(a, b_, c, causal=causal,
                                            use_flash=True),
            mesh=mesh, in_specs=spec, out_specs=spec,
            check_vma=False)(q_, k_, v_)

    out = ring(qj, kj, vj)
    ref = scaled_dot_product_attention(qj, kj, vj, is_causal=causal,
                                       use_flash=False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)

    g_m = jax.grad(lambda a, b_, c: jnp.sum(ring(a, b_, c) ** 2),
                   argnums=(0, 1, 2))(qj, kj, vj)
    g_r = jax.grad(lambda a, b_, c: jnp.sum(scaled_dot_product_attention(
        a, b_, c, is_causal=causal, use_flash=False) ** 2),
        argnums=(0, 1, 2))(qj, kj, vj)
    for gm, gr, name in zip(g_m, g_r, "qkv"):
        np.testing.assert_allclose(np.asarray(gm), np.asarray(gr),
                                   rtol=5e-3, atol=5e-3,
                                   err_msg=f"d{name} mismatch")


def test_zigzag_ring_flash_matches_dense():
    """Balanced zigzag causal ring on the flash hop: fwd + grads match
    dense attention after the layout permutation."""
    from jax import shard_map
    from jax.sharding import Mesh, PartitionSpec as P

    from paddle_tpu.distributed.sp import ring_attention, zigzag_permutation

    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = Mesh(np.array(jax.devices()[:4]), ("sep",))
    b, s, h, d = 1, 1024, 2, 64
    q, k, v = _rand(b, s, h, d, seed=11)
    perm, inv = zigzag_permutation(s, 4)
    qj, kj, vj = (jnp.asarray(q[:, perm]), jnp.asarray(k[:, perm]),
                  jnp.asarray(v[:, perm]))
    spec = P(None, "sep")

    def ring(q_, k_, v_):
        return shard_map(
            lambda a, b_, c: ring_attention(a, b_, c, causal=True,
                                            use_flash=True,
                                            layout="zigzag"),
            mesh=mesh, in_specs=spec, out_specs=spec,
            check_vma=False)(q_, k_, v_)

    out = ring(qj, kj, vj)
    ref = scaled_dot_product_attention(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), is_causal=True,
        use_flash=False)
    np.testing.assert_allclose(np.asarray(out)[:, inv], np.asarray(ref),
                               rtol=2e-3, atol=2e-3)

    g_m = jax.grad(lambda a, b_, c: jnp.sum(ring(a, b_, c) ** 2),
                   argnums=(0, 1, 2))(qj, kj, vj)
    g_r = jax.grad(lambda a, b_, c: jnp.sum(scaled_dot_product_attention(
        a, b_, c, is_causal=True, use_flash=False) ** 2),
        argnums=(0, 1, 2))(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    for gm, gr, name in zip(g_m, g_r, "qkv"):
        np.testing.assert_allclose(np.asarray(gm)[:, inv],
                                   np.asarray(gr), rtol=5e-3, atol=5e-3,
                                   err_msg=f"d{name} mismatch")


def test_sdpa_flash_autoselect_heuristic(monkeypatch):
    """use_flash tri-state: None = auto (flash only at long key
    lengths), True = force, False = never. Regression: the GPT config
    flag was silently ignored on the main path before r4."""
    import paddle_tpu.ops.pallas.flash_attention as fa
    from paddle_tpu.ops import nn_functional as NF

    calls = []
    monkeypatch.setattr(fa, "flash_attention_supported",
                        lambda *a, **k: True)
    monkeypatch.setattr(
        fa, "flash_attention",
        lambda q, k, v, causal=False, scale=None: calls.append(1) or q)

    q = jnp.zeros((1, 256, 2, 64))
    NF.scaled_dot_product_attention(q, q, q)  # auto, short: XLA path
    assert not calls
    NF.scaled_dot_product_attention(q, q, q, use_flash=True)  # forced
    assert len(calls) == 1
    # measured r4 crossover: flash wins from S=512 up (BERT-base body
    # 243 -> 216.6 ms/step), XLA wins at S<=256
    mid_q = jnp.zeros((1, 512, 2, 64))
    NF.scaled_dot_product_attention(mid_q, mid_q, mid_q)  # auto, >=512
    assert len(calls) == 2
    long_q = jnp.zeros((1, 4096, 2, 64))
    NF.scaled_dot_product_attention(long_q, long_q, long_q)  # auto, long
    assert len(calls) == 3
    NF.scaled_dot_product_attention(long_q, long_q, long_q,
                                    use_flash=False)
    assert len(calls) == 3


def test_gpt_flash_flag_plumbs_to_attention(monkeypatch):
    """GPTConfig(use_flash_attention=False) must actually bypass the
    flash kernel even where the auto heuristic would pick it."""
    import paddle_tpu as pt
    import paddle_tpu.ops.pallas.flash_attention as fa
    from paddle_tpu.models import GPTConfig, GPTForCausalLM

    monkeypatch.setattr(fa, "flash_attention_supported",
                        lambda *a, **k: True)

    def boom(*a, **k):
        raise AssertionError("flash kernel reached with flag off")

    monkeypatch.setattr(fa, "flash_attention", boom)
    monkeypatch.setenv("PT_FLASH_MIN_SEQ", "1")
    # _FLASH_MIN_SEQ is read at import; patch the module constant too
    from paddle_tpu.ops import nn_functional as NF
    monkeypatch.setattr(NF, "_FLASH_MIN_SEQ", 1)

    pt.seed(0)
    cfg = GPTConfig(vocab_size=64, hidden_size=16, num_layers=1,
                    num_heads=2, max_seq_len=16, dropout=0.0,
                    attn_dropout=0.0, use_flash_attention=False)
    m = GPTForCausalLM(cfg)
    ids = np.zeros((1, 16), np.int32)
    float(m(pt.to_tensor(ids), labels=pt.to_tensor(ids)))  # no boom


def test_sdpa_causal_kv_cache_never_uses_flash(monkeypatch):
    """Causal attention with sq != sk (a concatenated KV cache) must not
    route to the flash kernel: its diagonal-aligned causal mask has no
    cache-length offset (regression: silent wrong outputs in the GPT
    dynamic-cache path with use_flash forced)."""
    import paddle_tpu.ops.pallas.flash_attention as fa
    from paddle_tpu.ops import nn_functional as NF

    monkeypatch.setattr(fa, "flash_attention_supported",
                        lambda *a, **k: True)

    def boom(*a, **k):
        raise AssertionError("flash taken for causal sq != sk")

    monkeypatch.setattr(fa, "flash_attention", boom)
    q = jnp.zeros((1, 128, 2, 64))
    kv = jnp.zeros((1, 256, 2, 64))
    out = NF.scaled_dot_product_attention(q, kv, kv, is_causal=True,
                                          use_flash=True)
    assert out.shape == q.shape
    # and the XLA path applies the cache offset: the first new token
    # (global position 128) must see all 129 visible keys, not just 1
    qv = jnp.ones((1, 1, 1, 4))
    kvv = jnp.asarray(
        np.arange(8, dtype=np.float32).reshape(1, 8, 1, 1) *
        jnp.ones((1, 8, 1, 4)))
    got = NF.scaled_dot_product_attention(qv, kvv, kvv, is_causal=True,
                                          use_flash=False)
    assert float(got[0, 0, 0, 0]) > 0  # attends beyond position 0


@pytest.mark.parametrize("causal", [False, True])
def test_fused_single_qblock_backward_multi_kblock(causal):
    """The nq==1 fused backward with nk>1 (cross-attention: short Q,
    long K): dQ must accumulate across the streamed K blocks and dK/dV
    must land in the right per-block slots — including the causal
    branch, where the second K block is FULLY masked (its dk/dv must
    come out exactly zero via the skip path, not garbage). Reachable
    in production via q_len<=block <= k_len cross-attention."""
    rng = np.random.default_rng(7)
    b, h, d = 2, 2, 64
    sq, sk = 128, 256  # block 128 -> nq=1, nk=2 through the fused path
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, sk, h, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, sk, h, d)).astype(np.float32))

    def loss_flash(q_, k_, v_):
        return jnp.sum(fa.flash_attention(
            q_, k_, v_, causal=causal, block_q=128, block_k=128) ** 2)

    def loss_ref(q_, k_, v_):
        # the flash causal mask is diagonal-aligned (q_pos >= k_pos,
        # no cache offset) — mirror it for the reference
        o = scaled_dot_product_attention(q_, k_, v_, use_flash=False,
                                         attn_mask=_diag_mask(sq, sk)
                                         if causal else None)
        return jnp.sum(o ** 2)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=5e-3, atol=5e-3,
                                   err_msg=f"d{name} mismatch")
    if causal:
        # K block 1 (positions 128..255) is fully masked: its dk/dv
        # must be EXACT zeros (the pl.when skip writes them)
        assert np.all(np.asarray(g_flash[1])[:, 128:] == 0.0)
        assert np.all(np.asarray(g_flash[2])[:, 128:] == 0.0)


def _diag_mask(sq, sk):
    """Diagonal-aligned causal mask (the flash kernel's convention:
    q_pos >= k_pos with no sk-sq cache offset)."""
    qpos = jnp.arange(sq)[:, None]
    kpos = jnp.arange(sk)[None, :]
    return jnp.where(qpos >= kpos, 0.0, -jnp.inf)[None, None]


def test_single_kblock_causal_forward_sq_gt_sk():
    """nq>1/nk==1 causal single-K-block forward (the qb-offset mask
    lines in _fwd_single_block_kernel): q longer than k, grid over Q
    blocks, every block sees the one K block under the diagonal-aligned
    mask."""
    rng = np.random.default_rng(11)
    b, h, d = 1, 2, 64
    sq, sk = 256, 128  # block 128 -> nq=2, nk=1 single-block fwd path
    q = jnp.asarray(rng.standard_normal((b, sq, h, d)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((b, sk, h, d)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((b, sk, h, d)).astype(np.float32))
    out = fa.flash_attention(q, k, v, causal=True, block_q=128,
                             block_k=128)
    ref = scaled_dot_product_attention(q, k, v, use_flash=False,
                                       attn_mask=_diag_mask(sq, sk))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-3, atol=2e-3)


# -- the forward's lane-wide carries and the mask on cut blocks alone --------

def _dense_reference(q, k, v, causal, window=None):
    """float32 attention of [B, H, Sq, D] over [B, KVH, Sk, D] under the
    kernel's diagonal-aligned mask; (out, logsumexp rows)."""
    g = q.shape[1] // k.shape[1]
    k, v = jnp.repeat(k, g, axis=1), jnp.repeat(v, g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k) * q.shape[-1] ** -0.5
    if causal:
        qp = jnp.arange(q.shape[2])[:, None]
        kp = jnp.arange(k.shape[2])[None, :]
        seen = qp >= kp
        if window is not None:
            seen = seen & (kp > qp - window)
        s = jnp.where(seen, s, -jnp.inf)
    return (jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v),
            jax.nn.logsumexp(s, -1))


# (sq, sk, block_q, block_k): one K block; a 3 x 3 grid of blocks; Q
# blocks twice as tall as K blocks; queries longer than keys
_LAYOUTS = {"nk1": (128, 128, 128, 128), "nk3": (384, 384, 128, 128),
            "bq_ne_bk": (512, 512, 256, 128), "sq_gt_sk": (256, 128, 128, 128)}


@pytest.mark.parametrize("group,d", [(1, 128), (7, 64), (8, 256)])
@pytest.mark.parametrize("window", [None, 200, 128, 1024],
                         ids=["no_window", "edge_in_tile", "one_block",
                              "wider_than_seq"])
@pytest.mark.parametrize("layout", list(_LAYOUTS))
def test_forward_blocks_match_dense(layout, window, group, d):
    """Every kind of block the forward meets — wholly visible, cut by
    the diagonal, cut by the window's lower edge, skipped — over grouped
    heads and the three head sizes, in the streaming and the
    single-block kernel: output AND log-sum-exp rows against the dense
    float32 attention."""
    sq, sk, bq, bk = _LAYOUTS[layout]
    if window is not None and window <= sq - sk:
        window = sq - sk + 1  # the last row still sees the last key
    rng = np.random.default_rng(hash((layout, window, group)) % 2 ** 31)
    q = jnp.asarray(rng.standard_normal((1, group, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((1, 1, sk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((1, 1, sk, d)), jnp.float32)
    out, lse = fa._flash_fwd(q, k, v, d ** -0.5, True, bq, bk, group=group,
                             window=window)
    ref, ref_lse = _dense_reference(q, k, v, True, window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(lse[..., 0]), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("d", [64, 128])
@pytest.mark.parametrize("causal", [False, True])
def test_lse_rows_and_cotangent_match_dense(causal, d):
    """flash_attention_lse over a 3 x 3 grid of blocks: the rows of the
    log-sum-exp and the gradients THROUGH them (the lse cotangent folds
    into delta) are what the dense attention gives."""
    rng = np.random.default_rng(17 + d)
    q, k, v = (jnp.asarray(rng.standard_normal((1, 384, 2, d)), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(rng.standard_normal((1, 384, 2)), jnp.float32)

    def flash(q_, k_, v_):
        o, lse = fa.flash_attention_lse(q_, k_, v_, causal=causal,
                                        block_q=128, block_k=128)
        return jnp.sum(o ** 2) + jnp.sum(lse * w), lse

    def dense(q_, k_, v_):
        o, lse = _dense_reference(*(jnp.swapaxes(x, 1, 2)
                                    for x in (q_, k_, v_)), causal)
        lse = jnp.swapaxes(lse, 1, 2)
        return jnp.sum(o ** 2) + jnp.sum(lse * w), lse

    (_, lse), g = jax.value_and_grad(flash, argnums=(0, 1, 2),
                                     has_aux=True)(q, k, v)
    (_, ref_lse), g_ref = jax.value_and_grad(dense, argnums=(0, 1, 2),
                                             has_aux=True)(q, k, v)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse),
                               rtol=2e-5, atol=2e-5)
    for got, want, name in zip(g, g_ref, "qkv"):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=5e-3, atol=5e-3,
                                   err_msg=f"d{name} mismatch")


@pytest.mark.parametrize("length", [None, 512, 385, 129, 128, 1, 0])
def test_relevant_never_skips_a_visible_key(length):
    """The scalar test that guards a grid step off: a block it skips
    holds no key any of its queries sees, and a block above the
    diagonal or behind the window is skipped (a block it passes may
    still hide every key of SOME rows). With a true length: a block of
    queries or of keys that starts at or past it is skipped too, and no
    LIVE query (one before the length) loses a key it sees."""
    for bq, bk, window in [(128, 128, None), (128, 128, 200),
                           (128, 128, 128), (256, 128, None),
                           (128, 256, 300)]:
        live = (None if length is None else tuple(
            int(x) for x in fa._live_blocks(jnp.int32(length), bq, bk)))
        for qb in range(4):
            for kb in range(4):
                qp = np.arange(qb * bq, (qb + 1) * bq)[:, None]
                kp = np.arange(kb * bk, (kb + 1) * bk)[None, :]
                seen = qp >= kp
                if window is not None:
                    seen = seen & (kp > qp - window)
                relevant = bool(fa._relevant(qb, kb, bq, bk, window, live))
                if length is None:
                    assert relevant == bool(seen.any()), (bq, bk, window,
                                                          qb, kb)
                    continue
                assert relevant == bool(seen.any() and qb * bq < length
                                        and kb * bk < length), (
                    bq, bk, window, length, qb, kb)
                # what a live query sees lies in a block that runs
                assert relevant or not (seen & (qp < length)).any()


# -- the true lengths of right-padded sequences, as prefetched scalars -------

def _ragged_lengths(kind, sq, bq):
    """Two different lengths a case, the first as the case names it."""
    first = {"full": sq, "past_a_block_edge": bq + 1, "one_block": bq,
             "one": 1}[kind]
    return first, sq - bq // 2


@pytest.mark.parametrize("kind", ["full", "past_a_block_edge", "one_block",
                                  "one"])
@pytest.mark.parametrize("group,d", [(1, 128), (7, 64), (8, 256)])
@pytest.mark.parametrize("window", [None, 200, 128],
                         ids=["no_window", "edge_in_tile", "one_block"])
@pytest.mark.parametrize("layout", ["nk3", "bq_ne_bk"])
def test_forward_with_lengths_leaves_live_rows_as_they_were(layout, window,
                                                            group, d, kind):
    """Two right-padded sequences of different true lengths: every row
    before a sequence's length is BIT-equal to the call without lengths
    (it runs the same blocks in the same order), the rows of a Q block
    wholly past the length are zero, nothing is left uninitialised or
    infinite, and no log-sum-exp rows are written (forward only)."""
    sq, sk, bq, bk = _LAYOUTS[layout]
    rng = np.random.default_rng(hash((layout, window, group, kind)) % 2 ** 31)
    q = jnp.asarray(rng.standard_normal((2, group, sq, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((2, 1, sk, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((2, 1, sk, d)), jnp.float32)
    lengths = _ragged_lengths(kind, sq, bq)
    want, _ = fa._flash_fwd(q, k, v, d ** -0.5, True, bq, bk, group=group,
                            window=window)
    out, lse = fa._flash_fwd(q, k, v, d ** -0.5, True, bq, bk, group=group,
                             window=window,
                             lengths=jnp.asarray(lengths, jnp.int32))
    assert lse is None
    assert np.isfinite(np.asarray(out)).all()
    for b, n in enumerate(lengths):
        np.testing.assert_array_equal(np.asarray(out[b, :, :n]),
                                      np.asarray(want[b, :, :n]))
        assert not np.asarray(out[b, :, -(-n // bq) * bq:]).any()


def test_an_empty_sequence_comes_back_zero():
    """A length of 0 (an empty slot of a batch): every Q block is past
    the end, no block index leaves the array, the rows are zero."""
    rng = np.random.default_rng(5)
    q, k, v = (jnp.asarray(rng.standard_normal((2, 256, 2, 64)), jnp.float32)
               for _ in range(3))
    out = fa.flash_attention_grouped(q, k, v, block_q=128, block_k=128,
                                     lengths=jnp.asarray([0, 256]))
    want = fa.flash_attention_grouped(q, k, v, block_q=128, block_k=128)
    assert not np.asarray(out[0]).any()
    np.testing.assert_array_equal(np.asarray(out[1]), np.asarray(want[1]))


def test_lengths_are_for_the_causal_forward_alone():
    q = jnp.zeros((1, 2, 256, 64))
    with pytest.raises(ValueError, match="causal"):
        fa._flash_fwd(q, q, q, 0.125, False, 128, 128,
                      lengths=jnp.asarray([7]))


def _pallas_calls(fn, *args):
    """The ``pallas_call`` equations of ``fn``'s jaxpr, custom_vjp
    bodies included: (kernel name, operands, scalar-prefetch operands)."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found.append((
                    eqn.params["name"], len(eqn.invars),
                    eqn.params["grid_mapping"].num_index_operands))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return found


@pytest.mark.parametrize("entry", ["flash_attention", "flash_attention_lse",
                                   "flash_attention_grouped", "grad",
                                   "with_lengths"])
def test_without_lengths_the_call_has_no_scalar_prefetch_operand(entry):
    """``lengths=None`` builds the call it always built: q, k, v and no
    scalar-prefetch operand, in the trainer's forward and backward, the
    GPT prefill's entry and the grouped one (their programs are not this
    mechanism's to change); with lengths the one forward call gains
    exactly one."""
    q = jnp.zeros((1, 256, 2, 64), jnp.float32)
    kw = dict(block_q=128, block_k=128)
    fns = {
        "flash_attention": lambda x: fa.flash_attention(x, x, x, causal=True,
                                                        **kw),
        "flash_attention_lse": lambda x: fa.flash_attention_lse(
            x, x, x, causal=True, **kw),
        "flash_attention_grouped": lambda x: fa.flash_attention_grouped(
            x, x, x, **kw),
        "grad": jax.grad(lambda x: fa.flash_attention(
            x, x, x, causal=True, **kw).sum()),
        "with_lengths": lambda x: fa.flash_attention_grouped(
            x, x, x, lengths=jnp.asarray([100]), **kw),
    }
    calls = _pallas_calls(fns[entry], q)
    fwd = [c for c in calls if c[0] == "flash_fwd"]
    assert len(fwd) == 1, calls
    if entry == "with_lengths":
        assert fwd == [("flash_fwd", 4, 1)]
    else:
        assert fwd == [("flash_fwd", 3, 0)]
        assert all(c[2] == 0 for c in calls), calls
        assert len(calls) == (3 if entry == "grad" else 1), calls
