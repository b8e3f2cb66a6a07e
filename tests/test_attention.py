"""Attention stack tests: blockwise and flash vs naive oracle; ring
attention on the virtual 8-device mesh vs single-device full attention
(values AND gradients)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from elasticdl_tpu.ops.attention import (
    blockwise_attention,
    flash_attention,
    naive_attention,
)
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.parallel.context_parallel import ring_attention


@pytest.fixture(autouse=True)
def _opt_into_interpreted_kernels(monkeypatch):
    """use_pallas() routes to the jnp reference paths off-TPU; these
    tests exist to exercise the kernel code itself, so they opt into
    Pallas interpreter mode explicitly."""
    monkeypatch.setenv("ELASTICDL_TPU_FORCE_INTERPRET", "1")

B, H, L, D = 2, 2, 64, 8


def _qkv(seed=0, l=L, d=D):
    rs = np.random.RandomState(seed)
    mk = lambda: rs.randn(B, H, l, d).astype(np.float32)
    return jnp.array(mk()), jnp.array(mk()), jnp.array(mk())


@pytest.mark.parametrize("causal", [False, True])
def test_blockwise_matches_naive(causal):
    q, k, v = _qkv(0)
    ref = naive_attention(q, k, v, causal=causal)
    out = blockwise_attention(q, k, v, causal=causal, block_size=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_blockwise_uneven_blocks():
    q, k, v = _qkv(1, l=50)
    ref = naive_attention(q, k, v, causal=True)
    out = blockwise_attention(q, k, v, causal=True, block_size=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_naive(causal):
    # d=128 lane-aligned so the real kernel path runs (interpreted on CPU)
    q, k, v = _qkv(2, l=32, d=128)
    ref = naive_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_gradients_pallas_bwd(causal):
    """The Pallas two-pass backward (dq + dkv kernels) against the naive
    oracle: rectangular seq (lq != lk), mixed block sizes."""
    rs = np.random.RandomState(7)
    q = jnp.asarray(rs.randn(2, 2, 64, 128).astype(np.float32) * 0.3)
    k = jnp.asarray(rs.randn(2, 2, 32, 128).astype(np.float32) * 0.3)
    v = jnp.asarray(rs.randn(2, 2, 32, 128).astype(np.float32) * 0.3)

    def loss_flash(q, k, v):
        return (
            flash_attention(q, k, v, causal=causal, block_q=32,
                            block_k=16) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (naive_attention(q, k, v, causal=causal) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_sliding_window_matches_naive(causal):
    """Window-masked flash (fwd + Pallas bwd) against the naive oracle,
    block-skip predicate included (window smaller than a block)."""
    q, k, v = _qkv(11, l=64, d=128)
    w = 12
    ref = naive_attention(q, k, v, causal=causal, window=w)
    out = flash_attention(q, k, v, causal=causal, window=w,
                          block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    blk = blockwise_attention(q, k, v, causal=causal, window=w,
                              block_size=16)
    np.testing.assert_allclose(np.asarray(blk), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal, window=w,
                                block_q=16, block_k=16) ** 2).sum()

    def loss_ref(q, k, v):
        return (naive_attention(q, k, v, causal=causal, window=w) ** 2
                ).sum()

    g_f = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_r = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_f, g_r):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-3, atol=1e-4)


def test_sliding_window_validation():
    q, k, v = _qkv(12, l=32, d=128)
    with pytest.raises(ValueError, match="window"):
        flash_attention(q, k, v, causal=True, window=0)
    with pytest.raises(ValueError, match="window"):
        blockwise_attention(q, k, v, causal=True, window=-2)
    with pytest.raises(ValueError, match="square"):
        flash_attention(q, k[:, :, :16], v[:, :, :16], causal=True,
                        window=4)
    with pytest.raises(ValueError, match="square"):
        blockwise_attention(q, k[:, :, :16], v[:, :, :16], window=4)


@pytest.mark.slow
def test_sliding_window_model_trains():
    """transformer_lm with attn_window trains and differs from full
    attention (the mask actually bites)."""
    from elasticdl_tpu.common.model_utils import (
        format_params_str,
        load_model_spec_from_module,
    )
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.training.trainer import Trainer
    from model_zoo.transformer_lm import transformer_lm as zoo

    cfg = dict(vocab_size=32, seq_len=32, embed_dim=32, num_heads=2,
               num_layers=1, attn_window=4)
    rs = np.random.RandomState(0)
    tokens = rs.randint(0, 32, size=(4, 33)).astype(np.int32)
    batch = ({"tokens": tokens[:, :-1]}, tokens[:, 1:])
    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    spec = load_model_spec_from_module(zoo)
    t_win = Trainer(spec, mesh=mesh,
                    model_params=format_params_str(cfg))
    s_win = t_win.init_state(batch)
    s_win, l_win = t_win.train_step(s_win, batch)
    cfg_full = dict(cfg, attn_window=0)
    t_full = Trainer(spec, mesh=mesh,
                     model_params=format_params_str(cfg_full))
    s_full = t_full.init_state(batch)
    s_full, l_full = t_full.train_step(s_full, batch)
    assert abs(float(l_win) - float(l_full)) > 1e-6


def test_flash_gradients():
    q, k, v = _qkv(3, l=32, d=128)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=16,
                               block_k=16).sum()

    def loss_ref(q, k, v):
        return naive_attention(q, k, v, causal=True).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.slow
def test_ring_attention_8dev(causal):
    mesh = mesh_lib.build_mesh({"sp": 8})
    q, k, v = _qkv(4)
    ref = naive_attention(q, k, v, causal=causal)
    out = ring_attention(q, k, v, mesh, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_ring_attention_dp_sp_mesh():
    mesh = mesh_lib.build_mesh({"dp": 2, "sp": 4})
    q, k, v = _qkv(5)
    ref = naive_attention(q, k, v, causal=True)
    out = ring_attention(q, k, v, mesh, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_ring_attention_gradients():
    mesh = mesh_lib.build_mesh({"sp": 8})
    q, k, v = _qkv(6)

    def loss_ring(q, k, v):
        return ring_attention(q, k, v, mesh, causal=True).sum()

    def loss_ref(q, k, v):
        return naive_attention(q, k, v, causal=True).sum()

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr_, gn in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr_), np.asarray(gn),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.slow
def test_ring_attention_jit_compiles_once():
    mesh = mesh_lib.build_mesh({"sp": 8})
    q, k, v = _qkv(7)
    fn = jax.jit(lambda a, b, c: ring_attention(a, b, c, mesh))
    out1 = fn(q, k, v)
    out2 = fn(q + 1, k, v)
    assert out1.shape == q.shape and out2.shape == q.shape


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.slow
def test_ring_attention_jnp_fallback(causal, monkeypatch):
    """The non-Pallas ring path (blockwise forward + dense jnp backward
    recomputing P from the global lse) against the naive oracle."""
    monkeypatch.setenv("ELASTICDL_TPU_DISABLE_PALLAS", "1")
    mesh = mesh_lib.build_mesh({"sp": 8})
    q, k, v = _qkv(8)
    out = ring_attention(q, k, v, mesh, causal=causal)
    ref = naive_attention(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    g_ring = jax.grad(
        lambda a, b, c: (ring_attention(a, b, c, mesh,
                                        causal=causal) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    g_ref = jax.grad(
        lambda a, b, c: (naive_attention(a, b, c,
                                         causal=causal) ** 2).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    for gr_, gn in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr_), np.asarray(gn),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.slow
def test_ring_attention_uses_flash_kernels(monkeypatch):
    """Proof the ring's local compute is the Pallas flash kernel, both
    directions: count _flash_forward / _flash_backward invocations while
    tracing a ring attention value+grad on the sp mesh."""
    import elasticdl_tpu.ops.attention as attn_mod

    calls = {"fwd": 0, "bwd": 0}
    real_fwd, real_bwd = attn_mod._flash_forward, attn_mod._flash_backward

    def spy_fwd(*a, **kw):
        calls["fwd"] += 1
        return real_fwd(*a, **kw)

    def spy_bwd(*a, **kw):
        calls["bwd"] += 1
        return real_bwd(*a, **kw)

    monkeypatch.setattr(attn_mod, "_flash_forward", spy_fwd)
    monkeypatch.setattr(attn_mod, "_flash_backward", spy_bwd)
    mesh = mesh_lib.build_mesh({"sp": 8})
    q, k, v = _qkv(9)
    g = jax.grad(
        lambda a, b, c: ring_attention(a, b, c, mesh, causal=True).sum(),
        argnums=(0, 1, 2),
    )(q, k, v)
    assert calls["fwd"] > 0, "ring forward never reached the flash kernel"
    assert calls["bwd"] > 0, "ring backward never reached the flash kernel"
    assert all(x.shape == q.shape for x in g)


def test_ulysses_auto_picks_flash(monkeypatch):
    """Ulysses attn_impl='auto' must route the full-sequence local
    attention through the Pallas flash kernel (the _flash custom-vjp
    entry) whenever it can run."""
    import elasticdl_tpu.ops.attention as attn_mod
    from elasticdl_tpu.parallel.context_parallel import ulysses_attention

    calls = {"n": 0}
    real = attn_mod._flash

    def spy(*a, **kw):
        calls["n"] += 1
        return real(*a, **kw)

    monkeypatch.setattr(attn_mod, "_flash", spy)
    mesh = mesh_lib.build_mesh({"sp": 8})
    rs = np.random.RandomState(10)
    mk = lambda: jnp.asarray(rs.randn(2, 8, 64, 16).astype(np.float32))
    out = ulysses_attention(mk(), mk(), mk(), mesh, causal=True,
                            attn_impl="auto")
    assert calls["n"] > 0, "ulysses auto did not reach the flash kernel"
    assert out.shape == (2, 8, 64, 16)


def test_jax_flash_off_tpu_fallback_and_window_rejection():
    """attn_impl='jax_flash' off-TPU falls back to the blockwise path
    (values match naive); sliding windows are rejected explicitly."""
    from elasticdl_tpu.ops.attention import jax_flash_attention

    q, k, v = _qkv(13, l=32, d=16)
    out = jax_flash_attention(q, k, v, causal=True)
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    with pytest.raises(ValueError, match="sliding-window"):
        jax_flash_attention(q, k, v, causal=True, window=4)


def test_ulysses_jax_flash_matches_naive():
    """attn_impl='jax_flash' through Ulysses: the dispatch map routes
    the local full-sequence attention to jax's bundled kernel (which
    falls back to blockwise off-TPU) — values must match the naive
    oracle on the sp mesh."""
    from elasticdl_tpu.parallel.context_parallel import ulysses_attention

    rs = np.random.RandomState(21)
    mk = lambda: jnp.asarray(rs.randn(2, 8, 64, 16).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    mesh = mesh_lib.build_mesh({"sp": 8})
    out = ulysses_attention(q, k, v, mesh, causal=True,
                            attn_impl="jax_flash")
    ref = naive_attention(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


# ------------------------------------------------- grouped-query (GQA)


@pytest.mark.parametrize("hkv", [1, 2])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_gqa_matches_expanded_naive(causal, hkv):
    """GQA/MQA through the Pallas kernels (fwd + both backward passes)
    vs the naive oracle on repeat-expanded kv. dk/dv must come back
    group-summed in the kv head count."""
    from elasticdl_tpu.ops.attention import expand_kv

    rs = np.random.RandomState(31)
    b, h, l, d = 2, 4, 64, 128
    q = jnp.asarray(rs.randn(b, h, l, d).astype(np.float32) * 0.3)
    k = jnp.asarray(rs.randn(b, hkv, l, d).astype(np.float32) * 0.3)
    v = jnp.asarray(rs.randn(b, hkv, l, d).astype(np.float32) * 0.3)

    def loss_flash(q, k, v):
        return (
            flash_attention(q, k, v, causal=causal, block_q=16,
                            block_k=16) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (
            naive_attention(q, expand_kv(k, h), expand_kv(v, h),
                            causal=causal) ** 2
        ).sum()

    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    ref = naive_attention(q, expand_kv(k, h), expand_kv(v, h),
                          causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)
    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        assert gf.shape == gr.shape
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-3, atol=1e-4)


def test_gqa_sliding_window_matches_naive():
    """GQA composes with the sliding-window block-skip predicate."""
    from elasticdl_tpu.ops.attention import expand_kv

    rs = np.random.RandomState(32)
    b, h, hkv, l, d = 1, 4, 2, 64, 128
    q = jnp.asarray(rs.randn(b, h, l, d).astype(np.float32) * 0.3)
    k = jnp.asarray(rs.randn(b, hkv, l, d).astype(np.float32) * 0.3)
    v = jnp.asarray(rs.randn(b, hkv, l, d).astype(np.float32) * 0.3)
    out = flash_attention(q, k, v, causal=True, window=16, block_q=16,
                          block_k=16)
    ref = naive_attention(q, expand_kv(k, h), expand_kv(v, h),
                          causal=True, window=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_gqa_head_divisibility_validated():
    rs = np.random.RandomState(33)
    q = jnp.asarray(rs.randn(1, 4, 32, 16).astype(np.float32))
    k = jnp.asarray(rs.randn(1, 3, 32, 16).astype(np.float32))
    v = jnp.asarray(rs.randn(1, 3, 32, 16).astype(np.float32))
    with pytest.raises(ValueError, match="num_kv_heads"):
        flash_attention(q, k, v, causal=True)


def test_gqa_lse_surface_both_paths(monkeypatch):
    """The ring-attention (out, lse) surface under GQA: kernel path and
    the pure-jnp fallback agree, dk/dv group-summed in both."""
    from elasticdl_tpu.ops.attention import (
        attention_backward_lse,
        attention_forward_lse,
        expand_kv,
    )

    rs = np.random.RandomState(34)
    b, h, hkv, l, d = 2, 4, 2, 32, 128
    q = jnp.asarray(rs.randn(b, h, l, d).astype(np.float32) * 0.3)
    k = jnp.asarray(rs.randn(b, hkv, l, d).astype(np.float32) * 0.3)
    v = jnp.asarray(rs.randn(b, hkv, l, d).astype(np.float32) * 0.3)
    o_k, lse_k = attention_forward_lse(q, k, v, causal=True,
                                       block_q=16, block_k=16)
    g = jnp.ones_like(o_k)
    grads_k = attention_backward_lse(q, k, v, o_k, lse_k, g, causal=True,
                                     block_q=16, block_k=16)
    # jnp fallback path (kernels disabled; monkeypatch restores the env
    # at test end, after which only jnp-path asserts remain)
    monkeypatch.setenv("ELASTICDL_TPU_DISABLE_PALLAS", "1")
    o_j, lse_j = attention_forward_lse(q, k, v, causal=True)
    grads_j = attention_backward_lse(q, k, v, o_j, lse_j, g,
                                     causal=True)
    np.testing.assert_allclose(np.asarray(o_k), np.asarray(o_j),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse_k), np.asarray(lse_j),
                               rtol=1e-4, atol=1e-5)
    for gk, gj in zip(grads_k, grads_j):
        assert gk.shape == gj.shape
        np.testing.assert_allclose(np.asarray(gk), np.asarray(gj),
                                   rtol=1e-3, atol=1e-4)
    assert grads_k[1].shape == k.shape and grads_k[2].shape == v.shape


def test_gqa_sliding_window_gradients():
    """Windowed GQA through BOTH Pallas backward passes: the dkv
    kernel's remapped q-block index (qb = qi % n_q while the streamed
    dim enumerates (group, q_block) pairs) drives the window mask — a
    regression that masked with the raw streamed index would corrupt
    dk/dv here and nowhere else in the suite."""
    from elasticdl_tpu.ops.attention import expand_kv

    rs = np.random.RandomState(35)
    b, h, hkv, l, d = 1, 4, 2, 64, 128
    q = jnp.asarray(rs.randn(b, h, l, d).astype(np.float32) * 0.3)
    k = jnp.asarray(rs.randn(b, hkv, l, d).astype(np.float32) * 0.3)
    v = jnp.asarray(rs.randn(b, hkv, l, d).astype(np.float32) * 0.3)

    def loss_flash(q, k, v):
        return (
            flash_attention(q, k, v, causal=True, window=16, block_q=16,
                            block_k=16) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (
            naive_attention(q, expand_kv(k, h), expand_kv(v, h),
                            causal=True, window=16) ** 2
        ).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        assert gf.shape == gr.shape
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-3, atol=1e-4)


def _pack_segments(b, l, seed=11):
    """Random packing: each row is 2-4 contiguous same-id runs."""
    rs = np.random.RandomState(seed)
    seg = np.zeros((b, l), np.int32)
    for r in range(b):
        cuts = np.sort(rs.choice(np.arange(8, l - 1), size=rs.randint(1, 4),
                                 replace=False))
        sid, prev = 0, 0
        for c in list(cuts) + [l]:
            seg[r, prev:c] = sid
            sid, prev = sid + 1, c
    return jnp.asarray(seg)


@pytest.mark.parametrize("causal", [False, True])
def test_segment_mask_blockwise_matches_naive(causal):
    q, k, v = _qkv(3)
    seg = _pack_segments(B, L)
    ref = naive_attention(q, k, v, causal=causal, segments=seg)
    out = blockwise_attention(q, k, v, causal=causal, block_size=16,
                              segments=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_segment_mask_flash_matches_naive(causal):
    """Packed-sequence masking through the Pallas kernel: the segment-id
    tiles must mask cross-segment blocks identically to the oracle,
    with segment boundaries landing INSIDE blocks (block 16, cuts
    anywhere)."""
    q, k, v = _qkv(4, l=64, d=128)
    seg = _pack_segments(B, 64)
    ref = naive_attention(q, k, v, causal=causal, segments=seg)
    out = flash_attention(q, k, v, causal=causal, block_q=16,
                          block_k=16, segments=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("hkv", [2, 1])
def test_segment_mask_flash_gradients(hkv):
    """Segment masking through BOTH Pallas backward kernels (dq and the
    group-summed dk/dv), including under GQA/MQA."""
    rs = np.random.RandomState(9)
    q = jnp.asarray(rs.randn(2, 2, 64, 128).astype(np.float32) * 0.3)
    k = jnp.asarray(rs.randn(2, hkv, 64, 128).astype(np.float32) * 0.3)
    v = jnp.asarray(rs.randn(2, hkv, 64, 128).astype(np.float32) * 0.3)
    seg = _pack_segments(2, 64)

    def loss_flash(q, k, v):
        return (
            flash_attention(q, k, v, causal=True, block_q=16,
                            block_k=16, segments=seg) ** 2
        ).sum()

    def loss_ref(q, k, v):
        return (
            naive_attention(q, k, v, causal=True, segments=seg) ** 2
        ).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-3, atol=1e-4)


def test_segment_validation():
    q, k, v = _qkv(5, l=32, d=128)
    with pytest.raises(ValueError, match="batch, seq"):
        flash_attention(q, k, v, segments=jnp.zeros((B, 7), jnp.int32))
    rect_k = jnp.concatenate([k, k], axis=2)
    with pytest.raises(ValueError, match="square"):
        flash_attention(q, rect_k, rect_k,
                        segments=jnp.zeros((B, 32), jnp.int32))


@pytest.mark.parametrize("pos_emb", ["learned", "rope"])
@pytest.mark.slow
def test_packed_rows_match_unpacked_model(pos_emb):
    """End-to-end packing contract on the LM: a row packing two
    sequences (segment_ids + restarting positions) must produce the
    SAME logits as the two sequences run as separate rows."""
    from model_zoo.transformer_lm.transformer_lm import TransformerLM

    model = TransformerLM(
        vocab_size=32, seq_len=32, embed_dim=32, num_heads=2,
        num_layers=2, pos_emb=pos_emb, tp_shard=False,
    )
    rs = np.random.RandomState(0)
    seq_a = rs.randint(0, 32, size=(1, 16)).astype(np.int32)
    seq_b = rs.randint(0, 32, size=(1, 16)).astype(np.int32)
    packed = jnp.asarray(np.concatenate([seq_a, seq_b], axis=1))
    seg = jnp.asarray([[0] * 16 + [1] * 16], jnp.int32)
    params = model.init(jax.random.PRNGKey(0), {"tokens": packed})
    lp = model.apply(params, {"tokens": packed, "segment_ids": seg})
    la = model.apply(params, {"tokens": jnp.asarray(seq_a)})
    lb = model.apply(params, {"tokens": jnp.asarray(seq_b)})
    np.testing.assert_allclose(np.asarray(lp[:, :16]), np.asarray(la),
                               rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(np.asarray(lp[:, 16:]), np.asarray(lb),
                               rtol=2e-4, atol=2e-5)


def test_loss_ignores_negative_labels():
    """Packed boundaries mark cross-segment targets -100; the LM loss
    must average over valid tokens only."""
    from model_zoo.transformer_lm.transformer_lm import loss

    rs = np.random.RandomState(1)
    logits = jnp.asarray(rs.randn(2, 4, 8).astype(np.float32))
    labels = jnp.asarray([[1, 2, 3, 4], [5, 6, 7, 0]], jnp.int32)
    base = loss(labels, logits)
    # masking one target changes the average over the REMAINING ones
    masked = labels.at[0, 1].set(-100)
    got = loss(masked, logits)
    import optax as _optax
    tok = _optax.softmax_cross_entropy_with_integer_labels(
        logits, labels
    )
    row0 = (tok[0, [0, 2, 3]].mean(), tok[1].mean())
    np.testing.assert_allclose(
        float(got), float((row0[0] + row0[1]) / 2), rtol=1e-6
    )
    assert not np.isclose(float(base), float(got))


@pytest.mark.parametrize("causal", [False, True])
def test_flash_rectangular_segment_pair(causal):
    """The (q_seg, k_seg) pair form on rectangular shapes — one ring
    rotation's geometry — through the Pallas kernels, vs the oracle.
    Rows with NO matching key in the k shard (ids 9) must come back
    EXACTLY 0 on both the Pallas and blockwise-fallback backends (the
    public contract), and flagged with the lse sentinel on the
    attention_forward_lse surface ring merges consume."""
    from elasticdl_tpu.ops import attention as attn_mod
    from elasticdl_tpu.ops.attention import attention_forward_lse

    rs = np.random.RandomState(21)
    q = jnp.asarray(rs.randn(2, 2, 32, 128).astype(np.float32) * 0.3)
    k = jnp.asarray(rs.randn(2, 2, 16, 128).astype(np.float32) * 0.3)
    v = jnp.asarray(rs.randn(2, 2, 16, 128).astype(np.float32) * 0.3)
    q_seg = jnp.asarray(
        np.concatenate([np.zeros((2, 12)), np.full((2, 10), 1),
                        np.full((2, 10), 9)], axis=1), jnp.int32)
    k_seg = jnp.asarray(
        np.concatenate([np.zeros((2, 8)), np.ones((2, 8))], axis=1),
        jnp.int32)
    ref = naive_attention(q, k, v, causal=causal,
                          segments=(q_seg, k_seg))
    out = flash_attention(q, k, v, causal=causal, block_q=16,
                          block_k=16, segments=(q_seg, k_seg))
    # blockwise fallback backend (block sizes that do not tile)
    out_bw = flash_attention(q, k, v, causal=causal, block_q=24,
                             block_k=24, segments=(q_seg, k_seg))
    visible = np.asarray(q_seg[0]) != 9
    for got in (out, out_bw):
        np.testing.assert_allclose(
            np.asarray(got)[:, :, visible],
            np.asarray(ref)[:, :, visible],
            rtol=1e-4, atol=1e-5,
        )
        np.testing.assert_array_equal(
            np.asarray(got)[:, :, ~visible], 0.0
        )
    out_lse, lse = attention_forward_lse(
        q, k, v, causal=causal, block_q=16, block_k=16,
        segments=(q_seg, k_seg)
    )
    np.testing.assert_allclose(
        np.asarray(out_lse)[:, :, visible],
        np.asarray(ref)[:, :, visible], rtol=1e-4, atol=1e-5,
    )
    masked_lse = np.asarray(lse)[:, :, ~visible]
    np.testing.assert_array_equal(
        masked_lse, np.float32(attn_mod._NEG_INF)
    )


@pytest.mark.parametrize("causal,window", [(True, None), (True, 16),
                                           (False, 16)])
@pytest.mark.slow
def test_cond_mask_matches_default(monkeypatch, causal, window):
    """EDL_FLASH_COND_MASK=1 branches the per-element mask out of
    interior blocks; outputs and gradients must equal the default
    straight-line-select path exactly."""
    rs = np.random.RandomState(77)
    q = jnp.asarray(rs.randn(2, 2, 64, 128).astype(np.float32) * 0.3)
    k = jnp.asarray(rs.randn(2, 2, 64, 128).astype(np.float32) * 0.3)
    v = jnp.asarray(rs.randn(2, 2, 64, 128).astype(np.float32) * 0.3)

    def run():
        def loss(q, k, v):
            return (flash_attention(
                q, k, v, causal=causal, window=window,
                block_q=16, block_k=16,
            ) ** 2).sum()

        out = flash_attention(q, k, v, causal=causal, window=window,
                              block_q=16, block_k=16)
        grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
        return out, grads

    monkeypatch.delenv("EDL_FLASH_COND_MASK", raising=False)
    out_ref, g_ref = run()
    monkeypatch.setenv("EDL_FLASH_COND_MASK", "1")
    out_cond, g_cond = run()
    np.testing.assert_array_equal(np.asarray(out_ref),
                                  np.asarray(out_cond))
    for a, b in zip(g_ref, g_cond):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_stream_clamps_cover_every_running_block():
    """Property: the DMA-clamp ranges (which pin out-of-mask streamed
    blocks to a resident index) must contain EVERY block the kernels
    actually compute on — a clamp that excludes a run=True step would
    silently feed the wrong k/v (or q) tile. Brute-forced against
    _block_run over causal x window x block sizes x ring offsets."""
    from elasticdl_tpu.ops.attention import (
        _block_run,
        _kv_stream_clamp,
        _q_stream_clamp,
    )

    cases = 0
    for causal in (False, True):
        for window in (None, 8, 24, 64):
            for block_q, block_k in ((16, 16), (16, 32), (32, 16),
                                     (8, 64)):
                for lq, lk in ((64, 64), (128, 64), (64, 128)):
                    # offsets include fully-masked geometries (ring
                    # rotations where no block runs) on purpose: the
                    # clamps must still emit valid indices there
                    for pos_offset in (0, -64, 64, lk):
                        n_q, n_k = lq // block_q, lk // block_k
                        kv_cl = _kv_stream_clamp(
                            causal, window, block_q, block_k, n_k,
                            pos_offset,
                        )
                        q_cl = _q_stream_clamp(
                            causal, window, block_q, block_k, n_q,
                            pos_offset,
                        )
                        if kv_cl is None:
                            assert not causal and window is None
                            continue
                        for qi in range(n_q):
                            for ki in range(n_k):
                                if not bool(_block_run(
                                        qi, ki, block_q, block_k,
                                        causal, window, pos_offset)):
                                    continue
                                # a computing step must read its TRUE
                                # block on both streamed sides
                                assert int(kv_cl(qi, ki)) == ki, (
                                    causal, window, block_q, block_k,
                                    lq, lk, pos_offset, qi, ki,
                                )
                                assert int(q_cl(ki, qi)) == qi, (
                                    causal, window, block_q, block_k,
                                    lq, lk, pos_offset, qi, ki,
                                )
                                cases += 1
                        # and every clamped index is a valid block
                        for qi in range(n_q):
                            for t in range(n_k):
                                assert 0 <= int(kv_cl(qi, t)) < n_k
                        for ki in range(n_k):
                            for t in range(n_q):
                                assert 0 <= int(q_cl(ki, t)) < n_q
    assert cases > 1000  # the sweep actually exercised running blocks


def _packed_seg_for_ring(b, l, seed=31):
    """Packing whose segments CROSS shard boundaries on an 8-way ring
    (l=64 -> 8-token shards; cuts not at multiples of 8)."""
    rs = np.random.RandomState(seed)
    seg = np.zeros((b, l), np.int32)
    for r in range(b):
        cuts = sorted(rs.choice(np.arange(3, l - 1), size=3,
                                replace=False))
        sid, prev = 0, 0
        for c in list(cuts) + [l]:
            seg[r, prev:c] = sid
            sid, prev = sid + 1, c
    return jnp.asarray(seg)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.slow
def test_ring_attention_segments(causal):
    """Packed long-context: ring attention with sequence-sharded
    segment ids (k-side ids rotate with their shard) vs the oracle."""
    mesh = mesh_lib.build_mesh({"sp": 8})
    q, k, v = _qkv(24)
    seg = _packed_seg_for_ring(B, L)
    ref = naive_attention(q, k, v, causal=causal, segments=seg)
    out = ring_attention(q, k, v, mesh, causal=causal, segments=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_ring_attention_segments_gradients():
    mesh = mesh_lib.build_mesh({"sp": 8})
    q, k, v = _qkv(25)
    seg = _packed_seg_for_ring(B, L, seed=32)

    def loss_ring(q, k, v):
        return ring_attention(q, k, v, mesh, causal=True,
                              segments=seg).sum()

    def loss_ref(q, k, v):
        return naive_attention(q, k, v, causal=True,
                               segments=seg).sum()

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr_, gn in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr_), np.asarray(gn),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_ulysses_attention_segments(causal):
    from elasticdl_tpu.parallel.context_parallel import ulysses_attention

    mesh = mesh_lib.build_mesh({"dp": 4, "sp": 2})
    rs = np.random.RandomState(26)
    mk = lambda: jnp.asarray(rs.randn(4, 2, L, D).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    seg = _packed_seg_for_ring(4, L, seed=33)
    ref = naive_attention(q, k, v, causal=causal, segments=seg)
    out = ulysses_attention(q, k, v, mesh, causal=causal, segments=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_flash_rectangular_pair_gradients():
    """Backward through the rectangular (q_seg, k_seg) pair with rows
    whose segment id is absent from the k shard: (a) with the masked
    rows excluded from the loss (how packed losses behave), kernel
    grads match the oracle; (b) with them included, grads stay finite
    and the masked rows contribute ZERO (the -1e30-class lse rows are
    forced to p=0 in both backward kernels — without that they would
    contaminate dk/dv with p=1 garbage)."""
    rs = np.random.RandomState(41)
    q = jnp.asarray(rs.randn(2, 2, 32, 128).astype(np.float32) * 0.3)
    k = jnp.asarray(rs.randn(2, 2, 16, 128).astype(np.float32) * 0.3)
    v = jnp.asarray(rs.randn(2, 2, 16, 128).astype(np.float32) * 0.3)
    q_seg = jnp.asarray(
        np.concatenate([np.zeros((2, 12)), np.ones((2, 10)),
                        np.full((2, 10), 9)], axis=1), jnp.int32)
    k_seg = jnp.asarray(
        np.concatenate([np.zeros((2, 8)), np.ones((2, 8))], axis=1),
        jnp.int32)
    visible = jnp.asarray((np.asarray(q_seg) != 9)[:, None, :, None])

    def loss_flash(q, k, v):
        out = flash_attention(q, k, v, block_q=16, block_k=16,
                              segments=(q_seg, k_seg))
        return (jnp.where(visible, out, 0.0) ** 2).sum()

    def loss_ref(q, k, v):
        out = naive_attention(q, k, v, segments=(q_seg, k_seg))
        return (jnp.where(visible, out, 0.0) ** 2).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(gf), np.asarray(gr),
                                   rtol=1e-3, atol=1e-4)

    # (b) loss reads every row, masked included: finite grads, zero
    # contribution from the fully-masked rows
    def loss_all(q, k, v):
        return (flash_attention(q, k, v, block_q=16, block_k=16,
                                segments=(q_seg, k_seg)) ** 2).sum()

    dq, dk, dv = jax.grad(loss_all, argnums=(0, 1, 2))(q, k, v)
    for g_ in (dq, dk, dv):
        assert np.isfinite(np.asarray(g_)).all()
    masked_dq = np.asarray(dq)[:, :, np.asarray(q_seg[0]) == 9]
    np.testing.assert_array_equal(masked_dq, 0.0)


@pytest.mark.slow
def test_flash_config_fuzz_vs_oracle(monkeypatch):
    """Seeded sweep across the kernel config lattice (causal x window x
    GQA x segments x block sizes x rectangular shapes x cond-mask) in
    interpret mode vs the naive oracle — forward always, gradients on a
    subset. Catches interaction bugs no single-feature test exercises."""
    rs = np.random.RandomState(123)
    for trial in range(10):
        monkeypatch.setenv(
            "EDL_FLASH_COND_MASK", "1" if rs.randint(2) else ""
        )
        causal = bool(rs.randint(2))
        lq = int(rs.choice([16, 32, 48]))
        rect = (not causal) and rs.randint(2)
        lk = int(rs.choice([16, 32])) if rect else lq
        h = int(rs.choice([2, 4]))
        hkv = int(rs.choice([g for g in (1, 2, h) if h % g == 0]))
        window = None
        if not rect and rs.randint(2):
            window = int(rs.choice([4, 8, lq]))
        use_seg = bool(rs.randint(2)) and not rect
        bq = int(rs.choice([8, 16, 32]))
        bk = int(rs.choice([8, 16]))
        q = jnp.asarray(rs.randn(2, h, lq, 128).astype(np.float32) * .3)
        k = jnp.asarray(
            rs.randn(2, hkv, lk, 128).astype(np.float32) * .3)
        v = jnp.asarray(
            rs.randn(2, hkv, lk, 128).astype(np.float32) * .3)
        seg = None
        if use_seg:
            cuts = np.sort(rs.choice(np.arange(2, lq - 1), size=2,
                                     replace=False))
            s = np.zeros((2, lq), np.int32)
            s[:, cuts[0]:cuts[1]] = 1
            s[:, cuts[1]:] = 2
            seg = jnp.asarray(s)
        tag = ("trial=%d causal=%s lq=%d lk=%d hkv=%d window=%s "
               "seg=%s bq=%d bk=%d"
               % (trial, causal, lq, lk, hkv, window, use_seg, bq, bk))
        ref = naive_attention(q, k, v, causal=causal, window=window,
                              segments=seg)
        out = flash_attention(q, k, v, causal=causal, window=window,
                              block_q=bq, block_k=bk, segments=seg)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-5, err_msg=tag)
        if trial % 3 == 0:
            def lf(q, k, v):
                return (flash_attention(
                    q, k, v, causal=causal, window=window,
                    block_q=bq, block_k=bk, segments=seg) ** 2).sum()

            def lr(q, k, v):
                return (naive_attention(
                    q, k, v, causal=causal, window=window,
                    segments=seg) ** 2).sum()

            gf = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
            gr = jax.grad(lr, argnums=(0, 1, 2))(q, k, v)
            for a, b_ in zip(gf, gr):
                np.testing.assert_allclose(
                    np.asarray(a), np.asarray(b_), rtol=1e-3,
                    atol=1e-4, err_msg=tag)


@pytest.mark.parametrize("window", [4, 13, 24, 64])
@pytest.mark.slow
def test_ring_attention_window(window):
    """Causal sliding-window through the ring: rotation r applies the
    local window mask at static offset r*shard_len (causal auto-holds
    off-diagonal), band-empty rotations skip. Windows smaller than,
    straddling, and larger than the 8-token shards, vs the global
    oracle."""
    mesh = mesh_lib.build_mesh({"sp": 8})
    q, k, v = _qkv(51)
    ref = naive_attention(q, k, v, causal=True, window=window)
    out = ring_attention(q, k, v, mesh, causal=True, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_ring_attention_window_gradients():
    mesh = mesh_lib.build_mesh({"sp": 8})
    q, k, v = _qkv(52)

    def loss_ring(q, k, v):
        return ring_attention(q, k, v, mesh, causal=True,
                              window=13).sum()

    def loss_ref(q, k, v):
        return naive_attention(q, k, v, causal=True, window=13).sum()

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr_, gn in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr_), np.asarray(gn),
                                   rtol=1e-3, atol=1e-4)


@pytest.mark.slow
def test_ring_attention_window_with_segments():
    """Window AND packing compose through the ring."""
    mesh = mesh_lib.build_mesh({"sp": 8})
    q, k, v = _qkv(53)
    seg = _packed_seg_for_ring(B, L, seed=54)
    ref = naive_attention(q, k, v, causal=True, window=13,
                          segments=seg)
    out = ring_attention(q, k, v, mesh, causal=True, window=13,
                         segments=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("window", [4, 13, 30])
@pytest.mark.slow
def test_ring_attention_window_noncausal(window):
    """Two-sided (encoder) windows through the ring: signed-offset
    branches cover shards on BOTH sides of the diagonal; out-of-band
    rotations skip."""
    mesh = mesh_lib.build_mesh({"sp": 8})
    q, k, v = _qkv(55)
    ref = naive_attention(q, k, v, causal=False, window=window)
    out = ring_attention(q, k, v, mesh, causal=False, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_ring_attention_window_noncausal_gradients():
    mesh = mesh_lib.build_mesh({"sp": 8})
    q, k, v = _qkv(57)

    def loss_ring(q, k, v):
        return ring_attention(q, k, v, mesh, causal=False,
                              window=11).sum()

    def loss_ref(q, k, v):
        return naive_attention(q, k, v, causal=False, window=11).sum()

    g_ring = jax.jit(jax.grad(loss_ring, argnums=(0, 1, 2)))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gr_, gn in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(gr_), np.asarray(gn),
                                   rtol=1e-3, atol=1e-4)


def test_ulysses_attention_window():
    from elasticdl_tpu.parallel.context_parallel import ulysses_attention

    mesh = mesh_lib.build_mesh({"dp": 4, "sp": 2})
    rs = np.random.RandomState(56)
    mk = lambda: jnp.asarray(rs.randn(4, 2, L, D).astype(np.float32))
    q, k, v = mk(), mk(), mk()
    ref = naive_attention(q, k, v, causal=True, window=9)
    out = ulysses_attention(q, k, v, mesh, causal=True, window=9)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.slow
def test_ring_attention_window_noncausal_with_segments():
    """Two-sided window AND packing compose through the non-causal
    ring (the BertEncoder attn_window + packed path)."""
    mesh = mesh_lib.build_mesh({"sp": 8})
    q, k, v = _qkv(58)
    seg = _packed_seg_for_ring(B, L, seed=59)
    ref = naive_attention(q, k, v, causal=False, window=11,
                          segments=seg)
    out = ring_attention(q, k, v, mesh, causal=False, window=11,
                         segments=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-4, atol=1e-5)


def test_flash_bf16_matches_oracle_fwd_and_grads():
    """bf16 inputs are the ONLY dtype where _mxu_cast changes numerics
    (softmax weights / ds rounded to bf16 so p@V, ds@K, p@dO run at
    MXU bf16 rate) — so the bf16 path gets its own fwd+grad oracle
    check at bf16 tolerances (f32 tests are no-ops through the cast)."""
    rs = np.random.RandomState(11)
    mk = lambda: jnp.asarray(
        rs.randn(2, 2, 64, 128).astype(np.float32) * 0.3
    ).astype(jnp.bfloat16)
    q, k, v = mk(), mk(), mk()
    cot = jnp.asarray(
        rs.randn(2, 2, 64, 128).astype(np.float32) * 0.5
    )

    def f32(t):
        return t.astype(jnp.float32)

    out = flash_attention(q, k, v, causal=True, block_q=16, block_k=16)
    ref = naive_attention(f32(q), f32(k), f32(v), causal=True)
    np.testing.assert_allclose(
        np.asarray(f32(out)), np.asarray(ref), rtol=0.05, atol=0.02
    )

    def loss_flash(q, k, v):
        return jnp.sum(f32(flash_attention(
            q, k, v, causal=True, block_q=16, block_k=16)) * cot)

    def loss_ref(q, k, v):
        return jnp.sum(naive_attention(f32(q), f32(k), f32(v),
                                       causal=True) * cot)

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for gf, gr, name in zip(g_flash, g_ref, "qkv"):
        np.testing.assert_allclose(
            np.asarray(f32(gf)), np.asarray(f32(gr)),
            rtol=0.1, atol=0.05,
            err_msg="bf16 flash grad d%s diverges from oracle" % name,
        )


def test_fully_masked_rows_chunked_matches_one_shot():
    """The chunked (fori_loop) visibility reduction must equal the
    single fused expression for every mask flavor, including ragged
    final chunks."""
    from elasticdl_tpu.ops.attention import _fully_masked_rows

    rs = np.random.RandomState(3)
    q_seg = jnp.asarray(rs.randint(0, 4, (2, 45)))
    k_seg = jnp.asarray(rs.randint(0, 4, (2, 83)))
    for causal in (False, True):
        for window in (None, 9):
            one = _fully_masked_rows(q_seg, k_seg, causal, window,
                                     45, 83)
            chunked = _fully_masked_rows(q_seg, k_seg, causal, window,
                                         45, 83, chunk=32)
            np.testing.assert_array_equal(np.asarray(one),
                                          np.asarray(chunked))


# ------------------------------------------- fused paged decode kernel
#
# The CPU interpret=True parity battery for _paged_decode_fused (the
# autouse fixture above sets FORCE_INTERPRET=1, so use_kernel=True runs
# the REAL kernel body through the Pallas interpreter). Two oracles:
# the lax.scan path of paged_decode_attention itself (bit-for-bit the
# shared masks/merge, only reduction order differs) and naive_attention
# over the logically contiguous cache (independent math). Every case
# includes a drop-lane row (length=0, all-(-1) table) — the masked
# lanes the serving engine scatters between seated requests.


def _rowquant(rows):
    """Symmetric per-row int8 + f32 scale, matching the serving
    quantizer's layout (scale leaf on the trailing axis)."""
    sc = (np.abs(rows).max(-1, keepdims=True) / 127.0
          + 1e-8).astype(np.float32)
    q8 = np.clip(np.round(rows / sc), -127, 127).astype(np.int8)
    return q8, sc


def _paged_case(seed, b, h, hkv, t, d, bs, nb, m, quantized):
    """Pools + scattered -1-padded table + current tile; row 0 is the
    drop lane (nothing cached, no blocks)."""
    rs = np.random.RandomState(seed)
    q = rs.randn(b, h, t, d).astype(np.float32)
    k_cur = rs.randn(b, hkv, t, d).astype(np.float32)
    v_cur = rs.randn(b, hkv, t, d).astype(np.float32)
    k_pool = rs.randn(nb, bs, hkv, d).astype(np.float32)
    v_pool = rs.randn(nb, bs, hkv, d).astype(np.float32)
    length = rs.randint(1, m * bs + 1, size=(b,)).astype(np.int32)
    length[0] = 0  # drop lane
    table = np.full((b, m), -1, np.int32)
    order = rs.permutation(nb)
    ptr = 0
    for i in range(b):
        for j in range(-(-int(length[i]) // bs)):
            table[i, j] = order[ptr % nb]
            ptr += 1
    kwargs = dict(window=None)
    if quantized:
        k_pool, ksp = _rowquant(k_pool)
        v_pool, vsp = _rowquant(v_pool)
        k_cur, kcs = _rowquant(k_cur)
        v_cur, vcs = _rowquant(v_cur)
        kwargs.update(
            k_scale_pool=jnp.asarray(ksp), v_scale_pool=jnp.asarray(vsp),
            k_cur_scale=jnp.asarray(kcs), v_cur_scale=jnp.asarray(vcs),
        )
    args = tuple(jnp.asarray(a) for a in
                 (q, k_cur, v_cur, k_pool, v_pool, table, length))
    return args, kwargs


@pytest.mark.parametrize("quantized", (False, True),
                         ids=("fp32", "int8"))
@pytest.mark.parametrize("t", (1, 3))
@pytest.mark.parametrize("window", (None, 5))
@pytest.mark.parametrize("h,hkv", ((4, 4), (4, 2)),
                         ids=("mha", "gqa"))
def test_paged_fused_matches_scan_oracle(h, hkv, window, t, quantized):
    """use_kernel=True vs use_kernel=False on identical inputs: the
    two paths share _paged_valid/_tile_causal_mask and the tile merge,
    so any drift is a kernel bug, not a mask disagreement. t=1 runs
    the legacy [b, h, d] squeeze shape."""
    from elasticdl_tpu.ops.attention import paged_decode_attention

    args, kwargs = _paged_case(
        seed=17 * t + hkv, b=3, h=h, hkv=hkv, t=t, d=8, bs=4, nb=12,
        m=3, quantized=quantized,
    )
    kwargs["window"] = window
    if t == 1:  # legacy single-token shape (and its scale shapes)
        q, k_cur, v_cur = (a[:, :, 0] for a in args[:3])
        args = (q, k_cur, v_cur) + args[3:]
        for key in ("k_cur_scale", "v_cur_scale"):
            if key in kwargs:
                kwargs[key] = kwargs[key][:, :, 0]
    scan = paged_decode_attention(*args, use_kernel=False, **kwargs)
    fused = paged_decode_attention(*args, use_kernel=True, **kwargs)
    assert fused.shape == scan.shape
    np.testing.assert_allclose(
        np.asarray(fused), np.asarray(scan), rtol=2e-5, atol=2e-5,
        err_msg="h=%d hkv=%d window=%r t=%d quantized=%r"
                % (h, hkv, window, t, quantized),
    )


@pytest.mark.parametrize("quantized", (False, True),
                         ids=("fp32", "int8"))
@pytest.mark.parametrize("window", (None, 4))
def test_paged_fused_matches_naive(window, quantized):
    """Independent oracle: gather each row's cache contiguously
    (table order, dequantized for int8 — the kernel's in-register
    dequant is exact, so parity carries no quantization slack), append
    the current tile, and run naive_attention causally over the full
    sequence; the last t rows must equal the fused output."""
    from elasticdl_tpu.ops.attention import paged_decode_attention

    b, h, hkv, t, d, bs, nb, m = 3, 4, 2, 3, 8, 4, 12, 3
    args, kwargs = _paged_case(
        seed=5 if quantized else 6, b=b, h=h, hkv=hkv, t=t, d=d,
        bs=bs, nb=nb, m=m, quantized=quantized,
    )
    kwargs["window"] = window
    fused = np.asarray(
        paged_decode_attention(*args, use_kernel=True, **kwargs)
    )
    q, k_cur, v_cur, k_pool, v_pool, table, length = (
        np.asarray(a) for a in args
    )
    if quantized:
        k_pool = k_pool * np.asarray(kwargs["k_scale_pool"])
        v_pool = v_pool * np.asarray(kwargs["v_scale_pool"])
        k_cur = k_cur * np.asarray(kwargs["k_cur_scale"])
        v_cur = v_cur * np.asarray(kwargs["v_cur_scale"])
    for i in range(b):
        ln = int(length[i])
        rows_k = np.concatenate(
            [k_pool[bid] for bid in table[i] if bid >= 0]
            or [np.zeros((0, bs, hkv, d), np.float32).reshape(0, hkv, d)]
        )[:ln]
        rows_v = np.concatenate(
            [v_pool[bid] for bid in table[i] if bid >= 0]
            or [np.zeros((0, bs, hkv, d), np.float32).reshape(0, hkv, d)]
        )[:ln]
        # [ln + t, hkv, d] -> [1, hkv, ln + t, d]
        keys = np.concatenate(
            [rows_k, k_cur[i].transpose(1, 0, 2)]
        ).transpose(1, 0, 2)[None]
        vals = np.concatenate(
            [rows_v, v_cur[i].transpose(1, 0, 2)]
        ).transpose(1, 0, 2)[None]
        # tail-align the tile in a full-length causal query: rows
        # [ln, ln + t) get the tile's queries (the prefix rows carry
        # zeros — their outputs are ignored), so naive's square causal
        # + window mask at those rows IS the decode visibility
        q_full = np.zeros((1, h, ln + t, d), np.float32)
        q_full[:, :, ln:] = q[i]
        ref = np.asarray(naive_attention(
            jnp.asarray(q_full), jnp.asarray(keys), jnp.asarray(vals),
            causal=True, window=window, scale=d ** -0.5,
        ))[0, :, ln:]
        np.testing.assert_allclose(
            fused[i], ref, rtol=2e-5, atol=2e-5,
            err_msg="row %d window=%r int8=%r" % (i, window, quantized),
        )


# --------------------------------- the stream follows the live range
#
# _paged_kernel streams the table slots [j_lo, j_hi) in reach of a
# sequence (paged_live_blocks), _paged_trip_blocks of them a trip. The
# battery pins a trip at 3 blocks over a table of 8 (4-token blocks) so
# that lengths fall short of, on and past a trip's end, and a window
# starts the range inside a trip and on a trip's first block.

_S_BS, _S_M, _S_HKV, _S_GROUP, _S_D, _S_TRIP = 4, 8, 2, 2, 8, 3


@pytest.fixture
def three_block_trips(monkeypatch):
    from elasticdl_tpu.ops import attention

    monkeypatch.setattr(attention, "_PAGED_TRIP_ROWS",
                        _S_TRIP * _S_BS * _S_HKV)
    assert attention._paged_trip_blocks(8, _S_BS * _S_HKV, _S_M) == _S_TRIP


def _stream_case(lengths, t, arena, seed=0):
    """Operands for paged_decode_attention over ragged `lengths`: each
    sequence owns ceil(length / bs) scattered blocks, the rest of its
    table row is -1, and no sequence owns block 0 (where -1 clamps).
    `arena` is "bf16" or "int8"."""
    rs = np.random.RandomState(seed)
    b, h = len(lengths), _S_HKV * _S_GROUP
    nb = 1 + b * _S_M
    q = rs.randn(b, h, t, _S_D).astype(np.float32)
    cur = [rs.randn(b, _S_HKV, t, _S_D).astype(np.float32)
           for _ in range(2)]
    pools = [rs.randn(nb, _S_BS, _S_HKV, _S_D).astype(np.float32)
             for _ in range(2)]
    table = np.full((b, _S_M), -1, np.int32)
    order = 1 + rs.permutation(nb - 1)
    for i, ln in enumerate(lengths):
        used = -(-int(ln) // _S_BS)
        table[i, :used] = order[i * _S_M:i * _S_M + used]
    kwargs = {}
    if arena == "int8":
        (k_cur, kcs), (v_cur, vcs) = (_rowquant(x) for x in cur)
        (k_pool, ksp), (v_pool, vsp) = (_rowquant(x) for x in pools)
        kwargs = dict(
            k_scale_pool=jnp.asarray(ksp), v_scale_pool=jnp.asarray(vsp),
            k_cur_scale=jnp.asarray(kcs), v_cur_scale=jnp.asarray(vcs),
        )
        arrays = (q, k_cur, v_cur, k_pool, v_pool)
    else:
        arrays = tuple(jnp.asarray(x, jnp.bfloat16)
                       for x in (q, *cur, *pools))
    args = tuple(jnp.asarray(a) for a in arrays) + (
        jnp.asarray(table), jnp.asarray(lengths, jnp.int32))
    return args, kwargs


def _both_paths(args, kwargs, window, vmapped=False):
    """(scan, fused) results, bare over the batch or — as the serving
    engine calls it — one sequence a call under jax.vmap over slots,
    the pools closed over."""
    from elasticdl_tpu.ops.attention import paged_decode_attention

    def attend(use_kernel, *a, **kw):
        return paged_decode_attention(*a, use_kernel=use_kernel,
                                      window=window, **kw)

    if not vmapped:
        return tuple(attend(use, *args, **kwargs) for use in (False, True))
    q, k_cur, v_cur, k_pool, v_pool, table, length = args
    cur = {k: v for k, v in kwargs.items() if "cur" in k}
    pools = {k: v for k, v in kwargs.items() if "pool" in k}

    def lanes(use_kernel):
        def one(q1, k1, v1, tbl1, len1, cur1):
            return attend(
                use_kernel, q1[None], k1[None], v1[None], k_pool, v_pool,
                tbl1[None], len1[None], **pools,
                **{k: v[None] for k, v in cur1.items()})[0]

        return jax.vmap(one)(q, k_cur, v_cur, table, length, cur)

    return lanes(False), lanes(True)


def _assert_paths_agree(args, kwargs, window, vmapped=False):
    scan, fused = _both_paths(args, kwargs, window, vmapped)
    assert fused.shape == scan.shape
    assert np.isfinite(np.asarray(fused)).all()
    np.testing.assert_allclose(np.asarray(fused), np.asarray(scan),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("arena", ("bf16", "int8"))
@pytest.mark.parametrize("t", (1, 8))
@pytest.mark.parametrize("length", (
    0, 1, _S_BS - 1, _S_BS, _S_TRIP * _S_BS - 1, _S_TRIP * _S_BS,
    _S_TRIP * _S_BS + 1, _S_M * _S_BS))
def test_paged_stream_ends_where_the_sequence_does(
        length, t, arena, three_block_trips):
    """No trip, part of one, exactly one, one and a block, and the
    whole table: a sequence beside one of another length."""
    args, kwargs = _stream_case([length, 2 * _S_TRIP * _S_BS - 2], t,
                                arena, seed=length)
    _assert_paths_agree(args, kwargs, window=None)


@pytest.mark.parametrize("arena", ("bf16", "int8"))
@pytest.mark.parametrize("t", (1, 8))
@pytest.mark.parametrize("length,window,j_lo", (
    (30, 9, 5), (30, 18, 3), (32, 32, 0), (17, 2, 4), (9, 1, 2)),
    ids=("inside-a-trip", "a-trips-first-block", "window-is-the-table",
         "two-keys", "no-key"))
def test_paged_stream_starts_where_the_window_does(
        length, window, j_lo, t, arena, three_block_trips):
    from elasticdl_tpu.ops.attention import paged_live_blocks

    lo, hi = paged_live_blocks(np.int32(length), window, _S_BS, _S_M,
                               xp=np)
    assert (lo, hi) == (j_lo, -(-length // _S_BS))
    args, kwargs = _stream_case([length, 21], t, arena, seed=window)
    _assert_paths_agree(args, kwargs, window=window)


@pytest.mark.parametrize("arena", ("bf16", "int8"))
@pytest.mark.parametrize("t", (1, 8))
@pytest.mark.parametrize("window", (None, 9))
def test_paged_stream_under_vmap_over_ragged_slots(
        window, t, arena, three_block_trips):
    """One call a slot, as the engine's step makes them: ragged
    lengths, a free lane (length 0, table row all -1) among them."""
    args, kwargs = _stream_case([13, 0, 32, 5, 24], t, arena, seed=t)
    assert (np.asarray(args[5])[1] == -1).all()
    _assert_paths_agree(args, kwargs, window, vmapped=True)


@pytest.mark.parametrize("window", (None, 9))
@pytest.mark.parametrize("vmapped", (False, True), ids=("bare", "vmap"))
def test_paged_stream_never_reads_outside_the_live_range(
        window, vmapped, three_block_trips):
    """Every arena block outside its sequence's [j_lo, j_hi) holds
    NaN — the blocks a window has left behind, block 0 (where a -1
    clamps) and every block no table names: the fused result must
    equal the clean pool's, the scan's turns NaN (it streams the whole
    table and masks)."""
    from elasticdl_tpu.ops.attention import paged_live_blocks

    lengths = [30, 0, 13, 32]
    args, kwargs = _stream_case(lengths, 1, "bf16", seed=3)
    clean, _ = _both_paths(args, kwargs, window, vmapped)
    table = np.asarray(args[5])
    lo, hi = paged_live_blocks(np.asarray(lengths), window, _S_BS, _S_M,
                               xp=np)
    live = np.concatenate([table[i, lo[i]:hi[i]] for i in range(len(lengths))])
    assert (live > 0).all()
    dead = np.setdiff1d(np.arange(args[3].shape[0]), live)
    assert 0 in dead and len(dead) > len(lengths)
    poisoned = [np.array(pool, np.float32) for pool in args[3:5]]
    for pool in poisoned:
        pool[dead] = np.nan
    args = args[:3] + tuple(
        jnp.asarray(pool, jnp.bfloat16) for pool in poisoned) + args[5:]
    scan, fused = _both_paths(args, kwargs, window, vmapped)
    np.testing.assert_allclose(np.asarray(fused), np.asarray(clean),
                               rtol=2e-5, atol=2e-5)
    assert np.isnan(np.asarray(scan)).any()


@pytest.mark.parametrize("seed", range(4))
def test_paged_live_blocks_against_the_predicate(seed):
    """Brute force over random (length, tile, window, block size, table
    width): every block in which _paged_valid admits a key for some
    tile row lies inside [j_lo, j_hi), and — with no window or one of
    at least 2 — the range's first and last blocks each hold one."""
    from elasticdl_tpu.ops.attention import _paged_valid, paged_live_blocks

    rs = np.random.RandomState(seed)
    for _ in range(300):
        bs, m = int(rs.randint(1, 9)), int(rs.randint(1, 12))
        length = int(rs.randint(0, m * bs + 1))
        t = int(rs.randint(1, 10))
        window = None if rs.rand() < 0.3 else int(rs.randint(1, m * bs + 3))
        k_pos = np.arange(m * bs)[None, :]
        row_pos = length + np.arange(t)[:, None]
        seen = np.asarray(_paged_valid(k_pos, 1, length, row_pos, window))
        seen = seen.any(0).reshape(m, bs).any(1)  # [m]: a visible key
        j_lo, j_hi = (int(j) for j in paged_live_blocks(
            np.int32(length), window, bs, m, xp=np))
        case = (length, t, window, bs, m, j_lo, j_hi)
        assert 0 <= j_lo <= j_hi <= m, case
        assert not seen[:j_lo].any() and not seen[j_hi:].any(), case
        if j_hi > j_lo and (window is None or window >= 2):
            assert seen[j_lo] and seen[j_hi - 1], case
        if length == 0:
            assert j_lo == j_hi == 0, case
