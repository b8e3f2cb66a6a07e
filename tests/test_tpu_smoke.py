"""Hardware smoke tests: every Pallas kernel compiled (interpret=False)
on a real TPU chip, checked against the pure-jnp oracles.

Skipped under the default CPU test rig (tests/conftest.py pins
JAX_PLATFORMS=cpu). Run on hardware with:

    EDL_TPU_TEST_PLATFORM=tpu python -m pytest tests/test_tpu_smoke.py -q

Mosaic can reject shapes the Pallas interpreter accepts, so
interpreter-mode coverage (tests/test_ops.py, tests/test_attention.py)
does not prove these kernels run where it counts. This module is that
proof; scripts/build_and_test.sh runs it when a TPU is reachable.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

if jax.default_backend() != "tpu":  # pragma: no cover - rig-dependent
    pytest.skip(
        "TPU hardware smoke tests need a real chip "
        "(EDL_TPU_TEST_PLATFORM=tpu)",
        allow_module_level=True,
    )

from elasticdl_tpu.ops import attention, embedding_ops, optimizer_kernels
from elasticdl_tpu.ops import update_math as um


def _rand(rng, *shape, dtype=np.float32):
    return rng.standard_normal(shape).astype(dtype)


# ------------------------------------------------------------- attention


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_compiled(causal, dtype):
    rng = np.random.default_rng(0)
    b, h, seq, d = 2, 4, 256, 64
    q = jnp.asarray(_rand(rng, b, h, seq, d), dtype)
    k = jnp.asarray(_rand(rng, b, h, seq, d), dtype)
    v = jnp.asarray(_rand(rng, b, h, seq, d), dtype)
    out = attention.flash_attention(q, k, v, causal=causal,
                                    interpret=False)
    oracle = attention.naive_attention(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), causal=causal,
    )
    # fp32 matmuls on the MXU use bf16 multiply passes under default
    # precision, so even fp32 carries ~1e-3-scale error vs the fp32 oracle.
    tol = 2e-2
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(oracle), atol=tol, rtol=tol
    )


def test_jax_flash_dispatch_compiled():
    """attn_impl='jax_flash' routes to jax's bundled TPU flash kernel;
    values must match the naive oracle (the hardware sweep compares its
    speed against ours — scripts/bench_attention.py)."""
    rng = np.random.default_rng(3)
    b, h, seq, d = 2, 4, 256, 128
    q = jnp.asarray(_rand(rng, b, h, seq, d), jnp.bfloat16)
    k = jnp.asarray(_rand(rng, b, h, seq, d), jnp.bfloat16)
    v = jnp.asarray(_rand(rng, b, h, seq, d), jnp.bfloat16)
    out = attention.jax_flash_attention(q, k, v, causal=True)
    oracle = attention.naive_attention(
        q.astype(jnp.float32), k.astype(jnp.float32),
        v.astype(jnp.float32), causal=True,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(oracle), atol=2e-2,
        rtol=2e-2,
    )


def test_flash_attention_grad_compiled():
    rng = np.random.default_rng(1)
    b, h, seq, d = 1, 2, 128, 64
    q = jnp.asarray(_rand(rng, b, h, seq, d))
    k = jnp.asarray(_rand(rng, b, h, seq, d))
    v = jnp.asarray(_rand(rng, b, h, seq, d))

    def loss_flash(q, k, v):
        return attention.flash_attention(
            q, k, v, causal=True, interpret=False
        ).sum()

    def loss_ref(q, k, v):
        return attention.naive_attention(q, k, v, causal=True).sum()

    grads = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    refs = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(grads, refs):
        # MXU default-precision numerics (see forward test); compare by
        # absolute tolerance only — rtol misfires on near-zero grads.
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), atol=3e-2, rtol=0
        )


@pytest.mark.parametrize("causal", [False, True])
def test_sliding_window_compiled(causal):
    """Windowed flash fwd + two-pass Pallas bwd compiled on hardware,
    with a window smaller than a block (block-skip predicate active)."""
    rng = np.random.default_rng(5)
    b, h, seq, d = 1, 2, 256, 128
    q = jnp.asarray(_rand(rng, b, h, seq, d))
    k = jnp.asarray(_rand(rng, b, h, seq, d))
    v = jnp.asarray(_rand(rng, b, h, seq, d))
    w = 48

    def loss_flash(q, k, v):
        return (attention.flash_attention(
            q, k, v, causal=causal, window=w, interpret=False
        ) ** 2).sum()

    def loss_ref(q, k, v):
        return (attention.naive_attention(
            q, k, v, causal=causal, window=w
        ) ** 2).sum()

    out = attention.flash_attention(q, k, v, causal=causal, window=w,
                                    interpret=False)
    oracle = attention.naive_attention(q, k, v, causal=causal, window=w)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(oracle), atol=2e-2, rtol=2e-2
    )
    grads = jax.jit(jax.grad(loss_flash, argnums=(0, 1, 2)))(q, k, v)
    refs = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for g, r in zip(grads, refs):
        # the squared loss makes O(5) gradients, and fp32 operands go
        # through the MXU as bf16 passes on both sides: a relative term
        # for the large entries, the absolute one for those near zero
        # (on the v5e: 28 of 65536 entries up to 0.094 off, 1.5%)
        np.testing.assert_allclose(
            np.asarray(g), np.asarray(r), atol=5e-2, rtol=2e-2
        )


def test_rope_flash_compiled():
    """RoPE'd q/k through the compiled flash kernel vs the fp32 oracle."""
    rng = np.random.default_rng(6)
    b, h, seq, d = 1, 2, 256, 128
    q = jnp.asarray(_rand(rng, b, h, seq, d), jnp.bfloat16)
    k = jnp.asarray(_rand(rng, b, h, seq, d), jnp.bfloat16)
    v = jnp.asarray(_rand(rng, b, h, seq, d), jnp.bfloat16)
    pos = jnp.arange(seq)
    qr = attention.apply_rope(q, pos)
    kr = attention.apply_rope(k, pos)
    out = attention.flash_attention(qr, kr, v, causal=True,
                                    interpret=False)
    oracle = attention.naive_attention(
        attention.apply_rope(q.astype(jnp.float32), pos),
        attention.apply_rope(k.astype(jnp.float32), pos),
        v.astype(jnp.float32), causal=True,
    )
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(oracle),
        atol=2e-2, rtol=2e-2,
    )


# ----------------------------------------------------------- paged decode


def _paged_case(rng, b, t, int8, hkv=8, group=1, d=128, bs=16, m=6,
                num_blocks=32, lengths=None):
    """Operands for paged_decode_attention at the flagship serving
    shape: every sequence owns a distinct run of table slots, the last
    slots are unallocated (-1) and the cached lengths end mid-block.
    With `lengths` each sequence owns the blocks its length needs."""
    h = hkv * group
    dtype = jnp.bfloat16

    def rows(*shape):
        x = _rand(rng, *shape)
        if not int8:
            return jnp.asarray(x, dtype), None
        scale = np.abs(x).max(-1, keepdims=True) / 127.0
        return (jnp.asarray(np.round(x / scale), jnp.int8),
                jnp.asarray(scale, jnp.float32))

    q = jnp.asarray(_rand(rng, b, h, t, d), dtype)
    k_cur, k_cur_scale = rows(b, hkv, t, d)
    v_cur, v_cur_scale = rows(b, hkv, t, d)
    k_pool, k_scale_pool = rows(num_blocks, bs, hkv, d)
    v_pool, v_scale_pool = rows(num_blocks, bs, hkv, d)
    table = np.full((b, m), -1, np.int32)
    length = np.zeros((b,), np.int32)
    ids = rng.permutation(num_blocks)
    taken = 0
    for i in range(b):
        if lengths is None:
            used = m - 2 - (i % 2)
            length[i] = used * bs - 5 - i
        else:
            length[i] = lengths[i]
            used = -(-lengths[i] // bs)
        table[i, :used] = ids[taken:taken + used]
        taken += used
    args = (q, k_cur, v_cur, k_pool, v_pool, jnp.asarray(table),
            jnp.asarray(length))
    kwargs = {}
    if int8:
        kwargs = dict(k_scale_pool=k_scale_pool,
                      v_scale_pool=v_scale_pool,
                      k_cur_scale=k_cur_scale, v_cur_scale=v_cur_scale)
    return args, kwargs


#: the benchmark's serving cell (sc2-3b-serve): 2 kv heads x 12 query
#: heads each, 16,384 positions in 16-token blocks, window 4096; a free
#: lane, lengths inside one trip of the stream, past one, past the
#: window (the range starts inside the table)
CELL = dict(hkv=2, group=12, m=1024, num_blocks=704,
            lengths=(0, 72, 500, 2312, 8264))


@pytest.mark.parametrize("vmapped", [False, True])
@pytest.mark.parametrize("t", [1, 8])
@pytest.mark.parametrize("int8", [False, True])
@pytest.mark.parametrize("shape", [{}, CELL], ids=["hkv8-m6", "cell"])
def test_paged_decode_compiled(shape, int8, t, vmapped):
    """The fused paged kernel, compiled, against the lax.scan oracle at
    hkv 8 / d 128 / block 16 and at the serving cell's shape — bare
    over a batch, and under jax.vmap over slots with the pools closed
    over, which is how the serving engine calls it (serving/engine.py
    _build_paged_step)."""
    rng = np.random.default_rng(11)
    args, kwargs = _paged_case(
        rng, b=len(shape.get("lengths", range(4))), t=t, int8=int8,
        **shape)
    window = 4096 if shape else None

    def run(use_kernel):
        def attend(*a, **kw):
            return attention.paged_decode_attention(
                *a, use_kernel=use_kernel, window=window, **kw)

        if not vmapped:
            return jax.jit(attend)(*args, **kwargs)
        q, k_cur, v_cur, k_pool, v_pool, table, length = args
        cur = {k: v for k, v in kwargs.items() if "cur" in k}
        pools = {k: v for k, v in kwargs.items() if "pool" in k}

        def one(q1, k1, v1, tbl1, len1, cur1):
            return attend(
                q1[None], k1[None], v1[None], k_pool, v_pool,
                tbl1[None], len1[None], **pools,
                **{k: v[None] for k, v in cur1.items()},
            )[0]

        return jax.jit(jax.vmap(one))(q, k_cur, v_cur, table, length,
                                      cur)

    out = run(True)
    oracle = run(False)
    assert out.shape == oracle.shape and np.isfinite(
        np.asarray(out)).all()
    # both accumulate in fp32 over the same rows; they differ by the
    # MXU's default-precision passes and the order of the block merge
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(oracle), atol=2e-2, rtol=2e-2
    )


def test_flash_under_four_device_mesh():
    """Flash forward + backward inside a jit over a dp=4 mesh: XLA
    cannot partition a Mosaic kernel, so the model routes through
    context_parallel.sharded_flash_attention (one shard_map program).
    Checked against the same call on one device."""
    if len(jax.devices()) < 4:
        pytest.skip(
            "test_flash_under_four_device_mesh needs 4 chips, this "
            "machine has %d" % len(jax.devices())
        )
    from jax.sharding import NamedSharding, PartitionSpec as P

    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.parallel.context_parallel import (
        sharded_flash_attention,
    )

    mesh = mesh_lib.build_mesh("dp=4", devices=jax.devices()[:4])
    rng = np.random.default_rng(12)
    b, h, seq, d = 8, 4, 256, 128
    q, k, v = (jnp.asarray(_rand(rng, b, h, seq, d), jnp.bfloat16)
               for _ in range(3))

    def loss_mesh(q, k, v):
        return (sharded_flash_attention(
            q, k, v, mesh, causal=True
        ).astype(jnp.float32) ** 2).sum()

    def loss_one(q, k, v):
        return (attention.flash_attention(
            q, k, v, causal=True, interpret=False
        ).astype(jnp.float32) ** 2).sum()

    batch_sh = NamedSharding(mesh, P(("dp", "fsdp")))
    with mesh:
        val, grads = jax.jit(
            jax.value_and_grad(loss_mesh, argnums=(0, 1, 2)),
            in_shardings=(batch_sh,) * 3,
        )(q, k, v)
    assert len(grads[0].sharding.device_set) == 4
    ref_val, ref_grads = jax.jit(
        jax.value_and_grad(loss_one, argnums=(0, 1, 2))
    )(q, k, v)
    np.testing.assert_allclose(float(val), float(ref_val), rtol=1e-3)
    for g, r in zip(grads, ref_grads):
        np.testing.assert_allclose(
            np.asarray(g, np.float32), np.asarray(r, np.float32),
            atol=1e-2, rtol=0,
        )


# ------------------------------------------------- dense optimizer kernels


def test_sgd_kernel_compiled():
    rng = np.random.default_rng(2)
    p, g = _rand(rng, 1000, 37), _rand(rng, 1000, 37)
    out = optimizer_kernels.sgd_update(p, g, 0.1, interpret=False)
    np.testing.assert_allclose(
        np.asarray(out), um.sgd_math(p, g, 0.1), atol=1e-6
    )


def test_momentum_kernel_compiled():
    rng = np.random.default_rng(3)
    p, v, g = (_rand(rng, 513, 129) for _ in range(3))
    new_p, new_v = optimizer_kernels.momentum_update(
        p, v, g, 0.01, momentum=0.9, nesterov=True, interpret=False
    )
    ref_p, ref_v = um.momentum_math(p, v, g, 0.01, 0.9, 1.0)
    np.testing.assert_allclose(np.asarray(new_p), np.asarray(ref_p),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_v), np.asarray(ref_v),
                               atol=1e-6)


def test_adam_kernel_compiled():
    rng = np.random.default_rng(4)
    p, m, v, g = (_rand(rng, 2048) for _ in range(4))
    outs = optimizer_kernels.adam_update(
        p, m, v, g, step=3, lr=1e-3, interpret=False
    )
    alpha = um.adam_alpha(1e-3, 0.9, 0.999, 3)
    refs = um.adam_math(p, m, v, g, alpha, 0.9, 0.999, 1e-8)
    for o, r in zip(outs, refs):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-6)


def test_adam_amsgrad_kernel_compiled():
    rng = np.random.default_rng(5)
    p, m, v, ms, g = (_rand(rng, 300, 7) for _ in range(5))
    ms = np.abs(ms)
    outs = optimizer_kernels.adam_update(
        p, m, v, g, step=1, lr=1e-3, max_square=ms, interpret=False
    )
    alpha = um.adam_alpha(1e-3, 0.9, 0.999, 1)
    refs = um.adam_amsgrad_math(p, m, v, ms, g, alpha, 0.9, 0.999, 1e-8)
    for o, r in zip(outs, refs):
        np.testing.assert_allclose(np.asarray(o), np.asarray(r), atol=1e-6)


def test_adagrad_kernel_compiled():
    rng = np.random.default_rng(6)
    p, a, g = (_rand(rng, 4096) for _ in range(3))
    a = np.abs(a)
    new_p, new_a = optimizer_kernels.adagrad_update(
        p, a, g, 0.05, interpret=False
    )
    ref_p, ref_a = um.adagrad_math(p, a, g, 0.05, 1e-10)
    np.testing.assert_allclose(np.asarray(new_p), np.asarray(ref_p),
                               atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_a), np.asarray(ref_a),
                               atol=1e-6)


# ----------------------------------------------------- sparse row kernels


def test_embedding_gather_compiled():
    rng = np.random.default_rng(7)
    table = _rand(rng, 5000, 64)
    ids = rng.integers(0, 5000, size=37).astype(np.int32)
    out = embedding_ops.embedding_gather(
        jnp.asarray(table), jnp.asarray(ids), interpret=False
    )
    np.testing.assert_allclose(np.asarray(out), table[ids], atol=1e-6)


def test_sparse_sgd_update_compiled():
    rng = np.random.default_rng(8)
    table = _rand(rng, 1000, 128)
    ids = np.array([3, 77, 500, 999], np.int32)
    grads = _rand(rng, 4, 128)
    out = embedding_ops.sparse_sgd_update(
        jnp.asarray(table), jnp.asarray(ids), jnp.asarray(grads), 0.1,
        interpret=False,
    )
    ref = table.copy()
    ref[ids] -= 0.1 * grads
    np.testing.assert_allclose(np.asarray(out), ref, atol=1e-6)


def test_sparse_adam_update_compiled():
    rng = np.random.default_rng(9)
    vocab, dim, n = 800, 64, 16
    table, m, v = (_rand(rng, vocab, dim) for _ in range(3))
    v = np.abs(v)
    ids = rng.integers(0, vocab, size=n).astype(np.int32)
    ids = np.unique(ids).astype(np.int32)  # kernel expects deduped rows
    grads = _rand(rng, ids.size, dim)
    new_t, new_m, new_v = embedding_ops.sparse_adam_update(
        jnp.asarray(table), jnp.asarray(m), jnp.asarray(v),
        jnp.asarray(ids), jnp.asarray(grads), step=2, lr=1e-3,
        interpret=False,
    )
    alpha = um.adam_alpha(1e-3, 0.9, 0.999, 2)
    ref_rows = um.adam_math(
        table[ids], m[ids], v[ids], grads, alpha, 0.9, 0.999, 1e-8
    )
    for new, base, ref in zip((new_t, new_m, new_v), (table, m, v),
                              ref_rows):
        expect = base.copy()
        expect[ids] = np.asarray(ref)
        np.testing.assert_allclose(np.asarray(new), expect, atol=1e-5)


def test_sparse_adagrad_update_compiled():
    rng = np.random.default_rng(10)
    vocab, dim = 600, 32
    table, accum = _rand(rng, vocab, dim), np.abs(_rand(rng, vocab, dim))
    ids = np.array([0, 5, 599], np.int32)
    grads = _rand(rng, 3, dim)
    new_t, new_a = embedding_ops.sparse_adagrad_update(
        jnp.asarray(table), jnp.asarray(accum), jnp.asarray(ids),
        jnp.asarray(grads), 0.05, interpret=False,
    )
    ref_t, ref_a = um.adagrad_math(
        table[ids], accum[ids], grads, 0.05, 1e-10
    )
    expect_t, expect_a = table.copy(), accum.copy()
    expect_t[ids] = np.asarray(ref_t)
    expect_a[ids] = np.asarray(ref_a)
    np.testing.assert_allclose(np.asarray(new_t), expect_t, atol=1e-6)
    np.testing.assert_allclose(np.asarray(new_a), expect_a, atol=1e-6)


# -------------------------------------------------- end-to-end on hardware


def test_trainer_step_on_tpu():
    """One real compiled train step (trainer + flash attention path) on
    the chip — the bench's hot loop, as a pass/fail correctness check."""
    from elasticdl_tpu.common.model_utils import (
        format_params_str,
        load_model_spec_from_module,
    )
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.training.trainer import Trainer
    from model_zoo.transformer_lm import transformer_lm as zoo

    spec = load_model_spec_from_module(zoo)
    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(
        spec,
        mesh=mesh,
        model_params=format_params_str(
            dict(vocab_size=256, seq_len=128, embed_dim=128,
                 num_heads=4, num_layers=2, dtype="bf16")
        ),
    )
    rng = np.random.RandomState(0)
    tokens = rng.randint(0, 256, size=(8, 129)).astype(np.int32)
    batch = ({"tokens": tokens[:, :-1]}, tokens[:, 1:])
    state = trainer.init_state(batch)
    losses = []
    for _ in range(5):
        state, loss = trainer.train_step(state, batch)
        losses.append(float(loss))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], "loss did not decrease on-chip"
