"""Block-paged KV pool unit tests (tier-1).

The host-side block allocator (serving/kv_pool.py): alloc/extend/free
reuse order, reservation-backed extends, fragmentation invariants under
random request lengths, clean out-of-blocks signalling; the
prefix-sharing layer (refcounted chains, the content-addressed index,
reclaimable-LRU revival/eviction, copy-on-write under reservation
pressure); plus the paged decode-attention op (ops/attention.py)
against a dense oracle for both the single-token step and the
verify-k query tile. Engine/server-level paged behavior (parity at
concurrency, admission backpressure, reclamation on evict) lives in
tests/test_serving_e2e.py on the drills shard."""

import numpy as np
import pytest

from elasticdl_tpu.serving.kv_pool import (
    BlockAllocator,
    OutOfBlocks,
    blocks_for,
)


def test_blocks_for():
    assert blocks_for(0, 4) == 0
    assert blocks_for(1, 4) == 1
    assert blocks_for(4, 4) == 1
    assert blocks_for(5, 4) == 2
    assert blocks_for(17, 4) == 5


def test_alloc_free_reuse_order_is_lifo():
    a = BlockAllocator(num_blocks=8, block_size=4)
    assert a.alloc("r0", tokens=8) == 0   # 2 blocks, nothing shared
    assert a.alloc("r1", tokens=4) == 0   # 1 block
    t0, t1 = a.table("r0"), a.table("r1")
    assert len(t0) == 2 and len(t1) == 1
    assert len(set(t0) | set(t1)) == 3    # disjoint
    assert a.num_free() == 5
    # free r0: its blocks come back and are reused FIRST, last-out
    # first-in (warm reuse)
    assert a.free("r0") == 2
    a.alloc("r2", tokens=8)
    assert a.table("r2") == list(reversed(t0))
    # double free is a harmless no-op
    assert a.free("r0") == 0


def test_alloc_reserves_full_commitment():
    a = BlockAllocator(num_blocks=4, block_size=4)
    # 1 block materialized now, 3 promised in total
    a.alloc("r0", tokens=4, commit_tokens=12)
    assert a.num_free() == 3
    assert a.available() == 1  # 3 free minus 2 reserved
    assert a.can_fit(4) and not a.can_fit(8)
    with pytest.raises(OutOfBlocks):
        a.alloc("r1", tokens=8)
    # the reservation makes the seated request's growth infallible
    a.extend("r0", total_tokens=8)
    a.extend("r0", total_tokens=12)
    assert len(a.table("r0")) == 3
    assert a.available() == 1  # reservation fully drawn down
    # freeing returns blocks AND releases nothing extra (none left)
    assert a.free("r0") == 3
    assert a.num_free() == 4 and a.available() == 4


def test_free_releases_undrawn_reservation():
    a = BlockAllocator(num_blocks=4, block_size=4)
    a.alloc("r0", tokens=4, commit_tokens=16)  # commit all 4
    assert a.available() == 0
    a.free("r0")  # only 1 block was materialized
    assert a.num_free() == 4 and a.available() == 4


def test_extend_beyond_commitment_competes_with_admission():
    a = BlockAllocator(num_blocks=2, block_size=4)
    a.alloc("r0", tokens=4, commit_tokens=4)
    a.alloc("r1", tokens=4, commit_tokens=4)
    with pytest.raises(OutOfBlocks):
        a.extend("r0", total_tokens=8)  # past its commitment, pool dry
    assert len(a.table("r0")) == 1  # untouched by the failed extend


def test_alloc_failure_leaves_state_clean():
    a = BlockAllocator(num_blocks=2, block_size=4)
    a.alloc("r0", tokens=4)
    free_before = a.num_free()
    with pytest.raises(OutOfBlocks):
        a.alloc("r1", tokens=4, commit_tokens=12)
    assert a.num_free() == free_before
    assert a.table("r1") == []
    a.alloc("r1", tokens=4)  # a fitting request still seats


def test_fragmentation_under_random_request_lengths():
    """Random admit/complete churn with mixed lengths: the allocator's
    invariants (conservation, disjoint ownership, non-negative
    availability) must hold at every step, and a drained pool must be
    whole again."""
    rs = np.random.RandomState(7)
    a = BlockAllocator(num_blocks=32, block_size=4)
    live = {}
    for i in range(300):
        if live and (rs.rand() < 0.4 or not a.can_fit(24)):
            slot = rs.choice(sorted(live))
            a.free(slot)
            del live[slot]
        else:
            tokens = int(rs.randint(1, 25))
            total = tokens + int(rs.randint(0, 25))
            slot = "r%d" % i
            if a.can_fit(total):
                a.alloc(slot, tokens, commit_tokens=total)
                live[slot] = total
                # grow a random live request inside its commitment
                a.extend(slot, min(total, tokens + int(rs.randint(0, 8))))
        # ---- invariants
        used = sum(len(a.table(s)) for s in live)
        assert used == a.blocks_in_use()
        assert used + a.num_free() == 32
        assert a.available() >= 0
        owned = [b for s in live for b in a.table(s)]
        assert len(owned) == len(set(owned))  # no block owned twice
    for slot in list(live):
        a.free(slot)
    assert a.num_free() == 32 and a.available() == 32


def test_paged_decode_attention_matches_dense_oracle():
    """The op must equal plain softmax attention over the logically
    contiguous cache (pool rows gathered in table order + the current
    token), for MHA and GQA, with and without a sliding window."""
    import jax.numpy as jnp

    from elasticdl_tpu.ops.attention import paged_decode_attention

    rs = np.random.RandomState(0)
    bs, nb = 4, 10
    for hkv, h in ((2, 2), (1, 4)):
        for window in (None, 5):
            d = 8
            b = 3
            k_pool = rs.randn(nb, bs, hkv, d).astype(np.float32)
            v_pool = rs.randn(nb, bs, hkv, d).astype(np.float32)
            q = rs.randn(b, h, d).astype(np.float32)
            k_cur = rs.randn(b, hkv, d).astype(np.float32)
            v_cur = rs.randn(b, hkv, d).astype(np.float32)
            # each row: different length + scattered table, -1 padded
            lengths = np.asarray([0, 5, 11], np.int32)
            table = np.full((b, 3), -1, np.int32)
            table[1, :2] = [7, 2]
            table[2, :3] = [4, 9, 1]
            out = np.asarray(paged_decode_attention(
                jnp.asarray(q), jnp.asarray(k_cur), jnp.asarray(v_cur),
                jnp.asarray(k_pool), jnp.asarray(v_pool),
                jnp.asarray(table), jnp.asarray(lengths),
                window=window,
            ))
            group = h // hkv
            for i in range(b):
                ln = int(lengths[i])
                rows_k = np.concatenate(
                    [k_pool[bid] for bid in table[i] if bid >= 0]
                    or [np.zeros((0, hkv, d), np.float32)]
                )[:ln]
                rows_v = np.concatenate(
                    [v_pool[bid] for bid in table[i] if bid >= 0]
                    or [np.zeros((0, hkv, d), np.float32)]
                )[:ln]
                keys = np.concatenate([rows_k, k_cur[i][None]])
                vals = np.concatenate([rows_v, v_cur[i][None]])
                if window is not None:
                    # visible: k_pos in (ln - window, ln]
                    k_pos = np.arange(ln + 1)
                    keep = k_pos > ln - window
                    keys, vals = keys[keep], vals[keep]
                for j in range(h):
                    kvh = j // group
                    s = keys[:, kvh] @ q[i, j] * d ** -0.5
                    w = np.exp(s - s.max())
                    w = w / w.sum()
                    ref = w @ vals[:, kvh]
                    np.testing.assert_allclose(
                        out[i, j], ref, rtol=2e-5, atol=2e-5,
                        err_msg="row %d head %d hkv=%d window=%r"
                                % (i, j, hkv, window),
                    )


def test_paged_decode_attention_tile_matches_dense_oracle():
    """The verify-k query tile (speculative verify / shared-prefix
    suffix prefill): row j attends every pool row < length plus tile
    keys j' <= j, for MHA and GQA, with and without a window."""
    import jax.numpy as jnp

    from elasticdl_tpu.ops.attention import paged_decode_attention

    rs = np.random.RandomState(1)
    bs, nb, d, b, t = 4, 10, 8, 3, 3
    for hkv, h in ((2, 2), (1, 4)):
        for window in (None, 5):
            k_pool = rs.randn(nb, bs, hkv, d).astype(np.float32)
            v_pool = rs.randn(nb, bs, hkv, d).astype(np.float32)
            q = rs.randn(b, h, t, d).astype(np.float32)
            k_cur = rs.randn(b, hkv, t, d).astype(np.float32)
            v_cur = rs.randn(b, hkv, t, d).astype(np.float32)
            lengths = np.asarray([0, 5, 11], np.int32)
            table = np.full((b, 3), -1, np.int32)
            table[1, :2] = [7, 2]
            table[2, :3] = [4, 9, 1]
            out = np.asarray(paged_decode_attention(
                jnp.asarray(q), jnp.asarray(k_cur), jnp.asarray(v_cur),
                jnp.asarray(k_pool), jnp.asarray(v_pool),
                jnp.asarray(table), jnp.asarray(lengths),
                window=window,
            ))
            assert out.shape == (b, h, t, d)
            group = h // hkv
            for i in range(b):
                ln = int(lengths[i])
                rows_k = np.concatenate(
                    [k_pool[bid] for bid in table[i] if bid >= 0]
                    or [np.zeros((0, hkv, d), np.float32)]
                )[:ln]
                rows_v = np.concatenate(
                    [v_pool[bid] for bid in table[i] if bid >= 0]
                    or [np.zeros((0, hkv, d), np.float32)]
                )[:ln]
                for jq in range(t):
                    keys = np.concatenate(
                        [rows_k, k_cur[i].transpose(1, 0, 2)[:jq + 1]]
                    )
                    vals = np.concatenate(
                        [rows_v, v_cur[i].transpose(1, 0, 2)[:jq + 1]]
                    )
                    k_pos = np.arange(ln + jq + 1)
                    keep = np.ones(len(k_pos), bool)
                    if window is not None:
                        keep = k_pos > ln + jq - window
                    keys, vals = keys[keep], vals[keep]
                    for j in range(h):
                        kvh = j // group
                        s = keys[:, kvh] @ q[i, j, jq] * d ** -0.5
                        w = np.exp(s - s.max())
                        w = w / w.sum()
                        ref = w @ vals[:, kvh]
                        np.testing.assert_allclose(
                            out[i, j, jq], ref, rtol=2e-5, atol=2e-5,
                            err_msg="row %d head %d tile %d hkv=%d "
                                    "window=%r" % (i, j, jq, hkv,
                                                   window),
                        )


# --------------------------------------------------- int8 arenas


def _np_quantize_rows(rows):
    """Numpy twin of the model's `_kv_quantize_rows` (symmetric
    per-row int8, f32 scales, zero rows keep scale 1) — the oracle the
    arena round-trip and attention tests quantize with."""
    amax = np.abs(rows).max(-1, keepdims=True)
    scale = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
    q8 = np.clip(np.round(rows / scale), -127, 127).astype(np.int8)
    return q8, scale


def test_np_quantizer_matches_model_quantizer():
    import jax.numpy as jnp

    from model_zoo.transformer_lm.transformer_lm import (
        _kv_quantize_rows,
    )

    rows = np.random.RandomState(2).randn(1, 2, 6, 8).astype(np.float32)
    rows[0, 1, 3] = 0.0  # a zero row must keep scale 1
    q8, sc = _np_quantize_rows(rows)
    mq8, msc = _kv_quantize_rows(jnp.asarray(rows))
    np.testing.assert_array_equal(q8, np.asarray(mq8))
    np.testing.assert_allclose(sc, np.asarray(msc), rtol=1e-6)


def test_int8_prompt_block_write_round_trips_quantizer():
    """build_pools maps int8 rows AND their f32 scale leaves through
    the same kv_row_leaf convention, and write_prompt_block inserts a
    quantized cache block bit-exactly (quantize-at-insertion: the
    arena holds exactly what the quantizer produced)."""
    import jax.numpy as jnp

    from elasticdl_tpu.serving.kv_pool import (
        build_pools,
        write_prompt_block,
    )

    rs = np.random.RandomState(3)
    hkv, d, cache_len, bs, nb = 2, 8, 16, 4, 6
    rows = rs.randn(1, hkv, cache_len, d).astype(np.float32)
    q8, sc = _np_quantize_rows(rows)
    kv = {
        "k": jnp.asarray(q8), "k_scale": jnp.asarray(sc),
        "pos": jnp.zeros((), jnp.int32),
    }
    pools = build_pools(kv, cache_len, nb, bs)
    assert pools["k"].dtype == jnp.int8
    assert pools["k"].shape == (nb, bs, hkv, d)
    assert pools["k_scale"].dtype == jnp.float32
    assert pools["k_scale"].shape == (nb, bs, hkv, 1)
    assert pools["pos"].shape == ()  # non-row leaf stays a placeholder
    # the leaves' kinds, as the pool hands them to its programs: by
    # declaration, here the kv_row_leaf convention's (k, k_scale, pos)
    pools = write_prompt_block(
        pools, kv, jnp.asarray(1, jnp.int32), jnp.asarray(4, jnp.int32),
        block_size=bs, kinds=("rows", "rows", "scalar"),
    )
    np.testing.assert_array_equal(
        np.asarray(pools["k"][4]),
        q8[0, :, bs:2 * bs, :].transpose(1, 0, 2),
    )
    np.testing.assert_array_equal(
        np.asarray(pools["k_scale"][4]),
        sc[0, :, bs:2 * bs, :].transpose(1, 0, 2),
    )
    # untouched blocks stay zero
    assert not np.asarray(pools["k"][0]).any()


def test_int8_scatter_rows_round_trips_and_drops():
    """The per-step decode scatter writes int8 rows + scale rows in
    lockstep; out-of-bounds lanes drop from BOTH leaves."""
    import jax.numpy as jnp

    from elasticdl_tpu.serving.kv_pool import scatter_rows

    rs = np.random.RandomState(4)
    hkv, d, bs, nb, s = 2, 8, 4, 6, 3
    pools = {
        "k": jnp.zeros((nb, bs, hkv, d), jnp.int8),
        "k_scale": jnp.zeros((nb, bs, hkv, 1), jnp.float32),
        "pos": jnp.zeros((), jnp.int32),
    }
    raw = rs.randn(s, hkv, d).astype(np.float32)
    q8, sc = _np_quantize_rows(raw)
    rows = {"k": jnp.asarray(q8), "k_scale": jnp.asarray(sc)}
    bids = jnp.asarray([2, nb, 5], jnp.int32)  # lane 1 = drop sentinel
    offs = jnp.asarray([1, 0, 3], jnp.int32)
    out = scatter_rows(pools, rows, bids, offs)
    np.testing.assert_array_equal(np.asarray(out["k"][2, 1]), q8[0])
    np.testing.assert_array_equal(
        np.asarray(out["k_scale"][2, 1]), sc[0]
    )
    np.testing.assert_array_equal(np.asarray(out["k"][5, 3]), q8[2])
    np.testing.assert_array_equal(
        np.asarray(out["k_scale"][5, 3]), sc[2]
    )
    # the dropped lane touched nothing: everything else is still zero
    mask = np.ones((nb, bs), bool)
    mask[2, 1] = mask[5, 3] = False
    assert not np.asarray(out["k"])[mask].any()
    assert not np.asarray(out["k_scale"])[mask].any()


def test_copy_block_carries_scale_leaves():
    """Device-side CoW must duplicate the scale arenas alongside the
    int8 rows — a copied block that kept stale scales would silently
    dequantize to wrong values."""
    import jax.numpy as jnp

    from elasticdl_tpu.serving.kv_pool import copy_block

    rs = np.random.RandomState(5)
    nb, bs, hkv, d = 6, 4, 2, 8
    pools = {
        "k": jnp.asarray(
            rs.randint(-127, 128, size=(nb, bs, hkv, d)), jnp.int8
        ),
        "k_scale": jnp.asarray(
            rs.rand(nb, bs, hkv, 1).astype(np.float32)
        ),
        "pos": jnp.zeros((), jnp.int32),
    }
    out = copy_block(pools, 1, 4, kinds=("rows", "rows", "scalar"))
    np.testing.assert_array_equal(
        np.asarray(out["k"][4]), np.asarray(pools["k"][1])
    )
    np.testing.assert_array_equal(
        np.asarray(out["k_scale"][4]), np.asarray(pools["k_scale"][1])
    )
    # source untouched
    np.testing.assert_array_equal(
        np.asarray(out["k"][1]), np.asarray(pools["k"][1])
    )


def test_paged_int8_attention_matches_dense_deferred_oracle():
    """The streaming int8 scan vs the dense DEFERRED-dequantize oracle
    (same quantizer, so the comparison carries no quantization error —
    float tolerance only): s = (q·k8)·ks, softmax, out = (w·vs)@v8,
    for the t=1 legacy shape and the verify-k tile, MHA and GQA, with
    and without a sliding window."""
    import jax.numpy as jnp

    from elasticdl_tpu.ops.attention import paged_decode_attention

    rs = np.random.RandomState(6)
    bs, nb, d, b = 4, 10, 8, 3
    for t in (1, 3):
        for hkv, h in ((2, 2), (1, 4)):
            for window in (None, 5):
                kf = rs.randn(nb, bs, hkv, d).astype(np.float32)
                vf = rs.randn(nb, bs, hkv, d).astype(np.float32)
                k_pool, ks_pool = _np_quantize_rows(kf)
                v_pool, vs_pool = _np_quantize_rows(vf)
                q = rs.randn(b, h, t, d).astype(np.float32)
                kc_f = rs.randn(b, hkv, t, d).astype(np.float32)
                vc_f = rs.randn(b, hkv, t, d).astype(np.float32)
                k_cur, ks_cur = _np_quantize_rows(kc_f)
                v_cur, vs_cur = _np_quantize_rows(vc_f)
                lengths = np.asarray([0, 5, 11], np.int32)
                table = np.full((b, 3), -1, np.int32)
                table[1, :2] = [7, 2]
                table[2, :3] = [4, 9, 1]
                args = (
                    jnp.asarray(q), jnp.asarray(k_cur),
                    jnp.asarray(v_cur), jnp.asarray(k_pool),
                    jnp.asarray(v_pool), jnp.asarray(table),
                    jnp.asarray(lengths),
                )
                kwargs = dict(
                    window=window,
                    k_scale_pool=jnp.asarray(ks_pool),
                    v_scale_pool=jnp.asarray(vs_pool),
                    k_cur_scale=jnp.asarray(ks_cur),
                    v_cur_scale=jnp.asarray(vs_cur),
                )
                if t == 1:  # exercise the squeezed legacy shape
                    args = (
                        jnp.asarray(q[:, :, 0]),
                        jnp.asarray(k_cur[:, :, 0]),
                        jnp.asarray(v_cur[:, :, 0]),
                    ) + args[3:]
                    kwargs["k_cur_scale"] = jnp.asarray(ks_cur[:, :, 0])
                    kwargs["v_cur_scale"] = jnp.asarray(vs_cur[:, :, 0])
                out = np.asarray(
                    paged_decode_attention(*args, **kwargs)
                )
                if t == 1:
                    out = out[:, :, None, :]
                group = h // hkv
                for i in range(b):
                    ln = int(lengths[i])
                    zero = np.zeros((0, hkv, d), np.float32)
                    pk = np.concatenate(
                        [k_pool[bid].astype(np.float32)
                         * ks_pool[bid]
                         for bid in table[i] if bid >= 0] or [zero]
                    )[:ln]
                    pv8 = np.concatenate(
                        [v_pool[bid].astype(np.float32)
                         for bid in table[i] if bid >= 0] or [zero]
                    )[:ln]
                    pvs = np.concatenate(
                        [np.broadcast_to(vs_pool[bid],
                                         (bs, hkv, 1))
                         for bid in table[i] if bid >= 0]
                        or [np.zeros((0, hkv, 1), np.float32)]
                    )[:ln]
                    for jq in range(t):
                        # deferred oracle: keys pre-scaled by ks; the
                        # weights (not the values) carry vs
                        ck = (k_cur[i].astype(np.float32)
                              * ks_cur[i]).transpose(1, 0, 2)[:jq + 1]
                        keys = np.concatenate([pk, ck])
                        v8 = np.concatenate(
                            [pv8,
                             v_cur[i].astype(np.float32)
                             .transpose(1, 0, 2)[:jq + 1]]
                        )
                        vs = np.concatenate(
                            [pvs,
                             vs_cur[i].transpose(1, 0, 2)[:jq + 1]]
                        )
                        k_pos = np.arange(ln + jq + 1)
                        keep = np.ones(len(k_pos), bool)
                        if window is not None:
                            keep = k_pos > ln + jq - window
                        keys, v8, vs = keys[keep], v8[keep], vs[keep]
                        for j in range(h):
                            kvh = j // group
                            s = keys[:, kvh] @ q[i, j, jq] * d ** -0.5
                            w = np.exp(s - s.max())
                            w = w / w.sum()
                            ref = (w * vs[:, kvh, 0]) @ v8[:, kvh]
                            np.testing.assert_allclose(
                                out[i, j, jq], ref,
                                rtol=5e-5, atol=5e-5,
                                err_msg="row %d head %d tile %d t=%d "
                                        "hkv=%d window=%r"
                                        % (i, j, jq, t, hkv, window),
                            )


def test_paged_int8_attention_requires_all_scales():
    import jax.numpy as jnp
    import pytest as _pytest

    from elasticdl_tpu.ops.attention import paged_decode_attention

    z8 = jnp.zeros((2, 4, 1, 8), jnp.int8)
    zf = jnp.zeros((2, 4, 1, 1), jnp.float32)
    with _pytest.raises(ValueError, match="scale operands"):
        paged_decode_attention(
            jnp.zeros((1, 1, 8)), jnp.zeros((1, 1, 8), jnp.int8),
            jnp.zeros((1, 1, 8), jnp.int8), z8, z8,
            jnp.zeros((1, 2), jnp.int32), jnp.zeros((1,), jnp.int32),
            k_scale_pool=zf,  # v-side scales missing
        )


# ------------------------------------------- prefix sharing + CoW


def _shared(num_blocks=16, block_size=4):
    return BlockAllocator(num_blocks=num_blocks, block_size=block_size,
                          share_prefix=True)


def test_prefix_match_seats_by_incref():
    """An identical prompt seats on the resident chain: refcounts
    bump, no fresh blocks are drawn for the shared prefix, and the
    admission planner (can_seat) agrees with the seat."""
    a = _shared()
    prompt = list(range(10))  # 2 full blocks + a partial tail
    a.alloc("r0", tokens=10, commit_tokens=14, prompt=prompt)
    a.register_prefix("r0", prompt)
    free_before = a.num_free()
    chain, needed = a.plan(prompt, 10, 14)
    assert len(chain) == 2 and needed == 2  # 1 private + 1 growth
    assert a.can_seat(prompt, 10, 14)
    shared = a.alloc("r1", tokens=10, commit_tokens=14, prompt=prompt)
    assert shared == 8
    assert a.num_free() == free_before - 1  # only the private tail
    assert a.table("r1")[:2] == a.table("r0")[:2]
    assert a.table("r1")[2] != a.table("r0")[2]
    assert a.shared_blocks() == 2
    assert a.prefix_hits == 1 and a.prefix_hit_tokens == 8


def test_shared_chain_freed_only_at_refcount_zero():
    """free() decrefs; the chain's blocks leave the live set only when
    the LAST owner releases them — and then to the reclaimable cache,
    not the free list (they are still indexed)."""
    a = _shared()
    prompt = list(range(8))
    a.alloc("r0", tokens=8, prompt=prompt)
    a.register_prefix("r0", prompt)
    a.alloc("r1", tokens=8, prompt=prompt)
    chain = a.table("r0")
    assert a.table("r1") == chain  # fully shared (seat recomputes the
    assert a.shared_blocks() == 2  # tail row via the CoW-credit path)
    a.free("r0")
    # r1 still owns the chain: nothing freed, nothing cached
    assert a.blocks_in_use() == 2 and a.num_cached() == 0
    a.free("r1")
    assert a.blocks_in_use() == 0
    assert a.num_cached() == 2  # reclaimable, revivable by a match
    # a third request revives the chain at zero cost
    free_before = a.num_free()
    assert a.alloc("r2", tokens=8, prompt=prompt) == 8
    assert a.num_cached() == 0 and a.num_free() == free_before


def test_cow_under_reservation_pressure():
    """A full-prompt match reserves ONE CoW credit at seat; the fault
    draws it even when the pool is otherwise fully promised — and an
    unplanned CoW with a dry pool raises cleanly."""
    a = _shared(num_blocks=4, block_size=4)
    prompt = list(range(8))
    a.alloc("r0", tokens=8, prompt=prompt)
    a.register_prefix("r0", prompt)
    # full-prompt match: 2 shared + 1 CoW credit reserved
    chain, needed = a.plan(prompt, 8, 8)
    assert len(chain) == 2 and needed == 1
    a.alloc("r1", tokens=8, prompt=prompt)
    # pool: 2 live shared + 2 free, 1 of them reserved for r1's CoW
    assert a.available() == 1
    # a competing alloc may take only the unreserved remainder
    with pytest.raises(OutOfBlocks):
        a.alloc("r2", tokens=8)
    a.alloc("r2", tokens=4)
    assert a.available() == 0
    # the planned CoW still succeeds: it draws r1's credit
    old, new = a.cow("r1", 1)
    assert old == a.table("r0")[1] and a.table("r1")[1] == new
    assert a.table("r0")[1] == old  # r0 keeps the original
    # a SECOND (unplanned) CoW on the same slot has no credit and no
    # free block -> clean OutOfBlocks, nothing taken
    a.alloc("rX", tokens=0)  # no-op slot; keeps accounting honest
    with pytest.raises(OutOfBlocks):
        a.cow("r1", 0)
    assert a.table("r1")[0] == a.table("r0")[0]


def test_seat_on_reclaimable_chain_charges_revived_blocks():
    """Admission must charge the reclaimable chain blocks a seat
    revives: incref pops them out of the cache available() counts, so
    an uncharged revival lets _reserved exceed free + cached and a
    reservation-backed extend strands MID-DECODE. Repro from review:
    4-block pool, a 12-token prompt cached whole, then the same prompt
    with a commitment of 5 blocks — it must be refused at admission,
    not admitted and killed at its first extend."""
    a = _shared(num_blocks=4, block_size=4)
    prompt = list(range(12))  # 3 full blocks
    a.alloc("e", tokens=12, commit_tokens=13, prompt=prompt)
    a.register_prefix("e", prompt)
    a.free("e")
    assert a.num_cached() == 3 and a.num_free() == 1
    # commit 17 tokens = 5 blocks > pool; the shared seat would revive
    # 3 cached blocks (charged) + 2 growth = 5 > 4 (no CoW charge: the
    # revived tail is sole-owned, its re-write lands in place)
    chain, needed = a.plan(prompt, 12, 17)
    assert len(chain) == 3 and needed == 5
    assert not a.can_seat(prompt, 12, 17)
    with pytest.raises(OutOfBlocks):
        a.alloc("b", tokens=12, commit_tokens=17, prompt=prompt)
    # nothing was taken by the refused seat
    assert a.num_cached() == 3 and a.num_free() == 1
    # the revival charge must not DOUBLE-charge the tail as a CoW
    # credit: a full-budget reseat (commit = the whole pool) is
    # physically seatable — 3 revived + 1 growth — and refusing it
    # would starve it forever on an idle pool
    assert a.can_seat(prompt, 12, 16)
    assert a.alloc("b", tokens=12, commit_tokens=16,
                   prompt=prompt) == 12
    assert a.available() == 0  # 3 revived live, 1 free reserved
    # "b" owns the revived tail alone: write-in-place, no copy
    assert a.cow("b", 2) is None
    a.extend("b", 16)  # the growth block draws the reservation
    assert a.num_free() == 0 and a.available() == 0
    a.free("b")
    assert a.num_free() + a.num_cached() == 4 and a.available() == 4


def test_reclaimable_lru_eviction_is_leaf_first():
    """Under pressure the allocator evicts reclaimable blocks from the
    index; a chain's deeper blocks (leaves) go before their parents,
    so a surviving partial chain still matches."""
    a = _shared(num_blocks=4, block_size=4)
    prompt = list(range(16))  # 4 full blocks
    a.alloc("r0", tokens=16, prompt=prompt)
    a.register_prefix("r0", prompt)
    a.free("r0")
    assert a.num_cached() == 4 and a.num_free() == 0
    # a private alloc must evict exactly one reclaimable block — and
    # the LEAF (deepest chain block), never a parent
    a.alloc("r1", tokens=4)
    assert a.num_cached() == 3
    chain = a.match_prefix(prompt)
    assert len(chain) == 3  # prefix [0, 12) still matchable
    a.free("r1")
    # flush (the hot-reload hook) returns every cached block to free
    a.flush_index()
    assert a.num_cached() == 0 and a.num_free() == 4
    assert a.match_prefix(prompt) == []


# --------------------------------------------------- tiered host spill


def _tiered(num_blocks=4, block_size=4, host_blocks=8):
    return BlockAllocator(num_blocks=num_blocks, block_size=block_size,
                          share_prefix=True, host_blocks=host_blocks)


def test_eviction_spills_instead_of_forgetting():
    """With a host tier, device eviction DEMOTES the chain: the trie
    keeps resolving it (tail re-keyed onto a virtual id < -1), and the
    admission planner charges the spilled entry like a fresh draw —
    the chain saves its prefill, never its bytes."""
    a = _tiered()
    prompt = list(range(16))  # 4 full blocks
    a.alloc("r0", tokens=16, prompt=prompt)
    a.register_prefix("r0", prompt)
    a.free("r0")
    assert a.num_cached() == 4 and a.num_free() == 0
    a.alloc("r1", tokens=4)  # pressure: evicts ONE block — the leaf
    assert a.num_cached() == 3 and a.num_spilled() == 1
    assert a.spills == 1
    chain = a.match_prefix(prompt)
    assert len(chain) == 4 and chain[-1] < -1  # still fully matchable
    assert all(b >= 0 for b in chain[:3])  # resident prefix intact
    # plan: 3 reclaimable revivals + 1 spilled upload = 4 fresh-like
    # charges (no CoW: the tail is spilled, revival owns it solely)
    _chain, needed = a.plan(prompt, 16, 16)
    assert needed == 4
    a.free("r1")
    shared = a.alloc("r2", tokens=16, prompt=prompt)
    assert shared == 16  # the WHOLE prompt seated without prefill
    assert a.blocks_revived == 1
    assert a.num_spilled() == 0  # revival is a move, not a copy
    moves = a.take_revived()
    assert len(moves) == 1 and moves[0][0] < -1 and moves[0][1] >= 0
    a.free("r2")
    assert a.num_free() + a.num_cached() == 4


def test_spill_is_leaf_first_and_chain_stays_complete():
    """Deeper blocks spill before their parents, so every surviving
    trie path is a resident prefix + a spilled suffix — never a hole
    a revival could not reconstruct through."""
    a = _tiered()
    prompt = list(range(16))
    a.alloc("r0", tokens=16, prompt=prompt)
    a.register_prefix("r0", prompt)
    a.free("r0")
    for k in range(1, 5):
        a.alloc("p%d" % k, tokens=4)  # one eviction each
        chain = a.match_prefix(prompt)
        assert len(chain) == 4  # the full chain always resolves
        spilled = [b < 0 for b in chain]
        assert spilled == [False] * (4 - k) + [True] * k
    assert a.num_spilled() == 4 and a.num_cached() == 0


def test_host_budget_drops_leaf_first_and_is_bounded():
    """The host tier never exceeds its block budget: the oldest
    CHILDLESS spilled entry drops to make room (dropping an interior
    entry would orphan its children's keys)."""
    a = _tiered(num_blocks=2, block_size=4, host_blocks=1)
    prompt = list(range(8))  # 2 full blocks
    a.alloc("r0", tokens=8, prompt=prompt)
    a.register_prefix("r0", prompt)
    a.free("r0")
    # both cached blocks evict for a private 8-token alloc: the leaf
    # spills first, then the parent spills and the leaf (now the
    # oldest spilled entry, childless) drops for room
    a.alloc("r1", tokens=8)
    assert a.spills == 2 and a.host_drops == 1
    assert a.num_spilled() == 1  # never above the budget
    chain = a.match_prefix(prompt)
    assert len(chain) == 1 and chain[0] < -1  # root survived
    a.free("r1")
    # the surviving root still revives; the dropped tail re-prefills
    shared = a.alloc("r2", tokens=8, prompt=prompt)
    assert shared == 4 and a.blocks_revived == 1
    a.take_revived()
    a.free("r2")


def test_flush_index_clears_both_tiers():
    """Hot reload: stale-params rows must never seat a new request
    from either tier — the flush drops every spilled entry (counted
    as host drops) and empties the index."""
    drops = []
    a = _tiered()
    a._drop_sink = drops.append
    prompt = list(range(16))
    a.alloc("r0", tokens=16, prompt=prompt)
    a.register_prefix("r0", prompt)
    a.free("r0")
    a.alloc("r1", tokens=8)  # spill two blocks
    assert a.num_spilled() == 2
    a.flush_index()
    assert a.num_spilled() == 0 and a.num_cached() == 0
    assert len(drops) == 2 and a.host_drops == 2
    assert a.match_prefix(prompt) == []
    a.free("r1")
    assert a.num_free() == 4


def test_sinks_fire_in_order_spill_before_bid_reuse():
    """The spill sink must see the dying block id BEFORE it is
    recycled (the pool copies rows out through it), and the revival
    log pairs every vid with its fresh device block."""
    events = []
    a = _tiered(num_blocks=2, block_size=4, host_blocks=4)
    a._spill_sink = lambda bid, vid: events.append(("spill", bid, vid))
    a._drop_sink = lambda vid: events.append(("drop", vid))
    prompt = list(range(8))
    a.alloc("r0", tokens=8, prompt=prompt)
    a.register_prefix("r0", prompt)
    chain_bids = a.table("r0")
    a.free("r0")
    a.alloc("r1", tokens=8)  # both blocks spill, leaf first
    assert events == [("spill", chain_bids[1], -2),
                      ("spill", chain_bids[0], -3)]
    a.free("r1")
    shared = a.alloc("r2", tokens=8, prompt=prompt)
    assert shared == 8
    moves = a.take_revived()
    assert [vid for vid, _bid in moves] == [-3, -2]  # root-first
    assert sorted(bid for _vid, bid in moves) == sorted(a.table("r2"))
    a.free("r2")


def test_evictable_frontier_matches_brute_force_under_churn():
    """The O(1) eviction frontier must equal the brute-force
    definition — cached AND no resident indexed children — after every
    operation, and host accounting must conserve across spills, drops,
    revivals and flushes."""
    rs = np.random.RandomState(23)
    a = _tiered(num_blocks=16, block_size=4, host_blocks=6)
    prompts = [list(range(100 + 10 * i, 100 + 10 * i + 8))
               for i in range(4)]
    live = {}
    for i in range(500):
        roll = rs.rand()
        if live and (roll < 0.45 or not a.can_fit(16)):
            slot = rs.choice(sorted(live))
            a.free(slot)
            del live[slot]
        elif roll < 0.9:
            prompt = (prompts[rs.randint(len(prompts))]
                      if rs.rand() < 0.7 else
                      [int(x) for x in rs.randint(0, 50, size=6)])
            total = len(prompt) + int(rs.randint(1, 13))
            slot = "r%d" % i
            if a.can_seat(prompt, len(prompt), total):
                a.alloc(slot, len(prompt), commit_tokens=total,
                        prompt=prompt)
                a.take_revived()
                a.register_prefix(slot, prompt)
                live[slot] = prompt
        else:
            a.flush_index()
        # ---- invariants, after every op
        assert a.blocks_in_use() + a.num_free() + a.num_cached() == 16
        assert a.num_spilled() <= 6  # the budget holds at all times
        # brute-force evictability: cached, no resident indexed child
        brute = {
            bid for bid in a._cached
            if not any(c >= 0 for c in a._children.get(bid, ()))
        }
        assert set(a._evictable) == brute, (i, a._evictable, brute)
        # droppable spilled entries: childless, and every spilled
        # node's children are spilled (leaf-first both tiers)
        for vid in a._spilled:
            kids = a._children.get(vid, set())
            assert all(c < 0 for c in kids), (i, vid, kids)
        brute_leaves = {
            vid for vid in a._spilled if not a._children.get(vid)
        }
        assert set(a._spill_leaves) == brute_leaves
        # every index path is complete: a child's key parent resolves
        for node, key in a._index_key.items():
            parent = key[0]
            assert parent == -1 or parent in a._index_key, (i, node)
    for slot in list(live):
        a.free(slot)
    a.flush_index()
    assert a.num_free() == 16 and a.available() == 16


def test_pool_spill_revive_round_trips_rows_and_scales():
    """PagedKVPool-level: a spilled block's rows — int8 rows AND f32
    scale leaves — must round-trip the host tier bit-exactly through
    revival, and the host byte gauge must track block_bytes."""
    import jax.numpy as jnp

    from elasticdl_tpu.serving.kv_pool import PagedKVPool

    rs = np.random.RandomState(31)
    hkv, d, cache_len, bs, nb = 2, 8, 16, 4, 4
    kv_shapes = {
        "k": jnp.zeros((1, hkv, cache_len, d), jnp.int8),
        "k_scale": jnp.zeros((1, hkv, cache_len, 1), jnp.float32),
        "pos": jnp.zeros((), jnp.int32),
    }
    pool = PagedKVPool(kv_shapes, cache_len, num_slots=2,
                       num_blocks=nb, block_size=bs,
                       share_prefix=True, host_bytes=10 ** 6)
    prompt = list(range(100, 116))
    pool.seat(0, prompt, 16)
    table0 = pool.allocator.table(0)
    pat = rs.randint(-127, 128, size=(nb, bs, hkv, d)).astype(np.int8)
    sca = rs.rand(nb, bs, hkv, 1).astype(np.float32)
    pool.pools = dict(pool.pools, k=jnp.asarray(pat),
                      k_scale=jnp.asarray(sca))
    pool.register_prefix(0, prompt)
    pool.release(0)
    # a colliding-size seat evicts all four blocks -> all spill
    pool.seat(1, list(range(16)), 16)
    assert pool.allocator.num_spilled() == 4
    assert pool.host_bytes_in_use() == 4 * pool.block_bytes
    assert pool.stats()["kv_host_blocks"] == 4
    pool.release(1)
    shared = pool.seat(0, prompt, 16)
    assert shared == 16 and pool.revive_uploads == 1
    assert pool.host_bytes_in_use() == 0  # moved, not copied
    k = np.asarray(pool.pools["k"])
    ks = np.asarray(pool.pools["k_scale"])
    for old, new in zip(table0, pool.allocator.table(0)):
        np.testing.assert_array_equal(k[new], pat[old])
        np.testing.assert_array_equal(ks[new], sca[old])
    assert pool.stats()["prefill_tokens_revived"] == 16
    pool.release(0)


def test_pool_host_budget_never_exceeded():
    """The budget pin: under sustained eviction pressure the host
    tier's bytes stay at or under kv_host_bytes at every step."""
    import jax.numpy as jnp

    from elasticdl_tpu.serving.kv_pool import PagedKVPool

    hkv, d, cache_len, bs, nb = 1, 4, 16, 4, 4
    kv_shapes = {
        "k": jnp.zeros((1, hkv, cache_len, d), jnp.float32),
        "pos": jnp.zeros((), jnp.int32),
    }
    probe = PagedKVPool(kv_shapes, cache_len, num_slots=2,
                        num_blocks=nb, block_size=bs,
                        share_prefix=True, host_bytes=0)
    budget = 2 * probe.block_bytes  # room for exactly two blocks
    pool = PagedKVPool(kv_shapes, cache_len, num_slots=2,
                       num_blocks=nb, block_size=bs,
                       share_prefix=True, host_bytes=budget)
    assert pool.allocator.host_blocks == 2
    rs = np.random.RandomState(7)
    for i in range(40):
        prompt = [int(x) for x in rs.randint(0, 9, size=12)]
        if pool.can_seat(prompt, len(prompt), 16):
            pool.seat(0, prompt, 16)
            pool.register_prefix(0, prompt)
            pool.release(0)
        assert pool.host_bytes_in_use() <= budget, i
        assert pool.stats()["kv_host_bytes"] <= budget, i
    assert pool.allocator.spills > 2  # pressure actually engaged


def test_fragmentation_under_mixed_shared_private_churn():
    """Random admit/complete churn with a pool of recurring system
    prompts: conservation (live + free + cached == total), disjoint
    private ownership, refcount consistency, and a drained pool is
    whole again."""
    rs = np.random.RandomState(11)
    a = _shared(num_blocks=32, block_size=4)
    prompts = [list(range(100 + i, 100 + i + 8)) for i in range(3)]
    live = {}
    for i in range(400):
        if live and (rs.rand() < 0.45 or not a.can_fit(24)):
            slot = rs.choice(sorted(live))
            a.free(slot)
            del live[slot]
        else:
            shared_prompt = rs.rand() < 0.6
            prompt = (prompts[rs.randint(len(prompts))]
                      if shared_prompt else
                      [int(x) for x in rs.randint(0, 50, size=6)])
            total = len(prompt) + int(rs.randint(1, 17))
            slot = "r%d" % i
            if a.can_seat(prompt, len(prompt), total):
                a.alloc(slot, len(prompt), commit_tokens=total,
                        prompt=prompt)
                a.register_prefix(slot, prompt)
                live[slot] = prompt
                a.extend(slot, min(total,
                                   len(prompt) + int(rs.randint(0, 9))))
        # ---- invariants
        assert a.blocks_in_use() + a.num_free() + a.num_cached() == 32
        assert a.available() >= 0
        refs = {}
        for s in live:
            for b in a.table(s):
                refs[b] = refs.get(b, 0) + 1
        # every live table block carries exactly its reference count
        for b, n in refs.items():
            assert a._refcount.get(b, 0) == n, (b, n)
        # no block is simultaneously free/cached and referenced
        assert not (set(refs) & set(a._free))
        assert not (set(refs) & set(a._cached))
    for slot in list(live):
        a.free(slot)
    assert a.blocks_in_use() == 0
    assert a.num_free() + a.num_cached() == 32
    a.flush_index()
    assert a.num_free() == 32 and a.available() == 32


def test_chain_export_import_round_trip():
    """Disagg handoff, pool level: export_chain's dense byte copy of a
    registered chain (int8 rows + f32 scale leaves) must equal both the
    arena rows it was gathered from AND the host-tier bytes the same
    chain spills to; importing it into a FRESH pool re-keys the trie
    (refcount-0 reclaimable, dedup on re-import), a seat shares the
    whole chain with identical rows, and the ledger settles clean."""
    import jax.numpy as jnp

    from elasticdl_tpu.serving.kv_pool import PagedKVPool

    rs = np.random.RandomState(41)
    hkv, d, cache_len, bs, nb = 2, 8, 16, 4, 4
    kv_shapes = {
        "k": jnp.zeros((1, hkv, cache_len, d), jnp.int8),
        "k_scale": jnp.zeros((1, hkv, cache_len, 1), jnp.float32),
        "pos": jnp.zeros((), jnp.int32),
    }

    def _pool():
        return PagedKVPool(kv_shapes, cache_len, num_slots=2,
                           num_blocks=nb, block_size=bs,
                           share_prefix=True, host_bytes=10 ** 6)

    src = _pool()
    prompt = list(range(100, 116))
    src.seat(0, prompt, 16)
    table0 = src.allocator.table(0)
    pat = rs.randint(-127, 128, size=(nb, bs, hkv, d)).astype(np.int8)
    sca = rs.rand(nb, bs, hkv, 1).astype(np.float32)
    src.pools = dict(src.pools, k=jnp.asarray(pat),
                     k_scale=jnp.asarray(sca))
    src.register_prefix(0, prompt)
    src.release(0)

    blocks = src.export_chain(prompt)
    assert src.chain_exports == 1
    assert len(blocks) == 4
    assert src.leaf_dtypes() == ["int8", "float32"]
    for i, ((toks, rows), bid) in enumerate(zip(blocks, table0)):
        assert list(toks) == prompt[i * bs:(i + 1) * bs]
        np.testing.assert_array_equal(rows[0], pat[bid])
        np.testing.assert_array_equal(rows[1], sca[bid])
    # exported bytes == the host-tier bytes the same chain spills to:
    # a colliding-size seat evicts all four cached blocks to the host
    # store, and the spill reads through the same gather
    src.seat(1, list(range(16)), 16)
    assert src.allocator.num_spilled() == 4
    spilled = {tuple(np.asarray(r).tobytes() for r in rows)
               for rows in src._host_rows.values()}
    exported = {tuple(np.ascontiguousarray(r).tobytes() for r in rows)
                for _, rows in blocks}
    assert exported == spilled
    src.release(1)

    dst = _pool()
    added, tokens = dst.import_chain(
        blocks, leaf_dtypes=src.leaf_dtypes()
    )
    assert (added, tokens) == (4, 16)
    assert dst.chain_imports == 1
    assert dst.chain_import_tokens == 16
    # re-import dedups: the trie already resolves every level
    assert dst.import_chain(blocks) == (0, 0)
    assert dst.chain_imports == 1
    # imported chain parks refcount-0 reclaimable: nothing in use,
    # nothing pinned — the importer's walk references all settled
    a = dst.allocator
    assert a.blocks_in_use() == 0
    assert a.num_free() + a.num_cached() == nb
    # a seat shares the whole chain and reads back identical rows
    shared = dst.seat(0, prompt, 16)
    assert shared == 16
    k = np.asarray(dst.pools["k"])
    ks = np.asarray(dst.pools["k_scale"])
    for old, new in zip(table0, dst.allocator.table(0)):
        np.testing.assert_array_equal(k[new], pat[old])
        np.testing.assert_array_equal(ks[new], sca[old])
    dst.release(0)
    assert a.blocks_in_use() == 0
    # refused payloads fail BEFORE any allocation mutates the ledger
    with pytest.raises(ValueError):
        dst.import_chain(blocks, leaf_dtypes=["float32", "float32"])
    with pytest.raises(ValueError):
        dst.import_chain([((1, 2), blocks[0][1])])
    assert a.blocks_in_use() == 0
    assert a.num_free() + a.num_cached() == nb


@pytest.mark.slow
def test_disagg_handoff_matches_offline_int8_32way():
    """The disagg acceptance pin (drills shard): 32 concurrent GREEDY
    requests against a phase-split pair — a dedicated prefill replica
    and a paged + shared + speculative + INT8 decode replica — where
    EVERY unique prompt crosses a prefill->decode chain handoff before
    its requests decode. Token streams must equal the offline int8
    oracle (the handoff is token-exact by the prefix-sharing
    argument), both pools must drain to a clean two-pool ledger with
    zero transfers in flight, and the chain counters must show the
    handoff machinery actually carried the prompts."""
    import threading

    import jax

    from elasticdl_tpu.api.generation import autoregressive_generate
    from elasticdl_tpu.common.model_utils import (
        load_model_spec_from_module,
    )
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.proto import elasticdl_pb2 as pb
    from elasticdl_tpu.proto.service import ServingStub, build_channel
    from elasticdl_tpu.serving import GenerationServer, ServingConfig
    from elasticdl_tpu.serving.disagg import HandoffCoordinator
    from elasticdl_tpu.training.trainer import Trainer
    from model_zoo.transformer_lm import transformer_lm as zoo

    params = ("vocab_size=8; seq_len=16; embed_dim=32; num_heads=2; "
              "num_layers=1")
    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(
        load_model_spec_from_module(zoo), mesh=mesh,
        model_params=params + "; kv_cache_dtype='int8'",
    )
    toks = (np.arange(17)[None, :] % 8).astype(np.int32)
    batch = ({"tokens": toks[:, :-1]}, toks[:, 1:])
    state = trainer.init_state(batch)
    draft_trainer = Trainer(  # float draft, mismatched weights
        load_model_spec_from_module(zoo), mesh=mesh,
        model_params=params, seed=321,
    )
    draft_state = draft_trainer.init_state(batch)

    systems = [[1, 2, 3, 4], [5, 6, 7, 1, 2, 3, 4, 5]]
    specs = []
    for i in range(32):
        prompt = list(systems[i % 2]) + ([1 + i % 3] if i % 4 else [])
        specs.append({"prompt": prompt, "new": 3 + i % 5})

    cfg_p = ServingConfig(
        num_slots=2, queue_capacity=16,
        kv_block_size=4, kv_num_blocks=24, kv_shared=True,
        role="prefill",
    )
    cfg_d = ServingConfig(
        num_slots=6, queue_capacity=64,
        kv_block_size=4, kv_num_blocks=24, kv_shared=True,
        draft_k=2, role="decode",
    )
    sp = GenerationServer(trainer, state, cfg_p).start()
    sd = GenerationServer(
        trainer, state, cfg_d, draft=(draft_trainer, draft_state)
    ).start()

    class _Rep(object):
        def __init__(self, port):
            self.address = "localhost:%d" % port
            self.stub = ServingStub(build_channel(self.address))

    class _Req(object):
        def __init__(self, prompt):
            self.prompt = prompt
            self.temperature = 0.0
            self.seed = 0

    try:
        rp, rd = _Rep(sp.port), _Rep(sd.port)
        co = HandoffCoordinator()
        unique = sorted({tuple(s["prompt"]) for s in specs})
        for p in unique:
            payload = co.export_chain(
                rp, _Req(list(p)), co.new_transfer_id()
            )
            co.import_chain(rd, payload)

        results, errors = {}, {}

        def call(i, s):
            try:
                r = rd.stub.generate(
                    pb.GenerateRequest(
                        prompt=s["prompt"],
                        max_new_tokens=s["new"],
                    ),
                    timeout=120,
                )
                results[i] = list(r.tokens)
            except Exception as e:  # noqa: BLE001
                errors[i] = e

        threads = [
            threading.Thread(target=call, args=(i, s))
            for i, s in enumerate(specs)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=180)
        assert not errors, errors
        assert len(results) == 32

        stp = rp.stub.server_status(pb.ServerStatusRequest(),
                                    timeout=10)
        std = rd.stub.server_status(pb.ServerStatusRequest(),
                                    timeout=10)
        assert stp.role == "prefill" and std.role == "decode"
        assert stp.chain_exports == len(unique)
        assert std.chain_imports >= 1
        assert std.chain_import_tokens >= 4
        # every decode request seated on an imported chain
        assert std.prefix_hit_tokens > 0
        assert std.draft_k == 2 and std.draft_proposed > 0
        # clean two-pool post-drain ledger, nothing in flight
        assert stp.transfers_inflight == 0
        assert std.transfers_inflight == 0
        assert stp.kv_blocks_free == stp.kv_blocks_total == 24
        assert std.kv_blocks_free == std.kv_blocks_total == 24
    finally:
        sp.stop()
        sd.stop()

    for i, s in enumerate(specs):
        off = np.asarray(autoregressive_generate(
            trainer, state, np.asarray([s["prompt"]], np.int32),
            s["new"], use_cache=True,
        ))[0]
        assert list(off) == results[i], (i, s)
