"""Sequence/context parallelism over the `sp` mesh axis: ring attention
and Ulysses (all-to-all head/sequence transpose).

The reference has no long-context story (SURVEY.md §5: no ring attention,
no sequence parallelism anywhere in the tree); this module is the
TPU-native design the rebuild reserves the `sp` axis for. Two schemes,
both inside `jit` via `shard_map` and differentiable (ppermute and
all_to_all have transpose rules), so the same code paths train:

* **Ring** (`ring_attention`): the sequence axis of q/k/v is sharded
  over `sp`; key/value shards rotate around the ring with
  `jax.lax.ppermute` (ICI neighbor exchange) while partial softmax
  results merge online — the full sequence never materializes anywhere.
  Works for any head count; communication is 2(sp-1) neighbor hops of
  the local kv shard per attention.
* **Ulysses** (`ulysses_attention`): one `all_to_all` re-shards heads
  against sequence so each device holds heads/sp *full-sequence* heads,
  runs the local flash/blockwise kernel over the whole sequence, and
  transposes back. Requires heads % sp == 0; communication is 4
  all-to-alls of the activations per attention, and the inner kernel
  sees the full sequence (better MXU tiling than sp-chunked ring steps).
"""

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common.constants import MeshAxis
from elasticdl_tpu.parallel.pipeline import shard_map
from elasticdl_tpu.ops.attention import (
    NEG_INF as _NEG_INF,
    attention_backward_lse,
    attention_forward_lse,
    blockwise_attention,
    flash_attention,
    jax_flash_attention,
    lse_merge,
    resolve_block,
    segments_float0,
)


def _win_live(shard_len, window, size):
    """Number of statically-reachable windowed-rotation branches:
    offset r is live iff its closest pair (q=first row, k=last key)
    is inside the window, r*shard_len - (shard_len-1) < window. All
    inputs are static python ints at trace time."""
    return min(size, (window + shard_len - 2) // shard_len + 1)


def _win_offsets(shard_len, window, size, causal):
    """The static branch-offset list matching _win_case's indexing:
    causal -> [0..live), non-causal -> [-(live-1)..live). The skip
    branch goes LAST; fwd and bwd build their switches from this one
    list so they cannot desynchronize."""
    live = _win_live(shard_len, window, size)
    if causal:
        return list(range(live))
    return list(range(-(live - 1), live))


def _win_case(src, my, shard_len, window, size, causal):
    """Switch index for a windowed rotation, shared by the forward and
    backward rings so the skip invariant cannot desynchronize
    gradients from outputs (cf. _ring_case).

    Causal: shard offset r = my - src selects branch r; r < 0
    (strictly newer) and band-empty offsets map to the skip branch at
    index _win_live(...).
    Non-causal: signed offsets in (-live, live) select branch
    off + live - 1 (the two-sided band at |off| shards); |off| outside
    the band maps to the skip branch at index 2*live - 1."""
    off = my - src
    live = _win_live(shard_len, window, size)
    if causal:
        return jnp.where(
            (off < 0) | (off * shard_len - (shard_len - 1) >= window),
            live, off,
        ).astype(jnp.int32)
    empty = jnp.abs(off) * shard_len - (shard_len - 1) >= window
    return jnp.where(
        empty, 2 * live - 1, off + live - 1
    ).astype(jnp.int32)


def _ring_case(src, my):
    """Causal visibility of kv shard `src` from query shard `my` with
    equal shard lengths: 0 = fully visible (src strictly older), 1 =
    diagonal (local causal mask), 2 = fully masked (src strictly newer —
    skipped, no compute). This is why the per-shard kernels never need a
    dynamic position offset: the offsets only matter on the diagonal,
    where they cancel."""
    return jnp.where(src == my, 1, jnp.where(src < my, 0, 2)).astype(
        jnp.int32
    )


def _ring_fwd_impl(q, k, v, seg, axis_name, causal, scale, block_q,
                   block_k, window):
    """Ring forward: per rotation, the LOCAL flash kernel produces a
    normalized partial (o_i, lse_i) for the currently-held kv shard,
    merged online via lse_merge; kv shards rotate with ppermute. The full
    sequence never materializes. Returns (o [q.dtype], lse [f32]).

    `seg` (packed sequences): the LOCAL [b, lq] segment ids. The k-side
    ids travel WITH their kv shard around the ring, and each rotation
    masks with the rectangular (local q ids, held k ids) pair; a
    rotation whose shard shares no segment with a query row yields a
    (0, -inf) partial that the merge ignores (attention_forward_lse
    guarantees that sentinel form)."""
    size = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    b, h, lq, _ = q.shape
    perm = [((j + 1) % size, j) for j in range(size)]
    f32 = jnp.float32
    has_seg = seg is not None

    def _pair(kseg_cur):
        return (seg, kseg_cur) if has_seg else None

    def full(qq, kk, vv, kseg_cur):
        o, lse = attention_forward_lse(qq, kk, vv, causal=False,
                                       scale=scale, block_q=block_q,
                                       block_k=block_k,
                                       segments=_pair(kseg_cur))
        return o.astype(f32), lse

    def diag(qq, kk, vv, kseg_cur):
        o, lse = attention_forward_lse(qq, kk, vv, causal=True,
                                       scale=scale, block_q=block_q,
                                       block_k=block_k,
                                       segments=_pair(kseg_cur))
        return o.astype(f32), lse

    def skip(qq, kk, vv, kseg_cur):
        return (jnp.zeros(qq.shape, f32),
                jnp.full((b, h, lq), _NEG_INF, f32))

    # windowed: one statically-compiled branch per shard offset — the
    # global window mask of a rotation IS the local window mask with q
    # positions shifted by offset*shard_len (causal: offsets >= 0,
    # causality auto-holds off-diagonal and the symmetric lower bound
    # is auto-true; non-causal: signed offsets give the two-sided
    # band). `size` is a static int (psum of a literal), so the branch
    # list is a python list; only the selector is traced.
    def _win_branch(r):
        def br(qq, kk, vv, kseg_cur):
            o, lse = attention_forward_lse(
                qq, kk, vv, causal=(causal and r == 0), scale=scale,
                block_q=block_q, block_k=block_k,
                segments=_pair(kseg_cur), pos_offset=r * lq,
                window=window,
            )
            return o.astype(f32), lse

        return br

    def _win_branches():
        return [
            _win_branch(off)
            for off in _win_offsets(lq, window, size, causal)
        ] + [skip]

    def merge(o, lse, k_cur, v_cur, kseg_cur, i):
        # after i rotations device `my` holds the shard born on my+i
        if window is not None:
            o_i, lse_i = jax.lax.switch(
                _win_case((my + i) % size, my, lq, window, size,
                          causal),
                _win_branches(),
                q, k_cur, v_cur, kseg_cur,
            )
        elif causal:
            o_i, lse_i = jax.lax.switch(
                _ring_case((my + i) % size, my), (full, diag, skip),
                q, k_cur, v_cur, kseg_cur,
            )
        else:
            o_i, lse_i = full(q, k_cur, v_cur, kseg_cur)
        return lse_merge(o, lse, o_i, lse_i)

    def step(carry, i):
        # kseg rides the ring ONLY when packing is on (has_seg is
        # trace-static): the default path keeps its original
        # two-operand collective-permute shape
        if has_seg:
            o, lse, k_cur, v_cur, kseg_cur = carry
        else:
            (o, lse, k_cur, v_cur), kseg_cur = carry, None
        # rotation FIRST, local attention second: the ppermute depends
        # only on the held shard, so issuing it before the compute lets
        # XLA's latency-hiding scheduler run the ICI transfer UNDER the
        # flash kernel instead of after it (comm/compute overlap — the
        # point of ring attention)
        rot = (k_cur, v_cur, kseg_cur) if has_seg else (k_cur, v_cur)
        rot = jax.lax.ppermute(rot, axis_name, perm)
        o, lse = merge(o, lse, k_cur, v_cur, kseg_cur, i)
        return (o, lse) + rot, None

    o0 = jnp.zeros(q.shape, f32)
    lse0 = jnp.full((b, h, lq), _NEG_INF, f32)
    carry0 = (o0, lse0, k, v) + ((seg,) if has_seg else ())
    # the last shard's rotation would be discarded — merge it outside the
    # scan so each step pays exactly the ppermutes it uses
    final, _ = jax.lax.scan(step, carry0, jnp.arange(size - 1))
    if has_seg:
        o, lse, k_last, v_last, kseg_last = final
    else:
        (o, lse, k_last, v_last), kseg_last = final, None
    o, lse = merge(o, lse, k_last, v_last, kseg_last, size - 1)
    return o.astype(q.dtype), lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _ring_attention(q, k, v, seg, axis_name, causal, scale, block_q,
                    block_k, window):
    o, _ = _ring_fwd_impl(q, k, v, seg, axis_name, causal, scale,
                          block_q, block_k, window)
    return o


def _ring_vjp_fwd(q, k, v, seg, axis_name, causal, scale, block_q,
                  block_k, window):
    o, lse = _ring_fwd_impl(q, k, v, seg, axis_name, causal, scale,
                            block_q, block_k, window)
    return o, (q, k, v, seg, o, lse)


def _ring_vjp_bwd(axis_name, causal, scale, block_q, block_k, window,
                  res, g):
    """Ring backward: a second ring pass. Each rotation recomputes this
    shard's slice of the global softmax from the saved global logsumexp
    (attention_backward_lse — the Pallas two-pass kernels on TPU), adds
    dq locally, and accumulates dk/dv into buffers that TRAVEL WITH
    their kv shard around the ring; after the full cycle of ppermutes
    every dk/dv accumulator is back on the device that owns its shard."""
    q, k, v, seg, o, lse = res
    size = jax.lax.psum(1, axis_name)
    my = jax.lax.axis_index(axis_name)
    perm = [((j + 1) % size, j) for j in range(size)]
    f32 = jnp.float32
    has_seg = seg is not None

    def _pair(kseg_cur):
        return (seg, kseg_cur) if has_seg else None

    def full(kk, vv, kseg_cur):
        return attention_backward_lse(q, kk, vv, o, lse, g, causal=False,
                                      scale=scale, block_q=block_q,
                                      block_k=block_k, grad_dtype=f32,
                                      segments=_pair(kseg_cur))

    def diag(kk, vv, kseg_cur):
        return attention_backward_lse(q, kk, vv, o, lse, g, causal=True,
                                      scale=scale, block_q=block_q,
                                      block_k=block_k, grad_dtype=f32,
                                      segments=_pair(kseg_cur))

    def skip(kk, vv, kseg_cur):
        return (jnp.zeros(q.shape, f32), jnp.zeros(kk.shape, f32),
                jnp.zeros(vv.shape, f32))

    lq = q.shape[2]

    def _win_branch(r):
        def br(kk, vv, kseg_cur):
            return attention_backward_lse(
                q, kk, vv, o, lse, g, causal=(causal and r == 0),
                scale=scale,
                block_q=block_q, block_k=block_k, grad_dtype=f32,
                segments=_pair(kseg_cur), pos_offset=r * lq,
                window=window,
            )

        return br

    def _win_branches():
        return [
            _win_branch(off)
            for off in _win_offsets(lq, window, size, causal)
        ] + [skip]

    def grads(k_cur, v_cur, kseg_cur, i):
        if window is not None:
            return jax.lax.switch(
                _win_case((my + i) % size, my, lq, window, size,
                          causal),
                _win_branches(),
                k_cur, v_cur, kseg_cur,
            )
        if causal:
            return jax.lax.switch(
                _ring_case((my + i) % size, my), (full, diag, skip),
                k_cur, v_cur, kseg_cur,
            )
        return full(k_cur, v_cur, kseg_cur)

    def step(carry, i):
        if has_seg:
            dq, k_cur, v_cur, kseg_cur, dk_acc, dv_acc = carry
        else:
            (dq, k_cur, v_cur, dk_acc, dv_acc), kseg_cur = carry, None
        # two permutes instead of one: the kv shards don't depend on
        # this step's gradients, so their (large) transfer is issued
        # BEFORE the kernels and can ride ICI under the compute; only
        # the dk/dv accumulators — which need this step's results — pay
        # an exposed hop
        kv_rot = jax.lax.ppermute(
            (k_cur, v_cur) + ((kseg_cur,) if has_seg else ()),
            axis_name, perm,
        )
        dq_i, dk_i, dv_i = grads(k_cur, v_cur, kseg_cur, i)
        dq = dq + dq_i
        acc_rot = jax.lax.ppermute(
            (dk_acc + dk_i, dv_acc + dv_i), axis_name, perm
        )
        return (dq,) + kv_rot + acc_rot, None

    carry0 = (
        (jnp.zeros(q.shape, f32), k, v)
        + ((seg,) if has_seg else ())
        + (jnp.zeros(k.shape, f32), jnp.zeros(v.shape, f32))
    )
    final, _ = jax.lax.scan(step, carry0, jnp.arange(size - 1))
    if has_seg:
        dq, k_last, v_last, kseg_last, dk_acc, dv_acc = final
    else:
        (dq, k_last, v_last, dk_acc, dv_acc), kseg_last = final, None
    # final shard: compute in place, then one last hop delivers the
    # accumulators home (kv shards themselves are done rotating)
    dq_i, dk_i, dv_i = grads(k_last, v_last, kseg_last, size - 1)
    dq = dq + dq_i
    dk_acc, dv_acc = jax.lax.ppermute(
        (dk_acc + dk_i, dv_acc + dv_i), axis_name, perm
    )
    return (dq.astype(q.dtype), dk_acc.astype(k.dtype),
            dv_acc.astype(v.dtype), segments_float0(seg))


_ring_attention.defvjp(_ring_vjp_fwd, _ring_vjp_bwd)


def ring_attention_local(q, k, v, axis_name, causal=False, scale=None,
                         block_q=None, block_k=None, segments=None,
                         window=None):
    """Per-device body: q/k/v are the local sequence shards
    [batch, heads, local_len, dim]. Call inside shard_map/pjit with a
    named `axis_name` axis; returns the local output shard. The local
    compute per rotation is the Pallas flash kernel (fwd + two-pass bwd)
    when it can run, with a blockwise/dense jnp fallback. `segments`:
    the LOCAL [b, local_len] packed-sequence ids (k-side ids rotate
    with their kv shard)."""
    scale = float(scale) if scale is not None else q.shape[-1] ** -0.5
    # resolve tuned defaults here: the custom_vjp's nondiff args must be
    # concrete ints
    block_q = resolve_block(block_q, "q")
    block_k = resolve_block(block_k, "k")
    if causal and q.shape[2] != k.shape[2]:
        # The three-way shard classification (_ring_case) relies on
        # equal-length q/kv shards so diagonal offsets cancel; unequal
        # lengths would need per-shard position offsets in the kernel.
        raise ValueError(
            "causal ring attention requires equal q/kv sequence lengths "
            "per shard, got lq=%d lk=%d" % (q.shape[2], k.shape[2])
        )
    if window is not None:
        window = int(window)
        if window < 1:
            raise ValueError("window must be >= 1, got %r" % (window,))
    if segments is not None:
        segments = jnp.asarray(segments, jnp.int32)
    return _ring_attention(q, k, v, segments, axis_name, causal, scale,
                           block_q, block_k, window)


def ring_attention(q, k, v, mesh, causal=False, scale=None,
                   block_q=None, block_k=None, segments=None,
                   window=None,
                   seq_axis=MeshAxis.SP, batch_axes=(MeshAxis.DP,
                                                     MeshAxis.FSDP)):
    """Global-view ring attention: q/k/v are [batch, heads, seq, dim]
    arrays (sharded or not); the sequence axis is laid out over
    `seq_axis` and batch over `batch_axes`, and XLA inserts only the
    ring ppermutes — no full-sequence gather. `segments` [batch, seq]:
    packed-sequence ids, sequence-sharded like q (long-context packed
    training; each rotation masks with the held shard's ids).

    With an sp=1 mesh this degenerates to one shard_map program == plain
    attention.
    """
    spec = P(batch_axes, None, seq_axis, None)
    seg_spec = P(batch_axes, seq_axis)
    local = functools.partial(
        ring_attention_local,
        axis_name=seq_axis,
        causal=causal,
        scale=scale,
        block_q=block_q,
        block_k=block_k,
        window=window,
    )
    if segments is None:
        fn = shard_map(
            local, mesh, (spec, spec, spec), spec,
        )
        return fn(q, k, v)
    fn = shard_map(
        lambda qq, kk, vv, ss: local(qq, kk, vv, segments=ss),
        mesh, (spec, spec, spec, seg_spec), spec,
    )
    return fn(q, k, v, jnp.asarray(segments, jnp.int32))


def sharded_flash_attention(q, k, v, mesh, causal=False, window=None,
                            segments=None,
                            batch_axes=(MeshAxis.DP, MeshAxis.FSDP),
                            head_axis=MeshAxis.TP):
    """flash_attention under a multi-device mesh with no sp axis: q/k/v
    are global [batch, heads, seq, dim] arrays. XLA cannot partition a
    Mosaic kernel ("wrap the call in a shard_map"), so the call is one
    shard_map program: batch over `batch_axes`, heads over `head_axis`,
    each device running the bare kernel on its shard — no collective,
    attention rows are independent. An axis whose size does not divide
    the dimension it would shard is left out (that dimension is then
    computed redundantly on every device along it, which is what the
    partitioner does with a replicated operand); both head counts must
    divide for the head axis, or the GQA group map breaks."""
    def fits(dim, axes):
        size = 1
        for ax in axes:
            size *= mesh.shape.get(ax, 1)
        return size > 1 and dim % size == 0

    b_ax = tuple(batch_axes) if fits(q.shape[0], batch_axes) else None
    h_ax = (
        head_axis
        if fits(q.shape[1], (head_axis,)) and fits(k.shape[1], (head_axis,))
        else None
    )
    spec = P(b_ax, h_ax, None, None)
    local = functools.partial(flash_attention, causal=causal,
                              window=window)
    if segments is None:
        return shard_map(local, mesh, (spec, spec, spec), spec)(q, k, v)
    return shard_map(
        lambda qq, kk, vv, ss: local(qq, kk, vv, segments=ss),
        mesh, (spec, spec, spec, P(b_ax, None)), spec,
    )(q, k, v, jnp.asarray(segments, jnp.int32))


# Local full-sequence attention per Ulysses impl choice; "jax_flash" is
# jax's bundled TPU kernel (ops/attention.jax_flash_attention). Unknown
# values are validated in ulysses_attention before tracing.
_ULYSSES_LOCAL_ATTN = {
    "auto": flash_attention,
    "xla": blockwise_attention,
    "jax_flash": jax_flash_attention,
}


def ulysses_attention_local(q, k, v, axis_name, causal=False, scale=None,
                            attn_impl="auto", segments=None,
                            window=None):
    """Per-device body: q/k/v are local sequence shards
    [batch, heads, local_len, dim]. One tiled all_to_all turns them into
    [batch, heads/sp, full_len, dim] (device i holds head block i), the
    full-sequence attention kernel runs locally, and the inverse
    all_to_all restores the sequence-sharded layout. `segments`: local
    [b, local_len] packed ids — all-gathered to the full sequence (ints
    are tiny next to the activation all-to-alls) since the local kernel
    sees the whole sequence."""

    def to_heads(x):
        return jax.lax.all_to_all(
            x, axis_name, split_axis=1, concat_axis=2, tiled=True
        )

    local_attn = _ULYSSES_LOCAL_ATTN[attn_impl]
    kwargs = {}
    if window is not None:
        # each device holds FULL-sequence heads after the all_to_all,
        # so the plain single-shard window mask applies directly
        kwargs["window"] = window
    if segments is not None:
        kwargs["segments"] = jax.lax.all_gather(
            jnp.asarray(segments, jnp.int32), axis_name, axis=1,
            tiled=True,
        )
    out = local_attn(
        to_heads(q), to_heads(k), to_heads(v), causal=causal,
        scale=scale, **kwargs
    )
    return jax.lax.all_to_all(
        out, axis_name, split_axis=2, concat_axis=1, tiled=True
    )


def ulysses_attention(q, k, v, mesh, causal=False, scale=None,
                      attn_impl="auto", segments=None, window=None,
                      seq_axis=MeshAxis.SP, batch_axes=(MeshAxis.DP,
                                                        MeshAxis.FSDP)):
    """Global-view Ulysses attention: q/k/v are [batch, heads, seq, dim];
    the sequence axis is laid out over `seq_axis`. Each device computes
    heads/sp full-sequence heads between two all-to-all transposes.

    With an sp=1 mesh this degenerates to one shard_map program == plain
    attention. Requires heads to divide evenly over the sp axis — use
    ring attention otherwise.
    """
    if attn_impl not in _ULYSSES_LOCAL_ATTN:
        raise ValueError(
            "Unknown attn_impl %r (valid: %s)"
            % (attn_impl, ", ".join(sorted(_ULYSSES_LOCAL_ATTN)))
        )
    if segments is not None and attn_impl == "jax_flash":
        raise ValueError(
            "attn_impl='jax_flash' does not support packed-sequence "
            "masking; use attn_impl='auto' or 'xla'"
        )
    if window is not None and attn_impl == "jax_flash":
        raise ValueError(
            "attn_impl='jax_flash' does not support sliding-window "
            "attention; use attn_impl='auto' or 'xla'"
        )
    sp = mesh.shape.get(seq_axis, 1)
    heads = q.shape[1]
    if heads % sp:
        raise ValueError(
            "ulysses_attention needs num_heads (%d) divisible by the %s "
            "axis (%d); use ring attention for this config"
            % (heads, seq_axis, sp)
        )
    spec = P(batch_axes, None, seq_axis, None)
    seg_spec = P(batch_axes, seq_axis)
    local = functools.partial(
        ulysses_attention_local,
        axis_name=seq_axis,
        causal=causal,
        scale=scale,
        attn_impl=attn_impl,
        window=window,
    )
    if segments is None:
        fn = shard_map(
            local, mesh, (spec, spec, spec), spec,
        )
        return fn(q, k, v)
    fn = shard_map(
        lambda qq, kk, vv, ss: local(qq, kk, vv, segments=ss),
        mesh, (spec, spec, spec, seg_spec), spec,
    )
    return fn(q, k, v, jnp.asarray(segments, jnp.int32))
