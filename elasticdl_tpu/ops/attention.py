"""Attention ops: naive reference, blockwise (memory-efficient), and a
Pallas flash-attention TPU kernel with a two-pass Pallas backward
(query-parallel dq, key-parallel dk/dv, P recomputed from the saved
logsumexp).

The reference framework has no attention/sequence stack at all
(SURVEY.md §5 "long-context: absent") — this is net-new TPU-first
capability: the single-chip kernels here are the local compute of the
ring/context-parallel attention in parallel/context_parallel.py, which
shards the sequence axis over the `sp` mesh axis.

Layout convention: [batch, heads, seq, head_dim].
"""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.common.log_utils import default_logger as logger
from elasticdl_tpu.ops.dispatch import (
    interpret_mode,
    is_tpu_backend,
    use_cond_mask,
    use_paged_kernel,
    use_pallas,
)

_NEG_INF = -1e30
NEG_INF = _NEG_INF  # masking constant shared with context_parallel
# The kernels run their online softmax in the exp2 domain: log2(e) is
# folded into the (already present) q scale multiply, so every
# per-element exp() in the inner loop becomes the VPU-native exp2()
# without the implicit x*log2e multiply exp() performs. Outputs convert
# back to natural-log units (lse) at the block epilogue, so nothing
# outside the kernels sees base-2 values.
_LOG2E = float(np.log2(np.e))
_LN2 = float(np.log(2.0))

# Tuned flash block defaults: hardware sweeps (scripts/bench_attention.py)
# persist their winner here so every call site
# that leaves block sizes unset — the model zoo, ring attention — picks
# it up. Resolution order: explicit argument > EDL_FLASH_BLOCK_Q/K env >
# ops/flash_tuning.json > 128.
_TUNING_FILE = os.path.join(os.path.dirname(__file__),
                            "flash_tuning.json")
_tuning_cache = None


def _tuned_blocks():
    global _tuning_cache
    if _tuning_cache is None:
        cfg = {}
        try:
            with open(_TUNING_FILE) as f:
                cfg = json.load(f)
        except (OSError, ValueError):
            pass
        _tuning_cache = cfg if isinstance(cfg, dict) else {}
    return _tuning_cache


def _align8(value):
    """Flash blocks must be multiples of 8 (_flash_tiles) — a misaligned
    tuned value would silently disable the kernel repo-wide, so round
    down instead."""
    return max(8, (int(value) // 8) * 8)


def resolve_block(explicit, which):
    """Resolve a flash block size: `which` is "q" or "k"."""
    if explicit is not None:
        return int(explicit)
    raw = os.environ.get("EDL_FLASH_BLOCK_%s" % which.upper(), "")
    if raw:
        try:
            return _align8(raw)
        except ValueError:
            pass
    value = _tuned_blocks().get("block_%s" % which)
    try:
        return _align8(value) if value else 128
    except (TypeError, ValueError):
        return 128


def resolve_paged_rows(explicit=None):
    """Query-row tile for the fused paged decode kernel
    (_paged_decode_fused): the group*t query rows of each (batch,
    kv-head) program are padded up to a multiple of this, so it is the
    kernel's sublane occupancy knob — bigger tiles round tiny
    verify-k/GQA row counts up to fuller VPU/MXU sublanes at the price
    of masked-row FLOPs. Resolution order mirrors the flash blocks:
    explicit argument > EDL_PAGED_ROWS env > flash_tuning.json
    "paged_rows" > 8. The default 8 is the CPU-SAFE floor (one f32
    sublane tile): interpret mode pays per-element for padding, and 8
    is also the smallest legal Mosaic row tile, so an untuned install
    is correct everywhere — scripts/bench_attention.py --paged sweeps
    and persists the hardware winner."""
    if explicit is not None:
        return _align8(explicit)
    raw = os.environ.get("EDL_PAGED_ROWS", "")
    if raw:
        try:
            return _align8(raw)
        except ValueError:
            pass
    value = _tuned_blocks().get("paged_rows")
    try:
        return _align8(value) if value else 8
    except (TypeError, ValueError):
        return 8


def dispatch_summary():
    """The attention policy this process traces with, for the one
    start-up log line (common/platform_utils.log_startup). Per-shape
    exceptions are logged where they are decided: flash_attention
    warns when a sequence does not tile, the server names its paged
    decode implementation (paged_decode_impl)."""
    if not use_pallas():
        return {"attention": "xla-blockwise (kernels off)"}
    return {"attention": "pallas-flash%s, blocks (%d, %d)" % (
        " interpreted" if interpret_mode() else "",
        resolve_block(None, "q"), resolve_block(None, "k"),
    )}


def softmax_merge(o, l, m, s, v_blk, w_scale=None):
    """One online-softmax accumulation step: merge scores `s`
    [b,h,q,k_blk] and values `v_blk` [b,h,k_blk,d] into the running
    (output, denominator, rowmax) triple. Shared by blockwise_attention,
    ring attention and the paged decode scan so the subtle numerics
    live once.

    `w_scale` [b,h,k_blk] (optional) multiplies the weights ONLY in the
    value matmul — the v-side of the deferred int8-KV dequantize:
    `p @ (v8 * vs) == (p * vs^T) @ v8`, so scaling the [*, k] weights
    (a head_dim-times smaller array than the rows) lets `v_blk` stay
    int8 all the way into the matmul operand read. The softmax
    denominator `l` is NOT scaled — it normalizes probabilities, which
    are dequantize-invariant."""
    m_new = jnp.maximum(m, s.max(-1))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m - m_new)
    l_new = l * corr + p.sum(-1)
    pv = p if w_scale is None else p * w_scale[..., None, :]
    o_new = o * corr[..., None] + jnp.einsum("bhqk,bhkd->bhqd", pv,
                                             v_blk)
    return o_new, l_new, m_new


def softmax_finalize(o, l):
    return o / jnp.maximum(l, 1e-30)[..., None]


def lse_merge(o, lse, o_i, lse_i):
    """Merge two NORMALIZED attention partials (o, logsumexp) over the
    same queries but disjoint key sets — the combine step of ring
    attention (parallel/context_parallel.py). A fully-masked partial
    (lse_i == NEG_INF) contributes zero weight. Accumulate in float32."""
    lse_new = jnp.logaddexp(lse, lse_i)
    w = jnp.exp(lse - lse_new)[..., None]
    w_i = jnp.exp(lse_i - lse_new)[..., None]
    return o * w + o_i * w_i, lse_new


def group_size(q, k):
    """Grouped-query group size: q heads per kv head. 1 for standard
    multi-head attention; >1 when k/v carry fewer heads (GQA; ==num_heads
    for multi-query). Validates divisibility."""
    h, hkv = q.shape[1], k.shape[1]
    if h % hkv:
        raise ValueError(
            "grouped-query attention needs num_heads %% num_kv_heads "
            "== 0, got %d q heads / %d kv heads" % (h, hkv)
        )
    return h // hkv


def expand_kv(kv, num_heads):
    """Broadcast grouped-query K/V [b, hkv, l, d] to the full q head
    count (head j reads kv head j // group — the standard GQA layout:
    consecutive q heads share a kv head). Fallback for the jnp paths and
    kernels without native grouping; the Pallas flash kernels instead
    index kv blocks through the same j // group map, moving each kv
    block HBM->VMEM once per group instead of materializing the repeat."""
    hkv = kv.shape[1]
    if hkv == num_heads:
        return kv
    if num_heads % hkv:
        raise ValueError(
            "cannot expand %d kv heads to %d q heads" % (hkv, num_heads)
        )
    return jnp.repeat(kv, num_heads // hkv, axis=1)


def _check_segments(segments, b, lq, lk):
    """Normalize the sequence-packing mask argument.

    Accepted forms:
      * one [b, l] id array — square self-attention (q and k share the
        ids; every position sees itself, so no row is ever fully
        masked), or
      * a (q_seg [b, lq], k_seg [b, lk]) pair — rectangular, e.g. one
        ring-attention rotation where the held kv shard's ids differ
        from the local query shard's (rows CAN be fully masked there;
        the lse sentinel handling in attention_forward_lse covers it).

    Returns (q_seg, k_seg) int32 or None."""
    if segments is None:
        return None
    if isinstance(segments, (tuple, list)):
        if len(segments) != 2:
            raise ValueError(
                "segments pair must be (q_seg, k_seg), got %d items"
                % len(segments)
            )
        q_seg = jnp.asarray(segments[0], jnp.int32)
        k_seg = jnp.asarray(segments[1], jnp.int32)
    else:
        if lq != lk:
            raise ValueError(
                "a single segments array requires square self-"
                "attention (lq == lk), got lq=%d lk=%d; pass a "
                "(q_seg, k_seg) pair for rectangular shapes"
                % (lq, lk)
            )
        q_seg = k_seg = jnp.asarray(segments, jnp.int32)
    if q_seg.shape != (b, lq) or k_seg.shape != (b, lk):
        raise ValueError(
            "segments must be [batch, seq]: q side (%d, %d), k side "
            "(%d, %d); got %r / %r"
            % (b, lq, b, lk, tuple(q_seg.shape), tuple(k_seg.shape))
        )
    return q_seg, k_seg


def segments_float0(segments):
    """The float0 (empty) cotangent for integer segment ids — what a
    custom_vjp backward must return for a segments argument. Accepts
    None, one array, or the (q_seg, k_seg) pair."""
    if segments is None:
        return None
    if isinstance(segments, (tuple, list)):
        return tuple(
            np.zeros(s.shape, jax.dtypes.float0) for s in segments
        )
    return np.zeros(segments.shape, jax.dtypes.float0)


def causal_limit(q_pos, block_causal):
    """The last key position a causal row at `q_pos` sees: itself, or
    with `block_causal` B > 1 the end of its block of B positions
    (blocks aligned at position 0): causal across blocks, every
    position of a row's own block in both directions."""
    if block_causal and block_causal > 1:
        return q_pos // block_causal * block_causal + (block_causal - 1)
    return q_pos


def naive_attention(q, k, v, causal=False, scale=None, window=None,
                    segments=None, block_causal=0):
    """Reference softmax(q k^T) v; O(L^2) memory. The test oracle (the
    flash backward is the Pallas two-pass _flash_backward below).
    `window` (sliding-window/local attention): query at position p sees
    keys in (p - window, p] under causal, |p - k| < window otherwise —
    None means unbounded. k/v may carry fewer heads than q (GQA).
    `segments` [b, l] int: sequence-packing mask — attention stays
    within same-id runs (cross-segment scores are masked out).
    `block_causal` B > 1 (with causal): a row's limit is the end of
    its block of B positions (causal_limit)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    _check_window(window, q.shape[2], k.shape[2])
    segments = _check_segments(segments, q.shape[0], q.shape[2],
                               k.shape[2])
    k = expand_kv(k, q.shape[1])
    v = expand_kv(v, q.shape[1])
    scores = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    lq, lk = scores.shape[-2], scores.shape[-1]
    q_pos = jnp.arange(lq)[:, None]
    k_pos = jnp.arange(lk)[None, :]
    mask = jnp.ones((lq, lk), bool)
    if causal:
        mask &= causal_limit(q_pos, block_causal) >= k_pos
    if window is not None:
        mask &= q_pos - k_pos < window
        if not causal:
            mask &= k_pos - q_pos < window
    keep = jnp.broadcast_to(mask[None, None], scores.shape)
    if segments is not None:
        q_seg, k_seg = segments
        seg_mask = q_seg[:, :, None] == k_seg[:, None, :]
        keep = keep & seg_mask[:, None]
    scores = jnp.where(keep, scores, _NEG_INF)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", weights, v)


def blockwise_attention(q, k, v, causal=False, scale=None, block_size=512,
                        window=None, with_lse=False, segments=None,
                        pos_offset=0, block_causal=0):
    """Online-softmax attention via lax.scan over key blocks: O(L) memory,
    differentiable, pure jnp (the fallback when the flash kernel can't
    run). Matches naive_attention to float tolerance. With
    `with_lse=True` also returns the float32 logsumexp [b, h, lq] (the
    ring-attention partial form; see attention_forward_lse).
    `segments` [b, l] int: sequence-packing mask (see naive_attention).
    `block_causal`: a causal row's limit is its block's end
    (causal_limit)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    b, h, lq, d = q.shape
    lk = k.shape[2]
    _check_window(window, lq, lk)
    segments = _check_segments(segments, b, lq, lk)
    k = expand_kv(k, h)
    v = expand_kv(v, h)
    block = min(block_size, lk)
    q_seg = k_seg = None
    if segments is not None:
        q_seg, k_seg = segments
    if lk % block:
        # pad keys; padded positions masked below via k_pos >= lk
        pad = block - lk % block
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
        if k_seg is not None:
            k_seg = jnp.pad(k_seg, ((0, 0), (0, pad)),
                            constant_values=-1)
    n_blocks = k.shape[2] // block
    k_blocks = k.reshape(b, h, n_blocks, block, d)
    v_blocks = v.reshape(b, h, n_blocks, block, d)
    q_scaled = q * scale
    q_pos = jnp.arange(lq) + pos_offset

    def step(carry, inputs):
        o, l, m = carry
        kb, vb, kb_idx = inputs[:3]
        s = jnp.einsum("bhqd,bhkd->bhqk", q_scaled, kb)
        k_pos = kb_idx * block + jnp.arange(block)
        valid = jnp.broadcast_to((k_pos < lk)[None, :], (lq, block))
        if causal:
            valid = valid & (causal_limit(q_pos, block_causal)[:, None]
                             >= k_pos[None, :])
        if window is not None:
            valid = valid & (q_pos[:, None] - k_pos[None, :] < window)
            if not causal:
                valid = valid & (k_pos[None, :] - q_pos[:, None] < window)
        keep = jnp.broadcast_to(valid[None, None], s.shape)
        if segments is not None:
            seg_kb = inputs[3]  # [b, block]
            keep = keep & (
                q_seg[:, :, None] == seg_kb[:, None, :]
            )[:, None]
        s = jnp.where(keep, s, _NEG_INF)
        return softmax_merge(o, l, m, s, vb), None

    xs = [
        jnp.moveaxis(k_blocks, 2, 0),
        jnp.moveaxis(v_blocks, 2, 0),
        jnp.arange(n_blocks),
    ]
    if segments is not None:
        xs.append(
            jnp.moveaxis(k_seg.reshape(b, n_blocks, block), 1, 0)
        )
    o0 = jnp.zeros_like(q)
    l0 = jnp.zeros((b, h, lq), q.dtype)
    m0 = jnp.full((b, h, lq), _NEG_INF, q.dtype)
    (o, l, m), _ = jax.lax.scan(step, (o0, l0, m0), tuple(xs))
    out = softmax_finalize(o, l)
    if with_lse:
        lse = (m + jnp.log(jnp.maximum(l, 1e-30))).astype(jnp.float32)
        return out, lse
    return out


def _paged_valid(k_pos, bid, length, row_pos, window):
    """The ONE paged-decode visibility predicate, shared by the lax.scan
    oracle and the fused Pallas kernel so the two paths can never
    disagree about a mask bit (the flash kernels' _block_run/_block_mask
    discipline, applied to the paged shape). All operands broadcast:

      k_pos:   absolute position of a pool row (block j row r sits at
               j*block_size + r — the block table is position-ordered)
      bid:     the row's block id; -1 marks an unallocated table slot
               (the gather clamps to block 0, this predicate masks it)
      length:  tokens already cached; rows at k_pos >= length are junk
               (the partially-filled tail of the newest block)
      row_pos: the query row's absolute position (length + tile offset)
      window:  sliding window — a row sees keys k_pos > row_pos - window
               (static; None = unbounded)
    """
    valid = (k_pos < length) & (bid >= 0)
    if window is not None:
        valid = valid & (k_pos > row_pos - window)
    return valid


def paged_live_blocks(length, window, block_size, m, xp=jnp):
    """The half-open range [j_lo, j_hi) of table slots that can hold a
    key some row of a query tile at `length` sees — what the fused
    kernel streams, and what serving counts as streamed.

    It follows _paged_valid, which stays the source of truth for each
    key: keys live at k_pos < length, so j_hi = min(m, ceil(length /
    block_size)); tile row 0 (position `length`) reaches furthest back,
    to k_pos = length - window + 1, so j_lo is that key's block (0
    without a window) — later rows and the keys of block j_lo before
    the reach are masked per key. The tile width does not enter: every
    row sees the pool up to `length` and none further back than row 0.
    length == 0 (a free lane) gives the empty range (0, 0).

    `xp` is the array module: jnp inside the kernel (scalars from
    SMEM), np on the host (the engine's per-lane counters)."""
    j_hi = xp.minimum((length + block_size - 1) // block_size, m)
    if window is None:
        return j_hi * 0, j_hi
    j_lo = xp.maximum(length - window + 1, 0) // block_size
    return xp.minimum(j_lo, j_hi), j_hi


def _tile_causal_mask(group, t, window, row_pos=None, block_causal=0):
    """[group*t, t] visibility of the query tile's OWN keys, shared by
    the scan and fused paths (both merge the tile outside the pool
    stream): tile key j' (absolute position length + j') is visible to
    tile row j iff j' <= j — causal within the tile — and any window >= 1
    keeps the diagonal (_check_window). With `block_causal` B > 1 the
    row's limit is the end of its block of B ABSOLUTE positions
    (causal_limit over `row_pos` [b, t]): a tile that is one aligned
    block sees all of itself, and the mask is a sequence's own.
    Returns it as the scores [b, hkv, group*t, t] take it: [1, 1, ...]
    or [b, 1, ...]."""
    if block_causal and block_causal > 1:
        tri = (causal_limit(row_pos, block_causal)[:, :, None]
               >= row_pos[:, None, :])  # [b, t_q, t_k]
        if window is not None:
            tri = tri & (row_pos[:, :, None] - row_pos[:, None, :]
                         < window)
        b = row_pos.shape[0]
        return jnp.broadcast_to(
            tri[:, None, :, :], (b, group, t, t)
        ).reshape(b, 1, group * t, t)
    tile = jnp.arange(t)
    tri = tile[:, None] >= tile[None, :]  # [t_q, t_k] causal
    if window is not None:
        tri = tri & (tile[:, None] - tile[None, :] < window)
    return jnp.broadcast_to(
        tri[None, :, :], (group, t, t)
    ).reshape(group * t, t)[None, None]


def paged_decode_attention(q, k_cur, v_cur, k_pool, v_pool, block_table,
                           length, scale=None, window=None,
                           k_scale_pool=None, v_scale_pool=None,
                           k_cur_scale=None, v_cur_scale=None,
                           use_kernel=None, block_causal=0):
    """Decode attention over a BLOCK-PAGED KV pool for a tile of
    1 <= t new query tokens per sequence.

    The serving engine's paged pool (serving/kv_pool.py) stores every
    sequence's cached keys/values as fixed-size blocks scattered through
    one shared `[num_blocks, block_size, kv_heads, head_dim]` arena per
    layer; a sequence's logical cache is its BLOCK TABLE — the ordered
    block ids covering positions `[j*block_size, (j+1)*block_size)`.
    This op attends a sequence's query TILE over exactly that table,
    streaming one block at a time through the same online-softmax
    merge `blockwise_attention` scans with (softmax_merge /
    softmax_finalize), so no contiguous `seq_len` stripe is ever
    gathered or materialized: peak extra memory is ONE block per step.

    t = 1 is the classic per-token decode step. t > 1 is the
    VERIFY-k tile (speculative decode: the target checks k drafted
    tokens in one step) and the shared-prefix SUFFIX prefill (the
    unshared tail of a prompt decodes as one tile over the resident
    prefix blocks) — tile row j sits at absolute position
    `length + j`, sees every pool row `k_pos < length`, and sees tile
    keys `j' <= j` (causal within the tile). With `block_causal` B > 1
    (a block-diffusion model's denoising tile) a tile row sees the
    tile keys up to the end of its own block of B absolute positions:
    a tile that is one aligned block sees every one of its keys
    (_tile_causal_mask); the pool rows, all of them earlier, as ever.

    q:      [b, h, t, d]   the tile ([b, h, d] accepted for the t = 1
                           legacy shape; the result then drops t too)
    k_cur:  [b, hkv, t, d] the tile's own keys/values (at positions
    v_cur:  [b, hkv, t, d] `length + j`; NOT in the pool yet — the
                           engine scatters the committed rows after
                           the step)
    k_pool: [num_blocks, block_size, hkv, d]   shared arenas
    v_pool: [num_blocks, block_size, hkv, d]
    block_table: [b, m] int32, -1 padded past the allocated blocks
    length: [b] int32  tokens already cached (positions [0, length)
            are live; later rows of a partially-filled block are junk
            and masked, exactly like the dense decode's `k_pos <= pos`)
    window: sliding-window size (row j sees keys at
            `k_pos > length + j - window`).

    INT8 ARENAS (k_scale_pool is not None): the pools hold symmetric
    per-row int8 rows and the scale pools their f32 per-row scales
    `[num_blocks, block_size, hkv, 1]`; k_cur/v_cur are then int8 with
    `k_cur_scale`/`v_cur_scale` `[b, hkv, t, 1]` (the model quantizes
    the tile at the sow — quantize-at-insertion). The dequantize is
    DEFERRED into the blockwise online-softmax scan: k-scales fold
    into the per-block [*, block_size] score tile and v-scales into
    the weights (softmax_merge's w_scale), so no float copy of any
    cache row is ever materialized — the per-step dequantize work is
    on arrays head_dim-times smaller than the rows, and the dominant
    HBM stream (the arenas) stays int8 end to end. Same math as the
    offline dense int8 decode's deferral (transformer_lm._decode_step),
    reduction order aside.

    Table entries are traced values: block churn and sequence growth
    never recompile the consuming program. k/v may carry fewer heads
    than q (GQA): q heads are grouped under their kv head like the
    dense `_decode_step`, so pool reads scale with hkv. Returns
    [b, h, t, d] in float32 (the dense decode path's softmax
    precision).

    DISPATCH (`use_kernel`): None (default) auto-selects — the fused
    Pallas kernel (_paged_decode_fused) when dispatch.use_paged_kernel()
    says kernels are on AND _paged_kernel_supported() accepts the
    shapes; the lax.scan above otherwise. True/False (static) pin a
    path — the bench legs and the parity battery compare the two
    directly. Both paths share _paged_valid/_tile_causal_mask and the
    same outside-the-stream tile merge, so they can only differ by
    floating-point reduction order."""
    quantized = k_scale_pool is not None
    if quantized and (v_scale_pool is None or k_cur_scale is None
                      or v_cur_scale is None):
        raise ValueError(
            "int8 paged attention needs all four scale operands "
            "(k_scale_pool, v_scale_pool, k_cur_scale, v_cur_scale)"
        )
    squeeze = q.ndim == 3
    if squeeze:
        q = q[:, :, None, :]
        k_cur = k_cur[:, :, None, :]
        v_cur = v_cur[:, :, None, :]
        if quantized:
            k_cur_scale = k_cur_scale[:, :, None, :]
            v_cur_scale = v_cur_scale[:, :, None, :]
    b, h, t, d = q.shape
    hkv = k_cur.shape[1]
    if h % hkv:
        raise ValueError(
            "paged decode needs num_heads %% num_kv_heads == 0, got "
            "%d q heads / %d kv heads" % (h, hkv)
        )
    group = h // hkv
    block_size = k_pool.shape[1]
    m = block_table.shape[1]
    scale = scale if scale is not None else d ** -0.5
    f32 = jnp.float32
    # group layout [b, hkv, group, t, d] flattened to a (group*t) query
    # axis: kv head j serves q heads [j*group, (j+1)*group) — the dense
    # _decode_step's reshape — and softmax_merge's [b, h, q, k]
    # contract applies as-is with hkv as the head axis
    qg = (q * scale).reshape(b, hkv, group, t, d).astype(f32)
    qf = qg.reshape(b, hkv, group * t, d)
    length = jnp.asarray(length, jnp.int32)
    row_pos = length[:, None] + jnp.arange(t)[None, :]  # [b, t]

    def step(carry, j):
        o, l, mx = carry
        bid = block_table[:, j]  # [b]; -1 = unallocated
        safe = jnp.maximum(bid, 0)  # gather clamps; validity masks below
        kb = k_pool[safe].astype(f32)  # [b, block_size, hkv, d]
        vb = v_pool[safe].astype(f32)
        s = jnp.einsum("bhqd,bkhd->bhqk", qf, kb)  # [b, hkv, g*t, bs]
        w_scale = None
        if quantized:
            # deferred dequantize: the k-row scales multiply the
            # [*, block_size] score tile (head_dim-times smaller than
            # the rows), the v-row scales ride to softmax_merge's
            # weight multiply — the arenas stream int8, nothing floats
            ks = k_scale_pool[safe][..., 0]  # [b, block_size, hkv]
            s = s * ks.transpose(0, 2, 1)[:, :, None, :]
            w_scale = v_scale_pool[safe][..., 0].transpose(0, 2, 1)
        k_pos = j * block_size + jnp.arange(block_size)[None, None, :]
        valid = jnp.broadcast_to(
            _paged_valid(
                k_pos,                   # [1, 1, block_size]
                bid[:, None, None],      # [b, 1, 1]
                length[:, None, None],   # [b, 1, 1]
                row_pos[..., None],      # [b, t, 1]
                window,
            ),
            (b, t, block_size),
        )
        # [b, t, bs] -> [b, 1, group, t, bs] -> flatten the query axis
        vt = jnp.broadcast_to(
            valid[:, None, None], (b, hkv, group, t, block_size)
        ).reshape(b, hkv, group * t, block_size)
        s = jnp.where(vt, s, _NEG_INF)
        return softmax_merge(o, l, mx, s, vb.transpose(0, 2, 1, 3),
                             w_scale=w_scale), None

    if use_kernel is None:
        use_kernel = use_paged_kernel() and _paged_kernel_supported(
            m, k_pool, quantized)
    if use_kernel:
        o, l, mx = _paged_decode_fused(
            qf, k_pool, v_pool, block_table, length, t, window=window,
            k_scale_pool=k_scale_pool, v_scale_pool=v_scale_pool,
        )
    else:
        o0 = jnp.zeros((b, hkv, group * t, d), f32)
        l0 = jnp.zeros((b, hkv, group * t), f32)
        m0 = jnp.full((b, hkv, group * t), _NEG_INF, f32)
        (o, l, mx), _ = jax.lax.scan(step, (o0, l0, m0), jnp.arange(m))
    # the tile attends to itself causally: key j' (position
    # length + j') is visible to row j iff j' <= j (the diagonal is
    # always inside any window >= 1) — merged as one t-key block
    s_cur = jnp.einsum(
        "bhqd,bhkd->bhqk", qf, k_cur.astype(f32)
    )  # [b, hkv, g*t, t]
    cur_w_scale = None
    if quantized:
        # same deferral for the tile's own keys/values: the tile is
        # quantized at the sow (it lands in the arenas as-is), so its
        # scores see exactly the rows every LATER step will read back
        s_cur = s_cur * k_cur_scale[..., 0][:, :, None, :]
        cur_w_scale = v_cur_scale[..., 0]  # [b, hkv, t]
    trif = _tile_causal_mask(group, t, window, row_pos, block_causal)
    s_cur = jnp.where(trif, s_cur, _NEG_INF)
    o, l, mx = softmax_merge(
        o, l, mx, s_cur, v_cur.astype(f32),  # already [b, hkv, t, d]
        w_scale=cur_w_scale,
    )
    out = softmax_finalize(o, l).reshape(b, hkv, group, t, d)
    out = out.reshape(b, h, t, d)
    return out[:, :, 0, :] if squeeze else out


# ------------------------------------------------- fused paged kernel


def _paged_kernel_supported(m, pool=None, quantized=False):
    """Shape gate for the fused paged decode kernel — the ONE place
    that decides kernel-or-scan by shape. m == 0 (no table slots) has
    no pool to stream. `pool` is a row arena
    [num_blocks, block_size, hkv, d]; without one only m is judged.

    _paged_kernel copies whole blocks of the arenas viewed as
    [num_blocks, block_size*hkv, d] out of HBM itself, one
    (block_size*hkv, d) slab a copy, and Mosaic moves only whole tiles
    of a tiled memref: d must be a multiple of 128 lanes and a slab a
    whole number of 32-bit sublanes (an even number of bf16 rows, a
    multiple of 4 int8 rows). The f32 scale leaves of int8 arenas ride
    as 128-lane rows of block_size*hkv scales a block, which must
    divide 128 or be a multiple of it. Every other pool decodes
    through the scan; the interpreter takes any shape. Compiled for a
    described v5e over d in {64, 128, 256}, slabs of 1 to 512 rows and
    bf16 / int8 / f32 arenas (PERF.md §6, PR 25), and on the v5e
    against the scan at the shapes of tests/test_tpu_smoke.py."""
    if m < 1:
        return False
    if pool is None or interpret_mode():
        return True
    _, bs, hkv, d = pool.shape
    kv_rows = bs * hkv
    return (
        d % 128 == 0
        and kv_rows * jnp.dtype(pool.dtype).itemsize % 4 == 0
        and (not quantized or kv_rows % 128 == 0 or 128 % kv_rows == 0)
    )


def paged_decode_impl(m, pool=None, quantized=False):
    """Name of the implementation paged_decode_attention's auto
    dispatch picks for a pool (a row arena) with `m` table slots per
    sequence — what the server logs at start."""
    if not use_paged_kernel():
        return "scan (kernels off)"
    if m < 1:
        return "scan (no table slots)"
    if not _paged_kernel_supported(m, pool, quantized):
        return "scan (the pool's blocks are not whole tiles)"
    return "pallas-interpret" if interpret_mode() else "pallas"


#: a trip of the pool stream scores at least this many arena rows
#: (block_size * hkv a block) in one matmul, where the table is that
#: wide. Decided by a sweep at the sc2-3b serving shape on the v5e
#: (scripts/bench_attention.py --paged-trip; PERF.md §6, PR 25): a
#: call is launch-bound and flat in it (14 us) while a sequence has a
#: few dozen blocks in reach; over a full table of 1024 blocks it
#: takes 119 us at 256 rows, 78 at 1024 and 73 at 2048.
_PAGED_TRIP_ROWS = 1024
#: ... and at most this many score elements (query rows x arena rows,
#: fp32), so a wide suffix-prefill tile keeps a score tile that fits
_PAGED_TRIP_SCORES = 1 << 19


def _paged_trip_blocks(q_rows, kv_rows, m):
    """Blocks a trip of the pool stream moves and scores at once, from
    shapes alone: enough to reach _PAGED_TRIP_ROWS arena rows, no more
    than the table has, fewer where the query tile is tall."""
    want = -(-_PAGED_TRIP_ROWS // kv_rows)
    fit = _PAGED_TRIP_SCORES // (q_rows * kv_rows)
    return max(1, min(want, fit, m))


def _paged_kernel(tbl_ref, len_ref, q_ref, rows_ref, cols_ref, *rest,
                  m, bs, window, quantized, kblk, wblk, sgrp):
    """Fused paged decode attention, one Mosaic program per sequence,
    ALL kv heads per program, streaming only the blocks in reach.

    Scalar-prefetch operands (the vLLM PagedAttention shape): the
    flattened [b*m] block table and the [b] lengths land in SMEM before
    the grid runs. The arenas stay in HBM (`pl.ANY`); the program reads
    its sequence's live range [j_lo, j_hi) (paged_live_blocks) and
    loops over ceil((j_hi - j_lo) / kblk) TRIPS — none for a free lane.
    A trip copies `kblk` blocks HBM->VMEM by TABLE INDIRECTION
    (`tbl[batch*m + j]` names the arena block; -1 clamps to block 0 and
    is masked), into one of two buffers, so trip c+1's copies fly under
    trip c's matmuls. Slots past j_hi in the last trip copy block
    j_hi - 1 again: its rows are finite and their columns sit at
    k_pos >= length, which _paged_valid masks — what lies outside the
    range is never read, so it cannot reach the sum.

    `wblk` of a trip's blocks are scored at once, as one
    (wblk*bs*hkv, d) tile — arena row r of head g of its block i sits
    at tile row (i*bs + r)*hkv + g — and ONE matmul scores every query
    row against every tile row (wblk == kblk, the whole trip, but for
    int8 arenas); the cross-head products are masked with the same
    select that applies _paged_valid, so the weights of a foreign head
    are exactly 0 and the p @ v matmul needs no per-head split either.
    That trades hkv-fold MXU work on a few hundred rows (decode is
    bound by the pool stream, not the MXU) for whole-block DMAs and no
    in-kernel relayout. The row/column head ids and offsets ride in as
    two small int32 operands: vector integer div/mod is not something
    to ask of the VPU.

    int8 arenas stream int8 and dequantize DEFERRED, as in the scan: a
    block's bs*hkv k scales multiply its score columns and its v scales
    the weights, each a (1, bs*hkv) row, so a block is scored on its
    own (wblk == 1). A copy moves whole 128-lane rows: where a block
    has fewer scales, `sgrp` blocks share a row of the scale view and
    the block's lanes are rolled to the front.

    Scores run in the exp2 domain like the flash kernels (log2e
    pre-folded into q's scale multiply) and accumulate in the fp32
    output tiles (o, l, m) themselves, an online-softmax triple; the
    epilogue converts m back to natural log for the shared
    current-tile merge + finalize in paged_decode_attention."""
    n_pools = 4 if quantized else 2
    pools, rest = rest[:n_pools], rest[n_pools:]
    o_ref, l_ref, m_ref = rest[:3]
    bufs, sem = rest[3:3 + n_pools], rest[3 + n_pools]
    batch = pl.program_id(0)
    seq_len = len_ref[batch]
    j_lo, j_hi = paged_live_blocks(seq_len, window, bs, m)
    trips = (j_hi - j_lo + kblk - 1) // kblk

    def block_id(j):
        """Table entry of slot j, -1 = unallocated; slots past the live
        range read its last one."""
        return tbl_ref[batch * m + jnp.minimum(j, j_hi - 1)]

    def copies(c, slot, wait):
        """Start, or wait for, trip c's block copies into buffer
        `slot`: one DMA semaphore a buffer, waited once per copy. A
        rolled loop: a wide trip costs no time to trace (unrolled, 32
        blocks a trip took 4 s a layer)."""
        def one(i, carry):
            bid = jnp.maximum(block_id(j_lo + c * kblk + i), 0)
            # (k, v[, k scales, v scales]): `sgrp` blocks a scale row
            for pool, buf, grp in zip(pools, bufs, (1, 1, sgrp, sgrp)):
                copy = pltpu.make_async_copy(
                    pool.at[bid // grp], buf.at[slot, i], sem.at[slot])
                if wait:
                    copy.wait()
                else:
                    copy.start()
            return carry

        jax.lax.fori_loop(0, kblk, one, 0)

    def scales(buf, slot, u, j0):
        """Block j0's (1, bs*hkv) row of scales out of the row of
        `sgrp` blocks' scales that was copied for it."""
        row = buf[slot, u]
        if sgrp == 1:
            return row
        kv_rows = row.shape[1] // sgrp
        lane = (jnp.maximum(block_id(j0), 0) % sgrp) * kv_rows
        return pltpu.roll(row, (row.shape[1] - lane) % row.shape[1],
                          1)[:, :kv_rows]

    @pl.when(trips > 0)
    def _():
        copies(0, 0, wait=False)

    o_ref[0] = jnp.zeros_like(o_ref[0])
    l_ref[0] = jnp.zeros_like(l_ref[0])
    m_ref[0] = jnp.full_like(m_ref[0], _NEG_INF)
    q = q_ref[0]  # (hkv*n_rows, d), exp2-domain prescaled f32
    cols = cols_ref.shape[1]  # wblk*bs*hkv

    def merge(slot, u, j0):
        """Score blocks [u*wblk, (u+1)*wblk) of buffer `slot` — table
        slots j0 .. j0 + wblk - 1 — in one matmul and fold them into
        the (o, l, m) triple."""
        kb = bufs[0][slot, pl.ds(u * wblk, wblk)].reshape(cols, -1)
        vb = bufs[1][slot, pl.ds(u * wblk, wblk)].reshape(cols, -1)
        s = jax.lax.dot_general(
            q, kb.astype(jnp.float32), dimension_numbers=_dims(1, 1),
            preferred_element_type=jnp.float32,
        )  # (hkv*n_rows, wblk*bs*hkv), log2 units
        if quantized:
            s = s * scales(bufs[2], slot, u, j0)  # k scales, a row

        # the blocks' ids, one table read a block, laid along the
        # columns on a single row before anything is broadcast
        col_blk = cols_ref[2:3, :]
        bid = jax.lax.fori_loop(
            0, wblk,
            lambda i, bid: jnp.where(col_blk == i, block_id(j0 + i), bid),
            jnp.full(col_blk.shape, -1, jnp.int32))
        # every mask operand is broadcast to the full score tile as
        # int32 BEFORE it is compared: Mosaic broadcasts integers along
        # either axis, a (1, n) or scalar i1 it may not
        zeros = jnp.zeros(s.shape, jnp.int32)
        row_head = zeros + rows_ref[:, 0:1]  # (hkv*n_rows, 1) columns
        row_tok = zeros + rows_ref[:, 1:2]
        col_head = zeros + cols_ref[0:1, :]  # (1, wblk*bs*hkv) rows
        col_off = zeros + cols_ref[1:2, :]
        valid = _paged_valid(
            j0 * bs + col_off, zeros + bid, seq_len, seq_len + row_tok,
            window,
        ) & (row_head == col_head)
        s = jnp.where(valid, s, _NEG_INF)

        m_prev = m_ref[0]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)
        l_ref[0] = l_ref[0] * corr + p.sum(-1, keepdims=True)
        if quantized:
            p = p * scales(bufs[3], slot, u, j0)  # v's: the weights
        o_ref[0] = o_ref[0] * corr + jax.lax.dot_general(
            p, vb.astype(jnp.float32), dimension_numbers=_dims(1, 0),
            preferred_element_type=jnp.float32,
        )
        m_ref[0] = m_new

    def trip(c, carry):
        slot = c % 2

        @pl.when(c + 1 < trips)
        def _():
            copies(c + 1, 1 - slot, wait=False)

        copies(c, slot, wait=True)

        def part(u, carry):
            merge(slot, u, j_lo + c * kblk + u * wblk)
            return carry

        return jax.lax.fori_loop(0, kblk // wblk, part, carry)

    jax.lax.fori_loop(0, trips, trip, 0)
    # natural-log units at the boundary, like the flash epilogue:
    # nothing outside the kernel ever sees base-2 values
    m_ref[0] = m_ref[0] * _LN2


def _paged_decode_fused(qf, k_pool, v_pool, block_table, length, t,
                        window=None, k_scale_pool=None,
                        v_scale_pool=None, rows=None):
    """pallas_call wrapper for _paged_kernel: returns the pool-stream
    online-softmax partials (o [b,hkv,g*t,d], l, m [b,hkv,g*t]) in
    fp32 natural-log units — drop-in for the lax.scan's carry, so
    paged_decode_attention's tile merge + finalize is shared verbatim.

    qf is the scan's query layout: [b, hkv, group*t, d], already scale-
    multiplied, f32. The row axis pads up to resolve_paged_rows() (the
    tuned sublane tile) and the heads fold into it. The pools are never
    tiled by a BlockSpec: [num_blocks, bs, hkv, last] is VIEWED as
    [num_blocks, bs*hkv, last] (a reshape of contiguous dims, no copy)
    and handed to the kernel whole, in HBM; the kernel copies the
    blocks its sequence has in reach, _paged_trip_blocks of them a
    trip, into two VMEM buffers a pool — int8 arenas stay int8 through
    the DMA; their scale leaves are regrouped (one XLA copy a call, not
    a slot: the pools are not batched) into rows of 128 lanes."""
    b, hkv, gt, d = qf.shape
    num_blocks, bs = k_pool.shape[:2]
    m = block_table.shape[1]
    quantized = k_scale_pool is not None
    rows = resolve_paged_rows(rows)
    n_rows = max(rows, ((gt + rows - 1) // rows) * rows)
    q2 = qf.astype(jnp.float32) * _LOG2E  # exp2 domain
    if n_rows != gt:
        q2 = jnp.pad(
            q2, ((0, 0), (0, 0), (0, n_rows - gt), (0, 0))
        )
    q_rows, kv_rows = hkv * n_rows, bs * hkv
    kblk = _paged_trip_blocks(q_rows, kv_rows, m)
    q2 = q2.reshape(b, q_rows, d)
    tbl = jnp.asarray(block_table, jnp.int32).reshape(b * m)
    ln = jnp.asarray(length, jnp.int32)
    # query row R is head R // n_rows; row r of a head's padded tile is
    # tile token r % t (group-major [group, t] flatten; pad rows alias
    # real positions and are sliced off below). Trip column c is arena
    # row (c // hkv) % bs of head c % hkv in the trip's block
    # c // kv_rows, at position offset c // hkv from the trip's start.
    r_idx = np.arange(q_rows)
    wblk = 1 if quantized else kblk
    c_idx = np.arange(wblk * kv_rows)
    row_meta = np.stack(
        [r_idx // n_rows, (r_idx % n_rows) % t], axis=1
    ).astype(np.int32)  # [q_rows, 2]
    col_meta = np.stack(
        [c_idx % hkv, c_idx // hkv, c_idx // kv_rows], axis=0
    ).astype(np.int32)  # [3, wblk*kv_rows]

    def _seq_spec(last):
        """Per-sequence tile."""
        return pl.BlockSpec(
            (1, q_rows, last), lambda i, tbl_ref, len_ref: (i, 0, 0),
            memory_space=pltpu.VMEM,
        )

    def _const_spec(shape):
        return pl.BlockSpec(
            shape, lambda i, tbl_ref, len_ref: (0, 0),
            memory_space=pltpu.VMEM,
        )

    pools = [p.reshape(num_blocks, kv_rows, d) for p in (k_pool, v_pool)]
    sgrp = max(1, 128 // kv_rows)
    if quantized:
        # a copy moves whole 128-lane rows: `sgrp` blocks' scales share
        # one where a block has fewer than 128 arena rows
        pad = -num_blocks % sgrp
        pools += [
            jnp.pad(p.reshape(num_blocks, kv_rows), ((0, pad), (0, 0)))
            .reshape(-1, 1, sgrp * kv_rows)
            for p in (k_scale_pool, v_scale_pool)
        ]
    kernel = functools.partial(
        _paged_kernel, m=m, bs=bs, window=window, quantized=quantized,
        kblk=kblk, wblk=wblk, sgrp=sgrp,
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b,),
            in_specs=[_seq_spec(d), _const_spec(row_meta.shape),
                      _const_spec(col_meta.shape)]
            + [pl.BlockSpec(memory_space=pl.ANY)] * len(pools),
            out_specs=(_seq_spec(d), _seq_spec(1), _seq_spec(1)),
            scratch_shapes=[
                pltpu.VMEM((2, kblk) + p.shape[1:], p.dtype)
                for p in pools
            ] + [pltpu.SemaphoreType.DMA((2,))],
        ),
        out_shape=(
            jax.ShapeDtypeStruct((b, q_rows, d), jnp.float32),
            jax.ShapeDtypeStruct((b, q_rows, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, q_rows, 1), jnp.float32),
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)
        ),
        interpret=interpret_mode(),
    )
    # the scope names the kernel's HLO instruction and so its event in
    # a device trace (`paged_decode.N`); pallas_call's own `name=` would
    # do the same but also replace the custom call's `kernel_name`
    with jax.named_scope("paged_decode"):
        o, l, mx = call(tbl, ln, q2, row_meta, col_meta, *pools)
    o = o.reshape(b, hkv, n_rows, d)[:, :, :gt]
    l = l.reshape(b, hkv, n_rows)[:, :, :gt]
    mx = mx.reshape(b, hkv, n_rows)[:, :, :gt]
    return o, l, mx


def _check_window(window, lq, lk):
    """Sliding-window attention is defined for square self-attention
    only: with lq != lk a window can leave query rows with NO visible
    key, whose softmax is undefined (the jnp paths would emit mean(v),
    the kernel 0). Square shapes + window >= 1 guarantee the diagonal
    is always visible, so every row has at least one key."""
    if window is None:
        return
    if window < 1:
        raise ValueError("window must be >= 1, got %r" % (window,))
    if lq != lk:
        raise ValueError(
            "sliding-window attention requires square self-attention "
            "(lq == lk), got lq=%d lk=%d" % (lq, lk)
        )


def packed_positions(segments):
    """Per-token positions that RESTART at each segment boundary.

    segments: [..., l] int ids forming contiguous same-id runs (the
    sequence-packing layout). Returns int32 of the same shape: the
    token's offset within its own segment — what RoPE / learned
    position tables should see for packed rows."""
    segments = jnp.asarray(segments)
    l = segments.shape[-1]
    idx = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32),
                           segments.shape)
    is_start = jnp.concatenate(
        [
            jnp.ones_like(segments[..., :1], bool),
            segments[..., 1:] != segments[..., :-1],
        ],
        axis=-1,
    )
    starts = jax.lax.cummax(
        jnp.where(is_start, idx, 0), axis=segments.ndim - 1
    )
    return idx - starts


def apply_rope(x, positions, theta=10000.0):
    """Rotary position embedding (RoPE) over the head dimension.

    x: [b, h, l, d]; positions: [l] (shared across the batch) or
    [b, l] (per-row, the packed-sequence case) int/float absolute
    positions. Rotates feature pairs (i, i+d/2) by
    positions * theta^(-2i/d), so q·k after rotation depends only on
    RELATIVE distance — the property that lets ring/Ulysses sequence
    shards use their global positions with no learned table. Math in
    fp32, result in x.dtype. An odd tail feature (d % 2) passes
    through unrotated.
    """
    d = x.shape[-1]
    half = d // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    positions = jnp.asarray(positions)
    angles = positions.astype(jnp.float32)[..., :, None] * freqs
    if positions.ndim == 1:
        cos = jnp.cos(angles)[None, None]  # [1, 1, l, half]
        sin = jnp.sin(angles)[None, None]
    else:  # [b, l] -> [b, 1, l, half]
        cos = jnp.cos(angles)[:, None]
        sin = jnp.sin(angles)[:, None]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:2 * half]
    rot = jnp.concatenate(
        [x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1
    )
    if d % 2:
        rot = jnp.concatenate([rot, xf[..., 2 * half:]], axis=-1)
    return rot.astype(x.dtype)


# --------------------------------------------------------- flash kernel


def _dims(contract_a, contract_b):
    return (((contract_a,), (contract_b,)), ((), ()))


def _block_run(qi, ki, block_q, block_k, causal, window, pos_offset=0):
    """Whether query block qi overlaps key block ki under the causal
    and/or sliding-window mask — the block-skip invariant shared by the
    forward and both backward kernels. Causal: some q position >= the
    block's first k position. Window: some k position inside the newest
    window of some q position (last k pos > first q pos - window).
    `pos_offset` (static) shifts the q positions — ring attention's
    off-diagonal rotations run the window band at offset r*shard_len."""
    run = True
    q0 = qi * block_q + pos_offset
    if causal:
        run = q0 + block_q - 1 >= ki * block_k
    if window is not None:
        # newest k in block inside some q's lookback window
        back = ki * block_k + block_k - 1 > q0 - window
        run = jnp.logical_and(run, back) if causal else back
        if not causal:
            # oldest k in block inside some q's lookahead window
            fwd = q0 + block_q - 1 > ki * block_k - window
            run = jnp.logical_and(run, fwd)
    return run


def _block_mask(s, qi, ki, block_q, block_k, causal, window,
                pos_offset=0):
    if not causal and window is None:
        return s
    if use_cond_mask():
        # Interior blocks — fully inside the causal/window region — need
        # no per-element mask: branch it out so only edge blocks pay the
        # iota/compare/select VPU work (~half the running blocks are
        # interior for plain causal). Opt-in (EDL_FLASH_COND_MASK=1)
        # until the hardware A/B proves the branch beats the
        # straight-line select under Mosaic's pipeliner.
        interior = _block_interior(qi, ki, block_q, block_k, causal,
                                   window, pos_offset)
        return jax.lax.cond(
            interior,
            lambda ss: ss,
            lambda ss: _block_mask_apply(
                ss, qi, ki, block_q, block_k, causal, window,
                pos_offset,
            ),
            s,
        )
    return _block_mask_apply(s, qi, ki, block_q, block_k, causal,
                             window, pos_offset)


def _block_interior(qi, ki, block_q, block_k, causal, window,
                    pos_offset):
    """Dynamic predicate: every (q, k) pair in the block is visible, so
    the per-element mask is the identity. Causal: the newest key is at
    or before the oldest query. Window: the extreme pair distances stay
    inside the band."""
    q0 = qi * block_q + pos_offset
    inside = True
    if causal:
        inside = ki * block_k + block_k - 1 <= q0
    if window is not None:
        back = (q0 + block_q - 1) - ki * block_k < window
        inside = jnp.logical_and(inside, back)
        if not causal:
            fwd = (ki * block_k + block_k - 1) - q0 < window
            inside = jnp.logical_and(inside, fwd)
    return inside


def _block_mask_apply(s, qi, ki, block_q, block_k, causal, window,
                      pos_offset):
    q_pos = qi * block_q + pos_offset + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0
    )
    k_pos = ki * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1
    )
    keep = True
    if causal:
        # `causal` may be a block length B > 1 (flash_attention's
        # block_causal): the row's limit is then its block's end. The
        # block-skip predicates above hold as they are, because the
        # kernel blocks are whole multiples of B
        keep = causal_limit(q_pos, causal) >= k_pos
    if window is not None:
        in_w = q_pos - k_pos < window
        keep = jnp.logical_and(keep, in_w) if causal else in_w
        if not causal:
            keep = jnp.logical_and(keep, k_pos - q_pos < window)
    return jnp.where(keep, s, _NEG_INF)


def _mxu_cast(p, operand_dtype):
    """Cast an f32 probability/gradient matrix to the other matmul
    operand's dtype when that operand is bf16: an f32 LHS forces the
    MXU onto its (severalx slower) fp32 path, while bf16 x bf16 with an
    f32 preferred_element_type runs at full rate with f32 accumulation.
    p's values are softmax weights in [0, 1] (or ds of the same scale),
    so the bf16 rounding is well inside the bf16 output tolerance of
    the training paths that hit this; f32 inputs (tests, oracle
    comparisons) are left untouched."""
    if operand_dtype == jnp.bfloat16:
        return p.astype(jnp.bfloat16)
    return p


def _flash_kernel(q_ref, k_ref, v_ref, *rest, scale, causal, window,
                  block_q, block_k, n_k, has_segs=False,
                  pos_offset=0):
    if has_segs:
        qseg_ref, kseg_ref = rest[:2]
        rest = rest[2:]
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        m_scr[:] = jnp.full_like(m_scr, _NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # skip key blocks fully outside the causal/window mask
    run = _block_run(qi, ki, block_q, block_k, causal, window,
                     pos_offset)

    @pl.when(run)
    def _():
        # exp2 domain: log2e rides the existing scale multiply
        q = q_ref[0] * (scale * _LOG2E)
        s = jax.lax.dot_general(
            q, k_ref[0], dimension_numbers=_dims(1, 1),
            preferred_element_type=jnp.float32,
        )  # (block_q, block_k), in log2 units
        s = _block_mask(s, qi, ki, block_q, block_k, causal, window,
                        pos_offset)
        if has_segs:
            # sequence packing: mask cross-segment pairs.
            # qseg (block_q, 1) == kseg (1, block_k) broadcasts to s
            s = jnp.where(qseg_ref[0] == kseg_ref[0], s, _NEG_INF)
        m_prev = m_scr[:]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp2(s - m_new)
        corr = jnp.exp2(m_prev - m_new)
        l_scr[:] = l_scr[:] * corr + p.sum(-1, keepdims=True)
        acc_scr[:] = acc_scr[:] * corr + jax.lax.dot_general(
            _mxu_cast(p, v_ref.dtype), v_ref[0],
            dimension_numbers=_dims(1, 0),
            preferred_element_type=jnp.float32,
        )
        m_scr[:] = m_new

    @pl.when(ki == n_k - 1)
    def _():
        l = l_scr[:]
        o_ref[0] = (
            acc_scr[:] / jnp.maximum(l, 1e-30)
        ).astype(o_ref.dtype)
        # logsumexp residual for the backward kernels: exp(s - lse) == P.
        # m is in log2 units, so convert back to natural log here — no
        # consumer ever sees base-2 values. Defense in depth: a
        # fully-skipped row (l == 0; unreachable for the square shapes
        # _check_window enforces) gets a +inf-class sentinel so the
        # backward's exp(-1e30 - lse) underflows to 0 instead of
        # exploding.
        lse_ref[0] = jnp.where(
            l > 0.0,
            (m_scr[:] + jnp.log2(jnp.maximum(l, 1e-30))) * _LN2,
            -_NEG_INF,
        )


def _outer_spec(block, d):
    """Block indexed by grid dim 1 (the parallel/output dimension)."""
    return pl.BlockSpec(
        (1, block, d), lambda i, j, t: (i, j, 0),
        memory_space=pltpu.VMEM,
    )


# --- streamed-block DMA clamping -------------------------------------
# Mosaic's pipeline elides the HBM->VMEM copy when a block's index map
# returns the same indices as the previous grid step. The compute for
# blocks fully outside the causal/window mask is already skipped by
# pl.when(_block_run), but their input DMAs would still run — for
# causal attention that is ~half of all kv traffic fetched and thrown
# away. These clamps pin the streamed index to the nearest in-mask
# block, so out-of-mask steps revisit an already-resident block and the
# pipeline skips the copy. The bounds are the same inequalities as
# _block_run solved for the streamed index, so every step with
# run=True reads its true block; out-of-mask steps read a (resident,
# unused) one. Segments never relax the causal/window mask, so the
# clamps stay valid with packing.


def _kv_stream_clamp(causal, window, block_q, block_k, n_k, pos_offset):
    """Clamp for the forward/dq kernels' streamed k/v index t, given
    q-block index j."""
    if not causal and window is None:
        return None

    def clamp(j, t):
        q0 = j * block_q + pos_offset
        lo = 0
        hi = n_k - 1
        if causal:
            # run: q0 + block_q - 1 >= ki * block_k
            hi = jnp.minimum(hi, (q0 + block_q - 1) // block_k)
        if window is not None:
            # back: ki*block_k + block_k - 1 > q0 - window
            lo = jnp.maximum(
                lo, (q0 - window - block_k + 1) // block_k + 1
            )
            if not causal:
                # fwd: q0 + block_q - 1 > ki*block_k - window
                hi = jnp.minimum(
                    hi, (q0 + block_q + window - 2) // block_k
                )
        return jnp.maximum(jnp.minimum(t, hi), jnp.minimum(lo, n_k - 1))

    return clamp


def _q_stream_clamp(causal, window, block_q, block_k, n_q, pos_offset):
    """Clamp for the dk/dv kernel's streamed q-block index qb, given
    key-block index j — the _block_run inequalities solved for qb."""
    if not causal and window is None:
        return None

    def clamp(j, qb):
        lo = 0
        hi = n_q - 1
        if causal:
            # run: qb*block_q + pos_offset + block_q - 1 >= j*block_k
            lo = jnp.maximum(lo, (j * block_k - pos_offset) // block_q)
        if window is not None:
            # back: j*block_k + block_k - 1 > q0 - window
            hi = jnp.minimum(
                hi,
                (j * block_k + block_k + window - 2 - pos_offset)
                // block_q,
            )
            if not causal:
                # fwd: q0 + block_q - 1 > j*block_k - window
                lo = jnp.maximum(
                    lo,
                    (j * block_k - window - pos_offset - block_q + 1)
                    // block_q + 1,
                )
        return jnp.maximum(jnp.minimum(qb, hi), jnp.minimum(lo, n_q - 1))

    return clamp


def _inner_spec(block, d, clamp=None):
    """Block indexed by grid dim 2 (the sequential/streamed dimension)."""
    cl = clamp or (lambda j, t: t)
    return pl.BlockSpec(
        (1, block, d), lambda i, j, t: (i, cl(j, t), 0),
        memory_space=pltpu.VMEM,
    )


def _kv_inner_spec(block, d, h, hkv, clamp=None):
    """Streamed kv spec for the forward/dq kernels when k/v carry fewer
    heads than q (GQA): grid dim 0 indexes b*h q-rows; kv row = batch
    offset + q_head // group. Degenerates to _inner_spec at h == hkv."""
    if h == hkv:
        return _inner_spec(block, d, clamp)
    group = h // hkv
    cl = clamp or (lambda j, t: t)
    return pl.BlockSpec(
        (1, block, d),
        lambda i, j, t: ((i // h) * hkv + (i % h) // group, cl(j, t), 0),
        memory_space=pltpu.VMEM,
    )


def _dkv_q_spec(block, d, h, hkv, n_q, clamp=None):
    """Streamed q-side spec for the dk/dv kernel under GQA: grid dim 0
    indexes b*hkv kv-rows and grid dim 2 enumerates (group, q_block)
    pairs flattened as t = g * n_q + q_block, so each kv block
    accumulates over every q head in its group."""
    if h == hkv:
        # group == 1: row = i, t // n_q = 0, t % n_q = t
        return _inner_spec(block, d, clamp)
    group = h // hkv
    cl = clamp or (lambda j, qb: qb)
    return pl.BlockSpec(
        (1, block, d),
        lambda i, j, t: (
            (i // hkv) * h + (i % hkv) * group + t // n_q,
            cl(j, t % n_q), 0,
        ),
        memory_space=pltpu.VMEM,
    )



def _mosaic_params():
    """Grid semantics for all three flash kernels: (bh, output-block,
    streamed-block) = two parallel dims + one arbitrary (sequential
    accumulation over scratch). Lets Mosaic pipeline the parallel dims."""
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary")
    )


def _seg_specs(block_q, block_k, heads, dkv=False, n_q=1, clamp=None):
    """BlockSpec pair for the segment-id inputs: q-side ids ride as
    [b, lq, 1] column tiles, k-side as [b, 1, lk] row tiles so the
    in-kernel equality broadcasts to (block_q, block_k) without any
    reshape. `heads` is the grid-dim-0 head count (h, or hkv for the
    dk/dv kernel whose streamed dim enumerates (group, q_block)).
    `clamp` applies to the STREAMED side (k ids for the forward/dq
    kernels, q ids for dk/dv), matching the k/v (resp. q) tile the
    kernel actually reads at each step."""
    cl = clamp or (lambda j, t: t)
    if not dkv:
        return (
            pl.BlockSpec((1, block_q, 1),
                         lambda i, j, t: (i // heads, j, 0),
                         memory_space=pltpu.VMEM),
            pl.BlockSpec((1, 1, block_k),
                         lambda i, j, t: (i // heads, 0, cl(j, t)),
                         memory_space=pltpu.VMEM),
        )
    return (
        pl.BlockSpec((1, block_q, 1),
                     lambda i, j, t: (i // heads, cl(j, t % n_q), 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec((1, 1, block_k),
                     lambda i, j, t: (i // heads, 0, j),
                     memory_space=pltpu.VMEM),
    )


def _flash_forward(q, k, v, causal, scale, block_q, block_k, interpret,
                   window=None, with_residuals=False, segments=None,
                   pos_offset=0):
    b, h, lq, d = q.shape
    hkv = k.shape[1]
    lk = k.shape[2]
    bh = b * h
    q3 = q.reshape(bh, lq, d)
    k3 = k.reshape(b * hkv, lk, d)
    v3 = v.reshape(b * hkv, lk, d)
    n_q = lq // block_q
    n_k = lk // block_k
    kernel = functools.partial(
        _flash_kernel,
        scale=scale,
        causal=causal,
        window=window,
        block_q=block_q,
        block_k=block_k,
        n_k=n_k,
        has_segs=segments is not None,
        pos_offset=pos_offset,
    )
    kv_clamp = _kv_stream_clamp(causal, window, block_q, block_k, n_k,
                                pos_offset)
    in_specs = [
        _outer_spec(block_q, d),
        _kv_inner_spec(block_k, d, h, hkv, kv_clamp),
        _kv_inner_spec(block_k, d, h, hkv, kv_clamp),
    ]
    inputs = [q3, k3, v3]
    if segments is not None:
        q_seg, k_seg = segments
        in_specs += list(_seg_specs(block_q, block_k, h,
                                    clamp=kv_clamp))
        inputs += [
            q_seg.reshape(b, lq, 1),
            k_seg.reshape(b, 1, lk),
        ]
    call = pl.pallas_call(
        kernel,
        grid=(bh, n_q, n_k),
        in_specs=in_specs,
        out_specs=(
            _outer_spec(block_q, d),
            # lse rides as [bh, lq, 1] so stores stay (block_q, 1)
            # sublane columns — no 1-D reshape/transpose in the kernel
            _outer_spec(block_q, 1),
        ),
        out_shape=(
            jax.ShapeDtypeStruct((bh, lq, d), q.dtype),
            jax.ShapeDtypeStruct((bh, lq, 1), jnp.float32),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=_mosaic_params(),
        interpret=interpret_mode() if interpret is None else interpret,
    )
    with jax.named_scope("flash_fwd"):  # its event in a device trace
        out, lse = call(*inputs)
    out = out.reshape(b, h, lq, d)
    if with_residuals:
        return out, lse.reshape(b, h, lq, 1)
    return out


def _flash_bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                         *rest, scale, causal, window,
                         block_q, block_k, n_k, has_segs=False,
                         pos_offset=0):
    if has_segs:
        qseg_ref, kseg_ref = rest[:2]
        rest = rest[2:]
    dq_ref, dq_scr = rest
    qi = pl.program_id(1)
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = _block_run(qi, ki, block_q, block_k, causal, window,
                     pos_offset)

    @pl.when(run)
    def _():
        # exp2 domain (see _flash_kernel): fold log2e into the scale
        # and convert the saved natural-log lse on load
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], dimension_numbers=_dims(1, 1),
            preferred_element_type=jnp.float32,
        ) * (scale * _LOG2E)
        s = _block_mask(s, qi, ki, block_q, block_k, causal, window,
                        pos_offset)
        if has_segs:
            s = jnp.where(qseg_ref[0] == kseg_ref[0], s, _NEG_INF)
        p = jnp.exp2(s - lse_ref[0] * _LOG2E)  # (block_q, block_k)
        if has_segs:
            # a row fully masked by segments (possible only in the
            # rectangular pair form) carries an lse of the -1e30 class,
            # so exp(s - lse) = exp(0) = 1 there; its true softmax
            # contribution is zero — force it so
            p = jnp.where(lse_ref[0] < 0.5 * _NEG_INF, 0.0, p)
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], dimension_numbers=_dims(1, 1),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0]) * scale
        dq_scr[:] = dq_scr[:] + jax.lax.dot_general(
            _mxu_cast(ds, k_ref.dtype), k_ref[0],
            dimension_numbers=_dims(1, 0),
            preferred_element_type=jnp.float32,
        )

    @pl.when(ki == n_k - 1)
    def _():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _flash_bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref,
                          delta_ref, *rest, scale, causal, window,
                          block_q, block_k, n_q, n_q_total,
                          has_segs=False, pos_offset=0):
    if has_segs:
        qseg_ref, kseg_ref = rest[:2]
        rest = rest[2:]
    dk_ref, dv_ref, dk_scr, dv_scr = rest
    ki = pl.program_id(1)  # key block is the outer (parallel) dim here
    qi = pl.program_id(2)
    # under GQA the streamed dim enumerates (q_head_in_group, q_block)
    # pairs: the positional q block index for masking is qi % n_q
    # (identity when n_q_total == n_q, i.e. standard MHA)
    qb = qi % n_q

    @pl.when(qi == 0)
    def _():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = _block_run(qb, ki, block_q, block_k, causal, window,
                     pos_offset)

    @pl.when(run)
    def _():
        # exp2 domain (see _flash_kernel)
        s = jax.lax.dot_general(
            q_ref[0], k_ref[0], dimension_numbers=_dims(1, 1),
            preferred_element_type=jnp.float32,
        ) * (scale * _LOG2E)
        s = _block_mask(s, qb, ki, block_q, block_k, causal, window,
                        pos_offset)
        if has_segs:
            s = jnp.where(qseg_ref[0] == kseg_ref[0], s, _NEG_INF)
        p = jnp.exp2(s - lse_ref[0] * _LOG2E)  # (block_q, block_k)
        if has_segs:
            # see _flash_bwd_dq_kernel: fully-segment-masked rows
            # (rectangular pair form) must contribute zero to dk/dv
            p = jnp.where(lse_ref[0] < 0.5 * _NEG_INF, 0.0, p)
        # dV_j += P^T dO ; dP = dO V^T ; dS = P*(dP - D) ; dK_j += dS^T Q
        dv_scr[:] = dv_scr[:] + jax.lax.dot_general(
            _mxu_cast(p, do_ref.dtype), do_ref[0],
            dimension_numbers=_dims(0, 0),
            preferred_element_type=jnp.float32,
        )
        dp = jax.lax.dot_general(
            do_ref[0], v_ref[0], dimension_numbers=_dims(1, 1),
            preferred_element_type=jnp.float32,
        )
        ds = p * (dp - delta_ref[0]) * scale
        dk_scr[:] = dk_scr[:] + jax.lax.dot_general(
            _mxu_cast(ds, q_ref.dtype), q_ref[0],
            dimension_numbers=_dims(0, 0),
            preferred_element_type=jnp.float32,
        )

    @pl.when(qi == n_q_total - 1)
    def _():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _flash_backward(q, k, v, out, lse, g, causal, scale, block_q,
                    block_k, interpret, window=None, grad_dtype=None,
                    segments=None, pos_offset=0):
    """Two-pass flash backward: a dq kernel parallel over query blocks
    and a dk/dv kernel parallel over key blocks, both recomputing P from
    the saved logsumexp (the standard flash-attention backward; one
    matmul recompute instead of the O(L) blockwise-vjp scan).
    `grad_dtype` overrides the output dtype (ring attention asks for
    float32 partials so its cross-shard accumulation stays exact); the
    in-kernel accumulation is float32 either way.

    GQA (hkv < h): the dq pass reads kv blocks through the head-group
    index map; the dk/dv pass runs one kv-row per kv head and streams
    (group, q_block) pairs, so dk/dv come out group-summed in the native
    [b, hkv, lk, d] shape with no extra HBM round-trip."""
    b, h, lq, d = q.shape
    hkv = k.shape[1]
    group = h // hkv
    lk = k.shape[2]
    bh = b * h
    interp = interpret_mode() if interpret is None else interpret
    dq_dtype = grad_dtype or q.dtype
    dk_dtype = grad_dtype or k.dtype
    dv_dtype = grad_dtype or v.dtype
    n_q = lq // block_q
    n_k = lk // block_k
    # D_i = rowsum(dO * O), the softmax-jacobian diagonal term
    delta = jnp.sum(
        g.astype(jnp.float32) * out.astype(jnp.float32), axis=-1,
        keepdims=True,
    )
    q3 = q.reshape(bh, lq, d)
    k3 = k.reshape(b * hkv, lk, d)
    v3 = v.reshape(b * hkv, lk, d)
    do3 = g.reshape(bh, lq, d)
    lse3 = lse.reshape(bh, lq, 1)
    delta3 = delta.reshape(bh, lq, 1)

    seg_inputs = []
    if segments is not None:
        q_seg, k_seg = segments
        seg_inputs = [
            q_seg.reshape(b, lq, 1),
            k_seg.reshape(b, 1, lk),
        ]

    kv_clamp = _kv_stream_clamp(causal, window, block_q, block_k, n_k,
                                pos_offset)
    col_q = _outer_spec(block_q, 1)
    dq_in_specs = [
        _outer_spec(block_q, d),
        _kv_inner_spec(block_k, d, h, hkv, kv_clamp),
        _kv_inner_spec(block_k, d, h, hkv, kv_clamp),
        _outer_spec(block_q, d),
        col_q, col_q,
    ]
    if segments is not None:
        dq_in_specs += list(_seg_specs(block_q, block_k, h,
                                       clamp=kv_clamp))
    dq_call = pl.pallas_call(
        functools.partial(
            _flash_bwd_dq_kernel, scale=scale, causal=causal,
            window=window, block_q=block_q, block_k=block_k, n_k=n_k,
            has_segs=segments is not None, pos_offset=pos_offset,
        ),
        grid=(bh, n_q, n_k),
        in_specs=dq_in_specs,
        out_specs=_outer_spec(block_q, d),
        out_shape=jax.ShapeDtypeStruct((bh, lq, d), dq_dtype),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32)],
        compiler_params=_mosaic_params(),
        interpret=interp,
    )
    with jax.named_scope("flash_bwd_dq"):
        dq = dq_call(q3, k3, v3, do3, lse3, delta3, *seg_inputs)

    # key-block-parallel pass: q-side inputs stream over the inner dim
    # (all (group, q_block) pairs under GQA)
    q_clamp = _q_stream_clamp(causal, window, block_q, block_k, n_q,
                              pos_offset)
    q_spec = _dkv_q_spec(block_q, d, h, hkv, n_q, q_clamp)
    col_q_t = _dkv_q_spec(block_q, 1, h, hkv, n_q, q_clamp)
    dkv_in_specs = [
        q_spec, _outer_spec(block_k, d),
        _outer_spec(block_k, d), q_spec,
        col_q_t, col_q_t,
    ]
    if segments is not None:
        dkv_in_specs += list(
            _seg_specs(block_q, block_k, hkv, dkv=True, n_q=n_q,
                       clamp=q_clamp)
        )
    dkv_call = pl.pallas_call(
        functools.partial(
            _flash_bwd_dkv_kernel, scale=scale, causal=causal,
            window=window, block_q=block_q, block_k=block_k, n_q=n_q,
            n_q_total=group * n_q,
            has_segs=segments is not None, pos_offset=pos_offset,
        ),
        grid=(b * hkv, n_k, group * n_q),
        in_specs=dkv_in_specs,
        out_specs=(_outer_spec(block_k, d), _outer_spec(block_k, d)),
        out_shape=(
            jax.ShapeDtypeStruct((b * hkv, lk, d), dk_dtype),
            jax.ShapeDtypeStruct((b * hkv, lk, d), dv_dtype),
        ),
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
        ],
        compiler_params=_mosaic_params(),
        interpret=interp,
    )
    with jax.named_scope("flash_bwd_dkv"):
        dk, dv = dkv_call(q3, k3, v3, do3, lse3, delta3, *seg_inputs)
    return (
        dq.reshape(b, h, lq, d),
        dk.reshape(b, hkv, lk, d),
        dv.reshape(b, hkv, lk, d),
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6, 7, 8, 9))
def _flash(q, k, v, segments, causal, scale, block_q, block_k,
           interpret, window):
    return _flash_forward(q, k, v, causal, scale, block_q, block_k,
                          interpret, window=window, segments=segments)


def _flash_fwd(q, k, v, segments, causal, scale, block_q, block_k,
               interpret, window):
    out, lse = _flash_forward(q, k, v, causal, scale, block_q, block_k,
                              interpret, window=window,
                              with_residuals=True, segments=segments)
    return out, (q, k, v, segments, out, lse)


def _flash_bwd(causal, scale, block_q, block_k, interpret, window, res,
               g):
    q, k, v, segments, out, lse = res
    dq, dk, dv = _flash_backward(q, k, v, out, lse, g, causal, scale,
                                 block_q, block_k, interpret,
                                 window=window, segments=segments)
    return dq, dk, dv, segments_float0(segments)


_flash.defvjp(_flash_fwd, _flash_bwd)


def flash_attention(q, k, v, causal=False, scale=None, block_q=None,
                    block_k=None, interpret=None, window=None,
                    segments=None, block_causal=0):
    """Tiled online-softmax attention (Pallas). head_dim is zero-padded
    to the 128-lane width (zeros don't change q·k or add output columns
    that survive the final slice); falls back to blockwise_attention when
    Pallas is disabled or the sequence doesn't tile into the blocks.
    `window`: sliding-window/local attention (see naive_attention) — the
    block-skip predicate prunes out-of-window key blocks, so compute
    scales with window, not sequence. k/v may carry fewer heads than q
    (GQA/MQA): the kernels index kv blocks through the head-group map
    natively, no repeat is materialized. `segments` [b, l] int: sequence
    packing — attention confined to same-id runs in forward AND backward
    (the id tiles ride into the kernels as column/row blocks). With the
    single-array form every row sees at least itself; the rectangular
    (q_seg, k_seg) pair form (one ring rotation's geometry) CAN fully
    mask a row, and such rows return exactly 0 with zero gradient — the
    Pallas and blockwise backends are post-masked identically, so the
    two paths agree (ring itself merges unnormalized partials via
    attention_forward_lse instead). `block_causal` B > 1 (with causal):
    block-causal attention, row i sees key j iff j // B <= i // B
    (causal_limit): the kernels take B in place of `causal` where
    their blocks are whole multiples of B, the blockwise path else."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    block_causal = int(block_causal or 0)
    if block_causal > 1 and not causal:
        raise ValueError("block_causal needs causal=True")
    pair_form = isinstance(segments, (tuple, list))
    lq, lk, d = q.shape[2], k.shape[2], q.shape[3]
    group_size(q, k)  # validate GQA divisibility before kernel dispatch
    block_q = min(resolve_block(block_q, "q"), lq)
    block_k = min(resolve_block(block_k, "k"), lk)
    _check_window(window, lq, lk)
    segments = _check_segments(segments, q.shape[0], lq, lk)
    tiles = _flash_tiles(lq, lk, block_q, block_k)
    if block_causal > 1:
        tiles = tiles and not (block_q % block_causal
                               or block_k % block_causal)
    if not (use_pallas() and tiles):
        if use_pallas():
            # trace-time, so once per compiled program, not per step
            logger.warning(
                "flash_attention takes the XLA blockwise path: seq "
                "(%d, %d) does not tile into (%d, %d) blocks",
                lq, lk, block_q, block_k,
            )
        out = blockwise_attention(q, k, v, causal=causal, scale=scale,
                                  window=window, segments=segments,
                                  block_causal=block_causal)
    else:
        qp, kp, vp = _pad_lanes([q, k, v], d)
        out = _flash(qp, kp, vp, segments,
                     block_causal if block_causal > 1 else causal, scale,
                     block_q, block_k, interpret, window)[..., :d]
    if pair_form:
        # the fully-masked-row contract: both backends leave a
        # degenerate value there (blockwise: mean(v); kernel: depends
        # on block skipping), so mask to exactly 0 — jnp.where also
        # zeroes the row's gradient, matching the backward kernels'
        # zero-contribution handling. O(lq*lk) elementwise, fused.
        q_seg, k_seg = segments
        masked = _fully_masked_rows(q_seg, k_seg, causal, window, lq, lk)
        out = jnp.where(masked[:, None, :, None], 0.0, out)
    return out


def _fully_masked_rows(q_seg, k_seg, causal, window, lq, lk,
                       chunk=2048):
    """[b, lq] bool: True where a query row has NO visible key under the
    segment/causal/window mask — semantics mirror _block_mask at
    pos_offset 0 (pair-form flash_attention is the only caller; ring
    rotations handle offsets through the lse sentinel instead).

    The visibility reduction runs over key CHUNKS (fori_loop), so peak
    memory is O(b * lq * chunk) rather than materializing the full
    [b, lq, lk] pair mask — shard lengths on the ring hot path can
    grow without this check growing with them. One chunk (lk <= 2048)
    is the single fused expression it always was."""
    q_pos = jnp.arange(lq)[:, None]

    def visible(k_lo, k_seg_c, width):
        k_pos = k_lo + jnp.arange(width)[None, :]
        keep = q_seg[:, :, None] == k_seg_c[:, None, :]
        if causal:
            keep = jnp.logical_and(keep, q_pos >= k_pos)
        if window is not None:
            in_w = q_pos - k_pos < window
            keep = jnp.logical_and(keep, in_w)
            if not causal:
                keep = jnp.logical_and(keep, k_pos - q_pos < window)
        return keep.any(-1)

    if lk <= chunk:
        return jnp.logical_not(visible(0, k_seg, lk))

    n_chunks = -(-lk // chunk)
    pad = n_chunks * chunk - lk
    # pad keys with a segment id no query can carry (ids are >= 0)
    k_seg_p = jnp.pad(k_seg, ((0, 0), (0, pad)), constant_values=-1)

    def body(c, acc):
        k_lo = c * chunk
        k_seg_c = jax.lax.dynamic_slice_in_dim(
            k_seg_p, k_lo, chunk, axis=1)
        return jnp.logical_or(acc, visible(k_lo, k_seg_c, chunk))

    any_visible = jax.lax.fori_loop(
        0, n_chunks, body,
        jnp.zeros(q_seg.shape, bool),
    )
    return jnp.logical_not(any_visible)


def jax_flash_attention(q, k, v, causal=False, scale=None, window=None):
    """Dispatch to jax's BUNDLED TPU flash kernel
    (jax.experimental.pallas.ops.tpu.flash_attention) — an alternative
    hot path the hardware sweep benchmarks against ours
    (scripts/bench_attention.py), exposed as the model-zoo
    attn_impl='jax_flash' so the flagship can adopt whichever kernel
    wins on the target chip without code edits. Same [b, h, l, d]
    layout; head_dim zero-padded to the 128-lane width like our kernel.
    Sliding windows are not supported by the bundled kernel; off-TPU
    falls back to the blockwise reference path."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    if window is not None:
        raise ValueError(
            "attn_impl='jax_flash' does not support sliding-window "
            "attention; use the built-in flash kernel (attn_impl='auto')"
        )
    if not is_tpu_backend():
        return blockwise_attention(q, k, v, causal=causal, scale=scale)
    from jax.experimental.pallas.ops.tpu.flash_attention import (
        flash_attention as _bundled,
    )

    # the bundled kernel wants equal head counts; expand GQA kv
    k = expand_kv(k, q.shape[1])
    v = expand_kv(v, q.shape[1])
    d = q.shape[-1]
    q, k, v = _pad_lanes([q, k, v], d)
    out = _bundled(q, k, v, causal=causal, sm_scale=scale)
    return out[..., :d]


# ------------------------------------------- ring-attention local compute
# Ring attention (parallel/context_parallel.py) needs attention in its
# "partial" form — (normalized output, logsumexp) per kv shard, merged
# online across ppermute rotations — and a backward that recomputes this
# shard's slice of the GLOBAL softmax from the merged logsumexp. These
# two functions are that surface: the Pallas kernels when they can run,
# the jnp paths otherwise. They are not differentiable themselves; the
# ring's custom_vjp composes them.


def _pad_lanes(arrays, d):
    if d % 128 == 0:
        return arrays
    widths = ((0, 0), (0, 0), (0, 0), (0, 128 - d % 128))
    return [jnp.pad(x, widths) for x in arrays]


def _flash_tiles(lq, lk, block_q, block_k):
    return (lq % block_q == 0 and lk % block_k == 0
            and block_q % 8 == 0 and block_k % 8 == 0)


def attention_forward_lse(q, k, v, causal=False, scale=None,
                          block_q=None, block_k=None, interpret=None,
                          segments=None, pos_offset=0, window=None):
    """Attention returning (out, logsumexp): out [b,h,lq,d] in q.dtype,
    lse float32 [b,h,lq]. Pallas flash kernel when available and the
    sequence tiles, else the blockwise scan. k/v may carry fewer heads
    than q (GQA). `segments`: packing mask, single array or
    (q_seg, k_seg) pair — the pair form serves ring rotations, where a
    row CAN be fully masked; such rows come back with lse = exactly
    _NEG_INF (their `o` is an unnormalized degenerate value, but an
    lse_merge weights it exp(_NEG_INF - finite) = 0, so merged results
    are exact)."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    group_size(q, k)  # validate GQA divisibility
    lq, lk, d = q.shape[2], k.shape[2], q.shape[3]
    segments = _check_segments(segments, q.shape[0], lq, lk)
    bq = min(resolve_block(block_q, "q"), lq)
    bk = min(resolve_block(block_k, "k"), lk)
    if use_pallas() and _flash_tiles(lq, lk, bq, bk):
        qp, kp, vp = _pad_lanes([q, k, v], d)
        out, lse = _flash_forward(qp, kp, vp, causal, scale, bq, bk,
                                  interpret, with_residuals=True,
                                  segments=segments,
                                  pos_offset=pos_offset, window=window)
        out, lse = out[..., :d], lse[..., 0]
        if segments is not None or pos_offset:
            # a fully-segment-masked row leaves the kernel with
            # lse = -1e30 + log(lk) (p = exp(0) accumulates l = lk);
            # snap every +/-1e30-class value to exact _NEG_INF so the
            # lse_merge weight exp(lse_i - lse) is deterministically 0
            # and the flash/blockwise paths agree bit-for-bit
            lse = jnp.where(jnp.abs(lse) > -_NEG_INF * 0.5,
                            _NEG_INF, lse)
        return out, lse
    out, lse = blockwise_attention(q, k, v, causal=causal, scale=scale,
                                   with_lse=True, segments=segments,
                                   pos_offset=pos_offset,
                                   window=window)
    if segments is not None or pos_offset:
        # blockwise's empty-row lse is m+log(1e-30) ~ -1e30 already;
        # normalize exactly for deterministic merges
        lse = jnp.where(jnp.abs(lse) > -_NEG_INF * 0.5,
                        _NEG_INF, lse)
    return out, lse


def attention_backward_lse(q, k, v, out, lse, g, causal=False, scale=None,
                           block_q=None, block_k=None, interpret=None,
                           grad_dtype=None, segments=None,
                           pos_offset=0, window=None):
    """(dq, dk, dv) for attention given a saved logsumexp.

    `lse` may be the GLOBAL logsumexp of a ring while k/v are one shard:
    P = exp(q·k*scale - lse) is then this shard's exact slice of the
    global softmax, so per-shard partials sum to the exact gradient
    (`out`/`g` are the global output and its cotangent, entering through
    delta = rowsum(g*out)). Pallas two-pass kernels when available, else
    a dense jnp recompute (O(L^2) memory — the CPU/test fallback).
    `grad_dtype` (e.g. float32 for ring partial accumulation) overrides
    the default input-dtype outputs. Under GQA (k/v with fewer heads)
    dk/dv come back group-summed in the kv head count."""
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    lq, lk, d = q.shape[2], k.shape[2], q.shape[3]
    hkv = k.shape[1]
    group = group_size(q, k)
    segments = _check_segments(segments, q.shape[0], lq, lk)
    bq = min(resolve_block(block_q, "q"), lq)
    bk = min(resolve_block(block_k, "k"), lk)
    if use_pallas() and _flash_tiles(lq, lk, bq, bk):
        qp, kp, vp, outp, gp = _pad_lanes([q, k, v, out, g], d)
        dq, dk, dv = _flash_backward(
            qp, kp, vp, outp, lse[..., None], gp, causal, scale, bq, bk,
            interpret, grad_dtype=grad_dtype, segments=segments,
            pos_offset=pos_offset, window=window,
        )
        return dq[..., :d], dk[..., :d], dv[..., :d]
    f32 = jnp.float32
    b = q.shape[0]
    k = expand_kv(k, q.shape[1])
    v = expand_kv(v, q.shape[1])
    s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(f32), k.astype(f32)) * scale
    q_pos_d = jnp.arange(lq)[:, None] + pos_offset
    k_pos_d = jnp.arange(lk)[None, :]
    if causal:
        s = jnp.where((q_pos_d >= k_pos_d)[None, None], s, _NEG_INF)
    if window is not None:
        in_w = (q_pos_d - k_pos_d) < window
        if not causal:
            in_w = in_w & ((k_pos_d - q_pos_d) < window)
        s = jnp.where(in_w[None, None], s, _NEG_INF)
    if segments is not None:
        q_seg, k_seg = segments
        s = jnp.where(
            (q_seg[:, :, None] == k_seg[:, None, :])[:, None],
            s, _NEG_INF,
        )
    p = jnp.exp(s - lse.astype(f32)[..., None])
    if segments is not None:
        # fully-segment-masked rows carry a -1e30-class lse; their true
        # softmax contribution is zero (see _flash_bwd_dq_kernel)
        p = jnp.where(lse.astype(f32)[..., None] < 0.5 * _NEG_INF,
                      0.0, p)
    gf = g.astype(f32)
    dv = jnp.einsum("bhqk,bhqd->bhkd", p, gf)
    dp = jnp.einsum("bhqd,bhkd->bhqk", gf, v.astype(f32))
    delta = jnp.sum(gf * out.astype(f32), axis=-1, keepdims=True)
    ds = p * (dp - delta) * scale
    dq = jnp.einsum("bhqk,bhkd->bhqd", ds, k.astype(f32))
    dk = jnp.einsum("bhqk,bhqd->bhkd", ds, q.astype(f32))
    if group > 1:  # GQA: sum the expanded-head grads back per kv head
        dk = dk.reshape(b, hkv, group, lk, d).sum(2)
        dv = dv.reshape(b, hkv, group, lk, d).sum(2)
    return (dq.astype(grad_dtype or q.dtype),
            dk.astype(grad_dtype or k.dtype),
            dv.astype(grad_dtype or v.dtype))
