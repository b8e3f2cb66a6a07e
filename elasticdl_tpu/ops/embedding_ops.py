"""Sparse embedding-row kernels (Pallas TPU).

The TPU-native form of the reference's sparse PS path:

* `embedding_gather` — batched row lookup against an HBM-resident
  [vocab, dim] table via per-row DMA, replacing the
  pull_embedding_vectors RPC fan-out (worker/worker.py:380-409 →
  ps/embedding_table.py EmbeddingTable.get);
* `sparse_{sgd,momentum,adam,adagrad}_update` — in-place row updates
  against HBM tables (and their co-located slot tables), the Pallas
  counterpart of the Go sparse kernels that iterate rows and call the
  Eigen C API per row (go/pkg/kernel/kernel.go `SparseSGD`/`SparseAdam`/…
  → capi/kernel_api.cc). Only the rows named in `ids` move — the
  OptimizerWrapper contract (ps/optimizer_wrapper.py:70-351);
* `dedup_indexed_slices` — static-shape segment-sum dedup of duplicate
  ids, mirroring common/tensor_utils.py `deduplicate_indexed_slices`
  (the worker dedups before scattering grads to PS, worker.py:505-617).

Ids are int32; -1 is the padding id and marks rows to skip, which is how
dynamic id counts fit XLA's static shapes. Tables are aliased in/out
(`input_output_aliases`) so updates are true in-place HBM writes.
"""

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops import update_math as um
from elasticdl_tpu.ops.dispatch import interpret_mode, use_pallas

PADDING_ID = -1

_ID_CHUNK = 8  # ids per grid program


def _pad_ids(ids, chunk=_ID_CHUNK):
    ids = jnp.asarray(ids, jnp.int32).reshape(-1)
    n = ids.shape[0]
    padded = max(pl.cdiv(n, chunk), 1) * chunk
    return jnp.pad(ids, (0, padded - n), constant_values=PADDING_ID), n


def _pad_rows(rows, n_padded):
    rows = jnp.asarray(rows)
    return jnp.pad(rows, ((0, n_padded - rows.shape[0]), (0, 0)))


_LANE = 128


def _lane_pad(arr):
    """Pad the last dim up to a 128 multiple: Mosaic requires row-DMA
    slices to be lane-aligned, so tables with dim % 128 != 0 take a
    pad/unpad copy. The fast path (and the sane TPU table layout) is an
    embedding dim that is already a multiple of 128."""
    dim = arr.shape[-1]
    rem = dim % _LANE
    if rem == 0:
        return arr
    return jnp.pad(arr, ((0, 0), (0, _LANE - rem)))


# ------------------------------------------------------------------ gather


def _gather_kernel(ids_ref, table_ref, out_ref, sems):
    """One program gathers the whole id list: rows stream HBM→HBM with
    `_ID_CHUNK` DMAs in flight (double-buffered over the semaphore array),
    so row latency overlaps instead of serializing."""
    n = out_ref.shape[0]

    def get_dma(j):
        rid = jnp.maximum(ids_ref[j], 0)
        return pltpu.make_async_copy(
            table_ref.at[pl.ds(rid, 1), :],
            out_ref.at[pl.ds(j, 1), :],
            sems.at[j % _ID_CHUNK],
        )

    def warm(j, _):
        get_dma(j).start()
        return 0

    jax.lax.fori_loop(0, min(_ID_CHUNK, n), warm, 0, unroll=True)

    def body(j, _):
        get_dma(j).wait()

        @pl.when(j + _ID_CHUNK < n)
        def _():
            get_dma(j + _ID_CHUNK).start()

        return 0

    jax.lax.fori_loop(0, n, body, 0)


def embedding_gather(table, ids, interpret=None):
    """table[ids] for int32 ids (any shape); padding ids gather row 0.

    The table never leaves HBM — touched rows are DMA'd straight into the
    (HBM) output, which is the point when vocab >> touched ids.
    """
    ids = jnp.asarray(ids, jnp.int32)
    vocab, dim = table.shape
    # ids outside [0, vocab) (incl. PADDING_ID) clamp into range — the
    # caller masks padding rows out (safe_embedding_lookup); an
    # out-of-range DMA would read/write arbitrary HBM.
    ids = jnp.clip(ids, 0, vocab - 1)
    out_shape = ids.shape + (dim,)
    if not use_pallas():
        return jnp.take(table, ids, axis=0)
    table = _lane_pad(table)
    flat_ids, n = _pad_ids(ids)
    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)],
            out_specs=pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM),
            scratch_shapes=[pltpu.SemaphoreType.DMA((_ID_CHUNK,))],
        ),
        out_shape=jax.ShapeDtypeStruct(
            (flat_ids.shape[0], table.shape[1]), table.dtype
        ),
        interpret=interpret_mode() if interpret is None else interpret,
    )(flat_ids, table)
    return out[:n, :dim].reshape(out_shape)


# ----------------------------------------------------------- row updates


def _row_update_call(kernel, ids, hyper, tables, grads, interpret):
    """Shared driver: `tables` are aliased in/out; `grads` is [n, dim]."""
    vocab, true_dim = tables[0].shape
    dtype = tables[0].dtype
    # out-of-range ids are skipped exactly like PADDING_ID: an OOB row
    # DMA-write would corrupt whatever lives past the table in HBM.
    ids = jnp.asarray(ids, jnp.int32)
    ids = jnp.where(ids >= vocab, PADDING_ID, ids)
    tables = [_lane_pad(t) for t in tables]
    dim = tables[0].shape[1]
    flat_ids, _ = _pad_ids(ids)
    grads = _lane_pad(_pad_rows(grads, flat_ids.shape[0]))
    grid = flat_ids.shape[0] // _ID_CHUNK
    hyper = jnp.stack([jnp.asarray(h, jnp.float32) for h in hyper])
    n_tables = len(tables)
    hbm = pl.BlockSpec(memory_space=pltpu.MemorySpace.HBM)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,  # ids, hyper
            grid=(grid,),
            in_specs=[hbm] * n_tables
            + [
                pl.BlockSpec(
                    (_ID_CHUNK, dim),
                    lambda i, *_: (i, 0),
                    memory_space=pltpu.VMEM,
                )
            ],
            out_specs=[hbm] * n_tables,
            scratch_shapes=[pltpu.VMEM((1, dim), dtype)] * n_tables
            + [pltpu.SemaphoreType.DMA((n_tables,))],
        ),
        out_shape=[
            jax.ShapeDtypeStruct(t.shape, t.dtype) for t in tables
        ],
        input_output_aliases={2 + k: k for k in range(n_tables)},
        interpret=interpret_mode() if interpret is None else interpret,
    )(flat_ids, hyper, *tables, grads)
    if dim != true_dim:
        out = [o[:, :true_dim] for o in out]
    return tuple(out) if n_tables > 1 else out[0]


def _row_update_fallback(row_math, ids, tables, grads):
    """Pure-jnp path (ELASTICDL_TPU_DISABLE_PALLAS=1): gather touched
    rows, apply the shared update math, scatter back with OOB/padding ids
    dropped."""
    vocab = tables[0].shape[0]
    ids = jnp.asarray(ids, jnp.int32)
    safe = jnp.clip(ids, 0, vocab - 1)
    # negative ids would WRAP in .at[] indexing; push them out of range so
    # mode="drop" discards them together with ids >= vocab
    scatter_ids = jnp.where(ids < 0, vocab, ids)
    rows = [jnp.take(t, safe, axis=0) for t in tables]
    new_rows = row_math(rows, jnp.asarray(grads))
    outs = [
        t.at[scatter_ids].set(nr, mode="drop")
        for t, nr in zip(tables, new_rows)
    ]
    return tuple(outs) if len(outs) > 1 else outs[0]


def _row_copies(table_refs, rid, scratch, sems, inbound):
    copies = []
    for k, (r, s) in enumerate(zip(table_refs, scratch)):
        row = r.at[pl.ds(rid, 1), :]
        src, dst = (row, s) if inbound else (s, row)
        copies.append(pltpu.make_async_copy(src, dst, sems.at[k]))
    for c in copies:
        c.start()
    for c in copies:
        c.wait()


def _make_row_kernel(n_tables, math_fn):
    """Build a kernel: per id, DMA `n_tables` rows in, apply `math_fn`
    (scratch rows + grad row + hyper → new scratch rows), DMA back."""

    def kernel(ids_ref, hyper_ref, *refs):
        tables_in = refs[:n_tables]
        grads_ref = refs[n_tables]
        tables_out = refs[n_tables + 1:n_tables + 1 + n_tables]
        scratch = refs[n_tables * 2 + 1:n_tables * 3 + 1]
        sems = refs[-1]
        base = pl.program_id(0) * _ID_CHUNK

        def body(j, _):
            rid = ids_ref[base + j]

            @pl.when(rid >= 0)
            def _():
                _row_copies(tables_in, rid, scratch, sems, inbound=True)
                math_fn(scratch, grads_ref[j, :], hyper_ref)
                _row_copies(tables_out, rid, scratch, sems, inbound=False)

            return 0

        jax.lax.fori_loop(0, _ID_CHUNK, body, 0)

    return kernel


def _sgd_math(scratch, g, h):
    scratch[0][0, :] = um.sgd_math(scratch[0][0, :], g, h[0])


_sgd_row_kernel = _make_row_kernel(1, _sgd_math)


def sparse_sgd_update(table, ids, grads, lr, interpret=None):
    """rows[ids] -= lr * grads (kernel.go `SparseSGD`). Ids must be
    deduplicated (see dedup_indexed_slices); -1 ids are skipped."""
    if not use_pallas():
        return _row_update_fallback(
            lambda rows, g: [um.sgd_math(rows[0], g, lr)],
            ids, [table], grads,
        )
    return _row_update_call(
        _sgd_row_kernel, ids, [lr], [table], grads, interpret
    )


def _momentum_math(scratch, g, h):
    scratch[0][0, :], scratch[1][0, :] = um.momentum_math(
        scratch[0][0, :], scratch[1][0, :], g, h[0], h[1], h[2]
    )


_momentum_row_kernel = _make_row_kernel(2, _momentum_math)


def sparse_momentum_update(table, velocity, ids, grads, lr, momentum=0.9,
                           nesterov=False, interpret=None):
    """Momentum on touched rows (kernel.go `SparseMomentum`).
    Returns (new_table, new_velocity)."""
    nesterov_f = 1.0 if nesterov else 0.0
    if not use_pallas():
        return _row_update_fallback(
            lambda rows, g: um.momentum_math(
                rows[0], rows[1], g, lr, momentum, nesterov_f
            ),
            ids, [table, velocity], grads,
        )
    return _row_update_call(
        _momentum_row_kernel,
        ids,
        [lr, momentum, 1.0 if nesterov else 0.0],
        [table, velocity],
        grads,
        interpret,
    )


def _adam_math(scratch, g, h):
    scratch[0][0, :], scratch[1][0, :], scratch[2][0, :] = um.adam_math(
        scratch[0][0, :], scratch[1][0, :], scratch[2][0, :], g,
        h[0], h[1], h[2], h[3],
    )


_adam_row_kernel = _make_row_kernel(3, _adam_math)


def sparse_adam_update(table, m, v, ids, grads, step, lr, beta1=0.9,
                       beta2=0.999, eps=1e-8, interpret=None):
    """Bias-corrected Adam on touched rows (kernel.go `SparseAdam`).
    Returns (new_table, new_m, new_v). `step` may be a traced array."""
    if not use_pallas():
        alpha = um.adam_alpha(lr, beta1, beta2, step)
        return _row_update_fallback(
            lambda rows, g: um.adam_math(
                rows[0], rows[1], rows[2], g, alpha, beta1, beta2, eps
            ),
            ids, [table, m, v], grads,
        )
    return _row_update_call(
        _adam_row_kernel,
        ids,
        [um.adam_alpha(lr, beta1, beta2, step), beta1, beta2, eps],
        [table, m, v],
        grads,
        interpret,
    )


def _adagrad_math(scratch, g, h):
    scratch[0][0, :], scratch[1][0, :] = um.adagrad_math(
        scratch[0][0, :], scratch[1][0, :], g, h[0], h[1]
    )


_adagrad_row_kernel = _make_row_kernel(2, _adagrad_math)


def sparse_adagrad_update(table, accum, ids, grads, lr, eps=1e-10,
                          interpret=None):
    """Adagrad on touched rows (kernel.go `SparseAdagrad`).
    Returns (new_table, new_accum)."""
    if not use_pallas():
        return _row_update_fallback(
            lambda rows, g: um.adagrad_math(rows[0], rows[1], g, lr, eps),
            ids, [table, accum], grads,
        )
    return _row_update_call(
        _adagrad_row_kernel, ids, [lr, eps], [table, accum], grads,
        interpret,
    )


# ------------------------------------------------------------------ dedup


def dedup_indexed_slices(ids, values, num_unique=None):
    """Sum `values` rows that share an id; static output size.

    Parity with common/tensor_utils.py `deduplicate_indexed_slices`
    (tf.math.segment_sum over sorted unique ids), under XLA's static
    shapes: the result always has `num_unique` (default len(ids)) rows,
    surplus rows padded with id PADDING_ID and zero values.

    Returns (unique_ids [k], summed [k, dim]).
    """
    ids = jnp.asarray(ids, jnp.int32).reshape(-1)
    values = jnp.asarray(values)
    k = ids.shape[0] if num_unique is None else num_unique
    if not isinstance(ids, jax.core.Tracer):
        n_distinct = int(np.unique(np.asarray(ids)).size)
        if n_distinct > k:
            raise ValueError(
                "num_unique=%d < %d distinct ids: gradients would be "
                "silently dropped" % (k, n_distinct)
            )
    uniq, inverse = jnp.unique(
        ids, size=k, fill_value=PADDING_ID, return_inverse=True
    )
    inverse = inverse.reshape(-1)
    summed = jax.ops.segment_sum(values, inverse, num_segments=k)
    # unique() packs fill values at the end only when there are fewer than
    # `size` distinct ids; zero out rows whose slot is padding.
    summed = jnp.where((uniq != PADDING_ID)[:, None], summed, 0.0)
    return uniq, summed
