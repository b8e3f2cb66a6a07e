"""Pipeline parallelism over the ``pp`` mesh axis: GPipe-style
microbatch streaming built from shard_map + ppermute.

Net-new capability like ring attention (the reference has no pipeline
axis anywhere — SURVEY.md §2.5 "TP / PP / SP ... absent"); the design is
the standard TPU recipe (jax-ml scaling-book "pipelining"): each device
holds a contiguous chunk of the layer stack (leading dim of the stacked
params sharded over ``pp``), microbatches stream through the stages, and
the activation handoff between consecutive stages is a ``ppermute`` ring
step. The whole pipeline is a pure function, so jax AD derives the
backward pipeline (reverse ppermutes, transposed schedule) for free and
the Trainer's compiled step needs no changes.

Two schedules:

- ``gpipe`` (default): M microbatches over P stages take M + P - 1
  ticks of one full stage body each; bubble fraction (P-1)/(M+P-1).
- ``interleaved``: the Megatron-style circular schedule. Each device
  holds ``v`` NON-contiguous chunks of 1/(vP) of the layers (virtual
  stage s runs on device s mod P) and microbatches are injected in
  groups of P, so the pipe runs vM + P - 1 ticks of 1/v-size bodies —
  total stage-work (M + (P-1)/v) vs GPipe's (M + P - 1): the fill/drain
  bubble shrinks by the interleave factor (27% -> 16% at M=8, P=4,
  v=2). Requires M % P == 0 and layers % (vP) == 0, and the stacked
  params in ring-ordered ("interleaved") layout — device-major rows so
  each device's local chunk rows are exactly its v virtual stages; use
  :func:`interleave_layers` / :func:`deinterleave_layers` to convert a
  semantically-ordered stack (e.g. a checkpoint) to/from this layout.

Both schedules compute garbage during fill/drain ticks (masked out at
collection), the same trade the canonical SPMD pipelines make: a no-op
tick would still have to execute the stage body under SPMD.

Activation staging: ``remat=True`` wraps the per-tick body in
``jax.checkpoint`` — the AD-derived backward pipeline then stores ONLY
the inter-stage activation per tick (one microbatch-sized tensor) and
recomputes stage interiors, the per-microbatch staging 1F1B exists for.
The backward schedule itself is jax AD's transpose of the forward scan:
reverse ppermutes, ticks reversed — fwd+bwd totals 2(M+P-1) stage-times
for gpipe, exactly textbook non-interleaved 1F1B's critical path (1F1B
re-orders those same ticks to bound in-flight activations, which remat
achieves here), and 2(M + (P-1)/v) for the interleaved schedule, which
is where the real bubble shrink lives.
"""

import jax
import jax.numpy as jnp
from jax import shard_map as _shard_map
from jax.sharding import PartitionSpec as P

from elasticdl_tpu.common.constants import MeshAxis


def shard_map(f, mesh, in_specs, out_specs):
    # manual-collectives mode: the body mixes per-stage values with
    # replicated ones, which the varying-manual-axes checker rejects
    return _shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
        check_vma=False,
    )


def stage_size(mesh):
    return mesh.shape[MeshAxis.PP]


def _ring_perm(n_layers, n_stages, interleave):
    """Row permutation: semantic layer order -> interleaved layout.

    Virtual stage s (ring order, s in [0, v*P)) covers semantic layers
    [s*cl, (s+1)*cl), cl = L/(vP), and runs on device s mod P, local
    slot s // P. The interleaved layout is device-major: device d's
    contiguous block holds its slots j=0..v-1 = virtual stages j*P+d.
    """
    if n_layers % (n_stages * interleave) != 0:
        raise ValueError(
            "layer stack of %d rows not divisible by pp=%d x "
            "interleave=%d" % (n_layers, n_stages, interleave)
        )
    cl = n_layers // (n_stages * interleave)
    return [
        (j * n_stages + d) * cl + k
        for d in range(n_stages)
        for j in range(interleave)
        for k in range(cl)
    ]


def interleave_layers(stacked, n_stages, interleave):
    """Convert a semantically-ordered layer stack (leading dim = L) to
    the interleaved-schedule layout (see module docstring). Use on
    checkpoints trained with the gpipe schedule (or torn down via
    :func:`deinterleave_layers`) before applying schedule="interleaved".
    """
    import numpy as np

    def one(leaf):
        perm = np.asarray(
            _ring_perm(leaf.shape[0], n_stages, interleave))
        return jnp.take(leaf, perm, axis=0)

    return jax.tree.map(one, stacked)


def deinterleave_layers(stacked, n_stages, interleave):
    """Inverse of :func:`interleave_layers` (back to semantic order)."""
    import numpy as np

    def one(leaf):
        perm = np.asarray(
            _ring_perm(leaf.shape[0], n_stages, interleave))
        inv = np.empty_like(perm)
        inv[perm] = np.arange(len(perm))
        return jnp.take(leaf, inv, axis=0)

    return jax.tree.map(one, stacked)


def convert_params_to_interleaved(params, n_stages, interleave,
                                  like=None, stacked_key_prefix="blk_"):
    """Convert a gpipe-trained param dict (e.g. a checkpoint restored
    into a TrainState) to the interleaved-schedule layout: leaves whose
    top-level key starts with ``stacked_key_prefix`` get
    :func:`interleave_layers`; everything else passes through. When
    ``like`` (a same-structure params tree, e.g. the interleaved
    trainer's freshly-initialized state.params) is given, every leaf is
    re-placed onto its sharding via a host round-trip — the jnp.take
    gather de-shards, and the fresh buffers also keep a later donating
    train_step on the SOURCE state from tearing shared leaves out of
    the converted tree."""
    import numpy as np

    conv = {
        k: (interleave_layers(val, n_stages, interleave)
            if k.startswith(stacked_key_prefix) else val)
        for k, val in dict(params).items()
    }
    if like is not None:
        conv = jax.tree.map(
            lambda new, old: jax.device_put(
                np.asarray(new), old.sharding),
            conv, dict(like),
        )
    if isinstance(params, dict):
        return conv
    return type(params)(conv)


def pipeline_apply(stage_fn, stacked_params, x, mesh, num_microbatches,
                   batch_spec=None, schedule="gpipe", interleave=2,
                   remat=False):
    """Run `x` through all pipeline stages in order.

    stage_fn(local_params, x_mb) -> y_mb: one STAGE's computation (its
        chunk of the layer stack — for the interleaved schedule it is
        called per 1/(vP)-size chunk; same output shape as input).
    stacked_params: pytree whose every leaf has leading dim == total
        layers (or stages) divisible by pp, sharded P("pp") on dim 0 —
        each device receives its contiguous chunk. For
        schedule="interleaved" the rows must be in interleaved layout
        (:func:`interleave_layers`; fresh random inits need no
        conversion — row order is a labeling).
    x: [batch, ...]; batch must divide into num_microbatches, and the
        per-device batch (after dp/fsdp sharding) too.
    batch_spec: PartitionSpec of x (default: batch over (dp, fsdp)).
    schedule: "gpipe" | "interleaved" (module docstring).
    interleave: v, virtual chunks per device (interleaved schedule).
    remat: checkpoint the per-tick body — backward stores only the
        inter-stage activations and recomputes stage interiors.

    Returns y with x's shape/sharding (replicated over pp).
    """
    n_stages = stage_size(mesh)
    m = int(num_microbatches)
    if m < 1:
        raise ValueError("num_microbatches must be >= 1")
    if schedule not in ("gpipe", "interleaved"):
        raise ValueError("unknown schedule %r" % (schedule,))
    v = int(interleave) if schedule == "interleaved" else 1
    if v < 1:
        raise ValueError("interleave must be >= 1")
    for leaf in jax.tree.leaves(stacked_params):
        if leaf.shape[0] % (n_stages * v) != 0:
            raise ValueError(
                "stacked param leading dim %d not divisible by "
                "pp=%d x interleave=%d"
                % (leaf.shape[0], n_stages, v)
            )
    if schedule == "interleaved" and m % n_stages != 0:
        raise ValueError(
            "interleaved schedule injects microbatches in groups of "
            "pp: num_microbatches=%d %% pp=%d != 0 (use gpipe or pad)"
            % (m, n_stages)
        )
    if batch_spec is None:
        batch_spec = P((MeshAxis.DP, MeshAxis.FSDP))
    if remat:
        stage_fn = jax.checkpoint(stage_fn)
    # One body serves both schedules: v=1 reduces the circular
    # schedule exactly to GPipe (slot always 0, injection every tick,
    # banking at t - (P-1)) — proven by the (pp,m,v)=(2,2,1) oracle
    # test and the schedule-parity dryrun sub-run.
    return _interleaved_apply(
        stage_fn, stacked_params, x, mesh, m, v, batch_spec)


def _interleaved_apply(stage_fn, stacked_params, x, mesh, m, v,
                       batch_spec):
    """Circular schedule, both flavors: vM + P - 1 ticks of 1/v-size
    chunk bodies (v=1 IS GPipe). Device d at tick t runs its local slot
    j = ((t - d) // P) mod v (= virtual stage jP + d); device 0 injects
    fresh microbatches in groups of P during its slot-0 phases; device
    P-1 (owner of the final virtual stage vP-1) banks completed
    microbatches; every tick ends in one forward ring ppermute — the
    slot formula is exactly consistent with that single hop (virtual
    stage s's output arrives where s+1 lives, including the v-pass
    wrap-around)."""
    n_stages = stage_size(mesh)

    def body(params, xb):
        stage = jax.lax.axis_index(MeshAxis.PP)
        b_loc = xb.shape[0]
        if b_loc % m:
            raise ValueError(
                "per-device batch %d not divisible by %d microbatches"
                % (b_loc, m)
            )
        mbs = xb.reshape((m, b_loc // m) + xb.shape[1:])
        outs0 = jnp.zeros_like(mbs)
        act0 = jnp.zeros_like(mbs[0])
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def slot_params(j):
            def slc(leaf):
                rows = leaf.shape[0] // v
                return jax.lax.dynamic_slice_in_dim(
                    leaf, j * rows, rows, 0)

            return jax.tree.map(slc, params)

        def tick(carry, t):
            act, outs = carry
            # local slot: floor-divide keeps pre-arrival ticks (t < d)
            # harmless — the chunk computes garbage never banked
            j = jnp.mod((t - stage) // n_stages, v)
            # injection: device 0, slot-0 phase, next group not done
            m_idx = t % n_stages + n_stages * (t // (v * n_stages))
            inject = ((stage == 0)
                      & ((t // n_stages) % v == 0)
                      & (m_idx < m))
            feed = jax.lax.dynamic_index_in_dim(
                mbs, jnp.clip(m_idx, 0, m - 1), 0, keepdims=False
            )
            inp = jnp.where(inject, feed, act)
            out = stage_fn(slot_params(j), inp)
            # banking: mb bm finishes virtual stage vP-1 on device P-1
            # at t = (bm % P) + P(v-1) + (P-1) + vP*(bm // P)
            tp = t - (n_stages * (v - 1) + n_stages - 1)
            q = tp % (v * n_stages)
            bm = (tp // (v * n_stages)) * n_stages + q
            bank = ((stage == n_stages - 1) & (tp >= 0)
                    & (q < n_stages) & (bm < m))
            idx_c = jnp.clip(bm, 0, m - 1)
            current = jax.lax.dynamic_index_in_dim(
                outs, idx_c, 0, keepdims=False
            )
            banked = jnp.where(bank, out, current)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, banked, idx_c, 0
            )
            act = jax.lax.ppermute(out, MeshAxis.PP, fwd)
            return (act, outs), None

        (act, outs), _ = jax.lax.scan(
            tick, (act0, outs0), jnp.arange(v * m + n_stages - 1)
        )
        mask = (stage == n_stages - 1).astype(outs.dtype)
        outs = jax.lax.psum(outs * mask, MeshAxis.PP)
        return outs.reshape(xb.shape)

    return shard_map(
        body,
        mesh,
        (P(MeshAxis.PP), batch_spec),
        batch_spec,
    )(stacked_params, x)


def sequential_apply(stage_fn, stacked_params, x, n_stages):
    """Oracle: the same stages run one after another without the mesh —
    what pipeline_apply must equal numerically (tests + the pp=1 path).
    """
    chunk = jax.tree.leaves(stacked_params)[0].shape[0] // n_stages

    def one(i, xv):
        local = jax.tree.map(
            lambda l: jax.lax.dynamic_slice_in_dim(l, i * chunk, chunk, 0),
            stacked_params,
        )
        return stage_fn(local, xv)

    for i in range(n_stages):
        x = one(i, x)
    return x
