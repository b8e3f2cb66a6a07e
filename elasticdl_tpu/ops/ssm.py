"""The selective state-space recurrence of a Mamba-2 mixer, two ways.

Per head h (of H, width P) with a state S [P, N], input x_t [P], step
Δ_t > 0, decay rate A < 0 and the group's B_t, C_t [N] (head h reads
group h // (H / G)):

    S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t          y_t = S_t C_t

(the skip term D ⊙ x_t and everything around the recurrence are the
mixer's, model_zoo/transformer_lm/mamba2.py). The state is float32.

* `ssm_chunked_scan`: a whole sequence in chunks of `chunk` tokens
  (the "SSD" form): inside a chunk the recurrence is a masked,
  decay-weighted attention-like product, between chunks the state is
  carried by a short scan. Plain `jax.numpy` for XLA: the prefill and
  the training forward. A token with Δ = 0 decays nothing and adds
  nothing, so a caller that zeroes Δ from some position on gets the
  state AT that position out of a longer, padded sequence.
* `ssm_state_update`: one token for each of L lanes, the decode tick.
  It reads every lane's state once and writes it once: 2 x 4 x H P N
  bytes a lane against 6 H P N operations, so the bytes are the cost.
  On the TPU one Mosaic kernel, `ssm_state_update` in a device trace,
  grid over the lanes, a lane's heads one after another, the state
  updated IN PLACE (`input_output_aliases`): a donated state arena is
  never copied. Elsewhere (the CPU tests, kernels off, shapes the
  kernel refuses) `ssm_state_update_reference` computes the same in
  `jax.numpy`. Mapped over lanes by `jax.vmap` (the serving step maps
  one lane a sequence) the lanes are laid side by side and updated by
  ONE call.
"""

import functools

import jax
import jax.numpy as jnp
from jax.custom_batching import custom_vmap
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.dispatch import interpret_mode, use_pallas

HIGHEST = jax.lax.Precision.HIGHEST
#: the kernel's name in a device trace (the scope around the call names
#: its HLO instruction: `ssm_state_update.N`)
KERNEL_SCOPE = "ssm_state_update"
#: a lane's state goes through VMEM whole, in and out, double-buffered:
#: 4 x 2.1 MB at 64 heads of [64, 128], over the 16 MB default
_VMEM_LIMIT = 48 * 1024 * 1024


# ------------------------------------------------------ a whole sequence


def ssm_chunked_scan(x, dt, a, b_in, c_in, chunk=128, state=None):
    """y [b, l, H, P] float32 and the state after the last token
    [b, H, P, N] float32.

    x [b, l, H, P], dt [b, l, H] (Δ, float32, >= 0), a [H] (A, < 0),
    b_in, c_in [b, l, G, N]; `state` [b, H, P, N] is the state before
    the first token (None: zeros). `l` need not be a multiple of
    `chunk`: the tail is padded with Δ = 0."""
    bsz, l, h, p = x.shape
    g, n = b_in.shape[2:]
    r = h // g
    pad = -l % chunk
    f32 = jnp.float32
    x, dt, b_in, c_in = (
        jnp.pad(v.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) * (v.ndim - 2))
        for v in (x, dt, b_in, c_in))
    c = (l + pad) // chunk
    # heads as (group, head of the group), so that B and C are shared
    # by a group's heads without being repeated
    x = x.reshape(bsz, c, chunk, g, r, p)
    dt = dt.reshape(bsz, c, chunk, g, r)
    b_in = b_in.reshape(bsz, c, chunk, g, n)
    c_in = c_in.reshape(bsz, c, chunk, g, n)
    log_decay = dt * a.astype(f32).reshape(g, r)  # <= 0
    cum = jnp.cumsum(log_decay, axis=2)  # through token i, inclusive
    xdt = x * dt[..., None]
    # inside a chunk: y_i += sum_{j <= i} (C_i . B_j) e^{cum_i - cum_j}
    #                                      Δ_j x_j
    scores = jnp.einsum("bcign,bcjgn->bcgij", c_in, b_in)
    diff = (jnp.moveaxis(cum, 2, -1)[..., :, None]
            - jnp.moveaxis(cum, 2, -1)[..., None, :])  # [b,c,g,r,i,j]
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))
    weights = scores[:, :, :, None] * jnp.exp(
        jnp.where(causal, diff, -jnp.inf))
    y = jnp.einsum("bcgrij,bcjgrp->bcigrp", weights, xdt)
    # what a chunk adds to the state by its end, and its whole decay
    to_end = jnp.exp(cum[:, :, -1:] - cum)
    added = jnp.einsum("bcjgn,bcjgrp->bcgrpn", b_in,
                       xdt * to_end[..., None], precision=HIGHEST)
    chunk_decay = jnp.exp(cum[:, :, -1])  # [b, c, g, r]
    if state is None:
        state = jnp.zeros((bsz, h, p, n), f32)
    state = state.astype(f32).reshape(bsz, g, r, p, n)

    def carry(s, inputs):
        decay, add = inputs
        return s * decay[..., None, None] + add, s  # s: before the chunk

    state, before = jax.lax.scan(
        carry, state,
        (jnp.moveaxis(chunk_decay, 1, 0), jnp.moveaxis(added, 1, 0)))
    before = jnp.moveaxis(before, 0, 1)  # [b, c, g, r, p, n]
    y = y + jnp.einsum("bcign,bcgrpn->bcigrp", c_in, before,
                       precision=HIGHEST) * jnp.exp(cum)[..., None]
    y = y.reshape(bsz, l + pad, h, p)[:, :l]
    return y, state.reshape(bsz, h, p, n)


# ----------------------------------------------- one token for each lane


def ssm_state_update_reference(state, x, dt, a, b_in, c_in):
    """The same update in plain jax.numpy (see ssm_state_update)."""
    lanes, h, p, n = state.shape
    g = b_in.shape[1]
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32))  # [L, H]
    b_h = jnp.repeat(b_in.astype(f32), h // g, axis=1)  # [L, H, N]
    c_h = jnp.repeat(c_in.astype(f32), h // g, axis=1)
    xdt = x.astype(f32) * dt[..., None]
    state = (state * decay[..., None, None]
             + xdt[..., None] * b_h[:, :, None, :])
    return jnp.sum(state * c_h[:, :, None, :], axis=-1), state


def kernel_supported(h, p, n, g):
    """Shape gate of the Mosaic kernel: a head's state is whole
    (8, 128) float32 tiles and the heads of a group are whole."""
    return p % 8 == 0 and n % 128 == 0 and h % g == 0


def _update_kernel(state_ref, decay_ref, xdt_ref, b_ref, c_ref,
                   y_ref, out_ref, *, heads, group):
    """One lane: state [H, P, N]; decay, xdt [P, H] (a head a column,
    the decay the same down its column); b, c [G, N]; y [P, H]."""
    decay = decay_ref[...]
    xdt = xdt_ref[...]
    b_all = b_ref[...]
    c_all = c_ref[...]
    column = jax.lax.broadcasted_iota(jnp.int32, decay.shape, 1)
    y = jnp.zeros(decay.shape, jnp.float32)
    for head in range(heads):  # static: a column of a value is a slice
        at = slice(head // group, head // group + 1)
        new = (state_ref[head] * decay[:, head:head + 1]
               + xdt[:, head:head + 1] * b_all[at])
        out_ref[head] = new
        y_head = jnp.sum(new * c_all[at], axis=-1, keepdims=True)
        y = jnp.where(column == head, y_head, y)
    y_ref[...] = y


def _state_update_kernel(state, x, dt, a, b_in, c_in):
    lanes, h, p, n = state.shape
    g = b_in.shape[1]
    f32 = jnp.float32
    dt = dt.astype(f32)
    decay = jnp.exp(dt * a.astype(f32))  # [L, H]
    # a head a column: what a head's rows are multiplied by lies down
    # the sublanes its state's rows lie on
    decay_col = jnp.broadcast_to(decay[:, None, :], (lanes, p, h))
    xdt_col = jnp.swapaxes(x.astype(f32) * dt[..., None], 1, 2)
    call = pl.pallas_call(
        functools.partial(_update_kernel, heads=h, group=h // g),
        grid=(lanes,),
        in_specs=[
            pl.BlockSpec((None, h, p, n), lambda i: (i, 0, 0, 0)),
            pl.BlockSpec((None, p, h), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, p, h), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, g, n), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, g, n), lambda i: (i, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((None, p, h), lambda i: (i, 0, 0)),
            pl.BlockSpec((None, h, p, n), lambda i: (i, 0, 0, 0)),
        ],
        out_shape=[jax.ShapeDtypeStruct((lanes, p, h), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        input_output_aliases={0: 1},  # the state, in place
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret_mode(),
    )
    with jax.named_scope(KERNEL_SCOPE):
        y, state = call(state, decay_col, xdt_col, b_in.astype(f32),
                        c_in.astype(f32))
    return jnp.swapaxes(y, 1, 2), state


def _state_update(use_kernel, state, x, dt, a, b_in, c_in):
    _, h, p, n = state.shape
    if use_kernel is None:
        use_kernel = use_pallas() and kernel_supported(
            h, p, n, b_in.shape[1])
    fn = _state_update_kernel if use_kernel else ssm_state_update_reference
    return fn(state, x, dt, a, b_in, c_in)


@functools.lru_cache(maxsize=None)
def _lanes_as_one_call(use_kernel):
    """_state_update(use_kernel, ...) with a batching rule: mapped over
    lanes (everything but `a` mapped) the lanes are laid side by side
    and updated by ONE call, itself mappable again."""
    plain = functools.partial(_state_update, use_kernel)
    call = custom_vmap(plain)

    @call.def_vmap
    def _lanes(axis_size, in_batched, state, x, dt, a, b_in, c_in):
        mapped = in_batched[:3] + in_batched[4:]
        if in_batched[3] or not all(mapped):
            axes = [0 if b else None for b in in_batched]
            out = jax.vmap(plain, in_axes=axes)(state, x, dt, a, b_in,
                                                c_in)
            return out, (True, True)
        t = state.shape[1]
        flat = [v.reshape((axis_size * t,) + v.shape[2:])
                for v in (state, x, dt, b_in, c_in)]
        y, new = call(flat[0], flat[1], flat[2], a, flat[3], flat[4])
        return ((y.reshape((axis_size, t) + y.shape[1:]),
                 new.reshape(state.shape)), (True, True))

    return call


def ssm_state_update(state, x, dt, a, b_in, c_in, use_kernel=None):
    """One token for each of L lanes: (y [L, H, P] float32, the new
    state [L, H, P, N] float32).

    state [L, H, P, N] float32; x [L, H, P]; dt [L, H] (Δ, >= 0); a [H]
    (A, < 0); b_in, c_in [L, G, N]. `use_kernel=None` takes the Mosaic
    kernel where kernels are on and the shapes are whole tiles
    (`kernel_supported`); the kernel writes the state over its input.
    Whatever a lane's state holds, non-finite included, no other lane's
    result depends on it."""
    return _lanes_as_one_call(use_kernel)(state, x, dt, a, b_in, c_in)
