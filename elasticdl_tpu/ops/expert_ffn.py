"""Expert feed-forward products over row tiles: the kernel under
parallel/moe.py's drop-free expert layer.

The work is a list of TILES. Tile i is `tm` rows of activations
(`x_tiles[x_of[i]]`), ONE expert (`expert_of[i]`) and a float32 weight
a row (`gates[i]`); its result is, by how many matrices an expert has,

    gates[i] * ((relu(x W_gate[e]) * (x W_up[e])) W_down[e])        ReGLU
    gates[i] * ((silu(x W_gate[e]) * (x W_up[e])) W_down[e])        SwiGLU
    gates[i] * (relu(x W_up[e]^T)^2 W_down[e])                      relu^2

(the two-matrix form keeps BOTH matrices a hidden unit a row, [hidden,
d], as a checkpoint stores an up projection: where no multiple of 128
divides the hidden width (1856 = 29 x 64) the device lays a [d, hidden]
matrix out with d minor, and a kernel that wants it row-major makes the
compiler copy the whole bank every launch: 1.0 ms a layer a tick at 32
experts of 2688 x 1856, my chip run, PR 36)

in float32, products in the operands' dtype with float32 accumulation.
Only the first `n_live` tiles are computed; the rest are written as
zeros and move no weight. That one shape serves both ends of serving
(parallel/moe.py builds the lists):

* decode, a handful of rows: every hit expert is a tile over the SAME
  rows (`x_of` all 0), so a tick reads each hit expert's matrices once
  and no other expert's;
* prefill, thousands of rows: the (token, choice) pairs are sorted by
  expert and each expert's run is padded to whole tiles, so a token is
  multiplied by its own experts only.

On the TPU it is one Mosaic kernel: grid (tile, slice of the experts'
hidden width: one slice where an expert's matrices fit the VMEM twice,
`hidden_slice`), the expert of a tile read from scalar memory by the
weights' index maps, so the pipeline fetches `W[expert_of[i + 1]]`
while tile i is multiplied, and fetches nothing where the next tile is
the same expert's; dead tiles repeat the last live tile's block index
and fetch nothing. Elsewhere (the CPU
tests, kernels switched off) `expert_tiles_reference` computes the same
thing with a `lax.map` over tiles.
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from elasticdl_tpu.ops.dispatch import interpret_mode, use_pallas

#: the kernel's name in a device trace (the scope around the call names
#: its HLO instruction: `moe_expert_tiles.N`)
KERNEL_SCOPE = "moe_expert_tiles"
#: Mosaic may use this much VMEM for the kernel: two buffers of an
#: expert's three matrices whole (23.6 MB at 2560 x 768) beside a
#: 256-row tile's rows, results and accumulator (13 MB) are over the
#: 16 MB default and far under a v5e core's 128 MiB
_VMEM_LIMIT = 64 * 1024 * 1024


def hidden_slice(hidden, expert_bytes):
    """Columns of the experts' hidden width a grid step multiplies.
    All of it where ONE expert's matrices (`expert_bytes`), twice for
    the pipeline's two buffers, fit half of the kernel's VMEM: the
    weights' block index is then the expert alone, so a second tile of
    the same expert repeats it and fetches nothing (sliced, the slice
    index runs inside the tile index and every tile streams its
    expert again: at 16-row tiles over runs of 64 rows the kernel took
    2.7 times as long, my chip run, PR 43). Else the widest of 512,
    256, 128 that divides it, else all of it."""
    if 2 * expert_bytes <= _VMEM_LIMIT // 2:
        return hidden
    for th in (512, 256, 128):
        if hidden % th == 0 and hidden > th:
            return th
    return hidden


def kernel_supported(tm, d, hidden, dtype):
    """Shape gate of the Mosaic kernel: whole (sublane, 128) tiles of
    the operand dtype. The hidden width goes through in slices that
    are multiples of 128 or whole, as the full extent of its axis (a
    width that no 128-multiple divides, 1856 = 29 x 64, always does),
    so it has to be whole sublanes of the down projection's rows.
    Every other shape takes the reference."""
    sublanes = 32 // jnp.dtype(dtype).itemsize  # 8 f32, 16 bf16
    return tm % sublanes == 0 and d % 128 == 0 and hidden % sublanes == 0


#: what a gated expert does to its gate product, by `activation`
_GATE_ACTS = {
    "reglu": lambda a: jnp.maximum(a, 0.0),
    "swiglu": jax.nn.silu,
}


def _tile_kernel(x_of_ref, expert_of_ref, n_live_ref, x_ref, g_ref,
                 wg_ref, wu_ref, wd_ref, out_ref, acc_ref, *, gate_act):
    """The gated three-matrix form: (act(x W_gate) * (x W_up)) W_down,
    `gate_act` relu (ReGLU) or silu (SwiGLU)."""
    i, j = pl.program_id(0), pl.program_id(1)
    live = i < n_live_ref[0]

    @pl.when(j == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _multiply():
        x = x_ref[...]
        a = jnp.dot(x, wg_ref[...], preferred_element_type=jnp.float32)
        u = jnp.dot(x, wu_ref[...], preferred_element_type=jnp.float32)
        act = (gate_act(a) * u).astype(x.dtype)
        acc_ref[...] += jnp.dot(act, wd_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _write():
        out_ref[...] = acc_ref[...] * g_ref[...]


def _tile_kernel_relu2(x_of_ref, expert_of_ref, n_live_ref, x_ref, g_ref,
                       wu_ref, wd_ref, out_ref, acc_ref):
    """The two-matrix form: relu(x W_up^T)^2 W_down, both [hidden, d]."""
    i, j = pl.program_id(0), pl.program_id(1)
    live = i < n_live_ref[0]

    @pl.when(j == 0)
    def _zero():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(live)
    def _multiply():
        x = x_ref[...]
        u = jnp.maximum(jax.lax.dot_general(
            x, wu_ref[...], (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32), 0.0)
        acc_ref[...] += jnp.dot((u * u).astype(x.dtype), wd_ref[...],
                                preferred_element_type=jnp.float32)

    @pl.when(j == pl.num_programs(1) - 1)
    def _write():
        out_ref[...] = acc_ref[...] * g_ref[...]


def _expert_tiles_kernel(x_tiles, x_of, gates, expert_of, n_live, *weights,
                         activation):
    n_tiles, tm = gates.shape[:2]
    hidden, d = weights[-1].shape[1:]
    th = hidden_slice(hidden, sum(w[0].size * w.dtype.itemsize
                                  for w in weights))
    n_h = hidden // th

    def slice_of(i, j, n_live_ref):
        # a dead tile asks for the block the last live step left behind
        return jnp.where(i < n_live_ref[0], j, n_h - 1)

    into_hidden = pl.BlockSpec(  # [d, hidden]: ReGLU's gate and up
        (None, d, th),
        lambda i, j, xo, eo, nl: (eo[i], 0, slice_of(i, j, nl)))
    from_hidden = pl.BlockSpec(  # [hidden, d]: a down, and relu^2's up
        (None, th, d),
        lambda i, j, xo, eo, nl: (eo[i], slice_of(i, j, nl), 0))
    in_specs = [
        pl.BlockSpec((None, tm, d),
                     lambda i, j, xo, eo, nl: (xo[i], 0, 0)),
        pl.BlockSpec((None, tm, 1), lambda i, j, xo, eo, nl: (i, 0, 0)),
    ] + ([into_hidden] * 2 if len(weights) == 3 else [from_hidden]) + [
        from_hidden]
    call = pl.pallas_call(
        functools.partial(_tile_kernel, gate_act=_GATE_ACTS[activation])
        if len(weights) == 3 else _tile_kernel_relu2,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,
            grid=(n_tiles, n_h),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                (None, tm, d), lambda i, j, xo, eo, nl: (i, 0, 0)),
            scratch_shapes=[pltpu.VMEM((tm, d), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_tiles, tm, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT,
        ),
        interpret=interpret_mode(),
    )
    with jax.named_scope(KERNEL_SCOPE):
        return call(x_of, expert_of, n_live.reshape(1), x_tiles, gates,
                    *weights)


def _activation(x, weights, dot, activation):
    """An expert's hidden activation, float32: ReGLU or SwiGLU of
    (W_gate, W_up) [d, hidden], or relu^2 of (W_up,) [hidden, d]."""
    if len(weights) == 2:
        return (_GATE_ACTS[activation](dot(x, weights[0]))
                * dot(x, weights[1]))
    return jnp.square(jnp.maximum(dot(x, weights[0].T), 0.0))


def expert_tiles_reference(x_tiles, x_of, gates, expert_of, n_live,
                           *weights, activation):
    """The same tiles in plain jax.numpy, one at a time."""
    dot = functools.partial(jnp.dot, preferred_element_type=jnp.float32)

    def tile(i):
        def live():
            x, e = x_tiles[x_of[i]], expert_of[i]
            act = _activation(x, [w[e] for w in weights[:-1]], dot,
                              activation)
            return dot(act.astype(x.dtype), weights[-1][e]) * gates[i]

        return jax.lax.cond(
            i < n_live, live,
            lambda: jnp.zeros(gates.shape[1:2] + weights[-1].shape[2:],
                              jnp.float32))

    return jax.lax.map(tile, jnp.arange(gates.shape[0]))


def expert_tiles(x_tiles, x_of, gates, expert_of, n_live, *weights,
                 use_kernel=None, activation=None):
    """float32 [n_tiles, tm, d]: tile i's weighted expert product,
    zeros from tile `n_live` on.

    x_tiles [n_x, tm, d]; x_of, expert_of [n_tiles] int32; gates
    [n_tiles, tm, 1] float32; n_live int32 scalar; `weights` in
    x_tiles' dtype, three for gated experts (w_gate, w_up [E, d,
    hidden], w_down [E, hidden, d]) or two for relu^2 experts (w_up,
    w_down, BOTH [E, hidden, d]). `activation` names the form: None
    takes it from the count (three: "reglu", two: "relu2"); "swiglu"
    is the gated form with silu. `use_kernel=None` takes the Mosaic
    kernel where kernels are on and the shapes are whole tiles
    (`kernel_supported`)."""
    tm, d = x_tiles.shape[1:]
    if len(weights) not in (2, 3):
        raise ValueError("an expert has two matrices (relu^2) or three "
                         "(ReGLU, SwiGLU), not %d" % len(weights))
    if activation is None:
        activation = "reglu" if len(weights) == 3 else "relu2"
    if (activation not in ("reglu", "swiglu", "relu2")
            or (activation == "relu2") != (len(weights) == 2)):
        raise ValueError("activation %r with %d matrices an expert"
                         % (activation, len(weights)))
    if use_kernel is None:
        use_kernel = use_pallas() and kernel_supported(
            tm, d, weights[-1].shape[1], x_tiles.dtype)
    fn = _expert_tiles_kernel if use_kernel else expert_tiles_reference
    return fn(x_tiles, jnp.asarray(x_of, jnp.int32), gates,
              jnp.asarray(expert_of, jnp.int32),
              jnp.asarray(n_live, jnp.int32), *weights,
              activation=activation)
