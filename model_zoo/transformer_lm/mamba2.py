"""The Mamba-2 mixer: a layer of the stack that keeps, for a sequence,
a fixed-size state and no rows.

On u [b, l, D] (the layer's normed input; no bias but the
convolution's), with H heads of width P (inner width I = H P), G
groups of B and C, state width N and a causal depthwise convolution
of `conv` taps over the C = I + 2 G N channels of xBC:

    [z | xBC | dt] = u W_in                      W_in [D, 2 I + 2 G N + H]
    xBC_t = silu(b_c + sum_j w_c[:, j] xBC_{t-conv+1+j})   zeros before
                                                 the sequence
    xBC -> x [H, P], B [G, N], C [G, N]          head h reads group
                                                 h // (H / G)
    Δ_t = softplus(dt_t + dt_bias)   A = -exp(A_log)
    S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t    [H, P, N], float32
    y_t = S_t C_t + D_skip ⊙ x_t
    y   = GroupRMSNorm_G(y ⊙ silu(z)) ⊙ w_n      groups of I / G
    out = y W_out                                W_out [I, D]

The recurrence is ops/ssm.py's: the chunked form over a whole sequence
(the training forward and the prefill), one in-place update a token
for the decode step. What a sequence carries from token to token lives
in the `cache` collection, a per-sequence STATE and no rows: `state`
[b, H, P, N] float32 and `conv` [b, conv - 1, C], the last taps of xBC
before the convolution, in the compute dtype.

* prefill (`prompt_len`): the scan runs over the whole padded bucket
  and STOPS at the true prompt length: rows from `prompt_len` on get
  Δ = 0 and add nothing, and the tail kept is rows `prompt_len - conv
  + 1 .. prompt_len - 1`;
* decode: one token a sequence; a tile of several tokens would need
  the state of each position kept to roll back to, which nothing keeps
  yet, and is refused.

`A_log`, `D_skip`, `dt_bias` and the norm's scale are read as they are
(float32); the projections and the convolution are cast to the compute
dtype where they are used (and so are served as that cast,
serving/exec_weights.py).
"""

import jax
import jax.numpy as jnp
from flax import linen as nn

from elasticdl_tpu.ops.ssm import ssm_chunked_scan, ssm_state_update


class Mamba2Mixer(nn.Module):
    num_heads: int = 64
    head_dim: int = 64
    groups: int = 8
    state_dim: int = 128
    conv: int = 4
    chunk: int = 128
    norm_eps: float = 1e-5
    dtype: object = None

    @nn.compact
    def __call__(self, u, decode=False, prefill=False, prompt_len=None):
        b, l, d = u.shape
        h, p, g, n = (self.num_heads, self.head_dim, self.groups,
                      self.state_dim)
        inner, taps = h * p, self.conv - 1
        channels = inner + 2 * g * n
        dtype = self.dtype or u.dtype
        f32 = jnp.float32
        if h % g:
            raise ValueError("%d heads are no whole groups of %d"
                             % (h, g))
        with jax.named_scope("ssm_in_proj"):
            zxbcdt = nn.Dense(
                2 * inner + 2 * g * n + h, use_bias=False,
                dtype=self.dtype, name="in_proj",
                kernel_init=nn.initializers.lecun_normal())(u)
        z = zxbcdt[..., :inner]
        xbc = zxbcdt[..., inner:inner + channels]
        dt = zxbcdt[..., inner + channels:].astype(f32)
        w_c = jnp.asarray(self.param(
            "conv_kernel", nn.initializers.normal(self.conv ** -0.5),
            (channels, self.conv), f32), dtype).astype(f32)
        b_c = jnp.asarray(self.param(
            "conv_bias", nn.initializers.zeros, (channels,), f32),
            dtype).astype(f32)
        dt_bias = self.param("dt_bias", nn.initializers.zeros, (h,), f32)
        a = -jnp.exp(self.param("A_log", nn.initializers.zeros, (h,), f32))
        d_skip = self.param("D_skip", nn.initializers.ones, (h,), f32)
        carried = decode or prefill
        if carried:
            state = self.variable("cache", "state", jnp.zeros,
                                  (b, h, p, n), f32)
            tail = self.variable("cache", "conv", jnp.zeros,
                                 (b, taps, channels), dtype)
        if decode and l != 1:
            raise ValueError(
                "a state-space layer decodes one token a step: a tile "
                "of %d tokens needs the state of each position kept, "
                "which nothing keeps yet" % l)
        with jax.named_scope("ssm_conv"):
            before = (tail.value.astype(xbc.dtype) if decode
                      else jnp.zeros((b, taps, channels), xbc.dtype))
            padded = jnp.concatenate([before, xbc], axis=1)
            acc = b_c + sum(
                padded[:, j:j + l].astype(f32) * w_c[:, j]
                for j in range(self.conv))
            xbc_out = jax.nn.silu(acc).astype(dtype)
            if decode:
                tail.value = padded[:, 1:].astype(dtype)
            elif prefill:
                if prompt_len is None:
                    raise ValueError("prefill needs prompt_len")
                # rows prompt_len - taps .. prompt_len - 1 of xBC
                tail.value = jax.lax.dynamic_slice_in_dim(
                    padded, prompt_len, taps, axis=1).astype(dtype)
        x = xbc_out[..., :inner].reshape(b, l, h, p)
        b_in = xbc_out[..., inner:inner + g * n].reshape(b, l, g, n)
        c_in = xbc_out[..., inner + g * n:].reshape(b, l, g, n)
        delta = jax.nn.softplus(dt + dt_bias)  # [b, l, H] float32
        if decode:
            y, state.value = ssm_state_update(
                state.value, x[:, 0], delta[:, 0], a, b_in[:, 0],
                c_in[:, 0])
            y = y[:, None]
        else:
            if prefill:
                live = jnp.arange(l) < prompt_len
                delta = jnp.where(live[None, :, None], delta, 0.0)
            y, last = ssm_chunked_scan(x, delta, a, b_in, c_in,
                                       chunk=self.chunk)
            if prefill:
                state.value = last
        with jax.named_scope("ssm_out"):
            y = y + d_skip[:, None] * x.astype(f32)
            y = y.reshape(b, l, inner) * jax.nn.silu(z.astype(f32))
            grouped = y.reshape(b, l, g, inner // g)
            grouped = grouped * jax.lax.rsqrt(
                jnp.mean(jnp.square(grouped), axis=-1, keepdims=True)
                + self.norm_eps)
            scale = self.param("norm_scale", nn.initializers.ones,
                               (inner,), f32)
            y = (grouped.reshape(b, l, inner) * scale).astype(dtype)
            return nn.Dense(
                d, use_bias=False, dtype=self.dtype, name="out_proj",
                kernel_init=nn.initializers.lecun_normal())(y)
