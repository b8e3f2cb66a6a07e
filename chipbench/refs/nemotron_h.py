"""Plain float32 reference of the `nemotron_h` family
(NVIDIA-Nemotron-3-Nano-30B-A3B): a stack whose every layer is ONE
mixer behind one RMSNorm with one residual, x <- x + mixer_i(RMSNorm_i
(x)), the mixer by the layer's character in `layer_kinds`
(`hybrid_override_pattern`): `M` a Mamba-2 mixer, `E` a routed expert
layer with a shared expert, `*` grouped-query attention. Straight
`jax.numpy`, every matmul at `highest` precision, no kernel, no cache,
no batching, no chunking: the recurrence is a sequential `lax.scan`
over the tokens, attention in blocks of query rows, the experts one
after another, each over every token with the weight the router gave
it (zero for a token that did not choose it).

On u = RMSNorm_i(x) [T, D], eps `norm_eps`, no bias anywhere but the
convolution:

`M`, H heads of width P (inner I = H P), G groups, state N, K taps:
    [z | xBC | dt] = u W_in                 W_in [D, 2 I + 2 G N + H]
    xBC_t = silu(b_c + sum_{j<K} w_c[:, j] xBC_{t-K+1+j})   depthwise
                                            over I + 2 G N channels,
                                            zeros before the sequence
    xBC -> x [H, P], B [G, N], C [G, N]     head h reads group h // (H/G)
    Δ_t = softplus(dt_t + dt_bias)          [H]; no clamp: the config's
                                            time_step_* only initialise
                                            dt_bias
    A = -exp(A_log)                         [H]
    S_t = exp(Δ_t A) S_{t-1} + Δ_t x_t ⊗ B_t          [H, P, N]
    y_t = S_t C_t + D_skip ⊙ x_t
    y   = GroupRMSNorm_{G groups of I/G}(y ⊙ silu(z)) ⊙ w_n
    out = y W_out                           W_out [I, D]
`E`:
    s = sigmoid(u W_r)                      [moe_experts], float32
    the moe_top_k experts with the largest s + b_sel (n_group =
    topk_group = 1: no group limit); weights
    g = moe_route_scale * s_chosen / (sum s_chosen + 1e-20)
    out = sum over the chosen e HELD HERE of g_e relu(u W_up,e)^2 W_down,e
          + relu(u W_up,s)^2 W_down,s       the shared expert: every
                                            token, unweighted, once
    (a routed W_up is kept [hidden, D], a hidden unit a row, as the
    program and a checkpoint keep it, and multiplied as its transpose)
`*`:
    q D -> heads x head_dim, k and v D -> kv_heads x head_dim, causal
    softmax at scale head_dim^-1/2, o heads x head_dim -> D, and NO
    positional encoding (`assumed`).

Then RMSNorm and an untied head. `experts_held = [first, count]`: the
routed weights are those of experts first .. first + count of each
layer; the router keeps all its outputs, and what the experts held on
the other chips would add is left out
(chipbench/configs/nm3n-30b-serve.json, `deployment`). The vocabulary
is the slice the configuration states.

It imports nothing of the program and takes nothing the program made:
weights come from `make_leaf` (seed + leaf path), which the harness
also uses to fill the program's parameter tree; leaves are named as the
program names its parameters. What it shares with the `smallthinker`
reference (RMSNorm, attention in blocks, the fp8 rounding, a leaf's
key) it takes from there. `matmul_fp8` is the control of `correct`:
both operands of every product the configuration computes in bf16
(the projections, the experts, attention's, the head) rounded to fp8;
the router, the convolution and the recurrence stay as they are, as
the configuration keeps them in float32.
"""

import jax
import jax.numpy as jnp

from chipbench.refs.smallthinker import (  # noqa: F401
    HIGHEST,
    _attention,
    _rms_norm,
    block_weights,
    embed,
    leaf_key,
    matmul,
    matmul_fp8,
)

departures = [
    "experts_held of moe_experts routed experts a layer and a slice of "
    "the vocabulary: this chip's share of the deployment; the absent "
    "experts' part of each layer's result is left out; the shared "
    "expert is whole on every chip",
    "depth cut to a prefix of the published layer pattern",
    "serving only: the scan's backward and the expert kernel's are not "
    "built, so the family has no training cell",
]
assumed = [
    "attention applies no positional encoding: the Nemotron-H family "
    "applies none in its attention layers; the config's rope_theta / "
    "partial_rotary_factor keys are read by nothing",
    "the router reads the same normed input the experts multiply",
    "the state of the recurrence is float32 (the model card's serving "
    "setting for the SSM cache), the convolution's tail the compute "
    "dtype",
    "weights random from the seed: kernels N(0, 1/fan_in), q and k "
    "columns widened by qk_gain, embedding rows N(0, 1), router columns "
    "N(0, router_gain^2 / D); A_log = log U(1, 16); dt_bias the inverse "
    "softplus of Δ0 ~ logU(time_step_min, time_step_max) floored at "
    "time_step_floor; D_skip = 1; the selection bias N(0, "
    "sel_bias_std^2), so that selection and weighting differ; the "
    "convolution's taps N(0, 1/K) and its bias N(0, 0.1^2)",
]


# ------------------------------------------------------------- weights


def _attn_dims(cfg):
    d, h = cfg["embed_dim"], cfg["num_heads"]
    return d, h, cfg.get("num_kv_heads") or h, cfg.get("head_dim") or d // h


def _ssm_dims(cfg):
    """(H, P, G, N, K) of the Mamba-2 mixer."""
    return (cfg["ssm_heads"], cfg["ssm_head_dim"], cfg["ssm_groups"],
            cfg["ssm_state"], cfg["ssm_conv"])


def _held(cfg):
    first, count = cfg.get("experts_held") or (0, cfg["moe_experts"])
    return int(first), int(count)


def layer_leaves(cfg, i):
    """{path: (shape, kind)} of block i, paths as the program names
    its parameters."""
    d = cfg["embed_dim"]
    b = "block_%d/" % i
    leaves = {b + "RMSNorm_0/scale": ((d,), "scale")}
    kind = cfg["layer_kinds"][i]
    if kind == "M":
        h, p, g, n, k = _ssm_dims(cfg)
        inner, channels = h * p, h * p + 2 * g * n
        leaves.update({
            b + "ssm/in_proj/kernel": ((d, inner + channels + h), "kernel"),
            b + "ssm/conv_kernel": ((channels, k), "taps"),
            b + "ssm/conv_bias": ((channels,), "small"),
            b + "ssm/dt_bias": ((h,), "dt_bias"),
            b + "ssm/A_log": ((h,), "a_log"),
            b + "ssm/D_skip": ((h,), "one"),
            b + "ssm/norm_scale": ((inner,), "scale"),
            b + "ssm/out_proj/kernel": ((inner, d), "kernel"),
        })
    elif kind == "E":
        count, hidden = _held(cfg)[1], cfg["moe_hidden"]
        shared = cfg["moe_shared_hidden"]
        leaves.update({
            b + "moe/router": ((d, cfg["moe_experts"]), "router"),
            b + "moe/router_bias": ((cfg["moe_experts"],), "sel_bias"),
            # a hidden unit a row, both, as the program keeps them
            b + "moe/w_up": ((count, hidden, d), "experts_t"),
            b + "moe/w_down": ((count, hidden, d), "experts"),
            b + "moe/shared_up": ((d, shared), "kernel"),
            b + "moe/shared_down": ((shared, d), "kernel"),
        })
    elif kind == "*":
        _, h, hkv, hd = _attn_dims(cfg)
        leaves.update({
            b + "attn/qkv/kernel": ((d, (h + 2 * hkv) * hd), "qkv"),
            b + "attn/proj/kernel": ((h * hd, d), "kernel"),
        })
    else:
        raise ValueError("layer %d is of no kind: %r" % (i, kind))
    return leaves


def outer_leaves(cfg):
    d, v = cfg["embed_dim"], cfg["vocab_size"]
    return {
        "wte/embedding": ((v, d), "embed"),
        "ln_f/scale": ((d,), "scale"),
        "head/kernel": ((d, v), "kernel"),
    }


def all_leaves(cfg):
    leaves = dict(outer_leaves(cfg))
    for i in range(cfg["num_layers"]):
        leaves.update(layer_leaves(cfg, i))
    return leaves


def make_leaf(cfg, key, shape, kind):
    """One float32 parameter from its key (`assumed`, above). `cfg` is
    a tuple of the items `make_leaves` keeps (static under jit)."""
    cfg = dict(cfg)
    if kind == "one":
        return jnp.ones(shape, jnp.float32)
    if kind == "a_log":
        return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                          16.0))
    if kind == "dt_bias":
        lo, hi = cfg.get("time_step_min", 0.001), cfg.get(
            "time_step_max", 0.1)
        dt = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                        jnp.log(lo), jnp.log(hi)))
        dt = jnp.maximum(dt, cfg.get("time_step_floor", 1e-4))
        return dt + jnp.log(-jnp.expm1(-dt))  # softplus^-1(dt)
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "scale":
        return 1.0 + 0.1 * x
    if kind == "embed":
        return x
    if kind == "small":
        return 0.1 * x
    if kind == "sel_bias":
        return cfg.get("sel_bias_std", 0.1) * x
    if kind == "taps":  # [channels, K]
        return x * shape[1] ** -0.5
    if kind == "experts":  # [count, fan_in, fan_out]
        return x * shape[1] ** -0.5
    if kind == "experts_t":  # [count, fan_out, fan_in]
        return x * shape[2] ** -0.5
    x = x * shape[0] ** -0.5
    if kind == "router":
        return x * cfg.get("router_gain", 1.0)
    if kind == "qkv":
        _, h, hkv, hd = _attn_dims(cfg)
        gain = jnp.where(jnp.arange(shape[1]) < (h + hkv) * hd,
                         cfg.get("qk_gain", 1.0), 1.0)
        x = x * gain
    return x


_MAKE = jax.jit(make_leaf, static_argnums=(0, 2, 3))


def make_leaves(cfg, seed, leaves):
    """{path: float32 array} on the default device, one small jitted
    call per leaf (one compile per distinct shape and kind)."""
    frozen = tuple(sorted((k, v) for k, v in cfg.items()
                          if isinstance(v, (int, float, str))))
    return {p: _MAKE(frozen, leaf_key(seed, p), tuple(s), k)
            for p, (s, k) in leaves.items()}


# ------------------------------------------------------------- forward


def ssm_scan(x, delta, a, b_in, c_in):
    """The recurrence, token by token: x [l, H, P], delta [l, H], a [H],
    b_in, c_in [l, G, N] -> y [l, H, P] (without the skip term), from a
    zero state [H, P, N]."""
    h, p = x.shape[1:]
    g, n = b_in.shape[1:]

    def step(s, inputs):
        x_t, d_t, b_t, c_t = inputs
        b_h = jnp.repeat(b_t, h // g, axis=0)  # [H, N]
        c_h = jnp.repeat(c_t, h // g, axis=0)
        s = (jnp.exp(d_t * a)[:, None, None] * s
             + (d_t[:, None] * x_t)[:, :, None] * b_h[:, None, :])
        return s, jnp.sum(s * c_h[:, None, :], axis=-1)

    _, y = jax.lax.scan(step, jnp.zeros((h, p, n), jnp.float32),
                        (x, delta, b_in, c_in))
    return y


def mamba(cfg, w, u, mm=matmul):
    """The Mamba-2 mixer on u [l, D] (one sequence)."""
    h, p, g, n, k = _ssm_dims(cfg)
    inner = h * p
    channels = inner + 2 * g * n
    l = u.shape[0]
    zxbcdt = mm(u, w["ssm/in_proj/kernel"])
    z = zxbcdt[:, :inner]
    xbc = zxbcdt[:, inner:inner + channels]
    dt = zxbcdt[:, inner + channels:]
    padded = jnp.concatenate(
        [jnp.zeros((k - 1, channels), jnp.float32), xbc])
    xbc = jax.nn.silu(w["ssm/conv_bias"] + sum(
        padded[j:j + l] * w["ssm/conv_kernel"][:, j] for j in range(k)))
    x = xbc[:, :inner].reshape(l, h, p)
    b_in = xbc[:, inner:inner + g * n].reshape(l, g, n)
    c_in = xbc[:, inner + g * n:].reshape(l, g, n)
    delta = jax.nn.softplus(dt + w["ssm/dt_bias"])
    y = ssm_scan(x, delta, -jnp.exp(w["ssm/A_log"]), b_in, c_in)
    y = y + w["ssm/D_skip"][:, None] * x
    y = y.reshape(l, inner) * jax.nn.silu(z)
    grouped = y.reshape(l, g, inner // g)
    grouped = grouped * jax.lax.rsqrt(
        jnp.square(grouped).mean(-1, keepdims=True)
        + cfg.get("norm_eps", 1e-5))
    return mm(grouped.reshape(l, inner) * w["ssm/norm_scale"],
              w["ssm/out_proj/kernel"])


def router_weights(cfg, w, u):
    """[T, moe_experts] float32: a token's weight at each of its
    chosen experts, 0 elsewhere. The router is float32 in the
    configuration and stays so under the control."""
    scores = jax.nn.sigmoid(matmul(u, w["moe/router"]))
    _, top_i = jax.lax.top_k(scores + w["moe/router_bias"],
                             cfg["moe_top_k"])
    chosen = jnp.any(top_i[..., None] == jnp.arange(scores.shape[-1]),
                     axis=-2)
    picked = jnp.where(chosen, scores, 0.0)
    return cfg.get("moe_route_scale", 1.0) * picked / (
        picked.sum(-1, keepdims=True) + 1e-20)


def routed_experts(cfg, w, u, weights, mm=matmul):
    """sum over the routed experts held here of weight * relu^2 expert;
    u [T, D], weights [T, moe_experts]."""
    first, count = _held(cfg)

    def one(y, e):
        wu, wd = (jax.lax.dynamic_index_in_dim(w[name], e, 0, False)
                  for name in ("moe/w_up", "moe/w_down"))
        out = mm(jnp.square(jax.nn.relu(mm(u, wu.T))), wd)
        g = jax.lax.dynamic_index_in_dim(weights, first + e, 1, True)
        return y + g * out, None

    return jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(count))[0]


def shared_expert(w, u, mm=matmul):
    return mm(jnp.square(jax.nn.relu(mm(u, w["moe/shared_up"]))),
              w["moe/shared_down"])


def attention(cfg, w, u, mm=matmul, rows=512):
    """u [b, l, D]: causal grouped-query attention with no positional
    encoding."""
    b, l, _ = u.shape
    _, h, hkv, hd = _attn_dims(cfg)
    qkv = mm(u, w["attn/qkv/kernel"])
    q = qkv[..., :h * hd].reshape(b, l, h, hd).transpose(0, 2, 1, 3)
    k = qkv[..., h * hd:(h + hkv) * hd].reshape(b, l, hkv, hd)
    v = qkv[..., (h + hkv) * hd:].reshape(b, l, hkv, hd)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    return mm(_attention(q, k, v, 0, rows), w["attn/proj/kernel"])


def layer(cfg, w, x, mm=matmul, rows=512, i=0):
    """Block `i` on x [b, l, D] float32; `w` = block_weights(...). The
    index tells the kinds of layer apart (`layer_kinds`)."""
    b, l, d = x.shape
    u = _rms_norm(x, w["RMSNorm_0/scale"], cfg.get("norm_eps", 1e-5))
    kind = cfg["layer_kinds"][i]
    if kind == "M":
        return x + jnp.stack([mamba(cfg, w, u[j], mm) for j in range(b)])
    if kind == "*":
        return x + attention(cfg, w, u, mm, rows)
    u = u.reshape(b * l, d)
    out = (routed_experts(cfg, w, u, router_weights(cfg, w, u), mm)
           + shared_expert(w, u, mm))
    return x + out.reshape(b, l, d)


def head_logits(w, x, mm=matmul, eps=1e-5):
    """float32 logits of rows x [n, D] (the family's eps)."""
    return mm(_rms_norm(x, w["ln_f/scale"], eps), w["head/kernel"])


def forward(cfg, w, tokens, mm=matmul, rows=512):
    """float32 logits [b, l, vocab] of tokens [b, l] (l a multiple of
    `rows` or shorter): the whole model, for the tests."""
    x = embed(w, tokens)
    for i in range(cfg["num_layers"]):
        x = layer(cfg, block_weights(w, i), x, mm, rows, i)
    b, l, d = x.shape
    return head_logits(w, x.reshape(b * l, d), mm).reshape(b, l, -1)
