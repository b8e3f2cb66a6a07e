"""Plain float32 reference of the `smallthinker` family
(SmallThinker-21BA3B-Instruct, PowerInfer): RMSNorm, grouped-query
causal attention of two kinds by layer, and in every layer a routed
expert feed-forward fed by the layer's own input. Straight `jax.numpy`,
every matmul at `highest` precision, no kernel, no cache, no batching:
attention in blocks of query rows, the experts one after another, each
over every token with the weight the router gave it (zero for a token
that did not choose it).

Layer i of the stack, on x [T, D] (all matmuls without bias):

    a  = Attn_i(RMSNorm(x))     q D -> heads x head_dim, k and v D ->
                                kv_heads x head_dim, scale head_dim^-1/2,
                                causal, o heads x head_dim -> D.
                                rope_layout[i] == 1: rotary over all of
                                head_dim, theta rope_theta.
                                window_layout[i] == 1: keys in
                                (pos - attn_window, pos] only.
                                Both 0: no positional encoding and every
                                earlier key ("NoPE global").
    h  = RMSNorm(x + a)
    l  = x W_r                  float32, W_r [D, moe_experts]: the router
                                reads the layer's INPUT
    top moe_top_k of l; weights = softmax over the chosen logits
    y_e = (relu(h W_gate,e) * (h W_up,e)) W_down,e            ("ReGLU")
    x' = x + a + sum over the chosen e HELD HERE of g_e y_e

then RMSNorm and an untied head. `experts_held = [first, count]`: the
weights are those of experts first .. first + count of each layer; the
router keeps all its outputs, and what the experts held on the other
chips would add is left out (chipbench/configs/st21b-serve.json,
`deployment`). The vocabulary is the slice the configuration states.

It imports nothing of the program and takes nothing the program made:
weights come from `make_leaf` (seed + leaf path), which the harness
also uses to fill the program's parameter tree; leaves are named as the
program names its parameters. `matmul_fp8` is the control of `correct`:
both operands of every product the configuration computes in bf16
rounded to fp8 (e4m3, per-tensor scale); the router stays as it is, as
the configuration keeps it in float32.

`departures`: what is not as the model's description has it;
`assumed`: what this reference's author set (the configuration file
carries both lists too).
"""

import zlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST

departures = [
    "primary experts only: the family's secondary experts have no key "
    "in the published config and are not built",
    "experts_held of moe_experts experts a layer and a slice of the "
    "vocabulary: this chip's share of the deployment; the absent "
    "experts' part of each layer's result is left out",
    "depth cut to whole periods of the layer pattern [global, window, "
    "window, window]",
]
assumed = [
    "the router reads the layer's input as it is, not normalised",
    "a window layer sees keys in (pos - attn_window, pos]",
    "no bias on any projection",
    "weights random from the seed: kernels N(0, 1/fan_in), q and k "
    "columns widened by qk_gain, embedding rows N(0, 1) so that the "
    "residual stream the router reads has unit scale, router columns "
    "N(0, router_gain^2 / D): the six weights of a token are not flat",
]


# ------------------------------------------------------------- weights


def _dims(cfg):
    d, h = cfg["embed_dim"], cfg["num_heads"]
    hkv = cfg.get("num_kv_heads") or h
    return d, h, hkv, cfg.get("head_dim") or d // h


def _held(cfg):
    first, count = cfg.get("experts_held") or (0, cfg["moe_experts"])
    return int(first), int(count)


def layer_leaves(cfg, i):
    """{path: (shape, kind)} of block i, paths as the program names
    its parameters."""
    d, h, hkv, hd = _dims(cfg)
    count, hidden = _held(cfg)[1], cfg["moe_hidden"]
    b = "block_%d/" % i
    return {
        b + "RMSNorm_0/scale": ((d,), "scale"),
        b + "attn/qkv/kernel": ((d, (h + 2 * hkv) * hd), "qkv"),
        b + "attn/proj/kernel": ((h * hd, d), "kernel"),
        b + "RMSNorm_1/scale": ((d,), "scale"),
        b + "moe/router": ((d, cfg["moe_experts"]), "router"),
        b + "moe/w_gate": ((count, d, hidden), "experts"),
        b + "moe/w_up": ((count, d, hidden), "experts"),
        b + "moe/w_down": ((count, hidden, d), "experts"),
    }


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


def leaf_key(seed, path):
    return jax.random.fold_in(
        jax.random.PRNGKey(seed), zlib.crc32(path.encode()) & 0x7FFFFFFF
    )


def make_leaf(cfg, key, shape, kind):
    """One float32 parameter from its key (`assumed`, above). `cfg` is
    a tuple of the items `make_leaves` keeps (static under jit)."""
    cfg = dict(cfg)
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "scale":
        return 1.0 + 0.1 * x
    if kind == "embed":
        return x
    if kind == "experts":  # [count, fan_in, fan_out]
        return x * shape[1] ** -0.5
    x = x * shape[0] ** -0.5
    if kind == "router":
        return x * cfg.get("router_gain", 1.0)
    if kind == "qkv":
        _, h, hkv, hd = _dims(cfg)
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


def matmul(a, w):
    return jnp.matmul(a, w, precision=HIGHEST)


def _fp8(x):
    scale = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def matmul_fp8(a, w):
    return jnp.matmul(_fp8(a), _fp8(w), precision=HIGHEST)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.square(x).mean(-1, keepdims=True)
                             + eps) * scale


def _rope(x, theta):
    """x [b, h, l, d]: rotate feature pairs (i, i + d/2) by
    pos * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(x.shape[2], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _attention(q, k, v, window, rows):
    """q [b, h, l, d], k/v [b, hkv, l, d]; causal, keys in
    (pos - window, pos] (window 0: every earlier key). Blocks of `rows`
    query rows at a time."""
    b, h, l, d = q.shape
    hkv = k.shape[1]
    rows = min(rows, l)
    if l % rows:
        raise ValueError("length %d is not a multiple of %d" % (l, rows))
    qg = q.reshape(b, hkv, h // hkv, l, d)
    kpos = jnp.arange(l)

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qg, i * rows, rows, axis=3)
        s = jnp.einsum("bkgqd,bkld->bkgql", qi, k, precision=HIGHEST)
        s = s * d ** -0.5
        qpos = i * rows + jnp.arange(rows)
        ok = kpos[None, :] <= qpos[:, None]
        if window:
            ok = ok & (kpos[None, :] > qpos[:, None] - window)
        w = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
        return jnp.einsum("bkgql,bkld->bkgqd", w, v, precision=HIGHEST)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(l // rows))
    # [n, b, hkv, g, rows, d] -> [b, l, h * d]
    out = out.transpose(1, 0, 4, 2, 3, 5)
    return out.reshape(b, l, h * d)


def layer_kind(cfg, i):
    """(rotary, window) of layer i: (theta or 0, window or 0)."""
    rotary = (cfg.get("rope_layout") or [1] * cfg["num_layers"])[i]
    windowed = (cfg.get("window_layout") or [1] * cfg["num_layers"])[i]
    return (cfg.get("rope_theta", 10000.0) if rotary else 0,
            cfg.get("attn_window", 0) if windowed else 0)


def router_weights(cfg, w, x):
    """[T, moe_experts] float32: the softmax over a token's top
    `moe_top_k` router logits at the chosen experts, 0 elsewhere. The
    router is float32 in the configuration and stays so under the
    control."""
    logits = matmul(x, w["moe/router"])
    top_v, top_i = jax.lax.top_k(logits, cfg["moe_top_k"])
    gates = jax.nn.softmax(top_v, axis=-1)
    chosen = top_i[..., None] == jnp.arange(logits.shape[-1])
    return jnp.sum(jnp.where(chosen, gates[..., None], 0.0), axis=-2)


def experts(cfg, w, h, weights, mm=matmul):
    """sum over the experts held here of weight * ReGLU expert; h
    [T, D], weights [T, moe_experts]."""
    first, count = _held(cfg)

    def one(y, e):
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(w[name], e, 0, False)
                      for name in ("moe/w_gate", "moe/w_up", "moe/w_down"))
        out = mm(jax.nn.relu(mm(h, wg)) * mm(h, wu), wd)
        g = jax.lax.dynamic_index_in_dim(weights, first + e, 1, True)
        return y + g * out, None

    return jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(count))[0]


def block_weights(w, i):
    """Block i's leaves under their names inside the block."""
    p = "block_%d/" % i
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def layer(cfg, w, x, mm=matmul, rows=512, i=0):
    """Block `i` on x [b, l, D] float32; `w` = block_weights(...). The
    index tells the two kinds of layer apart (`layer_kind`)."""
    b, l, d = x.shape
    _, h, hkv, hd = _dims(cfg)
    eps = cfg.get("norm_eps", 1e-6)
    theta, window = layer_kind(cfg, i)
    y = _rms_norm(x, w["RMSNorm_0/scale"], eps)
    qkv = mm(y, w["attn/qkv/kernel"])
    q = qkv[..., :h * hd].reshape(b, l, h, hd).transpose(0, 2, 1, 3)
    k = qkv[..., h * hd:(h + hkv) * hd].reshape(b, l, hkv, hd)
    v = qkv[..., (h + hkv) * hd:].reshape(b, l, hkv, hd)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    if theta:
        q, k = _rope(q, theta), _rope(k, theta)
    a = mm(_attention(q, k, v, window, rows), w["attn/proj/kernel"])
    hidden = _rms_norm(x + a, w["RMSNorm_1/scale"], eps)
    weights = router_weights(cfg, w, x.reshape(b * l, d))
    y = experts(cfg, w, hidden.reshape(b * l, d), weights, mm)
    return x + a + y.reshape(b, l, d)


def embed(w, tokens):
    return w["wte/embedding"][tokens]


def head_logits(w, x, mm=matmul, eps=1e-6):
    """float32 logits of rows x [n, D]."""
    return mm(_rms_norm(x, w["ln_f/scale"], eps), w["head/kernel"])


def forward(cfg, w, tokens, mm=matmul, rows=512):
    """float32 logits [b, l, vocab] of tokens [b, l] (l a multiple of
    `rows` or shorter): the whole model, for the tests."""
    x = embed(w, tokens)
    for i in range(cfg["num_layers"]):
        x = layer(cfg, block_weights(w, i), x, mm, rows, i)
    b, l, d = x.shape
    return head_logits(w, x.reshape(b * l, d), mm).reshape(b, l, -1)
