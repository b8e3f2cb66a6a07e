"""Plain float32 reference of the `transformer_lm` family: LayerNorm,
grouped-query causal attention with rotary positions and an optional
sliding window, GELU(tanh) MLP, untied head; mean token cross-entropy
and adamw. Straight `jax.numpy`, every matmul at `highest` precision,
no kernels, no cache, no batching tricks: attention is computed in
blocks of query rows and the head in blocks of rows only so that the
published widths fit beside nothing else on one chip.

It imports nothing of the program and takes nothing the program made:
weights come from `make_leaf` (seed + leaf path), which the harness
also uses to fill the program's parameter tree.

`quantize` is the control of `correct`: the same mathematics with both
operands of every dense matmul rounded to fp8 (e4m3, per-tensor scale,
straight-through gradient) — the nearest precision below the bf16 the
configurations state.
"""

import zlib

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
LN_EPS = 1e-6  # flax LayerNorm's default, which the program uses


# ------------------------------------------------------------- weights


def layer_leaves(cfg, i):
    """{path: (shape, kind)} of block i, paths as the program names
    its parameters."""
    d = cfg["embed_dim"]
    hd = d // cfg["num_heads"]
    hkv = cfg.get("num_kv_heads") or cfg["num_heads"]
    qkv = (cfg["num_heads"] + 2 * hkv) * hd
    b = "block_%d/" % i
    return {
        b + "LayerNorm_0/scale": ((d,), "scale"),
        b + "LayerNorm_0/bias": ((d,), "bias"),
        b + "attn/qkv/kernel": ((d, qkv), "qkv"),
        b + "attn/proj/kernel": ((cfg["num_heads"] * hd, d), "kernel"),
        b + "LayerNorm_1/scale": ((d,), "scale"),
        b + "LayerNorm_1/bias": ((d,), "bias"),
        b + "mlp_up/kernel": ((d, 4 * d), "kernel"),
        b + "mlp_up/bias": ((4 * d,), "bias"),
        b + "mlp_down/kernel": ((4 * d, d), "kernel"),
        b + "mlp_down/bias": ((d,), "bias"),
    }


def outer_leaves(cfg):
    d, v = cfg["embed_dim"], cfg["vocab_size"]
    return {
        "wte/embedding": ((v, d), "embed"),
        "ln_f/scale": ((d,), "scale"),
        "ln_f/bias": ((d,), "bias"),
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
    """One float32 parameter from its key: kernels N(0, 1/fan_in), the
    q and k columns of qkv widened by `qk_gain` so that attention is
    peaked enough to matter at random weights, norm scales near 1,
    biases small but not zero. `cfg` may be a tuple of items (static
    under jit)."""
    cfg = dict(cfg)
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "scale":
        return 1.0 + 0.1 * x
    if kind == "bias":
        return 0.02 * x
    if kind == "embed":
        return x * shape[1] ** -0.5
    x = x * shape[0] ** -0.5
    if kind == "qkv":
        hd = cfg["embed_dim"] // cfg["num_heads"]
        hkv = cfg.get("num_kv_heads") or cfg["num_heads"]
        qk_cols = (cfg["num_heads"] + hkv) * hd
        gain = jnp.where(jnp.arange(shape[1]) < qk_cols,
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
    q = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
    return x + jax.lax.stop_gradient(q - x)


def matmul_fp8(a, w):
    return jnp.matmul(_fp8(a), _fp8(w), precision=HIGHEST)


def _layer_norm(x, scale, bias):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


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
    (pos - window, pos]. Blocks of `rows` query rows at a time."""
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


def block_weights(w, i):
    """Block i's leaves under their names inside the block."""
    p = "block_%d/" % i
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def layer(cfg, w, x, mm=matmul, rows=512):
    """One block on x [b, l, D] float32; `w` = block_weights(...)."""
    p = ""
    b, l, _ = x.shape
    h = cfg["num_heads"]
    hkv = cfg.get("num_kv_heads") or h
    hd = cfg["embed_dim"] // h
    y = _layer_norm(x, w[p + "LayerNorm_0/scale"], w[p + "LayerNorm_0/bias"])
    qkv = mm(y, w[p + "attn/qkv/kernel"])
    q = qkv[..., :h * hd].reshape(b, l, h, hd).transpose(0, 2, 1, 3)
    k = qkv[..., h * hd:(h + hkv) * hd].reshape(b, l, hkv, hd)
    v = qkv[..., (h + hkv) * hd:].reshape(b, l, hkv, hd)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    theta = cfg.get("rope_theta", 10000.0)
    att = _attention(_rope(q, theta), _rope(k, theta), v,
                     cfg.get("attn_window", 0), rows)
    x = x + mm(att, w[p + "attn/proj/kernel"])
    y = _layer_norm(x, w[p + "LayerNorm_1/scale"], w[p + "LayerNorm_1/bias"])
    y = mm(y, w[p + "mlp_up/kernel"]) + w[p + "mlp_up/bias"]
    y = jax.nn.gelu(y, approximate=True)
    return x + mm(y, w[p + "mlp_down/kernel"]) + w[p + "mlp_down/bias"]


def embed(w, tokens):
    return w["wte/embedding"][tokens]


def head_logits(w, x, mm=matmul):
    """float32 logits of rows x [n, D]."""
    return mm(_layer_norm(x, w["ln_f/scale"], w["ln_f/bias"]),
              w["head/kernel"])


# ------------------------------------------------------------ training


def loss(cfg, w, tokens, labels, mm=matmul, rows=512, head_rows=1024):
    """Mean over sequences of the mean token cross-entropy, as the
    program's `loss` computes it for labels that are all valid."""
    x = embed(w, tokens)
    for i in range(cfg["num_layers"]):
        x = jax.checkpoint(
            lambda xx, ww: layer(cfg, ww, xx, mm, rows)
        )(x, block_weights(w, i))
    b, l, d = x.shape
    head_rows = min(head_rows, b * l)

    def chunk(args):
        xr, yr = args
        logits = head_logits(w, xr, mm)
        return jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, yr[:, None], -1)[:, 0]

    ce = jax.lax.map(
        jax.checkpoint(chunk),
        (x.reshape(-1, head_rows, d), labels.reshape(-1, head_rows)),
    )
    return ce.reshape(b, l).mean(-1).mean()


def adamw(w, grads, mu, nu, step, lr, weight_decay, b1=0.9, b2=0.999,
          eps=1e-8):
    """One decoupled-weight-decay Adam update; `step` counts from 1."""
    def one(p, g, m, n):
        m = b1 * m + (1 - b1) * g
        n = b2 * n + (1 - b2) * g * g
        mhat = m / (1 - b1 ** step)
        nhat = n / (1 - b2 ** step)
        p = p - lr * (mhat / (jnp.sqrt(nhat) + eps) + weight_decay * p)
        return p, m, n

    out = {k: one(w[k], grads[k], mu[k], nu[k]) for k in w}
    return ({k: v[0] for k, v in out.items()},
            {k: v[1] for k, v in out.items()},
            {k: v[2] for k, v in out.items()})
