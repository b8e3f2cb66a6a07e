"""Plain float32 reference of the `afmoe` family (Trinity-Large-Preview,
Arcee): RMSNorm in SANDWICH form (a norm on each sublayer's input and
another on its output, before the residual add), grouped-query
attention with a per-head RMSNorm of q and of k and an output GATE, of
two kinds by layer (rotary over a sliding window; no positional
encoding over every earlier key), a leading dense SwiGLU layer, and
then layers of sigmoid-routed SwiGLU experts beside one shared expert.
Straight `jax.numpy`, every matmul at `highest` precision, no kernel,
no cache, no batching: attention in blocks of query rows over every
key, the experts one after another, each over every token with the
weight the router gave it (zero for a token that did not choose it).

The layer equations, from the published config's keys; what those keys
do not state is marked (A) and listed in `assumed`. D = embed_dim;
RMSNorm with a learned gain everywhere; no bias anywhere:

    x0 = E[tokens] * sqrt(D)                  mup_enabled (A: the
                                              multiplier is sqrt(hidden))
    layer i (0-based; window_layout[i] == 1 "sliding", else "full";
    the published pattern is full iff (i + 1) % 4 == 0):
      h  = norm_in(x)
      q  = h Wq [heads x head_dim]   k = h Wk, v = h Wv [kv_heads x
      head_dim]   g = h Wg [heads x head_dim]  (A: gate_proj is hidden
                                              -> heads x head_dim)
      q  = headnorm_q(q), k = headnorm_k(k)   RMSNorm over head_dim, one
                                              gain [head_dim] each (A)
      sliding: q, k = rope(q, k, theta); row p sees keys (p - window, p]
      full:    no positional encoding (A: NoPE on the global layers);
               row p sees keys [0, p]
      a  = softmax(q k^T / sqrt(head_dim)) v  GQA
      y  = (a * sigmoid(g)) Wo                the gate, elementwise,
                                              before the output projection
      x  = x + norm_post_attn(y)              sandwich (A: the four norm
                                              sites as in the family's
                                              published code)
      h  = norm_pre_mlp(x)
      mlp_layout[i] == 0 (the leading dense layers):
            m = (silu(h Wgate) * (h Wup)) Wdown         width dense_hidden
      else: s = sigmoid(h Wr) [moe_experts], float32    score_func sigmoid
            chosen = top_k(s + b)             b: expert_bias, a buffer,
                                              selection only (A)
            w = s[chosen]; w = w / sum(w) (route_norm);
            w = w * moe_route_scale (route_scale)
            m = shared(h) + sum over the chosen e HELD HERE of
                w_e * expert_e(h)             each SwiGLU of moe_hidden;
                                              one shared expert, every token
      x  = x + norm_post_mlp(m)
    logits = norm_f(x) W_head                 untied

"Depth-scaled sandwich norm" in the family's description is an
initialisation of the gains; at inference the norms are plain RMSNorms.
n_group 1 / topk_group 1: no group-limited selection.

`experts_held = [first, count]`: the weights are those of experts first
.. first + count of each expert layer; the router keeps all its
outputs, and what the experts held on the other chips would add to `m`
is left out BEFORE norm_post_mlp (chipbench/configs/
trinity-large-serve.json, `deployment`): `expert_mlp(..., shared=False)`
is one chip's routed part, and the parts of all the shares plus the
shared expert once are the uncut layer's `m`. The vocabulary is the
slice the configuration states.

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
    "experts_held of moe_experts experts a layer and a slice of the "
    "vocabulary: this chip's share of the deployment; the absent "
    "experts' part of each expert layer's result is left out before "
    "the layer's output norm",
    "depth cut to one leading dense layer and whole periods of the "
    "pattern [sliding, sliding, sliding, full] of expert layers",
]
assumed = [
    "mup_enabled: the embedding is multiplied by sqrt(hidden_size)",
    "gate_proj is hidden -> heads x head_dim without bias; its sigmoid "
    "multiplies the heads' output elementwise before o_proj",
    "q and k are RMS-normed over head_dim, one gain [head_dim] each, "
    "before the rotary",
    "the full-attention layers carry no positional encoding (NoPE); "
    "the sliding layers rotate over all of head_dim, theta rope_theta",
    "a sliding layer sees keys in (pos - sliding_window, pos]",
    "four norm sites a layer: on the input and on the output of "
    "attention and of the MLP slot, the output's before the residual "
    "add",
    "expert_bias is a buffer added to the scores for SELECTION only; "
    "the weights are the chosen scores, renormalised, times route_scale",
    "no bias on any projection",
    "weights random from the seed: kernels N(0, 1/fan_in), embedding "
    "rows N(0, 1/D) so that after the sqrt(D) multiplier the residual "
    "stream has unit scale, norm gains 1 + 0.1 N(0, 1) on a sublayer's "
    "input and 1 on its output, the q and k head gains times qk_gain "
    "(a head norm undoes a gain on the columns), router columns "
    "N(0, router_gain^2 / D), expert_bias N(0, sel_bias_std^2) of the "
    "scores' own spread so that selection and weight differ",
]


# ------------------------------------------------------------- weights


def _dims(cfg):
    d, h = cfg["embed_dim"], cfg["num_heads"]
    hkv = cfg.get("num_kv_heads") or h
    return d, h, hkv, cfg.get("head_dim") or d // h


def _held(cfg):
    first, count = cfg.get("experts_held") or (0, cfg["moe_experts"])
    return int(first), int(count)


def _layout(cfg, name, i):
    return (cfg.get(name) or [1] * cfg["num_layers"])[i]


def layer_kind(cfg, i):
    """(rotary theta or 0, window or 0, dense MLP?) of layer i."""
    return (cfg.get("rope_theta", 10000.0)
            if _layout(cfg, "rope_layout", i) else 0,
            cfg.get("attn_window", 0)
            if _layout(cfg, "window_layout", i) else 0,
            not _layout(cfg, "mlp_layout", i))


def layer_leaves(cfg, i):
    """{path: (shape, kind)} of block i, paths as the program names
    its parameters."""
    d, h, hkv, hd = _dims(cfg)
    b = "block_%d/" % i
    leaves = {
        b + "RMSNorm_0/scale": ((d,), "scale"),
        b + "attn/qkv/kernel": ((d, (h + 2 * hkv) * hd), "kernel"),
        b + "attn/gate/kernel": ((d, h * hd), "kernel"),
        b + "attn/q_norm/scale": ((hd,), "qk_scale"),
        b + "attn/k_norm/scale": ((hd,), "qk_scale"),
        b + "attn/proj/kernel": ((h * hd, d), "kernel"),
        b + "post_attn_norm/scale": ((d,), "one"),
        b + "RMSNorm_1/scale": ((d,), "scale"),
        b + "post_mlp_norm/scale": ((d,), "one"),
    }
    if layer_kind(cfg, i)[2]:
        wide = cfg["dense_hidden"]
        leaves.update({
            b + "mlp_gate/kernel": ((d, wide), "kernel"),
            b + "mlp_up/kernel": ((d, wide), "kernel"),
            b + "mlp_down/kernel": ((wide, d), "kernel"),
        })
        return leaves
    count, hidden = _held(cfg)[1], cfg["moe_hidden"]
    shared = cfg["moe_shared_hidden"]
    leaves.update({
        b + "moe/router": ((d, cfg["moe_experts"]), "router"),
        b + "moe/router_bias": ((cfg["moe_experts"],), "sel_bias"),
        b + "moe/w_gate": ((count, d, hidden), "experts"),
        b + "moe/w_up": ((count, d, hidden), "experts"),
        b + "moe/w_down": ((count, hidden, d), "experts"),
        b + "moe/shared_gate": ((d, shared), "kernel"),
        b + "moe/shared_up": ((d, shared), "kernel"),
        b + "moe/shared_down": ((shared, d), "kernel"),
    })
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


def leaf_key(seed, path):
    return jax.random.fold_in(
        jax.random.PRNGKey(seed), zlib.crc32(path.encode()) & 0x7FFFFFFF
    )


def make_leaf(cfg, key, shape, kind):
    """One float32 parameter from its key (`assumed`, above). `cfg` is
    a tuple of the items `make_leaves` keeps (static under jit)."""
    cfg = dict(cfg)
    if kind == "one":
        return jnp.ones(shape, jnp.float32)
    x = jax.random.normal(key, shape, jnp.float32)
    if kind == "scale":
        return 1.0 + 0.1 * x
    if kind == "qk_scale":
        return (1.0 + 0.1 * x) * cfg.get("qk_gain", 1.0)
    if kind == "embed":  # [vocab, D]: unit rows after the multiplier
        return x * shape[1] ** -0.5
    if kind == "sel_bias":
        return cfg.get("sel_bias_std", 0.2) * x
    if kind == "experts":  # [count, fan_in, fan_out]
        return x * shape[1] ** -0.5
    x = x * shape[0] ** -0.5
    if kind == "router":
        return x * cfg.get("router_gain", 1.0)
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
    query rows at a time, halved while a block's scores (rows x length
    a head) pass 2^22: at 33k keys a block of 512 rows would be 3 GB."""
    b, h, l, d = q.shape
    hkv = k.shape[1]
    rows = min(rows, l)
    if l % rows:
        raise ValueError("length %d is not a multiple of %d" % (l, rows))
    while rows % 2 == 0 and rows > 64 and rows * l > 1 << 22:
        rows //= 2
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


def attention(cfg, w, y, mm=matmul, rows=512, i=0):
    """Layer i's attention sublayer on its normed input y [b, l, D],
    before the output's norm: (a * sigmoid(g)) Wo."""
    b, l, _ = y.shape
    _, h, hkv, hd = _dims(cfg)
    eps = cfg.get("norm_eps", 1e-6)
    theta, window, _dense = layer_kind(cfg, i)
    qkv = mm(y, w["attn/qkv/kernel"])
    q = qkv[..., :h * hd].reshape(b, l, h, hd).transpose(0, 2, 1, 3)
    k = qkv[..., h * hd:(h + hkv) * hd].reshape(b, l, hkv, hd)
    v = qkv[..., (h + hkv) * hd:].reshape(b, l, hkv, hd)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    q = _rms_norm(q, w["attn/q_norm/scale"], eps)
    k = _rms_norm(k, w["attn/k_norm/scale"], eps)
    if theta:
        q, k = _rope(q, theta), _rope(k, theta)
    a = _attention(q, k, v, window, rows)
    gate = jax.nn.sigmoid(mm(y, w["attn/gate/kernel"]))
    return mm(a * gate, w["attn/proj/kernel"])


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


def _swiglu(u, gate, up, down, mm):
    return mm(jax.nn.silu(mm(u, gate)) * mm(u, up), down)


def routed_experts(cfg, w, u, weights, mm=matmul):
    """sum over the routed experts held here of weight * SwiGLU expert;
    u [T, D], weights [T, moe_experts]."""
    first, count = _held(cfg)

    def one(y, e):
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(w[name], e, 0, False)
                      for name in ("moe/w_gate", "moe/w_up", "moe/w_down"))
        g = jax.lax.dynamic_index_in_dim(weights, first + e, 1, True)
        return y + g * _swiglu(u, wg, wu, wd, mm), None

    return jax.lax.scan(one, jnp.zeros_like(u), jnp.arange(count))[0]


def shared_expert(w, u, mm=matmul):
    return _swiglu(u, w["moe/shared_gate"], w["moe/shared_up"],
                   w["moe/shared_down"], mm)


def expert_mlp(cfg, w, u, mm=matmul, shared=True):
    """An expert layer's `m` on u [T, D], before the output's norm: the
    held experts' routed part, and the shared expert (`shared`: every
    chip computes it alike, so the shares of a layer count it once)."""
    m = routed_experts(cfg, w, u, router_weights(cfg, w, u), mm)
    return m + shared_expert(w, u, mm) if shared else m


def block_weights(w, i):
    """Block i's leaves under their names inside the block."""
    p = "block_%d/" % i
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def layer(cfg, w, x, mm=matmul, rows=512, i=0):
    """Block `i` on x [b, l, D] float32; `w` = block_weights(...). The
    index tells sliding from full and dense from expert (`layer_kind`)."""
    b, l, d = x.shape
    eps = cfg.get("norm_eps", 1e-6)
    y = attention(cfg, w, _rms_norm(x, w["RMSNorm_0/scale"], eps), mm,
                  rows, i)
    x = x + _rms_norm(y, w["post_attn_norm/scale"], eps)
    u = _rms_norm(x, w["RMSNorm_1/scale"], eps).reshape(b * l, d)
    if layer_kind(cfg, i)[2]:
        m = _swiglu(u, w["mlp_gate/kernel"], w["mlp_up/kernel"],
                    w["mlp_down/kernel"], mm)
    else:
        m = expert_mlp(cfg, w, u, mm)
    return x + _rms_norm(m.reshape(b, l, d), w["post_mlp_norm/scale"], eps)


def embed(w, tokens):
    table = w["wte/embedding"]
    return table[tokens] * table.shape[1] ** 0.5


def head_logits(w, x, mm=matmul, eps=1e-5):
    """float32 logits of rows x [n, D]."""
    return mm(_rms_norm(x, w["ln_f/scale"], eps), w["head/kernel"])


def forward(cfg, w, tokens, mm=matmul, rows=512):
    """float32 logits [b, l, vocab] of tokens [b, l] (l a multiple of
    `rows` or shorter): the whole model, for the tests."""
    x = embed(w, tokens)
    for i in range(cfg["num_layers"]):
        x = layer(cfg, block_weights(w, i), x, mm, rows, i)
    b, l, d = x.shape
    return head_logits(w, x.reshape(b * l, d), mm,
                       cfg.get("norm_eps", 1e-5)).reshape(b, l, -1)
