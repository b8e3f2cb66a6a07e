"""Plain float32 reference of the `sdar_moe` family (SDAR-30B-A3B-Chat,
JetLM): RMSNorm, grouped-query attention with a per-head RMSNorm of q
and of k, BLOCK-CAUSAL over blocks of `block_causal` positions, and in
every layer a routed SwiGLU expert feed-forward; generation by
diffusion over blocks. Straight `jax.numpy`, every matmul at `highest`
precision, no kernel, no cache, no batching: attention in blocks of
query rows over every key, the experts one after another, each over
every token with the weight the router gave it (zero for a token that
did not choose it).

Layer i of the stack, on x [T, D] (all matmuls without bias):

    u  = RMSNorm(x)
    q, k, v = u W_q, u W_k, u W_v   heads x head_dim, kv_heads x head_dim
    q, k = RMSNorm_head(q) g_q, RMSNorm_head(k) g_k   over head_dim, a
                                gain each ([head_dim], every head's)
    rotary over all of head_dim (pairs (i, i + head_dim/2)), theta
    a  = softmax(q k^T / sqrt(head_dim) under the mask) v W_o
         mask: row i sees key j iff j // B <= i // B (B = block_causal,
         blocks aligned at absolute position 0)
    h  = x + a
    u' = RMSNorm(h)
    p  = softmax(u' W_r) over all moe_experts, float32; the moe_top_k
         largest, renormalised to sum 1 (the softmax over the chosen
         logits: the same numbers)
    y_e = (silu(u' W_gate,e) * (u' W_up,e)) W_down,e          ("SwiGLU")
    x' = h + sum over the chosen e HELD HERE of p_e y_e

then RMSNorm and an untied head. `experts_held = [first, count]`: the
weights are those of experts first .. first + count of each layer; the
router keeps all its outputs, and what the experts held on the other
chips would add is left out (chipbench/configs/sdar-30b-serve.json,
`deployment`). The vocabulary is the slice the configuration states,
its last row the mask token.

GENERATION (`generate`, `denoise_logits`): the prompt's whole blocks
are context; its last `p mod B` tokens open the first generated block
as given positions. A block starts as B mask tokens (given positions
apart). Denoising pass s = 0 .. S-1 runs the model over everything up
to the block's end, reads at each still-masked position the greedy
token and its softmax probability FROM THE LOGITS AT THAT POSITION (no
shift by one), and reveals the B / S masked positions of highest
probability (ties: the lower position first); a revealed token never
changes. After pass S - 1 the block is final and the next follows.
`generate` is that loop by full recomputation. `denoise_logits` gives
the logits of every pass of every block of a FINISHED request in one
forward: the clean sequence followed by S noisy copies of every
generated block, copy (b, s) holding the final token where the
position is given or was revealed before pass s and the mask token
elsewhere, its rows seeing the clean keys of earlier blocks and the
keys of their own copy, at their true positions.

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
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST

#: in a reveal-step list: the position was given (a prompt token that
#: opens the first generated block), not generated
GIVEN = -2
#: ... and: not revealed yet
MASKED = -1

departures = [
    "experts_held of moe_experts experts a layer and a slice of the "
    "vocabulary: this chip's share of the deployment; the absent "
    "experts' part of each layer's result is left out",
    "depth cut to the first layers of this chip's pipeline stage; "
    "every layer is of the one kind",
    "a revealed position is kept as a bit of its own, not found again "
    "by comparing the token with the mask id: a position whose greedy "
    "token is the mask token itself counts as revealed",
    "every block takes its S denoising passes, a first block with "
    "given positions too (its last passes may reveal nothing): the "
    "schedule is static, the host knows each lane's pass without a "
    "fetch",
]
assumed = [
    "block length 4 (the Chat release's default; the family ships 4-64)",
    "the mask token is the vocabulary slice's last row",
    "a position's token and confidence are read from the logits at "
    "that same position (no shift by one)",
    "static low-confidence reveal: B / S masked positions a pass, the "
    "highest softmax probabilities of the greedy tokens, ties to the "
    "lower position; greedy",
    "a commit pass over the final tokens writes the block's K and V "
    "rows (the reference, which caches nothing, has none)",
    "the prompt's last p mod B tokens open the first generated block "
    "as given positions",
    "no bias on any projection",
    "weights random from the seed: kernels N(0, 1/fan_in), norm gains "
    "1 + 0.1 N(0, 1), the q and k head gains times qk_gain (the head "
    "norm undoes any gain on the qkv columns, where st21b carries it), "
    "embedding rows N(0, 1) so that the residual stream has unit "
    "scale, router columns N(0, router_gain^2 / D): the eight weights "
    "of a token are not flat",
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
        b + "attn/qkv/kernel": ((d, (h + 2 * hkv) * hd), "kernel"),
        b + "attn/q_norm/scale": ((hd,), "qk_scale"),
        b + "attn/k_norm/scale": ((hd,), "qk_scale"),
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
    if kind == "qk_scale":
        return (1.0 + 0.1 * x) * cfg.get("qk_gain", 1.0)
    if kind == "embed":
        return x
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


def _rope(x, pos, theta):
    """x [b, h, l, d] at positions `pos` [l]: rotate feature pairs
    (i, i + d/2) by pos * theta^(-2i/d)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def clean_plan(cfg, length):
    """(pos, blk, copy) of a plain sequence of `length` tokens: row i
    at position i, in block i // B, all of the clean copy 0."""
    pos = jnp.arange(length, dtype=jnp.int32)
    return pos, pos // cfg["block_causal"], jnp.zeros_like(pos)


def _attention(q, k, v, plan, rows):
    """q [b, h, l, d], k/v [b, hkv, l, d]; `plan` = (pos, blk, copy)
    [l] each: row i sees key j iff j is CLEAN (copy 0) and of an
    earlier block, or of i's own block and copy. With every row clean
    that is the block-causal mask. Blocks of `rows` query rows at a
    time."""
    b, h, l, d = q.shape
    hkv = k.shape[1]
    rows = min(rows, l)
    if l % rows:
        raise ValueError("length %d is not a multiple of %d" % (l, rows))
    qg = q.reshape(b, hkv, h // hkv, l, d)
    _, blk, copy = plan

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(qg, i * rows, rows, axis=3)
        s = jnp.einsum("bkgqd,bkld->bkgql", qi, k, precision=HIGHEST)
        s = s * d ** -0.5
        qblk = jax.lax.dynamic_slice_in_dim(blk, i * rows, rows)[:, None]
        qcopy = jax.lax.dynamic_slice_in_dim(copy, i * rows, rows)[:, None]
        ok = (((copy[None, :] == 0) & (blk[None, :] < qblk))
              | ((copy[None, :] == qcopy) & (blk[None, :] == qblk)))
        w = jax.nn.softmax(jnp.where(ok, s, -1e30), axis=-1)
        return jnp.einsum("bkgql,bkld->bkgqd", w, v, precision=HIGHEST)

    out = jax.lax.map(jax.checkpoint(block), jnp.arange(l // rows))
    # [n, b, hkv, g, rows, d] -> [b, l, h * d]
    out = out.transpose(1, 0, 4, 2, 3, 5)
    return out.reshape(b, l, h * d)


def router_weights(cfg, w, x):
    """[T, moe_experts] float32: the softmax over all experts at a
    token's top `moe_top_k`, renormalised to sum 1, 0 elsewhere. The
    router is float32 in the configuration and stays so under the
    control."""
    probs = jax.nn.softmax(matmul(x, w["moe/router"]), axis=-1)
    top_v, top_i = jax.lax.top_k(probs, cfg["moe_top_k"])
    gates = top_v / jnp.sum(top_v, axis=-1, keepdims=True)
    chosen = top_i[..., None] == jnp.arange(probs.shape[-1])
    return jnp.sum(jnp.where(chosen, gates[..., None], 0.0), axis=-2)


def experts(cfg, w, h, weights, mm=matmul):
    """sum over the experts held here of weight * SwiGLU expert; h
    [T, D], weights [T, moe_experts]."""
    first, count = _held(cfg)

    def one(y, e):
        wg, wu, wd = (jax.lax.dynamic_index_in_dim(w[name], e, 0, False)
                      for name in ("moe/w_gate", "moe/w_up", "moe/w_down"))
        out = mm(jax.nn.silu(mm(h, wg)) * mm(h, wu), wd)
        g = jax.lax.dynamic_index_in_dim(weights, first + e, 1, True)
        return y + g * out, None

    return jax.lax.scan(one, jnp.zeros_like(h), jnp.arange(count))[0]


def block_weights(w, i):
    """Block i's leaves under their names inside the block."""
    p = "block_%d/" % i
    return {k[len(p):]: v for k, v in w.items() if k.startswith(p)}


def layer(cfg, w, x, mm=matmul, rows=512, i=0, plan=None):
    """Block `i` on x [b, l, D] float32; `w` = block_weights(...).
    Every layer is of the one kind. `plan` (pos, blk, copy): where
    each row sits and what it sees (`_attention`); None: a plain
    sequence under the block-causal mask."""
    b, l, d = x.shape
    _, h, hkv, hd = _dims(cfg)
    eps = cfg.get("norm_eps", 1e-6)
    plan = clean_plan(cfg, l) if plan is None else plan
    y = _rms_norm(x, w["RMSNorm_0/scale"], eps)
    qkv = mm(y, w["attn/qkv/kernel"])
    q = qkv[..., :h * hd].reshape(b, l, h, hd).transpose(0, 2, 1, 3)
    k = qkv[..., h * hd:(h + hkv) * hd].reshape(b, l, hkv, hd)
    v = qkv[..., (h + hkv) * hd:].reshape(b, l, hkv, hd)
    k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
    q = _rms_norm(q, w["attn/q_norm/scale"], eps)
    k = _rms_norm(k, w["attn/k_norm/scale"], eps)
    theta = cfg.get("rope_theta", 10000.0)
    q, k = _rope(q, plan[0], theta), _rope(k, plan[0], theta)
    hid = x + mm(_attention(q, k, v, plan, rows), w["attn/proj/kernel"])
    u = _rms_norm(hid, w["RMSNorm_1/scale"], eps).reshape(b * l, d)
    y = experts(cfg, w, u, router_weights(cfg, w, u), mm)
    return hid + y.reshape(b, l, d)


def embed(w, tokens):
    return w["wte/embedding"][tokens]


def head_logits(w, x, mm=matmul, eps=1e-6):
    """float32 logits of rows x [n, D]."""
    return mm(_rms_norm(x, w["ln_f/scale"], eps), w["head/kernel"])


def forward(cfg, w, tokens, mm=matmul, rows=512, plan=None):
    """float32 logits [b, l, vocab] of tokens [b, l] (l a multiple of
    `rows` or shorter): the whole model, for the tests and `generate`."""
    x = embed(w, tokens)
    for i in range(cfg["num_layers"]):
        x = layer(cfg, block_weights(w, i), x, mm, rows, i, plan)
    b, l, d = x.shape
    return head_logits(w, x.reshape(b * l, d), mm).reshape(b, l, -1)


# ---------------------------------------------------------- generation


def reveal_now(probs, masked, count):
    """Which of a block's positions a pass reveals: the `count` masked
    ones of highest probability, ties to the lower position (numpy
    bool [B])."""
    order = np.argsort(-np.where(masked, probs, -1.0), kind="stable")
    out = np.zeros(len(probs), bool)
    out[order[:count]] = True
    return out & masked


def generate(cfg, w, prompt, n, S, mm=matmul):
    """(tokens [n], reveal_steps [n]): `n` tokens after `prompt` by the
    published loop, every pass a full forward over the sequence so
    far; each token with the pass that revealed it."""
    B = cfg["block_causal"]
    if B % S:
        raise ValueError("%d denoising steps do not divide a block of %d"
                         % (S, B))
    mask = int(cfg["mask_token"])
    p = len(prompt)
    seq = list(prompt[:p // B * B])
    given = list(prompt[p // B * B:])
    tokens, steps = [], []
    fwd = jax.jit(lambda t: forward(cfg, w, t, mm))
    while len(tokens) < n:
        block = np.asarray(given + [mask] * (B - len(given)))
        reveal = np.asarray([GIVEN] * len(given)
                            + [MASKED] * (B - len(given)))
        given = []
        for s in range(S):
            logits = np.asarray(fwd(jnp.asarray(
                seq + block.tolist(), jnp.int32)[None])[0, -B:])
            z = logits - logits.max(-1, keepdims=True)
            probs = 1.0 / np.exp(z).sum(-1)  # of the greedy token
            now = reveal_now(probs, reveal == MASKED, B // S)
            block = np.where(now, logits.argmax(-1), block)
            reveal = np.where(now, s, reveal)
        seq += block.tolist()
        tokens += block[reveal != GIVEN].tolist()
        steps += reveal[reveal != GIVEN].tolist()
    return tokens[:n], steps[:n]


def denoise_plan(cfg, prompt, tokens, reveal_steps, S, rows=512):
    """What `denoise_logits` runs, as numpy: {"ids" [L] the clean
    sequence then S noisy copies of every generated block, padded to a
    multiple of `rows`; "plan" (pos, blk, copy) [L]; "at" [blocks, S,
    B] the rows of copy (b, s) in it; "reveal" [blocks, B] each
    position's reveal step (GIVEN for a prompt token)}. The served
    tokens must end a block: what a trimmed last block held beyond
    them the caller does not know."""
    B = cfg["block_causal"]
    p, n = len(prompt), len(tokens)
    if (p + n) % B:
        raise ValueError(
            "prompt %d + %d tokens do not end a block of %d" % (p, n, B))
    mask = int(cfg["mask_token"])
    clean = np.asarray(list(prompt) + list(tokens), np.int64)
    first = p // B  # the first generated block
    blocks = (p + n) // B - first
    reveal = np.concatenate([
        np.full(p - first * B, GIVEN), np.asarray(reveal_steps, np.int64)
    ]).reshape(blocks, B)
    final = clean[first * B:].reshape(blocks, B)
    ids, pos, blk, copy = [clean], [np.arange(p + n)], [
        np.arange(p + n) // B], [np.zeros(p + n, np.int64)]
    for b in range(blocks):
        for s in range(S):
            seen = (reveal[b] == GIVEN) | (
                (reveal[b] >= 0) & (reveal[b] < s))
            ids.append(np.where(seen, final[b], mask))
            pos.append((first + b) * B + np.arange(B))
            blk.append(np.full(B, first + b))
            copy.append(np.full(B, 1 + b * S + s))
    ids, pos, blk, copy = (np.concatenate(x) for x in (ids, pos, blk, copy))
    at = (p + n) + np.arange(blocks * S * B).reshape(blocks, S, B)
    pad = -len(ids) % min(rows, len(ids))
    # pad rows: a copy and a block of their own, seen by no other row
    ids = np.concatenate([ids, np.zeros(pad, np.int64)])
    pos = np.concatenate([pos, np.zeros(pad, np.int64)])
    blk = np.concatenate([blk, np.full(pad, -1)])
    copy = np.concatenate([copy, np.full(pad, -1)])
    return {"ids": ids.astype(np.int32), "at": at, "reveal": reveal,
            "plan": tuple(x.astype(np.int32) for x in (pos, blk, copy))}


def denoise_logits(cfg, w, prompt, tokens, reveal_steps, S, mm=matmul,
                   rows=512):
    """float32 [blocks, S, B, vocab]: the logits of every denoising
    pass of every generated block of a finished request (`prompt`,
    served `tokens`, each token's `reveal_steps`), in ONE forward
    (`denoise_plan`)."""
    dp = denoise_plan(cfg, prompt, tokens, reveal_steps, S, rows)
    plan = tuple(jnp.asarray(x) for x in dp["plan"])
    logits = forward(cfg, w, jnp.asarray(dp["ids"])[None], mm, rows, plan)
    return logits[0][dp["at"]]
