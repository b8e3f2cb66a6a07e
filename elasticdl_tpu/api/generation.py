"""Autoregressive decoding for the sequence model families.

The reference's inference story is batch prediction (PREDICTION tasks →
`Worker._predict_only`); for the net-new LM families this adds the
sequence counterpart: jit-compiled decoding with greedy argmax,
temperature sampling (top-k / nucleus filtered), and beam search
(`beam_search_generate`). Two execution strategies behind
`autoregressive_generate`: the default recomputes the full forward per
step inside a `lax.fori_loop` (simple, zero model requirements beyond
the convention), and `use_cache=True` streams single-token steps
through the model's per-layer KV caches (O(L) attention per token).
The causal mask guarantees positions >= i never influence the token
sampled at i in either strategy.

Works with any zoo model following the sequence convention
(features {"tokens": int32 [b, L]} -> logits [b, L, vocab]).
"""

import jax
import jax.numpy as jnp


class _LRUCache(dict):
    """Insertion-ordered bounded cache for compiled decode fns. Every
    distinct (batch, sampling-knob, length) combination compiles its own
    executable; a sweep over sampling configs or prompt lengths would
    otherwise accumulate compiled programs on the Trainer without bound.
    get() refreshes recency; inserting beyond max_entries evicts the
    least-recently-used entry (its executable is re-compiled on next
    use — correctness is unaffected)."""

    max_entries = 16

    def get(self, key, default=None):
        if key in self:
            val = super().pop(key)
            super().__setitem__(key, val)
            return val
        return default

    def __setitem__(self, key, value):
        if key in self:
            super().pop(key)
        elif len(self) >= self.max_entries:
            super().pop(next(iter(self)))
        super().__setitem__(key, value)


#: recompile-sentry hook (observability/runtime_health.py): the
#: serving engine attaches its sentry here so the offline decode
#: paths' jit caches count their compilations into the same
#: edl_serving_recompiles_total{fn=} family. None = counting off —
#: the executables are plain jax.jit either way.
_SENTRY = None


def set_decode_sentry(sentry):
    """Adopt `sentry` (RecompileSentry or None) for every decode-path
    jit site in this module. Process-global like the compile caches
    themselves: one serving process has one sentry."""
    global _SENTRY
    _SENTRY = sentry


def _tjit(name, fn, **jit_kwargs):
    from elasticdl_tpu.observability.runtime_health import tracked_jit

    return tracked_jit(fn, name, lambda: _SENTRY, **jit_kwargs)


def _decode_cache(trainer):
    return trainer.__dict__.setdefault("_generate_cache", _LRUCache())


def _maybe_dequantize(variables, qz):
    """Weight-only int8 support (api.quantization): dequantize INSIDE
    the jitted decode program — XLA fuses `int8 -> compute * scale`
    into each consuming matmul's operand read, so the weights travel
    HBM->VMEM as int8. `qz` is trace-static (baked into the compiled
    fn; the compile-cache keys carry it)."""
    if not qz:
        return variables
    from elasticdl_tpu.api.quantization import dequantize_params

    return dict(
        variables, params=dequantize_params(variables["params"])
    )


def _filter_logits(logits, top_k, top_p):
    """Standard sampling filters, static-shape: top-k keeps the k
    highest logits per row; nucleus (top-p) keeps the smallest set of
    tokens whose cumulative probability reaches p (always at least the
    argmax). Filtered entries drop to -inf before the categorical.

    Tie semantics (the usual static-shape formulation): every logit
    EQUAL to the k-th value survives top-k (>= k tokens on ties), and
    ties at the nucleus threshold likewise all survive — with float
    logits exact ties are measure-zero, so in practice exactly k."""
    neg = jnp.asarray(-jnp.inf, logits.dtype)
    if top_k and top_k > 0:
        k = min(int(top_k), logits.shape[-1])  # clamp to the vocab
        kth = jnp.sort(logits, axis=-1)[..., -k, None]
        logits = jnp.where(logits < kth, neg, logits)
    if top_p < 1.0:
        sorted_desc = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_desc, axis=-1)
        # keep while the mass BEFORE the token is < p (first always kept)
        keep = (jnp.cumsum(probs, axis=-1) - probs) < top_p
        thr = jnp.min(
            jnp.where(keep, sorted_desc, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < thr, neg, logits)
    return logits


def _next_token(step_logits, rng, position, temperature, top_k=0,
                top_p=1.0):
    """Sample/argmax the token for `position`. The RNG key is derived by
    fold_in(rng, position), NOT by sequentially splitting a stream, so
    the full-forward and KV-cached paths produce identical samples for
    the same (seed, temperature) regardless of how many model steps each
    runs."""
    if temperature > 0.0:
        # temperature first, filters on the ACTUAL sampling
        # distribution (the conventional top-p semantics)
        scaled = step_logits / temperature
        scaled = _filter_logits(scaled, top_k, top_p)
        sub = jax.random.fold_in(rng, position)
        nxt = jax.random.categorical(sub, scaled, axis=-1)
    else:
        nxt = jnp.argmax(step_logits, axis=-1)
    return nxt.astype(jnp.int32)


def serving_next_token(step_logits, seed, position, temperature,
                       top_k=0, top_p=1.0):
    """`_next_token` for the online serving scheduler: `temperature` and
    `seed` ride as TRACED per-slot values (one compiled decode step
    serves every sampling config in the batch), with `top_k`/`top_p`
    static server-level knobs. Token-parity contract with the offline
    sampler, which the serving tests lock: for any fixed temperature,
    the selected token equals `_next_token(step_logits,
    PRNGKey(seed), position, temperature, top_k, top_p)` — greedy is
    the same argmax, and sampling applies the same scale -> filter ->
    fold_in(rng, position) -> categorical pipeline. A request's tokens
    therefore never depend on what else shares the serving batch.

    step_logits: [V] (one slot's logits). Returns a scalar int32."""
    greedy = jnp.argmax(step_logits, axis=-1).astype(jnp.int32)
    # the guard keeps the division finite when temperature == 0 (the
    # sampled branch is discarded by the select in that case)
    safe_t = jnp.maximum(temperature, 1e-6)
    scaled = _filter_logits(step_logits / safe_t, top_k, top_p)
    sub = jax.random.fold_in(jax.random.PRNGKey(seed), position)
    sampled = jax.random.categorical(sub, scaled, axis=-1).astype(
        jnp.int32
    )
    return jnp.where(temperature > 0.0, sampled, greedy)


def autoregressive_generate(trainer, state, prompt, max_new_tokens,
                            temperature=0.0, seed=0, use_cache=False,
                            top_k=0, top_p=1.0):
    """Generate continuations of `prompt` with the trained model.

    trainer: Trainer whose model maps {"tokens": [b, L]} -> [b, L, V]
             logits (L = the model's static sequence length).
    state:   TrainState from the trainer.
    prompt:  int32 [b, p] with 1 <= p, p + max_new_tokens <= L.
    temperature: 0.0 = greedy argmax; > 0 = categorical sampling,
             optionally filtered by top_k (keep k highest logits) and/or
             top_p (nucleus: smallest set reaching cumulative prob p).
    use_cache: decode through the model's KV cache (decode=True path,
             one single-token step per position: O(L) attention per
             token instead of a full-sequence forward). Requires the
             model to support decode mode (the transformer_lm family).
             Greedy decoding matches the full-forward path exactly;
             temperature sampling uses the same position-derived RNG
             keys but can diverge where the two paths' logits differ in
             kernel numerics.
    Returns int32 [b, p + max_new_tokens].
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    b, p = prompt.shape
    model = trainer.model
    seq_len = getattr(model, "seq_len", None)
    if seq_len is None:
        raise ValueError(
            "model %r has no seq_len attribute; autoregressive_generate "
            "needs the sequence-family convention" % type(model).__name__
        )
    if not getattr(model, "causal", True):
        # e.g. the BERT encoder: bidirectional attention would let every
        # decode step see the zero-padded future positions
        raise ValueError(
            "model %r is not causal; autoregressive decoding needs a "
            "causal (left-to-right) model" % type(model).__name__
        )
    if not 0.0 < top_p <= 1.0:
        raise ValueError(
            "top_p must be in (0, 1], got %r (top_p -> 0 keeps nothing; "
            "use temperature=0 for greedy)" % (top_p,)
        )
    if top_k < 0:
        raise ValueError("top_k must be >= 0, got %r" % (top_k,))
    if temperature <= 0.0:
        # greedy ignores the filters; normalize them out of the compile
        # cache keys so greedy configs share one executable
        top_k, top_p = 0, 1.0
    total = p + int(max_new_tokens)
    if max_new_tokens < 1 or p < 1 or total > seq_len:
        raise ValueError(
            "need prompt length >= 1 and max_new_tokens >= 1 with "
            "prompt %d + new %d <= the model's seq_len %d"
            % (p, max_new_tokens, seq_len)
        )

    if use_cache:
        _require_kv_convention(model)
        return _kv_generate(
            trainer, state, prompt, p, total, temperature, seed,
            top_k, top_p,
        )

    # One compiled decode per (batch, sampling-mode) — the loop bounds
    # ride as traced scalars (lax.fori_loop accepts them under jit), so
    # every prompt/continuation length reuses the same executable.
    # Variables ride as arguments so params aren't baked in as constants.
    from elasticdl_tpu.api.quantization import is_quantized

    qz = is_quantized(state.params)
    cache = _decode_cache(trainer)
    key = (b, float(temperature), int(top_k), float(top_p), qz)
    decode_fn = cache.get(key)
    if decode_fn is None:
        def decode(variables, tokens, rng, start, stop):
            variables = _maybe_dequantize(variables, qz)

            def body(i, tokens):
                logits = model.apply(
                    variables, {"tokens": tokens}, training=False
                )
                # logits at position i-1 predict token i
                step_logits = jax.lax.dynamic_slice_in_dim(
                    logits, i - 1, 1, axis=1
                )[:, 0]  # [b, V]
                nxt = _next_token(step_logits, rng, i, temperature,
                                  top_k, top_p)
                return jax.lax.dynamic_update_slice(
                    tokens, nxt[:, None], (0, i)
                )

            return jax.lax.fori_loop(start, stop, body, tokens)

        decode_fn = _tjit("offline_decode_nocache", decode)
        cache[key] = decode_fn

    variables = {"params": state.params, **state.model_state}
    buf = jnp.zeros((b, seq_len), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt, (0, 0))
    with trainer.mesh:
        out = decode_fn(
            variables, buf, jax.random.PRNGKey(seed),
            jnp.asarray(p, jnp.int32), jnp.asarray(total, jnp.int32),
        )
    return out[:, :total]


def _prefill_bucket(p, seq_len):
    """Static prefill slab: smallest 64-multiple covering the prompt
    (clamped to the cache capacity). Positions in [p, p_pad) hold pad
    junk in the cache; decode overwrites each before attending to it."""
    return min(seq_len, -(-p // 64) * 64)


def _kv_shapes_for(cache, model, b):
    """Cache-buffer structure from an eval_shape'd decode init (no real
    params are materialized); depends only on the batch size, so it is
    cached separately from the compiled decodes."""
    kv_shapes = cache.get(("kv_shapes", b))
    if kv_shapes is None:
        def init_shapes():
            return model.init(
                jax.random.PRNGKey(0),
                {"tokens": jnp.zeros((b, 1), jnp.int32)},
                training=False, decode=True,
            )

        kv_shapes = jax.eval_shape(init_shapes)["cache"]
        cache[("kv_shapes", b)] = kv_shapes
    return kv_shapes


def kv_row_leaf(leaf, cache_len):
    """THE batch-1 decode-cache leaf convention, in one place: True for
    per-layer KV ROW buffers — `[1, kv_heads, cache_len, ...]` arrays
    (k/v rows, and the int8 format's per-row scales) in a tree from
    `_kv_shapes_for(cache, model, 1)`. These are the leaves the serving
    paged pool (serving/kv_pool.py) re-shapes into block arenas; the
    scalar position counter (and any other non-row state) is NOT a row
    leaf and stays per-sequence."""
    shape = getattr(leaf, "shape", None)
    return (shape is not None and len(shape) == 4 and shape[0] == 1
            and shape[2] == cache_len)


#: the kinds of a decode-cache leaf (cache_leaf_kinds)
ROWS, STATE, SCALAR = "rows", "state", "scalar"


def cache_leaf_kinds(model, kv_shapes, cache_len):
    """The KIND of every leaf of a batch-1 decode-cache template
    (`_kv_shapes_for(cache, model, 1)`), as a tree of the same
    structure: ROWS, a cached token a row of a `[1, kv_heads,
    cache_len, ...]` buffer, which the serving pool pages into block
    arenas by block table; STATE, a fixed-size per-sequence state
    `[1, ...]` (a state-space layer's) that the pool keeps a slot of
    for each of its lanes; SCALAR, the position counter. The model
    DECLARES them, by path (`model.cache_leaf_kind(path)`); one that
    declares nothing has rows where the `kv_row_leaf` convention finds
    them and scalars elsewhere. A leaf's rank decides nothing: a state
    may well be 4-d."""
    declare = getattr(model, "cache_leaf_kind", None)

    def kind(path, leaf):
        if declare is None:
            return ROWS if kv_row_leaf(leaf, cache_len) else SCALAR
        names = tuple(getattr(k, "key", getattr(k, "name", None))
                      for k in path)
        out = declare(names)
        if out == ROWS and not kv_row_leaf(leaf, cache_len):
            raise ValueError(
                "cache leaf %r is declared rows and is shaped %r, not "
                "[1, kv_heads, %d, ...]" % (names, leaf.shape, cache_len))
        if out == STATE and (not leaf.shape or leaf.shape[0] != 1):
            raise ValueError(
                "cache leaf %r is declared a per-sequence state and is "
                "shaped %r, not [1, ...]" % (names, leaf.shape))
        if out not in (ROWS, STATE, SCALAR):
            raise ValueError("cache leaf %r is declared %r"
                             % (names, out))
        return out

    return jax.tree_util.tree_map_with_path(kind, kv_shapes)


def _run_prefill(model, variables, kv_shapes, tokens2d, p_len, p_pad):
    """Shared batched-prefill contract for the greedy-KV and beam-KV
    paths: zero caches, ONE prefill=True forward over the static
    [:, :p_pad] slab, return (filled cache tree, logits at p_len-1).
    tokens2d: [b, L] int32."""
    b = tokens2d.shape[0]
    kv = jax.tree.map(
        lambda sh: jnp.zeros(sh.shape, sh.dtype), kv_shapes
    )
    logits, upd = model.apply(
        dict(variables, cache=kv),
        {"tokens": tokens2d[:, :p_pad]},
        training=False, prefill=True, prompt_len=p_len,
        mutable=["cache"],
    )
    last = jax.lax.dynamic_slice(
        logits, (0, p_len - 1, 0), (b, 1, logits.shape[-1])
    )[:, 0]  # [b, V]
    return upd["cache"], last


def _require_kv_convention(model):
    """use_cache=True needs BOTH decode mode and the batched-prefill
    mode; a clear error beats a TypeError from inside tracing."""
    import inspect

    params = inspect.signature(type(model).__call__).parameters
    missing = [k for k in ("decode", "prefill") if k not in params]
    if missing:
        raise ValueError(
            "model %r lacks %s mode(s); use_cache=True needs the "
            "KV-cache convention (decode + prefill kwargs — the "
            "transformer_lm family)"
            % (type(model).__name__, "/".join(missing))
        )


def _kv_generate(trainer, state, prompt, p, total, temperature, seed,
                 top_k=0, top_p=1.0):
    """KV-cached decode: batched prefill, then one single-token model
    step per generated position.

    The prompt is prefilled in ONE causal forward (the model's
    prefill=True mode writes every layer's k/v for positions [0, p) in
    a single MXU-friendly pass — the flash kernel runs over the whole
    prompt instead of p-1 tiny single-token steps), then a fori_loop
    with dynamic start runs the per-token decode. The prefill length is
    padded to a 64 bucket so one executable serves nearby prompt
    lengths; compiled once per (batch, total, bucket, sampling mode).
    """
    model = trainer.model
    b = prompt.shape[0]
    seq_len = model.seq_len
    p_pad = _prefill_bucket(p, seq_len)

    from elasticdl_tpu.api.quantization import is_quantized

    qz = is_quantized(state.params)
    cache = _decode_cache(trainer)
    key = ("kv", b, total, p_pad, float(temperature), int(top_k),
           float(top_p), qz)
    fn = cache.get(key)
    if fn is None:
        kv_shapes = _kv_shapes_for(cache, model, b)

        def run(variables, tokens, rng, p_len):
            variables = _maybe_dequantize(variables, qz)
            # ---- batched prefill: fill caches for [0, p), take the
            # logits at p-1, write the first generated token at p
            kv, last = _run_prefill(
                model, variables, kv_shapes, tokens, p_len, p_pad
            )
            nxt = _next_token(last, rng, p_len, temperature,
                              top_k, top_p)
            tokens = jax.lax.dynamic_update_slice(
                tokens, nxt.astype(jnp.int32)[:, None], (0, p_len)
            )

            # ---- per-token decode, dynamic start at p (the prefill
            # already produced the token at p): iteration i consumes
            # the token at position i and writes position i+1
            def body(i, carry):
                tokens, kv = carry
                tok = jax.lax.dynamic_slice(tokens, (0, i), (b, 1))
                logits, upd = model.apply(
                    dict(variables, cache=kv),
                    {"tokens": tok},
                    training=False, decode=True, mutable=["cache"],
                )
                nxt = _next_token(logits[:, 0], rng, i + 1, temperature,
                                  top_k, top_p)
                tokens = jax.lax.dynamic_update_slice(
                    tokens, nxt.astype(jnp.int32)[:, None], (0, i + 1)
                )
                return (tokens, upd["cache"])

            tokens, _ = jax.lax.fori_loop(
                p_len, total - 1, body, (tokens, kv)
            )
            return tokens

        fn = _tjit("offline_decode_kv", run)
        cache[key] = fn

    variables = {"params": state.params, **state.model_state}
    buf = jnp.zeros((b, seq_len), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt, (0, 0))
    with trainer.mesh:
        out = fn(
            variables, buf, jax.random.PRNGKey(seed),
            jnp.asarray(p, jnp.int32),
        )
    return out[:, :total]


def beam_search_generate(trainer, state, prompt, max_new_tokens,
                         num_beams=4, use_cache=False):
    """Beam-search decoding: keeps the `num_beams` highest-log-
    probability continuations per batch row and returns the best one.
    Deterministic; beams ride as extra batch rows so the compiled model
    is the same one the greedy path uses.

    Initial beam scores are [0, -inf, ...], which both deduplicates the
    first expansion (all beams start as copies of the prompt) and keeps
    every tensor static-shape. Returns int32 [b, p + max_new_tokens].

    use_cache=True: KV-cached strategy — one batched prompt prefill
    (beams share it: the caches are prefilled for b rows and tiled to
    b*num_beams), then single-token decode steps; beam reordering
    gathers the per-layer cache rows along the batch axis each step.
    O(L) attention per token instead of a full forward per step."""
    if use_cache:
        return _beam_kv_generate(trainer, state, prompt, max_new_tokens,
                                 num_beams)
    prompt = jnp.asarray(prompt, jnp.int32)
    b, p = prompt.shape
    model = trainer.model
    seq_len = getattr(model, "seq_len", None)
    if seq_len is None or not getattr(model, "causal", True):
        raise ValueError(
            "beam search needs a causal sequence-family model"
        )
    total = p + int(max_new_tokens)
    if max_new_tokens < 1 or p < 1 or total > seq_len:
        raise ValueError(
            "need prompt length >= 1 and max_new_tokens >= 1 with "
            "prompt %d + new %d <= the model's seq_len %d"
            % (p, max_new_tokens, seq_len)
        )
    k = int(num_beams)
    vocab = getattr(model, "vocab_size", None)
    if k < 1 or (vocab is not None and k > vocab):
        raise ValueError(
            "num_beams must be in [1, vocab_size], got %d" % k
        )

    from elasticdl_tpu.api.quantization import is_quantized

    qz = is_quantized(state.params)
    cache = _decode_cache(trainer)
    key = ("beam", b, k, qz)
    fn = cache.get(key)
    if fn is None:
        def run(variables, tokens, start, stop):
            # tokens [b, k, L]; scores [b, k]
            variables = _maybe_dequantize(variables, qz)
            neg = jnp.asarray(-jnp.inf, jnp.float32)
            scores = jnp.where(
                jnp.arange(k)[None, :] == 0, 0.0, neg
            ) * jnp.ones((b, 1), jnp.float32)

            def body(i, carry):
                tokens, scores = carry
                logits = model.apply(
                    variables,
                    {"tokens": tokens.reshape(b * k, -1)},
                    training=False,
                )
                step = jax.nn.log_softmax(
                    jax.lax.dynamic_slice_in_dim(
                        logits, i - 1, 1, axis=1
                    )[:, 0].reshape(b, k, -1).astype(jnp.float32),
                    axis=-1,
                )  # [b, k, V]
                cand = scores[:, :, None] + step
                v = cand.shape[-1]
                vals, idx = jax.lax.top_k(cand.reshape(b, k * v), k)
                beam_src = idx // v  # [b, k]
                tok = (idx % v).astype(jnp.int32)
                tokens = jnp.take_along_axis(
                    tokens, beam_src[:, :, None], axis=1
                )
                tokens = jax.lax.dynamic_update_slice(
                    tokens, tok[..., None], (0, 0, i)
                )
                return tokens, vals

            tokens, scores = jax.lax.fori_loop(
                start, stop, body, (tokens, scores)
            )
            best = jnp.argmax(scores, axis=-1)  # [b]
            return jnp.take_along_axis(
                tokens, best[:, None, None], axis=1
            )[:, 0], scores

        fn = _tjit("offline_beam_nocache", run)
        cache[key] = fn

    variables = {"params": state.params, **state.model_state}
    buf = jnp.zeros((b, k, seq_len), jnp.int32)
    buf = jax.lax.dynamic_update_slice(
        buf, jnp.broadcast_to(prompt[:, None, :], (b, k, p)), (0, 0, 0)
    )
    with trainer.mesh:
        out, _ = fn(
            variables, buf,
            jnp.asarray(p, jnp.int32), jnp.asarray(total, jnp.int32),
        )
    return out[:, :total]


def _beam_kv_generate(trainer, state, prompt, max_new_tokens, num_beams):
    """KV-cached beam search (beam_search_generate use_cache=True).

    Same selection math as the full-forward strategy — the [0, -inf]
    initial scores and top-k over (beam, vocab) — so the two strategies
    return identical tokens; only the attention cost differs. The
    prompt is prefilled ONCE for the b true rows (model prefill mode,
    see _kv_generate), the caches are row-tiled to b*num_beams, and
    each step gathers the cache rows of the surviving beams.
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    b, p = prompt.shape
    model = trainer.model
    seq_len = getattr(model, "seq_len", None)
    if seq_len is None or not getattr(model, "causal", True):
        raise ValueError(
            "beam search needs a causal sequence-family model"
        )
    _require_kv_convention(model)
    total = p + int(max_new_tokens)
    if max_new_tokens < 1 or p < 1 or total > seq_len:
        raise ValueError(
            "need prompt length >= 1 and max_new_tokens >= 1 with "
            "prompt %d + new %d <= the model's seq_len %d"
            % (p, max_new_tokens, seq_len)
        )
    k = int(num_beams)
    vocab = getattr(model, "vocab_size", None)
    if k < 1 or (vocab is not None and k > vocab):
        raise ValueError(
            "num_beams must be in [1, vocab_size], got %d" % k
        )
    bk = b * k
    p_pad = _prefill_bucket(p, seq_len)

    from elasticdl_tpu.api.quantization import is_quantized

    qz = is_quantized(state.params)
    cache = _decode_cache(trainer)
    key = ("beam_kv", b, k, total, p_pad, qz)
    fn = cache.get(key)
    if fn is None:
        kv_shapes = _kv_shapes_for(cache, model, b)

        def run(variables, tokens, p_len):
            # tokens [b, k, L]; shared prefill on the b true rows
            variables = _maybe_dequantize(variables, qz)
            kv, last = _run_prefill(
                model, variables, kv_shapes, tokens[:, 0], p_len, p_pad
            )
            # beams share the prompt: tile each cache row k times
            kv = jax.tree.map(
                lambda a: (
                    jnp.repeat(a, k, axis=0)
                    if a.ndim and a.shape[0] == b else a
                ),
                kv,
            )
            neg = jnp.asarray(-jnp.inf, jnp.float32)
            scores = jnp.where(
                jnp.arange(k)[None, :] == 0, 0.0, neg
            ) * jnp.ones((b, 1), jnp.float32)

            def expand(i, tokens, scores, kv, step_logits):
                """One beam expansion writing position i: the shared
                top-k over (beam, vocab) + beam gathers."""
                step = jax.nn.log_softmax(
                    step_logits.reshape(b, k, -1).astype(jnp.float32),
                    axis=-1,
                )  # [b, k, V]
                cand = scores[:, :, None] + step
                v = cand.shape[-1]
                vals, idx = jax.lax.top_k(cand.reshape(b, k * v), k)
                beam_src = idx // v  # [b, k]
                tok = (idx % v).astype(jnp.int32)
                tokens = jnp.take_along_axis(
                    tokens, beam_src[:, :, None], axis=1
                )
                tokens = jax.lax.dynamic_update_slice(
                    tokens, tok[..., None], (0, 0, i)
                )
                flat_src = (
                    jnp.arange(b)[:, None] * k + beam_src
                ).reshape(bk)
                kv = jax.tree.map(
                    lambda a: (
                        jnp.take(a, flat_src, axis=0)
                        if a.ndim and a.shape[0] == bk else a
                    ),
                    kv,
                )
                return tokens, vals, kv

            # first expansion (position p) from the prefill logits —
            # the [0, -inf] scores make the beam gather a no-op on the
            # identical tiled caches
            first = jnp.broadcast_to(
                last[:, None, :], (b, k, last.shape[-1])
            ).reshape(bk, -1)
            tokens, scores, kv = expand(p_len, tokens, scores, kv,
                                        first)

            def body(i, carry):
                tokens, scores, kv = carry
                tok = jax.lax.dynamic_slice(
                    tokens.reshape(bk, -1), (0, i - 1), (bk, 1)
                )
                logits, upd = model.apply(
                    dict(variables, cache=kv),
                    {"tokens": tok},
                    training=False, decode=True, mutable=["cache"],
                )
                tokens, scores, kv = expand(
                    i, tokens, scores, upd["cache"], logits[:, 0]
                )
                return tokens, scores, kv

            tokens, scores, _ = jax.lax.fori_loop(
                p_len + 1, total, body, (tokens, scores, kv)
            )
            best = jnp.argmax(scores, axis=-1)  # [b]
            return jnp.take_along_axis(
                tokens, best[:, None, None], axis=1
            )[:, 0]

        fn = _tjit("offline_beam_kv", run)
        cache[key] = fn

    variables = {"params": state.params, **state.model_state}
    buf = jnp.zeros((b, k, seq_len), jnp.int32)
    buf = jax.lax.dynamic_update_slice(
        buf, jnp.broadcast_to(prompt[:, None, :], (b, k, p)), (0, 0, 0)
    )
    with trainer.mesh:
        out = fn(variables, buf, jnp.asarray(p, jnp.int32))
    return out[:, :total]


def speculative_generate(trainer, state, draft_trainer, draft_state,
                         prompt, max_new_tokens, gamma=4,
                         return_stats=False):
    """Speculative greedy decoding: a small DRAFT model proposes gamma
    tokens per iteration (cheap single-token KV steps) and the TARGET
    model verifies them in ONE chunked decode step (the model's t>1
    decode mode: one batched cache read for gamma queries). Accepted
    prefix + the target's correction token advance the stream 1..gamma
    positions per target invocation.

    EXACTNESS: output tokens equal plain greedy decoding of the target
    model (same argmax at every position — the draft only affects how
    many target steps are needed, never what they produce; kernel
    reduction-order ULPs aside). Greedy only — temperature sampling
    would need the rejection-sampling correction.

    Cache rollback is counter-only: entries past the rolled-back
    counter are junk that the chunk mask hides and later writes
    overwrite — the same safety argument as the prefill slab.

    Both models follow the KV convention (decode + prefill modes) and
    share the vocabulary; the draft's seq_len must also cover the
    stream. Returns int32 [b, p + max_new_tokens]; with
    return_stats=True, (tokens, stats) where stats reports
    verify_calls (target invocations after prefill), committed_tokens,
    and acceptance_rate (mean accepted proposals / (gamma-1)) — the
    observability that tells a ceiling draft from a floor one.
    """
    prompt = jnp.asarray(prompt, jnp.int32)
    b, p = prompt.shape
    model, draft = trainer.model, draft_trainer.model
    for m in (model, draft):
        _require_kv_convention(m)
        if not getattr(m, "causal", True):
            raise ValueError("speculative decode needs causal models")
    if getattr(model, "vocab_size", None) != getattr(
            draft, "vocab_size", None):
        raise ValueError(
            "target and draft must share a vocabulary, got %r vs %r"
            % (getattr(model, "vocab_size", None),
               getattr(draft, "vocab_size", None))
        )
    gamma = int(gamma)
    if gamma < 1:
        raise ValueError("gamma must be >= 1, got %d" % gamma)
    total = p + int(max_new_tokens)
    seq_len = min(model.seq_len, draft.seq_len)
    # the last verify chunk can reach position (total-2) + gamma
    if max_new_tokens < 1 or p < 1 or total + gamma - 1 > seq_len:
        raise ValueError(
            "need prompt %d + new %d + gamma %d - 1 <= min seq_len %d "
            "(the verify chunk must fit the cache)"
            % (p, max_new_tokens, gamma, seq_len)
        )
    p_pad = _prefill_bucket(p, seq_len)

    cache = _decode_cache(trainer)
    from elasticdl_tpu.api.quantization import is_quantized

    qz = is_quantized(state.params)
    d_qz = is_quantized(draft_state.params)
    # the compiled fn closes over the DRAFT module too — same target
    # with a different draft must not reuse it. The cache entry holds a
    # STRONG reference to the draft trainer so its id cannot be
    # recycled onto a new object while the entry lives (the LRU bounds
    # the lifetime).
    # return_stats is NOT part of the key: the compiled program always
    # returns (tokens, n, acc); the flag only gates Python-side
    # post-processing, so both call forms share one executable
    key = ("spec", b, total, gamma, p_pad, qz, d_qz,
           id(draft_trainer))
    fn = None
    entry = cache.get(key)
    if entry is not None:
        fn, _draft_ref = entry
    if fn is None:
        kv_shapes = _kv_shapes_for(cache, model, b)
        # draft cache shapes live under the draft trainer's own cache
        d_cache = _decode_cache(draft_trainer)
        d_kv_shapes = _kv_shapes_for(d_cache, draft, b)

        def run(variables, d_variables, tokens, p_len):
            variables = _maybe_dequantize(variables, qz)
            d_variables = _maybe_dequantize(d_variables, d_qz)
            # ---- prefill BOTH models; target's logits pick token at p
            tkv, t_last = _run_prefill(
                model, variables, kv_shapes, tokens, p_len, p_pad
            )
            dkv, _ = _run_prefill(
                draft, d_variables, d_kv_shapes, tokens, p_len, p_pad
            )
            first = jnp.argmax(t_last, axis=-1).astype(jnp.int32)
            tokens = jax.lax.dynamic_update_slice(
                tokens, first[:, None], (0, p_len)
            )

            def cond(carry):
                tokens, pos, tkv, dkv, n, acc = carry
                return pos < total

            def body(carry):
                tokens, pos, tkv, dkv, n, acc = carry
                # ---- draft: gamma single-token proposals from pos-1
                def d_step(c, _):
                    dkv, tok = c
                    lg, upd = draft.apply(
                        dict(d_variables, cache=dkv),
                        {"tokens": tok},
                        training=False, decode=True, mutable=["cache"],
                    )
                    nxt = jnp.argmax(
                        lg[:, 0], axis=-1
                    ).astype(jnp.int32)[:, None]
                    return (upd["cache"], nxt), nxt

                tok0 = jax.lax.dynamic_slice(
                    tokens, (0, pos - 1), (b, 1)
                )
                # gamma-1 proposals: the verify chunk only ever reads
                # d[0..gamma-2] (row j feeds position pos-1+j), and the
                # gamma-th proposal could not change the commit count
                # either — it would be pure dead work
                (dkv, _), d_toks = jax.lax.scan(
                    d_step, (dkv, tok0), None, length=gamma - 1
                )
                d_toks = jnp.moveaxis(
                    d_toks[..., 0], 0, 1
                )  # [b, gamma-1]
                # stage proposals in the buffer so the verify chunk can
                # read them contiguously: positions pos .. pos+gamma-2
                tokens_staged = jax.lax.dynamic_update_slice(
                    tokens, d_toks, (0, pos)
                )
                # ---- target: ONE gamma-wide chunk from position pos-1
                chunk = jax.lax.dynamic_slice(
                    tokens_staged, (0, pos - 1), (b, gamma)
                )
                t_logits, t_upd = model.apply(
                    dict(variables, cache=tkv),
                    {"tokens": chunk},
                    training=False, decode=True, mutable=["cache"],
                )
                tkv = t_upd["cache"]
                g_toks = jnp.argmax(
                    t_logits, axis=-1
                ).astype(jnp.int32)  # [b, gamma] targets for pos..pos+gamma-1
                # ---- acceptance: longest common prefix over the
                # gamma-1 proposals, batch-min so every row stays in
                # lockstep (a row's extra accepted tokens are simply
                # re-derived next iteration). Committing a+1 tokens is
                # always valid: position pos+a takes the target's own
                # g[a] (correction when d[a] mismatched, bonus when
                # every proposal matched).
                match = jnp.cumprod(
                    (d_toks == g_toks[:, :gamma - 1]).astype(jnp.int32),
                    axis=1,
                )
                a = jnp.min(match.sum(axis=1))  # scalar in [0, gamma-1]
                c = a + 1                       # tokens to commit
                # commit g[0..c-1] at positions pos..pos+c-1 (g == d on
                # the accepted prefix; position pos+a takes the
                # target's correction when a < gamma)
                keep = jnp.arange(gamma)[None, :] < c
                window = jax.lax.dynamic_slice(
                    tokens, (0, pos), (b, gamma)
                )
                merged = jnp.where(keep, g_toks, window)
                tokens = jax.lax.dynamic_update_slice(
                    tokens, merged, (0, pos)
                )
                pos = pos + c
                # ---- rollback: counters to consumed = pos - 1; cache
                # rows past the counter are masked junk
                tkv = dict(tkv, pos=jnp.asarray(pos - 1, jnp.int32))
                dkv = dict(dkv, pos=jnp.asarray(pos - 1, jnp.int32))
                return (tokens, pos, tkv, dkv, n + 1, acc + a)

            zero = jnp.asarray(0, jnp.int32)
            tokens, _, _, _, n, acc = jax.lax.while_loop(
                cond, body, (tokens, p_len + 1, tkv, dkv, zero, zero)
            )
            return tokens, n, acc

        fn = _tjit("offline_speculative", run)
        cache[key] = (fn, draft_trainer)

    variables = {"params": state.params, **state.model_state}
    d_variables = {
        "params": draft_state.params, **draft_state.model_state
    }
    buf = jnp.zeros((b, seq_len), jnp.int32)
    buf = jax.lax.dynamic_update_slice(buf, prompt, (0, 0))
    with trainer.mesh:
        out, n, acc = fn(variables, d_variables, buf,
                         jnp.asarray(p, jnp.int32))
    out = out[:, :total]
    if not return_stats:
        return out
    verify_calls = int(n)
    stats = {
        "verify_calls": verify_calls,
        "committed_tokens": int(max_new_tokens) - 1,  # first from prefill
        # accepted proposals per verify, as a fraction of the gamma-1
        # proposed — counted in-loop (batch-min per iteration, like the
        # commit), so stream-end truncation of the last chunk doesn't
        # read as rejection
        "acceptance_rate": (
            float(acc) / max(1, (gamma - 1) * verify_calls)
            if gamma > 1 else 0.0
        ),
    }
    return out, stats
