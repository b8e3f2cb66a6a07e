"""Causal transformer language model — the long-context flagship.

The reference zoo has no sequence model (its largest config is
ResNet50); this family exercises the capabilities the TPU rebuild adds
on top of reference parity: flash attention on one chip and ring
attention over the `sp` mesh axis for sequences that don't fit a single
device (parallel/context_parallel.py). Same zoo spec surface as every
other family (custom_model/loss/optimizer/dataset_fn/eval_metrics_fn).

Records are token sequences; the training pair is (tokens[:-1] →
tokens[1:]) built in dataset_fn, so seq_len below is the model's input
length and records carry seq_len + 1 tokens.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
from flax import linen as nn

from elasticdl_tpu.common.constants import MeshAxis, Mode
from elasticdl_tpu.data.example_codec import decode_example
from elasticdl_tpu.ops.attention import (
    NEG_INF,
    apply_rope,
    blockwise_attention,
    causal_limit,
    expand_kv,
    flash_attention,
    jax_flash_attention,
    packed_positions,
    paged_decode_attention,
)
from elasticdl_tpu.ops.losses import chunked_softmax_xent
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.parallel.context_parallel import (
    ring_attention,
    sharded_flash_attention,
    ulysses_attention,
)
from elasticdl_tpu.parallel.moe import (
    held_experts,
    route_sigmoid_top_k,
    route_top_k,
)
from model_zoo.transformer_lm.mamba2 import Mamba2Mixer


def _tp_dense_init(split_axis):
    """Megatron-style kernel annotation: split_axis=1 is column-parallel
    (outputs sharded over tp), split_axis=0 row-parallel (inputs sharded;
    XLA inserts the all-reduce on the partial sums). The annotations are
    metadata only — on a tp=1 mesh they are no-ops; on tp>1 meshes
    parallel/sharding.py collect_annotations turns them into placements
    and GSPMD propagates through the activations."""
    names = [None, None]
    names[split_axis] = MeshAxis.TP
    return nn.with_partitioning(
        nn.initializers.lecun_normal(), tuple(names)
    )


def _kv_quantize_rows(rows):
    """Symmetric per-row int8 for the KV cache: rows [b, hkv, t, d] ->
    (int8 rows, f32 scales [b, hkv, t, 1]); a zero row keeps scale 1 so
    it stays exactly zero."""
    r32 = rows.astype(jnp.float32)
    amax = jnp.max(jnp.abs(r32), axis=-1, keepdims=True)
    scale = jnp.where(amax > 0, amax / 127.0, 1.0)
    q8 = jnp.clip(jnp.round(r32 / scale), -127, 127).astype(jnp.int8)
    return q8, scale


class CausalSelfAttention(nn.Module):
    """Self-attention block shared by the decoder (causal=True) and the
    BERT-class encoder (causal=False, model_zoo/bert)."""

    num_heads: int
    head_dim: int
    dtype: object = None  # compute dtype (bf16 on TPU); params stay fp32
    # "auto": our Pallas flash on TPU; "xla": blockwise scan;
    # "jax_flash": jax's bundled TPU flash kernel (sweep alternative)
    attn_impl: str = "auto"
    sp_impl: str = "ring"  # sp>1 scheme: "ring" | "ulysses"
    tp_shard: bool = True
    causal: bool = True
    use_rope: bool = False  # rotary q/k (global positions; sp-safe)
    rope_theta: float = 10000.0
    window: int = 0  # sliding-window size; 0 = full attention
    cache_len: int = 0  # KV-cache capacity for decode mode
    # grouped-query attention: kv heads (0 = num_heads, i.e. standard
    # MHA; 1 = multi-query). Q head j reads kv head j // group. Shrinks
    # the qkv projection and the decode KV cache by num_heads/kv_heads;
    # the Pallas flash kernels consume the grouped layout natively.
    num_kv_heads: int = 0
    # LoRA (attention-only): rank-r adapter branches on the qkv and
    # output projections. The base Dense param paths are UNCHANGED, so
    # a dense pretraining checkpoint warm-starts this model
    # (restore strict=False); lora_b is zero-init, so the warm-started
    # model's logits equal the dense model's exactly until the
    # adapters train. Combine with trainable_pattern="lora" to train
    # adapters only.
    lora_rank: int = 0
    lora_alpha: float = 16.0
    # KV-cache storage format: "" = compute dtype; "int8" = symmetric
    # per-row int8 with f32 scales. Decode is cache-bandwidth-bound
    # (every generated token re-reads the whole cache), so int8 halves
    # (vs bf16) the dominant HBM stream; the dequantize fuses into the
    # attention reads. Write-side rounding costs one quantize per
    # generated token — negligible next to the read stream.
    kv_cache_dtype: str = ""
    # per-head RMSNorm of q and of k over head_dim, each with its own
    # learned gain (`q_norm/scale`, `k_norm/scale`), before the rotary
    qk_norm: bool = False
    qk_norm_eps: float = 1e-6
    # block-causal attention (a block-diffusion model): row i sees key
    # j iff j // block_causal <= i // block_causal, blocks aligned at
    # absolute position 0: causal across blocks, every position of a
    # row's own block in both directions. 0 = plain causal. Through
    # prefill, the decode tile over the paged pool and the dense cache
    block_causal: int = 0
    # an output GATE: a fifth projection `gate` (hidden -> heads x
    # head_dim, no bias) of the layer's input beside q, k and v, whose
    # sigmoid multiplies the heads' output before the output projection
    attn_gate: bool = False

    def _cache_vars(self, b, hkv, d, dtype):
        """The cache buffers in the configured storage format. Returns
        (ck, cv, k_scale, v_scale) — scale vars are None for the
        plain-dtype format."""
        if self.kv_cache_dtype not in ("", "int8"):
            raise ValueError(
                "Unknown kv_cache_dtype %r (valid: '', 'int8')"
                % (self.kv_cache_dtype,)
            )
        if self.kv_cache_dtype == "int8":
            shape = (b, hkv, self.cache_len, d)
            sshape = (b, hkv, self.cache_len, 1)
            return (
                self.variable("cache", "k", jnp.zeros, shape, jnp.int8),
                self.variable("cache", "v", jnp.zeros, shape, jnp.int8),
                self.variable("cache", "k_scale", jnp.zeros, sshape,
                              jnp.float32),
                self.variable("cache", "v_scale", jnp.zeros, sshape,
                              jnp.float32),
            )
        shape = (b, hkv, self.cache_len, d)
        return (
            self.variable("cache", "k", jnp.zeros, shape, dtype),
            self.variable("cache", "v", jnp.zeros, shape, dtype),
            None, None,
        )

    def _cache_write(self, cvars, k, v, idx):
        """Store chunk rows [b, hkv, t, d] at position idx (k already
        RoPE-rotated at its absolute positions)."""
        ck, cv, ks, vs = cvars
        if ks is None:
            ck.value = jax.lax.dynamic_update_slice(
                ck.value, k.astype(ck.value.dtype), (0, 0, idx, 0)
            )
            cv.value = jax.lax.dynamic_update_slice(
                cv.value, v.astype(cv.value.dtype), (0, 0, idx, 0)
            )
            return
        kq, ksc = _kv_quantize_rows(k)
        vq, vsc = _kv_quantize_rows(v)
        ck.value = jax.lax.dynamic_update_slice(
            ck.value, kq, (0, 0, idx, 0)
        )
        cv.value = jax.lax.dynamic_update_slice(
            cv.value, vq, (0, 0, idx, 0)
        )
        ks.value = jax.lax.dynamic_update_slice(
            ks.value, ksc, (0, 0, idx, 0)
        )
        vs.value = jax.lax.dynamic_update_slice(
            vs.value, vsc, (0, 0, idx, 0)
        )

    def _cache_read(self, cvars, dtype):
        """The full cache as compute-dtype floats; for int8 storage the
        dequantize (q8 * scale) fuses into the consuming attention
        einsums — the HBM stream stays int8."""
        ck, cv, ks, vs = cvars
        if ks is None:
            return ck.value, cv.value
        return (
            (ck.value.astype(jnp.float32) * ks.value).astype(dtype),
            (cv.value.astype(jnp.float32) * vs.value).astype(dtype),
        )

    def _lora_branch(self, x, features, name):
        """(x @ A @ B) * alpha/rank — A lecun-init, B zeros."""
        a = self.param(
            "%s_lora_a" % name, nn.initializers.lecun_normal(),
            (x.shape[-1], self.lora_rank),
        )
        b = self.param(
            "%s_lora_b" % name, nn.initializers.zeros,
            (self.lora_rank, features),
        )
        dtype = self.dtype or x.dtype
        return (
            (x @ a.astype(dtype)) @ b.astype(dtype)
        ) * (self.lora_alpha / self.lora_rank)

    @nn.compact
    def __call__(self, x, training=False, decode=False, decode_pos=None,
                 prefill=False, segments=None, positions=None,
                 paged=None):
        b, l, e = x.shape
        h, d = self.num_heads, self.head_dim
        hkv = self.num_kv_heads or h
        if h % hkv:
            raise ValueError(
                "num_heads (%d) must be a multiple of num_kv_heads (%d)"
                % (h, hkv)
            )
        qkv = nn.Dense(
            (h + 2 * hkv) * d, use_bias=False, dtype=self.dtype,
            name="qkv",
            kernel_init=(
                _tp_dense_init(1) if self.tp_shard
                else nn.initializers.lecun_normal()
            ),
        )(x)
        if self.lora_rank:
            qkv = qkv + self._lora_branch(x, (h + 2 * hkv) * d, "qkv")
        q = qkv[..., : h * d].reshape(b, l, h, d).transpose(0, 2, 1, 3)
        k = (
            qkv[..., h * d:(h + hkv) * d]
            .reshape(b, l, hkv, d).transpose(0, 2, 1, 3)
        )
        v = (
            qkv[..., (h + hkv) * d:]
            .reshape(b, l, hkv, d).transpose(0, 2, 1, 3)
        )  # q: [b, h, l, d]; k/v: [b, hkv, l, d]
        if self.qk_norm:
            with jax.named_scope("qk_norm"):
                q = nn.RMSNorm(epsilon=self.qk_norm_eps, dtype=self.dtype,
                               name="q_norm")(q)
                k = nn.RMSNorm(epsilon=self.qk_norm_eps, dtype=self.dtype,
                               name="k_norm")(k)
        if self.block_causal and not self.causal:
            raise ValueError("block_causal needs a causal model")
        gate = None
        if self.attn_gate:
            gate = nn.Dense(
                h * d, use_bias=False, dtype=self.dtype, name="gate",
                kernel_init=(
                    _tp_dense_init(1) if self.tp_shard
                    else nn.initializers.lecun_normal()
                ),
            )(x)
        if decode:
            return self._decode_step(q, k, v, e, decode_pos,
                                     paged=paged, gate=gate)
        if self.use_rope:
            pos = jnp.arange(l) if positions is None else positions
            q = apply_rope(q, pos, self.rope_theta)
            k = apply_rope(k, pos, self.rope_theta)
        if prefill:
            # Batched prompt prefill: one causal forward populates the
            # decode KV cache for positions [0, l) — O(prompt) single-
            # token steps collapse into one MXU-friendly pass. Cache
            # layout/dtype matches _decode_step exactly (grouped hkv
            # heads, k already RoPE-rotated at its absolute position).
            # Positions >= the true prompt length hold pad-token junk;
            # that is safe because decode masks k_pos <= counter and
            # overwrites each position before first attending to it.
            if not self.causal:
                raise ValueError("prefill requires a causal model")
            _mesh = mesh_lib.current_mesh()
            if _mesh is not None and _mesh.shape.get(MeshAxis.SP, 1) > 1:
                raise NotImplementedError(
                    "prefill is single-shard (like decode); drop the "
                    "sp axis for generation"
                )
            if self.cache_len < l:
                raise ValueError(
                    "prefill length %d exceeds cache_len %d"
                    % (l, self.cache_len)
                )
            cvars = self._cache_vars(b, hkv, d, q.dtype)
            self._cache_write(cvars, k, v, 0)
            if self.kv_cache_dtype == "int8":
                # SELF-CONSISTENCY: attend over the rows decode will
                # re-read. The cache stores quantized rows; if prefill
                # attended the original floats, any later recompute of
                # these logits from the cache (a paged shared-prefix
                # seat re-running the last prompt token over resident
                # int8 blocks; a speculative verify tile) would see
                # different values and greedy parity across the
                # offline/serving seams would break. Quantize-dequant
                # here is a one-time prefill cost (the rows are live
                # floats anyway) — decode's per-step reads stay int8
                # with the deferred dequantize.
                kq, ksc = _kv_quantize_rows(k)
                vq, vsc = _kv_quantize_rows(v)
                k = (kq.astype(jnp.float32) * ksc).astype(q.dtype)
                v = (vq.astype(jnp.float32) * vsc).astype(q.dtype)
        if self.attn_impl not in ("auto", "xla", "jax_flash"):
            raise ValueError(
                "Unknown attn_impl %r (valid: 'auto', 'xla', "
                "'jax_flash')" % (self.attn_impl,)
            )
        if self.kv_cache_dtype not in ("", "int8"):
            # eager: a typo must fail the first TRAINING forward, not
            # hours later at the first cached generation
            raise ValueError(
                "Unknown kv_cache_dtype %r (valid: '', 'int8')"
                % (self.kv_cache_dtype,)
            )
        window = self.window or None
        mesh = mesh_lib.current_mesh()
        if self.block_causal > 1 and (
                self.attn_impl == "jax_flash"
                or (mesh is not None and mesh.size > 1)):
            raise NotImplementedError(
                "block_causal attention runs on one device through "
                "attn_impl 'auto' or 'xla'; the sharded, ring, ulysses "
                "and jax_flash paths have no such mask")
        if mesh is not None and mesh.shape.get(MeshAxis.SP, 1) > 1:
            # ring merges partials per kv rotation and ulysses
            # all-to-alls the head axis over sp — both want the full
            # head count, so GQA kv expands here (the grouped layout
            # still pays off in params and the decode cache)
            k = expand_kv(k, h)
            v = expand_kv(v, h)
            if self.sp_impl == "ulysses":
                out = ulysses_attention(
                    q, k, v, mesh, causal=self.causal,
                    attn_impl=self.attn_impl, segments=segments,
                    window=window,
                )
            elif self.sp_impl == "ring":
                if self.attn_impl == "jax_flash":
                    # the ring merges (o, logsumexp) partials per
                    # rotation; jax's bundled kernel doesn't expose lse
                    raise ValueError(
                        "attn_impl='jax_flash' is incompatible with "
                        "sp_impl='ring' (no logsumexp output); use "
                        "sp_impl='ulysses' or attn_impl='auto'"
                    )
                out = ring_attention(q, k, v, mesh, causal=self.causal,
                                     segments=segments, window=window)
            else:
                raise ValueError(
                    "Unknown sp_impl %r (valid: 'ring', 'ulysses')"
                    % (self.sp_impl,)
                )
        elif self.attn_impl == "xla":
            out = blockwise_attention(
                q, k, v, causal=self.causal, window=window,
                segments=segments, block_causal=self.block_causal,
            )
        elif self.attn_impl == "jax_flash":
            if segments is not None:
                raise ValueError(
                    "attn_impl='jax_flash' does not support packed-"
                    "sequence masking; use attn_impl='auto' or 'xla'"
                )
            out = jax_flash_attention(
                q, k, v, causal=self.causal, window=window
            )
        elif mesh is not None and mesh.size > 1:
            out = sharded_flash_attention(
                q, k, v, mesh, causal=self.causal, window=window,
                segments=segments,
            )
        else:  # "auto" (validated above) on one device
            out = flash_attention(
                q, k, v, causal=self.causal, window=window,
                segments=segments, block_causal=self.block_causal,
            )
        out = out.transpose(0, 2, 1, 3).reshape(b, l, h * d)
        return self._proj(out, e, gate)

    def _proj(self, out, e, gate=None):
        if gate is not None:
            with jax.named_scope("attn_gate"):
                out = out * jax.nn.sigmoid(gate).astype(out.dtype)
        y = nn.Dense(
            e, use_bias=False, dtype=self.dtype, name="proj",
            kernel_init=(
                _tp_dense_init(0) if self.tp_shard
                else nn.initializers.lecun_normal()
            ),
        )(out)
        if self.lora_rank:
            y = y + self._lora_branch(out, e, "proj")
        return y

    def _decode_step(self, q, k, v, e, decode_pos, paged=None,
                     gate=None):
        """Chunked decode against the KV cache: q is [b, h, t, d],
        k/v [b, hkv, t, d] for a chunk of t >= 1 tokens at absolute
        positions [decode_pos, decode_pos + t) — t = 1 is the classic
        per-token step; t > 1 is the speculative-verify / chunked-
        prefill-continuation step (one batched read of the cache for t
        queries instead of t reads). Cached keys/values live in the
        `cache` collection in the GROUPED head count — the GQA memory
        win: cache reads scale with hkv, not h. `decode_pos` comes from
        the model's single cache counter (one source of truth —
        per-layer counters could only drift apart). RoPE rotates q/k at
        their absolute positions; row i of the chunk masks
        `k_pos <= pos + i` (windowing `k_pos > pos + i - window`).

        `paged` (serving only): {"k": pool, "v": pool, "table": [b, m]}
        — this layer's slice of the block-paged serving KV pool
        (serving/kv_pool.py). The cached rows then live in the SHARED
        block arenas instead of per-sequence flax cache buffers:
        attention streams the sequence's block table
        (ops.paged_decode_attention) and the new token's k/v rows are
        SOWN into the "kv_out" collection for the engine to scatter
        into the pool — a module has no business writing an arena it
        shares with every other sequence. With kv_cache_dtype="int8"
        the dict also carries "k_scale"/"v_scale" arenas; rows are
        quantized HERE (at the sow — the one insertion point) and the
        dequantize defers into the attention scan, so the arenas
        stream int8 end to end."""
        if not self.causal:
            raise ValueError("decode mode requires a causal model")
        if self.cache_len < 1:
            raise ValueError("decode mode needs cache_len >= 1")
        if decode_pos is None:
            raise ValueError("decode mode needs decode_pos")
        b, h, t, d = q.shape
        hkv = k.shape[1]
        group = h // hkv
        dtype = q.dtype
        idx = decode_pos
        if self.use_rope:
            pos = idx + jnp.arange(t)
            q = apply_rope(q, pos, self.rope_theta)
            k = apply_rope(k, pos, self.rope_theta)
        if paged is not None:
            # t = 1: the classic per-token step. t > 1: a query TILE —
            # the speculative verify-k step and the shared-prefix
            # suffix prefill both decode t tokens at positions
            # [idx, idx + t) in ONE batched read of the pool, causal
            # within the tile (ops.paged_decode_attention).
            if self.kv_cache_dtype == "int8":
                # QUANTIZE AT INSERTION: the tile's rows are quantized
                # here, once, and sown in arena format (int8 rows +
                # f32 per-row scales) — the engine scatters them
                # verbatim, so the arenas only ever hold quantized
                # data and every later read defers the dequantize into
                # the scan (no float cache copy anywhere). Attention
                # over the tile's OWN keys uses the quantized rows
                # too, exactly like the dense int8 path that writes
                # the cache before reading it back.
                kq, ksc = _kv_quantize_rows(k)
                vq, vsc = _kv_quantize_rows(v)
                self.sow("kv_out", "k", kq)
                self.sow("kv_out", "v", vq)
                self.sow("kv_out", "k_scale", ksc)
                self.sow("kv_out", "v_scale", vsc)
                out = paged_decode_attention(
                    q, kq, vq,
                    paged["k"], paged["v"], paged["table"],
                    jnp.broadcast_to(idx, (b,)),
                    scale=d ** -0.5, window=self.window or None,
                    k_scale_pool=paged["k_scale"],
                    v_scale_pool=paged["v_scale"],
                    k_cur_scale=ksc, v_cur_scale=vsc,
                    block_causal=self.block_causal,
                ).astype(dtype)
                out = out.transpose(0, 2, 1, 3).reshape(b, t, h * d)
                return self._proj(out, e, gate)
            self.sow("kv_out", "k", k)  # [b, hkv, t, d] for the
            self.sow("kv_out", "v", v)  # engine's pool scatter
            out = paged_decode_attention(
                q, k, v,
                paged["k"], paged["v"], paged["table"],
                jnp.broadcast_to(idx, (b,)),
                scale=d ** -0.5, window=self.window or None,
                block_causal=self.block_causal,
            ).astype(dtype)
            out = out.transpose(0, 2, 1, 3).reshape(b, t, h * d)
            return self._proj(out, e, gate)
        cvars = self._cache_vars(b, hkv, d, dtype)
        self._cache_write(cvars, k, v, idx)
        scale = d ** -0.5
        # group the q heads under their kv head: [b, hkv, group, t, d]
        qg = (q * scale).reshape(b, hkv, group, t, d)
        ck, cv, ks, vs = cvars
        if ks is None:
            s = jnp.einsum(
                "bhgtd,bhkd->bhgtk", qg, ck.value
            ).astype(jnp.float32)  # [b, hkv, group, t, L]
        else:
            # int8 cache, DEFERRED dequantize: fold the per-row scales
            # into the scores instead of materializing a float copy of
            # the whole cache every step — the scale multiply runs on
            # [*, L] scores, a head_dim-times smaller array than the
            # [*, L, d] rows (the decode_kv_int8 bench regression)
            s = jnp.einsum(
                "bhgtd,bhkd->bhgtk", qg, ck.value.astype(dtype)
            ).astype(jnp.float32) * ks.value[..., 0][:, :, None, None]
        k_pos = jnp.arange(self.cache_len)[None, :]
        row_pos = (idx + jnp.arange(t))[:, None]
        valid = k_pos <= causal_limit(row_pos, self.block_causal)
        if self.window:
            valid = valid & (k_pos > row_pos - self.window)
        s = jnp.where(valid[None, None, None], s, NEG_INF)
        w = jax.nn.softmax(s, axis=-1)
        if vs is None:
            out = jnp.einsum(
                "bhgtk,bhkd->bhgtd", w.astype(dtype), cv.value
            )
        else:
            # v-side deferral: scale the [*, L] weights, read int8 rows
            out = jnp.einsum(
                "bhgtk,bhkd->bhgtd",
                (w * vs.value[..., 0][:, :, None, None]).astype(dtype),
                cv.value.astype(dtype),
            )
        # (hkv, group) flattens back to h in q's head order
        out = out.transpose(0, 3, 1, 2, 4).reshape(b, t, h * d)
        return self._proj(out, e, gate)


def _norm(kind, dtype, eps, name=None):
    """The stack's normalisation: "layer" (LayerNorm, scale and bias)
    or "rms" (RMSNorm, scale only). Unnamed it takes flax's own name
    in its parent (`LayerNorm_0`, `RMSNorm_0`, ...)."""
    if kind == "layer":
        return nn.LayerNorm(epsilon=eps, dtype=dtype, name=name)
    if kind == "rms":
        return nn.RMSNorm(epsilon=eps, dtype=dtype, name=name)
    raise ValueError("Unknown norm %r (valid: 'layer', 'rms')" % (kind,))


class ExpertFFN(nn.Module):
    """The block's MLP slot as a drop-free expert layer
    (parallel/moe.held_experts): a router over ALL `num_experts`
    (float32, its logits at full precision), top `top_k` a token,
    experts of width `hidden`, and this chip's share of them, `held =
    (first, count)`: the router keeps its width, the weights are the
    held experts' only, and what the others would add is left out. `()`
    holds them all. What kind of layer it is comes from parameters:

    * `activation`: "reglu", gated experts of three matrices,
      (relu(h W_gate) * (h W_up)) W_down; "swiglu", the same with silu
      in relu's place; "relu2", experts of two,
      relu(h W_up^T)^2 W_down: there is no `w_gate`, and `w_up` is
      [count, hidden, d] like `w_down`, a hidden unit a row, as a
      checkpoint stores an up projection (ops/expert_ffn.py says what
      the other orientation costs on the chip);
    * `scoring`: "softmax", the k largest logits and a softmax over
      those; "sigmoid", scores sigmoid(logits), the k largest of score
      + `router_bias` (a parameter that only selects), weights
      `route_scale` * score / the chosen scores' sum;
    * `shared_hidden` > 0: one more expert of that width and the
      experts' form that every token passes through, unweighted
      (`shared_up`, `shared_down`, and `shared_gate` where the experts
      are gated): a plain dense product that every chip of a
      deployment computes alike, counted once.

    `route_from` is what the router reads (a block with attention
    hands its own input, so that routing is known before attention
    runs; a layer that is the expert layer alone, the normed input the
    experts multiply); `h` what the experts multiply.

    `live` (bool [b] or [b * l]; None = every row, and then the layer
    is built of the same operations as before the argument existed)
    says which rows carry a sequence. A row that carries none MAKES NO
    CHOICE: the router still runs over it, its choices are then
    written over with -1 (parallel/moe.held_experts: no choice), so no
    expert is read for its sake and its routed part is exactly 0; the
    shared expert, a dense product of all rows, runs as ever. The
    decode step hands in which lanes are seated; nobody reads what a
    free lane computes.

    Where the caller collects "counters" (the serving step) it is
    handed what the layer did, of LIVE rows only: `moe.pairs_routed`,
    `moe.pairs_held` (scalars: live rows x `top_k`, and those of them
    held here), `moe.experts_hit`, `moe.expert_slots` (a mark an
    expert held: chosen by a live row; held at all), `moe.lanes`,
    `moe.lanes_live` (scalars: this call's rows, and those that
    chose), and `moe.tile_rows` (a mark of one item: the rows of the
    tiles `held_experts` computed, the tick's and not a lane's where
    lanes are mapped, so it counts once; `moe.pairs_held` over it is
    the share of the multiplied rows that are a held pair)."""

    num_experts: int
    top_k: int
    hidden: int
    held: tuple = ()
    dtype: object = None
    activation: str = "reglu"
    scoring: str = "softmax"
    route_scale: float = 1.0
    shared_hidden: int = 0

    @nn.compact
    def __call__(self, h, route_from, training=False, live=None):
        b, l, d = h.shape
        first, count = self.held or (0, self.num_experts)
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(
                "experts_held %r is no range of %d experts"
                % (self.held, self.num_experts))
        if self.activation not in ("reglu", "swiglu", "relu2"):
            raise ValueError("Unknown moe_activation %r (valid: 'reglu', "
                             "'swiglu', 'relu2')" % (self.activation,))
        if self.scoring not in ("softmax", "sigmoid"):
            raise ValueError("Unknown moe_scoring %r (valid: 'softmax', "
                             "'sigmoid')" % (self.scoring,))
        dtype = self.dtype or h.dtype

        def bank(fan_in):
            return nn.with_partitioning(
                nn.initializers.normal(fan_in ** -0.5),
                (MeshAxis.EP, None, None))

        router = self.param("router", nn.initializers.normal(d ** -0.5),
                            (d, self.num_experts), jnp.float32)
        into, back = (count, d, self.hidden), (count, self.hidden, d)
        banks = ((("w_gate", into, d), ("w_up", into, d))
                 if self.activation != "relu2" else (("w_up", back, d),))
        weights = [
            jnp.asarray(self.param(name, bank(fan_in), shape,
                                   jnp.float32), dtype)
            for name, shape, fan_in in banks + (
                ("w_down", back, self.hidden),)
        ]
        with jax.named_scope("moe_router"):
            logits = jnp.matmul(
                route_from.reshape(b * l, d).astype(jnp.float32), router,
                precision=jax.lax.Precision.HIGHEST)
            if self.scoring == "sigmoid":
                gates, experts = route_sigmoid_top_k(
                    logits, self.top_k,
                    self.param("router_bias", nn.initializers.zeros,
                               (self.num_experts,), jnp.float32),
                    self.route_scale)
            else:
                gates, experts = route_top_k(logits, self.top_k)
            if live is not None:
                live = jnp.broadcast_to(
                    jnp.asarray(live, bool).reshape(b, -1),
                    (b, l)).reshape(b * l)
                experts = jnp.where(live[:, None], experts, -1)
        rows = h.reshape(b * l, d).astype(dtype)
        with jax.named_scope("moe_experts"):
            # the Mosaic kernel has no backward: a training forward
            # takes the plain products
            y, held, hit, tile_rows = held_experts(
                rows, gates, experts, weights, first=first,
                use_kernel=False if training else None,
                activation=self.activation)
        if self.shared_hidden:
            with jax.named_scope("moe_shared"):
                into = (d, self.shared_hidden)
                *gate, up, down = (
                    jnp.asarray(self.param(
                        name, nn.initializers.normal(shape[0] ** -0.5),
                        shape, jnp.float32), dtype)
                    for name, shape in (
                        (("shared_gate", into),)
                        if self.activation != "relu2" else ()) + (
                        ("shared_up", into),
                        ("shared_down", (self.shared_hidden, d))))
                act = jnp.dot(rows, up, preferred_element_type=jnp.float32)
                if gate:  # gated like the experts: act(h W_gate) * (h W_up)
                    act = (jax.nn.silu if self.activation == "swiglu"
                           else jax.nn.relu)(jnp.dot(
                               rows, gate[0],
                               preferred_element_type=jnp.float32)) * act
                else:
                    act = jnp.square(jnp.maximum(act, 0.0))
                y = y + jnp.dot(act.astype(dtype), down,
                                preferred_element_type=jnp.float32)
        if (self.is_mutable_collection("counters")
                and not self.is_initializing()):
            lanes_live = b * l if live is None else jnp.sum(live)
            counts = {
                "moe.pairs_routed": lanes_live * self.top_k,
                "moe.pairs_held": jnp.sum(held),
                "moe.experts_hit": hit,
                "moe.expert_slots": jnp.ones_like(hit),
                "moe.lanes": b * l,
                "moe.lanes_live": lanes_live,
                "moe.tile_rows": tile_rows.reshape(1),
            }
            for name, value in counts.items():
                self.sow("counters", name, jnp.asarray(value, jnp.int32))
        return y.reshape(b, l, d)


class Block(nn.Module):
    """THE block of the stack, configured per layer: normalisation,
    rotary on or off (and its theta), this layer's window, and what
    sits in the MLP slot (the dense GELU MLP, the dense gated "swiglu"
    MLP of `dense_hidden`, or the expert layer fed by the block's own
    input). `sandwich_norm` norms each sublayer's OUTPUT too, before
    the residual add. `kind` "" is that block, attention then
    MLP. A stack that names its layers' kinds (`layer_kinds`) makes a
    layer ONE mixer behind one norm with one residual, x + f(norm(x)):
    "*" attention alone, "M" the Mamba-2 mixer alone (`ssm`), "E" the
    expert layer alone, its router reading the same normed input."""

    num_heads: int
    head_dim: int
    mlp_ratio: int = 4
    dtype: object = None
    attn_impl: str = "auto"
    sp_impl: str = "ring"
    tp_shard: bool = True
    causal: bool = True
    use_rope: bool = False
    rope_theta: float = 10000.0
    window: int = 0
    cache_len: int = 0
    num_kv_heads: int = 0  # grouped-query attention (0 = MHA)
    lora_rank: int = 0
    lora_alpha: float = 16.0
    kv_cache_dtype: str = ""  # "" | "int8" (see CausalSelfAttention)
    norm: str = "layer"  # "layer" | "rms"
    norm_eps: float = 1e-6
    # "gelu" dense MLP | "swiglu" dense gated MLP | "moe_reglu" expert
    # layer
    mlp: str = "gelu"
    dense_hidden: int = 0  # the "swiglu" MLP's width
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_hidden: int = 0
    experts_held: tuple = ()
    moe_activation: str = "reglu"
    moe_scoring: str = "softmax"
    moe_route_scale: float = 1.0
    moe_shared_hidden: int = 0
    # what the router of an attention-then-MLP block reads: "input",
    # the block's own input (routing known before attention runs);
    # "mlp", the normed input the experts multiply
    moe_route_from: str = "input"
    qk_norm: bool = False
    block_causal: int = 0
    attn_gate: bool = False  # CausalSelfAttention
    # a norm on each sublayer's output before the residual add
    # (`post_attn_norm`, `post_mlp_norm`), beside the two on its input
    sandwich_norm: bool = False
    kind: str = ""  # "" attention then MLP | "*" | "M" | "E"
    ssm: tuple = ()  # Mamba2Mixer's fields, as sorted (name, value)

    def _attention(self):
        return CausalSelfAttention(
            self.num_heads, self.head_dim, dtype=self.dtype,
            attn_impl=self.attn_impl, sp_impl=self.sp_impl,
            tp_shard=self.tp_shard, causal=self.causal,
            use_rope=self.use_rope, rope_theta=self.rope_theta,
            window=self.window,
            cache_len=self.cache_len,
            num_kv_heads=self.num_kv_heads,
            lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
            kv_cache_dtype=self.kv_cache_dtype,
            qk_norm=self.qk_norm, qk_norm_eps=self.norm_eps,
            block_causal=self.block_causal,
            attn_gate=self.attn_gate,
            name="attn",
        )

    def _experts(self):
        return ExpertFFN(
            self.moe_experts, self.moe_top_k, self.moe_hidden,
            held=tuple(self.experts_held), dtype=self.dtype,
            activation=self.moe_activation, scoring=self.moe_scoring,
            route_scale=self.moe_route_scale,
            shared_hidden=self.moe_shared_hidden,
            name="moe",
        )

    @nn.compact
    def __call__(self, x, training=False, decode=False, decode_pos=None,
                 prefill=False, segments=None, positions=None,
                 paged=None, prompt_len=None, live=None):
        # `live`: which rows carry a sequence (ExpertFFN), None = all
        e = x.shape[-1]
        block_in = x
        if self.sandwich_norm and self.kind:
            raise ValueError(
                "sandwich_norm is built for the attention-then-MLP "
                "block, not for a layer of one mixer (layer_kinds)")

        def post(y, name):
            # the sublayer's output under a norm of its own
            if not self.sandwich_norm:
                return y
            with jax.named_scope("post_norm"):
                return _norm(self.norm, self.dtype, self.norm_eps,
                             name=name)(y)

        y = _norm(self.norm, self.dtype, self.norm_eps)(x)
        if self.kind == "M":
            if segments is not None:
                raise ValueError(
                    "a state-space layer does not restart its state at "
                    "a packed document's boundary yet: no segment_ids")
            y = Mamba2Mixer(dtype=self.dtype, name="ssm",
                            **dict(self.ssm))(
                y, decode=decode, prefill=prefill, prompt_len=prompt_len)
            return x + y.astype(x.dtype)
        if self.kind == "E":
            return x + self._experts()(
                y, y, training, live=live).astype(x.dtype)
        x = x + post(self._attention()(
            y, training, decode=decode, decode_pos=decode_pos,
            prefill=prefill, segments=segments, positions=positions,
            paged=paged), "post_attn_norm")
        if self.kind == "*":
            return x
        if self.kind:
            raise ValueError(
                "Unknown layer kind %r (valid: 'M', 'E', '*')"
                % (self.kind,))
        y = _norm(self.norm, self.dtype, self.norm_eps)(x)
        if self.mlp == "moe_reglu":
            if self.moe_route_from not in ("input", "mlp"):
                raise ValueError(
                    "Unknown moe_route_from %r (valid: 'input', 'mlp')"
                    % (self.moe_route_from,))
            y = self._experts()(
                y, block_in if self.moe_route_from == "input" else y,
                training, live=live)
            return x + post(y.astype(x.dtype), "post_mlp_norm")
        if self.mlp not in ("gelu", "swiglu"):
            raise ValueError(
                "Unknown mlp %r (valid: 'gelu', 'swiglu', 'moe_reglu')"
                % (self.mlp,))
        up_init = (
            _tp_dense_init(1) if self.tp_shard
            else nn.initializers.lecun_normal()
        )
        down_init = (
            _tp_dense_init(0) if self.tp_shard
            else nn.initializers.lecun_normal()
        )
        if self.mlp == "swiglu":
            # the dense gated MLP, no bias: (silu(y W_gate) * (y W_up))
            # W_down, of width `dense_hidden`
            if self.dense_hidden < 1:
                raise ValueError("mlp 'swiglu' needs dense_hidden")
            with jax.named_scope("dense_mlp"):
                gate, up = (
                    nn.Dense(self.dense_hidden, use_bias=False,
                             dtype=self.dtype, kernel_init=up_init,
                             name=name)(y)
                    for name in ("mlp_gate", "mlp_up"))
                y = nn.Dense(
                    e, use_bias=False, dtype=self.dtype,
                    kernel_init=down_init, name="mlp_down",
                )(nn.silu(gate) * up)
            return x + post(y, "post_mlp_norm")
        y = nn.Dense(
            self.mlp_ratio * e, dtype=self.dtype, kernel_init=up_init,
            name="mlp_up",
        )(y)
        y = nn.gelu(y)
        y = nn.Dense(
            e, dtype=self.dtype, kernel_init=down_init, name="mlp_down"
        )(y)
        return x + post(y, "post_mlp_norm")


class LMHead(nn.Module):
    """Vocab projection. In fused mode it returns the hidden states and
    the kernel instead of running the matmul, so the loss can stream the
    head over sequence chunks (ops/losses.chunked_softmax_xent) and never
    materialize the full [b, s, vocab] fp32 logits — peak residency is
    O(b * s/num_chunks * vocab). The param path stays `head/kernel`,
    checkpoint-compatible with the plain Dense."""

    vocab_size: int
    dtype: object = None
    kernel_init: object = nn.initializers.lecun_normal()

    @nn.compact
    def __call__(self, x, fused=False):
        kernel = self.param(
            "kernel", self.kernel_init,
            (x.shape[-1], self.vocab_size), jnp.float32,
        )
        if fused:
            return x, kernel
        logits = x @ jnp.asarray(kernel, self.dtype or x.dtype)
        # loss math (softmax xent) wants fp32 logits regardless of the
        # compute dtype
        return logits.astype(jnp.float32)


def setup_decode_positions(mdl, tokens, decode, prefill, prompt_len):
    """THE KV-decode position convention, shared by every decoder
    family (TransformerLM here, TransformerMoE via import) so
    api/generation.py's prefill/decode contract lives in one place:

      * decode: one cached scalar counter ("cache"/"pos") that every
        layer's cache write and the position-embedding lookup read;
        advances by the chunk width (tokens [b, t], t >= 1).
      * prefill: the counter is SET to the true prompt length (may be
        < the padded prefill width) so the next decode step writes
        position prompt_len.

    Returns (decode_pos, wpe_idx): the pre-advance counter (None unless
    decode) and the [1, t] index array a learned position table should
    look up for this call."""
    t = tokens.shape[1]
    decode_pos = None
    if decode:
        pi = mdl.variable(
            "cache", "pos", lambda: jnp.zeros((), jnp.int32)
        )
        decode_pos = pi.value
        pi.value = decode_pos + t
        idx = decode_pos + jnp.arange(t)
        # Decode TILES (speculative verify, shared-prefix suffix
        # prefill) may carry PAD rows whose positions run past
        # seq_len. An out-of-bounds wpe gather fills NaN under jit,
        # and a NaN k/v row poisons the whole tile through the
        # attention value sum (0 weight x NaN = NaN) — clamp to the
        # table. Real rows are always in bounds (the engine admits
        # nothing past seq_len), so this only sanitizes pad rows,
        # whose outputs are never read.
        cap = getattr(mdl, "seq_len", None)
        if cap is not None:
            idx = jnp.minimum(idx, cap - 1)
        idx = idx[None, :]
    else:
        if prefill:
            if prompt_len is None:
                raise ValueError("prefill needs prompt_len")
            pi = mdl.variable(
                "cache", "pos", lambda: jnp.zeros((), jnp.int32)
            )
            pi.value = jnp.asarray(prompt_len, jnp.int32)
        idx = jnp.arange(t)[None, :]
    return decode_pos, idx


class TransformerLM(nn.Module):
    vocab_size: int = 256
    seq_len: int = 128
    embed_dim: int = 128
    num_heads: int = 4
    num_layers: int = 2
    dtype: object = None  # compute dtype; None = fp32
    attn_impl: str = "auto"
    sp_impl: str = "ring"  # sequence-parallel scheme: "ring" | "ulysses"
    pos_emb: str = "learned"  # "learned" wpe table | "rope" rotary q/k
    attn_window: int = 0  # sliding-window attention; 0 = full
    # The stack is ONE block driven by a per-layer pattern. Each layout
    # is a 0/1 entry a layer (empty = every layer alike): which layers
    # rotate q and k (pos_emb="rope"; a 0 is a layer with no positional
    # encoding at all) and which keep keys to `attn_window` (a 0 sees
    # every earlier key).
    rope_layout: tuple = ()
    window_layout: tuple = ()
    rope_theta: float = 10000.0
    head_dim: int = 0  # 0 = embed_dim // num_heads
    norm: str = "layer"  # "layer" LayerNorm | "rms" RMSNorm
    norm_eps: float = 1e-6
    # the MLP slot: "gelu" = the dense 4x GELU MLP; "swiglu" = the
    # dense gated MLP of width `dense_hidden`, no bias; "moe_reglu" =
    # the drop-free gated expert layer (ExpertFFN): a router over
    # `moe_experts`, `moe_top_k` a token, experts of width
    # `moe_hidden`, of which this chip holds `experts_held = (first,
    # count)` (() = all). `mlp_layout`, a 0/1 entry a layer like the
    # rotary and window layouts: a 0 is a layer whose slot holds the
    # dense gated MLP instead of `mlp` (leading dense layers before
    # expert layers)
    mlp: str = "gelu"
    mlp_layout: tuple = ()
    dense_hidden: int = 0
    moe_experts: int = 0
    moe_top_k: int = 0
    moe_hidden: int = 0
    experts_held: tuple = ()
    # what the expert layer is (ExpertFFN): experts "reglu" (gated,
    # three matrices) or "relu2" (two); the router's scoring "softmax"
    # (over the chosen logits) or "sigmoid" (selection bias,
    # renormalised, times `moe_route_scale`); a shared expert of width
    # `moe_shared_hidden` every token passes through (0 = none)
    moe_activation: str = "reglu"
    moe_scoring: str = "softmax"
    moe_route_scale: float = 1.0
    moe_shared_hidden: int = 0
    moe_route_from: str = "input"  # | "mlp" (Block)
    # per-head RMSNorm of q and k (CausalSelfAttention), and the block
    # length of block-causal attention (0 = causal): a block-diffusion
    # model, which the serving engine decodes a block of positions a
    # lane a tick (serving/engine.py, BLOCK TICK)
    qk_norm: bool = False
    block_causal: int = 0
    # the id such a model reads at a position not revealed yet (the
    # engine refuses a block model without one)
    mask_token: int = -1
    # an output gate on attention (CausalSelfAttention), a norm on
    # each sublayer's output before the residual add (Block), and the
    # embedding multiplied by sqrt(embed_dim)
    attn_gate: bool = False
    sandwich_norm: bool = False
    embed_scale: bool = False
    # a character a layer, "" = every layer attention then MLP as
    # above: "*" a layer that is attention alone, "M" the Mamba-2
    # mixer alone, "E" the expert layer alone, each x + f(norm(x)).
    # The rotary and window layouts keep an entry a LAYER (read at the
    # attention layers only)
    layer_kinds: str = ""
    # the Mamba-2 mixer's sizes (model_zoo/transformer_lm/mamba2.py)
    ssm_heads: int = 64
    ssm_head_dim: int = 64
    ssm_groups: int = 8
    ssm_state: int = 128
    ssm_conv: int = 4
    ssm_chunk: int = 128
    tp_shard: bool = True  # annotate kernels over the tp mesh axis
    fused_head: bool = False  # stream the LM head inside the loss
    num_kv_heads: int = 0  # grouped-query attention (0 = MHA)
    lora_rank: int = 0  # attention-LoRA adapters (0 = off)
    lora_alpha: float = 16.0
    # Per-block rematerialization for the training forward: recompute
    # activations in the backward instead of saving them, trading
    # ~1 extra block forward of FLOPs for O(num_layers) less live
    # memory — the knob that admits larger global batches (bigger MXU
    # tiles) when HBM, not FLOPs, limits the step. "" = off;
    # "full" = save only block boundaries; "dots" = additionally save
    # matmul outputs (jax dots_with_no_batch_dims_saveable — cheaper
    # backward, smaller memory win). Decode/prefill are untouched.
    remat: str = ""
    # KV-cache storage: "" = compute dtype; "int8" halves (vs bf16) the
    # decode path's dominant HBM stream (see CausalSelfAttention)
    kv_cache_dtype: str = ""

    def _layout(self, name):
        layout = tuple(getattr(self, name))
        if layout and len(layout) != self.num_layers:
            raise ValueError(
                "%s has %d entries for %d layers"
                % (name, len(layout), self.num_layers))
        return layout or (1,) * self.num_layers

    def _kinds(self):
        kinds = str(self.layer_kinds)
        if kinds and (len(kinds) != self.num_layers
                      or set(kinds) - set("ME*")):
            raise ValueError(
                "layer_kinds %r is not %d of 'M', 'E', '*'"
                % (kinds, self.num_layers))
        return kinds or ("",) * self.num_layers

    def layer_windows(self):
        """The window (0 = every earlier key) of each layer that has
        attention, in order: what the serving engine counts a tick's
        reach by."""
        return tuple(
            self.attn_window if on else 0
            for on, kind in zip(self._layout("window_layout"),
                                self._kinds()) if kind in ("", "*"))

    def cache_leaf_window(self, path):
        """The attention window (0 = every earlier key) of the layer a
        leaf of the decode cache belongs to, by its path: what the
        serving pool groups its block classes by."""
        layer = str(path[0])
        if not layer.startswith("block_"):
            return 0
        on = self._layout("window_layout")[int(layer[len("block_"):])]
        return self.attn_window if on else 0

    def cache_leaf_kind(self, path):
        """What a leaf of this model's decode cache is, by its path
        (api/generation.cache_leaf_kinds): the "rows" of an attention
        layer, a cached token each; the per-sequence "state" of a
        state-space layer; the "scalar" position counter."""
        if "ssm" in path:
            return "state"
        return "rows" if "attn" in path else "scalar"

    @nn.compact
    def __call__(self, features, training=False, decode=False,
                 prefill=False, prompt_len=None, paged=None):
        # `paged` (decode only): the serving engine's block-paged KV
        # pool — {"pools": tree mirroring this model's cache collection
        # with per-layer [num_blocks, block_size, hkv, d] arenas,
        # "table": [b, m] int32 block table}. Each block slices out its
        # own layer's arenas below; see serving/kv_pool.py. A pool of
        # several block classes adds "table_of": {block name: (first,
        # end) columns of "table"} (static), its class's table. The decode
        # step adds "live": [b] bool, which rows carry a sequence (a
        # free lane does not): the expert layers read no expert for a
        # row that carries none (ExpertFFN).
        tokens = features["tokens"]  # [b, seq_len]; [b, 1] when decode
        if decode and prefill:
            raise ValueError("decode and prefill are mutually exclusive")
        if paged is not None and not decode:
            raise ValueError("paged KV applies to decode mode only")
        # sequence packing: [b, seq_len] int ids of contiguous same-id
        # runs. Attention is confined to each run and positions restart
        # at run boundaries (the packed rows behave exactly like the
        # unpacked sequences stacked into separate batch rows).
        segments = features.get("segment_ids")
        positions = None
        if segments is not None:
            if decode or prefill:
                raise ValueError(
                    "segment_ids apply to training/eval forwards, not "
                    "decode/prefill"
                )
            segments = jnp.asarray(segments, jnp.int32)
            positions = packed_positions(segments)
        x = nn.Embed(
            self.vocab_size, self.embed_dim, dtype=self.dtype, name="wte"
        )(tokens)
        if self.embed_scale:
            x = x * jnp.asarray(self.embed_dim ** 0.5, x.dtype)
        # shared decode-counter convention (setup_decode_positions):
        # the counter drives every layer's cache write, RoPE rotation
        # and the wpe lookup
        decode_pos, wpe_idx = setup_decode_positions(
            self, tokens, decode, prefill, prompt_len
        )
        if self.pos_emb == "learned":
            wpe = nn.Embed(
                self.seq_len, self.embed_dim, dtype=self.dtype,
                name="wpe",
            )
            if positions is not None and not decode:
                x = x + wpe(positions)  # [b, l] packed offsets
            else:
                x = x + wpe(wpe_idx)
        elif self.pos_emb != "rope":
            raise ValueError(
                "Unknown pos_emb %r (valid: 'learned', 'rope')"
                % (self.pos_emb,)
            )
        head_dim = self.head_dim or self.embed_dim // self.num_heads
        kinds = self._kinds()
        windows = tuple(self.attn_window if on else 0
                        for on in self._layout("window_layout"))
        rotary = [self.pos_emb == "rope" and bool(on)
                  for on in self._layout("rope_layout")]
        mlps = [self.mlp if on else "swiglu"
                for on in self._layout("mlp_layout")]
        ssm = tuple(sorted({
            "num_heads": self.ssm_heads, "head_dim": self.ssm_head_dim,
            "groups": self.ssm_groups, "state_dim": self.ssm_state,
            "conv": self.ssm_conv, "chunk": self.ssm_chunk,
            "norm_eps": self.norm_eps}.items()))
        if self.remat not in ("", "full", "dots"):
            raise ValueError(
                "Unknown remat %r (valid: '', 'full', 'dots')"
                % (self.remat,)
            )
        # remat applies to the training/eval forward only: decode and
        # prefill run no backward, so recompute would be pure waste
        use_remat = bool(self.remat) and not decode and not prefill
        if use_remat:
            policy = (
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable
                if self.remat == "dots" else None
            )

            # training is closure-static; segments/positions are
            # non-differentiable int arrays, safe to close over
            def run_block(blk, xx):
                return blk(xx, training, segments=segments,
                           positions=positions)

            # default prevent_cse=True: the layer loop is unrolled (not
            # nn.scan), and CSE could merge the recomputed forward with
            # the primal one, silently negating the memory savings
            run_block = nn.remat(run_block, policy=policy)
        for i in range(self.num_layers):
            blk = Block(
                self.num_heads, head_dim, dtype=self.dtype,
                attn_impl=self.attn_impl, sp_impl=self.sp_impl,
                tp_shard=self.tp_shard,
                use_rope=rotary[i], rope_theta=self.rope_theta,
                window=windows[i],
                cache_len=self.seq_len,
                num_kv_heads=self.num_kv_heads,
                lora_rank=self.lora_rank, lora_alpha=self.lora_alpha,
                kv_cache_dtype=self.kv_cache_dtype,
                norm=self.norm, norm_eps=self.norm_eps,
                mlp=mlps[i], dense_hidden=self.dense_hidden,
                moe_experts=self.moe_experts,
                moe_top_k=self.moe_top_k, moe_hidden=self.moe_hidden,
                experts_held=tuple(self.experts_held),
                moe_activation=self.moe_activation,
                moe_scoring=self.moe_scoring,
                moe_route_scale=self.moe_route_scale,
                moe_shared_hidden=self.moe_shared_hidden,
                moe_route_from=self.moe_route_from,
                qk_norm=self.qk_norm, block_causal=self.block_causal,
                attn_gate=self.attn_gate,
                sandwich_norm=self.sandwich_norm,
                kind=kinds[i], ssm=ssm if kinds[i] == "M" else (),
                name="block_%d" % i,
            )
            blk_paged = None
            if paged is not None and kinds[i] in ("", "*"):
                arena = paged["pools"]["block_%d" % i]["attn"]
                table = paged["table"]
                # a pool of several block CLASSES (serving/kv_pool.py)
                # lays a table a class side by side and names each
                # layer's columns
                span = (paged.get("table_of") or {}).get("block_%d" % i)
                if span is not None:
                    table = table[:, span[0]:span[1]]
                blk_paged = {
                    "k": arena["k"], "v": arena["v"], "table": table,
                }
                if "k_scale" in arena:  # int8 arenas carry scale leaves
                    blk_paged["k_scale"] = arena["k_scale"]
                    blk_paged["v_scale"] = arena["v_scale"]
            if use_remat:
                x = run_block(blk, x)
            else:
                x = blk(x, training, decode=decode,
                        decode_pos=decode_pos, prefill=prefill,
                        segments=segments, positions=positions,
                        paged=blk_paged, prompt_len=prompt_len,
                        live=paged.get("live") if paged else None)
        x = _norm(self.norm, self.dtype, self.norm_eps, name="ln_f")(x)
        head = LMHead(
            self.vocab_size, dtype=self.dtype, name="head",
            kernel_init=(
                _tp_dense_init(1) if self.tp_shard
                else nn.initializers.lecun_normal()
            ),
        )
        if self.fused_head and training:
            hidden, kernel = head(x, fused=True)
            return {"lm_hidden": hidden, "lm_head_kernel": kernel}
        return head(x)


_DTYPES = {
    "bf16": jnp.bfloat16, "bfloat16": jnp.bfloat16,
    "fp32": jnp.float32, "float32": jnp.float32,
    "fp16": jnp.float16, "float16": jnp.float16,
}


def resolve_dtype(kwargs, family):
    """Shared "dtype": "bf16" -> jnp dtype resolution for the sequence
    families' custom_model kwargs."""
    dtype = kwargs.get("dtype")
    if isinstance(dtype, str):
        if dtype.lower() not in _DTYPES:
            raise ValueError(
                "Unknown dtype %r for %s (valid: %s)"
                % (dtype, family, sorted(_DTYPES))
            )
        kwargs["dtype"] = _DTYPES[dtype.lower()]
    return kwargs


def custom_model(**kwargs):
    # a layout arrives as a list (--model_params); a module's fields
    # are hashable
    kwargs = {k: tuple(v) if isinstance(v, list) else v
              for k, v in kwargs.items()}
    return TransformerLM(**resolve_dtype(kwargs, "transformer_lm"))


def loss(labels, predictions, sample_weights=None):
    # labels [b, l] int; predictions [b, l, vocab] logits, or the fused
    # {lm_hidden, lm_head_kernel} dict when fused_head is on (the head
    # matmul then streams inside the loss — ops/losses.py).
    # Negative labels are IGNORED (ce contribution 0; the packed-
    # sequence data path marks cross-segment boundary targets -100) —
    # rows average over their valid tokens only.
    labels = jnp.asarray(labels)
    valid = labels >= 0
    safe = jnp.maximum(labels, 0)
    if isinstance(predictions, dict) and "lm_hidden" in predictions:
        tok_ce = chunked_softmax_xent(
            predictions["lm_hidden"], predictions["lm_head_kernel"], safe
        )
    else:
        tok_ce = optax.softmax_cross_entropy_with_integer_labels(
            predictions, safe
        )
    tok_ce = jnp.where(valid, tok_ce, 0.0)
    ce = tok_ce.sum(axis=-1) / jnp.maximum(valid.sum(axis=-1), 1)
    if sample_weights is None:
        return jnp.mean(ce)
    return jnp.sum(ce * sample_weights) / jnp.maximum(
        jnp.sum(sample_weights), 1.0
    )


def optimizer(lr=3e-4):
    return optax.adamw(lr, weight_decay=0.01)


def dataset_fn(dataset, mode, metadata):
    def _parse(record):
        ex = decode_example(record)
        tokens = ex["tokens"].astype(np.int32)
        features = {"tokens": tokens[:-1]}
        if mode == Mode.PREDICTION:
            return features
        return features, tokens[1:]

    dataset = dataset.map(_parse)
    if mode == Mode.TRAINING:
        dataset = dataset.shuffle(buffer_size=1024, seed=0)
    return dataset


def eval_metrics_fn():
    return {
        "token_accuracy": lambda labels, predictions: (
            np.argmax(predictions, axis=-1)
            == np.asarray(labels)
        ).astype(np.float32).reshape(len(labels), -1).mean(axis=1)
    }


def feature_shapes(seq_len=128):
    return {"tokens": (seq_len,)}
