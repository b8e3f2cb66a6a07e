"""Memory-efficient loss math for large-vocabulary LM heads.

At the bench flagship config ([32, 1024] tokens, 32k vocab) the naive
path materializes fp32 logits of [b, s, vocab] = 4.2 GB per step (plus
the bf16 matmul output and the softmax backward buffers) — several GB of
HBM traffic that dwarfs the head matmul's FLOP cost. `chunked_softmax_xent`
streams the head: the sequence is split into chunks, each chunk's logits
are computed, reduced to per-token cross entropy, and *rematerialized* in
the backward pass (`jax.checkpoint`), so peak logits residency drops from
O(b*s*vocab) to O(b*chunk*vocab) at the cost of one extra head matmul in
the backward (the classic remat trade: FLOPs for HBM).

The reference has no counterpart (its zoo tops out at ResNet50 with a
1k-way softmax — model_zoo/ has no sequence model); this op exists for
the net-new long-context families (model_zoo/transformer_lm & friends).
"""

import jax
import jax.numpy as jnp
import optax


def chunked_softmax_xent(hidden, kernel, labels, num_chunks=8):
    """Per-token cross entropy of an LM head without full logits.

    hidden:  [b, s, d]  final hidden states (any float dtype; the matmul
             runs in hidden.dtype, the softmax math in fp32)
    kernel:  [d, vocab] head projection (cast to hidden.dtype for the
             matmul, matching nn.Dense(dtype=...) promotion)
    labels:  [b, s]     int targets
    returns: [b, s]     fp32 cross entropy per token

    Matches
        optax.softmax_cross_entropy_with_integer_labels(
            (hidden @ kernel).astype(f32), labels)
    to fp32 accuracy. A sequence that does not divide into `num_chunks`
    is zero-padded up to the next multiple and the padded tail dropped
    from the result, so the peak-logits bound O(b * ceil(s/num_chunks)
    * vocab) holds for every length (awkward lengths cost padding
    compute, not memory).
    """
    b, s, d = hidden.shape
    num_chunks = min(num_chunks, s)
    if num_chunks <= 1:
        return _direct_xent(hidden, kernel, labels)
    c = -(-s // num_chunks)  # ceil
    if num_chunks * c != s:
        pad = num_chunks * c - s
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad)))

    # [n, b, c, ...] so lax.scan streams chunks down the sequence.
    h_chunks = hidden.reshape(b, num_chunks, c, d).swapaxes(0, 1)
    y_chunks = labels.reshape(b, num_chunks, c).swapaxes(0, 1)

    chunk_fn = jax.checkpoint(_direct_xent)

    def body(_, hy):
        h, y = hy
        return None, chunk_fn(h, kernel, y)

    # the scope puts "fused_head" into the op_name of the loop and of
    # everything in its body, for whoever reads a device trace
    with jax.named_scope("fused_head"):
        _, ce = jax.lax.scan(body, None, (h_chunks, y_chunks))
    return ce.swapaxes(0, 1).reshape(b, num_chunks * c)[:, :s]


def _direct_xent(hidden, kernel, labels):
    logits = (hidden @ kernel.astype(hidden.dtype)).astype(jnp.float32)
    return optax.softmax_cross_entropy_with_integer_labels(logits, labels)
