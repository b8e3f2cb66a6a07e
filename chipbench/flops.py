"""The operations and bytes the algorithm needs, from shapes. Kept with
the benchmark so that no PR that claims a gain can change the count.
Matmul FLOPs are 2 per multiply-add; recomputation is never counted."""


def _dims(cfg):
    d = cfg["embed_dim"]
    h = cfg["num_heads"]
    hkv = cfg.get("num_kv_heads") or h
    return d, h, hkv, d // h


def attended_keys(seq, window=0):
    """Sum over query positions 0..seq-1 of the keys each attends to
    (causal, keys in (pos - window, pos])."""
    if not window or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def dense_flops_per_token(cfg):
    """Forward matmul FLOPs per token outside attention's score and
    value products: qkv (GQA-aware), proj, MLP, head. The embedding
    lookup is a gather, not a matmul."""
    d, h, hkv, hd = _dims(cfg)
    per_layer = 2 * d * (h + 2 * hkv) * hd + 2 * h * hd * d + 16 * d * d
    return cfg["num_layers"] * per_layer + 2 * d * cfg["vocab_size"]


def attention_flops(cfg, seq, layers=None):
    """Forward FLOPs of QK^T and PV for one sequence, causal and
    windowed: 4 * head_dim per (query, attended key) per head."""
    _, h, _, hd = _dims(cfg)
    layers = cfg["num_layers"] if layers is None else layers
    return layers * 4 * h * hd * attended_keys(seq, cfg.get("attn_window", 0))


def train_flops_per_token(cfg, seq):
    """Forward + backward (3x forward) per token at sequence length
    `seq`."""
    fwd = dense_flops_per_token(cfg) + attention_flops(cfg, seq) / seq
    return 3 * fwd


def flash_train_cost(cfg, seq, batch, dtype_bytes=2):
    """(flops, bytes) one train step needs from the three flash
    kernels over all layers. Products of 2*head_dim FLOPs per (query,
    attended key, head): forward QK^T and PV; backward dV, dP, dQ, dK
    and one recomputation of QK^T that every flash backward needs. The
    second recomputation (the dq and dkv kernels each redo QK^T and
    dP) is this implementation's and is not counted: 7 products against
    the forward's 2. Bytes: q, k, v, o, do and the gradients read or
    written once per kernel."""
    _, h, hkv, hd = _dims(cfg)
    fwd = attention_flops(cfg, seq) * batch
    flops = fwd * 7 / 2
    qo = batch * seq * h * hd * dtype_bytes
    kv = batch * seq * hkv * hd * dtype_bytes
    per_layer = (2 * qo + 2 * kv) + (3 * qo + 2 * kv) + (3 * qo + 4 * kv)
    return flops, cfg["num_layers"] * per_layer


def paged_decode_cost(cfg, context_tokens, dtype_bytes=2):
    """(flops, bytes) of paged decode attention for ONE generated token
    of one sequence over all layers, with `context_tokens` cached keys
    in reach (the window caps it): it streams K and V once."""
    _, h, hkv, hd = _dims(cfg)
    window = cfg.get("attn_window", 0)
    keys = min(context_tokens, window) if window else context_tokens
    flops = cfg["num_layers"] * 4 * h * hd * keys
    nbytes = cfg["num_layers"] * 2 * keys * hkv * hd * dtype_bytes
    return flops, nbytes


def roofline_share(flops, nbytes, seconds, peak_flops, peak_bytes_per_s):
    """(share in %, which bound): the least time the chip could take
    over the time it took."""
    t_flops = flops / peak_flops
    t_bytes = nbytes / peak_bytes_per_s
    bound = "flops" if t_flops >= t_bytes else "bytes"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound
