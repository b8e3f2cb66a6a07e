#!/usr/bin/env python
"""Microbench pin: the paged blockwise INT8 scan vs the dense
deferred-dequantize int8 decode step.

An earlier round explained the residual offline `decode_kv_int8`
gap (int8 ~0.85-0.95x fp after the deferred-dequantize fix: the
per-step int8->f32 cast feeding the score matmul plus the two [*, L]
scale multiplies). This PR folds the SAME deferral into the paged
pool's streaming scan (ops.attention.paged_decode_attention: k-scales
into the per-block score tile, v-scales into the weights), and this
bench pins that the blockwise formulation does not REGRESS the dense
deferred path — the scan adds block bookkeeping (table gather, online
softmax merges) but the dequantize work per cache row is identical.

Timed legs over the SAME logical cache (one decode step,
steady-state, jit-compiled):

  dense_deferred_int8  the model's dense int8 decode attention
                       (transformer_lm._decode_step shape): one
                       [*, L] score softmax with scales folded in
  paged_int8           paged_decode_attention over int8 block arenas
                       with the deferred scan (use_kernel=False)
  paged_fp             the same scan over fp arenas (the int8 delta
                       WITHIN the paged formulation)
  fused_int8/fused_fp  the FUSED Pallas kernel (use_kernel=True) on
                       the same arenas — the PR 18 leg. On TPU this
                       is the streaming VMEM kernel and the
                       acceptance number is fused_int8_vs_dense
                       <= 1.0; off-TPU the kernel INTERPRETS
                       (fused_interpreted=true in the record), which
                       checks the path end to end but times the
                       Pallas interpreter, not Mosaic — interpreted
                       ratios are reported for trajectory only.
  tile_*               the verify-k [b, h, t, d] variants of all four
                       paged legs (t = --verify_k: the speculative
                       verify tile / suffix-prefill shape)

Emits one JSON line; `--out` also writes it to a file. Defaults are
CPU-smoke sized; on hardware raise --seq_len/--batch and the dims.

Usage: python scripts/bench_int8_scan.py [--iters 50]
"""

import argparse
import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")


def parse_args(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--heads", type=int, default=8)
    p.add_argument("--kv_heads", type=int, default=0,
                   help="0 = --heads (MHA)")
    p.add_argument("--head_dim", type=int, default=64)
    p.add_argument("--seq_len", type=int, default=512)
    p.add_argument("--block_size", type=int, default=16)
    p.add_argument("--iters", type=int, default=50)
    p.add_argument("--verify_k", type=int, default=4,
                   help="query-tile rows for the tile_* legs")
    p.add_argument("--fused_iters", type=int, default=0,
                   help="iters for the fused legs; 0 = --iters on "
                        "TPU, min(--iters, 10) when the kernel can "
                        "only run interpreted (the interpreter is "
                        "~100x XLA, full iters would dominate the "
                        "bench wall clock)")
    p.add_argument("--no-fused", dest="fused", action="store_false",
                   help="skip the fused-kernel legs (pre-PR-18 "
                        "record shape)")
    p.add_argument("--out", default="")
    return p.parse_args(argv)


def time_fn(fn, args, iters):
    """Steady-state per-call seconds: one warm call pays the compile,
    then `iters` dispatches with a single block at the end (the async
    dispatch overhead amortizes exactly like the serving step loop)."""
    import jax

    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def main(argv=None):
    args = parse_args(argv)
    import jax
    import jax.numpy as jnp
    import numpy as np

    from elasticdl_tpu.ops.attention import paged_decode_attention

    b, h, d = args.batch, args.heads, args.head_dim
    hkv = args.kv_heads or h
    L, bs = args.seq_len, args.block_size
    if L % bs:
        raise SystemExit("seq_len must be a multiple of block_size")
    group = h // hkv
    rs = np.random.RandomState(0)

    def q8(rows):
        amax = np.abs(rows).max(-1, keepdims=True)
        sc = np.where(amax > 0, amax / 127.0, 1.0).astype(np.float32)
        return (np.clip(np.round(rows / sc), -127, 127)
                .astype(np.int8), sc)

    # one logical cache, three physical layouts
    kf = rs.randn(b, hkv, L, d).astype(np.float32)
    vf = rs.randn(b, hkv, L, d).astype(np.float32)
    k8, ks = q8(kf)
    v8, vs = q8(vf)
    q = rs.randn(b, h, d).astype(np.float32)
    kc = rs.randn(b, hkv, 1, d).astype(np.float32)
    vc = rs.randn(b, hkv, 1, d).astype(np.float32)
    kc8, kcs = q8(kc)
    vc8, vcs = q8(vc)
    length = np.full((b,), L, np.int32)

    # ---- dense deferred int8 (the offline decode_kv_int8 shape)
    @jax.jit
    def dense_deferred(qx, ck, csk, cv, csv):
        qg = (qx * d ** -0.5).reshape(b, hkv, group, 1, d)
        s = jnp.einsum(
            "bhgtd,bhkd->bhgtk", qg, ck.astype(jnp.float32)
        ) * csk[..., 0][:, :, None, None]
        w = jax.nn.softmax(s, axis=-1)
        return jnp.einsum(
            "bhgtk,bhkd->bhgtd",
            w * csv[..., 0][:, :, None, None],
            cv.astype(jnp.float32),
        )

    # ---- paged layouts: b private chains through shared arenas
    nb = b * (L // bs)
    table = np.arange(nb, dtype=np.int32).reshape(b, L // bs)
    k_pool8 = np.zeros((nb, bs, hkv, d), np.int8)
    v_pool8 = np.zeros((nb, bs, hkv, d), np.int8)
    ks_pool = np.zeros((nb, bs, hkv, 1), np.float32)
    vs_pool = np.zeros((nb, bs, hkv, 1), np.float32)
    k_poolf = np.zeros((nb, bs, hkv, d), np.float32)
    v_poolf = np.zeros((nb, bs, hkv, d), np.float32)
    for i in range(b):
        for j in range(L // bs):
            rows = slice(j * bs, (j + 1) * bs)
            bid = table[i, j]
            k_pool8[bid] = k8[i, :, rows].transpose(1, 0, 2)
            v_pool8[bid] = v8[i, :, rows].transpose(1, 0, 2)
            ks_pool[bid] = ks[i, :, rows].transpose(1, 0, 2)
            vs_pool[bid] = vs[i, :, rows].transpose(1, 0, 2)
            k_poolf[bid] = kf[i, :, rows].transpose(1, 0, 2)
            v_poolf[bid] = vf[i, :, rows].transpose(1, 0, 2)

    def paged_call(kernel):
        def call_int8(*a):
            return paged_decode_attention(
                a[0], a[1], a[2], a[3], a[4], a[5], a[6],
                k_scale_pool=a[7], v_scale_pool=a[8],
                k_cur_scale=a[9], v_cur_scale=a[10],
                use_kernel=kernel,
            )
        def call_fp(*a):
            return paged_decode_attention(*a, use_kernel=kernel)
        return jax.jit(call_int8), jax.jit(call_fp)

    scan_int8, scan_fp_fn = paged_call(False)
    fused_int8_fn, fused_fp_fn = paged_call(True)

    int8_args = (
        jnp.asarray(q), jnp.asarray(kc8[:, :, 0]),
        jnp.asarray(vc8[:, :, 0]), jnp.asarray(k_pool8),
        jnp.asarray(v_pool8), jnp.asarray(table),
        jnp.asarray(length), jnp.asarray(ks_pool),
        jnp.asarray(vs_pool), jnp.asarray(kcs[:, :, 0]),
        jnp.asarray(vcs[:, :, 0]),
    )
    fp_args = (
        jnp.asarray(q), jnp.asarray(kc[:, :, 0]),
        jnp.asarray(vc[:, :, 0]), jnp.asarray(k_poolf),
        jnp.asarray(v_poolf), jnp.asarray(table),
        jnp.asarray(length),
    )
    # the verify-k tile ([b, h, t, d]): same cache, t query rows
    t = args.verify_k
    q_t = rs.randn(b, h, t, d).astype(np.float32)
    kct = rs.randn(b, hkv, t, d).astype(np.float32)
    vct = rs.randn(b, hkv, t, d).astype(np.float32)
    kct8, kcts = q8(kct)
    vct8, vcts = q8(vct)
    tile_int8_args = (
        jnp.asarray(q_t), jnp.asarray(kct8), jnp.asarray(vct8),
        jnp.asarray(k_pool8), jnp.asarray(v_pool8),
        jnp.asarray(table), jnp.asarray(length),
        jnp.asarray(ks_pool), jnp.asarray(vs_pool),
        jnp.asarray(kcts), jnp.asarray(vcts),
    )
    tile_fp_args = (
        jnp.asarray(q_t), jnp.asarray(kct), jnp.asarray(vct),
        jnp.asarray(k_poolf), jnp.asarray(v_poolf),
        jnp.asarray(table), jnp.asarray(length),
    )

    dense_s = time_fn(
        dense_deferred,
        (jnp.asarray(q), jnp.asarray(k8), jnp.asarray(ks),
         jnp.asarray(v8), jnp.asarray(vs)),
        args.iters,
    )
    i8_s = time_fn(scan_int8, int8_args, args.iters)
    fp_s = time_fn(scan_fp_fn, fp_args, args.iters)
    tile_i8_s = time_fn(scan_int8, tile_int8_args, args.iters)
    tile_fp_s = time_fn(scan_fp_fn, tile_fp_args, args.iters)
    record = {
        "metric": "paged_int8_scan_vs_dense_deferred",
        "platform": jax.default_backend(),
        "batch": b, "heads": h, "kv_heads": hkv, "head_dim": d,
        "seq_len": L, "block_size": bs, "iters": args.iters,
        "verify_k": t,
        "dense_deferred_int8_us": round(dense_s * 1e6, 1),
        "paged_int8_us": round(i8_s * 1e6, 1),
        "paged_fp_us": round(fp_s * 1e6, 1),
        "tile_paged_int8_us": round(tile_i8_s * 1e6, 1),
        "tile_paged_fp_us": round(tile_fp_s * 1e6, 1),
        # the pin: the blockwise deferral vs the dense deferral
        "paged_int8_vs_dense_deferred": round(i8_s / dense_s, 3),
        # the int8 cost WITHIN the paged formulation
        "paged_int8_vs_paged_fp": round(i8_s / fp_s, 3),
    }
    if args.fused:
        from elasticdl_tpu.ops.dispatch import interpret_mode

        interpreted = interpret_mode()
        fi = args.fused_iters or (
            min(args.iters, 10) if interpreted else args.iters
        )
        f8_s = time_fn(fused_int8_fn, int8_args, fi)
        ffp_s = time_fn(fused_fp_fn, fp_args, fi)
        tile_f8_s = time_fn(fused_int8_fn, tile_int8_args, fi)
        tile_ffp_s = time_fn(fused_fp_fn, tile_fp_args, fi)
        record.update({
            "fused_interpreted": interpreted,
            "fused_iters": fi,
            "fused_int8_us": round(f8_s * 1e6, 1),
            "fused_fp_us": round(ffp_s * 1e6, 1),
            "tile_fused_int8_us": round(tile_f8_s * 1e6, 1),
            "tile_fused_fp_us": round(tile_ffp_s * 1e6, 1),
            # the PR 18 acceptance number (meaningful on TPU; the
            # interpreter's python-loop timings only track trajectory)
            "fused_int8_vs_dense_deferred": round(f8_s / dense_s, 3),
            "fused_int8_vs_paged_int8": round(f8_s / i8_s, 3),
            "fused_fp_vs_paged_fp": round(ffp_s / fp_s, 3),
            "tile_fused_int8_vs_tile_paged_int8":
                round(tile_f8_s / tile_i8_s, 3),
        })
    line = json.dumps(record)
    print(line, flush=True)
    if args.out:
        with open(args.out, "w") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
