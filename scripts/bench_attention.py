"""Flash-attention kernel tuning bench (run on real TPU).

Sweeps block sizes for the Pallas forward + two-pass backward at the
flagship shape and compares against the XLA blockwise path and jax's
bundled TPU flash kernel. The clock stops after block_until_ready
(common/timing_utils.fetch_sync).

Usage:  python scripts/bench_attention.py [b h s d]

`--paged` instead sweeps the fused paged decode kernel's query-row
tile (attention.resolve_paged_rows: the sublane occupancy knob) over
the serving decode shapes — the legacy single-token step and the
verify-k tile — against the lax.scan fallback, and with `--write`
persists the winner into ops/flash_tuning.json under "paged_rows",
exactly like the flash block sizes. Without a tuned entry the kernel
uses the CPU-SAFE default of 8 rows (one f32 sublane tile, the
smallest legal Mosaic row tile — correct everywhere, fuller tiles are
a hardware-measured upgrade). Run the sweep on real TPU: off-TPU the
kernel interprets and the timings only rank interpreter overhead.

Usage:  python scripts/bench_attention.py --paged [--write] \\
            [b h hkv d L bs t]

`--paged-trip` sweeps how many arena rows a trip of the paged kernel's
pool stream scores at once (attention._PAGED_TRIP_ROWS, a constant:
the winner is written into the code by hand) at a serving shape, one
call a slot under jax.vmap as the engine makes them, over three sets
of lengths: a steady generation mix with free lanes, every lane free,
and every lane at the table's end with no window.

Usage:  python scripts/bench_attention.py --paged-trip \\
            [slots hkv group d bs m window]
"""

import os
import sys
import time

import numpy as np

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from elasticdl_tpu.common.timing_utils import fetch_sync as fetch  # noqa: E402


def timed(fn, args, iters=20):
    out = fn(*args)
    fetch(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    fetch(out)
    return (time.perf_counter() - t0) / iters


def paged_sweep(argv, write):
    """Sweep resolve_paged_rows candidates for _paged_decode_fused on
    the two serving decode shapes; optionally persist the winner."""
    import json

    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops.attention import paged_decode_attention

    try:
        shape = [int(a) for a in argv] or [8, 8, 8, 128, 2048, 16, 4]
        b, h, hkv, d, L, bs, t = shape
    except ValueError:
        sys.exit("usage: bench_attention.py --paged [b h hkv d L bs t]")
    if L % bs:
        sys.exit("--paged needs L %% bs == 0")
    rs = np.random.RandomState(0)
    nb = b * (L // bs)
    table = jnp.asarray(
        np.arange(nb, dtype=np.int32).reshape(b, L // bs)
    )
    length = jnp.full((b,), L, jnp.int32)
    k_pool = jnp.asarray(rs.randn(nb, bs, hkv, d).astype(np.float32))
    v_pool = jnp.asarray(rs.randn(nb, bs, hkv, d).astype(np.float32))

    def legs(tq):
        q = jnp.asarray(rs.randn(b, h, tq, d).astype(np.float32))
        kc = jnp.asarray(rs.randn(b, hkv, tq, d).astype(np.float32))
        vc = jnp.asarray(rs.randn(b, hkv, tq, d).astype(np.float32))
        if tq == 1:  # legacy single-token shape
            q, kc, vc = q[:, :, 0], kc[:, :, 0], vc[:, :, 0]
        return q, kc, vc, k_pool, v_pool, table, length

    results = {}
    for tq in (1, t):
        inputs = legs(tq)
        scan = jax.jit(lambda *a: paged_decode_attention(
            *a, use_kernel=False))
        t_scan = timed(scan, inputs)
        print("t=%-3d scan (lax.scan oracle)          %8.1f us"
              % (tq, t_scan * 1e6))
        for rows in (8, 16, 32, 64):
            # rows threads through the EDL_PAGED_ROWS env knob, read
            # by resolve_paged_rows at trace time (first timed call)
            os.environ["EDL_PAGED_ROWS"] = str(rows)
            try:
                t_fused = timed(jax.jit(lambda *a: paged_decode_attention(
                    *a, use_kernel=True)), inputs)
            except Exception as e:  # noqa: BLE001
                print("t=%-3d rows=%-3d FAILED: %r"
                      % (tq, rows, repr(e)[:80]))
                continue
            finally:
                os.environ.pop("EDL_PAGED_ROWS", None)
            results.setdefault(rows, 0.0)
            results[rows] += t_fused
            print("t=%-3d rows=%-3d fused                  %8.1f us"
                  " (%.2fx scan)"
                  % (tq, rows, t_fused * 1e6, t_fused / t_scan))
    if not results:
        sys.exit("--paged: every fused config failed")
    best = min(results, key=results.get)
    print("winner: paged_rows=%d (summed %0.1f us over both shapes)"
          % (best, results[best] * 1e6))
    if write:
        path = os.path.join(
            os.path.dirname(os.path.abspath(__file__)), os.pardir,
            "elasticdl_tpu", "ops", "flash_tuning.json",
        )
        with open(path) as f:
            tuning = json.load(f)
        tuning["paged_rows"] = best
        tuning["paged_tuned_on"] = "%s b=%d h=%d hkv=%d d=%d L=%d " \
            "bs=%d t=%d" % (jax.default_backend(), b, h, hkv, d, L,
                            bs, t)
        with open(path, "w") as f:
            json.dump(tuning, f)
            f.write("\n")
        print("wrote paged_rows=%d to %s" % (best, path))


def paged_trip_sweep(argv):
    """Time the vmapped per-slot paged decode at each trip width; the
    per-call time is the step's over the slots."""
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops import attention

    try:
        shape = [int(a) for a in argv] or [16, 2, 12, 128, 16, 1024, 4096]
        slots, hkv, group, d, bs, m, window = shape
    except ValueError:
        sys.exit("usage: bench_attention.py --paged-trip "
                 "[slots hkv group d bs m window]")
    rs = np.random.RandomState(0)
    nb = slots * m + 1
    free = slots * 5 // 16
    mixes = {
        # the benchmark's gen-steady lengths, scaled to the table: a
        # few per cent of it in reach, a third of the lanes free
        "steady": [0] * free + [
            int(x) for x in np.minimum(
                m * bs // 7, m * bs // 200 + 1 + rs.exponential(
                    m * bs / 38, size=slots - free))],
        "all free": [0] * slots,
        "table's end": [m * bs] * slots,
    }

    def rows(*s):
        return jnp.asarray(rs.randn(*s).astype(np.float32), jnp.bfloat16)

    q, k_cur, v_cur = (rows(slots, n, 1, d)
                       for n in (hkv * group, hkv, hkv))
    k_pool, v_pool = rows(nb, bs, hkv, d), rows(nb, bs, hkv, d)

    def step(use_kernel, win):
        def one(q1, k1, v1, tbl1, len1):
            return attention.paged_decode_attention(
                q1[None], k1[None], v1[None], k_pool, v_pool, tbl1[None],
                len1[None], window=win, use_kernel=use_kernel)[0]

        return jax.jit(jax.vmap(one))

    for name, lengths in mixes.items():
        win = None if name == "table's end" else window
        table = np.full((slots, m), -1, np.int32)
        taken = 1
        for i, ln in enumerate(lengths):
            used = -(-int(ln) // bs)
            table[i, :used] = np.arange(taken, taken + used)
            taken += used
        args = (q, k_cur, v_cur, jnp.asarray(table),
                jnp.asarray(lengths, jnp.int32))
        live = sum(int(hi - lo) for lo, hi in zip(
            *attention.paged_live_blocks(
                np.asarray(lengths), win, bs, m, xp=np)))
        print("%s: %d slots, %d of %d table slots in reach"
              % (name, slots, live, slots * m))
        t_scan = timed(step(False, win), args, iters=5)
        print("  scan                      %9.1f us a call"
              % (t_scan / slots * 1e6))
        for trip_rows in (32, 64, 128, 256, 512, 1024, 2048):
            attention._PAGED_TRIP_ROWS = trip_rows
            rows = attention.resolve_paged_rows()
            kblk = attention._paged_trip_blocks(
                hkv * -(-group // rows) * rows, bs * hkv, m)
            try:
                t_fused = timed(step(True, win), args, iters=50)
            except Exception as e:  # noqa: BLE001
                print("  trip rows %-5d blocks %-3d FAILED: %r"
                      % (trip_rows, kblk, repr(e)[:200]))
                continue
            print("  trip rows %-5d blocks %-3d %9.1f us a call"
                  % (trip_rows, kblk, t_fused / slots * 1e6))


def main():
    import jax
    import jax.numpy as jnp

    from elasticdl_tpu.ops.attention import (
        blockwise_attention,
        flash_attention,
    )

    args = sys.argv[1:5]
    if args and len(args) != 4:
        sys.exit("usage: bench_attention.py [b h s d]")
    try:
        shape = [int(a) for a in args] or [32, 8, 1024, 128]
    except ValueError:
        sys.exit("usage: bench_attention.py [b h s d] (ints)")
    b, h, s, d = shape
    rs = np.random.RandomState(0)

    def mk():
        return jnp.asarray(
            rs.randn(b, h, s, d).astype(np.float32) * 0.1, jnp.bfloat16
        )

    q, k, v = mk(), mk(), mk()
    flops_fwd = 2 * 2 * b * h * s * s * d / 2  # causal
    print("shape b=%d h=%d s=%d d=%d   causal fwd %.1f GFLOP"
          % (b, h, s, d, flops_fwd / 1e9))

    def report(tag, t_f, t_b):
        print("%-34s fwd %7.2f ms (%5.1f TF/s)   fwd+bwd %7.2f ms"
              % (tag, t_f * 1e3, flops_fwd / t_f / 1e12, t_b * 1e3))

    def bench_pair(mk_fn, tag):
        fwd = jax.jit(mk_fn)
        grad = jax.jit(jax.grad(
            lambda q, k, v: mk_fn(q, k, v).astype(jnp.float32).mean(),
            argnums=(0, 1, 2),
        ))
        try:
            report(tag, timed(fwd, (q, k, v)), timed(grad, (q, k, v)))
        except Exception as e:  # noqa: BLE001
            print("%-34s FAILED: %r" % (tag, repr(e)[:90]))

    for bq, bk in [(64, 128), (64, 256), (64, 512),
                   (128, 128), (128, 256), (128, 512), (256, 256),
                   (256, 512), (512, 512), (256, 1024), (512, 1024),
                   (1024, 1024), (1024, 512), (128, 1024)]:
        if s % bq or s % bk:
            continue
        bench_pair(
            lambda q, k, v, bq=bq, bk=bk: flash_attention(
                q, k, v, causal=True, block_q=bq, block_k=bk
            ),
            "ours pallas bq=%d bk=%d" % (bq, bk),
        )

    bench_pair(
        lambda q, k, v: blockwise_attention(q, k, v, causal=True),
        "xla blockwise (scan)",
    )
    try:
        from jax.experimental.pallas.ops.tpu.flash_attention import (
            flash_attention as jax_flash,
        )

        sm = 1.0 / np.sqrt(d)
        bench_pair(
            lambda q, k, v: jax_flash(q, k, v, causal=True, sm_scale=sm),
            "jax bundled flash",
        )
    except ImportError:
        pass


if __name__ == "__main__":
    _argv = sys.argv[1:]
    if "--paged" in _argv:
        _argv.remove("--paged")
        _write = "--write" in _argv
        if _write:
            _argv.remove("--write")
        paged_sweep(_argv, _write)
    elif "--paged-trip" in _argv:
        _argv.remove("--paged-trip")
        paged_trip_sweep(_argv)
    else:
        main()
