"""On-chip microbenchmark: XLA-fused optax updates vs the Pallas dense
optimizer kernels (ops/optimizer_kernels.py), and (BENCH_SPARSE=1) the
XLA gather->update->scatter row path vs the Pallas sparse row kernels.

Answers "wire them or retire them with data": the reference's C++
Eigen kernels were its PS hot loop
(go/pkg/kernel/capi/kernel_api.cc:6-96), but on TPU the optimizer update
is fused by XLA into the compiled train step, so a standalone kernel
must beat the fused update to earn the Trainer slot.

Methodology (both matter on this rig):
* the mutable state is a CARRY donated back into the jit on every
  iteration (donate_argnums=0) — without donation XLA copies the whole
  buffer per call, and for the sparse case that ~512 MB table copy
  would swamp the ~4 MB of touched-row work being compared;
* the clock stops after block_until_ready on the carry
  (common/timing_utils.fetch_sync).

Run on hardware:  python scripts/bench_optimizer_kernels.py
                  BENCH_SPARSE=1 python scripts/bench_optimizer_kernels.py
Prints one JSON line per (path, size).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np
import optax

from elasticdl_tpu.ops import embedding_ops as eo
from elasticdl_tpu.ops import optimizer_kernels as ok
from elasticdl_tpu.ops import update_math as um


from elasticdl_tpu.common.timing_utils import fetch_sync as _fetch  # noqa: E402


def timed_carry(step, carry, iters=30, warmup=5):
    """step(carry) -> carry, jitted with the carry donated. Timing
    continues from the warmed carry (the pre-warmup buffers are consumed
    by donation)."""
    fn = jax.jit(step, donate_argnums=(0,))
    for _ in range(warmup):
        carry = fn(carry)
    _fetch(carry)
    t0 = time.perf_counter()
    for _ in range(iters):
        carry = fn(carry)
    _fetch(carry)
    return (time.perf_counter() - t0) / iters


def main():
    n = int(os.environ.get("N_PARAMS", str(64 * 1024 * 1024)))  # 64M f32
    rng = np.random.default_rng(0)
    # host originals: each timed run donates (consumes) its device
    # buffers, so every path gets a fresh device copy
    p_host = rng.standard_normal(n).astype(np.float32)
    g = jnp.asarray(rng.standard_normal(n), jnp.float32)

    def fresh_p():
        return jnp.asarray(p_host)

    results = []

    # --- SGD ---
    opt = optax.sgd(0.1)

    def optax_sgd(carry):
        p, s = carry
        u, s = opt.update(g, s, p)
        return optax.apply_updates(p, u), s

    def pallas_sgd(carry):
        (p,) = carry
        return (ok.sgd_update(p, g, 0.1),)

    p0 = fresh_p()
    t_optax = timed_carry(optax_sgd, (p0, opt.init(p0)))
    t_pallas = timed_carry(pallas_sgd, (fresh_p(),))
    results.append(dict(optimizer="sgd", n=n,
                        optax_ms=round(t_optax * 1e3, 3),
                        pallas_ms=round(t_pallas * 1e3, 3)))

    # --- Adam ---
    aopt = optax.adam(1e-3)

    def optax_adam(carry):
        p, s = carry
        u, s = aopt.update(g, s, p)
        return optax.apply_updates(p, u), s

    def pallas_adam(carry):
        p, m, v = carry
        return ok.adam_update(p, m, v, g, step=1, lr=1e-3)

    p0 = fresh_p()
    t_optax = timed_carry(optax_adam, (p0, aopt.init(p0)))
    p1 = fresh_p()
    t_pallas = timed_carry(
        pallas_adam, (p1, jnp.zeros_like(p1), jnp.zeros_like(p1))
    )
    results.append(dict(optimizer="adam", n=n,
                        optax_ms=round(t_optax * 1e3, 3),
                        pallas_ms=round(t_pallas * 1e3, 3)))

    # HBM roofline: adam reads p,m,v,g and writes p,m,v = 7 arrays
    for r in results:
        n_bufs = 3 if r["optimizer"] == "sgd" else 7
        gb = n_bufs * n * 4 / 1e9
        r["optax_gbps"] = round(gb / (r["optax_ms"] / 1e3), 1)
        r["pallas_gbps"] = round(gb / (r["pallas_ms"] / 1e3), 1)
        r["platform"] = jax.default_backend()
        print(json.dumps(r))


def sparse_main():
    """Sparse row update: Pallas row kernels vs the XLA gather->update->
    scatter path the Trainer uses (embedding/sparse_update
    .row_sparse_apply). The table is the donated carry, so neither path
    pays a full-table copy — exactly the Trainer's situation (donated
    TrainState)."""
    vocab = int(os.environ.get("SPARSE_VOCAB", str(2_000_000)))
    dim = int(os.environ.get("SPARSE_DIM", "64"))
    n_ids = int(os.environ.get("SPARSE_IDS", "8192"))
    rng = np.random.default_rng(0)
    table_host = rng.standard_normal((vocab, dim)).astype(np.float32)
    ids = jnp.asarray(
        np.unique(rng.integers(0, vocab, size=n_ids)), jnp.int32
    )
    grads = jnp.asarray(
        rng.standard_normal((ids.shape[0], dim)), jnp.float32
    )

    def xla_sparse_sgd(carry):
        (table,) = carry
        rows = jnp.take(table, ids, axis=0)
        return (table.at[ids].set(um.sgd_math(rows, grads, 0.1)),)

    def pallas_sparse_sgd(carry):
        (table,) = carry
        return (eo.sparse_sgd_update(table, ids, grads, 0.1),)

    for name, step in (("xla", xla_sparse_sgd),
                       ("pallas", pallas_sparse_sgd)):
        t = timed_carry(step, (jnp.asarray(table_host),), iters=20)
        gb = 2 * ids.shape[0] * dim * 4 / 1e9  # touched rows r/w
        print(json.dumps(dict(
            path=name, vocab=vocab, dim=dim, n_rows=int(ids.shape[0]),
            ms=round(t * 1e3, 3), touched_gbps=round(gb / t, 2),
            platform=jax.default_backend(),
        )))


if __name__ == "__main__":
    if os.environ.get("BENCH_SPARSE") == "1":
        sparse_main()
    else:
        main()
