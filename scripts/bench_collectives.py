"""Gradient-plane collective bandwidth (BASELINE.md target:
"PS→allreduce gradient bandwidth") + expert-parallel all-to-all cost.

The reference's gradient plane was gRPC push/pull to PS pods (256 MB
message cap); ours is the psum XLA inserts inside the compiled step.
This measures that plane directly: an all-reduce of a flagship-sized
gradient pytree over every device the mesh has.

* multi-chip TPU: the number is ICI all-reduce bandwidth — the
  v5e-16 figure BASELINE.md asks to establish;
* single chip: the collective degenerates to identity, so the bench
  reports the in-place gradient update bandwidth (HBM) instead and
  labels it as such;
* CPU (virtual 8-device mesh): functional smoke only, labeled cpu.

With >1 device it ALSO measures the MoE expert-parallel all-to-all
(parallel/moe.py moe_mlp_apply_a2a) at 8 and 64 experts: the raw
all_to_all of the capacity-bounded [E, C, D] send buffer (bytes/step +
latency + effective bandwidth) and the full explicit-dispatch forward
(route -> a2a -> expert FFNs -> reverse a2a -> combine). One JSON line
per a2a measurement, then the final all-reduce line with an "a2a"
summary dict embedded.

The clock stops after block_until_ready
(common/timing_utils.fetch_sync).

    python scripts/bench_collectives.py [size_mb]
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def main():
    import bench as bench_mod

    # the virtual-mesh functional smoke asks for the CPU by name and
    # labels its line so; any other run measures a TPU or stops
    if os.environ.get("JAX_PLATFORMS", "").split(",")[0] != "cpu":
        bench_mod.require_tpu()
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import PartitionSpec as P

    from elasticdl_tpu.common.timing_utils import fetch_sync
    from elasticdl_tpu.parallel import mesh as mesh_lib

    size_mb = float(sys.argv[1]) if len(sys.argv) > 1 else 256.0
    n = int(size_mb * 1e6 / 4)
    mesh = mesh_lib.build_mesh()
    n_dev = mesh.size
    axes = tuple(mesh.axis_names)

    def grad_allreduce(local):
        # the gradient plane: sum over every mesh axis (what the
        # batch-sharded loss's backward inserts for replicated params)
        return jax.lax.psum(local, axes)

    fn = jax.jit(
        jax.shard_map(
            grad_allreduce, mesh=mesh,
            in_specs=P(axes[0]), out_specs=P(),
            check_vma=False,
        )
    )
    rng = np.random.RandomState(0)
    # leading dim divisible by every axis: pad up
    rows = ((n // 128 + n_dev - 1) // n_dev) * n_dev
    x = jnp.asarray(rng.rand(rows, 128).astype(np.float32))
    bytes_payload = x.size * 4

    out = fn(x)
    fetch_sync(out)
    iters = 10
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(x)
    fetch_sync(out)
    dt = (time.perf_counter() - t0) / iters

    platform = jax.default_backend()

    # --- expert-parallel all-to-all ---
    a2a_summary = {}
    if n_dev > 1:
        from elasticdl_tpu.parallel import moe as moe_lib

        ep_mesh = mesh_lib.build_mesh({"ep": n_dev})
        t_tok, dmodel, hdim, topk, cf = 8192, 512, 512, 2, 1.25
        for n_exp in (8, 64):
            if n_exp % n_dev:
                continue
            cap = moe_lib.expert_capacity(
                t_tok // n_dev * topk, n_exp, cf)
            e_loc = n_exp // n_dev
            local_bytes = n_dev * e_loc * cap * dmodel * 4
            # raw all_to_all of the dispatch send buffer
            buf = jnp.asarray(rng.rand(
                n_dev * n_dev, e_loc, cap, dmodel).astype(np.float32))
            a2a_fn = jax.jit(
                jax.shard_map(
                    lambda b: jax.lax.all_to_all(
                        b, "ep", split_axis=0, concat_axis=0),
                    mesh=ep_mesh, in_specs=P("ep"), out_specs=P("ep"),
                    check_vma=False,
                )
            )
            out = a2a_fn(buf)
            fetch_sync(out)
            t0 = time.perf_counter()
            for _ in range(iters):
                out = a2a_fn(buf)
            fetch_sync(out)
            raw_dt = (time.perf_counter() - t0) / iters

            # full explicit dispatch forward at the same shapes
            prng = np.random.RandomState(1)
            params = {
                "router": jnp.asarray(
                    prng.rand(dmodel, n_exp).astype(np.float32)),
                "w_up": jnp.asarray((prng.rand(
                    n_exp, dmodel, hdim) / np.sqrt(dmodel)
                ).astype(np.float32)),
                "b_up": jnp.zeros((n_exp, hdim), jnp.float32),
                "w_down": jnp.asarray((prng.rand(
                    n_exp, hdim, dmodel) / np.sqrt(hdim)
                ).astype(np.float32)),
                "b_down": jnp.zeros((n_exp, dmodel), jnp.float32),
            }
            xt = jnp.asarray(
                rng.rand(t_tok, dmodel).astype(np.float32))
            disp_fn = jax.jit(
                lambda p, xv: moe_lib.moe_mlp_apply_a2a(
                    p, xv, ep_mesh, capacity_factor=cf,
                    router_top_k=topk,
                )[0]
            )
            with ep_mesh:
                out = disp_fn(params, xt)
                fetch_sync(out)
                t0 = time.perf_counter()
                for _ in range(iters):
                    out = disp_fn(params, xt)
                fetch_sync(out)
            disp_dt = (time.perf_counter() - t0) / iters
            entry = {
                "experts": n_exp,
                "capacity_per_group": cap,
                "a2a_bytes_per_step_per_device_mb": round(
                    local_bytes / 1e6, 2),
                "a2a_global_bytes_per_step_mb": round(
                    local_bytes * n_dev / 1e6, 2),
                "a2a_latency_ms": round(raw_dt * 1e3, 3),
                "a2a_effective_gbps": round(
                    local_bytes * n_dev / raw_dt / 1e9, 2),
                "dispatch_fwd_ms": round(disp_dt * 1e3, 3),
                "tokens": t_tok, "d_model": dmodel,
                "router_top_k": topk, "capacity_factor": cf,
            }
            a2a_summary["e%d" % n_exp] = entry
            print(json.dumps(dict(
                {"metric": "moe_a2a_dispatch", "platform": platform,
                 "devices": n_dev}, **entry)), flush=True)

    # ring all-reduce moves 2*(n-1)/n of the payload per link; report
    # the conventional algorithm bandwidth payload/time and the bus
    # bandwidth alongside
    algo_bw = bytes_payload / dt
    bus_bw = algo_bw * (2 * (n_dev - 1) / n_dev if n_dev > 1 else 1.0)
    print(json.dumps({
        "metric": (
            "grad_allreduce_bandwidth" if n_dev > 1
            else "grad_reduce_hbm_bandwidth_single_device"
        ),
        "value": round(algo_bw / 1e9, 2),
        "unit": "GB/s",
        "vs_baseline": None if platform == "cpu" else 1.0,
        "bus_bandwidth_gbps": round(bus_bw / 1e9, 2),
        "payload_mb": round(bytes_payload / 1e6, 1),
        "devices": n_dev,
        "mesh": dict(mesh.shape),
        "platform": platform,
        "step_ms": round(dt * 1e3, 3),
        "a2a": a2a_summary or None,
    }))


if __name__ == "__main__":
    main()
