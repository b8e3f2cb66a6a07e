"""Pipeline-schedule A/B: gpipe vs interleaved step time for the
transformer_pp family on a virtual pp mesh.

The interleaved (circular, Megatron-style) schedule runs vM + P - 1
ticks of 1/v-size chunk bodies vs GPipe's M + P - 1 full-stage ticks —
total stage-work (M + (P-1)/v) vs (M + P - 1). At the round-4
comparison point (M=8, P=4, v=2) that is 9.5 vs 11 stage-times: ~14%
less work on an oversubscribed virtual mesh (where wall-clock tracks
TOTAL work, all virtual devices timesharing the host) and the same
ratio in fill/drain bubble on real chips (where wall-clock tracks the
critical path — the two views agree because every device's tick count
IS the critical path).

Run on the 8-device virtual CPU mesh:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        JAX_PLATFORMS=cpu python scripts/bench_pipeline.py

Prints one JSON line:
    {"metric": "pp_interleaved_speedup", "value": gpipe_ms/inter_ms,
     "gpipe_step_ms": ..., "interleaved_step_ms": ...,
     "work_ratio_expected": 11/9.5, ...}
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import numpy as np  # noqa: E402


def main():
    import jax

    from elasticdl_tpu.common.model_utils import (
        format_params_str,
        load_model_spec_from_module,
    )
    from elasticdl_tpu.common.timing_utils import fetch_sync
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.training.trainer import Trainer
    from model_zoo.transformer_pp import transformer_pp as zoo

    n_dev = len(jax.devices())
    pp = 4 if n_dev % 4 == 0 else max(
        d for d in (2, 1) if n_dev % d == 0)
    dp = n_dev // pp
    m, v = 8, 2
    cfg = dict(
        vocab_size=512, seq_len=64, embed_dim=128, num_heads=4,
        num_layers=2 * pp * v, num_microbatches=m,
    )
    batch_size = dp * m  # per-device batch == m (microbatch size 1)
    iters, warmup = 10, 2

    rng = np.random.RandomState(0)
    tokens = rng.randint(
        0, cfg["vocab_size"], size=(batch_size, cfg["seq_len"] + 1)
    ).astype(np.int32)
    batch = ({"tokens": tokens[:, :-1]}, tokens[:, 1:])

    def measure(extra):
        mesh = mesh_lib.build_mesh({"dp": dp, "pp": pp})
        trainer = Trainer(
            load_model_spec_from_module(zoo),
            mesh=mesh,
            model_params=format_params_str(dict(cfg, **extra)),
        )
        state = trainer.init_state(batch)
        losses = []
        for _ in range(warmup):
            state, loss = trainer.train_step(state, batch)
            losses.append(float(loss))
        fetch_sync(state.params)
        t0 = time.perf_counter()
        for _ in range(iters):
            state, loss = trainer.train_step(state, batch)
        fetch_sync(state.params)
        dt = (time.perf_counter() - t0) / iters
        assert np.isfinite(float(loss))
        return dt, losses[0]

    g_dt, g_loss0 = measure({})
    i_dt, _ = measure({"pp_schedule": "interleaved",
                       "pp_interleave": v})
    # expected work ratio: (M + P - 1) / (M + (P-1)/v) stage-times
    expected = (m + pp - 1) / (m + (pp - 1) / v)
    print(json.dumps({
        "metric": "pp_interleaved_speedup",
        "value": round(g_dt / i_dt, 4),
        "unit": "x (gpipe step time / interleaved step time)",
        "gpipe_step_ms": round(g_dt * 1e3, 2),
        "interleaved_step_ms": round(i_dt * 1e3, 2),
        "work_ratio_expected": round(expected, 4),
        "pp": pp, "dp": dp, "microbatches": m, "interleave": v,
        "num_layers": cfg["num_layers"],
        "n_devices": n_dev,
        "platform": jax.default_backend(),
        "first_loss_gpipe": round(g_loss0, 6),
    }))


if __name__ == "__main__":
    main()
