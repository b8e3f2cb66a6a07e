"""Capture a jax.profiler trace of the flagship train step and print
the top device-side ops — the tool behind the round-2 finding that
attention consumed ~44% of the step at ~11% of the FLOPs.

Usage (on TPU):
    python scripts/profile_step.py [trace_dir]
Prints a per-op duration summary from the Chrome trace; the full
xplane/trace files stay in trace_dir for TensorBoard's profile plugin.
"""

import collections
import glob
import gzip
import json
import os
import sys

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)


def capture(trace_dir):
    import jax
    import numpy as np

    from elasticdl_tpu.common.model_utils import (
        format_params_str,
        load_model_spec_from_module,
    )
    from elasticdl_tpu.common.timing_utils import fetch_sync
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.training.trainer import Trainer
    from model_zoo.transformer_lm import transformer_lm as zoo

    cfg = dict(vocab_size=32000, seq_len=1024, embed_dim=1024,
               num_heads=8, num_layers=8, dtype="bf16")
    bsz = 32
    trainer = Trainer(
        load_model_spec_from_module(zoo),
        mesh=mesh_lib.build_mesh(),
        model_params=format_params_str(cfg),
    )
    rng = np.random.RandomState(0)
    tok = rng.randint(0, 32000, size=(bsz, 1025)).astype(np.int32)
    batch = ({"tokens": tok[:, :-1]}, tok[:, 1:])
    state = trainer.init_state(batch)
    batch = jax.device_put(batch, mesh_lib.batch_sharding(trainer.mesh))
    for _ in range(3):
        state, _ = trainer.train_step(state, batch)
    fetch_sync(state.params)
    with jax.profiler.trace(trace_dir):
        for _ in range(3):
            state, _ = trainer.train_step(state, batch)
        fetch_sync(state.params)


def summarize(trace_dir, top=30):
    paths = glob.glob(
        os.path.join(trace_dir, "plugins/profile/*/*.trace.json.gz")
    )
    if not paths:
        print("no trace found under", trace_dir)
        return
    with gzip.open(sorted(paths)[-1]) as f:
        events = json.load(f).get("traceEvents", [])
    durs = collections.Counter()
    for e in events:
        if e.get("ph") == "X" and e.get("dur"):
            durs[e.get("name", "")] += e["dur"]
    print("top device/host ops by total duration (3 steps):")
    for name, d in durs.most_common(top):
        print("%10.2f ms  %s" % (d / 1000.0, name[:100]))


def main():
    trace_dir = sys.argv[1] if len(sys.argv) > 1 else "/tmp/edl-trace"
    import bench as bench_mod

    bench_mod.require_tpu()
    capture(trace_dir)
    summarize(trace_dir)


if __name__ == "__main__":
    main()
