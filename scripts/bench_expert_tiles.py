"""The expert layer alone on the chip, by the height of its sorted tiles.

    python scripts/bench_expert_tiles.py [--shapes nm3n-tick,sdar-pass]
        [--live 1.0,0.66] [--loops 20] [--slices]

For each shape (a serve configuration's decode step or a prefill bucket
at its published widths, random bf16 weights, a uniform random top-k)
and each height of `parallel/moe._TILE_LADDER` it times

* `layer`: `held_experts` whole (sort, gathers, kernel, the pairs'
  gather and sum), `--loops` calls chained inside ONE program so that
  no launch gap is in the figure;
* `kernel`: `ops/expert_ffn.expert_tiles` alone over the tile list that
  call built;

and prints one JSON line a reading (microseconds a call, the live
tiles, the rows multiplied, the weights' share of the 819 GB/s
roofline), the rule's own height marked `"rule": true`. `--slices`
times each gated shape a second time with the experts' hidden width
cut into slices of 256 as for an expert too large for the VMEM (a
second tile of an expert then streams its matrices again: what
`ops/expert_ffn.hidden_slice` spares). Runs on the TPU only; the lines
also land in `chiprun_out/expert_tiles/readings.jsonl`.
"""

import argparse
import json
import os
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from elasticdl_tpu.ops import expert_ffn  # noqa: E402
from elasticdl_tpu.parallel import moe  # noqa: E402

#: rows, choices a row, experts held / of the layer, d, hidden, form
SHAPES = {
    "st21b-tick": (16, 6, 32, 64, 2560, 768, "reglu"),
    "nm3n-tick": (32, 6, 32, 128, 2688, 1856, "relu2"),
    "sdar-pass": (128, 8, 32, 128, 2048, 768, "swiglu"),
    "sdar-fused": (256, 8, 32, 128, 2048, 768, "swiglu"),
    "st21b-prefill-256": (256, 6, 32, 64, 2560, 768, "reglu"),
    "sdar-prefill-1024": (1024, 8, 32, 128, 2048, 768, "swiglu"),
    "st21b-prefill-2048": (2048, 6, 32, 64, 2560, 768, "reglu"),
}
HBM_BYTES_PER_S = 819e9


def _case(name, live, seed=0):
    t, k, count, total, d, hidden, form = SHAPES[name]
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    into, back = (count, d, hidden), (count, hidden, d)
    shapes = [back, back] if form == "relu2" else [into, into, back]
    weights = [(jax.random.normal(key, shape, jnp.float32)
                * shape[1] ** -0.5).astype(jnp.bfloat16)
               for key, shape in zip(ks, shapes)]
    h = jax.random.normal(ks[3], (t, d), jnp.float32).astype(jnp.bfloat16)
    gates, experts = moe.route_top_k(
        jax.random.normal(ks[4], (t, total)), k)
    dead = jax.random.uniform(ks[5], (t,)) >= live
    experts = jnp.where(dead[:, None], -1, experts)
    return h, gates, experts, weights, form


def _timed(fn, args, loops):
    """Microseconds a call of `fn` chained `loops` times in one program
    (each call's input depends on the one before), best of three."""
    jax.block_until_ready(fn(*args))
    best = float("inf")
    for _ in range(3):
        start = time.perf_counter()
        jax.block_until_ready(fn(*args))
        best = min(best, time.perf_counter() - start)
    return best / loops * 1e6


def _readings(name, live, loops, sliced):
    h, gates, experts, weights, form = _case(name, live)
    t, k, count = h.shape[0], experts.shape[1], weights[0].shape[0]
    rule = (moe.DECODE_ROWS if t <= moe.DECODE_ROWS
            else moe.sorted_tile_rows(t, k, count))
    heights = [rule] if t <= moe.DECODE_ROWS else moe._TILE_LADDER
    real_rows, real_tiles = moe.sorted_tile_rows, moe.expert_tiles
    real_slice = expert_ffn.hidden_slice
    if sliced:
        expert_ffn.hidden_slice = lambda hidden, _: real_slice(
            hidden, float("inf"))
    try:
        for tm in heights:
            moe.sorted_tile_rows = lambda *_: tm
            handed = []

            def spy(*args, **kwargs):
                handed.append((args, kwargs))
                return real_tiles(*args, **kwargs)

            moe.expert_tiles = spy
            path = (moe._hit_tiles if t <= moe.DECODE_ROWS
                    else moe._grouped_tiles)
            _, _, hit, tile_rows = path(h, gates, experts, 0, *weights,
                                        None, activation=form)
            moe.expert_tiles = real_tiles
            (tile_args, tile_kwargs), = handed

            def layer(h, gates, experts, *weights):
                def body(_, h):
                    y = path(h, gates, experts, 0, *weights, None,
                             activation=form)[0]
                    return h + (y * 1e-3).astype(h.dtype)
                return jax.lax.fori_loop(0, loops, body, h)

            def kernel(x_tiles, x_of, tile_gates, expert_of, n_live, *w):
                def body(_, g):
                    y = real_tiles(x_tiles, x_of, g, expert_of, n_live,
                                   *w, **tile_kwargs)
                    return g + y[:, :, :1] * 0.0
                return jax.lax.fori_loop(0, loops, body, tile_gates)

            hit_bytes = int(jnp.sum(hit)) * sum(
                int(np.prod(w.shape[1:])) * 2 for w in weights)
            layer_us = _timed(jax.jit(layer), (h, gates, experts, *weights),
                              loops)
            kernel_us = _timed(jax.jit(kernel), tile_args, loops)
            yield {
                "shape": name, "live": live, "tm": tm, "rule": tm == rule,
                "sliced": sliced,
                "hidden_slice": expert_ffn.hidden_slice(
                    weights[-1].shape[1],
                    sum(w[0].size * w.dtype.itemsize for w in weights)),
                "n_tiles": int(tile_args[3].shape[0]),
                "live_tiles": int(tile_rows) // tm,
                "tile_rows": int(tile_rows),
                "pairs_held": int(jnp.sum(
                    (experts >= 0) & (experts < count))),
                "experts_hit": int(jnp.sum(hit)),
                "layer_us": round(layer_us, 1),
                "kernel_us": round(kernel_us, 1),
                "weights_roofline_pct": round(
                    100 * hit_bytes / HBM_BYTES_PER_S / (kernel_us * 1e-6),
                    1),
            }
    finally:
        moe.sorted_tile_rows, moe.expert_tiles = real_rows, real_tiles
        expert_ffn.hidden_slice = real_slice


def main():
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--shapes", default=",".join(SHAPES))
    parser.add_argument("--live", default="1.0,0.66,0.34")
    parser.add_argument("--loops", type=int, default=20)
    parser.add_argument("--slices", action="store_true")
    args = parser.parse_args()
    device = jax.devices()[0]
    if device.platform != "tpu":
        sys.exit("bench_expert_tiles: no TPU (found %s); a CPU figure "
                 "is no device number" % device.platform)
    out_dir = os.path.join("chiprun_out", "expert_tiles")
    os.makedirs(out_dir, exist_ok=True)
    with open(os.path.join(out_dir, "readings.jsonl"), "a") as out:
        for name in args.shapes.split(","):
            gated = SHAPES[name][-1] != "relu2"
            for live in map(float, args.live.split(",")):
                for sliced in (False, True)[:1 + (args.slices and gated)]:
                    for reading in _readings(name, live, args.loops,
                                             sliced):
                        reading["device"] = device.device_kind
                        line = json.dumps(reading)
                        print(line, flush=True)
                        out.write(line + "\n")


if __name__ == "__main__":
    main()
