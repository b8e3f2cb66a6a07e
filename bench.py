"""Headline benchmark: the framework's benchable families on the chip.
The default run is a SUITE — one JSON line per family (transformer
flagship first, then moe/bert/dlrm/decode/decode-int8-KV), closed by a
flagship summary line carrying every family's numbers:
    {"metric": ..., "value": N, ..., "suite": true, "families": {...}}
`EDL_BENCH_MODEL=<family>` runs exactly one family (one JSON line).

It runs in one process on the TPU JAX finds, and nowhere else: with no
chip, with a device kind the peaks table does not know, or when any
family fails, it exits nonzero. There is no CPU fallback and no retry
with kernels disabled — a number from this file is a device number or
there is no number.

The reference publishes no hardware throughput numbers (BASELINE.md), so
the baselines are *established* here: `vs_baseline` is the ratio to the
committed same-config hardware record (BENCH_BASELINE.json for the
flagship, BENCH_BASELINE_<FAMILY>.json otherwise), and 1.0 when the run
has no comparable record yet.
"""

import json
import os
import sys
import time

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

# Peak bf16 matmul FLOP/s per chip, keyed by the exact
# `jax.devices()[0].device_kind`. Source: Google Cloud documentation,
# "TPU v5e" (197 TFLOP/s bf16 per chip). A device that is not in the
# table is an error, never a default: add its row with its source.
_PEAK_FLOPS = {
    "TPU v5 lite": 197e12,
}


def _env_float(value, env_key, default, floor):
    """Explicit value, else env var (a malformed value warns and falls
    back to the default), floored."""
    if value is None:
        raw = os.environ.get(env_key, "")
        try:
            value = float(raw) if raw else default
        except ValueError:
            sys.stderr.write("bench: ignoring bad %s=%r\n" % (env_key, raw))
            value = default
    return max(float(value), floor)


def require_tpu():
    """Exit nonzero unless the first device JAX reports is a TPU; the
    in-process check shared by bench.py and the TPU-only measurement
    scripts (profile_step, bench_collectives). Returns that device."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(
            "bench: needs a TPU, but jax.devices()[0].platform is %r"
            % dev.platform
        )
    return dev


def _peak_flops(device_kind):
    try:
        return _PEAK_FLOPS[device_kind]
    except KeyError:
        raise KeyError(
            "no peak FLOP/s known for device_kind %r; add it to "
            "bench._PEAK_FLOPS with its source" % (device_kind,)
        ) from None


def transformer_flops_per_step(batch, seq, d_model, n_layers, vocab):
    """Matmul FLOPs for one fwd+bwd train step (backward = 2x forward).

    Per token forward: qkv (2*d*3d) + attn proj (2*d*d) + MLP
    (2*d*4d in + 2*4d*d out) = 24*d^2; attention scores+values add
    4*seq*d per token per layer; LM head 2*d*vocab.
    """
    per_token_layer = 24 * d_model * d_model + 4 * seq * d_model
    fwd = batch * seq * (n_layers * per_token_layer + 2 * d_model * vocab)
    return 3 * fwd


def _measure_steps(trainer, state, batch, iters, warmup):
    """Timed compiled-step loop; the clock stops after
    block_until_ready on the last step's state. Returns
    (step_time_s, last_loss)."""
    import jax
    import numpy as np

    for _ in range(warmup):
        state, loss = trainer.train_step(state, batch)
    jax.block_until_ready(state.params)
    t0 = time.perf_counter()
    for _ in range(iters):
        state, loss = trainer.train_step(state, batch)
    jax.block_until_ready(state.params)
    dt = (time.perf_counter() - t0) / iters
    if not np.isfinite(float(loss)):
        raise FloatingPointError("non-finite loss in bench")
    return dt, float(loss)


def apply_extra_params(cfg, batch_size):
    """The A/B channel shared by the transformer and decode benches:
    EDL_BENCH_EXTRA_PARAMS ("fused_head=True; seq_len=2048") model knobs
    and EDL_BENCH_BATCH. Shape-affecting keys merge INTO cfg so the
    synthetic batch follows (and vs_baseline correctly degrades to 1.0
    on config mismatch); the rest ride as model params. Returns
    (params_dict, extra_dict, batch_size); mutates cfg in place."""
    from elasticdl_tpu.common.model_utils import get_dict_from_params_str

    extra = get_dict_from_params_str(
        os.environ.get("EDL_BENCH_EXTRA_PARAMS", "")
    )
    cfg.update({k: v for k, v in extra.items() if k in cfg})
    batch_size = int(_env_float(None, "EDL_BENCH_BATCH", batch_size, 1))
    params = dict(cfg, dtype="bf16")
    params.update({k: v for k, v in extra.items() if k not in cfg})
    # the reported extra_params records EVERY ambient override, incl. a
    # bare EDL_BENCH_BATCH (report-only — batch_size is not a model
    # kwarg), so non-default runs are self-identifying and the
    # baseline policy can refuse them
    reported = dict(extra)
    if "EDL_BENCH_BATCH" in os.environ:
        reported["batch_size"] = batch_size
    return params, reported, batch_size


def run_transformer_bench():
    import numpy as np

    from model_zoo.transformer_lm import transformer_lm as zoo

    # d=1024/heads=8 -> head_dim 128: the flash kernel's 128-lane
    # tiles run unpadded, and the larger matmuls roughly double MFU
    # vs the previous d=512 flagship (0.34 vs 0.16 measured on v5e).
    cfg = dict(vocab_size=32000, seq_len=1024, embed_dim=1024,
               num_heads=8, num_layers=8)
    batch_size, iters, warmup = 32, 30, 5

    from elasticdl_tpu.common.model_utils import format_params_str

    params, extra, batch_size = apply_extra_params(cfg, batch_size)
    # packed=N (bench knob, not a model kwarg): train on rows carrying
    # N packed segments each — measures the segment-mask cost of the
    # sequence-packing path on the same shapes
    packed = int(params.pop("packed", 0))
    model_params = format_params_str(params)

    rng = np.random.RandomState(0)
    tokens = rng.randint(
        0, cfg["vocab_size"], size=(batch_size, cfg["seq_len"] + 1)
    ).astype(np.int32)
    features = {"tokens": tokens[:, :-1]}
    if packed:
        seg = np.minimum(
            np.arange(cfg["seq_len"]) * packed // cfg["seq_len"],
            packed - 1,
        )
        features["segment_ids"] = np.broadcast_to(
            seg.astype(np.int32), (batch_size, cfg["seq_len"])
        ).copy()
    batch = (features, tokens[:, 1:])
    step_time, n_chips, dev, platform, n_params = _run_zoo_bench(
        zoo, batch, iters, warmup, model_params=model_params
    )
    tokens_per_sec = batch_size * cfg["seq_len"] / step_time
    flops = transformer_flops_per_step(
        batch_size, cfg["seq_len"], cfg["embed_dim"], cfg["num_layers"],
        cfg["vocab_size"],
    )
    mfu = round(
        flops / step_time / (_peak_flops(dev.device_kind) * n_chips), 4)
    return {
        "metric": "transformer_lm_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec / n_chips, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,  # filled by _apply_vs_baseline
        "mfu": mfu,
        "samples_per_sec_per_chip": round(
            batch_size / step_time / n_chips, 2),
        "step_time_ms": round(step_time * 1e3, 2),
        "platform": platform,
        "device_kind": dev.device_kind,
        "params_m": round(n_params / 1e6, 1),
        "config": cfg,
        "extra_params": extra or None,
        "batch_size": batch_size,
    }


def _run_zoo_bench(zoo, batch, iters, warmup, model_params=""):
    """Shared setup + measurement for every bench target: spec -> mesh
    -> Trainer -> init -> pre-staged batch (the benchmark measures the
    compiled step; a real input pipeline double-buffers host->device
    transfers behind it) -> timed steps. Returns
    (step_time_s, n_chips, device, platform, n_params)."""
    import jax
    import numpy as np

    from elasticdl_tpu.common.model_utils import load_model_spec_from_module
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.training.trainer import Trainer

    spec = load_model_spec_from_module(zoo)
    mesh = mesh_lib.build_mesh()
    trainer = Trainer(spec, mesh=mesh, model_params=model_params)
    state = trainer.init_state(batch)
    n_params = sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(state.params)
    )
    batch = jax.device_put(batch, mesh_lib.batch_sharding(mesh))
    step_time, _ = _measure_steps(trainer, state, batch, iters, warmup)
    dev = jax.devices()[0]
    return (step_time, max(1, len(jax.devices())), dev,
            jax.default_backend(), n_params)


def run_resnet50_bench():
    """BASELINE.md secondary target: ResNet-50 images/sec (train)."""
    import numpy as np

    from model_zoo.imagenet_resnet50 import imagenet_resnet50 as zoo

    batch_size, size, iters, warmup = 64, 224, 20, 3

    rng = np.random.RandomState(0)
    batch = (
        {"image": rng.rand(batch_size, size, size, 3).astype(np.float32)},
        rng.randint(1000, size=(batch_size, 1)).astype(np.int32),
    )
    step_time, n_chips, dev, platform, _ = _run_zoo_bench(
        zoo, batch, iters, warmup
    )
    # ResNet-50 fwd ~4.1 GFLOP per 224x224 image; bwd = 2x fwd
    flops = 3 * batch_size * 4.1e9 * (size / 224.0) ** 2
    mfu = round(
        flops / step_time / (_peak_flops(dev.device_kind) * n_chips), 4)
    return {
        "metric": "resnet50_train_images_per_sec_per_chip",
        "value": round(batch_size / step_time / n_chips, 2),
        "unit": "images/sec/chip",
        "vs_baseline": None,  # filled by _apply_vs_baseline
        "mfu": mfu,
        "step_time_ms": round(step_time * 1e3, 2),
        "platform": platform,
        "device_kind": dev.device_kind,
        "batch_size": batch_size,
        "image_size": size,
    }


def run_vit_bench():
    """ViT images/sec (train) — net-new family (the reference zoo's
    vision ceiling is ResNet50). TPU config is ViT-Base-shaped at
    224px/patch 14 -> 256 patch tokens (tiles into the flash blocks;
    /16 would give 196, which falls back to blockwise)."""
    import numpy as np

    from elasticdl_tpu.common.model_utils import format_params_str
    from model_zoo.vit import vit as zoo

    cfg = dict(image_size=224, patch_size=14, num_classes=1000,
               embed_dim=768, num_heads=12, num_layers=12)
    batch_size, iters, warmup = 64, 20, 3

    params, extra, batch_size = apply_extra_params(cfg, batch_size)
    rng = np.random.RandomState(0)
    batch = (
        {"image": rng.rand(
            batch_size, cfg["image_size"], cfg["image_size"], 3
        ).astype(np.float32)},
        rng.randint(cfg["num_classes"],
                    size=(batch_size, 1)).astype(np.int32),
    )
    step_time, n_chips, dev, platform, n_params = _run_zoo_bench(
        zoo, batch, iters, warmup,
        model_params=format_params_str(params),
    )
    # fwd+bwd ~= 3 * 2 * params * tokens FLOPs (dense transformer rule;
    # attention at 256 tokens adds a few % — omitted, keeping the
    # estimate conservative)
    n_tokens = (cfg["image_size"] // cfg["patch_size"]) ** 2
    flops = 6.0 * n_params * n_tokens * batch_size
    mfu = round(
        flops / step_time / (_peak_flops(dev.device_kind) * n_chips), 4)
    return {
        "metric": "vit_train_images_per_sec_per_chip",
        "value": round(batch_size / step_time / n_chips, 2),
        "unit": "images/sec/chip",
        "vs_baseline": None,  # filled by _apply_vs_baseline
        "mfu": mfu,
        "step_time_ms": round(step_time * 1e3, 2),
        "params_m": round(n_params / 1e6, 1),
        "platform": platform,
        "device_kind": dev.device_kind,
        "config": cfg,
        "extra_params": extra or None,
        "batch_size": batch_size,
    }


def run_deepfm_bench():
    """BASELINE.md primary recsys target: DeepFM samples/sec (frappe
    schema; embedding + FM + DNN). MFU is not reported — the model is
    lookup/bandwidth-bound, not matmul-bound."""
    import numpy as np

    from model_zoo.deepfm_functional_api import deepfm_functional_api as zoo

    batch_size, iters, warmup = 8192, 30, 5

    rng = np.random.RandomState(0)
    batch = (
        {"feature": rng.randint(
            zoo.INPUT_DIM, size=(batch_size, 10)).astype(np.int32)},
        rng.randint(2, size=(batch_size,)).astype(np.int32),
    )
    step_time, n_chips, dev, platform, _ = _run_zoo_bench(
        zoo, batch, iters, warmup
    )
    return {
        "metric": "deepfm_train_samples_per_sec_per_chip",
        "value": round(batch_size / step_time / n_chips, 1),
        "unit": "samples/sec/chip",
        "vs_baseline": None,  # filled by _apply_vs_baseline
        "mfu": None,
        "step_time_ms": round(step_time * 1e3, 2),
        "platform": platform,
        "device_kind": dev.device_kind,
        "batch_size": batch_size,
    }


def run_decode_bench():
    """KV-cache autoregressive decode throughput (net-new surface: the
    reference has no generation story). Measures steady-state
    tokens/sec for batch decoding with the per-layer KV caches —
    O(L) attention per generated token."""
    import numpy as np

    from model_zoo.transformer_lm import transformer_lm as zoo

    cfg = dict(vocab_size=32000, seq_len=1024, embed_dim=1024,
               num_heads=8, num_layers=8)
    batch, prompt, new_tokens, iters = 16, 32, 224, 3

    from elasticdl_tpu.api.generation import autoregressive_generate
    from elasticdl_tpu.common.model_utils import (
        format_params_str,
        load_model_spec_from_module,
    )
    from elasticdl_tpu.parallel import mesh as mesh_lib
    from elasticdl_tpu.training.trainer import Trainer

    import jax

    # same A/B channel as the training bench (e.g. num_kv_heads for the
    # GQA decode-cache comparison; prompt/new_tokens for the batched-
    # prefill A/B — they are bench knobs, not model kwargs, so they are
    # popped out of the model params but stay in the reported extras)
    params, extra, batch = apply_extra_params(cfg, batch)
    if int(params.pop("moe", 0)):
        # decode the MoE family instead of the dense LM: the drop-free
        # inference dispatch (moe_infer_impl='dense'|'gather', see
        # parallel/moe.py moe_mlp_infer{,_gather}) only runs on
        # decode/prefill paths, so this knob is the one bench surface
        # that can A/B it on hardware:
        #   EDL_BENCH_MODEL=decode \
        #   EDL_BENCH_EXTRA_PARAMS="moe=1; moe_infer_impl='gather'"
        from model_zoo.transformer_moe import (  # noqa: F811
            transformer_moe as zoo,
        )
        params.setdefault("num_experts", 8)
        params.setdefault("router_top_k", 2)
        if "num_layers" not in extra:
            # match the moe training bench's depth (expert FFNs double
            # the layer cost vs the 8-layer dense decode config)
            params["num_layers"] = 4
        # the reported config must describe what actually ran
        cfg.update(num_layers=params["num_layers"],
                   num_experts=params["num_experts"],
                   router_top_k=params["router_top_k"])
    prompt = int(params.pop("prompt", prompt))
    new_tokens = int(params.pop("new_tokens", new_tokens))
    quantize = bool(params.pop("quantize", 0))
    beams = int(params.pop("beams", 0))  # 0 = greedy KV decode
    # speculative decode: gamma draft proposals per target verify.
    # spec_draft_layers=0 uses the TARGET as its own draft — acceptance
    # ~100%, measuring the mechanics ceiling; a shallow random draft
    # measures the floor (near-zero acceptance on random logits).
    spec_gamma = int(params.pop("spec_gamma", 0))
    spec_draft_layers = int(params.pop("spec_draft_layers", 2))
    # >0 distills the draft against the target before timing
    # (warm-start + KL on the target's own logits — api/distill.py):
    # the decode_spec_trained A/B vs the random-draft floor and the
    # self-draft (spec_draft_layers=0) ceiling
    spec_draft_train_steps = int(params.pop("spec_draft_train_steps", 0))
    # speculative verify chunks reach gamma-1 positions past the stream
    margin = spec_gamma - 1 if spec_gamma else 0
    if prompt + new_tokens + margin > cfg["seq_len"]:
        # scale to fit — the emitted prompt_len/new_tokens fields
        # report what actually ran
        room = cfg["seq_len"] - margin
        f = room / (prompt + new_tokens)
        prompt = max(1, int(prompt * f))
        new_tokens = max(1, min(room - prompt, int(new_tokens * f)))
        sys.stderr.write(
            "bench: prompt+new_tokens exceed seq_len %d (margin %d); "
            "scaled to prompt=%d new_tokens=%d\n"
            % (cfg["seq_len"], margin, prompt, new_tokens)
        )
    spec = load_model_spec_from_module(zoo)
    mesh = mesh_lib.build_mesh()
    trainer = Trainer(spec, mesh=mesh,
                      model_params=format_params_str(params))
    rng = np.random.RandomState(0)
    tokens = rng.randint(
        0, cfg["vocab_size"], size=(batch, cfg["seq_len"] + 1)
    ).astype(np.int32)
    state = trainer.init_state(
        ({"tokens": tokens[:, :-1]}, tokens[:, 1:])
    )
    prompt_ids = tokens[:, :prompt]
    if quantize:
        # weight-only int8 serving path (api/quantization.py): the
        # decode program dequantizes in-jit, weights travel as int8
        from elasticdl_tpu.api.quantization import quantize_params

        state = state.replace(params=quantize_params(state.params))

    if spec_gamma:
        from elasticdl_tpu.api.generation import speculative_generate

        if spec_draft_layers:
            d_params = dict(params, num_layers=spec_draft_layers)
            draft_trainer = Trainer(
                spec, mesh=mesh,
                model_params=format_params_str(d_params),
            )
            d_state = draft_trainer.init_state(
                ({"tokens": tokens[:, :-1]}, tokens[:, 1:])
            )
            if spec_draft_train_steps:
                from elasticdl_tpu.api.distill import (
                    distill_draft,
                    warm_start_draft,
                )

                d_state = warm_start_draft(state, d_state)
                d_state, _ = distill_draft(
                    trainer, state, draft_trainer, d_state,
                    [
                        rng.randint(
                            0, cfg["vocab_size"],
                            size=(batch, cfg["seq_len"]),
                        ).astype(np.int32)
                        for _ in range(spec_draft_train_steps)
                    ],
                )
        else:
            draft_trainer, d_state = trainer, state
        # acceptance telemetry once (same executable — return_stats
        # only gates Python-side post-processing), then the timed path
        # runs without stats
        _, spec_stats = speculative_generate(
            trainer, state, draft_trainer, d_state, prompt_ids,
            new_tokens, gamma=spec_gamma, return_stats=True,
        )
        extra["spec_acceptance_rate"] = round(
            spec_stats["acceptance_rate"], 3
        )
        extra["spec_verify_calls"] = spec_stats["verify_calls"]

        def decode():
            return speculative_generate(
                trainer, state, draft_trainer, d_state, prompt_ids,
                new_tokens, gamma=spec_gamma,
            )
    elif beams:
        from elasticdl_tpu.api.generation import beam_search_generate

        def decode():
            return beam_search_generate(
                trainer, state, prompt_ids, new_tokens,
                num_beams=beams, use_cache=True,
            )
    else:
        def decode():
            return autoregressive_generate(
                trainer, state, prompt_ids, new_tokens, use_cache=True
            )

    out = decode()  # compile
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = decode()
    jax.block_until_ready(out)
    dt = (time.perf_counter() - t0) / iters
    n_chips = max(1, len(jax.devices()))
    platform = jax.default_backend()
    tokens_per_sec = batch * new_tokens / dt
    return {
        "metric": "kv_cache_decode_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec / n_chips, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,  # filled by _apply_vs_baseline
        "mfu": None,
        "ms_per_token": round(dt * 1e3 / new_tokens, 3),
        "batch_size": batch,
        "prompt_len": prompt,
        "new_tokens": new_tokens,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "config": cfg,
        "extra_params": extra or None,
    }


def run_dlrm_bench():
    """BASELINE.json configs[4]: DLRM with ~1B embedding parameters
    (26 tables x 1.2M rows x 32 dims = 4 GB fp32 in sharded HBM,
    sparse-row updates). Samples/sec/chip; MFU not reported (the model
    is gather/bandwidth-bound)."""
    import numpy as np

    from model_zoo.dlrm import dlrm as zoo

    table_size, dim, batch_size, iters, warmup = (
        1_200_000, 32, 4096, 20, 3)

    from elasticdl_tpu.common.model_utils import format_params_str

    rng = np.random.RandomState(0)
    batch = (
        {
            "dense": rng.rand(batch_size, 13).astype(np.float32),
            "sparse": rng.randint(
                0, table_size, size=(batch_size, 26)
            ).astype(np.int32),
        },
        rng.randint(2, size=(batch_size,)).astype(np.int32),
    )
    step_time, n_chips, dev, platform, n_params = _run_zoo_bench(
        zoo, batch, iters, warmup,
        model_params=format_params_str(
            dict(table_size=table_size, embedding_dim=dim)
        ),
    )
    return {
        "metric": "dlrm_train_samples_per_sec_per_chip",
        "value": round(batch_size / step_time / n_chips, 1),
        "unit": "samples/sec/chip",
        "vs_baseline": None,  # filled by _apply_vs_baseline
        "mfu": None,
        "step_time_ms": round(step_time * 1e3, 2),
        "params_b": round(n_params / 1e9, 3),
        "platform": platform,
        "device_kind": dev.device_kind,
        "batch_size": batch_size,
        "table_size": table_size,
    }


def run_bert_bench():
    """BASELINE.json configs[4] first half: BERT-base-shape masked-LM
    pretraining throughput (12 layers x 768 x 12 heads, seq 512)."""
    import numpy as np

    from model_zoo.bert import bert as zoo

    cfg = dict(vocab_size=30522, seq_len=512, embed_dim=768,
               num_heads=12, num_layers=12)
    batch_size, iters, warmup = 16, 20, 3

    from elasticdl_tpu.common.model_utils import format_params_str

    params = dict(cfg, dtype="bf16")
    rng = np.random.RandomState(0)
    tokens = rng.randint(
        1, cfg["vocab_size"], size=(batch_size, cfg["seq_len"])
    ).astype(np.int32)
    # masked-LM batch matching the zoo's recipe (model_zoo/bert/bert.py
    # _mask_tokens): [MASK] is the reserved id vocab_size, and labels
    # carry the original token at masked positions, IGNORE_LABEL (-1)
    # elsewhere — so the bench loss is the real masked-subset loss
    masked = tokens.copy()
    mask_positions = np.zeros_like(tokens, bool)
    mask_positions[:, ::7] = True
    masked[mask_positions] = cfg["vocab_size"]
    labels = np.where(mask_positions, tokens, -1).astype(np.int32)
    batch = ({"tokens": masked}, labels)
    step_time, n_chips, dev, platform, n_params = _run_zoo_bench(
        zoo, batch, iters, warmup,
        model_params=format_params_str(params),
    )
    tokens_per_sec = batch_size * cfg["seq_len"] / step_time
    flops = transformer_flops_per_step(
        batch_size, cfg["seq_len"], cfg["embed_dim"],
        cfg["num_layers"], cfg["vocab_size"],
    )
    mfu = round(
        flops / step_time / (_peak_flops(dev.device_kind) * n_chips), 4)
    return {
        "metric": "bert_mlm_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec / n_chips, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,  # filled by _apply_vs_baseline
        "mfu": mfu,
        "step_time_ms": round(step_time * 1e3, 2),
        "params_m": round(n_params / 1e6, 1),
        "platform": platform,
        "device_kind": dev.device_kind,
        "config": cfg,
        "batch_size": batch_size,
    }


def run_moe_bench():
    """Mixture-of-experts LM training throughput: top-2 (GShard)
    routing over a stacked expert bank. Single-chip runs measure the
    dense-equivalent tokens/sec at k-of-E active expert FLOPs per
    token; on an ep mesh the same code all-to-alls tokens to their
    experts (driver dryrun sub-run 5 proves the sharded path)."""
    import numpy as np

    from model_zoo.transformer_moe import transformer_moe as zoo

    cfg = dict(vocab_size=32000, seq_len=1024, embed_dim=1024,
               num_heads=8, num_layers=4, num_experts=8,
               router_top_k=2)
    batch_size, iters, warmup = 16, 20, 3

    from elasticdl_tpu.common.model_utils import format_params_str

    params, extra, batch_size = apply_extra_params(cfg, batch_size)
    rng = np.random.RandomState(0)
    tokens = rng.randint(
        0, cfg["vocab_size"], size=(batch_size, cfg["seq_len"] + 1)
    ).astype(np.int32)
    batch = ({"tokens": tokens[:, :-1]}, tokens[:, 1:])
    step_time, n_chips, dev, platform, n_params = _run_zoo_bench(
        zoo, batch, iters, warmup,
        model_params=format_params_str(params),
    )
    tokens_per_sec = batch_size * cfg["seq_len"] / step_time
    return {
        "metric": "moe_lm_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec / n_chips, 1),
        "unit": "tokens/sec/chip",
        "vs_baseline": None,  # filled by _apply_vs_baseline
        "mfu": None,  # MoE FLOPs depend on routing; tokens/sec is the claim
        "step_time_ms": round(step_time * 1e3, 2),
        "params_m": round(n_params / 1e6, 1),
        "num_experts": cfg["num_experts"],
        "router_top_k": cfg["router_top_k"],
        "platform": platform,
        "device_kind": dev.device_kind,
        "config": cfg,
        "extra_params": extra or None,
        "batch_size": batch_size,
    }


_BENCHES = {
    "transformer": run_transformer_bench,
    "resnet50": run_resnet50_bench,
    "vit": run_vit_bench,
    "deepfm": run_deepfm_bench,
    "decode": run_decode_bench,
    "dlrm": run_dlrm_bench,
    "bert": run_bert_bench,
    "moe": run_moe_bench,
}

# Default-run suite: flagship FIRST (a truncated run still leaves the
# headline number in the stream), then the other train families, then
# the decode pair (greedy + int8 KV cache). resnet50/deepfm stay
# reachable via EDL_BENCH_MODEL.
_SUITE = (
    # (family, model, env overrides, expected parsed extra_params —
    #  part of the family's baseline identity)
    ("transformer", "transformer", None, None),
    ("moe", "moe", None, None),
    ("bert", "bert", None, None),
    ("dlrm", "dlrm", None, None),
    ("decode", "decode", None, None),
    ("decode_kv_int8", "decode",
     {"EDL_BENCH_EXTRA_PARAMS": "kv_cache_dtype='int8'"},
     {"kv_cache_dtype": "int8"}),
    # tail entry: if the suite budget truncates, only this drops
    ("vit", "vit", None, None),
)


def _baseline_path(family):
    return os.path.join(
        REPO, "BENCH_BASELINE.json" if family == "transformer"
        else "BENCH_BASELINE_%s.json" % family.upper())


def _baseline_comparable(family, base, result):
    """Same-config identity between a committed record and this run.
    Non-transformer families include extra_params in the identity (for
    decode_kv_int8 the extra IS the family); the transformer keeps the
    no-extras check so A/B knobs read as a direct ratio against the
    plain flagship record."""
    same = (base.get("metric") == result.get("metric")
            and base.get("config") == result.get("config")
            and base.get("batch_size") == result.get("batch_size")
            and base.get("device_kind") == result.get("device_kind"))
    if family != "transformer":
        same = same and (
            base.get("extra_params") == result.get("extra_params"))
    return same and bool(base.get("value"))


def _apply_vs_baseline(family, result):
    """Fill result["vs_baseline"]: ratio to the committed same-config
    hardware record, 1.0 when there is no comparable record (this run
    establishes it)."""
    vs = 1.0
    try:
        with open(_baseline_path(family)) as f:
            base = json.load(f)
        if _baseline_comparable(family, base, result):
            vs = round(result["value"] / float(base["value"]), 4)
    except (OSError, ValueError):
        pass
    result["vs_baseline"] = vs
    return result


def _maybe_persist_baseline(family, result, expected_extra=None):
    """Baseline persistence, the ONE policy for BENCH_BASELINE*.json: a
    family run becomes the committed record when there is no record
    yet, when the existing record's identity (config/batch/chip/extras)
    no longer matches this run's — a retuned config or a new chip
    generation starts a fresh baseline rather than pinning vs_baseline
    to 1.0 forever — or when the same-identity value improved. Refuses
    runs whose extra_params differ from the family's declared identity
    (ambient operator knobs must never become a committed record)."""
    if result.get("extra_params") != expected_extra:
        return
    path = _baseline_path(family)
    try:
        with open(path) as f:
            old = json.load(f)
    except (OSError, ValueError):
        old = {}
    better = (
        not old
        or not _baseline_comparable(family, old, result)
        or result.get("value", 0) > old.get("value", 0)
    )
    if better:
        rec = {k: v for k, v in result.items()
               if k not in ("vs_baseline", "family", "suite", "families")}
        with open(path, "w") as f:
            json.dump(rec, f, indent=1)
        sys.stderr.write("bench: %s updated\n" % os.path.basename(path))


def _run_one(model_name, family=None):
    """One family bench; fills vs_baseline. A failure propagates."""
    return _apply_vs_baseline(family or model_name, _BENCHES[model_name]())


_FAMILY_SUMMARY_KEYS = (
    "metric", "value", "unit", "vs_baseline", "mfu", "step_time_ms",
    "ms_per_token", "platform", "params_m", "params_b",
)


def run_suite():
    """Run every suite family, streaming one JSON line per family as it
    completes (a driver timeout still leaves every finished family in
    the stream), then print the flagship summary line carrying the
    whole suite in "families". A family that raises ends the run with
    its traceback. A per-suite wall-clock budget
    (EDL_BENCH_SUITE_BUDGET) skips trailing families rather than
    risking a silent driver kill."""
    budget_s = _env_float(None, "EDL_BENCH_SUITE_BUDGET", 900.0, 60.0)
    t0 = time.monotonic()
    families = {}
    flagship = None
    for fam, model, env_extra, expected_extra in _SUITE:
        if flagship is not None and time.monotonic() - t0 > budget_s:
            sys.stderr.write(
                "bench: suite budget %.0fs exhausted; skipping %s\n"
                % (budget_s, fam))
            families[fam] = {"skipped": "suite_budget"}
            continue
        saved = {k: os.environ.get(k) for k in (env_extra or {})}
        os.environ.update(env_extra or {})
        try:
            result = _run_one(model, family=fam)
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v
        _maybe_persist_baseline(fam, result, expected_extra)
        result["family"] = fam
        print(json.dumps(result), flush=True)
        families[fam] = {
            k: result[k] for k in _FAMILY_SUMMARY_KEYS if k in result
        }
        if fam == "transformer":
            flagship = result
    summary = dict(flagship)
    summary.pop("family", None)
    summary["suite"] = True
    summary["families"] = families
    print(json.dumps(summary))


def main():
    model_name = os.environ.get("EDL_BENCH_MODEL", "suite")
    if model_name != "suite" and model_name not in _BENCHES:
        sys.exit(
            "bench: unknown EDL_BENCH_MODEL %r (valid: suite, %s)"
            % (model_name, ", ".join(sorted(_BENCHES)))
        )
    from elasticdl_tpu.common.platform_utils import configure_compile_cache

    configure_compile_cache()
    dev = require_tpu()
    _peak_flops(dev.device_kind)  # an unknown chip fails before any run
    sys.stderr.write("bench: running on %s\n" % dev.device_kind)
    if model_name == "suite":
        run_suite()
    else:
        print(json.dumps(_run_one(model_name)))


if __name__ == "__main__":
    main()
