"""The real `sc2-3b-train` step compiled for a described v5e (no chip
attached): one chip as `train-4k` runs it, and dp=4 over the 2x2 host
as the queued `train-4k-dp4` will. What the TPU's compiler refuses, or
a step that no longer fits a device, shows here at no chip time. All in
this one file: only the worker that is given it loads the TPU library.
Nothing here is a measurement."""

import json
import os
import re

import jax
import pytest

from cbhelp import ROOT
from chipbench import offchip

HBM = 16e9


@pytest.fixture(scope="module")
def topo():
    try:
        return offchip.describe("v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to compile for
        pytest.skip("no v5e:2x2 topology can be described here: %s" % e)


@pytest.fixture(scope="module")
def no_cache():
    """A compile for a described chip is written to the persistent
    cache but can never be read back: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _files(traffic):
    cb = os.path.join(ROOT, "chipbench")
    cfg = json.load(open(os.path.join(cb, "configs", "sc2-3b-train.json")))
    mix = json.load(open(os.path.join(cb, "traffic", traffic + ".json")))
    for data in (cfg, mix):
        data.pop("rehearsal")
    return cfg, mix


@pytest.fixture(scope="module")
def one_chip(topo, no_cache):
    cfg, mix = _files("train-packed-4k")
    return offchip.compile_train_step(ROOT, cfg, mix, topo.devices[:1])


@pytest.fixture(scope="module")
def dp4(topo, no_cache):
    cfg, mix = _files("train-packed-4k-dp4")
    return offchip.compile_train_step(ROOT, cfg, mix, topo.devices)


def _kernels(compiled):
    return len(re.findall(r"tpu_custom_call", compiled.as_text()))


def test_one_chip_step_compiles_with_its_flash_kernels(one_chip):
    # 4 layers x (forward, its recomputation under remat, dq, dkv)
    assert _kernels(one_chip) == 16
    assert "all-reduce" not in one_chip.as_text()


def test_one_chip_step_fits_the_device(one_chip):
    m = one_chip.memory_analysis()
    # 688 M parameters x (fp32 weights + adamw's two moments)
    assert 8.0e9 < m.argument_size_in_bytes < 8.5e9
    assert offchip.device_bytes(one_chip) < 0.8 * HBM


def test_dp4_step_compiles_with_flash_kernels_and_collectives(dp4):
    assert _kernels(dp4) == 16
    assert "all-reduce" in dp4.as_text()


def test_dp4_step_fits_each_device_like_one_chip(dp4, one_chip):
    # same per-chip batch, state replicated: the bytes of one chip,
    # give or take the gradient exchange's buffers
    assert offchip.device_bytes(dp4) < 0.8 * HBM
    assert abs(offchip.device_bytes(dp4)
               - offchip.device_bytes(one_chip)) < 1.0e9


def test_flash_kernels_are_named_in_the_lowered_step(topo):
    """The trace reduction finds the kernels by these names."""
    from unittest import mock

    import jax.numpy as jnp

    from elasticdl_tpu.ops import attention, dispatch

    q = jax.ShapeDtypeStruct((1, 24, 4096, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 2, 4096, 128), jnp.bfloat16)

    def loss(q, k, v):
        return attention.flash_attention(
            q, k, v, causal=True, window=4096).astype(jnp.float32).sum()

    with mock.patch.object(dispatch, "is_tpu_backend", lambda: True):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).trace(
            q, kv, kv).lower(lowering_platforms=("tpu",)).as_text()
    for name in ("_flash_kernel", "_flash_bwd_dq_kernel",
                 "_flash_bwd_dkv_kernel"):
        assert name in text, name
