"""The server has ONE engine: the block-paged pool is its KV layout
(PR 30 removed the dense per-slot pool and the switch that chose
between the two). Locks what that left behind: the `--kv_paged` flag
that the benchmark's files still pass, the default configuration's
same-bytes block budget, the dead environment variable, the input
check on a model without the `paged` argument, and a hot reload that
would change quantization under the compiled executables."""

import os

import jax
import numpy as np
import pytest

from elasticdl_tpu.common.model_utils import (
    get_model_spec,
    load_model_spec_from_module,
)
from elasticdl_tpu.parallel import mesh as mesh_lib
from elasticdl_tpu.serving import main as serving_main
from elasticdl_tpu.serving.engine import PagedContinuousBatchingEngine
from elasticdl_tpu.serving.server import GenerationServer, ServingConfig
from elasticdl_tpu.training.trainer import Trainer
from model_zoo.transformer_lm import transformer_lm as zoo

ZOO = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "model_zoo")
PARAMS = ("vocab_size=8; seq_len=32; embed_dim=16; num_heads=2; "
          "num_layers=1; pos_emb='rope'")


@pytest.fixture(scope="module")
def rig():
    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(load_model_spec_from_module(zoo), mesh=mesh,
                      model_params=PARAMS, seed=0)
    toks = (np.arange(33)[None, :] % 8).astype(np.int32)
    state = trainer.init_state(({"tokens": toks[:, :-1]}, toks[:, 1:]))
    return trainer, state


def _flags(*extra):
    return ["--model_zoo", ZOO,
            "--model_def", "transformer_lm.transformer_lm.custom_model",
            "--model_params", PARAMS, "--port", "0", "--num_slots", "2",
            "--kv_block_size", "4", *extra]


@pytest.mark.parametrize("flag", [None, "1", "0"])
def test_kv_paged_flag_unset_and_1_build_the_paged_server_0_is_refused(
        flag, capsys, monkeypatch):
    """`--kv_paged 1` is what the benchmark's serve configuration
    passes; unset is what everything else does. Both build the same
    server. `0` asked for the pool that is gone: refused at parse
    time, with a message that says so."""
    # build_server would point this process's (and its children's)
    # compile cache at the checkout for every test that runs after
    from elasticdl_tpu.common import platform_utils

    monkeypatch.setattr(platform_utils, "configure_compile_cache",
                        lambda: None)
    extra = () if flag is None else ("--kv_paged", flag)
    if flag == "0":
        with pytest.raises(SystemExit) as exc:
            serving_main.parse_serving_args(_flags(*extra))
        assert exc.value.code == 2
        err = capsys.readouterr().err
        assert "--kv_paged" in err
        assert "dense KV pool was removed in PR 30" in err
        return
    server = serving_main.build_server(
        serving_main.parse_serving_args(_flags(*extra)))
    assert isinstance(server.engine, PagedContinuousBatchingEngine)
    stats = server.engine.kv_stats()
    assert stats["kv_paged"] and stats["kv_block_size"] == 4
    assert stats["kv_blocks_total"] == 2 * 32 // 4


def test_default_server_is_paged_with_the_same_bytes_budget(rig):
    """GenerationServer(trainer, state), no configuration: the paged
    pool, holding the rows of num_slots sequences of seq_len tokens in
    blocks of 16 — on the wire too (ServerStatus.kv_paged, field 18,
    stays for the drills and chip_smoke.py that assert it)."""
    from elasticdl_tpu.proto import elasticdl_pb2 as pb

    trainer, state = rig
    server = GenerationServer(trainer, state).start(grpc_server=False)
    try:
        cfg = server.config
        st = server.raw_servicer.server_status(pb.ServerStatusRequest())
        assert st.kv_paged
        assert st.kv_block_size == 16
        assert st.kv_blocks_total == cfg.num_slots * 32 // 16
        assert st.kv_blocks_free == st.kv_blocks_total
    finally:
        server.stop()
    with pytest.raises(TypeError):
        ServingConfig(kv_paged=True)


def test_edl_kv_paged_in_the_environment_changes_nothing(
        rig, monkeypatch):
    """EDL_KV_PAGED chose the engine once; nothing reads it now."""
    trainer, state = rig
    monkeypatch.setenv("EDL_KV_PAGED", "0")
    server = GenerationServer(
        trainer, state, ServingConfig(num_slots=2, kv_block_size=4))
    assert isinstance(server.engine, PagedContinuousBatchingEngine)
    assert server.engine.kv_stats()["kv_paged"]
    assert server.engine.kv_stats()["kv_blocks_total"] == 2 * 32 // 4


def test_model_without_the_paged_argument_is_refused_by_name():
    """transformer_moe decodes with `decode` / `prefill` and takes no
    `paged` argument (ROADMAP D12: served offline only until R1). The
    server refuses it when it is built, says what the model has to
    implement, and names no engine."""
    mesh = mesh_lib.build_mesh({"dp": 1}, devices=jax.devices()[:1])
    trainer = Trainer(
        get_model_spec(ZOO, "transformer_moe.transformer_moe.custom_model"),
        mesh=mesh,
        model_params="vocab_size=8; seq_len=16; embed_dim=16; "
                     "num_heads=2; num_layers=1; num_experts=2",
    )
    toks = np.zeros((1, 16), np.int32)
    state = trainer.init_state(({"tokens": toks}, toks))
    with pytest.raises(ValueError) as exc:
        GenerationServer(trainer, state)
    message = str(exc.value)
    assert "`paged` argument" in message and "kv_out" in message
    assert type(trainer.model).__name__ in message
    assert "engine" not in message.lower()


def test_hot_reload_cannot_change_quantization(rig):
    """The compiled executables bake the dequantize path: a reload
    that hands int8 params to a server built on float ones is refused
    before anything is swapped (the check lived in the dense engine's
    half of set_params and never ran under the paged one)."""
    from elasticdl_tpu.api.quantization import quantize_params

    trainer, state = rig
    eng = PagedContinuousBatchingEngine(trainer, state, 2, block_size=4)
    before = eng.variables
    with pytest.raises(ValueError, match="cannot change quantization"):
        eng.set_params(
            state.replace(params=quantize_params(state.params, 1)), 1)
    assert eng.variables is before and eng.model_version == 0
