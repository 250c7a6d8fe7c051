"""Streamed (host-RAM-bounded) checkpoint loading: for every family,
``load_hf_checkpoint_streamed`` must place EXACTLY the weights the
eager loader reads -- sharded on the mesh, vocab-padded for its tp --
while only ever holding one layer (plus embeddings) on host."""

import numpy as np
import pytest

import jax

from realhf_tpu.models import sharding as shard_rules
from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import MoEConfig, TransformerConfig
from realhf_tpu.models.hf import (
    load_hf_checkpoint,
    load_hf_checkpoint_streamed,
    save_hf_checkpoint,
    save_hf_checkpoint_streamed,
)
from realhf_tpu.parallel.mesh import ParallelismConfig, make_mesh


def _cfg(family, vocab=96):
    base = dict(n_layers=3, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
                intermediate_dim=64, vocab_size=vocab, n_positions=128,
                compute_dtype="float32")
    if family == "gpt2":
        g = dict(base, n_kv_heads=4)  # gpt2 fused c_attn has no GQA
        return TransformerConfig(
            layer_norm_type=None, mlp_type=None,
            activation_function="gelu_new", apply_rotary=False,
            use_attention_bias=True, use_attn_proj_bias=True,
            use_mlp_bias=True, tied_embedding=True, **g)
    if family == "mixtral":
        return TransformerConfig(
            layer_norm_type="rms", mlp_type="moe",
            activation_function="silu", apply_rotary=True,
            use_attention_bias=False, use_attn_proj_bias=False,
            use_mlp_bias=False,
            moe=MoEConfig(num_experts=4, top_k=2), **base)
    if family == "olmoe":
        return TransformerConfig(
            layer_norm_type="rms", mlp_type="moe",
            activation_function="silu", apply_rotary=True,
            use_attention_bias=False, use_attn_proj_bias=False,
            use_mlp_bias=False, qk_norm="full",
            moe=MoEConfig(num_experts=4, top_k=2, norm_topk_prob=False),
            **dict(base, n_kv_heads=4))
    if family == "gemma":
        return TransformerConfig(
            layer_norm_type="gemma", mlp_type="llama",
            activation_function="gelu_new", apply_rotary=True,
            use_attention_bias=False, use_attn_proj_bias=False,
            use_mlp_bias=False, normalize_embed=True,
            tied_embedding=True, **base)
    return TransformerConfig(
        layer_norm_type="rms", mlp_type="llama",
        activation_function="silu", apply_rotary=True,
        use_attention_bias=False, use_attn_proj_bias=False,
        use_mlp_bias=False, **base)


@pytest.mark.parametrize("family", ["llama", "gpt2", "mixtral", "gemma",
                                    "olmoe"])
def test_streamed_matches_eager(family, tmp_path):
    cfg = _cfg(family)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    path = str(tmp_path / family)
    save_hf_checkpoint(path, family, cfg,
                       jax.tree.map(np.asarray, params))

    par = ParallelismConfig(data_parallel_size=4, tensor_parallel_size=2)
    mesh = make_mesh(par)
    cfg_s, streamed = load_hf_checkpoint_streamed(path, mesh,
                                                  family=family)
    cfg_e, eager = load_hf_checkpoint(path, family=family)
    assert cfg_s.n_layers == cfg_e.n_layers == cfg.n_layers

    host = shard_rules.unpad_vocab(
        cfg_s, jax.tree.map(np.asarray, streamed))
    e_flat = jax.tree_util.tree_flatten_with_path(eager)[0]
    s_flat = jax.tree_util.tree_flatten_with_path(host)[0]
    assert [k for k, _ in e_flat] == [k for k, _ in s_flat]
    for (kp, a), (_, b) in zip(e_flat, s_flat):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            rtol=1e-6, atol=1e-7, err_msg=str(kp))

    # leaves really landed sharded on the mesh
    wq = streamed["blocks"]["attn"]["wq"]
    assert wq.sharding.mesh.shape == mesh.shape


def test_streamed_critic_value_head(tmp_path):
    cfg = _cfg("llama")
    params = T.init_params(cfg, jax.random.PRNGKey(1))
    path = str(tmp_path / "actor")
    save_hf_checkpoint(path, "llama", cfg,
                       jax.tree.map(np.asarray, params))

    par = ParallelismConfig(data_parallel_size=4, tensor_parallel_size=2)
    mesh = make_mesh(par)
    cfg_s, streamed = load_hf_checkpoint_streamed(
        path, mesh, family="llama", is_critic=True)
    cfg_e, eager = load_hf_checkpoint(path, family="llama",
                                      is_critic=True)
    assert cfg_s.is_critic
    np.testing.assert_allclose(
        np.asarray(streamed["head"]["w"], np.float32),
        np.asarray(eager["head"]["w"], np.float32), rtol=1e-6)


def test_streamed_bare_gpt2_naming(tmp_path):
    """Bare GPT2Model exports (no ``transformer.`` container prefix)
    load through the lazy PrefixedStateView on the streamed path just
    as the eager loader's dict-rename fallback does."""
    import json
    import os

    import safetensors.numpy

    cfg = _cfg("gpt2")
    params = T.init_params(cfg, jax.random.PRNGKey(4))
    src = str(tmp_path / "full")
    save_hf_checkpoint(src, "gpt2", cfg, jax.tree.map(np.asarray, params))

    bare = str(tmp_path / "bare")
    os.makedirs(bare)
    state = {}
    for f in os.listdir(src):
        if f.endswith(".safetensors"):
            state.update(safetensors.numpy.load_file(os.path.join(src, f)))
    stripped = {
        (k[len("transformer."):] if k.startswith("transformer.") else k): v
        for k, v in state.items() if k != "lm_head.weight"}
    safetensors.numpy.save_file(
        stripped, os.path.join(bare, "model.safetensors"))
    with open(os.path.join(src, "config.json")) as f:
        conf = json.load(f)
    with open(os.path.join(bare, "config.json"), "w") as f:
        json.dump(conf, f)

    mesh = make_mesh(ParallelismConfig(data_parallel_size=4,
                                       tensor_parallel_size=2))
    _, streamed = load_hf_checkpoint_streamed(bare, mesh, family="gpt2")
    _, eager = load_hf_checkpoint(bare, family="gpt2")
    host = shard_rules.unpad_vocab(cfg, jax.tree.map(np.asarray, streamed))
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(eager)[0],
            jax.tree_util.tree_flatten_with_path(host)[0]):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-6, err_msg=str(kp))


def test_build_model_streamed_flag(tmp_path):
    """ModelSpec.streamed_load routes build_model through the
    streaming loader and yields the same weights as the eager path."""
    from realhf_tpu.api.experiment import ModelSpec
    from realhf_tpu.system.model_host import build_model

    cfg = _cfg("llama")
    params = T.init_params(cfg, jax.random.PRNGKey(3))
    path = str(tmp_path / "m")
    save_hf_checkpoint(path, "llama", cfg,
                       jax.tree.map(np.asarray, params))

    par = ParallelismConfig(data_parallel_size=4, tensor_parallel_size=2)
    kw = dict(path=path, hf_family="llama", parallel=par, bf16=False)
    m_s = build_model("actor", ModelSpec(streamed_load=True, **kw),
                      None, 10)
    m_e = build_model("actor", ModelSpec(**kw), None, 10)
    for a, b in zip(jax.tree.leaves(m_s.engine.params),
                    jax.tree.leaves(m_e.engine.params)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32), rtol=1e-6)


def test_streamed_load_auto_threshold(tmp_path, monkeypatch):
    """streamed_load=None streams automatically for checkpoints whose
    safetensors total exceeds the cutoff, stays eager below it, and
    False forces eager regardless."""
    from realhf_tpu.api.experiment import ModelSpec
    from realhf_tpu.system import model_host

    cfg = _cfg("llama")
    params = T.init_params(cfg, jax.random.PRNGKey(7))
    path = str(tmp_path / "m")
    save_hf_checkpoint(path, "llama", cfg,
                       jax.tree.map(np.asarray, params))

    spec = ModelSpec(path=path, hf_family="llama")
    assert not model_host._use_streamed_load(spec)  # tiny -> eager
    monkeypatch.setattr(model_host, "STREAMED_LOAD_AUTO_BYTES", 1)
    assert model_host._use_streamed_load(spec)      # auto-streams
    # auto streams on process-spanning meshes too: every member sizes
    # the same spec.path, so the collective schedule agrees (r5: the
    # multiproc -> eager restriction is lifted)
    assert model_host._use_streamed_load(spec, multiproc=True)
    assert model_host._use_streamed_load(
        ModelSpec(path=path, hf_family="llama", streamed_load=True),
        multiproc=True)
    spec_off = ModelSpec(path=path, hf_family="llama",
                         streamed_load=False)
    assert not model_host._use_streamed_load(spec_off)  # forced eager


def test_streamed_vocab_padding_roundtrip(tmp_path):
    """vocab_size NOT divisible by tp: the streamed loader must pad
    wte/head for the mesh's tp and the streamed saver must strip that
    padding back to the true vocab (the early-return paths hide both
    when vocab % tp == 0)."""
    import jax.numpy as jnp

    cfg = _cfg("llama", vocab=97)  # 97 % 2 != 0 -> real padding
    host = jax.tree.map(np.asarray, T.init_params(cfg,
                                                  jax.random.PRNGKey(6)))
    path = str(tmp_path / "m")
    save_hf_checkpoint(path, "llama", cfg, host)

    mesh = make_mesh(ParallelismConfig(data_parallel_size=4,
                                       tensor_parallel_size=2))
    _, streamed = load_hf_checkpoint_streamed(path, mesh, family="llama")
    assert streamed["embed"]["wte"].shape[0] == 98  # padded to tp mult
    assert streamed["head"]["w"].shape[1] == 98
    back = shard_rules.unpad_vocab(cfg, jax.tree.map(np.asarray, streamed))
    for (kp, a), (_, b) in zip(
            jax.tree_util.tree_flatten_with_path(host)[0],
            jax.tree_util.tree_flatten_with_path(back)[0]):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-6, atol=1e-7, err_msg=str(kp))

    # streamed SAVE from the padded device params strips the padding
    out = str(tmp_path / "out")
    save_hf_checkpoint_streamed(out, "llama", cfg, streamed)
    _, loaded = load_hf_checkpoint(out, family="llama")
    assert loaded["embed"]["wte"].shape[0] == 97
    np.testing.assert_allclose(
        np.asarray(loaded["head"]["w"], np.float32),
        np.asarray(host["head"]["w"], np.float32), rtol=1e-6, atol=1e-7)


@pytest.mark.parametrize("family", ["llama", "mixtral", "olmoe"])
def test_streamed_save_roundtrip(family, tmp_path):
    """save_hf_checkpoint_streamed (one shard per layer, sliced from
    sharded device arrays) produces a directory the EAGER loader reads
    back to the exact original weights."""
    import jax.numpy as jnp

    cfg = _cfg(family)
    host = jax.tree.map(np.asarray, T.init_params(cfg,
                                                  jax.random.PRNGKey(5)))
    mesh = make_mesh(ParallelismConfig(data_parallel_size=4,
                                       tensor_parallel_size=2))
    padded = shard_rules.pad_vocab(cfg, host, 2)
    dev = jax.tree_util.tree_map_with_path(
        lambda kp, leaf: jax.device_put(
            jnp.asarray(leaf),
            _sharding_at(shard_rules.param_shardings(cfg, mesh), kp)),
        padded)
    path = str(tmp_path / "out")
    save_hf_checkpoint_streamed(path, family, cfg, dev)

    import os
    shard_files = [f for f in os.listdir(path)
                   if f.endswith(".safetensors")]
    assert len(shard_files) == cfg.n_layers + 1  # one per layer + rest

    _, loaded = load_hf_checkpoint(path, family=family)
    e_flat = jax.tree_util.tree_flatten_with_path(host)[0]
    l_flat = jax.tree_util.tree_flatten_with_path(loaded)[0]
    assert [k for k, _ in e_flat] == [k for k, _ in l_flat]
    for (kp, a), (_, b) in zip(e_flat, l_flat):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-6, atol=1e-7, err_msg=str(kp))


def _sharding_at(shardings, kp):
    node = shardings
    for entry in kp:
        node = node[entry.key]
    return node


def test_streamed_bf16_cast(tmp_path):
    cfg = _cfg("llama")
    params = T.init_params(cfg, jax.random.PRNGKey(2))
    path = str(tmp_path / "m")
    save_hf_checkpoint(path, "llama", cfg,
                       jax.tree.map(np.asarray, params))
    mesh = make_mesh(ParallelismConfig(data_parallel_size=8))
    cfg_s, streamed = load_hf_checkpoint_streamed(
        path, mesh, family="llama", param_dtype="bfloat16")
    import jax.numpy as jnp
    for leaf in jax.tree.leaves(streamed):
        assert leaf.dtype == jnp.bfloat16


def test_agreed_streamed_load_follows_leader(tmp_path, monkeypatch):
    """On a process-spanning mesh the auto verdict is GROUP-AGREED:
    the lowest-rank process publishes via name_resolve and members
    adopt it even when their own filesystem view would disagree
    (stale-NFS divergence would otherwise hang mismatched collective
    load schedules)."""
    import collections

    import jax as _jax

    from realhf_tpu.api.experiment import ModelSpec
    from realhf_tpu.base import constants
    from realhf_tpu.system import model_host

    cfg = _cfg("llama")
    params = T.init_params(cfg, jax.random.PRNGKey(9))
    path = str(tmp_path / "m")
    save_hf_checkpoint(path, "llama", cfg,
                       jax.tree.map(np.asarray, params))
    spec = ModelSpec(path=path, hf_family="llama")

    monkeypatch.setattr(constants, "_experiment_name", "agreetest")
    monkeypatch.setattr(constants, "_trial_name", "t0")

    Dev = collections.namedtuple("Dev", "process_index")

    class FakeMesh:
        class devices:
            flat = [Dev(0), Dev(1)]

    # leader (process 0): sizes the checkpoint -> streams (cutoff 1)
    monkeypatch.setattr(model_host, "STREAMED_LOAD_AUTO_BYTES", 1)
    monkeypatch.setattr(_jax, "process_index", lambda: 0)
    assert model_host._agreed_streamed_load(spec, FakeMesh, "roleA")

    # member (process 1) with a DIVERGENT local view (cutoff back to
    # huge -> its own verdict would be eager): adopts the leader's
    monkeypatch.setattr(model_host, "STREAMED_LOAD_AUTO_BYTES", 1e18)
    monkeypatch.setattr(_jax, "process_index", lambda: 1)
    assert model_host._agreed_streamed_load(spec, FakeMesh, "roleA")

    # explicit flag short-circuits the rendezvous entirely (patch
    # back to the leader so a regression fails fast instead of
    # stalling in the member's 300s name_resolve wait)
    monkeypatch.setattr(_jax, "process_index", lambda: 0)
    spec_off = ModelSpec(path=path, hf_family="llama",
                         streamed_load=False)
    assert not model_host._agreed_streamed_load(spec_off, FakeMesh,
                                                "roleB")
