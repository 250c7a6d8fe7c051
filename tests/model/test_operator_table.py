"""The operator table (``models/operators.py``): a layer operator is
ONE record, and the modules around it ask the record.

(a) A toy operator that only THIS file knows (put into the table and
into the config's vocabulary by monkeypatch) initialises, is sharded,
trains a step, prefills, decodes and shows on the engine's spans:
``transformer.py``, ``sharding.py``, ``engine.py`` and ``tracing.py``
take a new operator without an edit. (b) For every family's tiny
config the two walks of the leaves' one declaration agree, and every
cache key has the shape and dtype its record declares."""

import glob
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realhf_tpu.api.config import ModelName
from realhf_tpu.engine.engine import Engine
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.interfaces import sft
from realhf_tpu.models import config as C
from realhf_tpu.models import operators as O
from realhf_tpu.models import sharding
from realhf_tpu.models import transformer as T
from realhf_tpu.models.hf import registry
from realhf_tpu.obs import tracing
from realhf_tpu.ops.sampling import GenerationHyperparameters
from realhf_tpu.parallel import mesh as mesh_lib

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _toy_apply(cfg, lp, u, ctx):
    """``y_t = w * (u_t + u_{t-1})``: a mixer of one leaf whose state
    is one row, the token before."""
    before = jnp.pad(u, ((0, 0), (1, 0), (0, 0)))[:, :-1]
    return (u + before) * lp["toy"]["w"].astype(u.dtype), (u,)


def _toy_step(cfg, lp, u, rows, ctx):
    (before,) = rows  # [B, 1, H]
    return (u + before[:, 0].astype(u.dtype)) \
        * lp["toy"]["w"].astype(u.dtype), (u[:, None].astype(before.dtype),)


TOY = O.Operator(
    leaves=lambda cfg, i: {"toy": {
        "w": O.Leaf((cfg.hidden_dim,), O.ONES, O.HEADS)}},
    scope="toy", apply=_toy_apply, step=_toy_step,
    state=(O.State("toy_row", lambda cfg, b, s: (b, 1, cfg.hidden_dim),
                   lambda cfg, rows, seg, total, dtype: rows[:, :, -1:],
                   jnp.float32),),
    attrs=lambda cfg, n: dict(toy_layers=n, toy_width=cfg.hidden_dim),
    token_counter="toy_tokens_total", state_bytes="toy_state_bytes")


@pytest.fixture
def toy(monkeypatch):
    monkeypatch.setitem(O.OPERATORS, "toy", TOY)
    monkeypatch.setattr(C, "OPERATORS", C.OPERATORS + ("toy",))
    monkeypatch.setitem(C.OPERATOR_LETTERS, "toy", "t")
    return C.TransformerConfig(
        n_layers=3, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
        intermediate_dim=48, vocab_size=64, layer_norm_type="rms",
        mlp_type="llama", apply_rotary=True, use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        compute_dtype="float32",
        layer_pattern=(("toy", "dense"), ("attention", "dense"),
                       ("toy", C.ABSENT)))


def _engine(cfg, params, **kwargs):
    par = mesh_lib.ParallelismConfig()
    ctx = mesh_lib.MeshContext(
        ModelName("toy", 0), mesh_lib.make_mesh(par, jax.devices()[:1]), par)
    return Engine(cfg, ctx, jax.tree.map(np.asarray, params), **kwargs)


def test_a_toy_operator_runs_with_no_other_module_touched(toy):
    cfg = toy
    assert cfg.pattern_string == "t a t" and cfg.layers_of("toy") == (0, 2)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    assert set(params["layers"]["2"]) == {"ln1", "toy"}
    assert np.array_equal(params["layers"]["0"]["toy"]["w"], np.ones(32))
    specs = sharding.param_pspecs(cfg)
    assert specs["layers"]["0"]["toy"] == {"w": O.HEADS}
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=lambda x: isinstance(x, sharding.P))
    # a weight that is not the identity's, so that the state matters
    params["layers"]["0"]["toy"]["w"] = jnp.linspace(0.5, 1.5, 32)

    ids = np.asarray(jax.random.randint(jax.random.PRNGKey(1), (2, 10),
                                        0, 64), np.int32)
    seg = np.ones_like(ids)
    want, _ = T.forward(cfg, params, jnp.asarray(ids), jnp.asarray(seg))
    want = np.asarray(T.lm_logits(cfg, params, want))
    n_pre = 8
    hidden, cache = T.prefill(cfg, params, jnp.asarray(ids[:, :n_pre]),
                              jnp.asarray(seg[:, :n_pre]), total_len=10)
    assert cache["toy_row"].shape == (2, 2, 1, 32) \
        and cache["toy_row"].dtype == jnp.float32
    empty = T.init_kv_cache(cfg, 2, 10)
    assert {k: (v.shape, v.dtype) for k, v in empty.items()} == \
        {k: (v.shape, v.dtype) for k, v in cache.items()}
    got = [np.asarray(T.lm_logits(cfg, params, hidden))]
    for t in range(n_pre, 10):  # two tokens
        h, cache = T.decode_step(cfg, params, cache, jnp.asarray(ids[:, t]),
                                 jnp.full((2,), t, jnp.int32),
                                 uniform_slot=True)
        got.append(np.asarray(T.lm_logits(cfg, params, h))[:, None])
    assert np.abs(np.concatenate(got, axis=1) - want).max() < 1e-5

    engine = _engine(cfg, params, optimizer=OptimizerConfig(
        lr=1e-2, warmup_steps_proportion=0.0,
        lr_scheduler_type="constant"), total_train_steps=10)
    mb = dict(input_ids=ids, seg_ids=seg,
              prompt_mask=np.zeros(ids.shape, bool))
    before = np.asarray(engine.params["layers"]["2"]["toy"]["w"])
    tracing.start()
    stats = engine.train_batch([mb], sft._make_loss_fn(cfg),
                               loss_fn_key="sft")
    engine.generate(ids[:, :n_pre], seg[:, :n_pre], np.broadcast_to(
        np.arange(n_pre, dtype=np.int32), (2, n_pre)),
        jax.random.PRNGKey(0), GenerationHyperparameters(
            max_new_tokens=2, greedy=True, force_no_logits_mask=True),
        eos_token_id=None, pad_token_id=0)
    capture = tracing.stop()
    assert np.isfinite(stats["loss"])
    assert not np.array_equal(
        before, np.asarray(engine.params["layers"]["2"]["toy"]["w"]))
    [train] = capture.named("engine:train")
    [generate] = capture.named("engine:generate")
    for span in (train, generate):
        a = span["attributes"]
        assert (a["layer_pattern"], a["toy_layers"], a["toy_width"],
                a["conv_layers"]) == ("t a t", 2, 32, 0)
    assert generate["attributes"]["toy_state_bytes"] == 2 * 2 * 1 * 32 * 4
    assert capture.counter("toy_tokens_total", role="toy") \
        == 2 * (ids.size + ids[:, :n_pre].size + 2 * 2)


TINY = sorted(glob.glob(os.path.join(
    ROOT, "tests", "benchmark", "*", "configs", "tiny-*.json")))
FAMILIES = {"KeyeVL2": "keye_vl2"}  # model_type -> the registry's name


def _tiny(path):
    with open(path) as f:
        hf = json.load(f)
    return registry.config_from_hf(
        FAMILIES.get(hf["model_type"], hf["model_type"]), hf)


@pytest.mark.parametrize("path", TINY, ids=os.path.basename)
def test_the_two_walks_of_a_declaration_agree(path):
    """``init_params`` and ``param_pspecs`` return one structure, a
    spec no longer than its leaf's shape; every cache key has the shape
    and the dtype its record declares, over the layers that own it."""
    cfg = _tiny(path)
    params = jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    specs = sharding.param_pspecs(cfg)
    is_spec = lambda x: isinstance(x, sharding.P)
    assert jax.tree.structure(params) == jax.tree.structure(
        specs, is_leaf=is_spec)
    for leaf, spec in zip(jax.tree.leaves(params),
                          jax.tree.leaves(specs, is_leaf=is_spec)):
        assert len(spec) <= len(leaf.shape), (leaf.shape, spec)
    batch, slots = 3, 256  # (a multiple of the cache's rounding)
    cache = T.init_kv_cache(cfg, batch, slots, jnp.bfloat16)
    declared = {st.key: ((n, *st.shape(cfg, batch, slots)),
                         jnp.dtype(st.dtype or jnp.bfloat16))
                for st, _, n in O.states(cfg)}
    kv = (cfg.kv_layers, batch, cfg.n_kv_heads, slots)
    assert {k: (v.shape, v.dtype) for k, v in cache.items()} == {
        **declared,
        "k": (kv + (cfg.head_dim,), jnp.bfloat16),
        "v": (kv + (cfg.v_head_dim,), jnp.bfloat16),
        "valid": ((batch, slots), jnp.dtype(bool)),
        "length": ((batch,), jnp.dtype(jnp.int32))}
    assert cfg.kv_layers == cfg.n_passes * sum(
        O.OPERATORS[op].kv for op, _ in cfg.layer_kinds)
    grown = T.extend_kv_cache(cache, 128)
    for st, _, n in O.states(cfg):
        more = 0 if st.slots is None else 128
        assert grown[st.key].shape == (
            n, *st.shape(cfg, batch, slots + more))
