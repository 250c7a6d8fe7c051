"""Kimi-Linear's plumbing (``tests/model/test_kimi_linear.py`` holds
its numerics to the reference; the tiny model, its two checkpoints and
the packed row are that file's): the configuration read from the
published keys and written back, what the family cannot run refused by
name, the spans and counters of a train step and of a generation, the
scan's own sub-part of the program's text, the checkpoint's round trip
(whole and streamed), and what does not run a pattern. None of it
depends on the decay's draw, so ONE checkpoint is built here: the
share under the harness's initialisation. A file of its own so that
``--dist loadfile`` can give it to another worker than the numerics'.
"""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference
from benchmark.families import kimi_linear as family
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.interfaces import sft
from realhf_tpu.models import hf as hf_models
from realhf_tpu.models import transformer as T
from realhf_tpu.models.hf import registry
from realhf_tpu.parallel import mesh as mesh_lib
from realhf_tpu.models.operators import n_params

from test_kimi_linear import (  # noqa: F401 - ``built`` is a fixture
    _BASE,
    CONFIGS,
    DOCS_IN_ROW,
    NAME,
    ROLE,
    ROW,
    _engine,
    _packed,
    built,
)


def test_config_is_read_from_the_published_keys(built):
    from realhf_tpu.models.config import DeltaConfig, LatentConfig
    model = built("share")
    cfg, hf = model["cfg"], model["hf"]
    assert cfg.layer_pattern == (
        ("delta", "dense"), ("delta", "moe"), ("delta", "moe"),
        ("latent", "moe"), ("delta", "moe"))
    assert cfg.pattern_string == "d d d l d"
    assert (cfg.layers_of("delta"), cfg.kv_layers, cfg.layers_of("latent"),
            cfg.layers_of("conv", "window"), cfg.n_moe_layers) == (
        (0, 1, 2, 4), 1, (3,), (), 4)
    assert cfg.delta == DeltaConfig(n_heads=4, head_dim=16, conv_kernel=4)
    assert cfg.delta.gate_rank == 16
    assert cfg.latent == LatentConfig(kv_rank=24, rope_dim=8, v_dim=12)
    assert (cfg.head_dim, cfg.v_head_dim, cfg.n_kv_heads, cfg.n_q_heads,
            cfg.layer_norm_epsilon) == (24, 12, 4, 4, 1e-5)
    # NO rotary embedding anywhere: the latent layers say so
    assert cfg.rotary_by_operator == {"latent": None}
    assert cfg.rotary_of("latent") is None
    moe = cfg.moe
    assert (moe.num_experts, moe.n_held, moe.experts_held, moe.top_k,
            moe.score_fn, moe.use_expert_bias, moe.norm_topk_prob,
            moe.routed_scaling_factor, moe.norm_topk_eps,
            moe.intermediate_dim, moe.shared_intermediate_dim) == (
        16, 4, (4, 4), 3, "sigmoid", True, True, 2.446, 1e-20, 16, 16)
    back = hf_models.config_to_hf(NAME, cfg)
    for key, value in hf.items():
        if key not in ("initializer_range", "eos_token_id", "rope_theta"):
            assert back[key] == value, key
    again = hf_models.config_from_hf(NAME, back)
    again.param_dtype = again.compute_dtype = "float32"
    assert again == cfg
    # the family's count is the checkpoint's; the program's leaves out
    # the layers' norms and the final one
    held = sum(v.size for v in model["tensors"].values())
    assert family.n_params(hf) == held
    assert n_params(cfg) == held - (2 * 5 + 1) * 64


@pytest.mark.parametrize("key,value", [
    ("mla_use_nope", False), ("q_lora_rank", 32),
    ("rope_scaling", {"type": "yarn", "factor": 4.0}),
    ("num_expert_group", 4), ("topk_group", 2),
    ("moe_router_activation_func", "softmax"),
    ("num_nextn_predict_layers", 1), ("moe_layer_freq", 2),
    ("linear_attn_config", dict(_BASE["linear_attn_config"],
                                full_attn_layers=[3, 4])),
    ("linear_attn_config", dict(_BASE["linear_attn_config"],
                                kda_layers=[1, 2, 3])),
])
def test_what_the_family_cannot_run_is_refused_by_name(key, value):
    hf = dict(CONFIGS["whole"], **{key: value})
    with pytest.raises(NotImplementedError,
                       match="kda_layers" if key == "linear_attn_config"
                       else key):
        hf_models.config_from_hf(NAME, hf)
    with pytest.raises(NotImplementedError):
        family.dims(hf)


def test_train_step_spans_say_what_ran(built):
    """One optimizer step through ``Engine.train_batch``: the span's
    attributes delta layers bring, the counter, every new leaf moved,
    the selection bias left as loaded."""
    from realhf_tpu.obs import tracing
    from realhf_tpu.ops.delta_rule import CHUNK
    model = built("share")
    cfg = dataclasses.replace(
        model["cfg"], gradient_checkpointing=True)
    engine = _engine(cfg, model["params"], optimizer=OptimizerConfig(
        lr=1e-2, warmup_steps_proportion=0.0,
        lr_scheduler_type="constant"), total_train_steps=10)
    ids, seg, _ = _packed()
    mb = dict(input_ids=ids, seg_ids=seg,
              prompt_mask=np.zeros((1, ROW), bool))
    before = jax.tree.map(np.asarray, engine.params)
    tracing.start()
    stats = engine.train_batch([mb, mb], sft._make_loss_fn(cfg),
                               loss_fn_key="sft")
    capture = tracing.stop()
    after = jax.tree.map(np.asarray, engine.params)
    for i in cfg.layers_of("delta"):
        d0, d1 = (p["layers"][str(i)]["delta"] for p in (before, after))
        assert sorted(d0) == sorted(
            "wq wk wv conv_q conv_k conv_v a_log w_fa w_fb dt_bias w_b "
            "w_ga w_gb o_norm wo".split())
        for leaf in d0:
            assert not np.array_equal(d0[leaf], d1[leaf]), (i, leaf)
    for i in range(1, 5):
        m0, m1 = (p["layers"][str(i)]["mlp"] for p in (before, after))
        assert not np.array_equal(m0["router"], m1["router"])
        assert np.array_equal(m0["expert_bias"], m1["expert_bias"])
    [span] = capture.named("engine:train")
    a = span["attributes"]
    assert (a["layer_pattern"], a["delta_layers"], a["delta_heads"],
            a["delta_head_dim"], a["delta_chunk"], a["latent_layers"],
            a["kv_lora_rank"], a["qk_dim"], a["v_dim"],
            a["shared_expert"], a["experts_held"], a["experts"],
            a["top_k"], a["router"], a["conv_layers"],
            a["dense_layers"]) == (
        "d d d l d", 4, 4, 16, CHUNK, 1, 24, 24, 12, 16, 4, 16, 3,
        "sigmoid_bias", 0, 1)
    assert a["rotary"] == "l:none"
    tokens = 2 * sum(DOCS_IN_ROW)
    assert capture.counter("delta_tokens_total", role=ROLE) == tokens * 4
    assert capture.counter("moe_routed_pairs_total", role=ROLE,
                           dispatch="ragged") == tokens * 3 * 4
    assert np.isfinite(stats["loss"]) and np.isfinite(stats["grad_norm"])


def test_generate_span_says_the_delta_states_bytes(built):
    from realhf_tpu.obs import tracing
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    model = built("share")
    engine = model["engine"]
    ids = model["docs"][:, :8]
    tracing.start()
    engine.generate(
        ids, np.ones_like(ids), np.tile(np.arange(8), (2, 1)),
        jax.random.PRNGKey(0),
        GenerationHyperparameters(max_new_tokens=2, greedy=True,
                                  force_no_logits_mask=True),
        eos_token_id=None, pad_token_id=0)
    capture = tracing.stop()
    [span] = capture.named("engine:generate")
    a = span["attributes"]
    # four delta layers, two streams: 4 heads x 16 x 16 float32 and
    # three rows of 3 x 64 in float32 (the test's compute dtype)
    assert a["delta_state_bytes"] == 4 * 2 * (4 * 16 * 16 * 4
                                              + 3 * 192 * 4)
    assert (a["kv_layers"], a["conv_state_bytes"]) == (1, 0)
    assert capture.counter("delta_tokens_total", role=ROLE) == \
        (2 * 8 + 2 * 2) * 4


def test_the_scan_is_a_sub_part_of_delta(built):
    """``obs/parts.py``: the chunked recurrence lowers under
    ``delta/scan`` (forward, rematerialised and backward), the
    projections, convolutions, gates, the output's norm and ``wo``
    under ``delta`` itself; a reader of the whole part
    (``train.delta_s``) holds both."""
    from realhf_tpu.obs import parts
    assert parts.DELTA in parts.PARTS
    assert parts.SUB_STEPS[parts.DELTA] == (parts.SCAN,)
    model = built("share")
    cfg = dataclasses.replace(model["cfg"], gradient_checkpointing=True)
    ids, seg, _ = _packed()

    def loss(p):
        with jax.named_scope(parts.FORWARD_BACKWARD):
            h, _ = T.forward(cfg, p, jnp.asarray(ids), jnp.asarray(seg))
        return (h ** 2).sum()

    text = jax.jit(jax.grad(loss)).lower(model["params"]).compile().as_text()
    table = parts.parse_program(text)
    seen = {(part, pass_) for part, pass_, *_ in table.values()}
    for pass_ in (parts.FWD, parts.BWD):
        assert ("delta", pass_) in seen
        assert ("delta/scan", pass_) in seen
    assert parts.classify(
        "jit(f)/forward_backward/layers/delta/scan/while/body/dot_general"
    )[:1] == ("delta/scan",)
    assert parts.classify(
        "jit(f)/transpose(jvp(forward_backward))/layers/delta/mul"
    )[:2] == ("delta", parts.BWD)


def test_hf_round_trip_is_bit_equal(built, tmp_path):
    """Checkpoint -> program -> checkpoint: every tensor back under its
    name with its bits, ``A_log`` in its [1, 1, heads, 1] and the taps
    in Conv1d's [channels, 1, taps]."""
    model = built("share")
    cfg, params = registry.load_hf_checkpoint(model["ckpt"], NAME)
    path = str(tmp_path / "out")
    registry.save_hf_checkpoint(path, NAME, cfg, params)
    back = reference.load_tensors(path)
    assert set(back) == set(model["tensors"])
    for name, want in model["tensors"].items():
        assert back[name].shape == want.shape, name
        assert np.array_equal(back[name].view(np.uint16),
                              want.view(np.uint16)), name
    with open(os.path.join(path, "config.json")) as f:
        written = json.load(f)
    assert written["linear_attn_config"]["kda_layers"] == [1, 2, 3, 5]
    assert written["expert_share"] == {"of": 16, "first": 4}


@pytest.mark.parametrize("tp", [1, 2])
def test_streamed_load_and_save_round_trip(built, tmp_path, tp):
    model = built("share")
    par = mesh_lib.ParallelismConfig(tensor_parallel_size=tp)
    mesh = mesh_lib.make_mesh(par, jax.devices()[:tp])
    cfg, params = registry.load_hf_checkpoint_streamed(
        model["ckpt"], mesh, NAME, param_dtype="float32")
    ref = model["params"]
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(ref)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    path = str(tmp_path / "streamed")
    registry.save_hf_checkpoint_streamed(path, NAME, cfg, params)
    back = reference.load_tensors(path)
    assert set(back) == set(model["tensors"])


def test_what_does_not_run_a_pattern_refuses_by_name(built):
    """The slot engine, the paged pool, pipeline stages and the
    allocation search know one kind of block and one kind of decode
    state: under delta layers they raise, naming the layers that keep
    a state a head."""
    from realhf_tpu.engine import inflight, kv_pool
    from realhf_tpu.models import sharding
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    model = built("share")
    cfg, params = model["cfg"], model["params"]
    g = GenerationHyperparameters(max_new_tokens=2, greedy=True,
                                  force_no_logits_mask=True)
    named = (r"layer pattern \(layer_pattern 'd d d l d': 4 delta layers, "
             r"1 latent layers")
    with pytest.raises(NotImplementedError, match="slot engine.*" + named):
        inflight.InflightBatchingGenerator(
            cfg, params, g, n_slots=2, max_prompt_len=8,
            eos_token_id=None, pad_token_id=0)
    with pytest.raises(NotImplementedError, match="KV pool.*" + named):
        kv_pool.KVPool(cfg, n_blocks=4, block_len=8)
    with pytest.raises(NotImplementedError, match="pipeline.*" + named):
        sharding.param_pspecs(cfg, pipeline_parallel=True)
    with pytest.raises(NotImplementedError, match="slot engine.*" + named):
        model["engine"].inflight_generator(g)


def test_the_config_says_what_a_delta_layer_may_be():
    """``TransformerConfig``: delta layers need their ``DeltaConfig``
    and a pattern; only latent and (since ``nemotron_h``) full attention
    layers may say that they have no rotary embedding: a window layer
    may not."""
    from realhf_tpu.models.config import (
        DeltaConfig,
        RotaryConfig,
        TransformerConfig,
    )
    base = dict(
        n_layers=2, n_kv_heads=2, n_q_heads=2, hidden_dim=32, head_dim=16,
        intermediate_dim=64, vocab_size=64, layer_norm_type="rms",
        mlp_type="llama", apply_rotary=True, use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu")
    delta = DeltaConfig(n_heads=2, head_dim=16)
    cfg = TransformerConfig(
        **base, layer_pattern=(("delta", "dense"), ("attention", "dense")),
        delta=delta)
    assert (cfg.layers_of("delta"), cfg.layers_of("attention")) == ((0,), (1,))
    assert delta.width == 32 and delta.conv_kernel == 4
    with pytest.raises(ValueError, match="1 delta layers, delta is None"):
        TransformerConfig(**base, layer_pattern=(
            ("delta", "dense"), ("attention", "dense")))
    with pytest.raises(ValueError, match="0 delta layers"):
        TransformerConfig(**base, delta=delta, layer_pattern=(
            ("attention", "dense"),) * 2)
    with pytest.raises(NotImplementedError, match="layer_pattern"):
        TransformerConfig(**base, delta=delta)
    with pytest.raises(ValueError, match=r"lacks \['window'\]"):
        TransformerConfig(
            **base, delta=delta, sliding_window=8,
            rotary_by_operator={"window": None},
            layer_pattern=(("delta", "dense"), ("window", "dense")))
    bare = TransformerConfig(
        **base, delta=delta, rotary_by_operator={"attention": None},
        layer_pattern=(("delta", "dense"), ("attention", "dense")))
    assert bare.rotary_of("attention") is None
    assert RotaryConfig().describe() == "plain@10000/1"
