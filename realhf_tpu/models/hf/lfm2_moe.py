"""LFM2-MoE HF conversion (``LFM2-24B-A2B``, ``model_type: lfm2_moe``):
gated short convolutions and attention layers in one stack, a dense
lead (``num_dense_layers``) before sparse layers whose router scores
by sigmoid and chooses by score + ``expert_bias``.

The family DECLARES its layers: ``layer_types`` and
``num_dense_layers`` of the published config become
``TransformerConfig.layer_pattern``, one (operator, feed-forward) a
layer, and the parameters are a tree a layer under
``params["layers"]`` (``models/transformer.py``). Layers of unlike
kinds hold unlike tensors, so the converters work a LAYER at a time
(``layer_from_hf`` / ``layer_to_hf``), which is also what the streamed
load and save call.

**An expert-parallel rank's share.** A published checkpoint holds all
``num_experts``. A rank's checkpoint says in its ``config.json`` what
it holds::

    "num_experts": 8,                          experts held here
    "expert_share": {"of": 64, "first": 0}     experts 0..7 of 64

``num_experts`` counts the experts whose weights are in the files,
``expert_share.of`` is the published count (the width of the router
and of ``expert_bias``), ``first`` the global id of the first one
held; the files name experts by their GLOBAL id. Without
``expert_share`` every expert is held. ``MoEConfig.experts_held``
carries it to ``ops/moe.py``.

Tensor names and the sparse block are from memory of
``transformers``' ``modeling_lfm2_moe.py`` (not in the 4.57.6
installed here); conv, attention, dense feed-forward and layer order
are 4.57.6's ``modeling_lfm2.py``, which tests hold the benchmark's
reference to. ``conv_bias: true`` is refused, not ignored.
"""

from typing import Any, Dict

import numpy as np

from realhf_tpu.models.config import MoEConfig, TransformerConfig
from realhf_tpu.models.hf.registry import (
    HFFamily,
    StateDict,
    held_expert_ids,
    layered_converters,
    register_hf_family,
)

_PRE = "model.layers.{}."
#: leaf of a feed-forward (dense, or one expert) -> HF's name for it
_FFN = (("wg", "w1"), ("wu", "w3"), ("wd", "w2"))
_ATTN = (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
         ("wo", "out_proj"))


def _config_from_hf(d: Dict[str, Any], is_critic: bool) -> TransformerConfig:
    if d.get("conv_bias", False):
        raise NotImplementedError(
            "lfm2_moe: conv_bias=true is not implemented "
            "(LFM2-24B-A2B publishes false)")
    n = d["num_hidden_layers"]
    types = d.get("layer_types") or ["full_attention"] * n
    unknown = set(types) - {"conv", "full_attention"}
    if unknown or len(types) != n:
        raise NotImplementedError(
            f"lfm2_moe: layer_types {sorted(unknown)} / {len(types)} "
            f"entries for {n} layers")
    dense = d.get("num_dense_layers", 0)
    share = d.get("expert_share")
    held = d["num_experts"]
    nq = d["num_attention_heads"]
    rope = d.get("rope_parameters") or {}
    return TransformerConfig(
        n_layers=n,
        n_kv_heads=d.get("num_key_value_heads", nq),
        n_q_heads=nq,
        hidden_dim=d["hidden_size"],
        head_dim=d.get("head_dim") or d["hidden_size"] // nq,
        intermediate_dim=d["intermediate_size"],
        vocab_size=d["vocab_size"],
        n_positions=d.get("max_position_embeddings"),
        layer_norm_epsilon=d.get("norm_eps", 1e-5),
        activation_function="silu",
        use_attention_bias=False,
        use_attn_proj_bias=False,
        use_mlp_bias=False,
        layer_norm_type="rms",
        mlp_type="llama",
        apply_rotary=True,
        rotary_base=float(rope.get("rope_theta",
                                   d.get("rope_theta", 1000000.0))),
        tied_embedding=d.get("tie_word_embeddings", True),
        qk_norm="head",
        layer_pattern=tuple(
            ("conv" if t == "conv" else "attention",
             "dense" if i < dense else "moe")
            for i, t in enumerate(types)),
        conv_kernel=d.get("conv_L_cache", 3),
        moe=MoEConfig(
            num_experts=share["of"] if share else held,
            top_k=d["num_experts_per_tok"],
            routing_type="none",
            norm_topk_prob=d.get("norm_topk_prob", True),
            score_fn="sigmoid",
            use_expert_bias=d.get("use_expert_bias", True),
            routed_scaling_factor=float(
                d.get("routed_scaling_factor", 1.0)),
            intermediate_dim=d["moe_intermediate_size"],
            experts_held=(share["first"], held) if share else None),
        is_critic=is_critic,
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    moe = cfg.moe
    d = {
        "model_type": "lfm2_moe",
        "architectures": ["Lfm2MoeForCausalLM"],
        "hidden_size": cfg.hidden_dim,
        "intermediate_size": cfg.intermediate_dim,
        "moe_intermediate_size": moe.intermediate_dim
        or cfg.intermediate_dim,
        "num_hidden_layers": cfg.n_layers,
        "layer_types": ["conv" if op == "conv" else "full_attention"
                        for op, _ in cfg.layer_pattern],
        "num_dense_layers": sum(ff == "dense"
                                for _, ff in cfg.layer_pattern),
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "num_experts": moe.n_held,
        "num_experts_per_tok": moe.top_k,
        "norm_topk_prob": moe.norm_topk_prob,
        "use_expert_bias": moe.use_expert_bias,
        "routed_scaling_factor": moe.routed_scaling_factor,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.n_positions or 128000,
        "norm_eps": cfg.layer_norm_epsilon,
        "rope_parameters": {"rope_theta": cfg.rotary_base,
                            "rope_type": "default"},
        "conv_L_cache": cfg.conv_kernel,
        "conv_bias": False,
        "tie_word_embeddings": cfg.tied_embedding,
        "torch_dtype": "float32",
    }
    if moe.experts_held is not None:
        d["expert_share"] = {"of": moe.num_experts,
                             "first": moe.experts_held[0]}
    return d


def layer_from_hf(state: StateDict, cfg: TransformerConfig,
                  i: int) -> Dict[str, Any]:
    """The tree of layer ``i``: the leaves its (operator,
    feed-forward) has, HF Linear weights (out, in) transposed."""
    pre = _PRE.format(i)
    op, ff = cfg.layer_pattern[i]
    lp: Dict[str, Any] = {
        "ln1": {"scale": state[pre + "operator_norm.weight"]},
        "ln2": {"scale": state[pre + "ffn_norm.weight"]}}
    if op == "conv":
        lp["conv"] = {
            "w_in": state[pre + "conv.in_proj.weight"].T,
            # Conv1d's [channels, 1, taps] -> [taps, channels]
            "w": state[pre + "conv.conv.weight"][:, 0, :].T,
            "w_out": state[pre + "conv.out_proj.weight"].T}
    else:
        lp["attn"] = {leaf: state[f"{pre}self_attn.{hf}.weight"].T
                      for leaf, hf in _ATTN}
        lp["attn"]["q_norm"] = state[pre + "self_attn.q_layernorm.weight"]
        lp["attn"]["k_norm"] = state[pre + "self_attn.k_layernorm.weight"]
    ffn = pre + "feed_forward."
    if ff == "dense":
        lp["mlp"] = {leaf: state[f"{ffn}{hf}.weight"].T
                     for leaf, hf in _FFN}
        return lp
    lp["mlp"] = {"router": state[ffn + "gate.weight"].T}
    if cfg.moe.use_expert_bias:
        lp["mlp"]["expert_bias"] = state[ffn + "expert_bias"]
    for leaf, hf in _FFN:
        lp["mlp"][leaf] = np.stack(
            [state[f"{ffn}experts.{e}.{hf}.weight"].T
             for e in held_expert_ids(cfg)], axis=0)
    return lp


def layer_to_hf(lp: Dict[str, Any], cfg: TransformerConfig, i: int,
                out: StateDict):
    """Inverse of :func:`layer_from_hf`."""
    pre = _PRE.format(i)
    op, ff = cfg.layer_pattern[i]
    c = np.ascontiguousarray
    out[pre + "operator_norm.weight"] = c(lp["ln1"]["scale"])
    out[pre + "ffn_norm.weight"] = c(lp["ln2"]["scale"])
    if op == "conv":
        out[pre + "conv.in_proj.weight"] = c(lp["conv"]["w_in"].T)
        out[pre + "conv.conv.weight"] = c(lp["conv"]["w"].T[:, None, :])
        out[pre + "conv.out_proj.weight"] = c(lp["conv"]["w_out"].T)
    else:
        for leaf, hf in _ATTN:
            out[f"{pre}self_attn.{hf}.weight"] = c(lp["attn"][leaf].T)
        out[pre + "self_attn.q_layernorm.weight"] = c(lp["attn"]["q_norm"])
        out[pre + "self_attn.k_layernorm.weight"] = c(lp["attn"]["k_norm"])
    ffn = pre + "feed_forward."
    if ff == "dense":
        for leaf, hf in _FFN:
            out[f"{ffn}{hf}.weight"] = c(lp["mlp"][leaf].T)
        return
    out[ffn + "gate.weight"] = c(lp["mlp"]["router"].T)
    if cfg.moe.use_expert_bias:
        out[ffn + "expert_bias"] = c(lp["mlp"]["expert_bias"])
    for leaf, hf in _FFN:
        for j, e in enumerate(held_expert_ids(cfg)):
            out[f"{ffn}experts.{e}.{hf}.weight"] = c(lp["mlp"][leaf][j].T)


_params_from_hf, _params_to_hf = layered_converters(
    layer_from_hf, layer_to_hf,
    final_norm="model.embedding_norm.weight")

register_hf_family(HFFamily(
    name="lfm2_moe", hf_model_type="lfm2_moe",
    config_from_hf=_config_from_hf,
    config_to_hf=_config_to_hf,
    params_from_hf=_params_from_hf,
    params_to_hf=_params_to_hf,
    layer_from_hf=layer_from_hf,
    layer_to_hf=layer_to_hf,
))
