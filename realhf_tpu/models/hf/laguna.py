"""Laguna HF conversion (``Laguna-XS.2``, ``model_type: laguna``): window
and full attention in one stack, each kind with its own count of query
heads and its own rotary embedding, an output gate a head, a dense
lead before sparse layers that add a SHARED expert to the routed ones.

The family DECLARES its layers, as ``lfm2_moe`` does: ``layer_types``
(``full_attention`` / ``sliding_attention``) and ``mlp_layer_types``
(``dense`` / ``sparse``) of the published config become
``TransformerConfig.layer_pattern`` (operators "attention" and
"window"), ``num_attention_heads_per_layer`` becomes ``layer_q_heads``
and ``rope_parameters``, which is keyed by the layer type, becomes
``rotary_by_operator``: plain rotary over the whole head at base 10,000
in the window layers, YaRN over ``partial_rotary_factor`` of it in the
full ones. Converters work a LAYER at a time (``layer_from_hf`` /
``layer_to_hf``), which the streamed load and save call.

**An expert-parallel rank's share** is said as in ``lfm2_moe.py``:
``num_experts`` counts the experts whose weights are in the files,
``expert_share: {"of": 256, "first": 0}`` the published count (the
router's width) and the global id of the first one held; the files name
experts by their GLOBAL id. The shared expert is in every rank's files.

``transformers`` 4.57.6 has no ``laguna`` and there is no network here:
the tensor names follow the families whose config keys these are
(``self_attn.{q,k,v,o}_proj``, ``mlp.gate``, ``mlp.experts.{e}.{gate,
up,down}_proj``, ``mlp.shared_expert``) with ``self_attn.g_proj`` for
the output gate, and are NOT confirmed against the published modelling
code; nor are: pre-norm with two norms a layer and no query/key norm;
the halves convention of the (partial) rotation; one sigmoid gate a
head from the layer's normed input; a sigmoid router whose 8 gates are
renormalised, scaled by ``moe_routed_scaling_factor`` and carry no
selection bias; a shared expert added with weight 1. What is claimed is
the architecture's shapes and named mechanisms, not that the published
checkpoint loads. ``moe_apply_router_weight_on_input: true`` and an
``attention_bias`` are refused, not ignored.
"""

from typing import Any, Dict

import numpy as np

from realhf_tpu.models.config import (
    MoEConfig,
    RotaryConfig,
    TransformerConfig,
)
from realhf_tpu.models.hf.registry import (
    HFFamily,
    StateDict,
    held_expert_ids,
    layered_converters,
    register_hf_family,
)

_PRE = "model.layers.{}."
#: leaf of a feed-forward (dense, shared, or one expert) -> HF's name
_FFN = (("wg", "gate_proj"), ("wu", "up_proj"), ("wd", "down_proj"))
_ATTN = (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
         ("wo", "o_proj"), ("w_gate", "g_proj"))
#: ``layer_types`` entry <-> operator of the pattern
_OPERATOR = {"full_attention": "attention", "sliding_attention": "window"}
_LAYER_TYPE = {op: t for t, op in _OPERATOR.items()}


def _rotary_from_hf(rp: Dict[str, Any]) -> RotaryConfig:
    kind = rp.get("rope_type", "default")
    if kind not in ("default", "yarn"):
        raise NotImplementedError(f"laguna: rope_type {kind!r}")
    yarn = kind == "yarn"
    return RotaryConfig(
        base=float(rp["rope_theta"]),
        partial_factor=float(rp.get("partial_rotary_factor", 1.0)),
        scaling_type="yarn" if yarn else None,
        factor=float(rp.get("factor", 1.0)),
        original_max_positions=rp.get("original_max_position_embeddings"),
        beta_fast=float(rp.get("beta_fast", 32.0)),
        beta_slow=float(rp.get("beta_slow", 1.0)),
        attention_factor=float(rp.get("attention_factor", 1.0)))


def _rotary_to_hf(rc: RotaryConfig) -> Dict[str, Any]:
    d = {"rope_type": rc.scaling_type or "default", "rope_theta": rc.base,
         "partial_rotary_factor": rc.partial_factor}
    if rc.scaling_type == "yarn":
        d.update(factor=rc.factor,
                 original_max_position_embeddings=rc.original_max_positions,
                 beta_fast=rc.beta_fast, beta_slow=rc.beta_slow,
                 attention_factor=rc.attention_factor)
    return d


def _config_from_hf(d: Dict[str, Any], is_critic: bool) -> TransformerConfig:
    if d.get("attention_bias", False):
        raise NotImplementedError("laguna: attention_bias=true")
    if d.get("moe_apply_router_weight_on_input", False):
        raise NotImplementedError(
            "laguna: moe_apply_router_weight_on_input=true (the gates "
            "multiply the experts' OUTPUT here)")
    n = d["num_hidden_layers"]
    types = d.get("layer_types") or ["full_attention"] * n
    ffs = d.get("mlp_layer_types") or ["sparse"] * n
    nq = d["num_attention_heads"]
    heads = d.get("num_attention_heads_per_layer") or [nq] * n
    if (set(types) - set(_OPERATOR) or set(ffs) - {"dense", "sparse"}
            or not len(types) == len(ffs) == len(heads) == n):
        raise NotImplementedError(
            f"laguna: layer_types {sorted(set(types))}, mlp_layer_types "
            f"{sorted(set(ffs))}, {len(types)}/{len(ffs)}/{len(heads)} "
            f"entries for {n} layers")
    share = d.get("expert_share")
    held = d["num_experts"]
    return TransformerConfig(
        n_layers=n,
        n_kv_heads=d.get("num_key_value_heads", nq),
        n_q_heads=nq,
        hidden_dim=d["hidden_size"],
        head_dim=d.get("head_dim") or d["hidden_size"] // nq,
        intermediate_dim=d["intermediate_size"],
        vocab_size=d["vocab_size"],
        n_positions=d.get("max_position_embeddings"),
        layer_norm_epsilon=d.get("rms_norm_eps", 1e-6),
        activation_function="silu",
        use_attention_bias=False,
        use_attn_proj_bias=False,
        use_mlp_bias=False,
        layer_norm_type="rms",
        mlp_type="llama",
        apply_rotary=True,
        tied_embedding=d.get("tie_word_embeddings", False),
        sliding_window=d.get("sliding_window"),
        layer_pattern=tuple(
            (_OPERATOR[t], "dense" if ff == "dense" else "moe")
            for t, ff in zip(types, ffs)),
        layer_q_heads=tuple(heads),
        rotary_by_operator={
            _OPERATOR[t]: _rotary_from_hf(d["rope_parameters"][t])
            for t in sorted(set(types))},
        attn_output_gate=bool(d.get("gating", False)),
        moe=MoEConfig(
            num_experts=share["of"] if share else held,
            top_k=d["num_experts_per_tok"],
            routing_type="none",
            norm_topk_prob=d.get("norm_topk_prob", True),
            score_fn="sigmoid",
            routed_scaling_factor=float(
                d.get("moe_routed_scaling_factor", 1.0)),
            norm_topk_eps=1e-20,
            intermediate_dim=d["moe_intermediate_size"],
            shared_intermediate_dim=d.get(
                "shared_expert_intermediate_size"),
            experts_held=(share["first"], held) if share else None),
        is_critic=is_critic,
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    moe = cfg.moe
    d = {
        "model_type": "laguna",
        "architectures": ["LagunaForCausalLM"],
        "hidden_size": cfg.hidden_dim,
        "intermediate_size": cfg.intermediate_dim,
        "moe_intermediate_size": moe.intermediate_dim
        or cfg.intermediate_dim,
        "num_hidden_layers": cfg.n_layers,
        "layer_types": [_LAYER_TYPE[op] for op, _ in cfg.layer_pattern],
        "mlp_layer_types": ["dense" if ff == "dense" else "sparse"
                            for _, ff in cfg.layer_pattern],
        "num_attention_heads": cfg.n_q_heads,
        "num_attention_heads_per_layer": [
            cfg.q_heads(i) for i in range(cfg.n_layers)],
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "num_experts": moe.n_held,
        "num_experts_per_tok": moe.top_k,
        "norm_topk_prob": moe.norm_topk_prob,
        "moe_routed_scaling_factor": moe.routed_scaling_factor,
        "moe_apply_router_weight_on_input": False,
        "gating": cfg.attn_output_gate,
        "attention_bias": False,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.n_positions or 262144,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "rope_parameters": {
            _LAYER_TYPE[op]: _rotary_to_hf(rc)
            for op, rc in cfg.rotary_by_operator.items()},
        "tie_word_embeddings": cfg.tied_embedding,
        "torch_dtype": "float32",
    }
    if cfg.sliding_window is not None:
        d["sliding_window"] = cfg.sliding_window
    if moe.shared_intermediate_dim is not None:
        d["shared_expert_intermediate_size"] = moe.shared_intermediate_dim
    if moe.experts_held is not None:
        d["expert_share"] = {"of": moe.num_experts,
                             "first": moe.experts_held[0]}
    return d


def _attn_leaves(cfg: TransformerConfig):
    return [(leaf, hf) for leaf, hf in _ATTN
            if leaf != "w_gate" or cfg.attn_output_gate]


def layer_from_hf(state: StateDict, cfg: TransformerConfig,
                  i: int) -> Dict[str, Any]:
    """The tree of layer ``i``: the leaves its (operator,
    feed-forward) has, HF Linear weights (out, in) transposed."""
    pre = _PRE.format(i)
    _, ff = cfg.layer_pattern[i]
    lp: Dict[str, Any] = {
        "ln1": {"scale": state[pre + "input_layernorm.weight"]},
        "ln2": {"scale": state[pre + "post_attention_layernorm.weight"]},
        "attn": {leaf: state[f"{pre}self_attn.{hf}.weight"].T
                 for leaf, hf in _attn_leaves(cfg)}}
    mlp = pre + "mlp."
    if ff == "dense":
        lp["mlp"] = {leaf: state[f"{mlp}{hf}.weight"].T
                     for leaf, hf in _FFN}
        return lp
    lp["mlp"] = {"router": state[mlp + "gate.weight"].T}
    for leaf, hf in _FFN:
        lp["mlp"][leaf] = np.stack(
            [state[f"{mlp}experts.{e}.{hf}.weight"].T
             for e in held_expert_ids(cfg)], axis=0)
    if cfg.moe.shared_intermediate_dim is not None:
        lp["mlp"]["shared"] = {
            leaf: state[f"{mlp}shared_expert.{hf}.weight"].T
            for leaf, hf in _FFN}
    return lp


def layer_to_hf(lp: Dict[str, Any], cfg: TransformerConfig, i: int,
                out: StateDict):
    """Inverse of :func:`layer_from_hf`."""
    pre = _PRE.format(i)
    _, ff = cfg.layer_pattern[i]
    c = np.ascontiguousarray
    out[pre + "input_layernorm.weight"] = c(lp["ln1"]["scale"])
    out[pre + "post_attention_layernorm.weight"] = c(lp["ln2"]["scale"])
    for leaf, hf in _attn_leaves(cfg):
        out[f"{pre}self_attn.{hf}.weight"] = c(lp["attn"][leaf].T)
    mlp = pre + "mlp."
    if ff == "dense":
        for leaf, hf in _FFN:
            out[f"{mlp}{hf}.weight"] = c(lp["mlp"][leaf].T)
        return
    out[mlp + "gate.weight"] = c(lp["mlp"]["router"].T)
    for leaf, hf in _FFN:
        for j, e in enumerate(held_expert_ids(cfg)):
            out[f"{mlp}experts.{e}.{hf}.weight"] = c(lp["mlp"][leaf][j].T)
        if "shared" in lp["mlp"]:
            out[f"{mlp}shared_expert.{hf}.weight"] = c(
                lp["mlp"]["shared"][leaf].T)


_params_from_hf, _params_to_hf = layered_converters(
    layer_from_hf, layer_to_hf)

register_hf_family(HFFamily(
    name="laguna", hf_model_type="laguna",
    config_from_hf=_config_from_hf,
    config_to_hf=_config_to_hf,
    params_from_hf=_params_from_hf,
    params_to_hf=_params_to_hf,
    layer_from_hf=layer_from_hf,
    layer_to_hf=layer_to_hf,
))
