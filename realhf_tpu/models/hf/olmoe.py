"""OLMoE HF conversion (``OLMoE-1B-7B``: llama attention with an
RMSNorm over the whole query and key projections, and a sparse MoE
FFN whose top-k gates are NOT renormalised).

Shares the llama backbone and Mixtral's expert stacking
(``registry.moe_mlp_from_hf``); what differs
is HF's naming (``mlp.gate`` / ``mlp.experts.{e}.{gate,up,down}_proj``
against Mixtral's ``block_sparse_moe`` / ``w1,w3,w2``), the two norm
scales ``self_attn.{q,k}_norm.weight`` of width heads x head_dim, and
``norm_topk_prob``, which the published config sets to false.
``clip_qkv`` is not implemented: the published value is null, and
another one is refused, not ignored.
"""

from typing import Any, Dict

from realhf_tpu.models.config import MoEConfig, TransformerConfig
from realhf_tpu.models.hf.llama import (
    _config_to_hf_llama,
    llama_backbone_from_hf,
    llama_backbone_to_hf,
)
from realhf_tpu.models.hf.registry import (
    HFFamily,
    StateDict,
    moe_mlp_from_hf,
    moe_mlp_to_hf,
    register_hf_family,
    stack_layers,
    unstack_layers,
)

_ATTN = "model.layers.{}.self_attn."
_MOE = "model.layers.{}.mlp."
_EXPERT_WEIGHTS = (("wg", "gate_proj"), ("wu", "up_proj"),
                   ("wd", "down_proj"))


def _config_from_hf(d: Dict[str, Any], is_critic: bool) -> TransformerConfig:
    if d.get("clip_qkv") is not None:
        raise NotImplementedError(
            f"olmoe: clip_qkv={d['clip_qkv']!r} is not implemented "
            "(OLMoE-1B-7B publishes null)")
    nq = d["num_attention_heads"]
    return TransformerConfig(
        n_layers=d["num_hidden_layers"],
        n_kv_heads=d.get("num_key_value_heads", nq),
        n_q_heads=nq,
        hidden_dim=d["hidden_size"],
        head_dim=d["hidden_size"] // nq,
        intermediate_dim=d["intermediate_size"],
        vocab_size=d["vocab_size"],
        n_positions=d.get("max_position_embeddings"),
        layer_norm_epsilon=d.get("rms_norm_eps", 1e-5),
        activation_function="silu",
        use_attention_bias=d.get("attention_bias", False),
        use_attn_proj_bias=False,
        use_mlp_bias=False,
        layer_norm_type="rms",
        mlp_type="moe",
        apply_rotary=True,
        rotary_base=d.get("rope_theta", 10000.0),
        scale_attn_by_inverse_layer_idx=False,
        tied_embedding=d.get("tie_word_embeddings", False),
        qk_norm="full",
        moe=MoEConfig(
            num_experts=d.get("num_experts", 64),
            top_k=d.get("num_experts_per_tok", 8),
            routing_type="aux_loss",
            norm_topk_prob=d.get("norm_topk_prob", False),
            aux_loss_coeff=d.get("router_aux_loss_coef", 1e-2)),
        is_critic=is_critic,
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    d = _config_to_hf_llama(cfg, "llama")
    d.pop("head_dim")  # OLMoE has no such key: hidden_size / heads
    d.update({
        "model_type": "olmoe",
        "architectures": ["OlmoeForCausalLM"],
        "clip_qkv": None,
        "num_experts": cfg.moe.num_experts,
        "num_experts_per_tok": cfg.moe.top_k,
        "norm_topk_prob": cfg.moe.norm_topk_prob,
        "router_aux_loss_coef": cfg.moe.aux_loss_coeff,
    })
    return d


def _params_from_hf(state: StateDict, cfg: TransformerConfig) -> Dict[str, Any]:
    nl = cfg.n_layers
    params = llama_backbone_from_hf(state, cfg)
    attn = params["blocks"]["attn"]
    attn["q_norm"] = stack_layers(state, _ATTN + "q_norm.weight", nl)
    attn["k_norm"] = stack_layers(state, _ATTN + "k_norm.weight", nl)
    params["blocks"]["mlp"] = moe_mlp_from_hf(state, cfg, _MOE,
                                              _EXPERT_WEIGHTS)
    return params


def _params_to_hf(params: Dict[str, Any], cfg: TransformerConfig) -> StateDict:
    out: StateDict = {}
    llama_backbone_to_hf(params, cfg, out)
    attn = params["blocks"]["attn"]
    unstack_layers(attn["q_norm"], _ATTN + "q_norm.weight", out)
    unstack_layers(attn["k_norm"], _ATTN + "k_norm.weight", out)
    moe_mlp_to_hf(params["blocks"]["mlp"], _MOE, _EXPERT_WEIGHTS, out)
    return out


register_hf_family(HFFamily(
    name="olmoe", hf_model_type="olmoe",
    config_from_hf=_config_from_hf,
    config_to_hf=_config_to_hf,
    params_from_hf=_params_from_hf,
    params_to_hf=_params_to_hf,
))
