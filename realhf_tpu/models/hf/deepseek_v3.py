"""DeepSeek-V3 HF conversion (``model_type: deepseek_v3``;
Moonlight-16B-A3B is one): latent attention in every layer, a dense
lead of ``first_k_dense_replace`` layers before sparse ones that add
shared experts to the routed ones.

The equations are those of ``transformers``' ``modeling_deepseek_v3.py``
(4.57.6, installed here; ``tests/model/test_deepseek_v3.py`` holds the
program to that module at toy widths): ``LatentConfig`` has the
attention, ``ops/moe.py:router_probs`` the router (sigmoid scores in
float32, the k chosen by score + ``e_score_correction_bias``, the gates
the scores themselves over (their sum + 1e-20), times
``routed_scaling_factor``). ``kv_a_layernorm`` is built without an
epsilon there, so it norms at 1e-6 whatever ``rms_norm_eps`` says; the
``n_shared_experts`` shared experts are ONE SwiGLU of
``n_shared_experts x moe_intermediate_size``. The family DECLARES its
layers (``TransformerConfig.layer_pattern``, operator "latent"), so
converters work a LAYER at a time, which the streamed load and save
call.

Refused by name, not guessed: query compression (``q_lora_rank`` other
than null), YaRN (``rope_scaling``, whose ``mscale`` also moves the
scores' scale), group-limited selection (``n_group`` or ``topk_group``
over 1), a ``topk_method`` other than ``noaux_tc``, a ``scoring_func``
other than sigmoid, multi-token prediction
(``num_nextn_predict_layers``), ``moe_layer_freq`` other than 1,
``rope_interleave: false`` and an ``attention_bias``.

**An expert-parallel rank's share** is said as in ``lfm2_moe.py``:
``n_routed_experts`` counts the experts whose weights are in the files,
``expert_share: {"of": 64, "first": 0}`` the published count (the width
of the router and of its bias) and the global id of the first one held;
the files name experts by their GLOBAL id. The shared experts are in
every rank's files.
"""

from typing import Any, Dict

import numpy as np

from realhf_tpu.models.config import (
    LatentConfig,
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
#: matrix leaf of a latent layer's attention -> HF's name
_ATTN = (("wq", "q_proj"), ("w_kv_a", "kv_a_proj_with_mqa"),
         ("w_kv_b", "kv_b_proj"), ("wo", "o_proj"))
#: published key -> the one value of it this family runs
_ONLY = {"q_lora_rank": None, "rope_scaling": None, "n_group": 1,
         "topk_group": 1, "topk_method": "noaux_tc",
         "scoring_func": "sigmoid", "num_nextn_predict_layers": 0,
         "moe_layer_freq": 1, "rope_interleave": True,
         "attention_bias": False, "hidden_act": "silu"}


def _config_from_hf(d: Dict[str, Any], is_critic: bool) -> TransformerConfig:
    for key, only in _ONLY.items():
        if d.get(key, only) != only:
            raise NotImplementedError(
                f"deepseek_v3: {key}={d[key]!r} (only {only!r} runs here)")
    n = d["num_hidden_layers"]
    nq = d["num_attention_heads"]
    if d.get("num_key_value_heads", nq) != nq:
        raise NotImplementedError(
            "deepseek_v3: latent attention has a key a query head")
    lead = min(d.get("first_k_dense_replace", 0), n)
    share = d.get("expert_share")
    held = d["n_routed_experts"]
    rope = d["qk_rope_head_dim"]
    head = d["qk_nope_head_dim"] + rope
    return TransformerConfig(
        n_layers=n,
        n_kv_heads=nq,
        n_q_heads=nq,
        hidden_dim=d["hidden_size"],
        head_dim=head,
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
        layer_pattern=tuple(("latent", "dense" if i < lead else "moe")
                            for i in range(n)),
        latent=LatentConfig(kv_rank=d["kv_lora_rank"], rope_dim=rope,
                            v_dim=d["v_head_dim"]),
        rotary_by_operator={"latent": RotaryConfig(
            base=float(d.get("rope_theta", 10000.0)),
            partial_factor=rope / head, interleaved=True)},
        moe=MoEConfig(
            num_experts=share["of"] if share else held,
            top_k=d["num_experts_per_tok"],
            routing_type="none",
            norm_topk_prob=d.get("norm_topk_prob", True),
            score_fn="sigmoid",
            use_expert_bias=True,
            routed_scaling_factor=float(
                d.get("routed_scaling_factor", 1.0)),
            norm_topk_eps=1e-20,
            intermediate_dim=d["moe_intermediate_size"],
            shared_intermediate_dim=(
                d["moe_intermediate_size"] * d["n_shared_experts"]
                if d.get("n_shared_experts") else None),
            experts_held=(share["first"], held) if share else None),
        is_critic=is_critic,
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    moe, lat = cfg.moe, cfg.latent
    fe = moe.intermediate_dim or cfg.intermediate_dim
    d = {
        "model_type": "deepseek_v3",
        "architectures": ["DeepseekV3ForCausalLM"],
        "hidden_size": cfg.hidden_dim,
        "intermediate_size": cfg.intermediate_dim,
        "moe_intermediate_size": fe,
        "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.n_layers - cfg.n_moe_layers,
        "moe_layer_freq": 1,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "q_lora_rank": None,
        "kv_lora_rank": lat.kv_rank,
        "qk_nope_head_dim": cfg.head_dim - lat.rope_dim,
        "qk_rope_head_dim": lat.rope_dim,
        "v_head_dim": lat.v_dim,
        "n_routed_experts": moe.n_held,
        "n_shared_experts": (moe.shared_intermediate_dim or 0) // fe,
        "num_experts_per_tok": moe.top_k,
        "norm_topk_prob": moe.norm_topk_prob,
        "routed_scaling_factor": moe.routed_scaling_factor,
        "scoring_func": "sigmoid",
        "topk_method": "noaux_tc",
        "n_group": 1,
        "topk_group": 1,
        "num_nextn_predict_layers": 0,
        "hidden_act": "silu",
        "attention_bias": False,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.n_positions or 8192,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "rope_theta": cfg.rotary_of("latent").base,
        "rope_scaling": None,
        "rope_interleave": True,
        "tie_word_embeddings": cfg.tied_embedding,
        "torch_dtype": "float32",
    }
    if moe.experts_held is not None:
        d["expert_share"] = {"of": moe.num_experts,
                             "first": moe.experts_held[0]}
    return d


def layer_from_hf(state: StateDict, cfg: TransformerConfig,
                  i: int) -> Dict[str, Any]:
    """The tree of layer ``i``: the leaves its feed-forward has, HF
    Linear weights (out, in) transposed."""
    pre = _PRE.format(i)
    _, ff = cfg.layer_pattern[i]
    lp: Dict[str, Any] = {
        "ln1": {"scale": state[pre + "input_layernorm.weight"]},
        "ln2": {"scale": state[pre + "post_attention_layernorm.weight"]},
        "attn": {leaf: state[f"{pre}self_attn.{hf}.weight"].T
                 for leaf, hf in _ATTN}}
    lp["attn"]["kv_a_norm"] = state[pre + "self_attn.kv_a_layernorm.weight"]
    mlp = pre + "mlp."
    if ff == "dense":
        lp["mlp"] = {leaf: state[f"{mlp}{hf}.weight"].T
                     for leaf, hf in _FFN}
        return lp
    lp["mlp"] = {"router": state[mlp + "gate.weight"].T,
                 "expert_bias": state[mlp + "gate.e_score_correction_bias"]}
    for leaf, hf in _FFN:
        lp["mlp"][leaf] = np.stack(
            [state[f"{mlp}experts.{e}.{hf}.weight"].T
             for e in held_expert_ids(cfg)], axis=0)
    if cfg.moe.shared_intermediate_dim is not None:
        lp["mlp"]["shared"] = {
            leaf: state[f"{mlp}shared_experts.{hf}.weight"].T
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
    for leaf, hf in _ATTN:
        out[f"{pre}self_attn.{hf}.weight"] = c(lp["attn"][leaf].T)
    out[pre + "self_attn.kv_a_layernorm.weight"] = c(lp["attn"]["kv_a_norm"])
    mlp = pre + "mlp."
    if ff == "dense":
        for leaf, hf in _FFN:
            out[f"{mlp}{hf}.weight"] = c(lp["mlp"][leaf].T)
        return
    out[mlp + "gate.weight"] = c(lp["mlp"]["router"].T)
    out[mlp + "gate.e_score_correction_bias"] = c(lp["mlp"]["expert_bias"])
    for leaf, hf in _FFN:
        for j, e in enumerate(held_expert_ids(cfg)):
            out[f"{mlp}experts.{e}.{hf}.weight"] = c(lp["mlp"][leaf][j].T)
        if "shared" in lp["mlp"]:
            out[f"{mlp}shared_experts.{hf}.weight"] = c(
                lp["mlp"]["shared"][leaf].T)


_params_from_hf, _params_to_hf = layered_converters(
    layer_from_hf, layer_to_hf)

register_hf_family(HFFamily(
    name="deepseek_v3", hf_model_type="deepseek_v3",
    config_from_hf=_config_from_hf,
    config_to_hf=_config_to_hf,
    params_from_hf=_params_from_hf,
    params_to_hf=_params_to_hf,
    layer_from_hf=layer_from_hf,
    layer_to_hf=layer_to_hf,
))
