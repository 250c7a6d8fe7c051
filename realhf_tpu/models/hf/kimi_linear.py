"""Kimi-Linear HF conversion (``model_type: kimi_linear``;
Kimi-Linear-48B-A3B-Instruct is one): Kimi Delta Attention layers (a
gated delta rule with one decay a key channel: operator "delta",
``models/config.py:DeltaConfig`` has the equations) among latent
attention layers WITHOUT a rotary embedding (``mla_use_nope``; operator
"latent", ``LatentConfig``), a dense lead of ``first_k_dense_replace``
layers before sparse ones with a shared expert beside the routed ones.

The family DECLARES its layers: ``linear_attn_config.kda_layers`` and
``full_attn_layers`` list them, read as 1-BASED (27 is listed and 0 is
not in the published 27-layer config), and become
``TransformerConfig.layer_pattern``. The router is DeepSeek-V3's as
``ops/moe.py:router_probs`` computes it: sigmoid scores in float32
(``moe_router_activation_func``), the ``num_experts_per_token`` chosen
by score + ``e_score_correction_bias`` (``use_grouped_topk`` with ONE
group is plain top-k), the gates the scores themselves over (their sum
+ 1e-20) under ``moe_renormalize``, times ``routed_scaling_factor``;
``num_shared_experts`` shared experts are ONE SwiGLU of that many
times ``moe_intermediate_size``. Converters work a LAYER at a time,
which the streamed load and save call.

Refused by name, not guessed: ``mla_use_nope: false`` (which rotary
convention the latent layers would take is not stated), query
compression (``q_lora_rank`` other than null), ``rope_scaling``, more
than one expert group, a router activation other than sigmoid,
multi-token prediction, ``moe_layer_freq`` other than 1, a layer in
neither list or in both.

**An expert-parallel rank's share** is said as in ``deepseek_v3.py``:
``num_experts`` counts the experts whose weights are in the files,
``expert_share: {"of": 256, "first": 0}`` the published count (the
width of the router and of its bias) and the global id of the first
one held; the files name experts by their GLOBAL id. The shared expert
is in every rank's files.

``transformers`` 4.57.6 has no ``kimi_linear`` and there is no network
here: the tensor names (``self_attn.{q,k,v}_proj``,
``self_attn.{q,k,v}_conv1d.weight`` [width, 1, taps], ``self_attn.A_log``
[1, 1, heads, 1], ``f_a_proj`` / ``f_b_proj``, ``dt_bias``, ``b_proj``,
``g_a_proj`` / ``g_b_proj``, ``o_norm``, ``o_proj``;
``block_sparse_moe.gate.{weight, e_score_correction_bias}``,
``experts.N.{w1, w2, w3}`` = gate, down, up,
``block_sparse_moe.shared_experts.*``; a dense layer's ``mlp.*``) are
the published modelling code's AS REMEMBERED and are not confirmed; nor
are: convolutions without a bias, the l2 norm's 1e-6, ``o_norm`` at
``rms_norm_eps``, ``kv_a_layernorm`` at 1e-6. What is claimed is the
architecture's shapes and named mechanisms, not that the published
checkpoint loads.
"""

from typing import Any, Dict

import numpy as np

from realhf_tpu.models.config import (
    DeltaConfig,
    LatentConfig,
    MoEConfig,
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
#: leaf of a dense or shared feed-forward -> HF's name
_FFN = (("wg", "gate_proj"), ("wu", "up_proj"), ("wd", "down_proj"))
#: leaf of the routed experts' stacks -> HF's name of ONE expert's
_EXPERT = (("wg", "w1"), ("wu", "w3"), ("wd", "w2"))
#: matrix leaf of a latent layer's attention -> HF's name
_LATENT = (("wq", "q_proj"), ("w_kv_a", "kv_a_proj_with_mqa"),
           ("w_kv_b", "kv_b_proj"), ("wo", "o_proj"))
#: matrix leaf of a delta layer -> HF's name (Linear: (out, in))
_DELTA = (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
          ("w_fa", "f_a_proj"), ("w_fb", "f_b_proj"), ("w_b", "b_proj"),
          ("w_ga", "g_a_proj"), ("w_gb", "g_b_proj"), ("wo", "o_proj"))
#: the taps' leaf -> HF's Conv1d
_CONVS = (("conv_q", "q_conv1d"), ("conv_k", "k_conv1d"),
          ("conv_v", "v_conv1d"))
#: published key -> the one value of it this family runs
_ONLY = {"mla_use_nope": True, "q_lora_rank": None, "rope_scaling": None,
         "num_expert_group": 1, "topk_group": 1,
         "moe_router_activation_func": "sigmoid",
         "num_nextn_predict_layers": 0, "moe_layer_freq": 1,
         "hidden_act": "silu"}


def _operators(d: Dict[str, Any]):
    """The operator of every layer from the two 1-based lists."""
    n = d["num_hidden_layers"]
    lin = d["linear_attn_config"]
    delta, latent = set(lin["kda_layers"]), set(lin["full_attn_layers"])
    if delta & latent or delta | latent != set(range(1, n + 1)):
        raise NotImplementedError(
            f"kimi_linear: kda_layers {sorted(delta)} and "
            f"full_attn_layers {sorted(latent)} do not name each of "
            f"the layers 1..{n} once")
    return ["delta" if i + 1 in delta else "latent" for i in range(n)]


def _config_from_hf(d: Dict[str, Any], is_critic: bool) -> TransformerConfig:
    for key, only in _ONLY.items():
        if d.get(key, only) != only:
            raise NotImplementedError(
                f"kimi_linear: {key}={d[key]!r} (only {only!r} runs here)")
    n = d["num_hidden_layers"]
    nq = d["num_attention_heads"]
    if d.get("num_key_value_heads", nq) != nq:
        raise NotImplementedError(
            "kimi_linear: latent attention has a key a query head")
    ops = _operators(d)
    lin = d["linear_attn_config"]
    lead = min(d.get("first_k_dense_replace", 0), n)
    share = d.get("expert_share")
    held = d["num_experts"]
    rope = d["qk_rope_head_dim"]
    has_latent, has_delta = "latent" in ops, "delta" in ops
    return TransformerConfig(
        n_layers=n,
        n_kv_heads=nq,
        n_q_heads=nq,
        hidden_dim=d["hidden_size"],
        head_dim=d["qk_nope_head_dim"] + rope,
        intermediate_dim=d["intermediate_size"],
        vocab_size=d["vocab_size"],
        n_positions=d.get("model_max_length"),
        layer_norm_epsilon=d.get("rms_norm_eps", 1e-5),
        activation_function="silu",
        use_attention_bias=False,
        use_attn_proj_bias=False,
        use_mlp_bias=False,
        layer_norm_type="rms",
        mlp_type="llama",
        apply_rotary=True,  # no absolute positions; NO rotary either:
        rotary_by_operator={"latent": None},
        tied_embedding=d.get("tie_word_embeddings", False),
        layer_pattern=tuple((op, "dense" if i < lead else "moe")
                            for i, op in enumerate(ops)),
        latent=LatentConfig(kv_rank=d["kv_lora_rank"], rope_dim=rope,
                            v_dim=d["v_head_dim"]) if has_latent else None,
        delta=DeltaConfig(
            n_heads=lin["num_heads"], head_dim=lin["head_dim"],
            conv_kernel=lin["short_conv_kernel_size"])
        if has_delta else None,
        moe=MoEConfig(
            num_experts=share["of"] if share else held,
            top_k=d["num_experts_per_token"],
            routing_type="none",
            norm_topk_prob=d.get("moe_renormalize", True),
            score_fn="sigmoid",
            use_expert_bias=True,
            routed_scaling_factor=float(
                d.get("routed_scaling_factor", 1.0)),
            norm_topk_eps=1e-20,
            intermediate_dim=d["moe_intermediate_size"],
            shared_intermediate_dim=(
                d["moe_intermediate_size"] * d["num_shared_experts"]
                if d.get("num_shared_experts") else None),
            experts_held=(share["first"], held) if share else None),
        is_critic=is_critic,
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    moe, lat, dl = cfg.moe, cfg.latent, cfg.delta
    fe = moe.intermediate_dim or cfg.intermediate_dim
    d = {
        "model_type": "kimi_linear",
        "architectures": ["KimiLinearForCausalLM"],
        "hidden_size": cfg.hidden_dim,
        "intermediate_size": cfg.intermediate_dim,
        "moe_intermediate_size": fe,
        "num_hidden_layers": cfg.n_layers,
        "first_k_dense_replace": cfg.n_layers - cfg.n_moe_layers,
        "moe_layer_freq": 1,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.hidden_dim // cfg.n_q_heads,
        "linear_attn_config": {
            "kda_layers": [i + 1 for i in cfg.layers_of("delta")],
            "full_attn_layers": [i + 1 for i in cfg.layers_of("latent")],
            "num_heads": dl.n_heads, "head_dim": dl.head_dim,
            "short_conv_kernel_size": dl.conv_kernel},
        "mla_use_nope": True,
        "q_lora_rank": None,
        "kv_lora_rank": lat.kv_rank,
        "qk_nope_head_dim": cfg.head_dim - lat.rope_dim,
        "qk_rope_head_dim": lat.rope_dim,
        "v_head_dim": lat.v_dim,
        "num_experts": moe.n_held,
        "num_experts_per_token": moe.top_k,
        "num_shared_experts": (moe.shared_intermediate_dim or 0) // fe,
        "moe_renormalize": moe.norm_topk_prob,
        "moe_router_activation_func": "sigmoid",
        "routed_scaling_factor": moe.routed_scaling_factor,
        "use_grouped_topk": True,
        "num_expert_group": 1,
        "topk_group": 1,
        "num_nextn_predict_layers": 0,
        "hidden_act": "silu",
        "vocab_size": cfg.vocab_size,
        "model_max_length": cfg.n_positions or 1048576,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "rope_scaling": None,
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
    a = pre + "self_attn."
    lp: Dict[str, Any] = {
        "ln1": {"scale": state[pre + "input_layernorm.weight"]},
        "ln2": {"scale": state[pre + "post_attention_layernorm.weight"]}}
    if op == "delta":
        lp["delta"] = {leaf: state[f"{a}{hf}.weight"].T
                       for leaf, hf in _DELTA}
        for leaf, hf in _CONVS:
            # Conv1d's [channels, 1, taps] -> [taps, channels]
            lp["delta"][leaf] = state[f"{a}{hf}.weight"][:, 0, :].T
        lp["delta"].update(
            a_log=state[a + "A_log"].reshape(-1),
            dt_bias=state[a + "dt_bias"],
            o_norm=state[a + "o_norm.weight"])
    else:
        lp["attn"] = {leaf: state[f"{a}{hf}.weight"].T
                      for leaf, hf in _LATENT}
        lp["attn"]["kv_a_norm"] = state[a + "kv_a_layernorm.weight"]
    if ff == "dense":
        lp["mlp"] = {leaf: state[f"{pre}mlp.{hf}.weight"].T
                     for leaf, hf in _FFN}
        return lp
    moe = pre + "block_sparse_moe."
    lp["mlp"] = {"router": state[moe + "gate.weight"].T,
                 "expert_bias": state[moe + "gate.e_score_correction_bias"]}
    for leaf, hf in _EXPERT:
        lp["mlp"][leaf] = np.stack(
            [state[f"{moe}experts.{e}.{hf}.weight"].T
             for e in held_expert_ids(cfg)], axis=0)
    if cfg.moe.shared_intermediate_dim is not None:
        lp["mlp"]["shared"] = {
            leaf: state[f"{moe}shared_experts.{hf}.weight"].T
            for leaf, hf in _FFN}
    return lp


def layer_to_hf(lp: Dict[str, Any], cfg: TransformerConfig, i: int,
                out: StateDict):
    """Inverse of :func:`layer_from_hf`."""
    pre = _PRE.format(i)
    op, ff = cfg.layer_pattern[i]
    a = pre + "self_attn."
    c = np.ascontiguousarray
    out[pre + "input_layernorm.weight"] = c(lp["ln1"]["scale"])
    out[pre + "post_attention_layernorm.weight"] = c(lp["ln2"]["scale"])
    if op == "delta":
        dl = lp["delta"]
        for leaf, hf in _DELTA:
            out[f"{a}{hf}.weight"] = c(dl[leaf].T)
        for leaf, hf in _CONVS:
            out[f"{a}{hf}.weight"] = c(dl[leaf].T[:, None, :])
        out[a + "A_log"] = c(np.asarray(dl["a_log"]).reshape(1, 1, -1, 1))
        out[a + "dt_bias"] = c(dl["dt_bias"])
        out[a + "o_norm.weight"] = c(dl["o_norm"])
    else:
        for leaf, hf in _LATENT:
            out[f"{a}{hf}.weight"] = c(lp["attn"][leaf].T)
        out[a + "kv_a_layernorm.weight"] = c(lp["attn"]["kv_a_norm"])
    if ff == "dense":
        for leaf, hf in _FFN:
            out[f"{pre}mlp.{hf}.weight"] = c(lp["mlp"][leaf].T)
        return
    moe = pre + "block_sparse_moe."
    out[moe + "gate.weight"] = c(lp["mlp"]["router"].T)
    out[moe + "gate.e_score_correction_bias"] = c(lp["mlp"]["expert_bias"])
    for leaf, hf in _EXPERT:
        for j, e in enumerate(held_expert_ids(cfg)):
            out[f"{moe}experts.{e}.{hf}.weight"] = c(lp["mlp"][leaf][j].T)
    if "shared" in lp["mlp"]:
        for leaf, hf in _FFN:
            out[f"{moe}shared_experts.{hf}.weight"] = c(
                lp["mlp"]["shared"][leaf].T)


_params_from_hf, _params_to_hf = layered_converters(
    layer_from_hf, layer_to_hf)

register_hf_family(HFFamily(
    name="kimi_linear", hf_model_type="kimi_linear",
    config_from_hf=_config_from_hf,
    config_to_hf=_config_to_hf,
    params_from_hf=_params_from_hf,
    params_to_hf=_params_to_hf,
    layer_from_hf=layer_from_hf,
    layer_to_hf=layer_to_hf,
))
