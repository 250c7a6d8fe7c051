"""Nemotron-H HF conversion (``model_type: nemotron_h``;
NVIDIA-Nemotron-3-Nano-30B-A3B-BF16 is one): layers that are ONE part
each, ``x <- x + part(RMSNorm(x))``, the part said a layer by a letter of
``hybrid_override_pattern``:

- ``M``: the Mamba-2 state-space mixer (operator "ssm",
  ``models/config.py:SsmConfig`` has the equations): ``d_inner =
  mamba_num_heads x mamba_head_dim`` (NOT ``expand x hidden_size``), B
  and C shared by ``n_groups`` groups of heads, a convolution of
  ``conv_kernel`` taps WITH a bias, ``Delta = softplus(dt + dt_bias)``
  without a clamp (``time_step_limit`` absent), the gate BEFORE the
  grouped RMSNorm;
- ``E``: a sparse feed-forward alone: DeepSeek-V3's router as
  ``ops/moe.py:router_probs`` computes it (sigmoid scores in float32,
  the ``num_experts_per_tok`` chosen by score +
  ``e_score_correction_bias``, ONE group, the gates the scores over
  (their sum + 1e-20) under ``norm_topk_prob``, times
  ``routed_scaling_factor``), experts of TWO matrices ``W_down
  relu(W_up u)^2`` (``mlp_hidden_act: relu2``, no gate), one shared
  expert of the same form at ``moe_shared_expert_intermediate_size``;
- ``*``: grouped-query attention alone, no bias and NO rotary embedding
  or other positional term (``rotary_by_operator["attention"] = None``;
  the config's ``rope_theta`` and ``partial_rotary_factor`` are read by
  nothing).

They become ``TransformerConfig.layer_pattern`` entries with one part
``ABSENT``. Converters work a LAYER at a time, which the streamed load
and save call.

Refused by name, not guessed: ``-`` in the pattern (a dense
feed-forward layer: no published model of this family here has one),
biases other than the convolution's, more than one expert group, an
activation other than relu2 / silu, more than one shared expert, a
``time_step_limit``, ``norm_eps`` other than ``layer_norm_epsilon``.

**An expert-parallel rank's share** is said as in ``deepseek_v3.py``:
``n_routed_experts`` counts the experts whose weights are in the files,
``expert_share: {"of": 128, "first": 0}`` the published count (the width
of the router and of its bias) and the global id of the first one held;
the files name experts by their GLOBAL id. The shared expert is in every
rank's files.

``transformers`` 4.57.6 has no ``nemotron_h`` and there is no network
here: the tensor names (``backbone.embeddings``, ``backbone.layers.N.
norm``, ``mixer.{in_proj, conv1d, A_log, D, dt_bias, norm, out_proj}``,
``mixer.{q,k,v,o}_proj``, ``mixer.gate.{weight,
e_score_correction_bias}``, ``mixer.experts.N.{up,down}_proj``,
``mixer.shared_experts.{up,down}_proj``, ``backbone.norm_f``,
``lm_head``) are the published modelling code's AS REMEMBERED and are
not confirmed. What is claimed is the architecture's shapes and named
mechanisms (held to ``transformers``' ``Zamba2MambaMixer`` and
``deepseek_v3`` router in ``tests/model/test_nemotron_h.py``), not that
the published checkpoint loads.
"""

from typing import Any, Dict

import numpy as np

from realhf_tpu.models.config import (
    ABSENT,
    MoEConfig,
    SsmConfig,
    TransformerConfig,
)
from realhf_tpu.models.hf.registry import (
    HFFamily,
    StateDict,
    held_expert_ids,
    layered_converters,
    register_hf_family,
)

_PRE = "backbone.layers.{}."
#: a pattern letter -> the layer's (operator, feed-forward)
_KINDS = {"M": ("ssm", ABSENT), "E": (ABSENT, "moe"),
          "*": ("attention", ABSENT)}
_LETTERS = {kind: letter for letter, kind in _KINDS.items()}
#: leaf of an ungated feed-forward -> HF's name
_FFN = (("wu", "up_proj"), ("wd", "down_proj"))
_ATTN = (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
         ("wo", "o_proj"))
#: leaf of an ssm layer that is stored as it is -> HF's name
_SSM_AS_IS = (("conv_bias", "conv1d.bias"), ("a_log", "A_log"), ("d", "D"),
              ("dt_bias", "dt_bias"), ("norm", "norm.weight"))
#: published key -> the one value of it this family runs
_ONLY = {"attention_bias": False, "mlp_bias": False, "use_bias": False,
         "mamba_proj_bias": False, "use_conv_bias": True,
         "mamba_hidden_act": "silu", "mlp_hidden_act": "relu2",
         "n_group": 1, "topk_group": 1, "time_step_limit": None,
         "sliding_window": None}


def layer_kinds(d: Dict[str, Any]):
    """(operator, feed-forward) of every layer from the pattern's
    letters."""
    pattern = d["hybrid_override_pattern"]
    if len(pattern) != d["num_hidden_layers"] or set(pattern) - set(_KINDS):
        raise NotImplementedError(
            f"nemotron_h: hybrid_override_pattern {pattern!r} does not "
            f"say each of the {d['num_hidden_layers']} layers by one of "
            f"{sorted(_KINDS)} ('-', a dense feed-forward layer, does "
            "not run here)")
    return tuple(_KINDS[letter] for letter in pattern)


def _config_from_hf(d: Dict[str, Any], is_critic: bool) -> TransformerConfig:
    for key, only in _ONLY.items():
        if d.get(key, only) != only:
            raise NotImplementedError(
                f"nemotron_h: {key}={d[key]!r} (only {only!r} runs here)")
    eps = d.get("layer_norm_epsilon", 1e-5)
    if d.get("norm_eps", eps) != eps or d.get("n_shared_experts", 1) > 1:
        raise NotImplementedError(
            "nemotron_h: one epsilon for every norm (norm_eps = "
            "layer_norm_epsilon) and at most one shared expert")
    kinds = layer_kinds(d)
    share = d.get("expert_share")
    held = d["n_routed_experts"]
    has = {part for kind in kinds for part in kind}
    return TransformerConfig(
        n_layers=d["num_hidden_layers"],
        n_kv_heads=d["num_key_value_heads"],
        n_q_heads=d["num_attention_heads"],
        hidden_dim=d["hidden_size"],
        head_dim=d["head_dim"],
        intermediate_dim=d["intermediate_size"],
        vocab_size=d["vocab_size"],
        n_positions=d.get("max_position_embeddings"),
        layer_norm_epsilon=eps,
        activation_function="relu2",
        use_attention_bias=False,
        use_attn_proj_bias=False,
        use_mlp_bias=False,
        layer_norm_type="rms",
        mlp_type=None,  # two matrices, no gate
        apply_rotary=True,  # no absolute positions; NO rotary either:
        rotary_by_operator={"attention": None},
        tied_embedding=d.get("tie_word_embeddings", False),
        layer_pattern=kinds,
        ssm=SsmConfig(
            n_heads=d["mamba_num_heads"], head_dim=d["mamba_head_dim"],
            state=d["ssm_state_size"], n_groups=d["n_groups"],
            conv_kernel=d["conv_kernel"]) if "ssm" in has else None,
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
                d["moe_shared_expert_intermediate_size"]
                if d.get("n_shared_experts", 1) else None),
            experts_held=(share["first"], held) if share else None)
        if "moe" in has else None,
        is_critic=is_critic,
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    d = {
        "model_type": "nemotron_h",
        "architectures": ["NemotronHForCausalLM"],
        "hidden_size": cfg.hidden_dim,
        "intermediate_size": cfg.intermediate_dim,
        "num_hidden_layers": cfg.n_layers,
        "hybrid_override_pattern": "".join(
            _LETTERS[kind] for kind in cfg.layer_pattern),
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "attention_bias": False,
        "mlp_bias": False,
        "use_bias": False,
        "mamba_proj_bias": False,
        "use_conv_bias": True,
        "mamba_hidden_act": "silu",
        "mlp_hidden_act": "relu2",
        "n_group": 1,
        "topk_group": 1,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.n_positions or 262144,
        "layer_norm_epsilon": cfg.layer_norm_epsilon,
        "norm_eps": cfg.layer_norm_epsilon,
        "tie_word_embeddings": cfg.tied_embedding,
        "torch_dtype": "float32",
    }
    if cfg.ssm is not None:
        sm = cfg.ssm
        d.update(mamba_num_heads=sm.n_heads, mamba_head_dim=sm.head_dim,
                 ssm_state_size=sm.state, n_groups=sm.n_groups,
                 conv_kernel=sm.conv_kernel)
    if cfg.moe is not None:
        moe = cfg.moe
        d.update(
            n_routed_experts=moe.n_held,
            num_experts_per_tok=moe.top_k,
            norm_topk_prob=moe.norm_topk_prob,
            routed_scaling_factor=moe.routed_scaling_factor,
            moe_intermediate_size=moe.intermediate_dim
            or cfg.intermediate_dim,
            n_shared_experts=int(moe.shared_intermediate_dim is not None),
            moe_shared_expert_intermediate_size=(
                moe.shared_intermediate_dim or 0))
        if moe.experts_held is not None:
            d["expert_share"] = {"of": moe.num_experts,
                                 "first": moe.experts_held[0]}
    return d


def layer_from_hf(state: StateDict, cfg: TransformerConfig,
                  i: int) -> Dict[str, Any]:
    """The tree of layer ``i``: ONE norm (``ln1`` a mixer's, ``ln2`` a
    feed-forward's) and the leaves of its one part, HF Linear weights
    (out, in) transposed."""
    pre = _PRE.format(i)
    op, ff = cfg.layer_pattern[i]
    m = pre + "mixer."
    norm = {"scale": state[pre + "norm.weight"]}
    if op == "ssm":
        ssm = {leaf: state[m + hf] for leaf, hf in _SSM_AS_IS}
        ssm.update(
            w_in=state[m + "in_proj.weight"].T,
            # Conv1d's [channels, 1, taps] -> [taps, channels]
            conv=state[m + "conv1d.weight"][:, 0, :].T,
            w_out=state[m + "out_proj.weight"].T)
        return {"ln1": norm, "ssm": ssm}
    if op == "attention":
        return {"ln1": norm,
                "attn": {leaf: state[f"{m}{hf}.weight"].T
                         for leaf, hf in _ATTN}}
    assert ff == "moe", (op, ff)
    mlp = {"router": state[m + "gate.weight"].T,
           "expert_bias": state[m + "gate.e_score_correction_bias"]}
    for leaf, hf in _FFN:
        mlp[leaf] = np.stack(
            [state[f"{m}experts.{e}.{hf}.weight"].T
             for e in held_expert_ids(cfg)], axis=0)
    if cfg.moe.shared_intermediate_dim is not None:
        mlp["shared"] = {leaf: state[f"{m}shared_experts.{hf}.weight"].T
                         for leaf, hf in _FFN}
    return {"ln2": norm, "mlp": mlp}


def layer_to_hf(lp: Dict[str, Any], cfg: TransformerConfig, i: int,
                out: StateDict):
    """Inverse of :func:`layer_from_hf`."""
    pre = _PRE.format(i)
    op, _ = cfg.layer_pattern[i]
    m = pre + "mixer."
    c = np.ascontiguousarray
    out[pre + "norm.weight"] = c(lp["ln2" if op == ABSENT else "ln1"]["scale"])
    if op == "ssm":
        ssm = lp["ssm"]
        for leaf, hf in _SSM_AS_IS:
            out[m + hf] = c(ssm[leaf])
        out[m + "in_proj.weight"] = c(ssm["w_in"].T)
        out[m + "conv1d.weight"] = c(np.asarray(ssm["conv"]).T[:, None, :])
        out[m + "out_proj.weight"] = c(ssm["w_out"].T)
        return
    if op == "attention":
        for leaf, hf in _ATTN:
            out[f"{m}{hf}.weight"] = c(lp["attn"][leaf].T)
        return
    mlp = lp["mlp"]
    out[m + "gate.weight"] = c(mlp["router"].T)
    out[m + "gate.e_score_correction_bias"] = c(mlp["expert_bias"])
    for leaf, hf in _FFN:
        for j, e in enumerate(held_expert_ids(cfg)):
            out[f"{m}experts.{e}.{hf}.weight"] = c(mlp[leaf][j].T)
        if "shared" in mlp:
            out[f"{m}shared_experts.{hf}.weight"] = c(mlp["shared"][leaf].T)


_params_from_hf, _params_to_hf = layered_converters(
    layer_from_hf, layer_to_hf, final_norm="backbone.norm_f.weight",
    embed="backbone.embeddings.weight")

register_hf_family(HFFamily(
    name="nemotron_h", hf_model_type="nemotron_h",
    config_from_hf=_config_from_hf,
    config_to_hf=_config_to_hf,
    params_from_hf=_params_from_hf,
    params_to_hf=_params_to_hf,
    layer_from_hf=layer_from_hf,
    layer_to_hf=layer_to_hf,
))
