"""Keye-VL-2.0 HF conversion (``Keye-VL-2.0-30B-A3B``, ``model_type:
KeyeVL2``): the LANGUAGE MODEL, which is ``qwen3_moe``'s (grouped-query
attention with an RMSNorm a head on q and k, a softmax router whose k
gates are renormalised, no shared expert) with one more block in every
attention module: the learned indexer of ``sa_config`` (``indexer_num_
heads`` heads of ``indexer_head_dim`` over ONE index key, the ``topk``
best-scored earlier tokens a query attends over).

The family DECLARES its layers, as the other patterned families do:
every layer is operator "sparse" (``TransformerConfig.layer_pattern``),
its feed-forward "moe" unless ``mlp_only_layers`` / ``decoder_sparse_
step`` say "dense" as ``qwen3_moe`` reads them. Converters work a
LAYER at a time (``layer_from_hf`` / ``layer_to_hf``), which the
streamed load and save call.

**An expert-parallel rank's share** is said as in ``lfm2_moe.py``:
``num_experts`` counts the experts whose weights are in the files,
``expert_share: {"of": 128, "first": 0}`` the published count (the
router's width) and the global id of the first one held; the files name
experts by their GLOBAL id.

``transformers`` 4.57.6 carries ``qwen3_moe`` and NOT ``KeyeVL2``, and
there is no network here. The attention's and experts' tensor names are
``qwen3_moe``'s (``self_attn.{q,k,v,o}_proj``, ``self_attn.{q,k}_norm``,
``mlp.gate``, ``mlp.experts.{e}.{gate,up,down}_proj``); the indexer's
(``self_attn.indexer.{wq, wk, k_norm, weights_proj}``, ``k_norm`` a
LayerNorm with a bias) follow DeepSeek-V3.2's published module at this
config's sizes and are NOT confirmed against Keye's modelling code;
nor are: the indexer's query projected from the layer's normed input
(this model has no compressed query), its rotary embedding the layer's
over the whole 64-wide head in halves, ``q_chunk_size`` /
``kv_chunk_size`` tiles of the indexer's computation that change no
result. What is claimed is the architecture's shapes and named
mechanisms, not that the published checkpoint loads. ``mrope_section``
splits the rotary pairs over three position axes of the vision
tower's inputs; on TEXT the three hold the same position and the
sections are one plain rotary embedding, which is what runs here: the
vision tower is no part of this family. ``attention_bias``, a
``sliding_window`` in use and ``indexer_num_kv_heads`` other than 1 are
refused, not ignored.
"""

from typing import Any, Dict

import numpy as np

from realhf_tpu.models.config import (
    IndexerConfig,
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
#: leaf of a feed-forward (dense, or one expert) -> HF's name
_FFN = (("wg", "gate_proj"), ("wu", "up_proj"), ("wd", "down_proj"))
_ATTN = (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
         ("wo", "o_proj"))
#: leaf of ``lp["index"]`` -> (HF's name under ``self_attn.indexer.``,
#: whether it is a Linear's (out, in) matrix)
_INDEX = (("wq", "wq.weight", True), ("wk", "wk.weight", True),
          ("k_norm", "k_norm.weight", False),
          ("k_norm_bias", "k_norm.bias", False),
          ("w_weights", "weights_proj.weight", True))
_ROPE_SCALING = {"mrope_section": [16, 24, 24], "rope_type": "default",
                 "type": "default"}


def _sparse_ffn(d: Dict[str, Any], i: int) -> bool:
    """Whether layer ``i``'s feed-forward is the mixture of experts, as
    ``Qwen3MoeDecoderLayer`` decides it."""
    return (i not in d.get("mlp_only_layers", [])
            and d.get("num_experts", 0) > 0
            and (i + 1) % d.get("decoder_sparse_step", 1) == 0)


def _config_from_hf(d: Dict[str, Any], is_critic: bool) -> TransformerConfig:
    sa = d["sa_config"]
    if d.get("attention_bias", False):
        raise NotImplementedError("keye_vl2: attention_bias=true")
    if d.get("use_sliding_window", False):
        raise NotImplementedError("keye_vl2: use_sliding_window=true")
    if sa.get("indexer_num_kv_heads", 1) != 1:
        raise NotImplementedError(
            "keye_vl2: indexer_num_kv_heads="
            f"{sa['indexer_num_kv_heads']} (every head of a token shares "
            "ONE index key and one selection)")
    rope = d.get("rope_scaling") or {}
    if rope.get("rope_type", rope.get("type", "default")) != "default":
        raise NotImplementedError(f"keye_vl2: rope_scaling {rope!r}")
    n = d["num_hidden_layers"]
    nq = d["num_attention_heads"]
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
        rotary_base=float(d.get("rope_theta", 10000.0)),
        tied_embedding=d.get("tie_word_embeddings", False),
        qk_norm="head",
        layer_pattern=tuple(
            ("sparse", "moe" if _sparse_ffn(d, i) else "dense")
            for i in range(n)),
        indexer=IndexerConfig(heads=sa["indexer_num_heads"],
                              head_dim=sa["indexer_head_dim"],
                              topk=sa["topk"]),
        moe=MoEConfig(
            num_experts=share["of"] if share else held,
            top_k=d["num_experts_per_tok"],
            routing_type="none",
            norm_topk_prob=d.get("norm_topk_prob", False),
            intermediate_dim=d["moe_intermediate_size"],
            experts_held=(share["first"], held) if share else None),
        is_critic=is_critic,
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    moe, ix = cfg.moe, cfg.indexer
    d = {
        "model_type": "KeyeVL2",
        "architectures": ["KeyeVL2ForConditionalGeneration"],
        "hidden_size": cfg.hidden_dim,
        "intermediate_size": cfg.intermediate_dim,
        "moe_intermediate_size": moe.intermediate_dim
        or cfg.intermediate_dim,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "hidden_act": "silu",
        "num_experts": moe.n_held,
        "num_local_experts": moe.n_held,
        "num_experts_per_tok": moe.top_k,
        "norm_topk_prob": moe.norm_topk_prob,
        "decoder_sparse_step": 1,
        "mlp_only_layers": [i for i, (_, ff) in enumerate(cfg.layer_pattern)
                            if ff == "dense"],
        "attention_bias": False,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.n_positions or 262144,
        "max_window_layers": cfg.n_layers,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "rope_theta": cfg.rotary_base,
        "rope_scaling": dict(_ROPE_SCALING),
        "sa_config": {"indexer_head_dim": ix.head_dim,
                      "indexer_num_heads": ix.heads,
                      "indexer_num_kv_heads": 1,
                      "kv_chunk_size": 512, "q_chunk_size": 512,
                      "topk": ix.topk},
        "sliding_window": None,
        "use_sliding_window": False,
        "tie_word_embeddings": cfg.tied_embedding,
        "torch_dtype": "float32",
    }
    if moe.experts_held is not None:
        d["expert_share"] = {"of": moe.num_experts,
                             "first": moe.experts_held[0]}
    return d


def layer_from_hf(state: StateDict, cfg: TransformerConfig,
                  i: int) -> Dict[str, Any]:
    """The tree of layer ``i``: attention, its two norms a head, the
    indexer, the feed-forward its pattern entry says; HF Linear weights
    (out, in) transposed."""
    pre = _PRE.format(i)
    _, ff = cfg.layer_pattern[i]
    attn = pre + "self_attn."
    lp: Dict[str, Any] = {
        "ln1": {"scale": state[pre + "input_layernorm.weight"]},
        "ln2": {"scale": state[pre + "post_attention_layernorm.weight"]},
        "attn": {leaf: state[f"{attn}{hf}.weight"].T for leaf, hf in _ATTN},
        "index": {leaf: state[f"{attn}indexer.{hf}"].T if matrix
                  else state[f"{attn}indexer.{hf}"]
                  for leaf, hf, matrix in _INDEX}}
    lp["attn"]["q_norm"] = state[attn + "q_norm.weight"]
    lp["attn"]["k_norm"] = state[attn + "k_norm.weight"]
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
    return lp


def layer_to_hf(lp: Dict[str, Any], cfg: TransformerConfig, i: int,
                out: StateDict):
    """Inverse of :func:`layer_from_hf`."""
    pre = _PRE.format(i)
    _, ff = cfg.layer_pattern[i]
    c = np.ascontiguousarray
    attn = pre + "self_attn."
    out[pre + "input_layernorm.weight"] = c(lp["ln1"]["scale"])
    out[pre + "post_attention_layernorm.weight"] = c(lp["ln2"]["scale"])
    for leaf, hf in _ATTN:
        out[f"{attn}{hf}.weight"] = c(lp["attn"][leaf].T)
    out[attn + "q_norm.weight"] = c(lp["attn"]["q_norm"])
    out[attn + "k_norm.weight"] = c(lp["attn"]["k_norm"])
    for leaf, hf, matrix in _INDEX:
        out[f"{attn}indexer.{hf}"] = c(lp["index"][leaf].T if matrix
                                       else lp["index"][leaf])
    mlp = pre + "mlp."
    if ff == "dense":
        for leaf, hf in _FFN:
            out[f"{mlp}{hf}.weight"] = c(lp["mlp"][leaf].T)
        return
    out[mlp + "gate.weight"] = c(lp["mlp"]["router"].T)
    for leaf, hf in _FFN:
        for j, e in enumerate(held_expert_ids(cfg)):
            out[f"{mlp}experts.{e}.{hf}.weight"] = c(lp["mlp"][leaf][j].T)


_params_from_hf, _params_to_hf = layered_converters(
    layer_from_hf, layer_to_hf)

register_hf_family(HFFamily(
    name="keye_vl2", hf_model_type="KeyeVL2",
    config_from_hf=_config_from_hf,
    config_to_hf=_config_to_hf,
    params_from_hf=_params_from_hf,
    params_to_hf=_params_to_hf,
    layer_from_hf=layer_from_hf,
    layer_to_hf=layer_to_hf,
))
