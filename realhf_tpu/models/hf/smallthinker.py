"""SmallThinker HF conversion (``SmallThinker-21BA3B-Instruct``,
``model_type: smallthinker``): a router that reads the layer's INPUT,
before attention and before any norm; ReLU-gated experts, no shared
one and no dense layer; full layers WITHOUT a rotary embedding beside
window layers with one.

A layer, with ``x`` its input::

    z = x W_r                   [T, E] float32: MoEConfig.router_input
    a = x + Attn(RMSNorm(x; input_layernorm)) W_o
    h = RMSNorm(a; post_attention_layernorm)
    the k largest z of a token; g = softmax over THOSE k logits
    y = a + sum_j g_j (relu(h W_gate[e_j]) * (h W_up[e_j])) W_down[e_j]

The family DECLARES its layers, as ``laguna`` does, by two lists of the
published config: ``sliding_window_layout`` (1: the layer attends the
last ``sliding_window_size`` tokens, operator "window"; 0: its whole
document, "attention") and ``rope_layout`` (1: the layer rotates
queries and keys at base ``rope_theta`` over the whole head; 0: it has
NO positional embedding). ``rotary_by_operator`` says the rotary
embedding a KIND of layer, so the layers of one kind must agree in
``rope_layout`` (the published lists are equal: every window layer
rotates, no full layer does); lists that do not are refused by name.

**An expert-parallel rank's share** is said as in ``lfm2_moe.py``:
``moe_num_primary_experts`` counts the experts whose weights are in
the files, ``expert_share: {"of": 64, "first": 0}`` the published count
(the router's width) and the global id of the first one held; the files
name experts by their GLOBAL id. ``expert_dispatch: "dense"`` beside it
(not a published key either) asks for the dense mode over the held
stacks, every held expert over every token, where a share otherwise
takes the ragged mode's sorted pairs (``ops/moe.py``): the same result,
at a cost that does not move with the routing.

``transformers`` 4.57.6 has no ``smallthinker`` and there is no network
here. The tensor names (``self_attn.{q,k,v,o}_proj``,
``block_sparse_moe.primary_router``, ``block_sparse_moe.experts.{e}.
{gate,up,down}``) follow the public GGUF converter's table for the
family and are NOT confirmed against the published modelling code; nor
are: that the router reads ``x`` itself and not its norm; top-k on the
logits and then the softmax (``moe_primary_router_apply_softmax:
true``; false is refused, not ignored); the gates on the experts'
OUTPUT; the halves convention of the rotary embedding; a window that
counts the query itself. What is claimed is the architecture's shapes
and named mechanisms, not that the published checkpoint loads. The
"secondary experts" of the model's description have no key in the
config and are no part of this family.
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
_MOE = "block_sparse_moe."
#: leaf of an expert -> HF's name
_FFN = (("wg", "gate"), ("wu", "up"), ("wd", "down"))
_ATTN = (("wq", "q_proj"), ("wk", "k_proj"), ("wv", "v_proj"),
         ("wo", "o_proj"))


def _layouts(d: Dict[str, Any]):
    """(window layout, rope layout): one 0 or 1 a layer."""
    n = d["num_hidden_layers"]
    window = d.get("sliding_window_layout") or [0] * n
    rope = d.get("rope_layout") or [1] * n
    if not (len(window) == len(rope) == n
            and set(window) | set(rope) <= {0, 1}):
        raise NotImplementedError(
            f"smallthinker: sliding_window_layout {window} and "
            f"rope_layout {rope} for {n} layers")
    return window, rope


def _config_from_hf(d: Dict[str, Any], is_critic: bool) -> TransformerConfig:
    if d.get("rope_scaling") is not None:
        raise NotImplementedError(
            f"smallthinker: rope_scaling={d['rope_scaling']!r} (the "
            "published value is null)")
    if not d.get("moe_primary_router_apply_softmax", True):
        raise NotImplementedError(
            "smallthinker: moe_primary_router_apply_softmax=false (the "
            "gates are a softmax over the chosen logits here)")
    window, rope = _layouts(d)
    ops = ["window" if w else "attention" for w in window]
    rotary: Dict[str, Any] = {}
    for op, r in zip(ops, rope):
        if rotary.setdefault(op, r) != r:
            raise NotImplementedError(
                "smallthinker: layers of one kind differ in rope_layout "
                f"(sliding_window_layout {window}, rope_layout {rope}); "
                "rotary_by_operator says the embedding a KIND of layer")
    nq = d["num_attention_heads"]
    share = d.get("expert_share")
    held = d["moe_num_primary_experts"]
    dispatch = d.get("expert_dispatch", "ragged")
    if dispatch not in ("ragged", "dense"):
        raise NotImplementedError(
            f"smallthinker: expert_dispatch={dispatch!r} (ragged: the "
            "sorted pairs through the grouped products; dense: every "
            "held expert over every token)")
    plain = RotaryConfig(base=float(d.get("rope_theta", 10000.0)))
    return TransformerConfig(
        n_layers=d["num_hidden_layers"],
        n_kv_heads=d.get("num_key_value_heads", nq),
        n_q_heads=nq,
        hidden_dim=d["hidden_size"],
        head_dim=d.get("head_dim") or d["hidden_size"] // nq,
        intermediate_dim=d["moe_ffn_hidden_size"],
        vocab_size=d["vocab_size"],
        n_positions=d.get("max_position_embeddings"),
        layer_norm_epsilon=d.get("rms_norm_eps", 1e-6),
        activation_function="relu",
        use_attention_bias=False,
        use_attn_proj_bias=False,
        use_mlp_bias=False,
        layer_norm_type="rms",
        mlp_type="llama",
        apply_rotary=True,
        tied_embedding=d.get("tie_word_embeddings", False),
        sliding_window=d.get("sliding_window_size")
        if "window" in ops else None,
        layer_pattern=tuple((op, "moe") for op in ops),
        rotary_by_operator={op: plain if r else None
                            for op, r in rotary.items()},
        moe=MoEConfig(
            num_experts=share["of"] if share else held,
            top_k=d["moe_num_active_primary_experts"],
            routing_type="none",
            # softmax over all E, the k largest renormalised: the
            # softmax over the k chosen logits alone
            norm_topk_prob=True,
            router_input="layer_input",
            experts_held=(share["first"], held) if share else None,
            use_grouped_gemm=dispatch == "ragged"),
        is_critic=is_critic,
    )


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    moe = cfg.moe
    rotary = cfg.rotary_by_operator
    base = next((rc.base for rc in rotary.values() if rc is not None),
                10000.0)
    d = {
        "model_type": "smallthinker",
        "architectures": ["SmallThinkerForCausalLM"],
        "hidden_size": cfg.hidden_dim,
        "moe_ffn_hidden_size": cfg.intermediate_dim,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_q_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "head_dim": cfg.head_dim,
        "moe_num_primary_experts": moe.n_held,
        "moe_num_active_primary_experts": moe.top_k,
        "moe_primary_router_apply_softmax": True,
        "norm_topk_prob": True,
        "sliding_window_layout": [int(op == "window")
                                  for op, _ in cfg.layer_pattern],
        "rope_layout": [int(rotary[op] is not None)
                        for op, _ in cfg.layer_pattern],
        "rope_theta": base,
        "rope_scaling": None,
        "vocab_size": cfg.vocab_size,
        "max_position_embeddings": cfg.n_positions or 16384,
        "rms_norm_eps": cfg.layer_norm_epsilon,
        "tie_word_embeddings": cfg.tied_embedding,
        "torch_dtype": "float32",
    }
    if cfg.sliding_window is not None:
        d["sliding_window_size"] = cfg.sliding_window
    if moe.experts_held is not None:
        d["expert_share"] = {"of": moe.num_experts,
                             "first": moe.experts_held[0]}
    if not moe.use_grouped_gemm:
        d["expert_dispatch"] = "dense"
    return d


def layer_from_hf(state: StateDict, cfg: TransformerConfig,
                  i: int) -> Dict[str, Any]:
    """The tree of layer ``i``, HF Linear weights (out, in)
    transposed; the held experts stacked in the order of their global
    ids."""
    pre = _PRE.format(i)
    moe = pre + _MOE
    lp: Dict[str, Any] = {
        "ln1": {"scale": state[pre + "input_layernorm.weight"]},
        "ln2": {"scale": state[pre + "post_attention_layernorm.weight"]},
        "attn": {leaf: state[f"{pre}self_attn.{hf}.weight"].T
                 for leaf, hf in _ATTN},
        "mlp": {"router": state[moe + "primary_router.weight"].T}}
    for leaf, hf in _FFN:
        lp["mlp"][leaf] = np.stack(
            [state[f"{moe}experts.{e}.{hf}.weight"].T
             for e in held_expert_ids(cfg)], axis=0)
    return lp


def layer_to_hf(lp: Dict[str, Any], cfg: TransformerConfig, i: int,
                out: StateDict):
    """Inverse of :func:`layer_from_hf`."""
    pre = _PRE.format(i)
    moe = pre + _MOE
    c = np.ascontiguousarray
    out[pre + "input_layernorm.weight"] = c(lp["ln1"]["scale"])
    out[pre + "post_attention_layernorm.weight"] = c(lp["ln2"]["scale"])
    for leaf, hf in _ATTN:
        out[f"{pre}self_attn.{hf}.weight"] = c(lp["attn"][leaf].T)
    out[moe + "primary_router.weight"] = c(lp["mlp"]["router"].T)
    for leaf, hf in _FFN:
        for j, e in enumerate(held_expert_ids(cfg)):
            out[f"{moe}experts.{e}.{hf}.weight"] = c(lp["mlp"][leaf][j].T)


_params_from_hf, _params_to_hf = layered_converters(
    layer_from_hf, layer_to_hf)

register_hf_family(HFFamily(
    name="smallthinker", hf_model_type="smallthinker",
    config_from_hf=_config_from_hf,
    config_to_hf=_config_to_hf,
    params_from_hf=_params_from_hf,
    params_to_hf=_params_to_hf,
    layer_from_hf=layer_from_hf,
    layer_to_hf=layer_to_hf,
))
