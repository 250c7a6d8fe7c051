"""Ouro (ByteDance's looped language models, ``model_type`` "ouro";
"Scaling Latent Reasoning via Looped Language Models",
arXiv:2510.25741) HF conversion.

A llama-like stack (RMSNorm, rotate-half rotary over the whole head,
multi-head attention without biases, SwiGLU, untied head) that differs
in three things, all of which ``TransformerConfig`` says and
``models/transformer.py`` runs on the normal path:

- the stack runs ``total_ut_steps`` times over ONE set of weights
  (``n_passes``), ``model.norm`` after EVERY pass, its output what the
  next pass starts from;
- a layer norms AFTER each operator too, inside the residual's add
  (``post_norm``): ``input_layernorm`` before and ``input_layernorm_2``
  after attention, ``post_attention_layernorm`` before and
  ``post_attention_layernorm_2`` after the feed-forward;
- ``model.early_exit_gate``, a ``Linear(hidden, 1)`` WITH a bias on
  every pass's final hidden state (``exit_gate``).

``transformers`` here has no ``ouro`` and there is no network: the
tensor names below are the published modelling code's as remembered
(``benchmark/configs/ouro-2.6b-l6.json`` lists them under ``assumed``);
shapes and mechanisms are claimed, not that the published checkpoint
loads. ``early_exit_threshold`` under 1 (a token leaving the loop
early) is refused: every token runs every pass. A critic or reward
model of the family puts its value head on the LAST pass's hidden
state (it keeps the gate's leaves and asks them nothing).
"""

import dataclasses
from typing import Any, Dict

import numpy as np

from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.models.hf.llama import (_config_from_hf_llama,
                                        _config_to_hf_llama,
                                        _params_from_hf_llama,
                                        _params_to_hf_llama)
from realhf_tpu.models.hf.registry import (HFFamily, StateDict,
                                           register_hf_family, stack_layers,
                                           unstack_layers)

#: the entropy term's weight in the looped objective: not a key of the
#: published config.json; the paper's later-stage value (0.1 earlier)
ENTROPY_COEFF = 0.05
_PRE = "model.layers.{}."
#: leaf of a block -> the norm AFTER an operator under HF's name
_POST_NORMS = (("ln1_post", "input_layernorm_2.weight"),
               ("ln2_post", "post_attention_layernorm_2.weight"))
_GATE = "model.early_exit_gate."


def _config_from_hf(d: Dict[str, Any], is_critic: bool) -> TransformerConfig:
    if float(d.get("early_exit_threshold", 1.0)) < 1.0:
        raise NotImplementedError(
            f"early_exit_threshold={d['early_exit_threshold']}: a token "
            "that leaves the loop before its last pass is not "
            "implemented (every row of a batch runs every pass)")
    return dataclasses.replace(
        _config_from_hf_llama(d, is_critic),
        n_passes=int(d.get("total_ut_steps", 1)), post_norm=True,
        exit_gate=True, exit_entropy_coeff=ENTROPY_COEFF)


def _config_to_hf(cfg: TransformerConfig) -> Dict[str, Any]:
    d = _config_to_hf_llama(cfg, "llama")
    del d["attention_bias"]
    d.update(model_type="ouro", architectures=["OuroForCausalLM"],
             total_ut_steps=cfg.n_passes, early_exit_threshold=1.0,
             use_sliding_window=False)
    return d


def _params_from_hf(state: StateDict, cfg: TransformerConfig
                    ) -> Dict[str, Any]:
    params = _params_from_hf_llama(state, cfg)
    for leaf, name in _POST_NORMS:
        params["blocks"][leaf] = {
            "scale": stack_layers(state, _PRE + name, cfg.n_layers)}
    params["exit_gate"] = {"w": state[_GATE + "weight"].T.copy(),
                           "b": state[_GATE + "bias"]}
    return params


def _params_to_hf(params: Dict[str, Any], cfg: TransformerConfig
                  ) -> StateDict:
    out = _params_to_hf_llama(params, cfg)
    for leaf, name in _POST_NORMS:
        unstack_layers(params["blocks"][leaf]["scale"], _PRE + name, out)
    gate = params["exit_gate"]
    out[_GATE + "weight"] = np.ascontiguousarray(gate["w"].T)
    out[_GATE + "bias"] = np.ascontiguousarray(gate["b"])
    return out


register_hf_family(HFFamily(
    name="ouro", hf_model_type="ouro",
    config_from_hf=_config_from_hf,
    config_to_hf=_config_to_hf,
    params_from_hf=_params_from_hf,
    params_to_hf=_params_to_hf,
))
