"""Supervised fine-tuning interface.

Parity with reference ``realhf/impl/model/interface/sft_interface.py``
(SFTInterface:87, compute_packed_sft_loss:19): next-token NLL over
non-prompt tokens of packed sequences.
"""

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from realhf_tpu.api import model as model_api
from realhf_tpu.api.data import SequenceSample
from realhf_tpu.base import logging
from realhf_tpu.interfaces import common
from realhf_tpu.obs import parts
from realhf_tpu.ops import functional as F

logger = logging.getLogger("SFTInterface")


def _answer_mask(mb):
    """[S, L] bool: position t predicts token t+1, an ANSWER token of
    the same document (reference compute_packed_sft_loss:19 shifts the
    prompt mask by one)."""
    seg = mb["seg_ids"]
    next_same = jnp.concatenate(
        [(seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] != 0),
         jnp.zeros_like(seg[:, :1], bool)], axis=1)
    next_is_prompt = jnp.concatenate(
        [mb["prompt_mask"][:, 1:], jnp.zeros_like(seg[:, :1], bool)],
        axis=1)
    return next_same & ~next_is_prompt


def _make_loss_fn(cfg):
    if cfg.exit_gate:
        return _make_looped_loss_fn(cfg)

    def loss_fn(params, h, mb):
        mask = _answer_mask(mb)
        denom = jnp.maximum(mask.sum(), 1)
        # the loss is a weighted sum of log-probabilities, its weights
        # known before the head runs: the head computes its gradient
        # where it has the logits. The weights carry the sign, so the
        # sum IS the loss and its cotangent is the constant 1: the
        # compiler drops the head's scalings (-1 costs a pass over dW)
        nll, _ = F.weighted_logprob_sum(
            cfg, params, h, mb["input_ids"], mb["seg_ids"],
            -(mask / denom))
        return nll, {"nll": nll, "n_tokens": denom.astype(jnp.float32)}

    return loss_fn


def _make_looped_loss_fn(cfg):
    """The objective of a looped model with an exit gate (``cfg.
    exit_gate``; "Scaling Latent Reasoning via Looped Language Models",
    first stage): an answer token i with next-token loss ``nll_{t,i}``
    from pass t's hidden state and exit distribution ``p_{t,i}``
    (``F.exit_log_distribution``) costs

        sum_t p_{t,i} nll_{t,i} - beta H(p_{.,i}),  H(p) = -sum p log p

    averaged over the answer tokens, ``beta = cfg.exit_entropy_coeff``.
    Gradients reach the gate through p and the shared layers through
    every pass. The function asks the engine for EVERY pass's state
    (``every_pass``: ``Engine._objective`` then hands it
    ``models/transformer.py:PassStates`` for ``h``). Statistics a
    step: ``nll`` (pass T's, what an un-looped reader compares),
    ``exit_p<t>`` and ``nll_pass<t>`` (t from 1: the mean exit mass and
    loss a pass), ``expected_exit_pass`` (mean of sum_t t p_t) and
    ``exit_entropy`` (mean H)."""
    beta = cfg.exit_entropy_coeff

    def loss_fn(params, states, mb):
        log_p = F.exit_log_distribution(states.gate)  # [T, S, L]
        with jax.named_scope(parts.EXIT):
            mask = _answer_mask(mb)
            denom = jnp.maximum(mask.sum(), 1)
            p = jnp.exp(log_p)
        # sum_t mean(p_t nll_t): the weights are known before the head
        # runs (the gate reads the states), so every pass's head
        # computes its gradient where it has the logits; the gate is
        # reached through the weights, the statistics read ``lp``. The
        # weights carry the sign, as in the plain loss
        weighted_nll, lp = F.weighted_logprob_sum(
            cfg, params, states.hidden, mb["input_ids"], mb["seg_ids"],
            -p * mask / denom)
        with jax.named_scope(parts.EXIT):
            def mean(x):  # [..., S, L] -> [...] over the answer tokens
                return (x * mask).sum((-2, -1)) / denom

            entropy = mean(-(p * log_p).sum(0))
            p_mean, nll_mean = mean(p), mean(-lp)
            loss = weighted_nll - beta * entropy
            passes = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)
            stats = {"nll": nll_mean[-1],
                     "n_tokens": denom.astype(jnp.float32),
                     "expected_exit_pass": (passes * p_mean).sum(),
                     "exit_entropy": entropy}
            for t in range(p.shape[0]):
                stats[f"exit_p{t + 1}"] = p_mean[t]
                stats[f"nll_pass{t + 1}"] = nll_mean[t]
        return loss, stats

    loss_fn.every_pass = True
    return loss_fn


@dataclasses.dataclass
class SFTInterface(model_api.ModelInterface):
    token_normalize_scope: str = "dp"  # kept for config parity

    def train_step(self, model: model_api.Model, input_: SequenceSample,
                   n_mbs: Optional[int] = None) -> Dict:
        engine = model.engine
        n_mbs = n_mbs or 1
        mbs = common.split_minibatches(input_, n_mbs)
        batches = []
        for mb in mbs:
            seqlens = common.flat_seqlens(mb)
            batches.append(common.build_stream_batch(
                seqlens,
                token_keys=dict(
                    input_ids=mb.data["packed_input_ids"],
                    prompt_mask=mb.data["prompt_mask"]),
                n_streams=engine.n_streams))
        batches = common.pad_stream_batches(batches)
        # weight by ANSWER tokens (what each microbatch loss averages
        # over), so grad accumulation equals the one-big-batch gradient
        weights = [float((~b.arrays["prompt_mask"].astype(bool)
                          & (b.arrays["seg_ids"] != 0)).sum())
                   for b in batches]
        if not any(w > 0 for w in weights):
            weights = [float(b.n_tokens) for b in batches]
        stats = engine.train_batch(
            [b.arrays for b in batches],
            _make_loss_fn(model.config),
            loss_weights=weights, loss_fn_key="sft")
        model.inc_version()
        return stats

    def evaluate(self, model: model_api.Model, eval_dataloader) -> Dict:
        losses, tokens = [], []
        for batch in eval_dataloader:
            seqlens = common.flat_seqlens(batch)
            sb = common.build_stream_batch(
                seqlens,
                token_keys=dict(
                    input_ids=batch.data["packed_input_ids"],
                    prompt_mask=batch.data["prompt_mask"]),
                n_streams=model.engine.n_streams)
            lp = np.asarray(model.engine.forward_logprobs(
                sb.arrays["input_ids"], sb.arrays["seg_ids"]))
            seg = sb.arrays["seg_ids"]
            next_same = np.concatenate(
                [(seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] != 0),
                 np.zeros_like(seg[:, :1], bool)], axis=1)
            next_is_prompt = np.concatenate(
                [sb.arrays["prompt_mask"][:, 1:],
                 np.zeros_like(seg[:, :1], bool)], axis=1)
            mask = next_same & ~next_is_prompt
            losses.append(-(lp * mask).sum())
            tokens.append(mask.sum())
        if not tokens:
            return {}
        loss = float(np.sum(losses) / max(1, np.sum(tokens)))
        return {"loss": loss, "ppl": float(np.exp(loss))}

    def save(self, model: model_api.Model, save_dir: str,
             host_params=None, writer: bool = True):
        common.save_checkpoint(model, save_dir, host_params,
                               writer=writer)


model_api.register_interface("sft", SFTInterface)
