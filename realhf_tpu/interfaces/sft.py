"""Supervised fine-tuning interface.

Parity with reference ``realhf/impl/model/interface/sft_interface.py``
(SFTInterface:87, compute_packed_sft_loss:19): next-token NLL over
non-prompt tokens of packed sequences.
"""

import dataclasses
from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from realhf_tpu.api import model as model_api
from realhf_tpu.api.data import SequenceSample
from realhf_tpu.base import logging
from realhf_tpu.interfaces import common
from realhf_tpu.ops import functional as F

logger = logging.getLogger("SFTInterface")


def _make_loss_fn(cfg):

    def loss_fn(params, h, mb):
        lp = F.shifted_logprobs_from_hidden(
            cfg, params, h, mb["input_ids"], mb["seg_ids"])
        # loss_mask[t] gates predicting token t+1: valid next-token
        # positions that are not prompt tokens (reference
        # compute_packed_sft_loss:19 shifts the prompt mask by one).
        seg = mb["seg_ids"]
        next_same = jnp.concatenate(
            [(seg[:, 1:] == seg[:, :-1]) & (seg[:, 1:] != 0),
             jnp.zeros_like(seg[:, :1], bool)], axis=1)
        next_is_prompt = jnp.concatenate(
            [mb["prompt_mask"][:, 1:], jnp.zeros_like(seg[:, :1], bool)],
            axis=1)
        mask = next_same & ~next_is_prompt
        denom = jnp.maximum(mask.sum(), 1)
        nll = -(lp * mask).sum() / denom
        return nll, {"nll": nll, "n_tokens": denom.astype(jnp.float32)}

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
