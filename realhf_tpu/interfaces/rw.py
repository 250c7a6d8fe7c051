"""Paired reward modeling interface (Bradley-Terry).

Parity with reference ``realhf/impl/model/interface/rw_interface.py``
(PairedRewardInterface:103, _paired_rw_loss_from_model_outputs:25):
each batch element packs interleaved (pos, neg) full sequences; the
score is the critic head's value at each sequence's final token; loss
is -log sigmoid(score_pos - score_neg) averaged over pairs. The
`inference` handler scores sequences for PPO's rew_inf MFC.
"""

import dataclasses
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from realhf_tpu.api import model as model_api
from realhf_tpu.api.data import SequenceSample
from realhf_tpu.base import logging
from realhf_tpu.engine import packing
from realhf_tpu.interfaces import common
from realhf_tpu.models import transformer as T

logger = logging.getLogger("PairedRewardInterface")


def _make_loss_fn(cfg):

    def loss_fn(params, h, mb):
        values = T.critic_values(cfg, params, h)  # [S, L]
        # Gather per-pair (pos, neg) end-of-sequence scores via (row,
        # col) coordinates (stable under stream padding), plus a pair
        # validity mask (groups may have fewer than max_pairs pairs).
        pos = values[mb["pos_row"], mb["pos_col"]]
        neg = values[mb["neg_row"], mb["neg_col"]]
        valid = mb["pair_valid"]
        denom = jnp.maximum(valid.sum(), 1)
        losses = -jax.nn.log_sigmoid(pos - neg)
        loss = (losses * valid).sum() / denom
        acc = ((pos > neg) & (valid > 0)).sum() / denom
        return loss, {
            "loss": loss,
            "acc": acc.astype(jnp.float32),
            "pos_score": (pos * valid).sum() / denom,
            "neg_score": (neg * valid).sum() / denom,
        }

    return loss_fn


@dataclasses.dataclass
class PairedRewardInterface(model_api.ModelInterface):
    enable_save: bool = True
    output_scaling: float = 1.0
    output_bias: float = 0.0

    def _score_batch(self, model, input_: SequenceSample,
                     n_mbs: Optional[int] = None) -> np.ndarray:
        """Value at the final token of every sequence (flattened).
        ``n_mbs`` chunks the batch (contiguous, order-preserving) so
        packed rows stay short, as in PPOActorInterface.inference."""
        pieces = []
        for chunk in common.split_minibatches(input_, n_mbs or 1):
            seqlens = common.flat_seqlens(chunk)
            sb = common.build_stream_batch(
                seqlens,
                token_keys=dict(
                    input_ids=chunk.data["packed_input_ids"]),
                n_streams=model.engine.n_streams)
            values = np.asarray(model.engine.forward_values(
                sb.arrays["input_ids"], sb.arrays["seg_ids"]))
            pieces.append(packing.per_seq_gather(
                sb.info, values, [l - 1 for l in seqlens]))
        scores = np.concatenate(pieces)
        return (scores - self.output_bias) * self.output_scaling

    def inference(self, model: model_api.Model, input_: SequenceSample,
                  n_mbs: Optional[int] = None) -> SequenceSample:
        scores = self._score_batch(model, input_, n_mbs)
        # One score per batch element: elements holding multiple
        # sequences (paired data) keep per-sequence scores concatenated.
        n_per_elem = [len(l) for l in input_.seqlens["packed_input_ids"]]
        assert sum(n_per_elem) == len(scores)
        return SequenceSample(
            keys=["rewards"],
            trailing_shapes=dict(rewards=()),
            dtypes=dict(rewards=np.float32),
            ids=input_.ids,
            seqlens=dict(rewards=[[1] * n for n in n_per_elem]),
            data=dict(rewards=scores.astype(np.float32)),
        )

    def train_step(self, model: model_api.Model, input_: SequenceSample,
                   n_mbs: Optional[int] = None) -> Dict:
        engine = model.engine
        mbs = common.split_minibatches(input_, n_mbs or 1)
        batches, weights = [], []
        for mb in mbs:
            seqlens = common.flat_seqlens(mb)
            sb = common.build_stream_batch(
                seqlens,
                token_keys=dict(input_ids=mb.data["packed_input_ids"]),
                n_streams=engine.n_streams)
            # (row, col) of each sequence's final token
            ends = [(sb.info.stream[i], sb.info.offset[i] + ln - 1)
                    for i, ln in enumerate(seqlens)]
            pr, pc, nr, nc, valid = [], [], [], [], []
            si = 0
            n_pairs_total = sum(
                len(lens) // 2 for lens in mb.seqlens["packed_input_ids"])
            for lens in mb.seqlens["packed_input_ids"]:
                for p in range(len(lens) // 2):
                    pr.append(ends[si + 2 * p][0])
                    pc.append(ends[si + 2 * p][1])
                    nr.append(ends[si + 2 * p + 1][0])
                    nc.append(ends[si + 2 * p + 1][1])
                    valid.append(1.0)
                si += len(lens)
            sb.arrays["pos_row"] = np.asarray(pr, np.int32)
            sb.arrays["pos_col"] = np.asarray(pc, np.int32)
            sb.arrays["neg_row"] = np.asarray(nr, np.int32)
            sb.arrays["neg_col"] = np.asarray(nc, np.int32)
            sb.arrays["pair_valid"] = np.asarray(valid, np.float32)
            batches.append(sb)
            weights.append(n_pairs_total)
        batches = common.pad_stream_batches(batches)
        # pair vectors are 1D (pad_stream_batches leaves them); pad to a
        # common pair count so microbatches stack
        npair = max(b.arrays["pos_row"].shape[0] for b in batches)
        for b in batches:
            for k in ("pos_row", "pos_col", "neg_row", "neg_col",
                      "pair_valid"):
                v = b.arrays[k]
                b.arrays[k] = np.pad(v, (0, npair - v.shape[0]))
        stats = engine.train_batch(
            [b.arrays for b in batches],
            _make_loss_fn(model.config),
            loss_weights=weights, loss_fn_key="paired_rw")
        model.inc_version()
        return stats

    def save(self, model: model_api.Model, save_dir: str,
             host_params=None, writer: bool = True):
        if not self.enable_save:
            return
        common.save_checkpoint(model, save_dir, host_params,
                               writer=writer)


model_api.register_interface("paired_rw", PairedRewardInterface)
