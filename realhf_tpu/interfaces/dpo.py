"""Direct Preference Optimization interface.

Parity with reference ``realhf/impl/model/interface/dpo_interface.py``
(DPOInterface:99) + ``utils/dpo_functional.py:7``: the ref model's
`inference` produces per-sequence answer logprob sums ("seqlogp"); the
train step maximizes log sigmoid(beta * (pi_logratio - ref_logratio))
over (pos, neg) pairs.
"""

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from realhf_tpu.api import model as model_api
from realhf_tpu.api.data import SequenceSample
from realhf_tpu.base import logging
from realhf_tpu.interfaces import common
from realhf_tpu.ops import functional as F

logger = logging.getLogger("DPOInterface")


def _answer_masks(sb: common.StreamBatch, seqlens: List[int],
                  prompt_lens_per_seq: List[int]) -> np.ndarray:
    """[S, L] mask of shifted positions covering answer tokens: for a
    sequence at (stream, off) with length l and prompt p, positions
    off+p-1 .. off+l-2 (predicting tokens p..l-1)."""
    s, l = sb.arrays["seg_ids"].shape
    mask = np.zeros((s, l), np.float32)
    for i, (ln, pl) in enumerate(zip(seqlens, prompt_lens_per_seq)):
        row, off = sb.info.stream[i], sb.info.offset[i]
        mask[row, off + pl - 1: off + ln - 1] = 1.0
    return mask


def _make_loss_fn(cfg, n_seqs: int, beta: float):

    def loss_fn(params, h, mb):
        lp = F.shifted_logprobs_from_hidden(
            cfg, params, h, mb["input_ids"], mb["seg_ids"])
        masked = (lp * mb["answer_mask"]).reshape(-1)
        sums = jax.ops.segment_sum(masked, mb["seq_index"].reshape(-1),
                                   num_segments=n_seqs + 1)[:n_seqs]
        pi_pos = sums[mb["pos_seq"]]
        pi_neg = sums[mb["neg_seq"]]
        ref_pos = mb["ref_pos"]
        ref_neg = mb["ref_neg"]
        valid = mb["pair_valid"]
        denom = jnp.maximum(valid.sum(), 1)
        logits = beta * ((pi_pos - pi_neg) - (ref_pos - ref_neg))
        loss = (-jax.nn.log_sigmoid(logits) * valid).sum() / denom
        pos_score = (beta * (pi_pos - ref_pos) * valid).sum() / denom
        neg_score = (beta * (pi_neg - ref_neg) * valid).sum() / denom
        kl = (-(pi_pos - ref_pos + pi_neg - ref_neg) * valid).sum() / denom
        return loss, {"loss": loss, "pos_score": pos_score,
                      "neg_score": neg_score, "kl": kl}

    return loss_fn


@dataclasses.dataclass
class DPOInterface(model_api.ModelInterface):
    beta: float = 0.1
    enable_save: bool = True

    def _prompt_lens_per_seq(self, input_: SequenceSample) -> List[int]:
        out = []
        for lens, pl in zip(input_.seqlens["packed_input_ids"],
                            input_.data["prompt_lens"].reshape(-1).tolist()):
            out.extend([int(pl)] * len(lens))
        return out

    def _seq_logp(self, model, input_: SequenceSample) -> np.ndarray:
        """Per-sequence answer logprob sums under the model."""
        seqlens = common.flat_seqlens(input_)
        sb = common.build_stream_batch(
            seqlens,
            token_keys=dict(input_ids=input_.data["packed_input_ids"]),
            n_streams=model.engine.n_streams)
        lp = np.asarray(model.engine.forward_logprobs(
            sb.arrays["input_ids"], sb.arrays["seg_ids"]))
        mask = _answer_masks(sb, seqlens, self._prompt_lens_per_seq(input_))
        sums = np.zeros(len(seqlens), np.float64)
        masked = lp * mask
        for i, ln in enumerate(seqlens):
            row, off = sb.info.stream[i], sb.info.offset[i]
            sums[i] = masked[row, off:off + ln].sum()
        return sums.astype(np.float32)

    def inference(self, model: model_api.Model, input_: SequenceSample,
                  n_mbs: Optional[int] = None) -> SequenceSample:
        sums = self._seq_logp(model, input_)
        n_per_elem = [len(l) for l in input_.seqlens["packed_input_ids"]]
        return SequenceSample(
            keys=["seqlogp"],
            trailing_shapes=dict(seqlogp=()),
            dtypes=dict(seqlogp=np.float32),
            ids=input_.ids,
            seqlens=dict(seqlogp=[[1] * n for n in n_per_elem]),
            data=dict(seqlogp=sums),
        )

    def train_step(self, model: model_api.Model, input_: SequenceSample,
                   n_mbs: Optional[int] = None) -> Dict:
        engine = model.engine
        mbs = common.split_minibatches(input_, n_mbs or 1)
        batches, weights, n_seqs_max = [], [], 0
        for mb in mbs:
            seqlens = common.flat_seqlens(mb)
            n_seqs_max = max(n_seqs_max, len(seqlens))
        for mb in mbs:
            seqlens = common.flat_seqlens(mb)
            sb = common.build_stream_batch(
                seqlens,
                token_keys=dict(input_ids=mb.data["packed_input_ids"]),
                n_streams=engine.n_streams)
            sb.arrays["answer_mask"] = _answer_masks(
                sb, seqlens, self._prompt_lens_per_seq(mb))
            # map pads to index n_seqs_max (one shared dustbin segment)
            seg = sb.arrays["seg_ids"]
            sb.arrays["seq_index"] = np.where(
                seg > 0, seg - 1, n_seqs_max).astype(np.int32)
            ref = mb.data["seqlogp"].reshape(-1)
            pos_seq, neg_seq, rp, rn, valid = [], [], [], [], []
            si = 0
            for lens in mb.seqlens["packed_input_ids"]:
                for p in range(len(lens) // 2):
                    pos_seq.append(si + 2 * p)
                    neg_seq.append(si + 2 * p + 1)
                    rp.append(ref[si + 2 * p])
                    rn.append(ref[si + 2 * p + 1])
                    valid.append(1.0)
                si += len(lens)
            sb.arrays["pos_seq"] = np.asarray(pos_seq, np.int32)
            sb.arrays["neg_seq"] = np.asarray(neg_seq, np.int32)
            sb.arrays["ref_pos"] = np.asarray(rp, np.float32)
            sb.arrays["ref_neg"] = np.asarray(rn, np.float32)
            sb.arrays["pair_valid"] = np.asarray(valid, np.float32)
            batches.append(sb)
            weights.append(len(valid))
        batches = common.pad_stream_batches(batches)
        npair = max(b.arrays["pos_seq"].shape[0] for b in batches)
        for b in batches:
            for k in ("pos_seq", "neg_seq", "ref_pos", "ref_neg",
                      "pair_valid"):
                v = b.arrays[k]
                b.arrays[k] = np.pad(v, (0, npair - v.shape[0]))
        stats = engine.train_batch(
            [b.arrays for b in batches],
            _make_loss_fn(model.config, n_seqs_max, self.beta),
            loss_weights=weights, loss_fn_key=("dpo", n_seqs_max, self.beta))
        model.inc_version()
        return stats

    def save(self, model: model_api.Model, save_dir: str,
             host_params=None, writer: bool = True):
        if not self.enable_save:
            return
        common.save_checkpoint(model, save_dir, host_params,
                               writer=writer)


model_api.register_interface("dpo", DPOInterface)
