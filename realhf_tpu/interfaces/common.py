"""Shared interface plumbing: SequenceSample <-> stream-array batches.

Each interface packs its minibatch of ragged sequences into [S, L]
stream arrays (see engine/packing.py) before handing them to the
jitted engine, and unpacks engine outputs back into flat packed
arrays for the data plane.
"""

import dataclasses
from typing import Dict, List, Optional, Sequence

import numpy as np

from realhf_tpu.api.data import SequenceSample
from realhf_tpu.base.datapack import flat2d
from realhf_tpu.engine import packing


def seqlens_of(input_: SequenceSample, key: str = "packed_input_ids") -> List[int]:
    """Total sequence length per batch element for a key (elements may
    hold several sequences, e.g. reward pairs)."""
    return [sum(l) for l in input_.seqlens[key]]


def flat_seqlens(input_: SequenceSample, key: str = "packed_input_ids") -> List[int]:
    """Per-sequence lengths, flattened over batch elements."""
    return flat2d(input_.seqlens[key])


@dataclasses.dataclass
class StreamBatch:
    """One packed minibatch ready for the engine."""
    info: packing.PackInfo
    arrays: Dict[str, np.ndarray]
    n_tokens: int


def build_stream_batch(
    seqlens: Sequence[int],
    token_keys: Dict[str, np.ndarray],
    shifted_keys: Optional[Dict[str, np.ndarray]] = None,
    n_streams: int = 1,
    bucket: int = packing.DEFAULT_BUCKET,
    min_len: Optional[int] = None,
) -> StreamBatch:
    """Pack flat per-token arrays into stream layout.

    ``token_keys`` have per-sequence length l; ``shifted_keys`` have
    length l-1 (logprobs/advantages/...) and are aligned to the
    sequence start so that index t corresponds to predicting token t+1.
    """
    info = packing.plan_packing(seqlens, n_streams, bucket, min_len)
    arrays = {"seg_ids": packing.segment_ids(info)}
    for k, v in token_keys.items():
        arrays[k] = packing.pack_tokens(info, v)
    if shifted_keys:
        short = [l - 1 for l in seqlens]
        for k, v in shifted_keys.items():
            arrays[k] = packing.pack_tokens(info, v, seqlens=short)
    return StreamBatch(info=info, arrays=arrays,
                       n_tokens=int(np.sum(seqlens)))


def split_minibatches(input_: SequenceSample, n: int,
                      min_size: int = 1) -> List[SequenceSample]:
    """Token-balanced minibatch split (SequenceSample.split), clamped
    so tiny batches still work."""
    n = max(1, min(n, input_.bs // max(1, min_size)))
    if n <= 1:
        return [input_]
    return input_.split(n, min_size=min_size)


def run_train_microbatched(engine, sample: SequenceSample, build_sb,
                           loss_fn, loss_fn_key, n_mbs: Optional[int],
                           weight_key: str = "loss_mask") -> Dict:
    """One optimizer step over ``n_mbs`` memory microbatches of
    ``sample`` (MFCDef.n_mbs; reference model_api.py:305-463).

    Gradients are combined with weights equal to each microbatch's
    LOSS-MASK token count, which makes the accumulated gradient exactly
    the one-big-batch gradient (each microbatch loss is a mean over its
    own masked tokens). Weighting by total tokens would over-weight
    response tokens in prompt-heavy microbatches.
    """
    sbs = pad_stream_batches(
        [build_sb(m) for m in split_minibatches(sample, n_mbs or 1)])
    weights = [float(np.asarray(sb.arrays[weight_key]).sum()) for sb in sbs]
    if not any(w > 0 for w in weights):  # degenerate batch: avoid 0/0
        weights = [float(sb.n_tokens) for sb in sbs]
    return engine.train_batch([sb.arrays for sb in sbs], loss_fn,
                              loss_weights=weights, loss_fn_key=loss_fn_key)


def run_train_minibatches(engine, minibatch_samples, build_sb, loss_fn,
                          loss_fn_key, n_mbs: Optional[int],
                          weight_key: str = "loss_mask") -> List[Dict]:
    """The PPO-style minibatch loop: one optimizer step per minibatch
    sample, each accumulating over ``n_mbs`` memory microbatches.

    Wherever the minibatches stack, the WHOLE loop runs inside one
    jitted dispatch (``Engine.train_minibatches``: lax.scan threads
    params/opt state through the per-minibatch step): one dispatch
    and one host sync instead of one per minibatch -- identical
    update order and numerics to sequential ``train_batch`` calls,
    which a single minibatch and uneven microbatch counts still take."""
    splits = [split_minibatches(s, n_mbs or 1) for s in minibatch_samples]
    if (len(minibatch_samples) == 1
            or len({len(g) for g in splits}) != 1):
        # uneven microbatch counts cannot stack into one [N, M, ...];
        # counts are checked BEFORE any packing so the fallback does
        # not redo build_sb work
        return [run_train_microbatched(engine, m, build_sb, loss_fn,
                                       loss_fn_key, n_mbs, weight_key)
                for m in minibatch_samples]
    per_mb = [[build_sb(m) for m in group] for group in splits]
    flat = pad_stream_batches([sb for g in per_mb for sb in g])
    it = iter(flat)
    groups = [[next(it) for _ in g] for g in per_mb]
    stacks, weights = [], []
    for g in groups:
        w = [float(np.asarray(sb.arrays[weight_key]).sum()) for sb in g]
        if not any(x > 0 for x in w):
            w = [float(sb.n_tokens) for sb in g]
        stacks.append([sb.arrays for sb in g])
        weights.append(w)
    return engine.train_minibatches(stacks, loss_fn, weights,
                                    loss_fn_key)


def pad_stream_batches(batches: List[StreamBatch]) -> List[StreamBatch]:
    """Pad a list of stream batches to a common [S, L] so they can be
    stacked and scanned as microbatches in one jitted step."""
    s = max(b.arrays["seg_ids"].shape[0] for b in batches)
    l = max(b.arrays["seg_ids"].shape[1] for b in batches)
    out = []
    for b in batches:
        arrays = {}
        for k, v in b.arrays.items():
            if v.ndim < 2:  # per-pair/per-seq vectors, not [S, L] grids
                arrays[k] = v
                continue
            pad = [(0, s - v.shape[0]), (0, l - v.shape[1])] + \
                [(0, 0)] * (v.ndim - 2)
            arrays[k] = np.pad(v, pad)
        out.append(StreamBatch(info=b.info, arrays=arrays,
                               n_tokens=b.n_tokens))
    return out


def save_checkpoint(model, save_dir: str, host_params=None,
                    writer: bool = True):
    """Shared interface-save body (reference interfaces all end in the
    same ``api.save_hf(...)`` call).

    Default path: stream one layer at a time straight from the device
    arrays (``save_hf_checkpoint_streamed``), never materializing the
    full model on host. On a PROCESS-SPANNING mesh the per-layer
    slices are collective gathers every group member must join --
    ModelHost.save_role calls this on all members with
    ``writer=True`` only on the group leader, which alone writes
    files. ``host_params`` (a pre-gathered host copy) keeps the eager
    non-streamed path available for external callers."""
    from realhf_tpu.models.hf import (
        save_hf_checkpoint,
        save_hf_checkpoint_streamed,
    )
    if host_params is not None:
        if writer:
            save_hf_checkpoint(save_dir, model.hf_family, model.config,
                               host_params, tokenizer=model.tokenizer)
    else:
        save_hf_checkpoint_streamed(save_dir, model.hf_family,
                                    model.config, model.engine.params,
                                    tokenizer=model.tokenizer,
                                    writer=writer)
