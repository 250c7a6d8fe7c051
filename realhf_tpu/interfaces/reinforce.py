"""REINFORCE / ReMax: critic-free policy gradient with a greedy
baseline.

Parity with reference ``examples/new_algorithms/reinforce/
reinforce_interface.py``: each prompt samples one response AND decodes
one greedy response; the greedy response's reward is the variance
baseline (ReMax), so the per-prompt advantage is
``r_sampled - r_greedy`` broadcast over the sampled response tokens,
and the loss is plain REINFORCE ``-adv * logpi`` (no clipping, no
critic, no GAE). Both responses live as two nested sequences inside
each batch element (sampled first, greedy second), so ids are
preserved and the runtime's data merge works unchanged -- the same
grouping device as GRPO.
"""

import dataclasses
from typing import Dict, Optional

import numpy as np

from realhf_tpu.api import model as model_api
from realhf_tpu.api.data import SequenceSample
from realhf_tpu.base import logging
from realhf_tpu.interfaces import common
from realhf_tpu.interfaces.ppo import PPOActorInterface, _shifted_loss_mask

logger = logging.getLogger("ReinforceInterface")


@dataclasses.dataclass
class ReinforceInterface(PPOActorInterface):
    """Reuses the PPO actor's generate/inference plumbing; overrides
    sampling (paired sampled+greedy decode) and the loss."""
    kl_coef: float = 0.0  # optional k3 penalty vs the reference policy

    def __post_init__(self):
        super().__post_init__()
        if self.gconfig.greedy:
            raise ValueError(
                "ReinforceInterface needs a SAMPLED rollout; the greedy "
                "baseline decode is issued internally.")
        if not self.gconfig.force_no_logits_mask:
            # the greedy baseline has no logits mask, so the sampled
            # half's mask cannot ride the interleaved layout; without
            # replay, warped sampling would make recomputed logprobs
            # inconsistent with the rollout distribution
            raise ValueError(
                "ReinforceInterface does not replay the sampling "
                "logits mask; set force_no_logits_mask=True (and "
                "disable top-k/top-p if exact logprob consistency "
                "matters).")

    # ------------------------------------------------------------------
    def generate(self, model: model_api.Model, input_: SequenceSample,
                 n_mbs: Optional[int] = None) -> SequenceSample:
        import copy

        sampled = super().generate(model, input_, n_mbs=n_mbs)
        # shallow-copy twin with a greedy gconfig (dataclasses.replace
        # would re-run __post_init__, which rejects greedy configs)
        greedy_itf = copy.copy(self)
        greedy_itf.gconfig = dataclasses.replace(
            self.gconfig, greedy=True, force_no_logits_mask=True)
        greedy = PPOActorInterface.generate(greedy_itf, model, input_,
                                            n_mbs=n_mbs)

        # interleave: element i holds [sampled_i, greedy_i]
        keys = [k for k in sampled.keys if k in greedy.keys]
        s_parts = sampled.select(keys).unpack()
        g_parts = greedy.select(keys).unpack()

        def nest(key):
            return [s.seqlens[key][0] + g.seqlens[key][0]
                    for s, g in zip(s_parts, g_parts)]

        data = {}
        for k in keys:
            pieces = []
            for s, g in zip(s_parts, g_parts):
                pieces.append(np.concatenate(
                    [np.atleast_1d(s.data[k]), np.atleast_1d(g.data[k])]))
            data[k] = np.concatenate(pieces)
        with SequenceSample.disable_validation():
            return SequenceSample(
                keys=keys,
                trailing_shapes={k: sampled.trailing_shapes[k]
                                 for k in keys},
                dtypes={k: sampled.dtypes[k] for k in keys},
                ids=list(input_.ids),
                seqlens={k: nest(k) for k in keys},
                data=data,
                metadata={})

    # ------------------------------------------------------------------
    def train_step(self, model: model_api.Model, input_: SequenceSample,
                   n_mbs: Optional[int] = None) -> Dict:
        engine = model.engine
        seqlens = common.flat_seqlens(input_)
        n_seqs = len(seqlens)
        assert n_seqs % 2 == 0, "sampled+greedy pairs expected"

        prompt_mask = np.asarray(input_.data["prompt_mask"], bool)
        rewards = np.asarray(input_.data["rewards"], np.float32)
        has_ref = "packed_ref_logprobs" in input_.keys and self.kl_coef > 0

        # ReMax advantage: r_sampled - r_greedy per pair; greedy
        # sequences get advantage 0 (they only serve as the baseline
        # and contribute no gradient).
        pairs = rewards.reshape(-1, 2)
        adv_seq = np.zeros_like(rewards)
        adv_seq[0::2] = np.clip(pairs[:, 0] - pairs[:, 1],
                                -self.max_reward_clip,
                                self.max_reward_clip)

        loss_mask = _shifted_loss_mask(prompt_mask, seqlens)
        lens_m1 = np.asarray(seqlens) - 1
        advantages = np.repeat(adv_seq, lens_m1).astype(np.float32)
        # zero out greedy-sequence tokens entirely
        keep = np.repeat(np.tile([True, False], n_seqs // 2), lens_m1)
        loss_mask = loss_mask & keep
        advantages = advantages * loss_mask

        global_stats = dict(
            task_reward=float(pairs[:, 0].mean()),
            greedy_reward=float(pairs[:, 1].mean()),
            advantage=float(adv_seq[0::2].mean()),
            n_seqs=n_seqs)

        nested = input_.seqlens["packed_input_ids"]
        nested_m1 = [[l - 1 for l in lens] for lens in nested]
        data = dict(
            packed_input_ids=input_.data["packed_input_ids"],
            advantages=advantages,
            ppo_loss_mask=loss_mask)
        keys = list(data)
        if has_ref:
            data["ref_logp"] = np.asarray(
                input_.data["packed_ref_logprobs"], np.float32)
            keys.append("ref_logp")
        with SequenceSample.disable_validation():
            sample = SequenceSample(
                keys=keys,
                trailing_shapes={k: () for k in keys},
                dtypes=dict(packed_input_ids=np.int32,
                            advantages=np.float32,
                            ppo_loss_mask=np.bool_,
                            **({"ref_logp": np.float32} if has_ref
                               else {})),
                ids=list(input_.ids),
                seqlens=dict(
                    packed_input_ids=nested,
                    advantages=nested_m1,
                    ppo_loss_mask=nested_m1,
                    **({"ref_logp": nested_m1} if has_ref else {})),
                data=data,
                metadata={})
        mbs = common.split_minibatches(sample, self.n_minibatches)

        cfg = model.config
        temperature = self.gconfig.temperature
        kl_coef = self.kl_coef

        def loss_fn(params, h, mb):
            import jax.numpy as jnp

            from realhf_tpu.ops import functional as F
            lp = F.shifted_logprobs_from_hidden(
                cfg, params, h, mb["input_ids"], mb["seg_ids"],
                temperature=temperature)
            m = mb["loss_mask"]
            denom = jnp.maximum(m.sum(), 1.0)
            pg = -(mb["advantages"] * lp * m).sum() / denom
            total = pg
            stats = dict(reinforce_loss=pg)
            if has_ref:
                diff = mb["ref_logp"] - lp
                kl = (jnp.where(m > 0, jnp.exp(diff) - diff - 1.0,
                                0.0)).sum() / denom
                total = total + kl_coef * kl
                stats["ref_kl"] = kl
            return total, stats

        def build_sb(minibatch):
            mb_lens = common.flat_seqlens(minibatch)
            shifted = dict(
                advantages=minibatch.data["advantages"],
                loss_mask=minibatch.data["ppo_loss_mask"]
                .astype(np.float32))
            if has_ref:
                shifted["ref_logp"] = minibatch.data["ref_logp"]
            return common.build_stream_batch(
                mb_lens,
                token_keys=dict(
                    input_ids=minibatch.data["packed_input_ids"]),
                shifted_keys=shifted,
                n_streams=engine.n_streams)

        all_stats = [
            common.run_train_microbatched(
                engine, minibatch, build_sb, loss_fn,
                ("reinforce", temperature, kl_coef, has_ref), n_mbs)
            for minibatch in mbs
        ]
        model.inc_version()
        agg = {k: float(np.mean([s[k] for s in all_stats]))
               for k in all_stats[0]}
        agg.update(global_stats)
        return agg


model_api.register_interface("reinforce", ReinforceInterface)
