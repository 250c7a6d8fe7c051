"""PPO actor and critic interfaces.

Parity with reference ``realhf/impl/model/interface/ppo_interface.py``
(PPOActorInterface:110, PPOCriticInterface:639): the actor's three
handlers (generate / inference / train_step) and the critic's two
(inference / train_step), including KL-penalized rewards, GAE,
advantage/value normalization, dual-clip PPO losses, adaptive KL
control, logits-mask replay, and early stopping.
"""

import dataclasses
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from realhf_tpu.api import model as model_api
from realhf_tpu.api.data import SequenceSample
from realhf_tpu.base import logging
from realhf_tpu.base.datapack import flat2d
from realhf_tpu.engine import packing
from realhf_tpu.interfaces import common, ppo_functional
from realhf_tpu.models import transformer as T
from realhf_tpu.ops import functional as F
from realhf_tpu.ops.gae import gae_packed_numpy
from realhf_tpu.ops.sampling import GenerationHyperparameters

logger = logging.getLogger("PPOInterface")


def _base_key() -> jax.Array:
    """Deterministic PRNG root: the EXPERIMENT seed when set, else 0.
    (Python hash() is process-salted and must not feed SPMD RNG; the
    per-worker ambient seed must not either -- every member of a
    worker group needs identical sampling keys.)"""
    from realhf_tpu.base import seeding
    try:
        seed = seeding.get_shared_seed()
    except RuntimeError:
        seed = 0
    return jax.random.PRNGKey(seed % (2 ** 31))


def _shifted_loss_mask(prompt_mask: np.ndarray,
                       seqlens: List[int]) -> np.ndarray:
    """Flat l-1 mask per sequence: True where the *predicted* token is
    a non-prompt token (reference ppo_interface.py:330-344)."""
    out, off = [], 0
    for l in seqlens:
        pm = prompt_mask[off:off + l]
        out.append(~pm[1:])
        off += l
    return np.concatenate(out)


def _make_rms(norm_type: str, beta: float, eps: float):
    if norm_type == "exp":
        return ppo_functional.ExponentialRunningMeanStd(beta=beta,
                                                        epsilon=eps)
    if norm_type == "ma":
        return ppo_functional.MovingAverageRunningMeanStd(epsilon=eps)
    raise NotImplementedError(norm_type)


@dataclasses.dataclass
class PPOActorInterface(model_api.ModelInterface):
    n_minibatches: int = 4
    gconfig: GenerationHyperparameters = dataclasses.field(
        default_factory=GenerationHyperparameters)
    kl_ctl: float = 0.1
    discount: float = 1.0
    gae_lambda: float = 1.0
    eps_clip: float = 0.2
    max_reward_clip: float = 20.0
    early_stop_kl: Optional[float] = None
    early_stop_imp_ratio: Optional[float] = None
    adv_norm: bool = True
    use_adaptive_kl_ctl: bool = False
    adaptive_kl_target: float = 6.0
    adaptive_kl_horizon: float = 10000.0
    value_norm: bool = False
    value_norm_type: str = "exp"
    value_norm_beta: float = 0.99995
    value_norm_eps: float = 1e-5
    enable_save: bool = True
    # -- async / off-policy consumption (docs/distributed.md "Async
    # RLHF") ----------------------------------------------------------
    #: drop sequences whose generation weight version lags the
    #: trainer's current version by more than this (the training-side
    #: mirror of ServingSpec.max_staleness); None keeps everything
    max_staleness: Optional[int] = None
    #: truncated importance-sampling bound for STALE sequences: each
    #: stale token's advantage is scaled by
    #: clip(pi_current/pi_behavior, 1/c, c) with the ratio
    #: stop-gradiented (decoupled-PPO style -- the ordinary PPO ratio
    #: still does the proximal clipping on top). None disables the
    #: correction; fresh (staleness 0) sequences are never touched.
    staleness_is_clip: Optional[float] = 2.0
    # -- agentic / multi-turn credit assignment (docs/agentic.md) ------
    #: place reward at each turn's last action token (the
    #: ``dense_rewards`` key packed by agentic trajectories) instead
    #: of at end-of-sequence; GAE then propagates credit across the
    #: masked observation gaps. Default False = existing
    #: end-of-sequence behavior, also used when the batch carries no
    #: ``dense_rewards``.
    turn_level_credit: bool = False

    def __post_init__(self):
        if isinstance(self.gconfig, dict):
            self.gconfig = GenerationHyperparameters(**self.gconfig)
        if self.use_adaptive_kl_ctl:
            self.kl_adapter = ppo_functional.AdaptiveKLController(
                self.kl_ctl, self.adaptive_kl_target, self.adaptive_kl_horizon)
        else:
            self.kl_adapter = ppo_functional.FixedKLController(self.kl_ctl)
        if self.value_norm:
            self.rms = _make_rms(self.value_norm_type, self.value_norm_beta,
                                 self.value_norm_eps)
        self._gen_calls = 0

    # ------------------------------------------------------------------
    def generate(self, model: model_api.Model, input_: SequenceSample,
                 n_mbs: Optional[int] = None) -> SequenceSample:
        engine = model.engine
        tok = model.tokenizer
        prompt_lens = flat2d(input_.seqlens["packed_prompts"])
        flat = input_.data["packed_prompts"]
        prompts, off = [], 0
        for l in prompt_lens:
            prompts.append(np.asarray(flat[off:off + l]))
            off += l

        ids, seg, pos = packing.left_padded_prompts(
            prompts, pad_id=tok.pad_token_id)
        self._gen_calls += 1
        key = jax.random.fold_in(_base_key(), self._gen_calls)
        out = engine.generate(ids, seg, pos, key, self.gconfig,
                              eos_token_id=tok.eos_token_id,
                              pad_token_id=tok.pad_token_id)
        out = out.to_host()  # one bundled D2H round-trip for all fields
        gen_tokens = np.asarray(out.tokens)
        gen_lp = np.asarray(out.logprobs)
        gen_lens = np.asarray(out.lengths)
        no_eos = np.asarray(out.no_eos_mask)
        mask = None
        if out.logits_mask is not None:
            mask = np.asarray(out.logits_mask)  # [B, T, V], True=allowed

        seqlens, in_ids, logprobs, prompt_mask, logits_masks = [], [], [], [], []
        vocab = model.config.vocab_size
        for i, p in enumerate(prompts):
            g = int(gen_lens[i])
            l = len(p) + g
            seqlens.append(l)
            in_ids.append(np.concatenate([p, gen_tokens[i, :g]]))
            lp = np.zeros(l - 1, np.float32)
            lp[len(p) - 1:] = gen_lp[i, :g]
            logprobs.append(lp)
            prompt_mask.append(np.concatenate(
                [np.ones(len(p), bool), np.zeros(g, bool)]))
            if mask is not None:
                # True = masked out (reference convention, genstep:131)
                m = np.zeros((l, vocab), bool)
                m[len(p) - 1:len(p) - 1 + g] = ~mask[i, :g]
                logits_masks.append(m)

        data = dict(
            seq_no_eos_mask=no_eos,
            packed_input_ids=np.concatenate(in_ids).astype(np.int32),
            packed_logprobs=np.concatenate(logprobs).astype(np.float32),
            prompt_mask=np.concatenate(prompt_mask),
        )
        if mask is not None and not self.gconfig.force_no_logits_mask:
            data["packed_logits_mask"] = np.concatenate(logits_masks)
        return SequenceSample.from_default(
            ids=input_.ids, seqlens=seqlens, data=data)

    # ------------------------------------------------------------------
    def inference(self, model: model_api.Model, input_: SequenceSample,
                  n_mbs: Optional[int] = None) -> SequenceSample:
        """Recompute logprobs under this model (used for ref_inf and
        actor_inf MFCs; reference ppo_interface.py:255). ``n_mbs``
        chunks the batch so a ref_inf that does not fit HBM at once
        still runs (reference microbatch contract)."""
        has_mask = ("packed_logits_mask" in input_.keys and
                    input_.data.get("packed_logits_mask") is not None)
        pieces = []
        # split() is contiguous and order-preserving: chunk outputs
        # concatenate back into the input order.
        for chunk in common.split_minibatches(input_, n_mbs or 1):
            seqlens = common.flat_seqlens(chunk)
            sb = common.build_stream_batch(
                seqlens,
                token_keys=dict(input_ids=chunk.data["packed_input_ids"]),
                n_streams=model.engine.n_streams)
            lmask = None
            if has_mask:
                # stored True=masked-out; engine wants True=allowed
                allowed = ~chunk.data["packed_logits_mask"]
                lmask = packing.pack_tokens(sb.info, allowed, fill=True)
            lp = np.asarray(model.engine.forward_logprobs(
                sb.arrays["input_ids"], sb.arrays["seg_ids"],
                temperature=self.gconfig.temperature, logits_mask=lmask))
            pieces.append(packing.unpack_tokens(
                sb.info, lp, seqlens=[l - 1 for l in seqlens]))
        flat_lp = np.concatenate(pieces)
        # Preserve per-element nesting (GRPO groups several sequences
        # inside one batch element).
        nested_m1 = [[l - 1 for l in lens]
                     for lens in input_.seqlens["packed_input_ids"]]
        with SequenceSample.disable_validation():
            return SequenceSample(
                keys=["packed_ref_logprobs"],
                trailing_shapes=dict(packed_ref_logprobs=()),
                dtypes=dict(packed_ref_logprobs=np.float32),
                ids=list(input_.ids),
                seqlens=dict(packed_ref_logprobs=nested_m1),
                data=dict(packed_ref_logprobs=flat_lp.astype(np.float32)),
                metadata={})

    # ------------------------------------------------------------------
    def train_step(self, model: model_api.Model, input_: SequenceSample,
                   n_mbs: Optional[int] = None) -> Dict:
        engine = model.engine
        seqlens = common.flat_seqlens(input_)
        n_seqs = len(seqlens)
        cu = np.concatenate([[0], np.cumsum(seqlens)]).astype(np.int64)
        short1 = cu - np.arange(n_seqs + 1)

        old_logp = np.asarray(input_.data["packed_logprobs"], np.float32)
        ref_logp = np.asarray(input_.data["packed_ref_logprobs"], np.float32)
        prompt_mask = np.asarray(input_.data["prompt_mask"], bool)
        reward_score = np.asarray(input_.data["rewards"], np.float32)
        values = np.asarray(input_.data["values"], np.float32).copy()
        seq_no_eos = np.asarray(input_.data["seq_no_eos_mask"], bool)

        if self.value_norm:
            denorm_values = self.rms.denormalize(values)
        else:
            denorm_values = values.copy()
        # zero the value at EOS of terminated sequences (reference :321)
        ends = cu[1:] - 1
        denorm_values[ends] = np.where(seq_no_eos, denorm_values[ends], 0.0)

        loss_mask = _shifted_loss_mask(prompt_mask, seqlens)

        # -- staleness accounting (docs/distributed.md "Async RLHF"):
        # async rollouts stamp each sample's generation weight_version
        # into metadata; staleness = trainer version - that stamp.
        # Over-stale sequences drop out of the loss entirely; the rest
        # get the clipped-IS correction inside the loss fn below.
        versions = input_.metadata.get("weight_version")
        cur_version = model.version.global_step
        seq_staleness = np.zeros(n_seqs, np.int64)
        if versions:
            seq_staleness = np.array(
                [max(0, cur_version - int(v)) for v in versions],
                np.int64)
        n_dropped = 0
        if versions and self.max_staleness is not None:
            drop = seq_staleness > self.max_staleness
            if drop.any():
                off = 0
                for i, l in enumerate(seqlens):
                    if drop[i]:
                        loss_mask[off:off + l - 1] = False
                    off += l - 1
                n_dropped = int(drop.sum())

        old_logp = old_logp * loss_mask
        ref_logp = ref_logp * loss_mask

        dense = None
        if self.turn_level_credit and "dense_rewards" in input_.keys \
                and input_.data.get("dense_rewards") is not None:
            dense = np.asarray(input_.data["dense_rewards"],
                               np.float32)
        if dense is not None:
            kl_rewards, rewards = \
                ppo_functional.get_packed_dense_rewards(
                    kl_ctl=self.kl_adapter.value,
                    clip_reward_value=self.max_reward_clip,
                    log_probs=old_logp, ref_log_probs=ref_logp,
                    dense_rewards=dense)
        else:
            kl_rewards, rewards = ppo_functional.get_packed_rewards(
                kl_ctl=self.kl_adapter.value,
                clip_reward_value=self.max_reward_clip,
                log_probs=old_logp, ref_log_probs=ref_logp,
                reward_score=reward_score, short1cu_seqlens=short1,
                seq_no_eos_mask=seq_no_eos)
        advantages, returns = gae_packed_numpy(
            rewards, denorm_values, short1, seq_no_eos.astype(np.float32),
            gamma=self.discount, lam=self.gae_lambda)

        if self.value_norm:
            self.rms.update(returns, mask=loss_mask)
        if self.adv_norm:
            m = loss_mask.astype(np.float64)
            denom = max(m.sum(), 1.0)  # every seq dropped as stale
            mean = (advantages * m).sum() / denom
            var = ((advantages - mean) ** 2 * m).sum() / denom
            advantages = ((advantages - mean) /
                          np.sqrt(var + 1e-5)).astype(np.float32) * loss_mask

        n_tokens = int(loss_mask.sum())
        mean_ref_kl = float((kl_rewards * loss_mask).sum())
        self.kl_adapter.update(mean_ref_kl / max(n_tokens, 1),
                               n_steps=n_seqs)

        global_stats = dict(
            task_reward=float(reward_score.mean()),
            kl_reward=mean_ref_kl / max(n_tokens, 1),
            advantage=float(advantages.sum() / max(n_tokens, 1)),
            avg_seq_len=float(np.mean(seqlens)),
            avg_prompt_len=float(prompt_mask.sum() / n_seqs),
            n_tokens=n_tokens,
            n_seqs=n_seqs,
        )
        if versions:
            global_stats.update(
                staleness_mean=float(seq_staleness.mean()),
                staleness_max=int(seq_staleness.max()),
                stale_seq_frac=float((seq_staleness > 0).mean()),
                n_dropped_stale=n_dropped)
        if dense is not None:
            global_stats["dense_reward_sum"] = float(dense.sum())
        if input_.metadata.get("n_turns"):
            global_stats["avg_turns"] = float(
                np.mean(input_.metadata["n_turns"]))

        train_data = dict(
            advantages=advantages,
            old_logp=old_logp,
            ppo_loss_mask=loss_mask,
            packed_input_ids=input_.data["packed_input_ids"],
            kl_rewards=kl_rewards,
        )
        # per-token staleness (shifted, length l-1) rides the
        # minibatch so the clipped-IS correction runs inside the loss
        has_stale = bool(versions) and self.staleness_is_clip is not None
        if has_stale:
            train_data["staleness"] = np.repeat(
                seq_staleness, [l - 1 for l in seqlens]
            ).astype(np.float32)
        has_mask = ("packed_logits_mask" in input_.keys and
                    input_.data.get("packed_logits_mask") is not None)
        if has_mask:
            train_data["packed_logits_mask"] = \
                input_.data["packed_logits_mask"]
        sample = SequenceSample.from_default(
            ids=input_.ids, seqlens=[[l] for l in
                                     common.seqlens_of(input_)],
            data=train_data)

        mbs = common.split_minibatches(sample, self.n_minibatches)
        cfg = model.config
        temperature = self.gconfig.temperature
        eps_clip = self.eps_clip
        early_kl = self.early_stop_kl
        early_imp = self.early_stop_imp_ratio
        is_clip = self.staleness_is_clip

        def loss_fn(params, h, mb):
            lmask = mb.get("logits_mask")
            lp = F.shifted_logprobs_from_hidden(
                cfg, params, h, mb["input_ids"], mb["seg_ids"],
                temperature=temperature, logits_mask=lmask)
            adv = mb["advantages"]
            stale_stats = {}
            if has_stale:
                # staleness-aware truncated IS (decoupled-PPO style):
                # stale tokens' advantages scale by
                # clip(pi_current/pi_behavior, 1/c, c), stop-gradiented
                # so the ordinary PPO ratio still does the proximal
                # clipping; fresh tokens keep weight 1
                behav_ratio = jnp.exp(
                    jax.lax.stop_gradient(lp) - mb["old_logp"])
                w = jnp.where(
                    mb["staleness"] > 0,
                    jnp.clip(behav_ratio, 1.0 / is_clip, is_clip),
                    1.0)
                adv = adv * w
                lm = mb["loss_mask"] > 0
                stale_stats["stale_is_weight"] = (
                    (w * lm).sum() / jnp.maximum(lm.sum(), 1))
            loss, stats = ppo_functional.actor_loss_fn(
                logprobs=lp, old_logprobs=mb["old_logp"],
                advantages=adv, eps_clip=eps_clip,
                loss_mask=mb["loss_mask"] > 0)
            # Early stop SKIPS the whole optimizer update (reference
            # semantics) via the engine's reserved stat -- a zeroed
            # loss would still apply AdamW weight decay and MoE aux
            # gradients.
            skip = jnp.zeros(())
            if early_imp is not None:
                skip = jnp.maximum(
                    skip, (stats["importance_weight"] > early_imp)
                    .astype(jnp.float32))
            if early_kl is not None:
                skip = jnp.maximum(
                    skip, (stats["approx_kl"] > early_kl)
                    .astype(jnp.float32))
            out_stats = dict(
                actor_loss=loss,
                ppo_approx_kl=stats["approx_kl"],
                actor_clip_ratio=stats["clip_ratio"],
                importance_weight=stats["importance_weight"],
                **stale_stats)
            if early_imp is not None or early_kl is not None:
                out_stats["__skip_update__"] = skip
            return loss, out_stats

        loss_key = ("ppo_actor", has_mask, temperature, eps_clip,
                    early_kl, early_imp, has_stale, is_clip)

        def build_sb(minibatch):
            mb_lens = common.flat_seqlens(minibatch)
            shifted = dict(
                advantages=minibatch.data["advantages"],
                old_logp=minibatch.data["old_logp"],
                loss_mask=minibatch.data["ppo_loss_mask"]
                .astype(np.float32))
            if has_stale:
                shifted["staleness"] = minibatch.data["staleness"]
            sb = common.build_stream_batch(
                mb_lens,
                token_keys=dict(
                    input_ids=minibatch.data["packed_input_ids"]),
                shifted_keys=shifted,
                n_streams=engine.n_streams)
            if has_mask:
                sb.arrays["logits_mask"] = packing.pack_tokens(
                    sb.info, ~minibatch.data["packed_logits_mask"],
                    fill=True)
            return sb

        # MFCDef.n_mbs: memory microbatching WITHIN each PPO minibatch
        # -- gradients accumulate over n_mbs scanned microbatches in a
        # single optimizer step; the minibatch loop itself runs fused
        # in one dispatch (common.run_train_minibatches).
        all_stats = common.run_train_minibatches(
            engine, mbs, build_sb, loss_fn, loss_key, n_mbs)
        model.inc_version()

        agg = {k: float(np.mean([s[k] for s in all_stats]))
               for k in all_stats[0]}
        agg.update(global_stats)
        return agg

    def save(self, model: model_api.Model, save_dir: str,
             host_params=None, writer: bool = True):
        if not self.enable_save:
            return
        common.save_checkpoint(model, save_dir, host_params,
                               writer=writer)


@dataclasses.dataclass
class PPOCriticInterface(model_api.ModelInterface):
    n_minibatches: int = 4
    kl_ctl: float = 0.1
    discount: float = 1.0
    gae_lambda: float = 0.95
    value_eps_clip: float = 0.2
    max_reward_clip: float = 20.0
    adaptive_kl_target: float = 6.0
    adaptive_kl_horizon: float = 10000.0
    use_adaptive_kl_ctl: bool = False
    value_norm: bool = False
    value_norm_type: str = "exp"
    value_norm_beta: float = 0.99995
    value_norm_eps: float = 1e-5
    enable_save: bool = True
    #: must match the actor's knob: the critic's regression target is
    #: computed from the same reward placement (docs/agentic.md)
    turn_level_credit: bool = False

    def __post_init__(self):
        if self.use_adaptive_kl_ctl:
            self.kl_adapter = ppo_functional.AdaptiveKLController(
                self.kl_ctl, self.adaptive_kl_target, self.adaptive_kl_horizon)
        else:
            self.kl_adapter = ppo_functional.FixedKLController(self.kl_ctl)
        if self.value_norm:
            self.rms = _make_rms(self.value_norm_type, self.value_norm_beta,
                                 self.value_norm_eps)

    def inference(self, model: model_api.Model, input_: SequenceSample,
                  n_mbs: Optional[int] = None) -> SequenceSample:
        """Produce values for every token (reference
        PPOCriticInterface.inference). ``n_mbs`` chunks the batch for
        HBM headroom."""
        pieces = []
        for chunk in common.split_minibatches(input_, n_mbs or 1):
            seqlens = common.flat_seqlens(chunk)
            sb = common.build_stream_batch(
                seqlens,
                token_keys=dict(input_ids=chunk.data["packed_input_ids"]),
                n_streams=model.engine.n_streams)
            values = np.asarray(model.engine.forward_values(
                sb.arrays["input_ids"], sb.arrays["seg_ids"]))
            pieces.append(packing.unpack_tokens(sb.info, values))
        flat = np.concatenate(pieces)
        return SequenceSample.from_default(
            ids=input_.ids, seqlens=common.flat_seqlens(input_),
            data=dict(values=flat.astype(np.float32)))

    def train_step(self, model: model_api.Model, input_: SequenceSample,
                   n_mbs: Optional[int] = None) -> Dict:
        engine = model.engine
        seqlens = common.flat_seqlens(input_)
        n_seqs = len(seqlens)
        cu = np.concatenate([[0], np.cumsum(seqlens)]).astype(np.int64)
        short1 = cu - np.arange(n_seqs + 1)

        old_logp = np.asarray(input_.data["packed_logprobs"], np.float32)
        ref_logp = np.asarray(input_.data["packed_ref_logprobs"], np.float32)
        prompt_mask = np.asarray(input_.data["prompt_mask"], bool)
        reward_score = np.asarray(input_.data["rewards"], np.float32)
        values = np.asarray(input_.data["values"], np.float32).copy()
        seq_no_eos = np.asarray(input_.data["seq_no_eos_mask"], bool)

        if self.value_norm:
            denorm_values = self.rms.denormalize(values)
        else:
            denorm_values = values.copy()
        ends = cu[1:] - 1
        denorm_values[ends] = np.where(seq_no_eos, denorm_values[ends], 0.0)
        values[ends] = np.where(seq_no_eos, values[ends], 0.0)

        loss_mask = _shifted_loss_mask(prompt_mask, seqlens)
        old_logp = old_logp * loss_mask
        ref_logp = ref_logp * loss_mask

        dense = None
        if self.turn_level_credit and "dense_rewards" in input_.keys \
                and input_.data.get("dense_rewards") is not None:
            dense = np.asarray(input_.data["dense_rewards"],
                               np.float32)
        if dense is not None:
            kl_rewards, rewards = \
                ppo_functional.get_packed_dense_rewards(
                    kl_ctl=self.kl_adapter.value,
                    clip_reward_value=self.max_reward_clip,
                    log_probs=old_logp, ref_log_probs=ref_logp,
                    dense_rewards=dense)
        else:
            kl_rewards, rewards = ppo_functional.get_packed_rewards(
                kl_ctl=self.kl_adapter.value,
                clip_reward_value=self.max_reward_clip,
                log_probs=old_logp, ref_log_probs=ref_logp,
                reward_score=reward_score, short1cu_seqlens=short1,
                seq_no_eos_mask=seq_no_eos)
        # Keep the critic's adaptive KL coefficient in sync with the
        # actor's (reference updates it inside the critic loss too,
        # ppo_interface.py:629).
        n_tokens = max(int(loss_mask.sum()), 1)
        self.kl_adapter.update(float((kl_rewards * loss_mask).sum())
                               / n_tokens, n_steps=n_seqs)
        _, returns = gae_packed_numpy(
            rewards, denorm_values, short1, seq_no_eos.astype(np.float32),
            gamma=self.discount, lam=self.gae_lambda)

        if self.value_norm:
            self.rms.update(returns, mask=loss_mask)
            target = self.rms.normalize(returns)
        else:
            target = returns

        # per-position old values: values[t] for t in 0..l-2 (flat l-1)
        old_values_short = np.concatenate(
            [values[cu[i]:cu[i + 1] - 1] for i in range(n_seqs)])

        sample = SequenceSample.from_default(
            ids=input_.ids,
            seqlens=[[l] for l in common.seqlens_of(input_)],
            data=dict(
                packed_input_ids=input_.data["packed_input_ids"],
                returns=target.astype(np.float32),
                # note: "values"-style keys resolve to length l; these
                # are l-1, so reuse minus-1 key names
                old_logp=old_values_short.astype(np.float32),
                ppo_loss_mask=loss_mask,
            ))
        mbs = common.split_minibatches(sample, self.n_minibatches)

        cfg = model.config
        eps = self.value_eps_clip

        def loss_fn(params, h, mb):
            new_values = T.critic_values(cfg, params, h)
            loss, stats = ppo_functional.critic_loss_fn(
                value=new_values, old_value=mb["old_values"],
                target_value=mb["returns"], value_eps_clip=eps,
                loss_mask=mb["loss_mask"] > 0)
            return loss, dict(
                value_loss=loss,
                value_clip_ratio=stats["value_clip_ratio"])

        def build_sb(minibatch):
            mb_lens = common.flat_seqlens(minibatch)
            return common.build_stream_batch(
                mb_lens,
                token_keys=dict(
                    input_ids=minibatch.data["packed_input_ids"]),
                shifted_keys=dict(
                    returns=minibatch.data["returns"],
                    old_values=minibatch.data["old_logp"],
                    loss_mask=minibatch.data["ppo_loss_mask"]
                    .astype(np.float32)),
                n_streams=engine.n_streams)

        all_stats = common.run_train_minibatches(
            engine, mbs, build_sb, loss_fn, ("ppo_critic", eps), n_mbs)
        model.inc_version()

        agg = {k: float(np.mean([s[k] for s in all_stats]))
               for k in all_stats[0]}
        agg["returns"] = float(returns.mean())
        return agg

    def save(self, model: model_api.Model, save_dir: str,
             host_params=None, writer: bool = True):
        if not self.enable_save:
            return
        common.save_checkpoint(model, save_dir, host_params,
                               writer=writer)


model_api.register_interface("ppo_actor", PPOActorInterface)
model_api.register_interface("ppo_critic", PPOCriticInterface)
