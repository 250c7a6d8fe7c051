"""Jitted autoregressive generation with KV cache + sampling.

TPU-native replacement for reference ``realhf/impl/model/nn/
real_llm_generate.py`` (generate:252) and its CUDA-graph decode
(cuda_graph.py): prefill + a `lax.scan` decode loop compiled once per
(batch, prompt-bucket, max_new_tokens) shape -- the XLA executable IS
the captured graph. Supports temperature / top-k / top-p, greedy,
min/max new tokens, EOS+pad handling, per-step sampled logprobs, and
the logits-mask output PPO replays later (genstep:131-136).
"""

import dataclasses
import functools
from typing import Optional

import jax
import jax.numpy as jnp

from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.obs import parts
from realhf_tpu.ops.sampling import (
    NEG_INF,
    GenerationHyperparameters,
    top_k_top_p_logits,
)

# Test hook: force the fixed-trip-count scan driver even when EOS
# early exit applies (parity tests compare the two paths).
_DISABLE_EARLY_EXIT = False


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class GenerationOutput:
    """Results in [B, max_new_tokens] layout; `lengths` counts the
    generated tokens per stream (including the EOS if emitted)."""
    tokens: jnp.ndarray          # int32 [B, T], pad_id beyond lengths
    logprobs: jnp.ndarray        # fp32 [B, T] of the sampled tokens
    logits_mask: Optional[jnp.ndarray]  # bool [B, T, V], True = allowed
    lengths: jnp.ndarray         # int32 [B]
    no_eos_mask: jnp.ndarray     # bool [B]: True if never emitted EOS

    def to_host(self) -> "GenerationOutput":
        """All fields as host numpy via ONE bundled ``jax.device_get``.
        Field-by-field ``np.asarray`` costs one blocking device sync
        per field. The class is a registered pytree, so device_get
        covers every field (including ones added later) and a None
        logits_mask passes through."""
        return jax.device_get(self)


def generate(
    cfg: TransformerConfig,
    params,
    prompt_ids: jnp.ndarray,   # [B, Lp] left-padded
    prompt_seg: jnp.ndarray,   # [B, Lp] 1 over content, 0 over pads
    prompt_pos: jnp.ndarray,   # [B, Lp]
    key: jax.Array,
    gconfig: GenerationHyperparameters,
    *,
    eos_token_id: Optional[int],
    pad_token_id: int,
    activation_constraint=None,
    moe_constraint=None,
    mesh=None,  # partitions the decode and delta kernels on dp x tp meshes
    attention_fn=None,  # sharded prefill attention on dp x tp meshes
) -> GenerationOutput:
    """Functional generation; wrap in jax.jit with gconfig/eos/pad
    static. See `build_generate_fn` for the cached jitted wrapper."""
    b, lp = prompt_ids.shape

    with jax.named_scope(parts.PREFILL):
        prompt_lens = (prompt_seg != 0).sum(-1).astype(jnp.int32)
        hidden, cache = T.prefill(
            cfg, params, prompt_ids, prompt_seg, prompt_pos,
            total_len=lp + gconfig.max_new_tokens,
            activation_constraint=activation_constraint,
            attention_fn=attention_fn, moe_constraint=moe_constraint,
            mesh=mesh)
        # left padding => last column is last token
        last_hidden = hidden[:, -1]

    def sample_step(logits, step_idx, unfinished, k):
        logits = logits.astype(jnp.float32)
        eos_suppress = None
        if eos_token_id is not None and gconfig.min_new_tokens > 0:
            eos_suppress = (
                (step_idx < gconfig.min_new_tokens)
                & (jnp.arange(logits.shape[-1])[None, :] == eos_token_id))
            logits = jnp.where(eos_suppress, NEG_INF, logits)
        if gconfig.greedy:
            warped = logits
            tokens = jnp.argmax(warped, -1).astype(jnp.int32)
        else:
            warped = top_k_top_p_logits(logits / gconfig.temperature,
                                        gconfig.top_k, gconfig.top_p)
            if eos_suppress is not None:
                # Re-pin after temperature scaling so the mask threshold
                # below classifies the suppressed EOS as disallowed.
                warped = jnp.where(eos_suppress, NEG_INF, warped)
            tokens = jax.random.categorical(k, warped, -1).astype(jnp.int32)
        logp = jax.nn.log_softmax(warped, -1)
        logprob = jnp.take_along_axis(logp, tokens[:, None], -1)[:, 0]
        mask = warped > NEG_INF / 2
        tokens = jnp.where(unfinished, tokens, pad_token_id)
        if eos_token_id is not None:
            unfinished = unfinished & (tokens != eos_token_id)
        return tokens, logprob, mask, unfinished

    t_max = gconfig.max_new_tokens
    with jax.named_scope(parts.SAMPLE):
        keys = jax.random.split(key, t_max)

    def step_once(last_hidden, cache, unfinished, emitted, step_idx, k):
        """One decode step, shared by the scan and while-loop drivers."""
        was_unfinished = unfinished
        with jax.named_scope(parts.DECODE):  # the vocabulary head
            logits = T.lm_logits(cfg, params, last_hidden)
        with jax.named_scope(parts.SAMPLE):
            tokens, logprob, mask, unfinished = sample_step(
                logits, step_idx, unfinished, k)
            emitted = emitted + was_unfinished.astype(jnp.int32)
            pos = prompt_lens + step_idx
        # all streams share the padded prompt length, so cache writes
        # land in one uniform slot (dynamic_update_slice fast path)
        with jax.named_scope(parts.DECODE):
            new_hidden, cache = T.decode_step(
                cfg, params, cache, tokens, pos, moe_constraint,
                uniform_slot=True, mesh=mesh)
        return new_hidden, cache, unfinished, emitted, tokens, logprob, mask

    want_mask = not gconfig.force_no_logits_mask
    early_exit = (not _DISABLE_EARLY_EXIT
                  and eos_token_id is not None
                  and gconfig.min_new_tokens < t_max)
    if early_exit:
        # EOS can end every stream before t_max: a while_loop stops
        # decoding the moment no stream is unfinished, writing into
        # preallocated output buffers. The reference terminates its
        # genstep loop the same way (real_llm_generate.py genstep
        # terminate check); lax.scan cannot early-exit.
        with jax.named_scope(parts.SAMPLE):  # what sampling writes into
            tokens_buf = jnp.full((b, t_max), pad_token_id, jnp.int32)
            logp_buf = jnp.zeros((b, t_max), jnp.float32)
            mask_buf = (jnp.zeros((b, t_max, cfg.vocab_size), bool)
                        if want_mask else jnp.zeros((1,), bool))

        def w_cond(c):
            step = c[0]
            unfinished = c[3]
            return (step < t_max) & jnp.any(unfinished)

        def w_body(c):
            step, last_hidden, cache, unfinished, emitted, bufs = c
            tb, lb, mb = bufs
            last_hidden, cache, unfinished, emitted, tok, lp, mask = \
                step_once(last_hidden, cache, unfinished, emitted,
                          step, keys[step])
            with jax.named_scope(parts.SAMPLE):
                tb = jax.lax.dynamic_update_slice(tb, tok[:, None],
                                                  (0, step))
                lb = jax.lax.dynamic_update_slice(lb, lp[:, None],
                                                  (0, step))
                if want_mask:
                    mb = jax.lax.dynamic_update_slice(
                        mb, mask[:, None, :], (0, step, 0))
            return (step + 1, last_hidden, cache, unfinished, emitted,
                    (tb, lb, mb))

        init = (jnp.int32(0), last_hidden, cache, jnp.ones((b,), bool),
                jnp.zeros((b,), jnp.int32),
                (tokens_buf, logp_buf, mask_buf))
        (_, _, _, unfinished, emitted,
         (tokens, logprobs, logits_mask)) = jax.lax.while_loop(
             w_cond, w_body, init)
        if not want_mask:
            logits_mask = None
    else:
        def body(carry, x):
            last_hidden, cache, unfinished, emitted = carry
            step_idx, k = x
            last_hidden, cache, unfinished, emitted, tok, lp, mask = \
                step_once(last_hidden, cache, unfinished, emitted,
                          step_idx, k)
            out = (tok, lp, mask) if want_mask else (tok, lp)
            return (last_hidden, cache, unfinished, emitted), out

        init = (last_hidden, cache, jnp.ones((b,), bool),
                jnp.zeros((b,), jnp.int32))
        (_, _, unfinished, emitted), outs = jax.lax.scan(
            body, init, (jnp.arange(t_max), keys))
        if want_mask:
            tokens, logprobs, logits_mask = outs
            logits_mask = logits_mask.swapaxes(0, 1)  # [B, T, V]
        else:
            tokens, logprobs = outs
            logits_mask = None
        tokens = tokens.T  # [B, T]
        logprobs = logprobs.T
    return GenerationOutput(
        tokens=tokens,
        logprobs=logprobs,
        logits_mask=logits_mask,
        lengths=emitted,
        no_eos_mask=unfinished,
    )


def build_generate_fn(cfg: TransformerConfig,
                      gconfig: GenerationHyperparameters,
                      eos_token_id: Optional[int], pad_token_id: int,
                      activation_constraint=None, moe_constraint=None,
                      out_sharding=None, mesh=None, attention_fn=None):
    """Jitted generate closure; XLA caches compilations per
    batch/bucket shape. Engines build this once and reuse it."""
    fn = functools.partial(generate, cfg, gconfig=gconfig,
                           eos_token_id=eos_token_id,
                           pad_token_id=pad_token_id,
                           activation_constraint=activation_constraint,
                           moe_constraint=moe_constraint,
                           mesh=mesh, attention_fn=attention_fn)

    def run(params, prompt_ids, prompt_seg, prompt_pos, key):
        return fn(params, prompt_ids, prompt_seg, prompt_pos, key)

    # XLA names a module for its function: jit_generate in a trace
    run.__name__ = "generate"

    # out_sharding: replicated outputs on multi-process meshes so every
    # worker-group member can read the generated tokens.
    return jax.jit(run, out_shardings=out_sharding)
