"""Shared tensor ops over packed streams.

Parity with reference ``realhf/impl/model/utils/functional.py``:
next-token logprob gathering (:165), masked normalization (:227),
logits masking (:214) -- expressed on the framework's [S, L] packed
stream layout. The vocab-parallel cross entropy of the reference
(``modules.py:1050``) is unnecessary: the head matmul, the
log-sum-exp and the label's select under GSPMD shard the vocab dim and
XLA inserts the reductions (all-reduces of one number a position).
"""

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.models.transformer import head_weight
from realhf_tpu.obs import parts

#: ``jax.named_scope`` of the head (``obs/parts.py``): in the
#: ``op_name`` of every operation it lowers to, forward and transposed
#: (a trace's events carry no scope: ``Engine.program_facts`` reads it
#: from the program's compiled text)
HEAD_SCOPE = parts.VOCAB_HEAD


def _chunk_logits(cfg, w, hc, temperature):
    """One chunk's float32 logits [S, C, V], the tp-padded vocabulary's
    tail sliced away, over ``temperature``."""
    logits = jnp.einsum("slh,hv->slv", hc, w,
                        preferred_element_type=jnp.float32)
    if logits.shape[-1] != cfg.vocab_size:  # tp-padded vocab
        logits = logits[..., :cfg.vocab_size]
    if temperature != 1.0:
        logits = logits / temperature
    return logits


def _shift_and_sum_exp(logits):
    """``z = logits - max`` and ``sum(exp(z))`` over the vocabulary:
    ``log_softmax`` is ``z - log(sum)``, which no caller here needs
    whole (its operations in its order, the max held constant)."""
    z = logits - jax.lax.stop_gradient(logits.max(-1, keepdims=True))
    return z, jnp.exp(z).sum(-1)


def _label_logprob(z, s_exp, lc):
    """Where the labels ``lc`` [S, C] stand in ``z`` [S, C, V] (bool)
    and their log-probabilities [S, C]. The label's entry by a select,
    not a gather: a gather makes the compiler write the whole
    log-softmax for it to read, and its transpose scatters a one-hot of
    the chunk's size and copies it into the logits' layout."""
    hit = (jax.lax.broadcasted_iota(lc.dtype, z.shape, 2)
           == lc[..., None])
    return hit, jnp.where(hit, z, 0.0).sum(-1) - jnp.log(s_exp)


def _next_tokens(input_ids, seg_ids):
    """Position t's label (token t+1) and whether it has one: t+1 lies
    in the same document and is no padding. Both [S, L]."""
    s = input_ids.shape[0]
    labels = jnp.concatenate(
        [input_ids[:, 1:], jnp.zeros((s, 1), input_ids.dtype)], axis=1)
    valid = jnp.concatenate(
        [(seg_ids[:, 1:] == seg_ids[:, :-1]) & (seg_ids[:, 1:] != 0),
         jnp.zeros((s, 1), bool)], axis=1)
    return labels, valid


def shifted_logprobs_from_hidden(
    cfg: TransformerConfig,
    params,
    hidden: jnp.ndarray,      # [S, L, H] final hidden states
    input_ids: jnp.ndarray,   # [S, L]
    seg_ids: jnp.ndarray,     # [S, L]
    *,
    chunk: int = 1024,
    temperature: float = 1.0,
    logits_mask: Optional[jnp.ndarray] = None,  # [S, L, V] bool, True=allowed
) -> jnp.ndarray:
    """Log p(input_ids[t+1] | ...) at every position t, zero where t+1
    starts a different segment or is padding.

    Computed in chunks along L so the full [S, L, V] logits tensor is
    never materialized (the fused-CE trick; reference gathers shifted
    logprobs after a full logits pass, functional.py:165). The chunk
    body is rematerialized under autodiff: without that the backward
    pass keeps every chunk's fp32 logits as scan residuals, which IS
    the [S, L, V] tensor (2.5 GB and more for a 4096-token row at
    V = 151936, asked of the v5e compiler).

    Returns [S, L] fp32; position t holds the logprob of token t+1.
    The last position of each segment (and pads) hold 0.
    """
    with jax.named_scope(HEAD_SCOPE):
        return _shifted_logprobs(cfg, params, hidden, input_ids, seg_ids,
                                 chunk, temperature, logits_mask)


def _shifted_logprobs(cfg, params, hidden, input_ids, seg_ids, chunk,
                      temperature, logits_mask):
    s, l, h = hidden.shape
    w = head_weight(cfg, params).astype(hidden.dtype)

    labels, valid = _next_tokens(input_ids, seg_ids)

    n_chunks = max(1, (l + chunk - 1) // chunk)
    pad_l = n_chunks * chunk - l
    if pad_l:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad_l), (0, 0)))
        labels = jnp.pad(labels, ((0, 0), (0, pad_l)))
        if logits_mask is not None:
            logits_mask = jnp.pad(logits_mask, ((0, 0), (0, pad_l), (0, 0)),
                                  constant_values=True)

    hidden_c = hidden.reshape(s, n_chunks, chunk, h).swapaxes(0, 1)
    labels_c = labels.reshape(s, n_chunks, chunk).swapaxes(0, 1)
    if logits_mask is not None:
        mask_c = logits_mask.reshape(s, n_chunks, chunk, -1).swapaxes(0, 1)
        xs = (hidden_c, labels_c, mask_c)
    else:
        xs = (hidden_c, labels_c)

    def body(_, x):
        if logits_mask is not None:
            hc, lc, mc = x
        else:
            hc, lc = x
            mc = None
        logits = _chunk_logits(cfg, w, hc, temperature)
        if mc is not None:
            logits = jnp.where(mc, logits, -1e30)
        z, s_exp = _shift_and_sum_exp(logits)
        return None, _label_logprob(z, s_exp, lc)[1]

    _, lp = jax.lax.scan(jax.checkpoint(body), None, xs)
    lp = lp.swapaxes(0, 1).reshape(s, n_chunks * chunk)[:, :l]
    return jnp.where(valid, lp, 0.0)


def weighted_logprob_sum(
    cfg: TransformerConfig,
    params,
    hidden: jnp.ndarray,      # [S, L, H], or [T, S, L, H]: T passes' states
    input_ids: jnp.ndarray,   # [S, L]
    seg_ids: jnp.ndarray,     # [S, L]
    c: jnp.ndarray,           # [S, L] or [T, S, L] float32 weights
    *,
    chunk: int = 1024,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """``sum_i c_i lp_i`` (a float32 scalar) and, as an output that
    carries no gradient, ``lp`` itself, float32 in ``c``'s shape: what
    :func:`shifted_logprobs_from_hidden` returns (of every pass of a
    looped model, where ``hidden`` and ``c`` have a leading axis of
    passes).

    The head of a loss that IS a weighted sum of log-probabilities,
    its weights known before the head runs (SFT: ``mask / denom``; a
    looped model's: times the exit distribution). Its cotangent
    ``c (onehot - softmax)`` is then known in the chunk that has the
    logits, so the forward rule of the ``custom_vjp`` forms it there
    and runs the two gradient products at once: three products a chunk
    where :func:`shifted_logprobs_from_hidden` under ``jax.grad`` runs
    four (forward, the rematerialised forward, two transposed), and a
    chunk's float32 logits are still never kept. The chunks of all
    passes go through ONE scan, one pass after the other: what is alive
    at a time is ONE chunk's logits of ONE pass, and the head's
    gradient is summed over T x chunks bodies in the weight's dtype, as
    that function's scan transposes sum it. A loss that needs ``lp``
    itself (a clipped ratio, a KL term, a sequence's log-sigmoid)
    calls that function. A call nobody differentiates runs no gradient
    product."""
    with jax.named_scope(HEAD_SCOPE):
        w = head_weight(cfg, params).astype(hidden.dtype)
        labels, valid = _next_tokens(input_ids, seg_ids)
        lead, (s, l) = hidden.shape[:-3], hidden.shape[-3:-1]
        n_chunks = max(1, (l + chunk - 1) // chunk)

        def chunks(x):  # lead + [S, L, ...] -> [passes * n_chunks, S, chunk, ...]
            x = x.reshape((-1,) + x.shape[len(lead):])
            x = jnp.pad(x, ((0, 0), (0, 0), (0, n_chunks * chunk - l))
                        + ((0, 0),) * (x.ndim - 3))
            # (reshape, then swap the rows behind the chunks, as
            # _shifted_logprobs does: XLA:CPU at optimization level 0,
            # the tests' flag, miscompiles [P, S, n, C] -> [P, n, S, C]
            # of a padded bf16 row)
            x = x.swapaxes(0, 1)  # [S, passes, L, ...]
            return x.reshape((s, -1, chunk) + x.shape[3:]).swapaxes(0, 1)

        total, lp = _weighted_sum(
            cfg, w, chunks(hidden), chunks(jnp.where(valid, c, 0.0)),
            chunks(jnp.broadcast_to(labels, lead + labels.shape)))
        lp = lp.swapaxes(0, 1).reshape((s, -1, n_chunks * chunk))
        lp = lp.swapaxes(0, 1)[..., :l].reshape(lead + (s, l))
        return total, jax.lax.stop_gradient(jnp.where(valid, lp, 0.0))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _weighted_sum(cfg, w, hidden_c, c_c, labels_c):
    """``(sum(c_c * lp), lp [N, S, C])`` over chunks ``hidden_c`` [N, S,
    C, H]. The primal: today's forward, no gradient product."""
    def body(_, x):
        hc, lc = x
        z, s_exp = _shift_and_sum_exp(_chunk_logits(cfg, w, hc, 1.0))
        return None, _label_logprob(z, s_exp, lc)[1]

    _, lp = jax.lax.scan(body, None, (hidden_c, labels_c))
    return (c_c * lp).sum(), lp


def _weighted_sum_fwd(cfg, w, hidden_c, c_c, labels_c):
    """The chunk that has the logits forms ``dlogits`` and runs both
    gradient products: ``dh`` a chunk is the scan's output, ``dW`` its
    carry. Operands as the transposed program of
    :func:`_shifted_logprobs` has them (read off its compiled text):
    float32 ``dlogits`` against the bf16 weight and states, float32
    out of the product, then the operand's dtype."""
    def body(dw, x):
        hc, cc, lc = x
        z, s_exp = _shift_and_sum_exp(_chunk_logits(cfg, w, hc, 1.0))
        hit, lp = _label_logprob(z, s_exp, lc)
        with jax.named_scope(parts.GRADIENT):
            dlogits = (jnp.where(hit, cc[..., None], 0.0)
                       - (cc / s_exp)[..., None] * jnp.exp(z))
            if dlogits.shape[-1] != w.shape[-1]:  # tp-padded vocab
                dlogits = jnp.pad(dlogits, ((0, 0), (0, 0), (
                    0, w.shape[-1] - dlogits.shape[-1])))
            dh = jax.lax.dot_general(
                dlogits, w, (((2,), (1,)), ((), ())),
                preferred_element_type=jnp.float32).astype(hc.dtype)
            dw_c = jax.lax.dot_general(
                hc, dlogits, (((0, 1), (0, 1)), ((), ())),
                preferred_element_type=jnp.float32).astype(w.dtype)
        return dw + dw_c, (lp, dh)

    dw, (lp, dh) = jax.lax.scan(body, jnp.zeros_like(w),
                                (hidden_c, c_c, labels_c))
    return ((c_c * lp).sum(), lp), (dw, dh, lp)


def _weighted_sum_bwd(cfg, residuals, cotangents):
    """Three scalings by the sum's cotangent ``g``; ``lp``'s own is
    dropped, the output carries no gradient. The SFT losses hand in
    weights that carry the loss's sign, so their ``g`` is the constant
    1 and the compiler drops the scalings (``dW``'s is a pass over the
    whole matrix)."""
    g = cotangents[0]
    dw, dh, lp = ((g * x).astype(x.dtype) for x in residuals)
    return dw, dh, lp, None


_weighted_sum.defvjp(_weighted_sum_fwd, _weighted_sum_bwd)


def exit_log_distribution(gate_logits: jnp.ndarray) -> jnp.ndarray:
    """log p_t [T, ...] of a looped model's exit distribution from its
    gate's logits [T, ...] (float32; ``lambda_t = sigmoid``):
    ``p_t = lambda_t prod_{j<t} (1 - lambda_j)`` for t < T and
    ``p_T = prod_{j<T} (1 - lambda_j)``: the last pass takes what is
    left, whatever its own gate says, so the p_t sum to 1. In logs:
    ``log_sigmoid`` has no overflow at either end. Part ``exit``."""
    with jax.named_scope(parts.EXIT):
        g = gate_logits.astype(jnp.float32)
        stay = jnp.cumsum(jax.nn.log_sigmoid(-g), axis=0)  # log prod_{j<=t}
        before = jnp.concatenate([jnp.zeros_like(stay[:1]), stay[:-1]])
        leave = jax.nn.log_sigmoid(g).at[-1].set(0.0)
        return before + leave


def masked_normalization(
    x: jnp.ndarray,
    mask: Optional[jnp.ndarray] = None,
    *,
    unbiased: bool = False,
    eps: float = 1e-5,
    high_precision: bool = True,
) -> jnp.ndarray:
    """Normalize x to zero mean / unit std over masked entries.

    Under pjit the arrays are global, so the "all-reduce over DP+TP"
    of the reference (functional.py:227) is implicit.
    """
    dtype = jnp.float64 if (high_precision and
                            jax.config.read("jax_enable_x64")) else jnp.float32
    xf = x.astype(dtype)
    if mask is None:
        factor = jnp.asarray(x.size, dtype)
        mean = xf.sum() / factor
        mean_sq = (xf ** 2).sum() / factor
    else:
        m = mask.astype(dtype)
        factor = m.sum()
        mean = (xf * m).sum() / factor
        mean_sq = (xf ** 2 * m).sum() / factor
    var = mean_sq - mean ** 2
    if unbiased:
        var = var * factor / (factor - 1)
    out = (xf - mean) * jax.lax.rsqrt(var + eps)
    if mask is not None:
        out = out * m
    return out.astype(x.dtype)


def masked_mean(x: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    m = mask.astype(jnp.float32)
    return (x.astype(jnp.float32) * m).sum() / jnp.maximum(m.sum(), 1.0)


def entropy_from_hidden(cfg, params, hidden, *, chunk: int = 1024,
                        temperature: float = 1.0) -> jnp.ndarray:
    """Per-position policy entropy, chunked like shifted logprobs."""
    with jax.named_scope(HEAD_SCOPE):
        return _entropy(cfg, params, hidden, chunk, temperature)


def _entropy(cfg, params, hidden, chunk, temperature):
    s, l, h = hidden.shape
    w = head_weight(cfg, params).astype(hidden.dtype)
    n_chunks = max(1, (l + chunk - 1) // chunk)
    pad_l = n_chunks * chunk - l
    if pad_l:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad_l), (0, 0)))
    hidden_c = hidden.reshape(s, n_chunks, chunk, h).swapaxes(0, 1)

    def body(_, hc):
        z, s_exp = _shift_and_sum_exp(
            _chunk_logits(cfg, w, hc, temperature))
        return None, jnp.log(s_exp) - (jnp.exp(z) * z).sum(-1) / s_exp

    _, ent = jax.lax.scan(body, None, hidden_c)
    return ent.swapaxes(0, 1).reshape(s, n_chunks * chunk)[:, :l]
