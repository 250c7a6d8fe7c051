"""Mixture-of-experts layer: routing, dispatch, expert GEMMs, losses.

TPU-native replacement for reference ``realhf/impl/model/modules/moe/``
(TopKRouter router.py:24, MoETokenDispatcher token_dispatcher.py:17,
GroupedMLP experts.py:98) and ``impl/model/utils/moe.py`` (aux losses
:13-166). Instead of permute/unpermute + grouped GEMM, dispatch is
expressed as dense one-hot einsums over a static expert-capacity axis
(XLA-friendly static shapes); expert GEMMs are one batched einsum over
the stacked [E, H, F] weights, which GSPMD shards over the "model"
axis (TP-sharded experts, the reference's layout) and can shard over
an expert axis for true EP.

Three dispatch modes:
- ``capacity_factor=None`` + ``use_grouped_gemm`` (default): RAGGED
  mode -- (token, k) pairs sorted by expert feed
  ``jax.lax.ragged_dot`` grouped GEMMs (the true grouped-GEMM
  equivalent of reference experts.py:98 GroupedMLP, lowered to TPU
  ragged matmuls). Exact (no token dropping), top-k cost only.
- ``capacity_factor=None`` + ``use_grouped_gemm=False``: dense mode --
  every expert sees every token, weighted by its gate (exact; E/topk
  times the FLOPs; the correctness reference for tests).
- ``capacity_factor=c``: capacity dispatch -- each expert processes at
  most c * T * topk / E tokens; overflow tokens are dropped from that
  expert (standard Switch/GShard semantics, reference
  topk_softmax_with_capacity, utils/moe.py:310).
"""

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from realhf_tpu.models.config import MoEConfig, TransformerConfig


def router_probs(cfg_moe: MoEConfig, logits: jnp.ndarray,
                 key: Optional[jax.Array] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[T, E] logits -> (top-k probs [T, k], indices [T, k]).

    Default (aux_loss/none): softmax over all experts, take top-k,
    renormalize (Mixtral semantics, equivalent to the reference's
    topk_softmax_with_capacity). Sinkhorn routing selects indices from
    the sinkhorn-normalized logits WITHOUT gradient, while gate values
    come from the raw logits (sigmoid for k=1, softmax for k>1) --
    matching reference router.py:53-76.
    """
    logits = logits.astype(jnp.float32)
    if cfg_moe.input_jitter_eps and key is not None:
        noise = jax.random.uniform(
            key, logits.shape, minval=1.0 - cfg_moe.input_jitter_eps,
            maxval=1.0 + cfg_moe.input_jitter_eps)
        logits = logits * noise
    if cfg_moe.routing_type == "sinkhorn":
        routed = sinkhorn(jax.lax.stop_gradient(logits))
        _, top_idx = jax.lax.top_k(routed, cfg_moe.top_k)
        if cfg_moe.top_k == 1:
            top_probs = jax.nn.sigmoid(
                jnp.take_along_axis(logits, top_idx, axis=-1))
        else:
            sel = jnp.take_along_axis(logits, top_idx, axis=-1)
            top_probs = jax.nn.softmax(sel, axis=-1)
        return top_probs, top_idx
    probs = jax.nn.softmax(logits, axis=-1)
    top_probs, top_idx = jax.lax.top_k(probs, cfg_moe.top_k)
    top_probs = top_probs / jnp.maximum(
        top_probs.sum(-1, keepdims=True), 1e-9)
    return top_probs, top_idx


def sinkhorn(logits: jnp.ndarray, n_iters: int = 8,
             tol: float = 1e-4) -> jnp.ndarray:
    """Sinkhorn normalization of routing logits (reference
    utils/moe.py:69), fixed iteration count for jit."""
    cost = jnp.exp(logits)
    d0 = jnp.ones(cost.shape[0], jnp.float32)
    d1 = jnp.ones(cost.shape[1], jnp.float32)

    def body(_, carry):
        d0, d1 = carry
        d0 = 1.0 / (cost.shape[0] * (cost @ d1.reshape(-1, 1))[:, 0] + 1e-8)
        d1 = 1.0 / (cost.shape[1] * (d0 @ cost) + 1e-8)
        return d0, d1

    d0, d1 = jax.lax.fori_loop(0, n_iters, body, (d0, d1))
    return jnp.log(d1[None, :] * cost * d0[:, None] + 1e-20)


def load_balancing_loss(probs: jnp.ndarray, top_idx: jnp.ndarray,
                        n_experts: int, top_k: int,
                        valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Switch-transformer aux loss (reference
    switch_load_balancing_loss_func, utils/moe.py:13), over valid
    tokens only."""
    t = probs.shape[0]
    if valid is None:
        valid = jnp.ones((t,), jnp.float32)
    n = jnp.maximum(valid.sum(), 1.0)
    counts = jnp.zeros(n_experts, jnp.float32).at[top_idx.reshape(-1)].add(
        jnp.repeat(valid, top_idx.shape[1]))
    fraction_tokens = counts / (n * top_k)
    fraction_probs = (probs * valid[:, None]).sum(axis=0) / n
    return n_experts * (fraction_tokens * fraction_probs).sum()


def z_loss(logits: jnp.ndarray,
           valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Router z-loss (reference z_loss_func, utils/moe.py:54)."""
    z = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1) ** 2
    if valid is None:
        return z.mean()
    return (z * valid).sum() / jnp.maximum(valid.sum(), 1.0)


def _expert_ffn(cfg: TransformerConfig, m: Dict, xs: jnp.ndarray
                ) -> jnp.ndarray:
    """Batched expert MLP: xs [E, C, H] -> [E, C, H] through stacked
    [E, H, F] weights (one einsum per projection = the grouped GEMM)."""
    from realhf_tpu.models.transformer import _activation
    cdt = xs.dtype
    gate = jnp.einsum("ech,ehf->ecf", xs, m["wg"].astype(cdt))
    up = jnp.einsum("ech,ehf->ecf", xs, m["wu"].astype(cdt))
    return jnp.einsum("ecf,efh->ech", _activation(cfg, gate) * up,
                      m["wd"].astype(cdt))


def ragged_dispatch_enabled(cfg: TransformerConfig) -> bool:
    """Single source of truth for whether the grouped-GEMM (ragged)
    dispatch path is active for this config."""
    return (cfg.mlp_type == "moe" and cfg.moe is not None
            and cfg.moe.capacity_factor is None
            and cfg.moe.use_grouped_gemm)


def _ragged_moe(cfg: TransformerConfig, m: Dict, xt: jnp.ndarray,
                top_probs: jnp.ndarray, top_idx: jnp.ndarray
                ) -> jnp.ndarray:
    """Grouped-GEMM dispatch: sort (token, k) pairs by expert, run
    ``jax.lax.ragged_dot`` per projection over the stacked [E, H, F]
    weights, scatter-add gate-weighted outputs back. Exact top-k MoE
    (reference GroupedMLP, experts.py:98) with static shapes."""
    from realhf_tpu.models.transformer import _activation
    t, h = xt.shape
    e = cfg.moe.num_experts
    k = cfg.moe.top_k
    cdt = xt.dtype

    flat_expert = top_idx.reshape(-1)                 # [T*k]
    order = jnp.argsort(flat_expert)                  # sort by expert
    tok_idx = order // k
    xs = xt[tok_idx]                                  # [T*k, H] sorted
    group_sizes = jnp.bincount(flat_expert, length=e).astype(jnp.int32)

    gate = jax.lax.ragged_dot(xs, m["wg"].astype(cdt), group_sizes)
    up = jax.lax.ragged_dot(xs, m["wu"].astype(cdt), group_sizes)
    down = jax.lax.ragged_dot(_activation(cfg, gate) * up,
                              m["wd"].astype(cdt), group_sizes)
    gates_sorted = top_probs.reshape(-1)[order]       # pads carry 0
    weighted = down.astype(jnp.float32) * gates_sorted[:, None]
    return jnp.zeros((t, h), jnp.float32).at[tok_idx].add(weighted)


def moe_mlp_with_losses(cfg: TransformerConfig, m: Dict, x: jnp.ndarray,
                        rng: Optional[jax.Array] = None,
                        valid_mask: Optional[jnp.ndarray] = None,
                        ep_constraint=None
                        ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """MoE feed-forward over [B, L, H]; ``valid_mask`` [B, L] excludes
    padding tokens from routing, expert capacity, and the aux losses
    (pad positions carry real hidden states in the packed layout).

    ``ep_constraint`` (models/sharding.py moe_ep_constraint) pins the
    expert-major intermediates to the expert-parallel axis so GSPMD
    lowers dispatch/combine to all-to-alls; requires the capacity or
    dense dispatch mode."""
    moe = cfg.moe
    if moe.input_jitter_eps and rng is None:
        raise NotImplementedError(
            "input_jitter_eps requires threading an rng key through the "
            "forward pass, which is not wired yet; unset it.")
    b, l, h = x.shape
    t = b * l
    xt = x.reshape(t, h)
    if valid_mask is None:
        valid = jnp.ones((t,), jnp.float32)
    else:
        valid = valid_mask.reshape(t).astype(jnp.float32)
    n_valid = jnp.maximum(valid.sum(), 1.0)
    logits = (xt.astype(jnp.float32)
              @ m["router"].astype(jnp.float32))  # [T, E]
    probs_full = jax.nn.softmax(logits, axis=-1)
    top_probs, top_idx = router_probs(moe, logits, rng)
    # pads contribute nothing: zero their gates everywhere below
    top_probs = top_probs * valid[:, None]

    e = moe.num_experts
    ep = ep_constraint if ep_constraint is not None else (lambda a: a)
    if ragged_dispatch_enabled(cfg):
        if ep_constraint is not None:
            raise ValueError(
                "expert_parallel requires the capacity or dense "
                "dispatch mode; ragged grouped GEMMs cannot shard the "
                "group dim (set capacity_factor or "
                "use_grouped_gemm=False).")
        out = _ragged_moe(cfg, m, xt.astype(x.dtype), top_probs,
                          top_idx)
    elif moe.capacity_factor is None:
        # Dense mode: every expert over all tokens, gate-weighted.
        xs = ep(jnp.broadcast_to(xt[None], (e, t, h)).astype(x.dtype))
        expert_out = ep(_expert_ffn(cfg, m, xs))  # [E, T, H]
        gates = jnp.zeros((t, e), jnp.float32)
        gates = jax.vmap(lambda g, idx, p: g.at[idx].add(p))(
            gates, top_idx, top_probs)
        out = jnp.einsum("eth,te->th", expert_out.astype(jnp.float32), gates)
    else:
        cap = max(1, int(moe.capacity_factor * t * moe.top_k / e))
        # position of each (token, k) within its expert's capacity;
        # pads removed from the one-hot so they never occupy slots
        onehot = jax.nn.one_hot(top_idx, e, dtype=jnp.int32)  # [T, k, E]
        onehot = onehot * valid.astype(jnp.int32)[:, None, None]
        flat = onehot.reshape(t * moe.top_k, e)
        pos = jnp.cumsum(flat, axis=0) * flat - 1  # [T*k, E]
        pos = pos.reshape(t, moe.top_k, e)
        within = (pos < cap) & (onehot > 0)
        # Each (token, expert) pair occupies at most one k slot, so the
        # k axis collapses before the big einsums: dispatch/combine are
        # [T, E, C], not [T, k, E, C].
        disp = within[..., None] & (
            pos[..., None] == jnp.arange(cap)[None, None, None, :])
        disp_tec = disp.sum(axis=1).astype(x.dtype)  # [T, E, C]
        expert_in = ep(jnp.einsum("th,tec->ech", xt.astype(x.dtype),
                                  disp_tec))
        expert_out = ep(_expert_ffn(cfg, m, expert_in))  # [E, C, H]
        combine = (disp.astype(jnp.float32)
                   * top_probs[:, :, None, None]).sum(axis=1)  # [T, E, C]
        out = jnp.einsum("ech,tec->th", expert_out.astype(jnp.float32),
                         combine)

    losses = {}
    if moe.routing_type == "aux_loss" and moe.aux_loss_coeff:
        losses["moe_aux_loss"] = moe.aux_loss_coeff * load_balancing_loss(
            probs_full, top_idx, e, moe.top_k, valid=valid)
    if moe.z_loss_coeff:
        losses["moe_z_loss"] = moe.z_loss_coeff * z_loss(logits, valid=valid)
    return out.reshape(b, l, h).astype(x.dtype), losses
