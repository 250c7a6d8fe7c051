"""Optimizer construction (optax) + LR schedules.

Parity with reference ``realhf/api/quickstart/model.py:62``
(OptimizerConfig) and ``base/timeutil.py:118-216`` (LR schedulers) +
Megatron's OptimizerParamScheduler usage (backend/megatron.py:158).
The reference's ZeRO-1 DistributedOptimizer is unnecessary machinery
here: optimizer state is a pytree that shards exactly like params
(GSPMD), and can additionally be sharded over the DP axis.
"""

import dataclasses
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp
import optax


#: the key of a layer's subtree that takes no update (see
#: ``make_optimizer``): a sparse layer's indexer
UNTRAINED_SUBTREE = "index"


@dataclasses.dataclass
class OptimizerConfig:
    """Mirrors reference OptimizerConfig field-by-field (type "empty"
    means no optimizer -- inference-only model)."""
    type: str = "adam"  # adam | empty
    lr: float = 1e-5
    weight_decay: float = 0.05
    beta1: float = 0.9
    beta2: float = 0.95
    eps: float = 1e-5
    min_lr_ratio: float = 0.0
    lr_scheduler_type: str = "cosine"  # linear | cosine | constant
    warmup_steps_proportion: float = 0.02
    gradient_clipping: float = 1.0
    # fp16 loss scaling is irrelevant on TPU (bf16 training); kept for
    # config-surface parity and ignored.
    initial_loss_scale: float = 2 ** 32
    # Keep optimizer state on host between train steps (reference
    # DeepSpeed zero-offload, deepspeed.py:445): frees
    # master+moments HBM for colocated MFCs at the cost of a
    # host<->device round trip per step (engine.train_batch).
    offload: bool = False
    # ZeRO-1-equivalent optimizer-state sharding over the DP axis
    # (reference Megatron DistributedOptimizer / DeepSpeed zero_stage=1,
    # always on in the reference's Megatron backend). Adam moments
    # shard as params x DATA; disable to replicate moments across DP.
    zero1: bool = True


def lr_schedule(cfg: OptimizerConfig, total_steps: int) -> optax.Schedule:
    warmup = int(cfg.warmup_steps_proportion * total_steps)
    decay_steps = max(1, total_steps - warmup)
    end = cfg.lr * cfg.min_lr_ratio
    if cfg.lr_scheduler_type == "constant":
        decay = optax.constant_schedule(cfg.lr)
    elif cfg.lr_scheduler_type == "linear":
        decay = optax.linear_schedule(cfg.lr, end, decay_steps)
    elif cfg.lr_scheduler_type == "cosine":
        alpha = cfg.min_lr_ratio
        decay = optax.cosine_decay_schedule(cfg.lr, decay_steps, alpha=alpha)
    else:
        raise NotImplementedError(cfg.lr_scheduler_type)
    if warmup <= 0:
        # no warmup: the FIRST step must already use the full lr
        # (linear_schedule(0, lr, 1) would silently zero it out)
        return decay
    return optax.join_schedules(
        [optax.linear_schedule(0.0, cfg.lr, warmup), decay], [warmup])


class MasterWeightsState(NamedTuple):
    """fp32 master copy + the wrapped optimizer's state. Both live in
    the optimizer state pytree, so ZeRO-1 shards them over DP
    (models/sharding.py:opt_state_shardings) -- the reference's
    Megatron DistributedOptimizer layout (megatron.py:823-940: bf16
    weights everywhere, fp32 master + moments sharded across DP)."""
    master: Any
    inner: Any


def with_master_weights(inner: optax.GradientTransformation
                        ) -> optax.GradientTransformation:
    """Mixed-precision wrapper: params stay in their compute dtype
    (bf16); the update runs in fp32 against a master copy kept in the
    state. The emitted update is the fp32 delta ``new_master - p``, so
    ``optax.apply_updates`` (which adds in promoted fp32 then casts to
    the param dtype) lands exactly ``round_bf16(new_master)``."""

    def init(params):
        master = jax.tree.map(lambda p: jnp.asarray(p, jnp.float32),
                              params)
        return MasterWeightsState(master, inner.init(master))

    def update(grads, state, params=None):
        assert params is not None, "master-weights update needs params"
        g32 = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        upd, inner_state = inner.update(g32, state.inner, state.master)
        new_master = optax.apply_updates(state.master, upd)
        delta = jax.tree.map(
            lambda nm, p: nm - p.astype(jnp.float32), new_master, params)
        return delta, MasterWeightsState(new_master, inner_state)

    return optax.GradientTransformation(init, update)


def make_optimizer(cfg: OptimizerConfig,
                   total_steps: Optional[int] = None,
                   master_weights: bool = False
                   ) -> optax.GradientTransformation:
    if cfg.type == "empty":
        return optax.identity()
    if cfg.type != "adam":
        raise NotImplementedError(f"Optimizer type {cfg.type}")
    sched = lr_schedule(cfg, total_steps or 10 ** 9)
    chain = []
    if cfg.gradient_clipping and cfg.gradient_clipping > 0:
        chain.append(optax.clip_by_global_norm(cfg.gradient_clipping))
    # Decay only matrix-shaped params (norm scales/biases excluded),
    # matching Megatron's no-weight-decay param groups; and nothing
    # under a sparse layer's "index": no gradient of the language-model
    # loss reaches an indexer (models/config.py:IndexerConfig), and a
    # decay alone would shrink what nothing trains. With neither, its
    # leaves stay bit-equal through every step.
    def decay_mask(params):
        return jax.tree_util.tree_map_with_path(
            lambda path, p: p.ndim >= 2 and not any(
                getattr(k, "key", None) == UNTRAINED_SUBTREE
                for k in path), params)

    chain.append(optax.adamw(
        learning_rate=sched, b1=cfg.beta1, b2=cfg.beta2, eps=cfg.eps,
        weight_decay=cfg.weight_decay, mask=decay_mask))
    tx = optax.chain(*chain)
    if master_weights:
        tx = with_master_weights(tx)
    return tx
