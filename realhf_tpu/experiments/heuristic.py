"""Heuristic per-MFC allocation (allocation_mode=heuristic).

TPU-native counterpart of the reference's heuristic allocation
(``realhf/experiments/common/ppo_exp.py:419``): given the device count
and each role's model size, choose a decoupled layout per MFC without
running the MCMC search. The reference's rules-of-thumb translated to
TPU terms:

- **train MFCs** run on the role's primary layout: TP just big enough
  that weights + optimizer state (Adam: ~16 bytes/param fp32 m/v +
  master copy) fit comfortably in one chip's HBM, all remaining
  devices go to DP (grad accumulation handles batch; DP maximizes MXU
  utilization on TPU). When even TP = one ICI ring (TP_CAP) cannot
  fit the training state, layers are additionally sharded over
  pipeline stages (parallel/pipeline.py GPipe schedule).
- **generate MFCs** prefer wide DP with minimal TP (decode is
  HBM-bandwidth bound and batch-parallel; TP collectives per token are
  pure overhead at small per-chip batch): TP = weights-fit minimum.
- **inference MFCs** (reward/ref scoring) size TP to fit weights in
  bf16 (no optimizer), rest DP.

All sizes are derived from ``models/operators.py:n_params``; the
layout is returned as {mfc_name: ParallelismConfig} plus the per-role
primary, mirroring the (RPCAllocation, MFCConfig) output of the
reference.
"""

import dataclasses
import json
import os
from typing import Dict, List, Optional, Tuple

from realhf_tpu.base import logging as _logging

logger = _logging.getLogger("heuristic")

from realhf_tpu.api.config import ModelInterfaceType
from realhf_tpu.models import operators
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.parallel.mesh import ParallelismConfig

# Per-chip HBM budget in bytes (v5e: 16 GiB; leave headroom for
# activations and XLA workspace).
DEFAULT_HBM_BUDGET = int(16 * 1024 ** 3 * 0.6)


def _model_config_of(spec) -> TransformerConfig:
    """Config WITHOUT loading weights (sizes only)."""
    if spec.random_init_config is not None:
        return TransformerConfig(**spec.random_init_config,
                                 is_critic=spec.is_critic)
    from realhf_tpu.models.hf.registry import config_from_hf, detect_family
    family = spec.hf_family or detect_family(spec.path)
    with open(os.path.join(spec.path, "config.json")) as f:
        hf_config = json.load(f)
    return config_from_hf(family, hf_config, is_critic=spec.is_critic)


def _pow2_up_to(n: int) -> List[int]:
    out, p = [], 1
    while p <= n:
        out.append(p)
        p *= 2
    return out


def _min_tp(param_bytes: float, n_devices: int,
            hbm_budget: int) -> int:
    for tp in _pow2_up_to(n_devices):
        if param_bytes / tp <= hbm_budget:
            return tp
    return n_devices


# TP beyond one ICI ring scales poorly (per-layer collectives cross
# more hops); past this the heuristic prefers pipeline stages, whose
# ppermute traffic is one activation per tick.
TP_CAP = 8


def train_state_bytes_per_chip(n_params: int, tp: int, pp: int,
                               dp: int) -> float:
    """Per-chip training-state bytes with bf16 weights + ZeRO-1:
    bf16 params (2 B) shard over tp*pp; fp32 master copy (4 B), Adam
    m/v (8 B) and the fp32 grad accumulator (4 B, live during the
    step) additionally shard over dp (engine/optim.py
    with_master_weights + models/sharding.py opt_state_shardings;
    reference layout: Megatron DistributedOptimizer,
    megatron.py:823-940 -- previously modeled as 18 B/param over tp*pp
    only)."""
    return n_params * (2.0 + 16.0 / max(dp, 1)) / (tp * pp)


def pipeline_activation_bytes(hidden_dim: int, tokens_per_dp_rank: float,
                              n_stages: int,
                              n_microbatches: Optional[int] = None
                              ) -> float:
    """Resident pipeline activation bytes per stage under tick-level
    remat (transformer cfg pipeline_remat="tick"): the scan saves each
    tick's boundary activation (input carry + stacked output), bf16,
    for T = M + S - 1 ticks of one microbatch's tokens each --
    depth-independent (parallel/pipeline.py remat_tick; reference 1F1B
    keeps <= S microbatch sets, static_schedule.py:319)."""
    m = n_microbatches or 2 * n_stages
    t = m + n_stages - 1
    return 2.0 * t * (tokens_per_dp_rank / m) * hidden_dim * 2.0


def choose_layout(cfg: TransformerConfig, n_devices: int,
                  interface_type: ModelInterfaceType,
                  trainable: bool,
                  hbm_budget: int = DEFAULT_HBM_BUDGET,
                  tokens_per_batch: Optional[float] = None
                  ) -> ParallelismConfig:
    """One MFC's layout on ``n_devices`` chips. ``tokens_per_batch``
    (train batch seqs x seqlen, when known) lets the trainable fit
    check budget pipeline activations instead of weights-only (a pp
    allocation that ignores them can OOM on real shapes)."""
    n_params = operators.n_params(cfg)

    if trainable:
        # ZeRO-1 changes the trade-off: moments shrink with dp, so the
        # fit check must use the dp each (tp, pp) candidate implies.
        def fits(tp, pp):
            dp = max(1, n_devices // (tp * pp))
            need = train_state_bytes_per_chip(n_params, tp, pp, dp)
            if pp > 1 and tokens_per_batch is not None:
                need += pipeline_activation_bytes(
                    cfg.hidden_dim, tokens_per_batch / dp, pp)
            return need <= hbm_budget

        tp = next((t for t in _pow2_up_to(n_devices) if fits(t, 1)),
                  n_devices)
        pp = 1
        if tp > TP_CAP:
            # Very large models: hold TP at one ICI ring and shard
            # layers over pipeline stages instead.
            tp = min(TP_CAP, n_devices)
            for cand in _pow2_up_to(max(1, n_devices // tp)):
                pp = cand
                if cfg.n_layers % cand == 0 and fits(tp, cand):
                    break
            while pp > 1 and cfg.n_layers % pp != 0:
                pp //= 2
        dp = max(1, n_devices // (tp * pp))
        per_chip = train_state_bytes_per_chip(n_params, tp, pp, dp)
        if pp > 1 and tokens_per_batch is not None:
            per_chip += pipeline_activation_bytes(
                cfg.hidden_dim, tokens_per_batch / dp, pp)
        if per_chip > hbm_budget:
            logger.warning(
                "Heuristic layout t%dp%d leaves %.1f GB/chip for a "
                "%.1f GB budget (n_layers=%d limits pipeline depth); "
                "expect OOM without remat/offload headroom or more "
                "devices.", tp, pp, per_chip / 1e9, hbm_budget / 1e9,
                cfg.n_layers)
        return ParallelismConfig(
            data_parallel_size=dp, tensor_parallel_size=tp,
            pipeline_parallel_size=pp, sequence_parallel=tp > 1)

    if interface_type == ModelInterfaceType.GENERATE:
        # bf16 weights + KV cache headroom
        bytes_needed = n_params * 2 * 1.5
    else:
        bytes_needed = n_params * 2 * 1.2
    tp = _min_tp(bytes_needed, n_devices, hbm_budget)
    pp = 1
    if (tp > TP_CAP and interface_type != ModelInterfaceType.GENERATE):
        tp = min(TP_CAP, n_devices)
        for cand in _pow2_up_to(max(1, n_devices // tp)):
            pp = cand
            if (cfg.n_layers % cand == 0
                    and bytes_needed / (tp * cand) <= hbm_budget):
                break
        while pp > 1 and cfg.n_layers % pp != 0:
            pp //= 2
    if bytes_needed / (tp * pp) > hbm_budget:
        logger.warning(
            "Heuristic layout t%dp%d leaves %.1f GB/chip for a %.1f GB "
            "budget (n_layers=%d limits pipeline depth); expect OOM "
            "without remat/offload headroom or more devices.",
            tp, pp, bytes_needed / (tp * pp) / 1e9, hbm_budget / 1e9,
            cfg.n_layers)
    dp = max(1, n_devices // (tp * pp))
    return ParallelismConfig(
        data_parallel_size=dp, tensor_parallel_size=tp,
        pipeline_parallel_size=pp,
        sequence_parallel=False)


def heuristic_allocations(
    spec, n_devices: int,
    hbm_budget: int = DEFAULT_HBM_BUDGET,
) -> Tuple[Dict[str, ParallelismConfig], Dict[str, ParallelismConfig]]:
    """(per-role primary layouts, per-MFC overrides) for an
    ExperimentSpec on ``n_devices`` chips.

    The primary layout of a role is its train MFC's layout when one
    exists (replicas have no optimizer), else its widest-TP MFC.
    MFC overrides are emitted only when they differ from the primary
    (each override creates a weight replica + realloc, reference
    resolve_replica_ids).
    """
    cfgs = {role: _model_config_of(ms) for role, ms in spec.models.items()}
    trainable_roles = {
        n.role for n in spec.mfcs
        if n.interface_type == ModelInterfaceType.TRAIN_STEP}

    # token estimate per train batch for the pipeline-activation
    # budget: dataset max_length bounds the PROMPT; RLHF train MFCs
    # consume prompt + generated tokens, so add the largest
    # max_new_tokens any generate MFC is configured with.
    max_len = (spec.dataset.args or {}).get("max_length") \
        if getattr(spec, "dataset", None) is not None else None
    gen_extra = 0
    for n in spec.mfcs:
        g = (n.interface_impl.args or {}).get("gconfig")
        if isinstance(g, dict):
            gen_extra = max(gen_extra, int(g.get("max_new_tokens", 0)))
    seq_est = (max_len + gen_extra) if max_len else None

    mfc_layouts: Dict[str, ParallelismConfig] = {}
    for node in spec.mfcs:
        trainable = (node.interface_type == ModelInterfaceType.TRAIN_STEP)
        tokens = (node.n_seqs * seq_est
                  if trainable and seq_est else None)
        mfc_layouts[node.name] = choose_layout(
            cfgs[node.role], n_devices, node.interface_type,
            trainable, hbm_budget, tokens_per_batch=tokens)

    primaries: Dict[str, ParallelismConfig] = {}
    for role in spec.models:
        role_nodes = [n for n in spec.mfcs if n.role == role]
        train = [n for n in role_nodes
                 if n.interface_type == ModelInterfaceType.TRAIN_STEP]
        if train:
            primaries[role] = mfc_layouts[train[0].name]
        elif role_nodes:
            primaries[role] = max(
                (mfc_layouts[n.name] for n in role_nodes),
                key=lambda p: p.tensor_parallel_size)
        else:
            primaries[role] = ParallelismConfig(
                data_parallel_size=n_devices)

    overrides = {
        n.name: mfc_layouts[n.name] for n in spec.mfcs
        if not mfc_layouts[n.name].same_layout(primaries[n.role])
    }
    return primaries, overrides


def apply_heuristic_allocations(spec, n_devices: int,
                                hbm_budget: int = DEFAULT_HBM_BUDGET):
    """Mutate an ExperimentSpec in place: set each role's primary
    parallelism and the per-MFC allocation overrides."""
    primaries, overrides = heuristic_allocations(spec, n_devices,
                                                 hbm_budget)
    for role, par in primaries.items():
        spec.models[role] = dataclasses.replace(spec.models[role],
                                                parallel=par)
    spec.allocations = dict(spec.allocations)
    spec.allocations.update(overrides)
    return spec
