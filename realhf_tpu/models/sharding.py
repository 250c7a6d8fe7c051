"""GSPMD sharding rules for the transformer.

TPU-native replacement for the reference's Megatron-derived TP/SP
modules (``realhf/impl/model/parallelism/model_parallel/modules.py``,
``mappings.py``): instead of hand-written column/row-parallel linears
and scatter/gather autograd functions, every parameter gets a
`PartitionSpec` and XLA inserts the collectives.

Mapping (reference module -> spec here):
- ParallelEmbedding (vocab-partitioned, modules.py:53)  -> wte P("model", None)
- ColumnParallelLinear (modules.py:727)                 -> wq/wk/wv/wg/wu P(..., "model")
- RowParallelLinear (modules.py:875)                    -> wo/wd P(..., "model", None)
- parallel_lm_logits + _VocabParallelCrossEntropy       -> head P(None, "model") + chunked CE in ops/functional.py
- sequence parallel scatter/gather (mappings.py:207-294)-> residual-stream
  constraint P("data", "model", None): XLA materializes the
  all-gather before attention/MLP and reduce-scatter after, which is
  exactly Megatron-SP's communication pattern.
"""

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from realhf_tpu.models import operators as O
from realhf_tpu.models.config import ABSENT, TransformerConfig
from realhf_tpu.parallel.mesh import CTX_AXIS, DATA_AXIS, MODEL_AXIS, PIPE_AXIS


def param_pspecs(cfg: TransformerConfig,
                 pipeline_parallel: bool = False) -> Dict[str, Any]:
    """PartitionSpec pytree congruent with ``init_params`` output.

    With ``pipeline_parallel`` the stacked-block leading (layer) dim is
    sharded over the "pipe" axis -- each stage owns a contiguous
    n_layers/pp slab (the reference's partition_pipeline_layers split,
    real_llm_parallel.py:342); embedding/head/final-norm stay
    pipe-replicated and run outside the pipeline loop.
    """
    if cfg.layer_pattern is not None:
        if pipeline_parallel:
            cfg.require_one_block("pipeline parallelism")
        return _pattern_pspecs(cfg)
    lead = PIPE_AXIS if pipeline_parallel else None
    col = P(lead, None, MODEL_AXIS)      # [nl, H, out_sharded]
    row = P(lead, MODEL_AXIS, None)      # [nl, in_sharded, H]
    col_b = P(lead, MODEL_AXIS)          # bias of a column-parallel linear
    rep2 = P(lead, None)                 # [nl, H] replicated over tp
    specs: Dict[str, Any] = {
        "embed": {"wte": P(MODEL_AXIS, None)},
        "blocks": {
            "ln1": {"scale": rep2},
            "attn": {"wq": col, "wk": col, "wv": col, "wo": row},
            "ln2": {"scale": rep2},
            "mlp": {},
        },
        "ln_f": {"scale": P(None)},
    }
    if cfg.uses_absolute_position:
        specs["embed"]["wpe"] = P(None, None)
    mlp = specs["blocks"]["mlp"]
    if cfg.mlp_type == "moe":
        # Experts TP-sharded (reference behavior: each expert's MLP is
        # column/row-parallel, experts.py:26). With expert_parallel the
        # E dim additionally shards over the data axis (real EP -- the
        # reference's dispatcher explicitly does not support it,
        # token_dispatcher.py:26-27).
        ep = DATA_AXIS if (cfg.moe is not None
                           and cfg.moe.expert_parallel) else None
        mlp["router"] = P(lead, None, None)
        mlp["wg"] = P(lead, ep, None, MODEL_AXIS)
        mlp["wu"] = P(lead, ep, None, MODEL_AXIS)
        mlp["wd"] = P(lead, ep, MODEL_AXIS, None)
    elif cfg.gated_mlp:
        mlp["wg"] = col
        mlp["wu"] = col
        mlp["wd"] = row
    else:
        mlp["wu"] = col
        mlp["wd"] = row
    if cfg.use_attention_bias:
        a = specs["blocks"]["attn"]
        a["bq"], a["bk"], a["bv"] = col_b, col_b, col_b
    if cfg.use_attn_proj_bias:
        specs["blocks"]["attn"]["bo"] = rep2
    if cfg.qk_norm is not None:
        # scales of the column-parallel projections' outputs
        specs["blocks"]["attn"]["q_norm"] = col_b
        specs["blocks"]["attn"]["k_norm"] = col_b
    if cfg.use_mlp_bias and cfg.mlp_type is None:
        mlp["bu"] = col_b
        mlp["bd"] = rep2
    if cfg.layer_norm_type is None:
        specs["blocks"]["ln1"]["bias"] = rep2
        specs["blocks"]["ln2"]["bias"] = rep2
        specs["ln_f"]["bias"] = P(None)
    if cfg.post_norm:  # as the norms before the operators
        specs["blocks"]["ln1_post"] = {"scale": rep2}
        specs["blocks"]["ln2_post"] = {"scale": rep2}
    if cfg.is_critic:
        specs["head"] = {"w": P(None, None)}
    elif not cfg.tied_embedding:
        specs["head"] = {"w": P(None, MODEL_AXIS)}
    if cfg.exit_gate:
        # one output: on every shard (2,049 values), as a critic's head
        specs["exit_gate"] = {"w": P(None, None), "b": P(None)}
    return specs


def _pattern_pspecs(cfg: TransformerConfig) -> Dict[str, Any]:
    """``param_pspecs`` of a patterned model: the same rules with no
    layer axis, a tree a layer: of each leaf its operator and its
    feed-forward declare (``models/operators.py``) the spec declared
    with it (a part a layer lacks has no norm either)."""
    layers = {str(i): {
        **{name: {"scale": P(None)}
           for name, part in (("ln1", op), ("ln2", ff)) if part != ABSENT},
        **O.walk({**O.OPERATORS[op].leaves(cfg, i),
                  **O.FEED_FORWARDS[ff](cfg)}, lambda leaf: leaf.spec)}
        for i, (op, ff) in enumerate(cfg.layer_pattern)}
    specs: Dict[str, Any] = {"embed": {"wte": P(MODEL_AXIS, None)},
                             "layers": layers,
                             "ln_f": {"scale": P(None)}}
    if cfg.is_critic:
        specs["head"] = {"w": P(None, None)}
    elif not cfg.tied_embedding:
        specs["head"] = {"w": P(None, MODEL_AXIS)}
    return specs


def padded_vocab_size(cfg: TransformerConfig, tp: int) -> int:
    """Vocab padded up to a tp multiple (Megatron's VocabUtility,
    reference model_parallel/utils.py:154)."""
    return ((cfg.vocab_size + tp - 1) // tp) * tp


def pad_vocab(cfg: TransformerConfig, params: Dict[str, Any],
              tp: int) -> Dict[str, Any]:
    """Zero-pad the vocab dim of wte/head so it shards over tp.
    Consumers slice logits back to cfg.vocab_size (lm_logits etc.), so
    padded entries are never sampled or normalized over."""
    import numpy as np
    vp = padded_vocab_size(cfg, tp)
    v = cfg.vocab_size
    if vp == v or params["embed"]["wte"].shape[0] == vp:  # already padded
        return params
    xp = jax.numpy if hasattr(params["embed"]["wte"], "devices") else np

    def _pad(a, axis):
        width = [(0, 0)] * a.ndim
        width[axis] = (0, vp - v)
        return xp.pad(a, width)

    params = {**params, "embed": {**params["embed"]}}
    params["embed"]["wte"] = _pad(params["embed"]["wte"], 0)
    if not cfg.is_critic and not cfg.tied_embedding:
        params = {**params, "head": {"w": _pad(params["head"]["w"], 1)}}
    return params


def normalize_vocab_padding(cfg: TransformerConfig, params: Dict[str, Any],
                            tp: int) -> Dict[str, Any]:
    """Re-pad params (possibly padded for a different tp) to the
    padding this tp needs."""
    return pad_vocab(cfg, unpad_vocab(cfg, params), tp)


def unpad_vocab(cfg: TransformerConfig, params: Dict[str, Any]
                ) -> Dict[str, Any]:
    """Inverse of pad_vocab (checkpoint saving)."""
    v = cfg.vocab_size
    if params["embed"]["wte"].shape[0] == v:
        return params
    params = {**params, "embed": {**params["embed"]}}
    params["embed"]["wte"] = params["embed"]["wte"][:v]
    if not cfg.is_critic and not cfg.tied_embedding:
        params = {**params, "head": {"w": params["head"]["w"][:, :v]}}
    return params


def repad_vocab_leaf(cfg: TransformerConfig, path, arr, target_tp: int):
    """Per-LEAF form of unpad_vocab+pad_vocab for streamed installs
    (parallel/realloc.py:install_param_chunks): the single place the
    which-leaves-carry-vocab rule lives, congruent with the tree forms
    above. ``path`` is the leaf's key tuple, e.g. ("embed", "wte")."""
    import numpy as np
    vp = padded_vocab_size(cfg, target_tp)
    v = cfg.vocab_size
    if path == ("embed", "wte"):
        arr = arr[:v]
        if vp != v:
            arr = np.pad(arr, [(0, vp - v)] + [(0, 0)] * (arr.ndim - 1))
    elif (path == ("head", "w") and not cfg.is_critic
            and not cfg.tied_embedding):
        arr = arr[:, :v]
        if vp != v:
            arr = np.pad(arr, [(0, 0), (0, vp - v)])
    return arr


def param_shardings(cfg: TransformerConfig, mesh: Mesh) -> Dict[str, Any]:
    pp = mesh.shape.get(PIPE_AXIS, 1)
    return jax.tree.map(lambda s: NamedSharding(mesh, s),
                        param_pspecs(cfg, pipeline_parallel=pp > 1),
                        is_leaf=lambda x: isinstance(x, P))


def zero1_moment_spec(spec: P, shape, dp: int) -> P:
    """Extend a parameter's PartitionSpec with the DATA axis on its
    largest free dim -- the ZeRO-1 sharding for that parameter's
    optimizer moments (reference Megatron DistributedOptimizer,
    backend/megatron.py:823-940: fp32 m/v sharded over DP). The
    all-gather of the parameter update that ZeRO-1 performs is inserted
    by GSPMD when `optax.apply_updates` output reshards to the param's
    own spec."""
    if dp <= 1:
        return spec
    entries = list(spec) + [None] * (len(shape) - len(spec))
    used = set()
    for e in entries:
        if e is None:
            continue
        for ax in (e if isinstance(e, tuple) else (e,)):
            used.add(ax)
    if DATA_AXIS in used:  # e.g. expert-parallel MoE weights
        return spec
    best_i, best = None, 0
    for i, (e, d) in enumerate(zip(entries, shape)):
        if e is None and d % dp == 0 and d > best:
            best, best_i = d, i
    if best_i is None:
        return spec
    entries[best_i] = DATA_AXIS
    return P(*entries)


def opt_state_shardings(opt_state_shape, cfg: TransformerConfig,
                        mesh: Mesh, zero1: bool = True):
    """NamedSharding pytree for an optax state (from
    ``jax.eval_shape(tx.init, params)``).

    Moment leaves are recognized by path suffix: optax states embed
    ``mu``/``nu`` (and any other per-parameter slot) as pytrees
    congruent with the params, so a state leaf whose key-path ends with
    a full parameter path IS that parameter's slot and gets the
    parameter's spec -- extended over the DATA axis when ``zero1``.
    Everything else (step counts, scalars) is replicated."""
    pp = mesh.shape.get(PIPE_AXIS, 1)
    dp = mesh.shape.get(DATA_AXIS, 1) if zero1 else 1
    pspecs = param_pspecs(cfg, pipeline_parallel=pp > 1)
    flat_p = jax.tree_util.tree_flatten_with_path(
        pspecs, is_leaf=lambda x: isinstance(x, P))[0]
    param_paths = [(tuple(str(k) for k in path), spec)
                   for path, spec in flat_p]

    def assign(path, leaf):
        strs = tuple(str(k) for k in path)
        for ppath, spec in param_paths:
            if len(strs) >= len(ppath) and strs[-len(ppath):] == ppath:
                if leaf.shape != ():
                    return NamedSharding(
                        mesh, zero1_moment_spec(spec, leaf.shape, dp))
                break
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map_with_path(assign, opt_state_shape)


def batch_pspec() -> P:
    """[B, L] token/segment arrays: DP over streams, context
    parallelism over the sequence dim."""
    return P(DATA_AXIS, CTX_AXIS)


def residual_pspec(sequence_parallel: bool) -> P:
    """[B, L, H] residual stream; with SP the sequence dim is also
    sharded over the TP axis (Megatron-SP analog)."""
    if sequence_parallel:
        return P(DATA_AXIS, (CTX_AXIS, MODEL_AXIS), None)
    return P(DATA_AXIS, CTX_AXIS, None)


def activation_constraint(mesh: Mesh, sequence_parallel: bool):
    """The per-block residual-stream constraint fed to
    ``transformer.forward(activation_constraint=...)``."""
    spec = residual_pspec(sequence_parallel)
    sharding = NamedSharding(mesh, spec)

    def constrain(x):
        # Inside the pipeline's shard_map (manual over "pipe") the
        # constraint must name the context mesh, whose "pipe" axis is
        # Manual; the engine's own mesh types every axis Auto.
        ctx_mesh = jax.sharding.get_abstract_mesh()
        if ctx_mesh.manual_axes:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(ctx_mesh, spec))
        return jax.lax.with_sharding_constraint(x, sharding)

    return constrain


def moe_ep_constraint(cfg: TransformerConfig, mesh: Mesh):
    """Constraint pinning expert-major ``[E, ...]`` MoE intermediates
    to the data axis when expert parallelism is on -- this is what
    turns the GShard dispatch/combine einsums into all-to-alls instead
    of letting XLA all-gather the expert weights. Returns None for
    non-EP configs (the common case)."""
    if not (cfg.n_moe_layers and cfg.moe is not None
            and cfg.moe.expert_parallel):
        return None

    def constrain(x):
        spec = P(DATA_AXIS, *([None] * (x.ndim - 1)))
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(mesh, spec))

    return constrain


