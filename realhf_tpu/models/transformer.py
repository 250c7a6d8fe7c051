"""The single transformer implementation used for every model role.

TPU-native counterpart of reference ``realhf/impl/model/nn/
real_llm_api.py`` (ReaLModel) + ``real_llm_base.py`` + ``modules/``:
one decoder-only transformer covering actor / critic / reference /
reward roles (critic mode swaps the LM head for a scalar value head).

Design (idiomatic JAX, not a torch translation):
- TWO parameter layouts (ROADMAP D2). A model of ONE kind of block
  holds **stacked** block weights (leading axis = layer) under
  ``params["blocks"]`` and scans them with ``jax.lax.scan``: what is
  traced and lowered does not grow with depth. A model whose layers
  are NOT all of one kind (``TransformerConfig.layer_pattern``) cannot
  stack its weights: it holds a tree a layer under ``params["layers"]``
  (``{"0": ..., "1": ...}``) and every layer loop below is unrolled
  over the pattern, so its trace grows with depth.
- What a layer's operator IS (its leaves, its forward, its decode
  state and one token's step) is its record in ``models/operators.py``;
  the loops here ask ``OPERATORS[op]`` and spell no operator's name.
  A patterned layer is ``x + op(norm(x))`` then ``x + ff(norm(x))``,
  or one of the two ALONE (the other part ``ABSENT`` in its pattern
  entry: it then holds one norm, ``ln1`` a mixer's, ``ln2`` a
  feed-forward's, and ``_block`` runs that part and its residual add
  only). Its attention layers may differ a layer: the window, the
  count of query heads (``layer_q_heads``; K and V keep one shape),
  the rotary table of their kind (``rotary_by_operator``, None: none).
  A feed-forward without a gate (``mlp_type`` None: ``wu`` and ``wd``
  alone, dense, shared or an expert's) is two products.
- Batches are packed streams ``[B, L]`` with segment ids (0 = pad);
  positions are derived per segment. DP shards B; TP shards heads and
  MLP; Megatron-style sequence parallelism falls out of GSPMD sharding
  constraints (see models/sharding.py).
- Generation uses a cache pytree and a single-token decode step; the
  jitted decode loop replaces CUDA-graph capture (reference
  ``nn/real_llm_generate.py:214``). Every cache has K and V stacked
  over the ATTENTION layers alone (``k``, ``v``, ``valid``,
  ``length``); beside them each operator's own states, stacked over
  ITS layers (``operators.py:State``: a convolution's last rows, a
  head's recurrent state in float32, an indexer's keys by slot).
- A LOOPED model (``TransformerConfig.n_passes`` > 1) runs its whole
  stack several times over ONE set of weights: ``forward`` writes the
  passes out around the scan of the layers (``_passes``), the final norm
  INSIDE the loop (its output starts the next pass and feeds that
  pass's head and exit gate), and every cache below holds
  ``cfg.kv_layers`` layers' worth of keys and values, pass t layer l
  at ``t x n_layers + l``. Its block may norm AFTER each operator too
  (``post_norm``). A model without these keys lowers as it always did.

Layer indexing convention matches the reference (real_llm_base.py:394):
0 = embedding, 1..n_layers = blocks, n_layers+1 = head -- used by HF
conversion and (later) pipeline splitting.
"""

import collections
import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from realhf_tpu.models import operators as O
from realhf_tpu.models.config import (ABSENT, ATTENTION_OPERATORS,
                                      TransformerConfig)
from realhf_tpu.models.operators import (DELTA_RESIDUALS, OPERATORS,
                                         PROJECTION_RESIDUALS,
                                         SSM_RESIDUALS, Ctx, _norm)
from realhf_tpu.obs import parts as P
from realhf_tpu.ops.flash_attention import RESIDUAL_NAMES, SELECT_RESIDUAL
from realhf_tpu.ops.moe import (STATS, _dense_mlp, moe_mlp_with_losses,
                                reduce_layers)
from realhf_tpu.ops.rotary import rotary_freqs

Params = Dict[str, Any]
KVCache = Dict[str, jnp.ndarray]
#: every name the policy of a rematerialised block keeps; the last is
#: a sparse layer's selection (int8, ``L x L`` bytes a row a layer):
#: kept, the backward's kernels mask by it and the indexer, which no
#: gradient reaches, does not run a second time
KEPT_RESIDUALS = RESIDUAL_NAMES + PROJECTION_RESIDUALS + DELTA_RESIDUALS \
    + (SELECT_RESIDUAL,) + SSM_RESIDUALS
#: what a block of a LOOPED model keeps (``_passes``): the flash
#: kernel's two residuals alone. Its stack is walked ``n_passes`` times
#: and the backward needs every application's, so each name costs T
#: times its rows: with q and the projected output kept too,
#: Ouro-2.6B's six layers at T = 4 and rows of 4096 compiled to 15.3 GB
#: of a chip's 16, without them to 13.7 (PERF.md, PR 53). Running
#: ``flash_fwd`` again would be the dearer saving (PR 36's table).
KEPT_IN_A_LOOP = RESIDUAL_NAMES


# ----------------------------------------------------------------------
# Initialization
# ----------------------------------------------------------------------
def init_params(cfg: TransformerConfig, key: jax.Array) -> Params:
    """Random-normal init (std 0.02, projection layers scaled by
    1/sqrt(2*n_layers) as in GPT-2/llama lineage)."""
    if cfg.layer_pattern is not None:
        return _init_pattern_params(cfg, key)
    pdt = jnp.dtype(cfg.param_dtype)
    h, f, v = cfg.hidden_dim, cfg.intermediate_dim, cfg.vocab_size
    nl, hd = cfg.n_layers, cfg.head_dim
    nq, nkv = cfg.n_q_heads, cfg.n_kv_heads
    std = 0.02
    proj_std = std / (2 * nl) ** 0.5

    keys = jax.random.split(key, 16)

    def norm(shape, k, s=std):
        return (s * jax.random.normal(k, shape)).astype(pdt)

    def zeros(shape):
        return jnp.zeros(shape, dtype=pdt)

    def ones(shape):
        return jnp.ones(shape, dtype=pdt)

    params: Params = {
        "embed": {"wte": norm((v, h), keys[0])},
        "blocks": {
            "ln1": {"scale": ones((nl, h))},
            "attn": {
                "wq": norm((nl, h, nq * hd), keys[1]),
                "wk": norm((nl, h, nkv * hd), keys[2]),
                "wv": norm((nl, h, nkv * hd), keys[3]),
                "wo": norm((nl, nq * hd, h), keys[4], proj_std),
            },
            "ln2": {"scale": ones((nl, h))},
            "mlp": {},
        },
        "ln_f": {"scale": ones((h,))},
    }
    if cfg.uses_absolute_position:
        assert cfg.n_positions is not None
        params["embed"]["wpe"] = norm(
            (cfg.n_positions + cfg.abs_position_embedding_offset, h), keys[5])

    mlp = params["blocks"]["mlp"]
    if cfg.mlp_type == "moe":
        ne = cfg.moe.num_experts
        mlp["router"] = norm((nl, h, ne), keys[6])
        mlp["wg"] = norm((nl, ne, h, f), keys[7])
        mlp["wu"] = norm((nl, ne, h, f), keys[8])
        mlp["wd"] = norm((nl, ne, f, h), keys[9], proj_std)
    elif cfg.gated_mlp:
        mlp["wg"] = norm((nl, h, f), keys[7])
        mlp["wu"] = norm((nl, h, f), keys[8])
        mlp["wd"] = norm((nl, f, h), keys[9], proj_std)
    else:
        mlp["wu"] = norm((nl, h, f), keys[8])
        mlp["wd"] = norm((nl, f, h), keys[9], proj_std)

    if cfg.use_attention_bias:
        a = params["blocks"]["attn"]
        a["bq"], a["bk"], a["bv"] = (zeros((nl, nq * hd)),
                                     zeros((nl, nkv * hd)),
                                     zeros((nl, nkv * hd)))
    if cfg.use_attn_proj_bias:
        params["blocks"]["attn"]["bo"] = zeros((nl, h))
    if cfg.qk_norm is not None:
        params["blocks"]["attn"]["q_norm"] = ones((nl, nq * hd))
        params["blocks"]["attn"]["k_norm"] = ones((nl, nkv * hd))
    if cfg.use_mlp_bias and cfg.mlp_type is None:
        mlp["bu"] = zeros((nl, f))
        mlp["bd"] = zeros((nl, h))
    if cfg.layer_norm_type is None:  # LayerNorm has bias; RMSNorm none
        params["blocks"]["ln1"]["bias"] = zeros((nl, h))
        params["blocks"]["ln2"]["bias"] = zeros((nl, h))
        params["ln_f"]["bias"] = zeros((h,))
    if cfg.post_norm:
        params["blocks"]["ln1_post"] = {"scale": ones((nl, h))}
        params["blocks"]["ln2_post"] = {"scale": ones((nl, h))}

    if cfg.is_critic:
        params["head"] = {"w": norm((h, 1), keys[10])}
    elif not cfg.tied_embedding:
        params["head"] = {"w": norm((h, v), keys[10])}
    if cfg.exit_gate:
        params["exit_gate"] = {"w": norm((h, 1), keys[11]),
                               "b": zeros((1,))}
    return params


def _init_pattern_params(cfg: TransformerConfig, key: jax.Array) -> Params:
    """``init_params`` of a patterned model: the same distributions,
    one tree a layer, each with the leaves its operator and its
    feed-forward declare (``models/operators.py``), drawn in the
    declarations' order; a part a layer lacks has no norm either."""
    pdt = jnp.dtype(cfg.param_dtype)
    h, v = cfg.hidden_dim, cfg.vocab_size
    std = 0.02
    proj_std = std / (2 * cfg.n_layers) ** 0.5
    per_layer = max(rec.keys for rec, n in O.used(cfg) if n)
    keys = iter(jax.random.split(key, per_layer * cfg.n_layers + 4))

    def norm(shape, s=std):
        return (s * jax.random.normal(next(keys), shape)).astype(pdt)

    def ones(shape):
        return jnp.ones(shape, dtype=pdt)

    def drawn(leaves):
        decay = {leaf.draw for leaf in jax.tree.leaves(
            leaves, is_leaf=lambda x: isinstance(x, O.Leaf))} \
            & {O.A_LOG, O.DT_BIAS}
        ka, kd = jax.random.split(next(keys)) if decay else (None, None)

        def draw(leaf):
            if leaf.draw in (O.STD, O.PROJ):
                return norm(leaf.shape,
                            proj_std if leaf.draw == O.PROJ else std)
            if leaf.draw == O.ONES:
                return ones(leaf.shape)
            if leaf.draw == O.ZEROS:
                return jnp.zeros(leaf.shape, pdt)
            if leaf.draw == O.A_LOG:
                return jnp.log(jax.random.uniform(
                    ka, leaf.shape, minval=1.0, maxval=16.0)).astype(pdt)
            dt = jnp.exp(jax.random.uniform(
                kd, leaf.shape, minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
            return (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt)

        return O.walk(leaves, draw)

    layers = {}
    for i, (op, ff) in enumerate(cfg.layer_pattern):
        layers[str(i)] = {
            **{name: {"scale": ones((h,))}
               for name, part in (("ln1", op), ("ln2", ff))
               if part != ABSENT},
            **drawn(OPERATORS[op].leaves(cfg, i)),
            **drawn(O.FEED_FORWARDS[ff](cfg))}
    params: Params = {"embed": {"wte": norm((v, h))}, "layers": layers,
                      "ln_f": {"scale": ones((h,))}}
    if cfg.is_critic:
        params["head"] = {"w": norm((h, 1))}
    elif not cfg.tied_embedding:
        params["head"] = {"w": norm((h, v))}
    return params


# ----------------------------------------------------------------------
# Building blocks
# ----------------------------------------------------------------------
def _mlp(cfg: TransformerConfig, lp: Params, x: jnp.ndarray,
         moe_constraint=None, sparse: Optional[bool] = None,
         layer_input: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    out, _ = _mlp_with_aux(cfg, lp, x, None, moe_constraint, sparse,
                           layer_input)
    return out


def _mlp_with_aux(cfg: TransformerConfig, lp: Params, x: jnp.ndarray,
                  seg_ids: Optional[jnp.ndarray] = None,
                  moe_constraint=None, sparse: Optional[bool] = None,
                  layer_input: Optional[jnp.ndarray] = None):
    """MLP returning (output, aux dict) -- non-empty only for MoE
    (router load-balancing / z losses, reference utils/moe.py:395,
    and the statistics of ``ops.moe.STATS``).
    ``seg_ids`` masks padding out of MoE routing/capacity/losses.
    ``sparse``: whether THIS layer's feed-forward is the mixture of
    experts; a patterned model says it a layer, a model of one block
    by ``mlp_type``. ``layer_input``: what the layer took in, before
    its first norm and its operator, which a router reads where
    ``MoEConfig.router_input`` says so (the experts read ``x``)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    m = lp["mlp"]
    if sparse is None:
        sparse = cfg.mlp_type == "moe"
    if sparse:
        with jax.named_scope(P.EXPERTS):
            squeeze = x.ndim == 2  # decode step: [B, H]
            x3 = x[:, None, :] if squeeze else x
            valid = None if seg_ids is None else (seg_ids != 0)
            route_on = None
            if cfg.moe.router_input == "layer_input":
                route_on = layer_input[:, None, :] if squeeze \
                    else layer_input
            out, aux = moe_mlp_with_losses(cfg, m, x3, valid_mask=valid,
                                           ep_constraint=moe_constraint,
                                           route_on=route_on)
            return (out[:, 0] if squeeze else out), aux
    with jax.named_scope(P.MLP):
        return _dense_mlp(cfg, m, x, cdt), {}


def _ff_part(cfg: TransformerConfig, sparse: Optional[bool]) -> str:
    """The part (``obs/parts.py``) a layer's feed-forward, the norm
    before it and the residual's add after it are put down to."""
    if sparse is None:
        sparse = cfg.mlp_type == "moe"
    return P.EXPERTS if sparse else P.MLP



def _block(cfg: TransformerConfig, lp: Params, x: jnp.ndarray, ctx: Ctx,
           constrain, moe_constraint=None, kind=None):
    """One block over packed streams [B, L, H]; returns (residual
    output, state, aux-losses). ``kind``: the layer's (operator,
    feed-forward) in a patterned model, None for the one block of
    ``mlp_type``; ``ctx``: what its operator is handed
    (``models/operators.py:Ctx``). The state feeds prefill's caches:
    what the operator's record says its ``apply`` returns ((k, v) of
    an attention layer, then what it keeps beside them), () of a layer
    without an operator; aux is non-empty for MoE. A part the layer's
    kind says is ``ABSENT`` is not run: the layer is its one part, that
    part's norm and one residual add."""
    op, sparse = ("attention", None) if kind is None \
        else (kind[0], kind[1] == "moe")
    state, layer_input = (), x
    if op != ABSENT:
        # the norm before an operator and the residual's add after it
        # go with the operator's projections, those around the
        # feed-forward with the feed-forward (obs/parts.py)
        rec = OPERATORS[op]
        with jax.named_scope(rec.scope):
            ln1 = _norm(cfg, x, lp["ln1"]["scale"], lp["ln1"].get("bias"))
        proj, state = rec.apply(cfg, lp, ln1, ctx)
        with jax.named_scope(rec.scope):
            x = constrain(x + _post_norm(cfg, lp, "ln1_post", proj))
    if kind is not None and kind[1] == ABSENT:
        return x, state, {}
    ff = _ff_part(cfg, sparse)
    with jax.named_scope(ff):
        ln2 = _norm(cfg, x, lp["ln2"]["scale"], lp["ln2"].get("bias"))
    mlp_out, aux = _mlp_with_aux(cfg, lp, ln2, ctx.seg_ids, moe_constraint,
                                 sparse, layer_input)
    with jax.named_scope(ff):
        x = constrain(x + _post_norm(cfg, lp, "ln2_post", mlp_out))
    return x, state, aux


def _post_norm(cfg: TransformerConfig, lp: Params, name: str,
               out: jnp.ndarray) -> jnp.ndarray:
    """An operator's output normed before the residual's add, where
    the layer holds that norm (``post_norm``: ``ln1_post`` after the
    mixer, ``ln2_post`` after the feed-forward); as it is elsewhere."""
    if name not in lp:
        return out
    return _norm(cfg, out, lp[name]["scale"], None)


def _remat(cfg: TransformerConfig, block_fn, kept=None):
    """``block_fn`` rematerialised in the backward, where
    ``cfg.gradient_checkpointing`` asks for it: it keeps what
    ``cfg.remat_policy`` names and, whatever that is,
    ``KEPT_RESIDUALS``: the flash kernel's output and log-sum-exp
    (``ops/flash_attention.py:RESIDUAL_NAMES``) and what the two wide
    attention projections made (``PROJECTION_RESIDUALS``). With both
    outputs of the kernel kept the recomputed block's ``flash_fwd``
    has no consumer and is not emitted, and with q and the projected
    output kept neither are ``x @ wq`` and ``attn @ wo``: the backward
    recomputes the norm before attention, k, v, the gate and the
    feed-forward, and runs the kernel's two backward passes. The XLA
    attention path keeps the same two (q is its einsum's operand) and
    no kernel's outputs. ``kept``: the names to keep in
    ``KEPT_RESIDUALS``' place (a looped model's blocks,
    ``KEPT_IN_A_LOOP``)."""
    if not cfg.gradient_checkpointing:
        return block_fn
    return jax.checkpoint(block_fn,
                          policy=_remat_policy(cfg.remat_policy, kept))


@functools.lru_cache(maxsize=None)
def _remat_policy(name: str, kept=None):
    """ONE policy object a name (and kept set, ``KEPT_RESIDUALS`` by
    default): every layer of an unrolled stack then
    carries the same one, and what jax caches by a checkpoint's
    parameters (a traced body's helper functions) is shared between
    the layers as it is under a policy of ``jax.checkpoint_policies``
    itself."""
    policies = jax.checkpoint_policies
    return policies.save_from_both_policies(
        getattr(policies, name),
        policies.save_only_these_names(
            *(KEPT_RESIDUALS if kept is None else kept)))


def rotary_table(cfg: TransformerConfig, positions: jnp.ndarray,
                 op: str = "attention"):
    """(cos, sin) ``positions.shape + (r // 2,)`` of an ``op`` layer's
    rotary embedding (``cfg.rotary_of(op)``), r the values of a head it
    rotates."""
    rc = cfg.rotary_of(op)
    return rotary_freqs(
        positions, cfg.rotated_dim(op), rc.base, rc.factor,
        rc.scaling_type, rc.original_max_positions,
        beta_fast=rc.beta_fast, beta_slow=rc.beta_slow,
        attention_factor=rc.attention_factor)


def _rotary_tables(cfg: TransformerConfig, positions: jnp.ndarray):
    """``{operator: (cos, sin)}``: ONE table for every layer (ones and
    zeros without a rotary embedding) unless the model declares one a
    kind of layer (``rotary_by_operator``)."""
    if cfg.rotary_by_operator is not None:
        return {op: rotary_table(cfg, positions, op)
                for op, rc in cfg.rotary_by_operator.items()
                if rc is not None}
    if cfg.apply_rotary:
        table = rotary_table(cfg, positions)
    else:
        half = cfg.head_dim // 2
        table = (jnp.ones((*positions.shape, half), jnp.float32),
                 jnp.zeros((*positions.shape, half), jnp.float32))
    tables = dict.fromkeys(ATTENTION_OPERATORS, table)
    if cfg.indexer is not None:
        # the layer's embedding again over the indexer's narrower head
        rc = cfg.rotary_of("attention")
        tables["index"] = rotary_freqs(
            positions, cfg.indexer.head_dim, rc.base, rc.factor,
            rc.scaling_type, rc.original_max_positions)
    return tables


def positions_from_segments(seg_ids: jnp.ndarray) -> jnp.ndarray:
    """Position of each token within its segment for packed streams.

    [B, L] int32 -> [B, L] int32. Pad tokens get position 0.
    """
    idx = jnp.arange(seg_ids.shape[1], dtype=jnp.int32)[None, :]
    new_seg = jnp.concatenate(
        [jnp.ones_like(seg_ids[:, :1], dtype=bool),
         seg_ids[:, 1:] != seg_ids[:, :-1]], axis=1)
    seg_start = jax.lax.cummax(jnp.where(new_seg, idx, 0), axis=1)
    return (idx - seg_start).astype(jnp.int32)


# ----------------------------------------------------------------------
# Forward (training / prefill)
# ----------------------------------------------------------------------
def forward(
    cfg: TransformerConfig,
    params: Params,
    input_ids: jnp.ndarray,  # [B, L] int32
    seg_ids: jnp.ndarray,    # [B, L] int32; 0 = padding
    positions: Optional[jnp.ndarray] = None,  # [B, L]; default from seg_ids
    *,
    return_kv: bool = False,
    return_aux: bool = False,
    activation_constraint=None,
    attention_fn=None,
    moe_constraint=None,  # models/sharding.py moe_ep_constraint (EP)
    pipeline=None,  # parallel.pipeline.PipelineContext when pp > 1
    mesh=None,  # what the arrays are sharded over (delta layers)
    return_passes: bool = False,  # a looped model: EVERY pass's states
):
    """Packed forward pass -> final hidden states [B, L, H] (after the
    final norm). Heads are applied separately (`lm_logits`,
    `critic_values`, or fused ops in `realhf_tpu.ops.functional`).

    A looped model (``cfg.n_passes``) returns its LAST pass's hidden
    states, which is what inference, generation and every loss but the
    looped objective read; with ``return_passes`` the first output is
    ``PassStates``: every pass's final hidden state [T, B, L, H] and
    its exit gate's logit [T, B, L] (float32).

    ``activation_constraint`` is an optional fn applied to the residual
    stream each block (sharding constraints; see models/sharding.py).
    ``mesh``: the mesh the parameters and the batch are sharded over,
    None for one device. A delta layer's kernels are partitioned by it
    (``ops/delta_rule.py``: a Mosaic call has no rule by which GSPMD
    could); attention brings its own in ``attention_fn``.
    """
    cdt = jnp.dtype(cfg.compute_dtype)
    constrain = activation_constraint or (lambda t: t)
    with jax.named_scope(P.EMBED):
        if positions is None:
            positions = positions_from_segments(seg_ids)
        x = params["embed"]["wte"].astype(cdt)[input_ids]
        if cfg.uses_absolute_position:
            x = x + params["embed"]["wpe"].astype(cdt)[
                positions + cfg.abs_position_embedding_offset]
        if cfg.normalize_embed:
            x = x * jnp.asarray(cfg.hidden_dim ** 0.5, dtype=cdt)
        x = constrain(x)

    with jax.named_scope(P.ATTN_PROJ):
        rotary = _rotary_tables(cfg, positions)
    # (a model of one block has ONE table and window for every layer)
    ctx = Ctx(rotary, cfg.sliding_window, seg_ids=seg_ids,
              attention_fn=attention_fn)

    if pipeline is not None and pipeline.n_stages > 1:
        cfg.require_one_block(
            "pipeline parallelism (parallel/pipeline.py, schedule.py)")
        if cfg.n_passes > 1 or cfg.exit_gate:
            raise NotImplementedError(
                "a looped model (n_passes, exit_gate) on a "
                "pipeline-parallel mesh: a stage would be walked once a "
                "pass")
        cos, sin = rotary["attention"]
        # Pipeline parallelism: blocks are stage-sharded over the
        # "pipe" mesh axis and run as a microbatch-rotation schedule
        # (parallel/pipeline.py). Embedding/rotary above and head/norm
        # below stay GSPMD with pipe-replicated weights.
        assert not return_kv, (
            "KV-cache prefill on a pipeline-parallel mesh is not "
            "supported; allocate generation MFCs on a dp/tp layout "
            "(decoupled allocation).")
        from realhf_tpu.parallel.pipeline import pipeline_blocks

        def pblock(lp, layer_idx, carry, seg, cos_, sin_):
            y, _, aux = _block(
                cfg, lp, carry, ctx._replace(
                    rotary={"attention": (cos_, sin_)}, seg_ids=seg,
                    layer_idx=layer_idx), constrain, moe_constraint)
            # the schedules add every aux entry up over ticks and
            # stages: right for the losses, not for a maximum
            for stat in STATS:
                aux.pop(stat, None)
            return y, aux

        # Nested remat for the 1F1B-class memory profile: each block
        # checkpoints its internals AND (pipeline_remat="tick") each
        # tick's whole slab evaluation checkpoints again, so the tick
        # scan's resident residuals are single boundary activations
        # while a tick's backward recompute holds only per-block
        # inputs transiently. The tick-level checkpoint
        # (parallel/pipeline.py) keeps NOTHING, the flash kernel's
        # residuals neither: it recomputes the whole slab by design,
        # and the blocks of that recomputation keep theirs (_remat).
        remat_tick = (cfg.gradient_checkpointing
                      and cfg.pipeline_remat == "tick")
        pblock = _remat(cfg, pblock)

        def block_step(slab, layer_ids, xc, segc, cosc, sinc):
            def body(carry, layer):
                lp, li = layer
                y, aux = pblock(lp, li, carry, segc, cosc, sinc)
                return y, aux
            with jax.named_scope(P.LAYERS):
                y, auxs = jax.lax.scan(body, xc, (slab, layer_ids))
            return y, {k: v.sum() for k, v in auxs.items()}

        if getattr(pipeline, "schedule", "gpipe") == "1f1b":
            # Steady-state 1F1B: explicit instruction streams with a
            # custom-VJP backward pipeline and bounded residuals
            # (parallel/schedule.py). Tick-level remat is moot here --
            # the backward already recomputes each stage-tick from its
            # saved boundary input.
            from realhf_tpu.parallel.schedule import pipeline_blocks_1f1b
            x, aux = pipeline_blocks_1f1b(
                pipeline, params["blocks"], cfg.n_layers, x, seg_ids,
                cos, sin, block_step, return_aux=return_aux)
        else:
            x, aux = pipeline_blocks(
                pipeline, params["blocks"], cfg.n_layers, x, seg_ids,
                cos, sin, block_step, return_aux=return_aux,
                remat_tick=remat_tick)
        x = _final_norm(cfg, params, x)
        if return_aux:
            return x, None, aux
        return x, None

    if cfg.layer_pattern is not None:
        with jax.named_scope(P.LAYERS):
            x, states, aux = _pattern_layers(
                cfg, params["layers"], x, ctx._replace(mesh=mesh),
                constrain, moe_constraint, return_kv, return_aux)
        x = _final_norm(cfg, params, x)
        return (x, states, aux) if return_aux else (x, states)

    if return_passes and not cfg.exit_gate:
        raise ValueError("return_passes: the model has no exit gate")

    def block_fn(lp, layer_idx, carry):
        # cfg/constrain are non-array closures; ctx's seg_ids and
        # tables are array closures -- jax.checkpoint differentiates
        # through closed-over arrays correctly.
        return _block(cfg, lp, carry, ctx._replace(layer_idx=layer_idx),
                      constrain, moe_constraint)

    if cfg.n_passes > 1 or cfg.exit_gate:
        assert not return_aux  # (a looped model has no experts)
        return _passes(cfg, params, x, block_fn, return_kv, return_passes)

    block_fn = _remat(cfg, block_fn)

    def scan_body(carry, layer):
        lp, layer_idx = layer
        y, kv, aux = block_fn(lp, layer_idx, carry)
        return y, (kv if return_kv else None,
                   aux if return_aux else None)

    # the scan's own operations (a layer's weights out of the stack,
    # what the backward keeps a layer) lower under the scope it is
    # called in; the blocks' parts nest inside
    with jax.named_scope(P.LAYERS):
        layer_ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        x, (kvs, auxs) = jax.lax.scan(scan_body, x,
                                      (params["blocks"], layer_ids))
    x = _final_norm(cfg, params, x)
    if return_aux:
        return x, kvs, reduce_layers(auxs or {})
    return x, kvs


class PassStates(NamedTuple):
    """What a looped model's forward hands the looped objective."""
    hidden: jnp.ndarray  # [T, B, L, H]: x^t, every pass's final norm's output
    gate: jnp.ndarray    # [T, B, L] float32: the exit gate's logit on x^t


def _passes(cfg: TransformerConfig, params: Params, x: jnp.ndarray,
            block_fn, return_kv: bool, return_passes: bool):
    """A looped model's ``cfg.n_passes`` passes over the one stack
    ``params["blocks"]``: x^0 [B, L, H] -> (x^T, or ``PassStates`` with
    ``return_passes``; K and V of every pass [T x n_layers, B, L, nkv,
    hd], pass t layer l at ``t x n_layers + l``, or None).
    ``block_fn(lp, layer_idx, x)``: one block, NOT yet rematerialised.

    A loop of passes around the scan of layers. Pass t runs every layer
    on x^(t-1), then the final norm: ``x^t = norm(h; ln_f)`` is what
    the next pass starts from, what the head of pass t reads and what
    the exit gate scores. The passes are written out (T scans of the
    layers in the program, T is small): a scan of passes makes the
    compiler hold a pass's kept residuals twice, in the layer scan's
    own stack and in the stack of passes it is copied to and sliced
    from (0.4 GB at Ouro-2.6B's six layers and rows of 4096, which the
    cell does not have; PERF.md, PR 53).

    The backward keeps, a layer a pass, a rematerialised block's input
    and ``KEPT_IN_A_LOOP`` (the flash kernel's output and log-sum-exp),
    T x n_layers layer applications' worth, and a pass's un-normed h;
    no more. q and the projected output, which a stack that runs once
    keeps too (``PROJECTION_RESIDUALS``), are made again: T times their
    rows did not fit beside 20 bytes a parameter.

    A shared weight's gradient is the SUM of the passes' gradients.
    Left to the layer scans' transposes it would be each pass's stacked
    gradients, a stack of zeros to write them into and a running sum
    beside them (the compiler counted 1.5 GB more for them at six
    layers). Here the sum is ONE accumulator that every layer scan
    carries (``_gradient_accumulator``): a layer's weights leave their
    stack through ``_layer_of``, whose transpose adds the layer's
    gradient into the accumulator's row IN PLACE. The additions are
    taken in the accumulator's dtype, which is the parameters' (bf16 in
    a bf16 engine, whose float32 accumulator then adds the
    microbatches' sums): a row is rounded once a pass, T - 1 roundings
    of 2^-9 beside what a bf16 backward loses anyway;
    ``tests/model/test_ouro.py`` bounds it against the float32
    reference. The loop's own operations lower under ``layers/loop``
    (obs/parts.py:LOOP), a layer scan's under ``layers``, the gate's
    under ``exit``."""
    blocks, grads = _gradient_accumulator(params["blocks"])

    def block_at(grads, layer_idx, carry):
        lp, grads = _layer_of(blocks, grads, layer_idx)
        y, kv, _ = block_fn(lp, layer_idx, carry)
        return y, kv, grads

    block_at = _remat(cfg, block_at, KEPT_IN_A_LOOP)

    def one_layer(carry, layer_idx):
        y, kv, grads = block_at(carry[1], layer_idx, carry[0])
        return (y, grads), (kv if return_kv else None)

    def tail(h):
        xt = _final_norm(cfg, params, h)
        return xt, (exit_logit(cfg, params, xt) if return_passes else None)

    tail = _remat(cfg, tail, ())
    states, kvs = [], []
    with jax.named_scope(P.LAYERS), jax.named_scope(P.LOOP):
        for _ in range(cfg.n_passes):
            with jax.named_scope(P.LAYERS):
                (h, grads), kv = jax.lax.scan(
                    one_layer, (x, grads),
                    jnp.arange(cfg.n_layers, dtype=jnp.int32))
            x, gate = tail(h)
            states.append((x, gate))
            kvs.append(kv)
        if return_passes:
            x = PassStates(*(jnp.stack(s) for s in zip(*states)))
        # [nl, ...] a pass -> [T x nl, ...]
        kvs = jax.tree.map(lambda *a: jnp.concatenate(a), *kvs) \
            if return_kv else None
    return x, kvs


@jax.custom_vjp
def _gradient_accumulator(blocks: Params):
    """(the stacked weights as constants of the loops they enter, an
    accumulator of their shape and dtype, zero). The weights' gradient
    is what has been added into the accumulator when the transposed
    loops hand it back (``_layer_of``)."""
    return blocks, jax.tree.map(jnp.zeros_like, blocks)


def _accumulator_fwd(blocks):
    return _gradient_accumulator(blocks), None


def _accumulator_bwd(_, cotangents):
    # no gradient comes back by the weights themselves: every use of
    # them in the loops is ``_layer_of``, which sends it to the
    # accumulator
    return (cotangents[1],)


_gradient_accumulator.defvjp(_accumulator_fwd, _accumulator_bwd)


@jax.custom_vjp
def _layer_of(blocks: Params, grads: Params, layer_idx: jnp.ndarray):
    """(layer ``layer_idx``'s weights out of their stack, the
    accumulator as it was). Transposed: the layer's gradient is added
    into row ``layer_idx`` of the accumulator, in place and in the
    accumulator's dtype, and nothing goes to ``blocks``."""
    return jax.tree.map(
        lambda a: jax.lax.dynamic_index_in_dim(a, layer_idx, 0,
                                               keepdims=False),
        blocks), grads


def _layer_of_fwd(blocks, grads, layer_idx):
    return _layer_of(blocks, grads, layer_idx), layer_idx


def _layer_of_bwd(layer_idx, cotangents):
    d_layer, d_grads = cotangents

    def add(acc, g):
        row = jax.lax.dynamic_index_in_dim(acc, layer_idx, 0, keepdims=False)
        return jax.lax.dynamic_update_index_in_dim(
            acc, row + g.astype(acc.dtype), layer_idx, 0)

    return None, jax.tree.map(add, d_grads, d_layer), None


_layer_of.defvjp(_layer_of_fwd, _layer_of_bwd)


def exit_logit(cfg: TransformerConfig, params: Params,
               x: jnp.ndarray) -> jnp.ndarray:
    """The exit gate's logit of a pass's final hidden state x [..., H]
    -> [...] float32: ``x . w + b`` (``lambda = sigmoid`` of it)."""
    with jax.named_scope(P.EXIT):
        g = params["exit_gate"]
        logit = jnp.einsum("...h,ho->...o", x, g["w"].astype(x.dtype),
                           preferred_element_type=jnp.float32)[..., 0]
        return logit + g["b"].astype(jnp.float32)[0]


def _final_norm(cfg: TransformerConfig, params: Params,
                x: jnp.ndarray) -> jnp.ndarray:
    """The norm after the last block: it goes with the head."""
    with jax.named_scope(P.VOCAB_HEAD):
        return _norm(cfg, x, params["ln_f"]["scale"],
                     params["ln_f"].get("bias"))


def _pattern_layers(cfg, layers, x, ctx, constrain, moe_constraint,
                    return_kv, return_aux):
    """The layers of a patterned model, unrolled: x -> (x, states,
    aux). Each layer's operator takes the window its kind says and the
    rotary table of its kind (``ctx``: what every layer is handed).
    ``states`` (for prefill; None unless ``return_kv``): by cache key,
    what the layers that own the key returned, stacked over THOSE
    layers (``models/operators.py``): K and V over the attention
    layers [n_attn, B, L, nkv, hd] (V ``v_head_dim`` wide where the
    layers are latent), then every record's own (the conv layers'
    convolution inputs [n_conv, B, L, H], the delta layers'
    [n_delta, B, L, 3 x width] and their rows' last states
    [n_delta, B, n, hd, hd], ...); a key no layer owns is not there.
    ``aux``: the sparse layers' entries reduced as
    ``ops.moe.reduce_layers`` does; ``{}`` unless ``return_aux``."""
    kept, auxs = collections.defaultdict(list), []
    for i, kind in enumerate(cfg.layer_pattern):

        def block_fn(lp, carry, i=i, kind=kind):
            return _block(cfg, lp, carry, ctx._replace(
                window=cfg.layer_window(i), layer_idx=jnp.int32(i)),
                constrain, moe_constraint, kind)

        x, state, aux = _remat(cfg, block_fn)(layers[str(i)], x)
        if return_kv:
            for key, rows in zip(OPERATORS[kind[0]].cache_keys, state):
                kept[key].append(rows)
        if aux:
            auxs.append(aux)
    states = None
    if return_kv:
        states = {key: jnp.stack(kept[key])
                  for key in ("k", "v", *(st.key for st, _, _ in
                                          O.states(cfg))) if key in kept}
    aux = {}
    if return_aux and auxs:
        aux = reduce_layers({k: jnp.stack([a[k] for a in auxs])
                             for k in auxs[0]})
    return x, states, aux


def lm_logits(cfg: TransformerConfig, params: Params,
              hidden: jnp.ndarray) -> jnp.ndarray:
    """[..., H] -> [..., V] logits in fp32 (tp-padded vocab entries,
    if any, are sliced away so they are never sampled)."""
    with jax.named_scope(P.VOCAB_HEAD):
        w = head_weight(cfg, params)
        logits = jnp.einsum("...h,hv->...v", hidden,
                            w.astype(hidden.dtype),
                            preferred_element_type=jnp.float32)
        if logits.shape[-1] != cfg.vocab_size:
            logits = logits[..., :cfg.vocab_size]
        return logits


def head_weight(cfg: TransformerConfig, params: Params) -> jnp.ndarray:
    if cfg.is_critic:
        return params["head"]["w"]
    if cfg.tied_embedding:
        return params["embed"]["wte"].T
    return params["head"]["w"]


def critic_values(cfg: TransformerConfig, params: Params,
                  hidden: jnp.ndarray) -> jnp.ndarray:
    """[..., H] -> [...] scalar values in fp32."""
    assert cfg.is_critic
    with jax.named_scope(P.VOCAB_HEAD):  # the critic's head is its head
        w = params["head"]["w"]
        return jnp.einsum("...h,ho->...o", hidden, w.astype(hidden.dtype),
                          preferred_element_type=jnp.float32)[..., 0]


# ----------------------------------------------------------------------
# KV cache + decode step (generation)
# ----------------------------------------------------------------------
# Cache layout is HEAD-MAJOR: k/v are [nl, B, nkv, S, hd] so the decode
# attention kernel streams a layer's rows straight from HBM with no
# transpose on the hot path (latent layers: the EXPANDED keys, hd wide,
# and values, v_head_dim wide, a head; the 576-wide latent row itself
# is not what is cached, ROADMAP R3c). The slot axis is pre-padded to a multiple
# of the kernel's K block so per-token calls never concat-pad.
_CACHE_LEN_MULTIPLE = 128
# Below this depth the decode layer loop is unrolled (XLA schedules
# across layers); deeper models use a lax.scan to keep compile time
# O(1). Both hand the attention kernel the WHOLE stacked cache and a
# layer index: a static index into the stack is no free view on the
# chip (see decode_step).
_DECODE_UNROLL_MAX_LAYERS = 48


def round_cache_len(n: int) -> int:
    """Round a KV-cache slot count up to the kernel-friendly multiple."""
    if n <= _CACHE_LEN_MULTIPLE:
        return n
    return -(-n // _CACHE_LEN_MULTIPLE) * _CACHE_LEN_MULTIPLE


def init_kv_cache(cfg: TransformerConfig, batch: int, max_len: int,
                  dtype=None) -> KVCache:
    """Padded KV cache sized max_prompt_len + max_new_tokens, matching
    reference `prepare_generate_inputs` (real_llm_generate.py:179)."""
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    max_len = round_cache_len(max_len)
    shape = (cfg.kv_layers, batch, cfg.n_kv_heads, max_len)
    cache = {
        "k": jnp.zeros(shape + (cfg.head_dim,), dtype),
        "v": jnp.zeros(shape + (cfg.v_head_dim,), dtype),
        "valid": jnp.zeros((batch, max_len), bool),
        "length": jnp.zeros((batch,), jnp.int32),
    }
    for st, _, n in O.states(cfg):  # what the layers keep beside K and V
        cache[st.key] = jnp.zeros((n, *st.shape(cfg, batch, max_len)),
                                  st.dtype or dtype)
    return cache


def prefill(cfg: TransformerConfig, params: Params, input_ids: jnp.ndarray,
            seg_ids: jnp.ndarray, positions: Optional[jnp.ndarray] = None,
            *, total_len: Optional[int] = None, activation_constraint=None,
            attention_fn=None, moe_constraint=None,
            mesh=None) -> Tuple[jnp.ndarray, KVCache]:
    """Run the packed forward and materialize a KV cache whose first
    L slots hold the prompt keys/values.

    ``total_len``: allocate the cache at its final decode size
    (prompt + max_new_tokens, rounded up to the kernel block) in ONE
    pad here, instead of a post-hoc `extend_kv_cache` concat copy."""
    hidden, kvs = forward(cfg, params, input_ids, seg_ids, positions,
                          return_kv=True,
                          activation_constraint=activation_constraint,
                          attention_fn=attention_fn,
                          moe_constraint=moe_constraint, mesh=mesh)
    b, lp = input_ids.shape
    with jax.named_scope(P.ATTN):  # the caches' layout is the kernels'
        cache = _prefill_cache(cfg, kvs, seg_ids, b, lp, total_len,
                               hidden.dtype)
    return hidden, cache


def _prefill_cache(cfg, kvs, seg_ids, b, lp, total_len, dtype) -> KVCache:
    """``prefill``'s cache from the states ``forward`` returned."""
    total = round_cache_len(total_len if total_len is not None else lp)
    more = {}
    if cfg.layer_pattern is None:
        k, v = kvs  # [nl, B, L, nkv, hd] (a looped model: nl a pass)
    else:
        # what each record says of its own states (of a convolution's
        # input the rows' last rows, a row's state after its last
        # token, ...), then K and V of the attention layers alone
        more = {st.key: st.fill(cfg, kvs[st.key], seg_ids, total, dtype)
                for st, _, _ in O.states(cfg)}
        k, v = kvs.get("k"), kvs.get("v")
        if k is None:
            k = v = jnp.zeros((0, b, lp, cfg.n_kv_heads, cfg.head_dim),
                              dtype)
    k = k.transpose(0, 1, 3, 2, 4)  # -> [nl, B, nkv, L, hd] head-major
    v = v.transpose(0, 1, 3, 2, 4)
    valid = seg_ids != 0
    pad = total - lp
    if pad:
        widths = [(0, 0), (0, 0), (0, 0), (0, pad), (0, 0)]
        k = jnp.pad(k, widths)
        v = jnp.pad(v, widths)
        valid = jnp.pad(valid, [(0, 0), (0, pad)])
    cache = {
        "k": k,
        "v": v,
        "valid": valid,
        "length": jnp.full((b,), lp, jnp.int32),
    }
    return {**cache, **more}


def extend_kv_cache(cache: KVCache, extra: int) -> KVCache:
    """Grow the cache along the slot axis by `extra` zero slots.

    Prefer ``prefill(..., total_len=...)`` which allocates the final
    size up front; this concat path remains for incremental callers."""
    _, b, _, s, _ = cache["k"].shape
    new_s = round_cache_len(s + extra)
    extra = new_s - s
    pad = lambda a: jnp.concatenate(
        [a, jnp.zeros(a.shape[:3] + (extra, a.shape[4]), a.dtype)], axis=3)
    more = {}
    for rec in OPERATORS.values():
        for st in rec.state:
            if st.slots is not None and st.key in cache:
                widths = [(0, 0)] * cache[st.key].ndim
                widths[st.slots] = (0, extra)
                more[st.key] = jnp.pad(cache[st.key], widths)
    return {
        **cache,  # length, and a patterned model's other states
        **more,
        "k": pad(cache["k"]),
        "v": pad(cache["v"]),
        "valid": jnp.concatenate(
            [cache["valid"], jnp.zeros((b, extra), bool)], axis=1),
    }


def _decode_layers(cfg, params, layer_body, x, k_all, v_all, first=0,
                   depth=None):
    """One walk of a decode step over the stacked blocks: ``layer_body(
    x, k_all, v_all, lp, l)`` a layer, layer i at row ``first + i`` of
    the K/V stack (``first``: a looped model's pass times its layers).
    Unrolled while the program's ``depth`` of layer bodies (the layers,
    times a looped model's passes) is shallow, else a scan."""
    depth = cfg.n_layers if depth is None else depth
    if depth <= _DECODE_UNROLL_MAX_LAYERS:
        for li in range(cfg.n_layers):
            with jax.named_scope(P.LAYERS):
                lp = jax.tree_util.tree_map(lambda a: a[li],
                                            params["blocks"])
            x, k_all, v_all = layer_body(x, k_all, v_all, lp, first + li)
        return x, k_all, v_all

    def body(carry, layer):
        return layer_body(*carry, *layer), None

    with jax.named_scope(P.LAYERS):
        layer_ids = jnp.arange(cfg.n_layers, dtype=jnp.int32)
        if first:
            layer_ids = layer_ids + first
        (x, k_all, v_all), _ = jax.lax.scan(
            body, (x, k_all, v_all), (params["blocks"], layer_ids))
    return x, k_all, v_all


def decode_step(
    cfg: TransformerConfig,
    params: Params,
    cache: KVCache,
    token: jnp.ndarray,      # [B] int32 -- the token to feed
    positions: jnp.ndarray,  # [B] int32 -- its position in the sequence
    moe_constraint=None,
    uniform_slot: bool = False,
    mesh=None,  # dp x tp mesh: partitions the pallas decode kernels
) -> Tuple[jnp.ndarray, KVCache]:
    """One decode step: feed `token`, return hidden [B, H] for the next
    token's logits and the updated cache. The jitted decode loop built
    on this replaces CUDA-graph decoding (reference
    real_llm_generate.py:214, cuda_graph.py).

    The stacked k/v caches stay whole through the layer loop and only
    the new token's slot is written per layer (`dynamic_update_slice`
    aliases in place inside the decode scan) -- threading them through
    a `lax.scan` as xs/ys would re-materialize the entire cache as a
    fresh stacked output every token, ~3x the roofline's intended HBM
    traffic. Shallow models unroll the layer loop, deep models scan;
    either way the scalar-prefetch attention kernel takes the whole
    stack and the layer index. ``k_all[l]`` at a static ``l`` looks
    like a free view and is not one on the chip: XLA made it a slice
    plus a transposing copy of the layer's cache, 2 x 24 times a
    token on Qwen2.5-0.5B, and chose a slot-minor layout for the
    stack that made the token's write 13 times dearer; together 43%
    of the generate program's device time (PERF.md, PR 30). With the
    kernel as the cache's consumer the loop's carry stays row-major.

    ``uniform_slot``: promise that every stream writes the SAME cache
    slot (true for the batch generate path, where prefill fills a
    common padded length and all streams advance in lockstep). The
    cache update then lowers to `dynamic_update_slice` instead of a
    per-row scatter -- on a v5e the scatter costs ~0.25 ms per stream
    per step, dominating decode beyond bs~16. Continuous batching
    (per-slot lengths) keeps the scatter path."""
    cdt = jnp.dtype(cfg.compute_dtype)
    b = token.shape[0]
    slot = cache["length"]  # write position per stream

    with jax.named_scope(P.EMBED):
        x = params["embed"]["wte"].astype(cdt)[token]
        if cfg.uses_absolute_position:
            x = x + params["embed"]["wpe"].astype(cdt)[
                positions + cfg.abs_position_embedding_offset]
        if cfg.normalize_embed:
            x = x * jnp.asarray(cfg.hidden_dim ** 0.5, dtype=cdt)

    with jax.named_scope(P.ATTN_PROJ):
        rotary = _rotary_tables(cfg, positions)

    with jax.named_scope(P.ATTN):  # the cache's bookkeeping
        if uniform_slot:
            s0 = slot[0]
            valid = jax.lax.dynamic_update_slice(
                cache["valid"], jnp.ones((b, 1), bool), (0, s0))
        else:
            valid = cache["valid"].at[jnp.arange(b), slot].set(True)
        new_len = slot + 1

    ctx = Ctx(rotary, cfg.sliding_window, mesh, valid=valid, slot=slot,
              s0=s0 if uniform_slot else None)

    def op_step(op, x, lp, rows, ctx):
        # one token of a layer's operator: the norm before it, its step
        # on its rows of the cache, the residual's add
        rec = OPERATORS[op]
        with jax.named_scope(rec.scope):
            ln1 = _norm(cfg, x, lp["ln1"]["scale"], lp["ln1"].get("bias"))
        proj, rows = rec.step(cfg, lp, ln1, rows, ctx)
        with jax.named_scope(rec.scope):
            return x + _post_norm(cfg, lp, "ln1_post", proj), rows

    def layer_body(x, k_all, v_all, lp, l):
        # a layer of a model of one block; l: its place in the K/V
        # stack, a Python int (unrolled) or a traced scalar
        x, (k_all, v_all) = op_step("attention", x, lp, (k_all, v_all),
                                    ctx._replace(l=l))
        return _ff_step(x, lp, None), k_all, v_all

    def _ff_step(x, lp, sparse, layer_input=None):
        # the norm, the feed-forward and the residual's add, one part
        # (a layer that is a mixer alone holds no "mlp" and has none);
        # ``layer_input``: the token before the layer's operator, which
        # a router may read (``MoEConfig.router_input``)
        if "mlp" not in lp:
            return x
        with jax.named_scope(_ff_part(cfg, sparse)):
            ln2 = _norm(cfg, x, lp["ln2"]["scale"], lp["ln2"].get("bias"))
            return x + _post_norm(cfg, lp, "ln2_post",
                                  _mlp(cfg, lp, ln2, moe_constraint,
                                       sparse, layer_input))

    k_all, v_all = cache["k"], cache["v"]
    # what the layers keep beside K and V: a state that grows with the
    # slots as its whole stack (a step writes the token's slot in
    # place), another a layer at a time, stacked anew below
    grown = {st.key: cache[st.key] for st, _, _ in O.states(cfg)
             if st.slots is not None}
    fresh = collections.defaultdict(list)
    if cfg.layer_pattern is not None:
        # a layer of the pattern at a time: an attention layer reads
        # and writes ITS slice of the K/V stack (the stack holds the
        # attention layers alone, window layers with EVERY row: the
        # kernel masks what is past the window), another operator its
        # own states (``models/operators.py``); a layer without an
        # operator is its feed-forward
        at, n_kv = collections.Counter(), 0  # an operator's layers so far
        for i, (op, ff) in enumerate(cfg.layer_pattern):
            lp, rec = params["layers"][str(i)], OPERATORS[op]
            layer_input = x
            if rec.step is not None:
                mine = [st.key for st in rec.state]
                with jax.named_scope(rec.scope):  # (its slices are its)
                    rows = ((k_all, v_all) if rec.kv else ()) + tuple(
                        grown[key] if key in grown else cache[key][at[op]]
                        for key in mine)  # as ``rec.cache_keys``
                x, rows = op_step(op, x, lp, rows, ctx._replace(
                    window=cfg.layer_window(i), l=n_kv, at=at[op]))
                if rec.kv:
                    k_all, v_all, *rows = rows
                    n_kv += 1
                for key, new in zip(mine, rows):
                    if key in grown:
                        grown[key] = new
                    else:
                        fresh[key].append(new)
                at[op] += 1
            x = _ff_step(x, lp, ff == "moe", layer_input)
    elif cfg.n_passes > 1:
        # a looped model: every pass over the same weights, each with
        # ITS rows of the K/V stack (pass t layer l at t x n_layers +
        # l), the final norm after every pass; at the published exit
        # threshold of 1 no token leaves early, so all passes run and
        # the gate is not asked
        for t in range(cfg.n_passes):
            x, k_all, v_all = _decode_layers(
                cfg, params, layer_body, x, k_all, v_all,
                t * cfg.n_layers, cfg.kv_layers)
            if t < cfg.n_passes - 1:
                x = _final_norm(cfg, params, x)
    else:
        x, k_all, v_all = _decode_layers(cfg, params, layer_body, x,
                                         k_all, v_all)
    x = _final_norm(cfg, params, x)
    new_cache = {"k": k_all, "v": v_all, "valid": valid, "length": new_len}
    for st, rec, _ in O.states(cfg):
        with jax.named_scope(rec.scope):
            new_cache[st.key] = grown[st.key] if st.key in grown \
                else jnp.stack(fresh[st.key])
    return x, new_cache
