"""The single transformer implementation used for every model role.

TPU-native counterpart of reference ``realhf/impl/model/nn/
real_llm_api.py`` (ReaLModel) + ``real_llm_base.py`` + ``modules/``:
one decoder-only transformer covering actor / critic / reference /
reward roles (critic mode swaps the LM head for a scalar value head).

Design (idiomatic JAX, not a torch translation):
- Parameters are a plain dict pytree with **stacked** block weights
  (leading axis = layer). The whole stack is scanned with
  ``jax.lax.scan``, which keeps compile time O(1) in depth and makes
  resharding between meshes a single device_put of the pytree.
- Batches are packed streams ``[B, L]`` with segment ids (0 = pad);
  positions are derived per segment. DP shards B; TP shards heads and
  MLP; Megatron-style sequence parallelism falls out of GSPMD sharding
  constraints (see models/sharding.py).
- Generation uses a per-layer KV cache pytree and a single-token
  decode step; the jitted decode loop replaces CUDA-graph capture
  (reference ``nn/real_llm_generate.py:214``).
- A model whose layers are NOT all of one kind
  (``TransformerConfig.layer_pattern``: gated short convolutions among
  attention layers, a dense lead before sparse layers) cannot stack
  its weights: it holds a tree a layer under ``params["layers"]``
  (``{"0": ..., "1": ...}``) and every layer loop below is unrolled
  over the pattern, each layer computing what its (operator,
  feed-forward) says. Its cache is two kinds of state side by side:
  K and V for the attention layers alone (stacked over THOSE), and
  the last ``conv_kernel - 1`` rows of the convolution's input for
  each conv layer. Its attention layers may differ a layer: full or
  over a window of ``sliding_window`` tokens (operator "window"),
  their count of query heads (``layer_q_heads``; K and V keep one
  shape), the rotary table of their kind (``rotary_by_operator``).
  Or they are LATENT (operator "latent", ``LatentConfig``): keys and
  values expanded from one compressed row a token, the key
  ``head_dim`` wide (its last ``rope_dim`` values one rotary part all
  heads share), the value ``v_head_dim``; attention, the flash kernels
  and the cache (K rows of one width, V rows of the other) take both.
  Or a layer keeps no keys and values at all (operator "delta",
  ``DeltaConfig``): a head's gated delta-rule state, chunked over the
  row by ``ops/delta_rule.py`` and reset at a document's first token;
  decoding carries that state [heads, hd, hd] in float32 and the last
  rows of its three short convolutions' inputs, a THIRD kind of decode
  state beside K/V and ``cache["conv"]``. A latent layer may have no
  rotary embedding (``rotary_by_operator["latent"] = None``).
  Or a layer's attention runs over the keys a learned indexer picks
  (operator "sparse", ``IndexerConfig``): the selection [B, L, L] is
  one more operand of the attention function beside ``seg_ids``, and
  the indexer's keys are a THIRD kind of attention cache
  (``cache["index_k"]``, one ``head_dim``-wide row a token a layer)
  beside K and V, which decoding scores, selects from and attends over.
  Or a layer is the Mamba-2 state-space mixer (operator "ssm",
  ``SsmConfig``): a head's state [head_dim, state] under one decay a
  head, chunked over the row by ``ops/ssm_scan.py``; decoding carries
  it in float32 (``cache["ssm"]``) beside the last rows of its ONE
  convolution's input (``cache["ssm_conv"]``), a FOURTH kind of decode
  state. And a layer may be a mixer OR a feed-forward ALONE (the other
  part ``ABSENT`` in its pattern entry): it then holds one norm
  (``ln1`` a mixer's, ``ln2`` a feed-forward's) and the leaves of its
  one part, and ``_block`` runs that part and its residual add only.
  A feed-forward without a gate (``mlp_type`` None: ``wu`` and ``wd``
  alone, dense, shared or an expert's) is two products.
  A model of one block takes none of these paths.
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

import functools
from typing import Any, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from realhf_tpu.base.backend import pallas_enabled
from realhf_tpu.models.config import (ABSENT, DELTA_L2_EPS, INDEX_NORM_EPS,
                                      LATENT_NORM_EPS, TransformerConfig)
from realhf_tpu.obs import parts as P
from realhf_tpu.ops.attention import decode_attention, packed_attention
from realhf_tpu.ops.delta_rule import RESIDUAL_NAMES as _SCAN_RESIDUALS
from realhf_tpu.ops.delta_rule import (Prepare, chunked_delta_rule,
                                       delta_rule_step)
from realhf_tpu.ops.flash_attention import RESIDUAL_NAMES, SELECT_RESIDUAL
from realhf_tpu.ops.rotary import apply_rotary, rotary_freqs
from realhf_tpu.ops.sparse_index import (index_scores, select_topk,
                                         selection_mask)
from realhf_tpu.ops.ssm_scan import RESIDUAL_NAMES as _SSM_SCAN_RESIDUALS
from realhf_tpu.ops.ssm_scan import chunked_ssm_scan, ssm_step

Params = Dict[str, Any]
KVCache = Dict[str, jnp.ndarray]
#: What only an attention layer's two WIDE projection products can
#: make, by the names ``_attention_op`` gives them
#: (``checkpoint_name``): q as the attention function takes it (after
#: bias, query/key norm and rotary) and the projected output after
#: ``wo`` and its bias. A rematerialised block keeps them
#: (``_remat``), so its backward runs neither ``attn @ wo`` nor,
#: where no query norm's backward needs q before the norm, ``x @ wq``
#: a second time: ``tokens x (q width + hidden) x 2`` bytes a layer a
#: microbatch in bf16. k and v stay recomputed: kept too they took
#: Laguna-XS.2's five-layer step from 13.87 to 14.00 GB of a chip's 16
#: for 0.6% of its tokens a second (PERF.md, PR 36).
PROJECTION_RESIDUALS = ("attn_q", "attn_proj_out")
#: What a delta layer's chunked recurrence made (``_delta_op``): its
#: heads' outputs, ``tokens x width`` values a layer a microbatch in
#: the compute dtype, and, where the recurrence is the kernels', what
#: its forward hands its backward (``ops/delta_rule.py:
#: RESIDUAL_NAMES``: every chunk's start state in float32). Kept, the
#: rematerialised block does not run the recurrence a second time: not
#: for its OUTPUT, and not for the backward kernel's sake. (The XLA
#: path names the output alone: its own backward runs it again a
#: segment at a time.)
DELTA_RESIDUALS = ("delta_out",) + _SCAN_RESIDUALS
#: What an ssm layer's chunked scan made (``_ssm_op``): its heads'
#: outputs before the gate, ``tokens x width`` values a layer a
#: microbatch in the compute dtype (with the projected output,
#: ``PROJECTION_RESIDUALS[1]``, ``tokens x (width + hidden) x 2``
#: bytes in bf16), and, where the scan is the kernels', what its
#: forward hands its backward (``ops/ssm_scan.py:RESIDUAL_NAMES``:
#: every chunk's start states in float32, ``heads x head_dim x state x
#: 4`` bytes a chunk of 128 tokens: 67 MB a layer a row of 4096 at 64
#: heads of 64 and a state of 128). Kept, the rematerialised block
#: does not run the scan a second time: not for its OUTPUT, and not
#: for the backward kernel's sake. (The XLA path names the output
#: alone: its own backward runs it again a segment at a time.)
SSM_RESIDUALS = ("ssm_out",) + _SSM_SCAN_RESIDUALS
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
    one tree a layer, each with the leaves of its own kind only."""
    pdt = jnp.dtype(cfg.param_dtype)
    h, f, v = cfg.hidden_dim, cfg.intermediate_dim, cfg.vocab_size
    nq, nkv, hd = cfg.n_q_heads, cfg.n_kv_heads, cfg.head_dim
    std = 0.02
    proj_std = std / (2 * cfg.n_layers) ** 0.5
    # a delta layer has more leaves than 16; the others keep the keys
    # they have always drawn
    per_layer = 16 if cfg.delta is None and cfg.indexer is None \
        and cfg.ssm is None else 24
    keys = iter(jax.random.split(key, per_layer * cfg.n_layers + 4))

    def norm(shape, s=std):
        return (s * jax.random.normal(next(keys), shape)).astype(pdt)

    def ones(shape):
        return jnp.ones(shape, dtype=pdt)

    def ffn(f, lead=()):
        # a feed-forward's matrices, gated or not, [*lead, ...]
        gate = {"wg": norm((*lead, h, f))} if cfg.gated_mlp else {}
        return {**gate, "wu": norm((*lead, h, f)),
                "wd": norm((*lead, f, h), proj_std)}

    layers = {}
    for i, (op, ff) in enumerate(cfg.layer_pattern):
        # a part a layer lacks has no norm either
        lp = {name: {"scale": ones((h,))}
              for name, part in (("ln1", op), ("ln2", ff))
              if part != ABSENT}
        if op == ABSENT:
            pass
        elif op == "ssm":
            lp["ssm"] = _init_ssm(cfg, norm, ones, next(keys), pdt,
                                  proj_std)
        elif op == "conv":
            lp["conv"] = {"w_in": norm((h, 3 * h)),
                          "w": norm((cfg.conv_kernel, h)),
                          "w_out": norm((h, h), proj_std)}
        elif op == "delta":
            lp["delta"] = _init_delta(cfg, norm, ones, next(keys), pdt,
                                      proj_std)
        elif op == "latent":
            lat = cfg.latent
            lp["attn"] = {
                "wq": norm((h, nq * hd)),
                "w_kv_a": norm((h, lat.kv_rank + lat.rope_dim)),
                "kv_a_norm": ones((lat.kv_rank,)),
                "w_kv_b": norm((lat.kv_rank,
                                nq * (hd - lat.rope_dim + lat.v_dim))),
                "wo": norm((nq * lat.v_dim, h), proj_std)}
        else:
            nq = cfg.q_heads(i)
            lp["attn"] = {"wq": norm((h, nq * hd)),
                          "wk": norm((h, nkv * hd)),
                          "wv": norm((h, nkv * hd)),
                          "wo": norm((nq * hd, h), proj_std)}
            if cfg.qk_norm is not None:
                heads = (1, 1) if cfg.qk_norm == "head" else (nq, nkv)
                lp["attn"]["q_norm"] = ones((heads[0] * hd,))
                lp["attn"]["k_norm"] = ones((heads[1] * hd,))
            if cfg.attn_output_gate:
                lp["attn"]["w_gate"] = norm((h, nq))
            if op == "sparse":
                ix = cfg.indexer
                lp["index"] = {
                    "wq": norm((h, ix.heads * ix.head_dim)),
                    "wk": norm((h, ix.head_dim)),
                    "k_norm": ones((ix.head_dim,)),
                    "k_norm_bias": jnp.zeros((ix.head_dim,), pdt),
                    "w_weights": norm((h, ix.heads))}
        if ff == "moe":
            ne, nh = cfg.moe.num_experts, cfg.moe.n_held
            fe = cfg.moe.intermediate_dim or f
            lp["mlp"] = {"router": norm((h, ne)), **ffn(fe, (nh,))}
            if cfg.moe.use_expert_bias:
                lp["mlp"]["expert_bias"] = jnp.zeros((ne,), pdt)
            fs = cfg.moe.shared_intermediate_dim
            if fs is not None:
                lp["mlp"]["shared"] = ffn(fs)
        elif ff != ABSENT:
            lp["mlp"] = ffn(f)
        layers[str(i)] = lp
    params: Params = {"embed": {"wte": norm((v, h))}, "layers": layers,
                      "ln_f": {"scale": ones((h,))}}
    if cfg.is_critic:
        params["head"] = {"w": norm((h, 1))}
    elif not cfg.tied_embedding:
        params["head"] = {"w": norm((h, v))}
    return params


def _init_delta(cfg, norm, ones, key, pdt, proj_std) -> Params:
    """A delta layer's leaves (``DeltaConfig`` has the equations). The
    decay's two leaves start as published: ``a_log = log U(1, 16)`` a
    head, ``dt_bias`` the inverse softplus of a step drawn
    log-uniformly from [1e-3, 1e-1], so a channel forgets between
    0.001 and 1.6 a token and a state lives hundreds of tokens."""
    h, dl = cfg.hidden_dim, cfg.delta
    w, r = dl.width, dl.gate_rank
    ka, kd = jax.random.split(key)
    dt = jnp.exp(jax.random.uniform(
        kd, (w,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    return {
        "wq": norm((h, w)), "wk": norm((h, w)), "wv": norm((h, w)),
        "conv_q": norm((dl.conv_kernel, w)),
        "conv_k": norm((dl.conv_kernel, w)),
        "conv_v": norm((dl.conv_kernel, w)),
        "a_log": jnp.log(jax.random.uniform(
            ka, (dl.n_heads,), minval=1.0, maxval=16.0)).astype(pdt),
        "w_fa": norm((h, r)), "w_fb": norm((r, w)),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt),
        "w_b": norm((h, dl.n_heads)),
        "w_ga": norm((h, r)), "w_gb": norm((r, w)),
        "o_norm": ones((dl.head_dim,)),
        "wo": norm((w, h), proj_std)}


def _init_ssm(cfg, norm, ones, key, pdt, proj_std) -> Params:
    """An ssm layer's leaves (``SsmConfig`` has the equations), the
    decay's as published: ``a_log = log U(1, 16)`` a head, ``dt_bias``
    the inverse softplus of a step drawn log-uniformly from [1e-3,
    1e-1], D = 1."""
    h, sm = cfg.hidden_dim, cfg.ssm
    ka, kd = jax.random.split(key)
    dt = jnp.exp(jax.random.uniform(
        kd, (sm.n_heads,), minval=jnp.log(1e-3), maxval=jnp.log(1e-1)))
    return {
        "w_in": norm((h, sm.in_dim)),
        "conv": norm((sm.conv_kernel, sm.conv_dim)),
        "conv_bias": jnp.zeros((sm.conv_dim,), pdt),
        "a_log": jnp.log(jax.random.uniform(
            ka, (sm.n_heads,), minval=1.0, maxval=16.0)).astype(pdt),
        "dt_bias": (dt + jnp.log(-jnp.expm1(-dt))).astype(pdt),
        "d": ones((sm.n_heads,)),
        "norm": ones((sm.width,)),
        "w_out": norm((sm.width, h), proj_std)}


# ----------------------------------------------------------------------
# Building blocks
# ----------------------------------------------------------------------
def _norm(cfg: TransformerConfig, x: jnp.ndarray, scale: jnp.ndarray,
          bias: Optional[jnp.ndarray],
          eps: Optional[float] = None) -> jnp.ndarray:
    """LayerNorm / RMSNorm / gemma-RMSNorm with fp32 accumulation, at
    ``cfg.layer_norm_epsilon`` unless the norm has an ``eps`` of its
    own (a latent's)."""
    eps = cfg.layer_norm_epsilon if eps is None else eps
    xf = x.astype(jnp.float32)
    if cfg.layer_norm_type is None:
        mean = xf.mean(-1, keepdims=True)
        var = jnp.mean((xf - mean) ** 2, -1, keepdims=True)
        out = (xf - mean) * jax.lax.rsqrt(var + eps)
        out = out * scale.astype(jnp.float32)
        if bias is not None:
            out = out + bias.astype(jnp.float32)
    elif cfg.layer_norm_type == "rms":
        var = jnp.mean(xf ** 2, -1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps)
        out = out * scale.astype(jnp.float32)
    elif cfg.layer_norm_type == "gemma":
        var = jnp.mean(xf ** 2, -1, keepdims=True)
        out = xf * jax.lax.rsqrt(var + eps)
        out = out * (1.0 + scale.astype(jnp.float32))
    else:
        raise NotImplementedError(cfg.layer_norm_type)
    return out.astype(x.dtype)


def _activation(cfg: TransformerConfig, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.activation_function == "silu":
        return jax.nn.silu(x)
    if cfg.activation_function == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if cfg.activation_function == "gelu_new":
        return jax.nn.gelu(x, approximate=True)
    if cfg.activation_function == "relu2":
        return jnp.square(jax.nn.relu(x))
    raise NotImplementedError(cfg.activation_function)


def _mlp(cfg: TransformerConfig, lp: Params, x: jnp.ndarray,
         moe_constraint=None, sparse: Optional[bool] = None
         ) -> jnp.ndarray:
    out, _ = _mlp_with_aux(cfg, lp, x, None, moe_constraint, sparse)
    return out


def _mlp_with_aux(cfg: TransformerConfig, lp: Params, x: jnp.ndarray,
                  seg_ids: Optional[jnp.ndarray] = None,
                  moe_constraint=None, sparse: Optional[bool] = None):
    """MLP returning (output, aux dict) -- non-empty only for MoE
    (router load-balancing / z losses, reference utils/moe.py:395,
    and the statistics of ``ops.moe.STATS``).
    ``seg_ids`` masks padding out of MoE routing/capacity/losses.
    ``sparse``: whether THIS layer's feed-forward is the mixture of
    experts; a patterned model says it a layer, a model of one block
    by ``mlp_type``."""
    cdt = jnp.dtype(cfg.compute_dtype)
    m = lp["mlp"]
    if sparse is None:
        sparse = cfg.mlp_type == "moe"
    if sparse:
        from realhf_tpu.ops.moe import moe_mlp_with_losses
        with jax.named_scope(P.EXPERTS):
            squeeze = x.ndim == 2  # decode step: [B, H]
            x3 = x[:, None, :] if squeeze else x
            valid = None if seg_ids is None else (seg_ids != 0)
            out, aux = moe_mlp_with_losses(cfg, m, x3, valid_mask=valid,
                                           ep_constraint=moe_constraint)
            return (out[:, 0] if squeeze else out), aux
    with jax.named_scope(P.MLP):
        return _dense_mlp(cfg, m, x, cdt), {}


def _ff_part(cfg: TransformerConfig, sparse: Optional[bool]) -> str:
    """The part (``obs/parts.py``) a layer's feed-forward, the norm
    before it and the residual's add after it are put down to."""
    if sparse is None:
        sparse = cfg.mlp_type == "moe"
    return P.EXPERTS if sparse else P.MLP


def _dense_mlp(cfg, m, x, cdt):
    if cfg.gated_mlp:
        gate = x @ m["wg"].astype(cdt)
        up = x @ m["wu"].astype(cdt)
        return _activation(cfg, gate) * up @ m["wd"].astype(cdt)
    up = x @ m["wu"].astype(cdt)
    if "bu" in m:
        up = up + m["bu"].astype(cdt)
    out = _activation(cfg, up) @ m["wd"].astype(cdt)
    if "bd" in m:
        out = out + m["bd"].astype(cdt)
    return out


def _qkv(cfg: TransformerConfig, lp: Params, x: jnp.ndarray):
    """q [..., heads, hd], k and v [..., n_kv_heads, hd] of the layer
    ``lp``. ``heads`` is what the layer's own ``wq`` is wide: layers of
    a patterned model may differ in it (``layer_q_heads``)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    a = lp["attn"]
    *lead, _ = x.shape
    q = x @ a["wq"].astype(cdt)
    k = x @ a["wk"].astype(cdt)
    v = x @ a["wv"].astype(cdt)
    if "bq" in a:
        q = q + a["bq"].astype(cdt)
        k = k + a["bk"].astype(cdt)
        v = v + a["bv"].astype(cdt)
    if cfg.qk_norm == "full":
        # over the whole projected width, before the head split and the
        # rotary embedding; under tensor parallelism that width is
        # sharded and the partitioner reduces the mean of squares
        q = _norm(cfg, q, a["q_norm"], None)
        k = _norm(cfg, k, a["k_norm"], None)
    q = q.reshape(*lead, q.shape[-1] // cfg.head_dim, cfg.head_dim)
    k = k.reshape(*lead, cfg.n_kv_heads, cfg.head_dim)
    v = v.reshape(*lead, cfg.n_kv_heads, cfg.head_dim)
    if cfg.qk_norm == "head":
        # over each head's own values, one scale of width head_dim for
        # all heads, before the rotary embedding
        q = _norm(cfg, q, a["q_norm"], None)
        k = _norm(cfg, k, a["k_norm"], None)
    return q, k, v


def _latent_qkv(cfg: TransformerConfig, lp: Params, x: jnp.ndarray,
                cos: jnp.ndarray, sin: jnp.ndarray):
    """q and k [..., heads, head_dim], ROTATED, and v [..., heads,
    v_dim] of the latent layer ``lp`` (``LatentConfig`` has the
    equations): the keys' first ``nope`` values and the values are
    expanded, a head at a time, from the token's normed latent; the
    keys' last ``rope_dim`` are ONE rotated part that every head gets,
    as the queries' last ``rope_dim`` are rotated. What makes k and v
    from the latent is sub-part ``attn_proj/latent`` (obs/parts.py)."""
    cdt = jnp.dtype(cfg.compute_dtype)
    a, lat = lp["attn"], cfg.latent
    *lead, _ = x.shape
    nope = cfg.head_dim - lat.rope_dim
    rc = cfg.rotary_of("latent")

    def rotated(t):
        # no rotary embedding (``rotary_by_operator["latent"] = None``):
        # the queries' last values and the shared key part go to the
        # scores as they are
        return t if rc is None else apply_rotary(t, cos, sin,
                                                 rc.interleaved)

    q = (x @ a["wq"].astype(cdt)).reshape(*lead, -1, cfg.head_dim)
    if rc is not None:
        q = jnp.concatenate([q[..., :nope], rotated(q[..., nope:])],
                            axis=-1)
    with jax.named_scope(P.LATENT):
        kv_a = x @ a["w_kv_a"].astype(cdt)
        c = _norm(cfg, kv_a[..., :lat.kv_rank], a["kv_a_norm"], None,
                  LATENT_NORM_EPS)
        kv = (c @ a["w_kv_b"].astype(cdt)).reshape(
            *lead, -1, nope + lat.v_dim)
        k_rope = rotated(kv_a[..., None, lat.kv_rank:])
        k = jnp.concatenate(
            [kv[..., :nope],
             jnp.broadcast_to(k_rope, (*kv.shape[:-1], lat.rope_dim))],
            axis=-1)
    return q, k, kv[..., nope:]


def _rotated_qkv(cfg: TransformerConfig, lp: Params, x: jnp.ndarray,
                 cos: jnp.ndarray, sin: jnp.ndarray, op: str):
    """q, k and v of an ``op`` layer as attention takes them:
    projected, normed, and q and k rotated by the kind's table."""
    if op == "latent":
        return _latent_qkv(cfg, lp, x, cos, sin)
    q, k, v = _qkv(cfg, lp, x)
    # (a kind of layer WITHOUT a rotary embedding has no table: its
    # queries and keys go to the scores as they are)
    if cfg.apply_rotary and cos is not None:
        interleaved = cfg.rotary_of(op).interleaved
        q = apply_rotary(q, cos, sin, interleaved)
        k = apply_rotary(k, cos, sin, interleaved)
    return q, k, v


def _short_conv(cfg: TransformerConfig, c: Params, u: jnp.ndarray,
                seg_ids: jnp.ndarray):
    """The gated short convolution over packed rows: u [B, L, H] (the
    normed residual) -> (its output [B, L, H], s [B, L, H]).

    ``[b, g, z] = split3(u W_in)``, ``s = b * z``, a depthwise causal
    convolution of ``conv_kernel`` taps over s (tap ``w[K-1]`` on the
    token itself, ``w[K-1-d]`` on the one d before it), gated by g,
    then ``W_out``. A token's window stops at its DOCUMENT's first
    token: s of another segment of the packed row, or of padding,
    counts as 0 (``seg_ids``; each id one contiguous run)."""
    cdt = u.dtype
    b_, g, z = jnp.split(u @ c["w_in"].astype(cdt), 3, axis=-1)
    s = b_ * z
    acc = _causal_conv(s, c["w"], seg_ids)
    return (g * acc.astype(cdt)) @ c["w_out"].astype(cdt), s


def _causal_conv(s: jnp.ndarray, w: jnp.ndarray,
                 seg_ids: jnp.ndarray) -> jnp.ndarray:
    """A depthwise causal convolution over packed rows: s [B, L, C]
    and taps w [K, C] -> [B, L, C] in float32, tap ``w[K-1]`` on the
    token itself, ``w[K-1-d]`` on the one d before it. A token's
    window stops at its DOCUMENT's first token: s of another segment
    of the packed row, or of padding, counts as 0."""
    k, n = w.shape[0], s.shape[1]
    w = w.astype(jnp.float32)
    acc = s.astype(jnp.float32) * w[k - 1]
    for d in range(1, k):
        before = jnp.pad(s, ((0, 0), (d, 0), (0, 0)))[:, :n]
        same = (seg_ids != 0) & (
            seg_ids == jnp.pad(seg_ids, ((0, 0), (d, 0)))[:, :n])
        acc = acc + jnp.where(same[..., None],
                              before.astype(jnp.float32), 0.0) * w[k - 1 - d]
    return acc


def _short_conv_step(cfg: TransformerConfig, c: Params, u: jnp.ndarray,
                     state: jnp.ndarray):
    """One token of :func:`_short_conv`: u [B, H] and the stream's
    last ``conv_kernel - 1`` rows of s, oldest first [B, K-1, H]
    (zeros before the document's first token) -> (output [B, H], the
    state moved on by one row)."""
    cdt = u.dtype
    b_, g, z = jnp.split(u @ c["w_in"].astype(cdt), 3, axis=-1)
    window = jnp.concatenate(
        [state, (b_ * z)[:, None].astype(state.dtype)], axis=1)
    acc = (window.astype(jnp.float32)
           * c["w"].astype(jnp.float32)[None]).sum(axis=1)
    return (g * acc.astype(cdt)) @ c["w_out"].astype(cdt), window[:, 1:]


def _conv_step(tail: jnp.ndarray, s: jnp.ndarray,
               taps: jnp.ndarray) -> jnp.ndarray:
    """One token of :func:`_causal_conv`: the stream's last ``K - 1``
    rows of the convolution's input, oldest first [B, K-1, C], the
    token's s [B, C] and taps [K, C] -> [B, C] in float32."""
    window = jnp.concatenate([tail, s[:, None].astype(tail.dtype)], axis=1)
    return (window.astype(jnp.float32)
            * taps.astype(jnp.float32)[None]).sum(axis=1)


_DELTA_CONVS = (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v"))


def _delta_inputs(cfg: TransformerConfig, c: Params, u: jnp.ndarray,
                  conv):
    """What the recurrence takes of a delta layer's normed input u
    [..., H] (``DeltaConfig`` has the equations): (the three
    convolutions' inputs side by side [..., 3 x width]; q~, k~ and v
    after convolution and SiLU and the decay's pre-activation
    ``(u w_fa) w_fb`` [..., n, hd], in the compute dtype; beta
    [..., n] in float32; ``prepare``). ``prepare(q~, k~, f)`` makes, in
    float32, q and k l2-normed a head (q scaled) and the log-decay g:
    the recurrence applies it where it computes (a segment of the row
    at a time, ``ops/delta_rule.py``). ``conv``: (which of the three,
    input [..., width], taps [K, width]) -> the convolution's output
    in float32."""
    cdt, dl = u.dtype, cfg.delta
    f32 = jnp.float32
    heads = (*u.shape[:-1], dl.n_heads, dl.head_dim)
    raw = [u @ c[w].astype(cdt) for w, _ in _DELTA_CONVS]
    q, k, v = (jax.nn.silu(conv(i, x, c[taps])).astype(cdt).reshape(heads)
               for i, (x, (_, taps)) in enumerate(zip(raw, _DELTA_CONVS)))
    f = ((u @ c["w_fa"].astype(cdt)) @ c["w_fb"].astype(cdt)).reshape(heads)
    beta = jax.nn.sigmoid((u @ c["w_b"].astype(cdt)).astype(f32))
    prepare = Prepare(rate=-jnp.exp(c["a_log"].astype(f32)),
                      dt_bias=c["dt_bias"].astype(f32).reshape(heads[-2:]),
                      scale=dl.head_dim ** -0.5, eps=DELTA_L2_EPS)
    return jnp.concatenate(raw, axis=-1), q, k, v, f, beta, prepare


def _delta_output(cfg: TransformerConfig, c: Params, u: jnp.ndarray,
                  o: jnp.ndarray) -> jnp.ndarray:
    """The heads' outputs o [..., n, hd] normed a head, gated from the
    normed input u and projected: [..., H]."""
    cdt = u.dtype
    gate = jax.nn.sigmoid(
        ((u @ c["w_ga"].astype(cdt)) @ c["w_gb"].astype(cdt)).astype(
            jnp.float32)).reshape(o.shape)
    y = _norm(cfg, o, c["o_norm"], None) * gate
    return y.astype(cdt).reshape(*u.shape[:-1], -1) @ c["wo"].astype(cdt)


def _delta_op(cfg: TransformerConfig, c: Params, u: jnp.ndarray,
              seg_ids: jnp.ndarray, mesh=None):
    """The delta operator over packed rows on the normed residual u
    [B, L, H] -> (its projected output [B, L, H], (the convolutions'
    inputs [B, L, 3 x width], each row's state after its last token
    [B, n, hd, hd] float32)): what prefill's caches are made of. The
    recurrence alone is sub-part ``delta/scan`` (obs/parts.py);
    ``mesh``: what the arrays are sharded over, by which the
    recurrence's kernels are partitioned (``ops/delta_rule.py``)."""
    raw, q, k, v, f, beta, prepare = _delta_inputs(
        cfg, c, u, lambda i, x, taps: _causal_conv(x, taps, seg_ids))
    with jax.named_scope(P.SCAN):
        o, last = chunked_delta_rule(q, k, v, f, beta, seg_ids,
                                     prepare=prepare, mesh=mesh)
        o = checkpoint_name(o, DELTA_RESIDUALS[0])
    proj = checkpoint_name(_delta_output(cfg, c, u, o),
                           PROJECTION_RESIDUALS[1])
    return proj, (raw, last)


def _delta_step(cfg: TransformerConfig, c: Params, u: jnp.ndarray,
                tail: jnp.ndarray, state: jnp.ndarray):
    """One token of :func:`_delta_op`: u [B, H], the stream's last
    ``conv_kernel - 1`` rows of the convolutions' inputs, oldest first
    [B, K-1, 3 x width], and its state [B, n, hd, hd] -> (output
    [B, H], the tail and the state moved on by the token)."""
    width = cfg.delta.width

    def conv(i, x, taps):
        return _conv_step(tail[..., i * width:(i + 1) * width], x, taps)

    raw, q, k, v, f, beta, prepare = _delta_inputs(cfg, c, u, conv)
    with jax.named_scope(P.SCAN):
        q, k, g = prepare(*(x.astype(jnp.float32) for x in (q, k, f)))
        o, state = delta_rule_step(q, k, v, g, beta, state)
    tail = jnp.concatenate([tail[:, 1:], raw[:, None].astype(tail.dtype)],
                           axis=1)
    return _delta_output(cfg, c, u, o.astype(u.dtype)), tail, state


def _ssm_inputs(cfg: TransformerConfig, c: Params, u: jnp.ndarray, conv):
    """What the scan takes of an ssm layer's normed input u [..., H]
    (``SsmConfig`` has the equations): (the convolution's input
    [..., conv_dim]; the gate z [..., width]; x [..., n, hd], B and C
    [..., g, state] after convolution, bias and SiLU, and the step's
    pre-activation dt [..., n], in the compute dtype). ``conv``: (input
    [..., conv_dim], taps [K, conv_dim]) -> the convolution's output in
    float32."""
    cdt, sm = u.dtype, cfg.ssm
    lead = u.shape[:-1]
    z, raw, dt = jnp.split(u @ c["w_in"].astype(cdt),
                           [sm.width, sm.width + sm.conv_dim], axis=-1)
    xbc = jax.nn.silu(conv(raw, c["conv"])
                      + c["conv_bias"].astype(jnp.float32)).astype(cdt)
    x, b, cc = jnp.split(
        xbc, [sm.width, sm.width + sm.n_groups * sm.state], axis=-1)
    return (raw, z, x.reshape(*lead, sm.n_heads, sm.head_dim),
            b.reshape(*lead, sm.n_groups, sm.state),
            cc.reshape(*lead, sm.n_groups, sm.state), dt)


def _ssm_leaves(c: Params):
    """The scan's three leaves a head in float32: the decay's rate
    ``-exp(a_log)``, the step's bias, D."""
    f32 = jnp.float32
    return dict(rate=-jnp.exp(c["a_log"].astype(f32)),
                dt_bias=c["dt_bias"].astype(f32), skip=c["d"].astype(f32))


def _ssm_output(cfg: TransformerConfig, c: Params, z: jnp.ndarray,
                y: jnp.ndarray) -> jnp.ndarray:
    """The heads' outputs y [..., n, hd] gated by SiLU(z) FIRST, then
    each of the ``n_groups`` groups of the width normed by its own root
    mean square (float32), scaled and projected: [..., H]."""
    f32, sm = jnp.float32, cfg.ssm
    y = y.reshape(z.shape).astype(f32) * jax.nn.silu(z.astype(f32))
    grouped = y.reshape(*z.shape[:-1], sm.n_groups, -1)
    grouped = grouped * jax.lax.rsqrt(
        jnp.mean(jnp.square(grouped), -1, keepdims=True)
        + cfg.layer_norm_epsilon)
    y = grouped.reshape(z.shape) * c["norm"].astype(f32)
    return y.astype(z.dtype) @ c["w_out"].astype(z.dtype)


def _ssm_op(cfg: TransformerConfig, c: Params, u: jnp.ndarray,
            seg_ids: jnp.ndarray, mesh=None):
    """The ssm operator over packed rows on the normed residual u
    [B, L, H] -> (its projected output [B, L, H], (the convolution's
    input [B, L, conv_dim], each row's state after its last token
    [B, n, hd, state] float32)): what prefill's caches are made of. The
    recurrence alone is sub-part ``ssm/scan`` (obs/parts.py); ``mesh``:
    what the arrays are sharded over, by which the scan's kernels are
    partitioned (``ops/ssm_scan.py``)."""
    raw, z, x, b, cc, dt = _ssm_inputs(
        cfg, c, u, lambda s, taps: _causal_conv(s, taps, seg_ids))
    with jax.named_scope(P.SCAN):
        y, last = chunked_ssm_scan(x, dt, b, cc, seg_ids, mesh=mesh,
                                   **_ssm_leaves(c))
        y = checkpoint_name(y, SSM_RESIDUALS[0])
    proj = checkpoint_name(_ssm_output(cfg, c, z, y),
                           PROJECTION_RESIDUALS[1])
    return proj, (raw, last)


def _ssm_step(cfg: TransformerConfig, c: Params, u: jnp.ndarray,
              tail: jnp.ndarray, state: jnp.ndarray):
    """One token of :func:`_ssm_op`: u [B, H], the stream's last
    ``conv_kernel - 1`` rows of the convolution's input, oldest first
    [B, K-1, conv_dim], and its state [B, n, hd, state] -> (output
    [B, H], the tail and the state moved on by the token)."""
    raw, z, x, b, cc, dt = _ssm_inputs(
        cfg, c, u, lambda s, taps: _conv_step(tail, s, taps))
    with jax.named_scope(P.SCAN):
        y, state = ssm_step(x, dt, b, cc, state, **_ssm_leaves(c))
    tail = jnp.concatenate([tail[:, 1:], raw[:, None].astype(tail.dtype)],
                           axis=1)
    return _ssm_output(cfg, c, z, y.astype(u.dtype)), tail, state


def _attn_scale(cfg: TransformerConfig, layer_idx: jnp.ndarray) -> jnp.ndarray:
    scale = cfg.head_dim ** -0.5 if cfg.scale_attn_weights else 1.0
    if cfg.scale_attn_by_inverse_layer_idx:
        scale = scale / (layer_idx.astype(jnp.float32) + 1.0)
    return scale


def _head_gate(lp: Params, ln1: jnp.ndarray, attn: jnp.ndarray):
    """``attn`` [..., heads, hd] times the layer's output gate, one a
    head from the normed input: ``sigmoid(ln1 W_g)`` [..., heads]
    (``attn_output_gate``); as it is where the layer has none."""
    if "w_gate" not in lp["attn"]:
        return attn
    gate = jax.nn.sigmoid(
        (ln1 @ lp["attn"]["w_gate"].astype(ln1.dtype)).astype(jnp.float32))
    return attn * gate[..., None].astype(attn.dtype)


def _index_inputs(cfg: TransformerConfig, ix: Params, u: jnp.ndarray,
                  cos: jnp.ndarray, sin: jnp.ndarray):
    """What a sparse layer's indexer makes of the normed input u
    [..., H] (``IndexerConfig`` has the equations; sub-part
    ``index/project``): its queries [..., heads, d] and its ONE key
    [..., d], both rotated by the indexer's table (the layer's rotary
    embedding over the whole d-wide head), in the compute dtype, and
    the heads' weights [..., heads], scaled, in float32."""
    cdt, ic = u.dtype, cfg.indexer
    with jax.named_scope(P.PROJECT):
        q = (u @ ix["wq"].astype(cdt)).reshape(
            *u.shape[:-1], ic.heads, ic.head_dim)
        k = (u @ ix["wk"].astype(cdt)).astype(jnp.float32)
        # a LayerNorm WITH a bias, whatever the model's norms are
        k = k - k.mean(-1, keepdims=True)
        k = k * jax.lax.rsqrt(
            jnp.mean(k * k, -1, keepdims=True) + INDEX_NORM_EPS) \
            * ix["k_norm"].astype(jnp.float32) \
            + ix["k_norm_bias"].astype(jnp.float32)
        interleaved = cfg.rotary_of("sparse").interleaved
        q = apply_rotary(q, cos, sin, interleaved)
        k = apply_rotary(k.astype(cdt)[..., None, :], cos, sin,
                         interleaved)[..., 0, :]
        w = (u @ ix["w_weights"].astype(cdt)).astype(jnp.float32) \
            * (ic.heads ** -0.5 * ic.head_dim ** -0.5)
    return q, k, w


def _index_select(cfg: TransformerConfig, ix: Params, u: jnp.ndarray,
                  seg_ids: jnp.ndarray, cos: jnp.ndarray,
                  sin: jnp.ndarray):
    """A sparse layer's selection over packed rows on the normed
    residual u [B, L, H] -> (the int8 mask [B, L, L] that the attention
    function takes, the indexer's keys [B, L, d] for prefill's cache).
    Part ``index`` (obs/parts.py). The selection is discrete and NO
    gradient passes it: ``stop_gradient`` on what it is made from says
    so by name and changes no number (the indexer's leaves get zeros
    from the language-model loss either way; the alignment loss that
    trains them is not part of this program, ROADMAP R4c)."""
    with jax.named_scope(P.INDEX):
        q, k, w = _index_inputs(cfg, jax.lax.stop_gradient(ix),
                                jax.lax.stop_gradient(u), cos, sin)
        select = selection_mask(q, k, w, seg_ids, cfg.indexer.topk)
        return checkpoint_name(select, SELECT_RESIDUAL), k


def _attention_op(cfg: TransformerConfig, lp: Params,
                  layer_idx: jnp.ndarray, ln1: jnp.ndarray,
                  seg_ids: jnp.ndarray, cos: jnp.ndarray,
                  sin: jnp.ndarray, attention_fn=None,
                  window: Optional[int] = None, op: str = "attention",
                  index_rotary=None):
    """Attention over packed streams on the normed residual ``ln1``
    [B, L, H] -> (its projected output [B, L, H], (k, v)). ``window``:
    the tokens THIS layer sees (``cfg.layer_window``), None for all;
    ``op``: the layer's operator (a latent layer's v, and the heads'
    outputs, are ``v_head_dim`` wide). A "sparse" layer runs its
    indexer first (``index_rotary``: the indexer's table), hands the
    selection to the attention function as ``select=`` and returns
    (k, v, the indexer's keys)."""
    more, states = {}, ()
    if op == "sparse":
        select, index_k = _index_select(cfg, lp["index"], ln1, seg_ids,
                                        *index_rotary)
        more, states = dict(select=select), (index_k,)
    with jax.named_scope(P.ATTN_PROJ):
        q, k, v = _rotated_qkv(cfg, lp, ln1, cos, sin, op)
        q = checkpoint_name(q, PROJECTION_RESIDUALS[0])
    attn_impl = attention_fn or packed_attention
    with jax.named_scope(P.ATTN):
        attn = attn_impl(q, k, v, seg_ids, causal=True,
                         scale=_attn_scale(cfg, layer_idx),
                         sliding_window=window, **more)
    with jax.named_scope(P.ATTN_PROJ):
        attn = _head_gate(lp, ln1, attn)
        attn = attn.reshape(*ln1.shape[:-1], -1)
        proj = attn @ lp["attn"]["wo"].astype(ln1.dtype)
        if "bo" in lp["attn"]:
            proj = proj + lp["attn"]["bo"].astype(ln1.dtype)
        proj = checkpoint_name(proj, PROJECTION_RESIDUALS[1])
    return proj, (k, v) + states


def _block(cfg: TransformerConfig, lp: Params, layer_idx: jnp.ndarray,
           x: jnp.ndarray, seg_ids: jnp.ndarray, cos: jnp.ndarray,
           sin: jnp.ndarray, constrain, attention_fn=None,
           moe_constraint=None, kind=None, window=None, mesh=None,
           index_rotary=None):
    """One block over packed streams [B, L, H]; returns (residual
    output, state, aux-losses). ``kind``: the layer's (operator,
    feed-forward) in a patterned model, None for the one block of
    ``mlp_type``; ``window``: its attention's window, None for the
    whole document. The state feeds prefill's caches: (k, v) of an
    attention layer (and the indexer's keys of a sparse one, whose
    table is ``index_rotary``), the convolution's input s [B, L, H] of a conv
    layer, (the convolutions' inputs, the rows' last states) of a
    delta layer, (the convolution's input, the rows' last states) of an
    ssm layer, None of a layer without an operator; aux is non-empty
    for MoE. ``mesh``: ``forward``'s. A part the layer's kind says is
    ``ABSENT`` is not run: the layer is its one part, that part's norm
    and one residual add."""
    op, sparse = ("attention", None) if kind is None \
        else (kind[0], kind[1] == "moe")
    state = None
    if op != ABSENT:
        # the norm before an operator and the residual's add after it
        # go with the operator's projections, those around the
        # feed-forward with the feed-forward (obs/parts.py)
        mixer = {"conv": P.CONV, "delta": P.DELTA, "ssm": P.SSM}.get(
            op, P.ATTN_PROJ)
        with jax.named_scope(mixer):
            ln1 = _norm(cfg, x, lp["ln1"]["scale"], lp["ln1"].get("bias"))
        if op == "conv":
            with jax.named_scope(P.CONV):
                proj, state = _short_conv(cfg, lp["conv"], ln1, seg_ids)
        elif op == "delta":
            with jax.named_scope(P.DELTA):
                proj, state = _delta_op(cfg, lp["delta"], ln1, seg_ids,
                                        mesh)
        elif op == "ssm":
            with jax.named_scope(P.SSM):
                proj, state = _ssm_op(cfg, lp["ssm"], ln1, seg_ids, mesh)
        else:
            proj, state = _attention_op(cfg, lp, layer_idx, ln1, seg_ids,
                                        cos, sin, attention_fn, window,
                                        op, index_rotary)
        with jax.named_scope(mixer):
            x = constrain(x + _post_norm(cfg, lp, "ln1_post", proj))
    if kind is not None and kind[1] == ABSENT:
        return x, state, {}
    ff = _ff_part(cfg, sparse)
    with jax.named_scope(ff):
        ln2 = _norm(cfg, x, lp["ln2"]["scale"], lp["ln2"].get("bias"))
    mlp_out, aux = _mlp_with_aux(cfg, lp, ln2, seg_ids, moe_constraint,
                                 sparse)
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
    tables = {"attention": table, "window": table}
    if cfg.indexer is not None:
        # the layer's embedding again over the indexer's narrower head
        rc = cfg.rotary_of("sparse")
        tables.update(sparse=table, index=rotary_freqs(
            positions, cfg.indexer.head_dim, rc.base, rc.factor,
            rc.scaling_type, rc.original_max_positions))
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
        from realhf_tpu.ops.moe import STATS
        from realhf_tpu.parallel.pipeline import pipeline_blocks

        def pblock(lp, layer_idx, carry, seg, cos_, sin_):
            y, _, aux = _block(cfg, lp, layer_idx, carry, seg, cos_,
                               sin_, constrain, attention_fn,
                               moe_constraint, window=cfg.sliding_window)
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
                cfg, params["layers"], x, seg_ids, rotary, constrain,
                attention_fn, moe_constraint, return_kv, return_aux, mesh)
        x = _final_norm(cfg, params, x)
        return (x, states, aux) if return_aux else (x, states)

    cos, sin = rotary["attention"]  # a model of one block has one table
    if return_passes and not cfg.exit_gate:
        raise ValueError("return_passes: the model has no exit gate")

    def block_fn(lp, layer_idx, carry):
        # cfg/constrain are non-array closures; seg_ids/cos/sin are
        # array closures -- jax.checkpoint differentiates through
        # closed-over arrays correctly.
        return _block(cfg, lp, layer_idx, carry, seg_ids, cos, sin,
                      constrain, attention_fn, moe_constraint,
                      window=cfg.sliding_window)

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
        from realhf_tpu.ops.moe import reduce_layers
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


def _pattern_layers(cfg, layers, x, seg_ids, rotary, constrain,
                    attention_fn, moe_constraint, return_kv, return_aux,
                    mesh=None):
    """The layers of a patterned model, unrolled: x -> (x, states,
    aux). Each attention layer takes the window its operator says and
    the rotary table of its kind (``rotary``: ``_rotary_tables``).
    ``states`` (for prefill): K and V stacked over the ATTENTION
    layers [n_attn, B, L, nkv, hd] (V ``v_head_dim`` wide where the
    layers are latent), the convolutions' inputs
    stacked over the CONV layers [n_conv, B, L, H], and of the DELTA
    layers their convolutions' inputs [n_delta, B, L, 3 x width] and
    the rows' last states [n_delta, B, n, hd, hd], of the SPARSE
    layers their indexer's keys [n_sparse, B, L, d], of the SSM layers
    their convolution's input [n_ssm, B, L, conv_dim] and the rows'
    last states [n_ssm, B, n, hd, state]; None unless
    ``return_kv``. ``aux``: the sparse layers' entries reduced as
    ``ops.moe.reduce_layers`` does; ``{}`` unless ``return_aux``."""
    ks, vs, convs, tails, deltas, index_ks, auxs = [], [], [], [], [], [], []
    ssm_tails, ssms = [], []
    # (the indexer's table only where there is one: every other model's
    # blocks are called as they were)
    more = {} if cfg.indexer is None else dict(
        index_rotary=rotary["index"])
    for i, kind in enumerate(cfg.layer_pattern):
        cos, sin = rotary.get(kind[0], (None, None))

        def block_fn(lp, carry, i=i, kind=kind, cos=cos, sin=sin):
            return _block(cfg, lp, jnp.int32(i), carry, seg_ids, cos,
                          sin, constrain, attention_fn, moe_constraint,
                          kind, cfg.layer_window(i), mesh, **more)

        x, state, aux = _remat(cfg, block_fn)(layers[str(i)], x)
        if return_kv and kind[0] == "conv":
            convs.append(state)
        elif return_kv and kind[0] != ABSENT:
            first, second = {"delta": (tails, deltas),
                             "ssm": (ssm_tails, ssms)}.get(
                                 kind[0], (ks, vs))
            first.append(state[0])
            second.append(state[1])
            if kind[0] == "sparse":
                index_ks.append(state[2])
        if aux:
            auxs.append(aux)
    states = None
    if return_kv:
        states = {name: jnp.stack(rows) if rows else None
                  for name, rows in (("k", ks), ("v", vs),
                                     ("conv", convs),
                                     ("delta_conv", tails),
                                     ("delta", deltas),
                                     ("index_k", index_ks),
                                     ("ssm_conv", ssm_tails),
                                     ("ssm", ssms))}
    aux = {}
    if return_aux and auxs:
        from realhf_tpu.ops.moe import reduce_layers
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
    if cfg.conv_layers:
        cache["conv"] = jnp.zeros(conv_state_shape(cfg, batch), dtype)
    if cfg.delta_layers:
        tail, state = delta_state_shapes(cfg, batch)
        cache["delta_conv"] = jnp.zeros(tail, dtype)
        cache["delta"] = jnp.zeros(state, jnp.float32)
    if cfg.sparse_layers:
        cache["index_k"] = jnp.zeros(
            index_cache_shape(cfg, batch, max_len), dtype)
    if cfg.ssm_layers:
        tail, state = ssm_state_shapes(cfg, batch)
        cache["ssm_conv"] = jnp.zeros(tail, dtype)
        cache["ssm"] = jnp.zeros(state, jnp.float32)
    return cache


def index_cache_shape(cfg: TransformerConfig, batch: int, slots: int):
    """The sparse layers' third attention cache: for each sparse layer,
    stream and cache slot the indexer's ONE key (``indexer.head_dim``
    values beside the ``2 x n_kv_heads x head_dim`` of K and V)."""
    return (len(cfg.sparse_layers), batch, slots, cfg.indexer.head_dim)


def conv_state_shape(cfg: TransformerConfig, batch: int):
    """The conv layers' decode state: for each conv layer and stream
    the last ``conv_kernel - 1`` rows of the convolution's input."""
    return (len(cfg.conv_layers), batch, cfg.conv_kernel - 1,
            cfg.hidden_dim)


def delta_state_shapes(cfg: TransformerConfig, batch: int):
    """The delta layers' decode state: for each delta layer and stream
    (the last ``conv_kernel - 1`` rows of its three convolutions'
    inputs side by side, in the cache's dtype; a head's state
    [hd, hd], in float32)."""
    dl = cfg.delta
    n = len(cfg.delta_layers)
    return ((n, batch, dl.conv_kernel - 1, 3 * dl.width),
            (n, batch, dl.n_heads, dl.head_dim, dl.head_dim))


def ssm_state_shapes(cfg: TransformerConfig, batch: int):
    """The ssm layers' decode state: for each ssm layer and stream (the
    last ``conv_kernel - 1`` rows of its convolution's input, x, B and
    C side by side, in the cache's dtype; a head's state [hd, state],
    in float32)."""
    sm = cfg.ssm
    n = len(cfg.ssm_layers)
    return ((n, batch, sm.conv_kernel - 1, sm.conv_dim),
            (n, batch, sm.n_heads, sm.head_dim, sm.state))


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
    more = {}
    if cfg.layer_pattern is None:
        k, v = kvs  # [nl, B, L, nkv, hd] (a looped model: nl a pass)
    else:
        # K and V of the attention layers alone; of each conv layer
        # the last rows of its input, 0 where the row is padding; of
        # each delta layer the same of its three convolutions and the
        # state after the row's last token
        k, v = kvs["k"], kvs["v"]
        if k is None:
            k = v = jnp.zeros((0, b, lp, cfg.n_kv_heads, cfg.head_dim),
                              dtype)

        def tails(rows, kernel):
            t = min(kernel - 1, lp)
            rows = jnp.where((seg_ids[:, lp - t:] != 0)[None, :, :, None],
                             rows[:, :, lp - t:], 0)
            return jnp.pad(rows, [(0, 0), (0, 0), (kernel - 1 - t, 0),
                                  (0, 0)])

        if kvs["conv"] is not None:
            more["conv"] = tails(kvs["conv"], cfg.conv_kernel)
        if kvs["delta"] is not None:
            more["delta_conv"] = tails(
                kvs["delta_conv"], cfg.delta.conv_kernel).astype(dtype)
            more["delta"] = kvs["delta"]
        if kvs["ssm"] is not None:
            more["ssm_conv"] = tails(
                kvs["ssm_conv"], cfg.ssm.conv_kernel).astype(dtype)
            more["ssm"] = kvs["ssm"]
        if kvs["index_k"] is not None:  # [n_sparse, B, L, d], by slot
            pad = round_cache_len(
                total_len if total_len is not None else lp) - lp
            more["index_k"] = jnp.pad(
                kvs["index_k"], [(0, 0), (0, 0), (0, pad), (0, 0)])
    k = k.transpose(0, 1, 3, 2, 4)  # -> [nl, B, nkv, L, hd] head-major
    v = v.transpose(0, 1, 3, 2, 4)
    valid = seg_ids != 0
    total = round_cache_len(total_len if total_len is not None else lp)
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
    if "index_k" in cache:
        more["index_k"] = jnp.pad(
            cache["index_k"], [(0, 0), (0, 0), (0, extra), (0, 0)])
    return {
        **cache,  # length, and a patterned model's other states
        **more,
        "k": pad(cache["k"]),
        "v": pad(cache["v"]),
        "valid": jnp.concatenate(
            [cache["valid"], jnp.zeros((b, extra), bool)], axis=1),
    }


def _stacked_decode_attention(q, k_all, v_all, valid, layer_idx, *,
                              scale, sliding_window, slot, mesh=None):
    """Decode attention against the FULL stacked cache at
    ``layer_idx``, a Python int (unrolled layer loop) or a traced
    scalar (scan). TPU: scalar-prefetch Pallas kernel (streams exactly
    one layer's rows from HBM, no slice copy), shard_map-partitioned
    over dp x tp meshes. A traced scale (deep
    scale_attn_by_inverse_layer_idx models) pre-multiplies q so the
    kernel still runs with a static scale -- slicing the layer out
    instead re-materializes a full layer-cache copy per token, the
    very bottleneck this kernel removes. The XLA slice path remains
    where the kernel does not apply: CPU, heads under 64, a mesh on
    which neither heads nor cache slots divide (GSPMD partitions the
    einsums itself), values of another width than the keys (latent
    layers)."""
    hd = q.shape[-1]
    if pallas_enabled() and hd >= 64 and v_all.shape[-1] == hd:
        from realhf_tpu.ops.decode_attention import run_decode_kernels
        out = run_decode_kernels(
            mesh, q, (k_all, v_all), valid, slot, layer_idx,
            scale=scale, sliding_window=sliding_window)
        if out is not None:
            return out
    return decode_attention(q, k_all[layer_idx], v_all[layer_idx], valid,
                            scale=scale, sliding_window=sliding_window,
                            slot=slot)


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

    def pick(lp, ln1, index_all, at):
        # a sparse layer's indexer on the token: its key into slot
        # `slot` of the layer's rows of the third cache, the token's
        # scores of every row, and the `topk` best of the valid ones
        with jax.named_scope(P.INDEX):
            qi, ki, w = _index_inputs(cfg, lp["index"], ln1,
                                      *rotary["index"])
            if uniform_slot:
                index_all = jax.lax.dynamic_update_slice(
                    index_all, ki[None, :, None].astype(index_all.dtype),
                    (at, 0, s0, 0))
            else:
                index_all = index_all.at[at, jnp.arange(b), slot].set(
                    ki.astype(index_all.dtype))
            with jax.named_scope(P.SCORES):
                scores = index_scores(qi[:, None], index_all[at],
                                      w[:, None])[:, 0]
            with jax.named_scope(P.SELECT):
                return select_topk(scores, valid,
                                   cfg.indexer.topk), index_all

    def layer_body(x, k_all, v_all, lp, l, sparse=None, op="attention",
                   window=cfg.sliding_window, picked=None):
        # l: the layer's place in the K/V stack, a Python int
        # (unrolled) or a traced scalar; op, window: its kind's rotary
        # table and what it sees (a patterned model says them a layer);
        # picked: ln1 -> the cache slots a sparse layer's token attends
        cos, sin = rotary.get(op, (None, None))  # a latent without one
        with jax.named_scope(P.ATTN_PROJ):
            ln1 = _norm(cfg, x, lp["ln1"]["scale"], lp["ln1"].get("bias"))
        keep = None if picked is None else picked(ln1)
        with jax.named_scope(P.ATTN_PROJ):
            # q: [B, nq, hd]; k/v: [B, nkv, hd]
            q, k, v = _rotated_qkv(cfg, lp, ln1, cos, sin, op)
        with jax.named_scope(P.ATTN):  # the token's write and the kernel
            if uniform_slot:
                # [1, B, nkv, 1, hd]
                kw = k[None, :, :, None, :].astype(k_all.dtype)
                vw = v[None, :, :, None, :].astype(v_all.dtype)
                k_all = jax.lax.dynamic_update_slice(
                    k_all, kw, (l, 0, 0, s0, 0))
                v_all = jax.lax.dynamic_update_slice(
                    v_all, vw, (l, 0, 0, s0, 0))
            else:
                k_all = k_all.at[l, jnp.arange(b), :, slot].set(
                    k.astype(k_all.dtype))
                v_all = v_all.at[l, jnp.arange(b), :, slot].set(
                    v.astype(v_all.dtype))
            base = cfg.head_dim ** -0.5 if cfg.scale_attn_weights else 1.0
            if not cfg.scale_attn_by_inverse_layer_idx:
                scale = base
            elif isinstance(l, int):
                scale = base / (l + 1)
            else:
                scale = _attn_scale(cfg, l)  # traced scalar
            if keep is not None:
                # over the selection, by the XLA path (as a latent
                # layer decodes): the stacked kernel masks by validity
                # and window alone
                attn = decode_attention(q, k_all[l], v_all[l], keep,
                                        scale=scale, slot=slot)
            else:
                attn = _stacked_decode_attention(
                    q, k_all, v_all, valid, l, scale=scale,
                    sliding_window=window, slot=slot, mesh=mesh)
        with jax.named_scope(P.ATTN_PROJ):
            attn = _head_gate(lp, ln1, attn)
            proj = attn.reshape(b, -1) @ lp["attn"]["wo"].astype(x.dtype)
            if "bo" in lp["attn"]:
                proj = proj + lp["attn"]["bo"].astype(x.dtype)
            x = x + _post_norm(cfg, lp, "ln1_post", proj)
        return _ff_step(x, lp, sparse), k_all, v_all

    def _ff_step(x, lp, sparse):
        # the norm, the feed-forward and the residual's add, one part
        # (a layer that is a mixer alone holds no "mlp" and has none)
        if "mlp" not in lp:
            return x
        with jax.named_scope(_ff_part(cfg, sparse)):
            ln2 = _norm(cfg, x, lp["ln2"]["scale"], lp["ln2"].get("bias"))
            return x + _post_norm(cfg, lp, "ln2_post",
                                  _mlp(cfg, lp, ln2, moe_constraint,
                                       sparse))

    k_all, v_all = cache["k"], cache["v"]
    index_all = cache.get("index_k")
    new_conv, new_tails, new_deltas = [], [], []
    new_ssm_tails, new_ssms = [], []
    if cfg.layer_pattern is not None:
        # a layer of the pattern at a time: an attention layer reads
        # and writes ITS slice of the K/V stack (the stack holds the
        # attention layers alone, window layers with EVERY row: the
        # kernel masks what is past the window), a conv layer its two
        # rows of state, a delta layer its heads' states and the
        # tails of its three convolutions, an ssm layer its heads'
        # states and its convolution's tail; a layer without an
        # operator is its feed-forward
        for i, (op, ff) in enumerate(cfg.layer_pattern):
            lp = params["layers"][str(i)]
            if op == ABSENT:
                x = _ff_step(x, lp, ff == "moe")
                continue
            if op == "ssm":
                with jax.named_scope(P.SSM):
                    ln1 = _norm(cfg, x, lp["ln1"]["scale"], None)
                    at = len(new_ssms)
                    proj, tail, state = _ssm_step(
                        cfg, lp["ssm"], ln1, cache["ssm_conv"][at],
                        cache["ssm"][at])
                    new_ssm_tails.append(tail)
                    new_ssms.append(state)
                    x = x + proj
                x = _ff_step(x, lp, ff == "moe")
                continue
            if op == "delta":
                with jax.named_scope(P.DELTA):
                    ln1 = _norm(cfg, x, lp["ln1"]["scale"], None)
                    at = len(new_deltas)
                    proj, tail, state = _delta_step(
                        cfg, lp["delta"], ln1, cache["delta_conv"][at],
                        cache["delta"][at])
                    new_tails.append(tail)
                    new_deltas.append(state)
                    x = x + proj
                x = _ff_step(x, lp, ff == "moe")
                continue
            if op == "sparse":
                def picked(ln1, lp=lp, at=cfg.sparse_layers.index(i)):
                    nonlocal index_all
                    keep, index_all = pick(lp, ln1, index_all, at)
                    return keep

                x, k_all, v_all = layer_body(
                    x, k_all, v_all, lp, cfg.attention_layers.index(i),
                    ff == "moe", op, None, picked)
                continue
            if op != "conv":
                x, k_all, v_all = layer_body(
                    x, k_all, v_all, lp, cfg.attention_layers.index(i),
                    ff == "moe", op, cfg.layer_window(i))
                continue
            with jax.named_scope(P.CONV):
                ln1 = _norm(cfg, x, lp["ln1"]["scale"], None)
                proj, state = _short_conv_step(
                    cfg, lp["conv"], ln1, cache["conv"][len(new_conv)])
                new_conv.append(state)
                x = x + proj
            x = _ff_step(x, lp, ff == "moe")
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
    if new_conv:
        with jax.named_scope(P.CONV):
            new_cache["conv"] = jnp.stack(new_conv)
    if new_deltas:
        with jax.named_scope(P.DELTA):
            new_cache["delta_conv"] = jnp.stack(new_tails)
            new_cache["delta"] = jnp.stack(new_deltas)
    if index_all is not None:
        new_cache["index_k"] = index_all
    if new_ssms:
        with jax.named_scope(P.SSM):
            new_cache["ssm_conv"] = jnp.stack(new_ssm_tails)
            new_cache["ssm"] = jnp.stack(new_ssms)
    return x, new_cache
