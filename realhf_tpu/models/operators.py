"""What a layer operator IS, once: one record for each name of
``models/config.py:OPERATORS``.

A record (``Operator``) says, and nothing else says: its LEAVES (for
layer ``i`` of a config each weight's shape, how ``init_params`` draws
it and how ``param_pspecs`` shards it: two walks of ONE declaration,
in its order); its SCOPE (``obs/parts.py``) and its forward over
packed rows, ``apply(cfg, lp, ln1, ctx) -> (proj, state)``; its DECODE
STATE (``State``: the cache keys its layers own) and one token's
``step(cfg, lp, ln1, rows, ctx) -> (proj, rows')``; and what the
ENGINE says of it (span attributes, counters, why it cannot run under
context parallelism). ``transformer.py``'s layer loops, ``sharding.py``,
``engine/engine.py`` and ``obs/tracing.py`` ask ``OPERATORS[op]`` and
spell no operator's name. The operators' arithmetic lives here with
them. ``FEED_FORWARDS`` declares a layer's second part, which has
leaves only: ``transformer.py`` runs it (``ops/moe.py``).

The K/V stack is the cache's own, as ``valid`` and ``length`` are: the
four attention operators' layers hold rows of ONE stack
(``Operator.kv``), once a pass of a looped model; ``State`` entries are
what a record keeps BESIDE it. Adding an operator: docs/customization.md.
"""

import functools
from typing import (Any, Callable, Dict, Iterator, NamedTuple, Optional,
                    Tuple)

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import PartitionSpec

from realhf_tpu.base.backend import pallas_enabled
from realhf_tpu.models import config as C
from realhf_tpu.models.config import (ABSENT, DELTA_L2_EPS, INDEX_NORM_EPS,
                                      LATENT_NORM_EPS, TransformerConfig)
from realhf_tpu.obs import metrics
from realhf_tpu.obs import parts as P
from realhf_tpu.ops import delta_rule, ssm_scan
from realhf_tpu.ops.attention import decode_attention, packed_attention
from realhf_tpu.ops.delta_rule import (Prepare, chunked_delta_rule,
                                       delta_rule_step)
from realhf_tpu.ops.flash_attention import SELECT_RESIDUAL
from realhf_tpu.ops.rotary import apply_rotary
from realhf_tpu.ops.sparse_index import (index_scores, pair_counts,
                                         scoring_blocks, select_topk,
                                         selection_mask)
from realhf_tpu.ops.ssm_scan import chunked_ssm_scan, ssm_step
from realhf_tpu.parallel.mesh import MODEL_AXIS

Params = Dict[str, Any]


class Ctx(NamedTuple):
    """What a layer's operator is handed beside its weights and its
    normed input. ``rotary``: ``{operator: (cos, sin)}``
    (``transformer.py:_rotary_tables``; "index": the indexer's);
    ``window``: the tokens THIS layer's attention sees, None for all;
    ``mesh``: what the arrays are sharded over. Over packed rows
    (``apply``): ``seg_ids`` [B, L], ``attention_fn``, ``layer_idx``.
    For one token (``step``): ``valid`` [B, S] with the token's slot
    set, ``slot`` [B] the slot each stream writes, ``s0`` that slot
    where every stream writes the SAME one (else None: a scatter a
    row), ``l`` the layer's place in the K/V stack (a Python int or a
    traced scalar) and ``at`` its place in its own stacks."""
    rotary: Dict[str, Tuple[Any, Any]]
    window: Optional[int] = None
    mesh: Any = None
    seg_ids: Any = None
    attention_fn: Any = None
    layer_idx: Any = None
    valid: Any = None
    slot: Any = None
    s0: Any = None
    l: Any = None
    at: Optional[int] = None


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
DELTA_RESIDUALS = ("delta_out",) + delta_rule.RESIDUAL_NAMES
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
SSM_RESIDUALS = ("ssm_out",) + ssm_scan.RESIDUAL_NAMES


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


@jax.named_scope(P.CONV)
def _short_conv(cfg: TransformerConfig, lp: Params, u: jnp.ndarray,
                ctx: Ctx):
    """The gated short convolution over packed rows: u [B, L, H] (the
    normed residual) -> (its output [B, L, H], (s [B, L, H],)).

    ``[b, g, z] = split3(u W_in)``, ``s = b * z``, a depthwise causal
    convolution of ``conv_kernel`` taps over s (tap ``w[K-1]`` on the
    token itself, ``w[K-1-d]`` on the one d before it), gated by g,
    then ``W_out``. A token's window stops at its DOCUMENT's first
    token: s of another segment of the packed row, or of padding,
    counts as 0 (``ctx.seg_ids``; each id one contiguous run)."""
    cdt, c = u.dtype, lp["conv"]
    b_, g, z = jnp.split(u @ c["w_in"].astype(cdt), 3, axis=-1)
    s = b_ * z
    acc = _causal_conv(s, c["w"], ctx.seg_ids)
    return (g * acc.astype(cdt)) @ c["w_out"].astype(cdt), (s,)


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


@jax.named_scope(P.CONV)
def _short_conv_step(cfg: TransformerConfig, lp: Params, u: jnp.ndarray,
                     rows, ctx: Ctx):
    """One token of :func:`_short_conv`: u [B, H] and the stream's
    last ``conv_kernel - 1`` rows of s, oldest first [B, K-1, H]
    (zeros before the document's first token) -> (output [B, H], the
    state moved on by one row)."""
    cdt, c, (state,) = u.dtype, lp["conv"], rows
    b_, g, z = jnp.split(u @ c["w_in"].astype(cdt), 3, axis=-1)
    window = jnp.concatenate(
        [state, (b_ * z)[:, None].astype(state.dtype)], axis=1)
    acc = (window.astype(jnp.float32)
           * c["w"].astype(jnp.float32)[None]).sum(axis=1)
    return (g * acc.astype(cdt)) @ c["w_out"].astype(cdt), (window[:, 1:],)


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


@jax.named_scope(P.DELTA)
def _delta_op(cfg: TransformerConfig, lp: Params, u: jnp.ndarray,
              ctx: Ctx):
    """The delta operator over packed rows on the normed residual u
    [B, L, H] -> (its projected output [B, L, H], (the convolutions'
    inputs [B, L, 3 x width], each row's state after its last token
    [B, n, hd, hd] float32)): what prefill's caches are made of. The
    recurrence alone is sub-part ``delta/scan`` (obs/parts.py);
    ``ctx.mesh``: what the arrays are sharded over, by which the
    recurrence's kernels are partitioned (``ops/delta_rule.py``)."""
    c, seg_ids = lp["delta"], ctx.seg_ids
    raw, q, k, v, f, beta, prepare = _delta_inputs(
        cfg, c, u, lambda i, x, taps: _causal_conv(x, taps, seg_ids))
    with jax.named_scope(P.SCAN):
        o, last = chunked_delta_rule(q, k, v, f, beta, seg_ids,
                                     prepare=prepare, mesh=ctx.mesh)
        o = checkpoint_name(o, DELTA_RESIDUALS[0])
    proj = checkpoint_name(_delta_output(cfg, c, u, o),
                           PROJECTION_RESIDUALS[1])
    return proj, (raw, last)


@jax.named_scope(P.DELTA)
def _delta_step(cfg: TransformerConfig, lp: Params, u: jnp.ndarray,
                rows, ctx: Ctx):
    """One token of :func:`_delta_op`: u [B, H], the stream's last
    ``conv_kernel - 1`` rows of the convolutions' inputs, oldest first
    [B, K-1, 3 x width], and its state [B, n, hd, hd] -> (output
    [B, H], (the tail and the state moved on by the token))."""
    c, width, (tail, state) = lp["delta"], cfg.delta.width, rows

    def conv(i, x, taps):
        return _conv_step(tail[..., i * width:(i + 1) * width], x, taps)

    raw, q, k, v, f, beta, prepare = _delta_inputs(cfg, c, u, conv)
    with jax.named_scope(P.SCAN):
        q, k, g = prepare(*(x.astype(jnp.float32) for x in (q, k, f)))
        o, state = delta_rule_step(q, k, v, g, beta, state)
    tail = jnp.concatenate([tail[:, 1:], raw[:, None].astype(tail.dtype)],
                           axis=1)
    return _delta_output(cfg, c, u, o.astype(u.dtype)), (tail, state)


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


def _scan_leaves(c: Params):
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


@jax.named_scope(P.SSM)
def _ssm_op(cfg: TransformerConfig, lp: Params, u: jnp.ndarray, ctx: Ctx):
    """The ssm operator over packed rows on the normed residual u
    [B, L, H] -> (its projected output [B, L, H], (the convolution's
    input [B, L, conv_dim], each row's state after its last token
    [B, n, hd, state] float32)): what prefill's caches are made of. The
    recurrence alone is sub-part ``ssm/scan`` (obs/parts.py);
    ``ctx.mesh``: what the arrays are sharded over, by which the scan's
    kernels are partitioned (``ops/ssm_scan.py``)."""
    c, seg_ids = lp["ssm"], ctx.seg_ids
    raw, z, x, b, cc, dt = _ssm_inputs(
        cfg, c, u, lambda s, taps: _causal_conv(s, taps, seg_ids))
    with jax.named_scope(P.SCAN):
        y, last = chunked_ssm_scan(x, dt, b, cc, seg_ids, mesh=ctx.mesh,
                                   **_scan_leaves(c))
        y = checkpoint_name(y, SSM_RESIDUALS[0])
    proj = checkpoint_name(_ssm_output(cfg, c, z, y),
                           PROJECTION_RESIDUALS[1])
    return proj, (raw, last)


@jax.named_scope(P.SSM)
def _ssm_step(cfg: TransformerConfig, lp: Params, u: jnp.ndarray, rows,
              ctx: Ctx):
    """One token of :func:`_ssm_op`: u [B, H], the stream's last
    ``conv_kernel - 1`` rows of the convolution's input, oldest first
    [B, K-1, conv_dim], and its state [B, n, hd, state] -> (output
    [B, H], (the tail and the state moved on by the token))."""
    c, (tail, state) = lp["ssm"], rows
    raw, z, x, b, cc, dt = _ssm_inputs(
        cfg, c, u, lambda s, taps: _conv_step(tail, s, taps))
    with jax.named_scope(P.SCAN):
        y, state = ssm_step(x, dt, b, cc, state, **_scan_leaves(c))
    tail = jnp.concatenate([tail[:, 1:], raw[:, None].astype(tail.dtype)],
                           axis=1)
    return _ssm_output(cfg, c, z, y.astype(u.dtype)), (tail, state)


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


def _attention_op(cfg: TransformerConfig, lp: Params, ln1: jnp.ndarray,
                  ctx: Ctx, op: str = "attention"):
    """Attention over packed streams on the normed residual ``ln1``
    [B, L, H] -> (its projected output [B, L, H], (k, v)).
    ``ctx.window``: the tokens THIS layer sees (``cfg.layer_window``),
    None for all; ``op``: the layer's operator (a latent layer's v, and
    the heads' outputs, are ``v_head_dim`` wide). A layer with an
    indexer (``lp["index"]``, a "sparse" one) runs it first, hands the
    selection to the attention function as ``select=`` and returns
    (k, v, the indexer's keys)."""
    seg_ids = ctx.seg_ids
    cos, sin = ctx.rotary.get(op, (None, None))  # a kind without one
    more, states = {}, ()
    if "index" in lp:
        select, index_k = _index_select(cfg, lp["index"], ln1, seg_ids,
                                        *ctx.rotary["index"])
        more, states = dict(select=select), (index_k,)
    with jax.named_scope(P.ATTN_PROJ):
        q, k, v = _rotated_qkv(cfg, lp, ln1, cos, sin, op)
        q = checkpoint_name(q, PROJECTION_RESIDUALS[0])
    attn_impl = ctx.attention_fn or packed_attention
    with jax.named_scope(P.ATTN):
        attn = attn_impl(q, k, v, seg_ids, causal=True,
                         scale=_attn_scale(cfg, ctx.layer_idx),
                         sliding_window=ctx.window, **more)
    with jax.named_scope(P.ATTN_PROJ):
        attn = _head_gate(lp, ln1, attn)
        attn = attn.reshape(*ln1.shape[:-1], -1)
        proj = attn @ lp["attn"]["wo"].astype(ln1.dtype)
        if "bo" in lp["attn"]:
            proj = proj + lp["attn"]["bo"].astype(ln1.dtype)
        proj = checkpoint_name(proj, PROJECTION_RESIDUALS[1])
    return proj, (k, v) + states


def _pick(cfg: TransformerConfig, ix: Params, ln1: jnp.ndarray,
          index_all: jnp.ndarray, ctx: Ctx):
    """A sparse layer's indexer on the token: its key into the token's
    slot of the layer's rows (``ctx.at``) of the third cache, the
    token's scores of every row, and the ``topk`` best of the valid
    ones -> (the cache slots the token attends [B, S], the cache)."""
    at = ctx.at
    with jax.named_scope(P.INDEX):
        qi, ki, w = _index_inputs(cfg, ix, ln1, *ctx.rotary["index"])
        if ctx.s0 is not None:
            index_all = jax.lax.dynamic_update_slice(
                index_all, ki[None, :, None].astype(index_all.dtype),
                (at, 0, ctx.s0, 0))
        else:
            index_all = index_all.at[
                at, jnp.arange(ln1.shape[0]), ctx.slot].set(
                    ki.astype(index_all.dtype))
        with jax.named_scope(P.SCORES):
            scores = index_scores(qi[:, None], index_all[at],
                                  w[:, None])[:, 0]
        with jax.named_scope(P.SELECT):
            return select_topk(scores, ctx.valid,
                               cfg.indexer.topk), index_all


def _attention_step(cfg: TransformerConfig, lp: Params, ln1: jnp.ndarray,
                    rows, ctx: Ctx, op: str = "attention"):
    """One token of :func:`_attention_op`: ln1 [B, H] and the WHOLE
    stacked caches (k_all, v_all, and a sparse layer's index_all) ->
    (projected output [B, H], the caches with the token written into
    slot ``ctx.slot`` of row ``ctx.l``). Only the token's slot is
    written; the kernel takes the whole stack and the layer's index
    (``transformer.py:decode_step`` says why)."""
    k_all, v_all, *index = rows
    b, l, slot = ln1.shape[0], ctx.l, ctx.slot
    cos, sin = ctx.rotary.get(op, (None, None))  # a latent without one
    keep = None
    if index:
        keep, index_all = _pick(cfg, lp["index"], ln1, index[0], ctx)
        index = [index_all]
    with jax.named_scope(P.ATTN_PROJ):
        # q: [B, nq, hd]; k/v: [B, nkv, hd]
        q, k, v = _rotated_qkv(cfg, lp, ln1, cos, sin, op)
    with jax.named_scope(P.ATTN):  # the token's write and the kernel
        if ctx.s0 is not None:
            # [1, B, nkv, 1, hd]
            kw = k[None, :, :, None, :].astype(k_all.dtype)
            vw = v[None, :, :, None, :].astype(v_all.dtype)
            k_all = jax.lax.dynamic_update_slice(
                k_all, kw, (l, 0, 0, ctx.s0, 0))
            v_all = jax.lax.dynamic_update_slice(
                v_all, vw, (l, 0, 0, ctx.s0, 0))
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
            # over the selection, by the XLA path (as a latent layer
            # decodes): the stacked kernel masks by validity and
            # window alone
            attn = decode_attention(q, k_all[l], v_all[l], keep,
                                    scale=scale, slot=slot)
        else:
            attn = _stacked_decode_attention(
                q, k_all, v_all, ctx.valid, l, scale=scale,
                sliding_window=ctx.window, slot=slot, mesh=ctx.mesh)
    with jax.named_scope(P.ATTN_PROJ):
        attn = _head_gate(lp, ln1, attn)
        proj = attn.reshape(b, -1) @ lp["attn"]["wo"].astype(ln1.dtype)
        if "bo" in lp["attn"]:
            proj = proj + lp["attn"]["bo"].astype(ln1.dtype)
    return proj, (k_all, v_all, *index)


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



# ----------------------------------------------------------------------
# The records
# ----------------------------------------------------------------------
#: how a leaf is drawn: normal at std 0.02; normal at the projections'
#: ``std / sqrt(2 n_layers)``; ones; zeros; and the decay's two as
#: published (``a_log = log U(1, 16)``, ``dt_bias`` the inverse
#: softplus of a step drawn log-uniformly from [1e-3, 1e-1], so a
#: channel forgets between 0.001 and 1.6 a token and a state lives
#: hundreds of tokens), from ONE key of the layer's own split in two
STD, PROJ, ONES, ZEROS, A_LOG, DT_BIAS = (
    "std", "proj", "ones", "zeros", "a_log", "dt_bias")
#: by column, by row, a vector by head or channel; WHOLE on every shard
COL, ROW = PartitionSpec(None, MODEL_AXIS), PartitionSpec(MODEL_AXIS, None)
HEADS, WHOLE = PartitionSpec(MODEL_AXIS), PartitionSpec(None)
WHOLE2 = PartitionSpec(None, None)


class Leaf(NamedTuple):
    """One weight of a layer (``counted``: by ``n_params``)."""
    shape: Tuple[int, ...]
    draw: str
    spec: PartitionSpec
    counted: bool = True


def walk(leaves: Dict[str, Any], fn: Callable[[Leaf], Any]) -> Dict[str, Any]:
    """``fn`` of every ``Leaf`` of a declaration, in ITS order (which
    is the order the keys are drawn in; ``jax.tree.map`` sorts)."""
    return {name: fn(x) if isinstance(x, Leaf) else walk(x, fn)
            for name, x in leaves.items()}


class State(NamedTuple):
    """One key of the decode cache that an operator's layers own,
    stacked over THOSE layers [n, ...]. ``shape(cfg, batch, slots)``:
    one layer's; ``dtype``: None for the cache's; ``fill(cfg, rows,
    seg_ids, total, dtype)``: prefill's entry from what the layers'
    ``apply`` returned, stacked [n, B, L, ...] (``total``: the cache's
    slots; as it is where that is each row's state after its last
    token); ``slots``: the STACK's axis that grows with the cache's
    slots, its rows written a slot a token in place (``step`` takes
    and returns the whole stack), None for a state replaced whole a
    token (``step`` takes and returns the layer's own)."""
    key: str
    shape: Callable[[TransformerConfig, int, int], Tuple[int, ...]]
    fill: Callable = lambda cfg, rows, seg_ids, total, dtype: rows
    dtype: Any = None
    slots: Optional[int] = None

    def nbytes(self, cfg, batch: int, slots: int, dtype) -> int:
        return int(np.prod(self.shape(cfg, batch, slots))) \
            * jnp.dtype(self.dtype or dtype).itemsize


class Operator(NamedTuple):
    """See the module's docstring. ``keys``: how many keys
    ``init_params`` deals a layer of a model with such layers (the
    draws are the tree's bits: 24 since the delta layer's 15 leaves,
    16 before). ``kv``: its layers hold rows of the K/V stack, and
    what ``apply`` returns and ``step`` takes begins with (k, v).
    ``attrs(cfg, n)``: of its n layers on every ``engine:*`` span of a
    patterned model; ``always``: also at n = 0. ``count(cfg, n,
    seg_ids, role)``: counters of its own from a batch's rows on the
    host -> more attributes of the span."""
    leaves: Callable[[TransformerConfig, int], Dict[str, Any]]
    scope: Optional[str] = None
    apply: Optional[Callable] = None
    step: Optional[Callable] = None
    kv: bool = False
    state: Tuple[State, ...] = ()
    keys: int = 16
    attrs: Optional[Callable[[TransformerConfig, int], Dict]] = None
    always: bool = False
    token_counter: Optional[str] = None
    count: Optional[Callable] = None
    state_bytes: Optional[str] = None
    no_context_parallel: Optional[str] = None

    @property
    def cache_keys(self) -> Tuple[str, ...]:
        """The cache keys of what ``apply`` returns and ``step`` takes,
        in their order."""
        return (("k", "v") if self.kv else ()) \
            + tuple(st.key for st in self.state)


def _attention_leaves(cfg, i, index=False):
    h, hd, nkv, nq = (cfg.hidden_dim, cfg.head_dim, cfg.n_kv_heads,
                      cfg.q_heads(i))
    kv = Leaf((h, nkv * hd), STD, COL)
    attn = {"wq": Leaf((h, nq * hd), STD, COL), "wk": kv, "wv": kv,
            "wo": Leaf((nq * hd, h), PROJ, ROW)}
    if cfg.qk_norm is not None:
        # one head's width on every shard; the whole width by head
        (q, k), spec = ((1, 1), WHOLE) if cfg.qk_norm == "head" \
            else ((nq, nkv), HEADS)
        attn["q_norm"] = Leaf((q * hd,), ONES, spec)
        attn["k_norm"] = Leaf((k * hd,), ONES, spec)
    if cfg.attn_output_gate:  # a gate a head: by head, as wq
        attn["w_gate"] = Leaf((h, nq), STD, COL)
    if not index:
        return {"attn": attn}
    # on every shard: the selection is one for all the heads of a
    # token, so every shard needs it whole
    ix = cfg.indexer
    return {"attn": attn, "index": {
        "wq": Leaf((h, ix.heads * ix.head_dim), STD, WHOLE2),
        "wk": Leaf((h, ix.head_dim), STD, WHOLE2),
        "k_norm": Leaf((ix.head_dim,), ONES, WHOLE),
        "k_norm_bias": Leaf((ix.head_dim,), ZEROS, WHOLE, counted=False),
        "w_weights": Leaf((h, ix.heads), STD, WHOLE2)}}


def _latent_leaves(cfg, i):
    # by head, as wq and wo: the expansion's columns are a head's
    # (nope + v) at a time; the compression, whose output is one row
    # for all heads, is on every shard
    h, hd, nq, lat = cfg.hidden_dim, cfg.head_dim, cfg.n_q_heads, cfg.latent
    return {"attn": {
        "wq": Leaf((h, nq * hd), STD, COL),
        "w_kv_a": Leaf((h, lat.kv_rank + lat.rope_dim), STD, WHOLE2),
        "kv_a_norm": Leaf((lat.kv_rank,), ONES, WHOLE),
        "w_kv_b": Leaf((lat.kv_rank, nq * (hd - lat.rope_dim + lat.v_dim)),
                       STD, COL),
        "wo": Leaf((nq * lat.v_dim, h), PROJ, ROW)}}


def _conv_leaves(cfg, i):
    # tensor parallel like a feed-forward: ``w_in`` by column (GSPMD
    # moves its three parts to a sharding by channel after the split),
    # the taps by channel, ``w_out`` by row
    h = cfg.hidden_dim
    return {"conv": {"w_in": Leaf((h, 3 * h), STD, COL),
                     "w": Leaf((cfg.conv_kernel, h), STD, COL),
                     "w_out": Leaf((h, h), PROJ, ROW)}}


def _delta_leaves(cfg, i):
    # (``DeltaConfig`` has the equations.) By head: the projections'
    # columns, the convolutions' channels, the decay's leaves, the step
    # and the expansions of the two gates; what is one rank or one head
    # wide (the gates' compressions, the output's norm) on every shard
    h, dl = cfg.hidden_dim, cfg.delta
    w, r, n = dl.width, dl.gate_rank, dl.n_heads
    proj, taps = Leaf((h, w), STD, COL), Leaf((dl.conv_kernel, w), STD, COL)
    down, up = Leaf((h, r), STD, WHOLE2), Leaf((r, w), STD, COL)
    return {"delta": {
        "wq": proj, "wk": proj, "wv": proj,
        "conv_q": taps, "conv_k": taps, "conv_v": taps,
        "a_log": Leaf((n,), A_LOG, HEADS), "w_fa": down, "w_fb": up,
        "dt_bias": Leaf((w,), DT_BIAS, HEADS),
        "w_b": Leaf((h, n), STD, COL), "w_ga": down, "w_gb": up,
        "o_norm": Leaf((dl.head_dim,), ONES, WHOLE),
        "wo": Leaf((w, h), PROJ, ROW)}}


def _ssm_leaves(cfg, i):
    # (``SsmConfig`` has the equations; D = 1.) By head, and a group's
    # B and C with its heads: ``w_in`` by column (GSPMD moves z, x, B,
    # C and dt to a sharding by head and group after the split), the
    # taps and the bias by channel, the three leaves a head, the
    # grouped norm's scale by its width, ``w_out`` by row
    h, sm = cfg.hidden_dim, cfg.ssm
    return {"ssm": {
        "w_in": Leaf((h, sm.in_dim), STD, COL),
        "conv": Leaf((sm.conv_kernel, sm.conv_dim), STD, COL),
        "conv_bias": Leaf((sm.conv_dim,), ZEROS, HEADS),
        "a_log": Leaf((sm.n_heads,), A_LOG, HEADS),
        "dt_bias": Leaf((sm.n_heads,), DT_BIAS, HEADS),
        "d": Leaf((sm.n_heads,), ONES, HEADS),
        "norm": Leaf((sm.width,), ONES, HEADS),
        "w_out": Leaf((sm.width, h), PROJ, ROW)}}


def _ffn(cfg, f, lead=()):
    """A feed-forward's matrices, gated or not (``mlp_type`` None has
    no ``wg``), [*lead, ...]: by column and by row."""
    h, none = cfg.hidden_dim, (None,) * len(lead)
    up = Leaf((*lead, h, f), STD, PartitionSpec(*none, None, MODEL_AXIS))
    return {**({"wg": up} if cfg.gated_mlp else {}), "wu": up,
            "wd": Leaf((*lead, f, h), PROJ,
                       PartitionSpec(*none, MODEL_AXIS, None))}


def _moe_leaves(cfg):
    m, h = cfg.moe, cfg.hidden_dim
    mlp = {"router": Leaf((h, m.num_experts), STD, WHOLE2),
           **_ffn(cfg, m.intermediate_dim or cfg.intermediate_dim,
                  (m.n_held,))}
    if m.use_expert_bias:
        mlp["expert_bias"] = Leaf((m.num_experts,), ZEROS, WHOLE)
    if m.shared_intermediate_dim is not None:
        mlp["shared"] = _ffn(cfg, m.shared_intermediate_dim)
    return {"mlp": mlp}


#: a feed-forward's leaves (``cfg -> {"mlp": ...}``), as an operator's
FEED_FORWARDS: Dict[str, Callable[[TransformerConfig], Dict[str, Any]]] = {
    "dense": lambda cfg: {"mlp": _ffn(cfg, cfg.intermediate_dim)},
    "moe": _moe_leaves,
    ABSENT: lambda cfg: {},
}


def _tail(key: str, of: Callable[[TransformerConfig], Tuple[int, int]],
          cast: bool = True) -> State:
    """A causal convolution's decode state, ``of(cfg)`` its (taps,
    channels): the stream's last ``taps - 1`` rows of the convolution's
    input, oldest first [B, taps - 1, channels]; prefill's, of each
    layer's input [n, B, L, channels]: the rows' last rows, 0 where the
    row is padding (and before a row shorter than that)."""
    def fill(cfg, rows, seg_ids, total, dtype):
        k, lp = of(cfg)[0], seg_ids.shape[1]
        t = min(k - 1, lp)
        rows = jnp.where((seg_ids[:, lp - t:] != 0)[None, :, :, None],
                         rows[:, :, lp - t:], 0)
        rows = jnp.pad(rows, [(0, 0), (0, 0), (k - 1 - t, 0), (0, 0)])
        return rows.astype(dtype) if cast else rows
    return State(key, lambda cfg, b, s: (b, of(cfg)[0] - 1, of(cfg)[1]),
                 fill)


def _by_slot(cfg, rows, seg_ids, total, dtype):
    """A ``State.fill``: [n, B, L, d] a token -> a slot, zeros after."""
    return jnp.pad(rows, [(0, 0), (0, 0), (0, total - rows.shape[2]),
                          (0, 0)])


def _count_selection(cfg, n, seg_ids, role) -> Dict[str, float]:
    """``sparse_pairs_total{role,kind}``: the (query, key) pairs the n
    sparse layers of the program about to run attend over these packed
    rows (``selected``: ``min(position + 1, topk)`` a token) and the
    pairs under their documents' causal masks (``causal``), one head's,
    times the layers (``ops.sparse_index.pair_counts``);
    ``index_tokens_total{role}``: valid tokens x sparse layers;
    ``index_blocks_total{role,kind}``: the blocks of queries the
    layers' indexers go over (``all``) and those of them that score and
    select, the rest being their visibility masks (``scored``), by the
    rule the program branches on (``ops.sparse_index.scoring_blocks``;
    the last two axes are what one call of the program sees). Their
    ratio is the span's ``index_scored_share``."""
    scoring = scoring_blocks(seg_ids, cfg.indexer.topk, xp=np)
    metrics.inc("index_blocks_total", n * int(scoring.sum()),
                role=role, kind="scored")
    metrics.inc("index_blocks_total", n * scoring.size, role=role,
                kind="all")
    selected, causal = pair_counts(seg_ids, cfg.indexer.topk)
    metrics.inc("sparse_pairs_total", n * selected, role=role,
                kind="selected")
    metrics.inc("sparse_pairs_total", n * causal, role=role,
                kind="causal")
    metrics.inc("index_tokens_total",
                n * int(np.count_nonzero(seg_ids)), role=role)
    return dict(index_scored_share=float(scoring.mean()))


def _attention(op: str, **more) -> Operator:
    """One of the four attention operators: ONE apply and ONE step,
    which differ by data (the kind's rotary table and projections, the
    window ``ctx`` carries, an ``index`` subtree in ``lp``)."""
    more.setdefault("leaves", _attention_leaves)
    return Operator(
        scope=P.ATTN_PROJ, kv=True,
        apply=functools.partial(_attention_op, op=op),
        step=functools.partial(_attention_step, op=op), **more)


OPERATORS: Dict[str, Operator] = {
    "conv": Operator(
        leaves=_conv_leaves, scope=P.CONV, apply=_short_conv,
        step=_short_conv_step,
        state=(_tail("conv", lambda cfg: (cfg.conv_kernel, cfg.hidden_dim),
                     cast=False),),
        attrs=lambda cfg, n: dict(conv_layers=n), always=True,
        token_counter="conv_tokens_total", state_bytes="conv_state_bytes"),
    "attention": _attention("attention"),
    "window": _attention(
        "window", attrs=lambda cfg, n: dict(
            window=cfg.sliding_window, window_layers=n)),
    "latent": _attention(
        "latent", leaves=_latent_leaves, attrs=lambda cfg, n: dict(
            latent_layers=n, kv_lora_rank=cfg.latent.kv_rank,
            qk_dim=cfg.head_dim, v_dim=cfg.latent.v_dim)),
    "delta": Operator(
        leaves=_delta_leaves, scope=P.DELTA, apply=_delta_op,
        step=_delta_step, keys=24,
        # the three convolutions' inputs side by side; a head's state
        state=(_tail("delta_conv", lambda cfg: (
            cfg.delta.conv_kernel, 3 * cfg.delta.width)),
               State("delta", lambda cfg, b, s: (
                   b, cfg.delta.n_heads, cfg.delta.head_dim,
                   cfg.delta.head_dim), dtype=jnp.float32)),
        attrs=lambda cfg, n: dict(
            delta_layers=n, delta_heads=cfg.delta.n_heads,
            delta_head_dim=cfg.delta.head_dim,
            delta_chunk=delta_rule.CHUNK),
        token_counter="delta_tokens_total", state_bytes="delta_state_bytes"),
    "sparse": _attention(
        "sparse", leaves=functools.partial(_attention_leaves, index=True),
        keys=24,
        # the third attention cache: the indexer's ONE key a token
        # (``indexer.head_dim`` values beside the ``2 x n_kv_heads x
        # head_dim`` of K and V)
        state=(State("index_k", lambda cfg, b, s: (
            b, s, cfg.indexer.head_dim), _by_slot, slots=2),),
        attrs=lambda cfg, n: dict(
            sparse_layers=n, index_heads=cfg.indexer.heads,
            index_dim=cfg.indexer.head_dim,
            index_topk=cfg.indexer.topk),
        count=_count_selection, state_bytes="index_cache_bytes",
        no_context_parallel=(
            "context parallelism (ops/ring_attention.py) is not "
            "implemented for a model with sparse layers (layer_pattern "
            "'{pattern}'): the ring takes no selection of keys")),
    "ssm": Operator(
        leaves=_ssm_leaves, scope=P.SSM, apply=_ssm_op, step=_ssm_step,
        keys=24,
        # the convolution's input (x, B and C side by side); a head's
        # state
        state=(_tail("ssm_conv", lambda cfg: (
            cfg.ssm.conv_kernel, cfg.ssm.conv_dim)),
               State("ssm", lambda cfg, b, s: (
                   b, cfg.ssm.n_heads, cfg.ssm.head_dim, cfg.ssm.state),
                   dtype=jnp.float32)),
        attrs=lambda cfg, n: dict(
            ssm_layers=n, ssm_heads=cfg.ssm.n_heads,
            ssm_head_dim=cfg.ssm.head_dim, ssm_state=cfg.ssm.state,
            ssm_groups=cfg.ssm.n_groups, ssm_chunk=ssm_scan.CHUNK),
        token_counter="ssm_tokens_total", state_bytes="ssm_state_bytes",
        no_context_parallel=(
            "context parallelism is not implemented for a model with "
            "ssm layers (layer_pattern '{pattern}'): a row cut along "
            "its length hands no state-space state from one part to "
            "the next")),
    # a layer that is its feed-forward alone
    ABSENT: Operator(leaves=lambda cfg, i: {}),
}
assert set(OPERATORS) == set(C.OPERATORS) \
    and {op for op, r in OPERATORS.items() if r.kv} \
    == set(C.ATTENTION_OPERATORS)
assert set(FEED_FORWARDS) == set(C.FEED_FORWARDS)


def used(cfg: TransformerConfig) -> Iterator[Tuple[Operator, int]]:
    """(record, how many of ``cfg``'s layers are its) of every
    operator, in the table's order; 0 for one ``cfg`` has none of."""
    for op, rec in OPERATORS.items():
        yield rec, len(cfg.layers_of(op))


def states(cfg: TransformerConfig) -> Iterator[Tuple[State, Operator, int]]:
    """(state, its record, the layers stacked in it) of every decode
    state beside K and V that a cache of ``cfg`` holds."""
    for rec, n in used(cfg):
        for st in rec.state if n else ():
            yield st, rec, n


def n_params(cfg: TransformerConfig) -> int:
    """Approximate parameter count (for FLOPs/memory estimates): the
    embedding and the head, a looped model's exit gate (its layers
    count ONCE however often they run) and, layer by layer, every leaf
    the layer's operator and feed-forward declare (each attention
    layer at its own count of query heads, the experts HELD, the router
    over all of them); a part a layer lacks counts nothing. What no
    record declares (the layer norms' scales, a model of one block's
    biases) is left out."""
    h, v = cfg.hidden_dim, cfg.vocab_size
    n = v * h + h if cfg.is_critic else (2 - cfg.tied_embedding) * v * h
    if cfg.exit_gate:
        n += h + 1
    for i, (op, ff) in enumerate(cfg.layer_kinds):
        n += sum(int(np.prod(leaf.shape)) for leaf in jax.tree.leaves(
            {**OPERATORS[op].leaves(cfg, i), **FEED_FORWARDS[ff](cfg)},
            is_leaf=lambda x: isinstance(x, Leaf)) if leaf.counted)
    return n


def token_counters() -> Tuple[str, ...]:
    """The counters that grow by tokens x an operator's layers."""
    return tuple(r.token_counter for r in OPERATORS.values()
                 if r.token_counter)
