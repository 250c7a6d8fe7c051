"""The Mamba-2 state-space scan with ONE decay a head, chunked over the
row: operator "ssm" of a layer pattern (``models/config.py:SsmConfig``,
``models/operators.py:_ssm_op``).

A head keeps a state S [P, N] (``head_dim`` x ``state``), 0 before a
document's first token. Token t brings x_t [P], a step ``Delta_t =
softplus(dt_t + dt_bias)`` > 0 and, from its GROUP of heads (head h
reads group ``h // (H / G)``), B_t and C_t [N]; with ``A = -exp(a_log)``
< 0 a head and ``a_t = Delta_t A`` <= 0::

    S_t = exp(a_t) S_{t-1} + Delta_t x_t B_t^T
    y_t = S_t C_t + D x_t

``chunked_ssm_scan`` computes every y_t of packed rows with all but
L / 128 of the L dependent steps turned into products. Inside a chunk
that starts from the state S_0, with G_t the sum of a over the chunk's
tokens up to t::

    y_t = exp(G_t) S_0 C_t
          + sum_{s <= t} exp(G_t - G_s) (C_t . B_s) Delta_s x_s + D x_t
    S_Q = exp(G_Q) S_0 + sum_s exp(G_Q - G_s) Delta_s x_s B_s^T

so ``C B^T`` [Q, Q] is made once a GROUP, masked by the decays a head,
and the chunk's end state is linear in S_0 with a SCALAR coefficient a
head: the scan over chunks that carries the state is elementwise.

**Every exponent taken is <= 0.** The decay is one number a head a
token, so ``G_t - G_s`` is taken as a difference FIRST and the
exponential after it, under the mask (``s <= t``, same document):
``exp(G_t) exp(-G_s)`` would leave float32 where a state halves every
token. Everything inside is float32 whatever the operands' dtype; the
products run at the caller's ``jax.default_matmul_precision``.

Documents and padding: ``seg_ids`` [B, L], 0 = padding, an id one
contiguous run. A token sees the state of its own document only (pairs
across documents are masked INSIDE a chunk, a chunk's start state
reaches the tokens whose document began before the chunk, and only the
chunk's last document writes its end state); a padding token leaves
the state as it is (Delta = 0) and counts with the document before it,
so the state after a row's last token is the scan's last carry whether
the row is padded on the left or on the right.

``CHUNK`` is 128: the published config's ``chunk_size`` is the
published kernels' tiling and no equation; 128 is taken here because a
chunk's [Q, Q] products then fill the chip's 128 x 128 matrix unit and
the intra-chunk work (``2 Q P`` a token a head) stays under the
recurrence's own ``4 P N`` at P = 64, N = 128.

**Two paths, one dispatch.** Where ``base/backend.pallas_enabled()``
(the chip; the TPU interpreter under the tests) and a GROUP's heads and
the state are whole lanes wide (``kernel_takes``), the scan is two
Pallas kernels under one ``jax.custom_vjp`` (``ssm_fwd``, ``ssm_bwd``:
the second half of this file): a grid over (row, group, block of
chunks) with the blocks in order and the group's ``H / G`` states
[P, N] in a VMEM scratch. A group's columns of x ``[L, H x P]`` and of
B and C ``[L, G x N]`` are cut out of the rows as they lie in HBM, in
the caller's dtype; ``C B^T`` [Q, Q] is made ONCE a group a chunk,
masked a head by its running decays and the documents; the decays'
difference, its exponential, every [Q, Q] and every [P, N] live in
VMEM, and only y, the last state and, where the call is
differentiated, every chunk's START state (what the backward is
handed: ``RESIDUAL_NAMES``) are written. Inside, a chunk's x is turned
``[H/G x P, Q]``, tokens along the lanes: what a token and a head
share (the step, a running decay) is then a ROW that multiplies a
head's P sublanes, and a sum over P is a sum over sublanes. The
backward walks the chunks in reverse with the end state's cotangent in
VMEM, makes a chunk's decays, masks and ``C B^T`` AGAIN from its
operands and its start state, and writes d of x, B (summed over the
group's heads in VMEM), C, D and of the two things the decays are
made from. Those two, the step ``Delta`` and the running sum of
``Delta A`` inside a chunk, are ``[B, L, H]`` float32, 1 MB a row:
one XLA pass makes them before the kernels (the softplus, the
padding's zero, the cumulative sum), and JAX differentiates it for
``dt``, ``rate`` and ``dt_bias``. The equations, the chunk of 128, the
exponents' sign, float32 inside and the products' precision (the
caller's ``jax.default_matmul_precision`` as read where the call is
traced: one bf16 pass with float32 accumulation at the default, the
highest otherwise; ``ops/delta_rule.py:_one_pass`` and ``_dot`` are
the rule) are this docstring's on both paths.

On a mesh of more than one device a bare Mosaic call does not lower,
and a (row, group) is a recurrence of its own: handed the mesh
(``mesh=``, the engine's), the kernels run on each device's own rows
("data") and groups ("model") under ``shard_map`` (``_scan_over``).

Everywhere else (the CPU, a group or a state of another width, rows or
groups that do not divide the mesh, a mesh that cuts a row along its
length) the XLA products below run, with JAX's own gradient: the row
goes through in rematerialised SEGMENTS of ``SEGMENT_CHUNKS`` chunks
(as ``ops/delta_rule.py``'s XLA path), so the backward holds one
segment's masks and products at a time and runs a second forward that
keeps nothing. GSPMD partitions it by rows ("data") and heads
("model": a group's B and C go with its heads where the groups
divide).
"""

import functools
import math
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from realhf_tpu.base.backend import pallas_enabled
from realhf_tpu.ops.delta_rule import _column, _dot, _iota, _one_pass, \
    doc_index
from realhf_tpu.ops.hlo_text import device_instructions

#: tokens a chunk (the module's docstring says why)
CHUNK = 128
#: chunks whose masks and products are made, and kept for the backward,
#: at once: 8 x 128 tokens of 64 heads hold 34 MB a [Q, Q] array a row
SEGMENT_CHUNKS = 8


def chunked_ssm_scan(x: jnp.ndarray, dt: jnp.ndarray, b: jnp.ndarray,
                     c: jnp.ndarray, seg_ids: jnp.ndarray, *,
                     rate: jnp.ndarray, dt_bias: jnp.ndarray,
                     skip: jnp.ndarray, mesh=None
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrence of this module's docstring over packed rows.

    x [B, L, H, P], dt [B, L, H] (before the softplus), b and c
    [B, L, G, N], seg_ids [B, L]; ``rate`` [H] (``-exp(a_log)``, < 0),
    ``dt_bias`` [H], ``skip`` [H] (D), float32 -> (y [B, L, H, P] in
    x's dtype, the state after each row's last token [B, H, P, N] in
    float32). ``mesh``: the mesh the operands are sharded over (rows
    over "data", heads over "model"), None for one device.

    By the kernels where ``pallas_enabled()``, a group's heads and the
    state are whole lanes wide (``kernel_takes``) and the mesh can be
    handed them (``_scan_over``), by the XLA products otherwise."""
    _, _, h, p = x.shape
    g, n_state = b.shape[2:]
    if pallas_enabled() and kernel_takes(h // g, p, n_state):
        scan = _scan_over(mesh, x.shape[0], g)
        if scan is not None:
            return _by_kernels(x, dt, b, c, seg_ids, rate, dt_bias, skip,
                               scan)
    return _by_xla(x, dt, b, c, seg_ids, rate, dt_bias, skip)


def _by_xla(x, dt, b, c, seg_ids, rate, dt_bias, skip):
    """``chunked_ssm_scan`` in XLA products with JAX's own gradient,
    the row in rematerialised segments of at most ``SEGMENT_CHUNKS``
    chunks that carry the state."""
    f32 = jnp.float32
    bsz, l, h, p = x.shape
    n_state = b.shape[-1]
    valid = seg_ids != 0
    doc = doc_index(seg_ids)
    n = -(-l // CHUNK)
    segments = -(-n // SEGMENT_CHUNKS)
    per = -(-n // segments)  # chunks a segment
    pad = segments * per * CHUNK - l
    if pad:
        x, dt, b, c = (
            jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
            for t in (x, dt, b, c))
        doc = jnp.pad(doc, ((0, 0), (0, pad)), mode="edge")

    def by_segment(t):  # [B, L, ...] -> [segments, B, per * CHUNK, ...]
        return jnp.moveaxis(
            t.reshape(bsz, segments, per * CHUNK, *t.shape[2:]), 1, 0)

    doc = by_segment(doc)
    # the document of the token before each segment (none: -1)
    before = jnp.pad(doc[:-1, :, -1], ((1, 0), (0, 0)), constant_values=-1)
    padding = jnp.pad(~valid, ((0, 0), (0, pad)), constant_values=True)
    segment = functools.partial(
        _segment, rate=rate.astype(f32), dt_bias=dt_bias.astype(f32),
        skip=skip.astype(f32))
    last, y = jax.lax.scan(
        lambda state, xs: jax.checkpoint(segment)(state, *xs),
        jnp.zeros((bsz, h, p, n_state), f32),
        (*map(by_segment, (x, dt, b, c, padding)), doc, before))
    y = jnp.moveaxis(y, 0, 1).reshape(bsz, segments * per * CHUNK, h, p)
    return y[:, :l], last


def _segment(state, x, dt, b, c, padding, doc, before, *, rate, dt_bias,
             skip):
    """``per`` chunks of a row from the state at their start: x
    [B, per x Q, H, P], dt [B, per x Q, H], b and c [B, per x Q, G, N],
    padding and doc [B, per x Q] (which tokens are padding, each
    token's document), before [B] (the document of the token before the
    segment) -> (the state after the segment, y [B, per x Q, H, P] in
    x's dtype)."""
    f32 = jnp.float32
    bsz, length, h, p = x.shape
    g = b.shape[2]
    n, q = length // CHUNK, CHUNK
    out_dtype = x.dtype
    # a padding token leaves the state as it is: no step, no decay
    delta = jnp.where(padding[..., None], 0.0,
                      jax.nn.softplus(dt.astype(f32) + dt_bias))
    a = (delta * rate).reshape(bsz, n, q, g, h // g)  # <= 0
    # the running decay inside a chunk, by token and by head
    by_token = jnp.cumsum(a, axis=2)  # [B, N, Q, G, H/G]
    by_head = by_token.transpose(0, 3, 4, 1, 2)  # [B, G, H/G, N, Q]
    x = x.astype(f32).reshape(bsz, n, q, g, h // g, p)
    xd = x * delta.reshape(bsz, n, q, g, h // g, 1)
    b = b.astype(f32).reshape(bsz, n, q, g, -1)
    c = c.astype(f32).reshape(bsz, n, q, g, -1)
    doc = doc.reshape(bsz, n, q)

    # which pairs and which states a token's document reaches
    before = jnp.concatenate([before[:, None], doc[:, :-1, -1]], axis=1)
    began_before = doc == before[..., None]  # [B, N, Q]: S_0 is its own
    to_the_end = doc == doc[..., -1:]  # the chunk's last document's
    through = doc[..., -1] == before  # [B, N]: S_0 lives to the end
    seen = (doc[..., :, None] == doc[..., None, :]) \
        & jnp.tril(jnp.ones((q, q), bool))  # [B, N, Q, Q]

    # inside the chunk: the difference first, the exponential after it,
    # under the mask; C B^T once a group
    decay = jnp.exp(jnp.where(
        seen[:, None, None],
        by_head[..., :, None] - by_head[..., None, :], -jnp.inf))
    cb = jnp.einsum("bnqgk,bnsgk->bgnqs", c, b)
    y = jnp.einsum("bghnqs,bnsghp->bnqghp", decay * cb[:, :, None], xd)

    # each chunk's end state from its start state: keep * S_0 + add
    g_end = by_token[:, :, -1:]  # [B, N, 1, G, H/G]
    out = jnp.where(to_the_end[..., None, None],
                    jnp.exp(g_end - by_token), 0.0)
    add = jnp.einsum("bnsghp,bnsgk->bnghpk", xd * out[..., None], b)
    keep = jnp.where(through[..., None, None], jnp.exp(g_end[:, :, 0]),
                     0.0)  # [B, N, G, H/G]

    def step(s, coeff):
        keep_n, add_n = coeff
        return keep_n[..., None, None] * s + add_n, s

    last, starts = jax.lax.scan(
        step, state.reshape(bsz, g, h // g, p, -1),
        (jnp.moveaxis(keep, 1, 0), jnp.moveaxis(add, 1, 0)))
    starts = jnp.moveaxis(starts, 0, 1)  # [B, N, G, H/G, P, K]
    into = jnp.where(began_before[..., None, None], jnp.exp(by_token), 0.0)
    y = y + jnp.einsum("bnqgk,bnghpk->bnqghp", c, starts) * into[..., None] \
        + x * skip.reshape(g, h // g, 1)
    return last.reshape(bsz, h, p, -1), \
        y.astype(out_dtype).reshape(bsz, length, h, p)


def ssm_step(x: jnp.ndarray, dt: jnp.ndarray, b: jnp.ndarray,
             c: jnp.ndarray, state: jnp.ndarray, *, rate: jnp.ndarray,
             dt_bias: jnp.ndarray, skip: jnp.ndarray
             ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token of the recurrence: x [B, H, P], dt [B, H], b and c
    [B, G, N], state [B, H, P, N] float32 -> (y [B, H, P], the state
    after the token), float32."""
    f32 = jnp.float32
    h, g = x.shape[1], b.shape[1]
    x, b, c = (t.astype(f32) for t in (x, b, c))
    b, c = (jnp.repeat(t, h // g, axis=1) for t in (b, c))
    delta = jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32))
    state = state * jnp.exp(delta * rate.astype(f32))[..., None, None] \
        + (delta[..., None] * x)[..., None] * b[:, :, None, :]
    return (state * c[:, :, None, :]).sum(-1) \
        + x * skip.astype(f32)[:, None], state


# ----------------------------------------------------------------------
# The same scan as two Pallas kernels under one custom_vjp
# ----------------------------------------------------------------------
#: names of the two kernels in a compiled program's text and in a
#: device trace (the engine's ``ssm_scan_kernel_calls``)
SSM_FWD, SSM_BWD = "ssm_fwd", "ssm_bwd"
#: what the forward kernel hands the backward one besides the operands
#: (``checkpoint_name``): every chunk's START state, a group's heads
#: one under the other [H/G x P, N], float32: ``B x G x L / 128`` of
#: them a layer. A rematerialised block that keeps it and the scan's
#: output runs no forward kernel in its backward
#: (``models/transformer.py:SSM_RESIDUALS``).
RESIDUAL_NAMES = ("ssm_starts",)
_LANES = 128
#: chunks a block (a grid step) at most, of operands of two bytes (half
#: as many of four): the backward's blocks, twice each, and its scratch
#: then take 7 of the 16 MiB of VMEM a kernel may take by default
BLOCK_CHUNKS = 4


def kernel_takes(heads_a_group: int, head_dim: int, state: int) -> bool:
    """Whether the kernels take a model of these widths: a group's
    columns of x ``[L, H x P]`` and of B and C ``[L, G x N]`` are cut
    out of the rows by the block specs, so both are whole lanes; a
    head's P rows of the turned chunk are whole sublane tiles."""
    return ((heads_a_group * head_dim) % _LANES == 0 and head_dim % 8 == 0
            and state % _LANES == 0)


def scan_kernel_calls(hlo_text: str) -> int:
    """The custom calls of a compiled program that are this module's
    kernels (``Engine.compiled_text``): one forward and one backward an
    ssm layer of a train program whose rematerialised blocks keep
    ``RESIDUAL_NAMES``, 0 on the XLA path."""
    return sum(opcode == "custom-call"
               and (SSM_FWD in name or SSM_BWD in name)
               for name, _, opcode in device_instructions(hlo_text))


def _chunk_rows(r):
    """The rows of chunk r of a block of several (its lanes, of an
    array that has the tokens along them)."""
    return pl.ds(pl.multiple_of(r * CHUNK, CHUNK), CHUNK)


def _sums(x, zero_one):
    """``x @ zero_one`` for a matrix of zeros and ones (running sums
    of the few rows x): x in three bf16 pieces, which the ones multiply
    exactly, added up in float32."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    pieces, rest = [], x
    for _ in range(3):
        pieces.append(rest.astype(bf16))
        rest = rest - pieces[-1].astype(f32)
    zero_one = zero_one.astype(bf16)
    # (the caller's precision is no business of a product of bf16s)
    return sum(jnp.dot(piece, zero_one, preferred_element_type=f32,
                       precision=jax.lax.Precision.DEFAULT)
               for piece in reversed(pieces))


def _as_multiplied(x, one_pass):
    """x as ``_dot`` multiplies it: rounded to bf16 where the products
    run in one pass."""
    return x.astype(jnp.bfloat16).astype(jnp.float32) if one_pass else x


def _chunk(refs, n, r, hg, one_pass):
    """What both kernels make of chunk n of the row, chunk r of the
    block in VMEM, before they look at a state: a dict of float32
    values. ``x`` is the chunk TURNED, [H/G x P, Q]: tokens along the
    lanes; what is one number a head a token (``cum``: the running sum
    of ``Delta A`` inside the chunk, ``delta``, ``e_in``, ``e_out``) is
    a row [H/G, Q]; ``seen`` and ``cb`` are [Q, Q] with the EARLIER
    token s along the sublanes, ``cb[s, t] = B_s . C_t`` under the
    mask."""
    f32 = jnp.float32
    meta_ref, dec_ref, skip_ref, x_ref, b_ref, c_ref = refs
    rows = _chunk_rows(r)
    q = CHUNK
    doc, began, to_end = (meta_ref[i, pl.ds(n, 1), :].astype(f32)
                          for i in range(3))
    at_or_before = _iota((q, q), 0) <= _iota((q, q), 1)
    seen = (_column(doc) == doc) & at_or_before
    dec = dec_ref[:, rows]
    cum, delta = _sums(dec[:hg], at_or_before), dec[hg:]
    end = cum[:, q - 1:q]  # [H/G, 1]
    b, c = b_ref[rows, :].astype(f32), c_ref[rows, :].astype(f32)
    # the start state lives to the chunk's end where the last token's
    # document began before the chunk (the exponential after the
    # broadcast along the state's lanes: Mosaic broadcasts along lanes
    # or along sublanes, not both at once)
    keep = jnp.exp(jnp.broadcast_to(
        jnp.where(began[:, q - 1:q] > 0, end, -jnp.inf), (hg, b.shape[1])))
    return dict(
        x=x_ref[rows, :].astype(f32).T, b=b, c=c, seen=seen, cum=cum,
        delta=delta, skip=skip_ref[...], keep=keep,
        # the running decays with the tokens along the sublanes:
        # ``decay[s, t]`` takes G_s down a column
        cum_t=jnp.concatenate(
            [cum, jnp.zeros((q - hg, q), f32)], axis=0).T[:, :hg],
        # every exponent is <= 0: cum falls along the chunk
        e_in=began * jnp.exp(cum), e_out=to_end * jnp.exp(end - cum),
        cb=jnp.where(seen, _dot(b, c, (1, 1), one_pass), 0.0))


def _head(m, h, p):
    """Head h of a chunk's dict: its rows of the turned chunk, its row
    of each [H/G, Q] array, and ``decay[s, t] = exp(G_t - G_s)``: the
    difference first, the exponential after it (where s > t the mask
    of ``cb`` holds a 0 against a 1)."""
    one = slice(h, h + 1)
    decay = jnp.exp(jnp.minimum(m["cum"][one] - m["cum_t"][:, one], 0.0))
    return dict(rows=slice(h * p, (h + 1) * p), decay=decay,
                **{k: m[k][one] for k in ("delta", "e_in", "e_out", "keep",
                                          "skip")})


def _forward_kernel(one_pass, hg, keep_starts, *refs):
    """Grid (row, group, block of ``per`` chunks), the blocks in order
    and a loop over a block's chunks inside, the group's states
    [H/G x P, N] in VMEM: y, the states after the row and, for the
    backward, every chunk's start states."""
    ins, refs = refs[:_OPERANDS], refs[_OPERANDS:]
    n_out = 3 if keep_starts else 2
    outs, (state_ref, yt_ref, xo_ref) = refs[:n_out], refs[n_out:]
    y_ref, last_ref = outs[:2]
    step = pl.program_id(2)
    per = y_ref.shape[0] // CHUNK
    p = state_ref.shape[0] // hg

    @pl.when(step == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    def chunk(r, carry):
        if keep_starts:
            outs[2][r] = state_ref[...]
        m = _chunk(ins, step * per + r, r, hg, one_pass)
        state = state_ref[...]
        from_state = _dot(state, m["c"], (1, 1), one_pass)  # [H/G x P, Q]
        for h in range(hg):
            d = _head(m, h, p)
            x = m["x"][d["rows"]]
            xd = x * d["delta"]
            yt_ref[d["rows"], :] = \
                _dot(xd, m["cb"] * d["decay"], (1, 0), one_pass) \
                + from_state[d["rows"]] * d["e_in"] + x * d["skip"]
            xo_ref[d["rows"], :] = xd * d["e_out"]
            state_ref[d["rows"], :] = state[d["rows"]] * d["keep"]
        y_ref[_chunk_rows(r), :] = yt_ref[...].T.astype(y_ref.dtype)
        state_ref[...] += _dot(xo_ref[...], m["b"], (1, 0), one_pass)
        return carry

    jax.lax.fori_loop(0, per, chunk, 0)

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        last_ref[...] = state_ref[...]


def _backward_kernel(one_pass, hg, *refs):
    """The blocks, and the chunks inside a block, in REVERSE with the
    end states' cotangent in VMEM: a chunk's decays, masks and ``C
    B^T`` are made again from its operands, then d of x, B, C, D and
    of the chunk's rows of ``Delta A`` and ``Delta``."""
    f32 = jnp.float32
    ins, refs = refs[:_OPERANDS], refs[_OPERANDS:]
    (starts_ref, dy_ref, dlast_ref), refs = refs[:3], refs[3:]
    (ddec_ref, dskip_ref, dx_ref, db_ref, dc_ref), refs = refs[:5], refs[5:]
    dstate_ref, dxt_ref, xo_ref, ye_ref, dcum_ref = refs
    step, steps = pl.program_id(2), pl.num_programs(2)
    per = dy_ref.shape[0] // CHUNK
    p = dstate_ref.shape[0] // hg
    q = CHUNK

    @pl.when(step == 0)
    def _():
        dstate_ref[...] = dlast_ref[...]
        dskip_ref[...] = jnp.zeros_like(dskip_ref)

    def chunk(i, carry):
        r = per - 1 - i
        rows = _chunk_rows(r)
        m = _chunk(ins, (steps - 1 - step) * per + r, r, hg, one_pass)
        state, dstate = starts_ref[r], dstate_ref[...]
        dy = dy_ref[rows, :].astype(f32).T  # [H/G x P, Q]
        from_state = _dot(state, m["c"], (1, 1), one_pass)
        to_state = _dot(dstate, m["b"], (1, 1), one_pass)
        dcb = jnp.zeros((q, q), f32)
        last = _iota((1, q), 1) == q - 1

        def over_p(x):
            return jnp.sum(x, axis=0, keepdims=True)

        for h in range(hg):
            d = _head(m, h, p)
            mine = d["rows"]
            x, dy_h = m["x"][mine], dy[mine]
            xd = x * d["delta"]
            w = m["cb"] * d["decay"]  # [s, t]
            dxd = _dot(dy_h, w, (1, 1), one_pass)
            dcb = dcb + _dot(xd, dy_h, (0, 0), one_pass) * d["decay"]
            # through the decays: a pair ``z[s, t] = (xd_s . dy_t) w[s,
            # t]`` counts + for its later token's running sum and - for
            # its earlier one's, and a token's ``Delta A`` is in both
            # of every pair after it: the two sums over z are taken
            # from the SAME rounded factors (``dy . (xd w)`` over s,
            # ``xd . (dy w)`` over t), so that what cancels does
            xd_r, dy_r = _as_multiplied(xd, one_pass), _as_multiplied(
                dy_h, one_pass)
            d_cum = over_p(dy_r * _dot(xd, w, (1, 0), one_pass)) \
                - over_p(xd_r * dxd)
            d_e_out = over_p(xd * to_state[mine]) * d["e_out"]
            d_e_in = over_p(dy_h * from_state[mine]) * d["e_in"]
            d_keep = jnp.sum(over_p(dstate[mine] * state[mine]) * d["keep"],
                             axis=1, keepdims=True)
            d_end = jnp.sum(d_e_out, axis=1, keepdims=True) + d_keep
            dcum_ref[h:h + 1, :] = d_cum + d_e_in - d_e_out \
                + jnp.where(last, d_end, 0.0)
            dxd = dxd + to_state[mine] * d["e_out"]
            ddec_ref[hg + h:hg + h + 1, rows] = over_p(x * dxd)
            dskip_ref[h:h + 1, :] += over_p(dy_h * x)
            dxt_ref[mine, :] = dxd * d["delta"] + dy_h * d["skip"]
            xo_ref[mine, :] = xd * d["e_out"]
            ye_ref[mine, :] = dy_h * d["e_in"]
            dstate_ref[mine, :] = dstate[mine] * d["keep"]
        # d of a token's ``Delta A``: of every running sum it is in
        ddec_ref[:hg, rows] = _sums(
            dcum_ref[...], _iota((q, q), 0) >= _iota((q, q), 1))
        dcb = jnp.where(m["seen"], dcb, 0.0)
        ye, xo = ye_ref[...], xo_ref[...]
        dx_ref[rows, :] = dxt_ref[...].T.astype(dx_ref.dtype)
        db_ref[rows, :] = (_dot(dcb, m["c"], (1, 0), one_pass)
                           + _dot(xo, dstate, (0, 0), one_pass)
                           ).astype(db_ref.dtype)
        dc_ref[rows, :] = (_dot(dcb, m["b"], (0, 0), one_pass)
                           + _dot(ye, state, (0, 0), one_pass)
                           ).astype(dc_ref.dtype)
        dstate_ref[...] += _dot(ye, m["c"], (1, 0), one_pass)
        return carry

    jax.lax.fori_loop(0, per, chunk, 0)


#: operands of both kernels: meta, dec, skip, x, b, c
_OPERANDS = 6
_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _blocks(n, dtype):
    """(blocks, chunks a block) for a row of n chunks of operands of
    ``dtype``: as few blocks as ``BLOCK_CHUNKS`` allows, of equal size
    (the row is padded up)."""
    most = max(1, BLOCK_CHUNKS * 2 // jnp.dtype(dtype).itemsize)
    blocks = -(-n // most)
    return blocks, -(-n // blocks)


def _sizes(dec, x, b):
    """(rows, groups, chunks a row, heads a group, a group's columns
    of x, the state's width)."""
    bsz, g, hg2, l = dec.shape
    return bsz, g, l // CHUNK, hg2 // 2, x.shape[-1] // g, b.shape[-1] // g


def _operand_specs(n, per, hg, wide, state, block_of):
    """Block specs of (meta [B, 3, N, Q], dec [B, G, 2 x H/G, L], skip
    [G, H/G, Q], x [B, L, H x P], b, c [B, L, G x N]) on the grid
    (row, group, step): a row's masks whole, a group's block
    ``block_of(step)`` of ``per`` chunks cut out of the row as it
    lies."""
    def rows(d):
        return pl.BlockSpec((None, per * CHUNK, d),
                            lambda i, j, s: (i, block_of(s), j))
    return [pl.BlockSpec((None, 3, n, CHUNK), lambda i, j, s: (i, 0, 0, 0)),
            pl.BlockSpec((None, None, 2 * hg, per * CHUNK),
                         lambda i, j, s: (i, j, 0, block_of(s))),
            pl.BlockSpec((None, hg, CHUNK), lambda i, j, s: (j, 0, 0)),
            rows(wide), rows(state), rows(state)]


def _state_spec(wide, state, per=None, block_of=None):
    """A group's states [H/G x P, N] of [B, G, ...] (after a row, or
    their cotangent), or ``per`` chunks' of [B, G, N, ...]."""
    if per is None:
        return pl.BlockSpec((None, None, wide, state),
                            lambda i, j, s: (i, j, 0, 0))
    return pl.BlockSpec((None, None, per, wide, state),
                        lambda i, j, s: (i, j, block_of(s), 0, 0))


def _forward_call(one_pass, keep_starts, *operands):
    _, dec, _, x, b, _ = operands
    bsz, g, n, hg, wide, state = _sizes(dec, x, b)
    blocks, per = _blocks(n, x.dtype)
    f32 = jnp.float32
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype),
                 jax.ShapeDtypeStruct((bsz, g, wide, state), f32)]
    out_specs = [pl.BlockSpec((None, per * CHUNK, wide),
                              lambda i, j, s: (i, s, j)),
                 _state_spec(wide, state)]
    if keep_starts:
        out_shape.append(jax.ShapeDtypeStruct((bsz, g, n, wide, state), f32))
        out_specs.append(_state_spec(wide, state, per, lambda s: s))
    return pl.pallas_call(
        functools.partial(_forward_kernel, one_pass, hg, keep_starts),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(bsz, g, blocks),
            in_specs=_operand_specs(n, per, hg, wide, state, lambda s: s),
            out_specs=out_specs,
            scratch_shapes=[pltpu.VMEM((wide, state), f32),
                            pltpu.VMEM((wide, CHUNK), f32),
                            pltpu.VMEM((wide, CHUNK), f32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        name=SSM_FWD,
    )(*operands)


def _backward_call(one_pass, operands, starts, dy, dlast):
    _, dec, skip, x, b, c = operands
    bsz, g, n, hg, wide, state = _sizes(dec, x, b)
    blocks, per = _blocks(n, x.dtype)
    f32 = jnp.float32

    def back(s):
        return blocks - 1 - s

    specs = _operand_specs(n, per, hg, wide, state, back)
    return pl.pallas_call(
        functools.partial(_backward_kernel, one_pass, hg),
        out_shape=[jax.ShapeDtypeStruct(dec.shape, f32),
                   jax.ShapeDtypeStruct((bsz,) + skip.shape, f32)]
        + [jax.ShapeDtypeStruct(t.shape, t.dtype) for t in (x, b, c)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(bsz, g, blocks),
            in_specs=specs + [_state_spec(wide, state, per, back), specs[3],
                              _state_spec(wide, state)],
            out_specs=[
                specs[1],
                # a (row, group)'s, resident over its chunks
                pl.BlockSpec((None, None, hg, CHUNK),
                             lambda i, j, s: (i, j, 0, 0)),
                *specs[3:]],
            scratch_shapes=[pltpu.VMEM((wide, state), f32)]
            + [pltpu.VMEM((wide, CHUNK), f32)] * 3
            + [pltpu.VMEM((hg, CHUNK), f32)]),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        name=SSM_BWD,
    )(*operands, starts, dy, dlast)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan(one_pass, meta, dec, skip, x, b, c):
    """meta [B, 3, N, Q] int32 (each token's document, whether it began
    before the chunk, whether it is the chunk's last token's), dec
    [B, G, 2 x H/G, L] float32 (a group's heads' ``Delta A``, then
    their ``Delta``, the tokens along the lanes), skip [G, H/G, Q] (D
    along the lanes), x [B, L, H x P], b, c [B, L, G x N] -> (y
    [B, L, H x P] in x's dtype, the states after the row
    [B, G, H/G x P, N] float32). ``one_pass``: the products in one bf16
    pass."""
    return tuple(_forward_call(one_pass, False, meta, dec, skip, x, b, c))


def _scan_fwd(one_pass, *operands):
    y, last, starts = _forward_call(one_pass, True, *operands)
    starts = checkpoint_name(starts, RESIDUAL_NAMES[0])
    return (y, last), (operands, starts)


def _scan_bwd(one_pass, residuals, cotangents):
    operands, starts = residuals
    ddec, dskip, dx, db, dc = _backward_call(
        one_pass, operands, starts, *cotangents)
    return (np.zeros(operands[0].shape, jax.dtypes.float0), ddec,
            dskip.sum(0), dx, db, dc)


_scan.defvjp(_scan_fwd, _scan_bwd)


def _scan_over(mesh, b: int, g: int):
    """``_scan`` as ``mesh`` runs it over ``b`` rows of ``g`` groups,
    or None where it cannot (``ops/delta_rule.py:_scan_over`` is the
    rule): on a mesh of rows over "data" and heads over "model" each
    device runs the kernels on its own rows and GROUPS under
    ``shard_map``, whose transpose adds d of ``skip`` up over "data";
    rows or groups that do not divide, and a mesh with another axis in
    use, go by the XLA products, which GSPMD partitions."""
    if mesh is None or mesh.size == 1:
        return _scan
    from realhf_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
    dp, tp = mesh.shape.get(DATA_AXIS, 1), mesh.shape.get(MODEL_AXIS, 1)
    if dp * tp != mesh.size or b % dp or g % tp:
        return None
    rows, groups = P(DATA_AXIS), P(DATA_AXIS, MODEL_AXIS)
    wide = P(DATA_AXIS, None, MODEL_AXIS)  # [B, L, H x P], [B, L, G x N]

    def scan(one_pass, *operands):
        return jax.shard_map(
            functools.partial(_scan, one_pass), mesh=mesh,
            in_specs=(rows, groups, P(MODEL_AXIS), wide, wide, wide),
            out_specs=(wide, groups),
            # (a pallas_call's outputs say nothing of how they vary)
            check_vma=False)(*operands)

    return scan


def _by_kernels(x, dt, b, c, seg_ids, rate, dt_bias, skip, scan=_scan):
    """``chunked_ssm_scan`` by the two kernels (``scan``: ``_scan`` as
    the mesh runs it, ``_scan_over``). What is one number a head a
    token is made here, one XLA pass over [B, L, H] in float32 that JAX
    differentiates: the step (0 at a padding token, which then leaves
    the state as it is) and ``Delta A``, turned so that the tokens lie
    along the lanes."""
    f32 = jnp.float32
    bsz, l, h, p = x.shape
    g, n_state = b.shape[2:]
    delta = jnp.where((seg_ids != 0)[..., None],
                      jax.nn.softplus(dt.astype(f32) + dt_bias.astype(f32)),
                      0.0)
    doc = doc_index(seg_ids)
    n = math.prod(_blocks(-(-l // CHUNK), x.dtype))
    pad = n * CHUNK - l
    if pad:
        x, delta, b, c = (
            jnp.pad(t, [(0, 0), (0, pad)] + [(0, 0)] * (t.ndim - 2))
            for t in (x, delta, b, c))
        doc = jnp.pad(doc, ((0, 0), (0, pad)), mode="edge")
    # [B, L, 2, G, H/G] -> [B, G, 2 x H/G, L]
    dec = jnp.stack([delta * rate.astype(f32), delta], axis=2).reshape(
        bsz, n * CHUNK, 2, g, h // g).transpose(0, 3, 2, 4, 1).reshape(
            bsz, g, 2 * (h // g), n * CHUNK)
    doc = doc.reshape(bsz, n, CHUNK)
    # the document of the token before each chunk (none: -1)
    before = jnp.pad(doc[:, :-1, -1], ((0, 0), (1, 0)), constant_values=-1)
    meta = jnp.stack([doc, doc == before[..., None], doc == doc[..., -1:]],
                     axis=1).astype(jnp.int32)
    y, last = scan(
        _one_pass(), meta, dec,
        jnp.broadcast_to(skip.astype(f32).reshape(g, h // g, 1),
                         (g, h // g, CHUNK)),
        *(t.reshape(bsz, n * CHUNK, -1) for t in (x, b, c)))
    return (y.reshape(bsz, n * CHUNK, h, p)[:, :l],
            last.reshape(bsz, h, p, n_state))
