"""The Mamba-2 state-space scan with ONE decay a head, chunked over the
row: operator "ssm" of a layer pattern (``models/config.py:SsmConfig``,
``models/transformer.py:_ssm_op``).

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

XLA products with JAX's own gradient: the row goes through in
rematerialised SEGMENTS of ``SEGMENT_CHUNKS`` chunks (as
``ops/delta_rule.py``'s XLA path), so the backward holds one segment's
masks and products at a time and runs a second forward that keeps
nothing. GSPMD partitions it by rows ("data") and heads ("model": a
group's B and C go with its heads where the groups divide).
"""

import functools
from typing import Tuple

import jax
import jax.numpy as jnp

from realhf_tpu.ops.delta_rule import doc_index

#: tokens a chunk (the module's docstring says why)
CHUNK = 128
#: chunks whose masks and products are made, and kept for the backward,
#: at once: 8 x 128 tokens of 64 heads hold 34 MB a [Q, Q] array a row
SEGMENT_CHUNKS = 8


def chunked_ssm_scan(x: jnp.ndarray, dt: jnp.ndarray, b: jnp.ndarray,
                     c: jnp.ndarray, seg_ids: jnp.ndarray, *,
                     rate: jnp.ndarray, dt_bias: jnp.ndarray,
                     skip: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrence of this module's docstring over packed rows.

    x [B, L, H, P], dt [B, L, H] (before the softplus), b and c
    [B, L, G, N], seg_ids [B, L]; ``rate`` [H] (``-exp(a_log)``, < 0),
    ``dt_bias`` [H], ``skip`` [H] (D), float32 -> (y [B, L, H, P] in
    x's dtype, the state after each row's last token [B, H, P, N] in
    float32)."""
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
