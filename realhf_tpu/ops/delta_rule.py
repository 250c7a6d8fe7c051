"""The gated delta rule with one decay a KEY CHANNEL (Kimi Delta
Attention), chunked over the row: operator "delta" of a layer pattern
(``models/config.py:DeltaConfig``, ``models/transformer.py:_delta_op``).

A head keeps a state S [dk, dv], 0 before a document's first token.
Token t brings q_t, k_t [dk] (l2-normed by the caller, q_t scaled), v_t
[dv], a log-decay g_t [dk] <= 0 and a step beta_t in (0, 1)::

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

``chunked_delta_rule`` computes every o_t of packed rows with all but
L / 64 of the L dependent steps turned into products. With
``u_t = beta_t (v_t - S_{t-1}^T (exp g_t * k_t))`` the update reads
``S_t = Diag(exp g_t) S_{t-1} + k_t u_t^T``; inside a chunk that
starts from the state S_0, with G_t the sum of g over the chunk's
tokens up to t::

    A_ts = sum_c k_t[c] k_s[c] exp(G_t[c] - G_s[c])        s <  t
    B_ts = sum_c q_t[c] k_s[c] exp(G_t[c] - G_s[c])        s <= t
    T    = (I + Diag(beta) A)^-1 Diag(beta)
    U    = T V - T (K * exp G) S_0
    O    = (Q * exp G) S_0 + B U
    S_C  = Diag(exp G_C) S_0 + (K * exp(G_C - G))^T U

so U, O and the chunk's last state are LINEAR in S_0: every chunk's
coefficients are made at once, a scan of L / 64 steps carries the
state from chunk to chunk (one [dk, dk] x [dk, dv] product a head a
step), and the outputs follow from each chunk's start state.

**Every exponent taken is <= 0.** The decay is a channel's, so
``exp(G_t - G_s)`` does not come apart into ``exp(G_t) exp(-G_s)``
safely: at 1.6 a token the second factor leaves float32 within a
chunk. Pairs inside a sub-block of 16 tokens take their own exponent,
a [16, 16, dk] array a sub-block that is reduced where it is made;
pairs across sub-blocks go through the LATER sub-block's first row r:
``exp(G_t - G_r) exp(G_r - G_s)``, both sums of g over a stretch of
tokens. The triangular inverse is forward substitution on the 16-wide
diagonal blocks, merged a block row at a time at the highest
precision. Everything inside is float32 whatever the operands' dtype;
the products run at the caller's ``jax.default_matmul_precision``.

Documents and padding: ``seg_ids`` [B, L], 0 = padding, an id one
contiguous run. A token sees the state of its own document only
(pairs across documents are masked, a chunk's start state reaches the
tokens whose document began before the chunk); a padding token leaves
the state as it is (g = 0, beta = 0) and counts with the document
before it, so the state after a row's last token is the scan's last
carry whether the row is padded on the left or on the right.

The gradient is JAX's own of this forward: nothing here is a kernel,
and a rematerialised block (``models/transformer.py:_remat``) runs it
again in the backward. A Pallas kernel for the scan and its backward
is ROADMAP R4b.
"""

import functools
from typing import Callable, Optional, Tuple

import jax
import jax.numpy as jnp

#: tokens a chunk, and tokens a sub-block inside it
CHUNK, SUB = 64, 16
#: chunks whose coefficients are made, and kept for the backward, at
#: once: at 32 heads of 128 a segment of 4 keeps the whole train step
#: of the benchmark's cell (rows of 2048, 602 M parameters) at 13.19 GB
#: as the chip's compiler counts it, 8 at 13.56, 2 at 13.09 (PERF.md,
#: PR 39)
SEGMENT_CHUNKS = 4
_HIGHEST = jax.lax.Precision.HIGHEST


def doc_index(seg_ids: jnp.ndarray) -> jnp.ndarray:
    """[B, L] segment ids -> the index of each token's document in its
    row, 1 for the first; a padding token counts with the document
    before it (0 before the row's first document)."""
    before = jnp.pad(seg_ids, ((0, 0), (1, 0)))[:, :-1]
    start = (seg_ids != 0) & (seg_ids != before)
    return jnp.cumsum(start.astype(jnp.int32), axis=1)


@jax.checkpoint
def _inside_sub_blocks(q4, k4, g4):
    """The pairs inside a sub-block, each with its own exponent: q4,
    k4, g4 [..., m, sub, dk] -> (k.k, q.k) [..., m, sub, sub], 0 where
    s > t. The [sub, sub, dk] decays are reduced where they are made
    and made again in the backward: nothing of that size is kept."""
    sub = k4.shape[-2]
    at_or_before = jnp.tril(jnp.ones((sub, sub), bool))[..., None]
    decayed = k4[..., None, :, :] * jnp.exp(jnp.where(
        at_or_before, g4[..., :, None, :] - g4[..., None, :, :], -jnp.inf))
    return ((k4[..., :, None, :] * decayed).sum(-1),
            (q4[..., :, None, :] * decayed).sum(-1))


@jax.checkpoint
def _pair_products(q, k, big_g):
    """(k.k, q.k), each [..., C, C]: ``sum_c x_t[c] k_s[c] exp(G_t[c] -
    G_s[c])`` at (t, s), 0 where s > t. q, k, big_g [..., C, dk],
    big_g the running sum of the log-decays."""
    *lead, c, dk = k.shape
    m, sub = c // SUB, SUB
    q4, k4, g4 = (x.reshape(*lead, m, sub, dk) for x in (q, k, big_g))
    inner = _inside_sub_blocks(q4, k4, g4)
    # across sub-blocks: through the later sub-block's first row
    first = g4[..., 0, :]  # [..., m, dk]
    to_first = jnp.exp(g4 - first[..., None, :])
    earlier = (jnp.arange(c)[None, :]
               < (jnp.arange(m) * sub)[:, None])[..., None]  # [m, C, 1]
    right = k[..., None, :, :] * jnp.exp(jnp.where(
        earlier, first[..., :, None, :] - big_g[..., None, :, :], -jnp.inf))
    on_diagonal = jnp.eye(m, dtype=k.dtype)[:, None, :, None]

    def whole(x4, inside):
        outer = jnp.einsum("...mtc,...msc->...mts", x4 * to_first, right)
        return outer.reshape(*lead, c, c) \
            + (inside[..., :, None, :] * on_diagonal).reshape(*lead, c, c)

    return whole(k4, inner[0]), whole(q4, inner[1])


def _unit_lower_inverse(n):
    """``(I + n)^-1`` for a strictly lower triangular n [..., C, C]:
    forward substitution a row at a time on the ``SUB``-wide diagonal
    blocks (all of them at once), then a block row at a time
    ``X[i, :i] = -X[i, i] n[i, :i] X[:i, :i]``."""
    *lead, c, _ = n.shape
    m, sub = c // SUB, SUB
    diag = jnp.stack(
        [n[..., i * sub:(i + 1) * sub, i * sub:(i + 1) * sub]
         for i in range(m)], axis=-3)  # [..., m, sub, sub]
    eye = jnp.eye(sub, dtype=n.dtype)

    def row(inv, i):
        # rows at and after i are still 0 and n is strictly lower, so
        # the whole width is summed
        mine = jax.lax.dynamic_index_in_dim(diag, i, -2, keepdims=False)
        new = eye[i] - (mine[..., :, None] * inv).sum(-2)
        return jax.lax.dynamic_update_index_in_dim(inv, new, i, -2), None

    inv, _ = jax.lax.scan(
        row, jnp.zeros_like(diag).at[..., 0, 0].set(1.0),
        jnp.arange(1, sub))
    x = jnp.pad(inv[..., 0, :, :], [(0, 0)] * (len(lead) + 1)
                + [(0, c - sub)])
    for i in range(1, m):
        at = i * sub
        below = jnp.matmul(n[..., at:at + sub, :at], x[..., :at],
                           precision=_HIGHEST)
        row = jnp.concatenate(
            [-jnp.matmul(inv[..., i, :, :], below[..., :at],
                         precision=_HIGHEST),
             inv[..., i, :, :],
             jnp.zeros((*lead, sub, c - at - sub), n.dtype)], axis=-1)
        x = jnp.concatenate([x, row], axis=-2)
    return x


def chunked_delta_rule(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                       g: jnp.ndarray, beta: jnp.ndarray,
                       seg_ids: jnp.ndarray,
                       prepare: Optional[Callable] = None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrence of this module's docstring over packed rows.

    q, k [B, L, H, dk], v [B, L, H, dv], g [B, L, H, dk] (<= 0), beta
    [B, L, H], seg_ids [B, L] -> (o [B, L, H, dv] in v's dtype, the
    state after each row's last token [B, H, dk, dv] in float32).

    The row goes through in SEGMENTS of at most ``SEGMENT_CHUNKS``
    chunks, a rematerialised scan that carries the state: the
    backward holds one segment's coefficients at a time, not the
    row's. ``prepare``: ``(q, k, g) -> (q, k, g)`` in float32, applied
    to a segment's tokens where the segment is computed (a layer's l2
    norm of q and k and its decay from a pre-activation): what it
    makes in float32 then lives a segment long, and the row is handed
    over in the dtype the caller has it in."""
    f32 = jnp.float32
    b, l, h, _ = k.shape
    valid = seg_ids != 0
    doc = doc_index(seg_ids)
    beta = jnp.where(valid[..., None], beta.astype(f32), 0.0)
    n = -(-l // CHUNK)
    segments = -(-n // SEGMENT_CHUNKS)
    per = -(-n // segments)  # chunks a segment
    pad = segments * per * CHUNK - l
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (q, k, v, g, beta))
        doc = jnp.pad(doc, ((0, 0), (0, pad)), mode="edge")

    def by_segment(x):  # [B, L, ...] -> [segments, B, per * CHUNK, ...]
        return jnp.moveaxis(
            x.reshape(b, segments, per * CHUNK, *x.shape[2:]), 1, 0)

    doc = by_segment(doc)
    # the document of the token before each segment (none: -1)
    before = jnp.pad(doc[:-1, :, -1], ((1, 0), (0, 0)), constant_values=-1)
    padding = jnp.pad(~valid, ((0, 0), (0, pad)), constant_values=True)
    segment = functools.partial(_segment, prepare=prepare)
    last, o = jax.lax.scan(
        lambda state, xs: jax.checkpoint(segment)(state, *xs),
        jnp.zeros((b, h, k.shape[-1], v.shape[-1]), f32),
        (*map(by_segment, (q, k, v, g, beta, padding)), doc, before))
    o = jnp.moveaxis(o, 0, 1).reshape(b, segments * per * CHUNK, h, -1)
    return o[:, :l], last


def _segment(state, q, k, v, g, beta, padding, doc, before, prepare):
    """``per`` chunks of a row from the state at their start: q, k, v,
    g [B, per x C, H, d], beta [B, per x C, H], padding and doc
    [B, per x C] (which tokens are padding, each token's document),
    before [B] (the document of the token before the segment) -> (the
    state after the segment, o [B, per x C, H, dv] in v's dtype)."""
    f32 = jnp.float32
    b, length, h, _ = k.shape
    n = length // CHUNK
    out_dtype = v.dtype
    q, k, g = q.astype(f32), k.astype(f32), g.astype(f32)
    if prepare is not None:
        q, k, g = prepare(q, k, g)
    # a padding token leaves the state as it is (its beta is 0)
    g = jnp.where(padding[..., None, None], 0.0, g)

    def chunks(x):  # [B, L, H, d] -> [B, H, N, C, d]
        return x.astype(f32).reshape(b, n, CHUNK, h, -1).transpose(
            0, 3, 1, 2, 4)

    q, k, v, g = chunks(q), chunks(k), chunks(v), chunks(g)
    beta = chunks(beta[..., None])[..., 0]  # [B, H, N, C]
    doc = doc.reshape(b, 1, n, CHUNK)
    big_g = jnp.cumsum(g, axis=-2)

    # which pairs and which states a token's document reaches
    same = doc[..., :, None] == doc[..., None, :]  # [B, 1, N, C, C]
    before = jnp.concatenate(
        [before[:, None, None], doc[:, :, :-1, -1]], axis=2)[..., None]
    began_before = (doc == before)[..., None]  # the start state is its own
    to_the_end = (doc == doc[..., -1:])[..., None]  # the last document's
    through = doc[..., -1:] == before  # [B, 1, N, 1]: S_0 lives to the end
    lower = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
    strict = jnp.tril(jnp.ones((CHUNK, CHUNK), bool), -1)

    kk, qk = _pair_products(q, k, big_g)
    a = jnp.where(same & strict, kk, 0.0)
    bm = jnp.where(same & lower, qk, 0.0)
    t = _unit_lower_inverse(beta[..., :, None] * a) \
        * beta[..., None, :]
    decay = jnp.exp(big_g)
    k_in = jnp.where(began_before, k * decay, 0.0)
    q_in = jnp.where(began_before, q * decay, 0.0)
    tv, tk = t @ v, t @ k_in
    g_end = big_g[..., -1:, :]
    k_out = jnp.where(to_the_end, k * jnp.exp(g_end - big_g), 0.0)
    k_out_t = jnp.swapaxes(k_out, -1, -2)
    add, mix = k_out_t @ tv, k_out_t @ tk  # [B, H, N, dk, dv], [.., dk, dk]
    keep = jnp.where(through, jnp.exp(g_end[..., 0, :]), 0.0)  # [B,H,N,dk]

    def step(s, coeff):
        keep_n, mix_n, add_n = coeff
        return keep_n[..., None] * s - mix_n @ s + add_n, s

    last, starts = jax.lax.scan(
        step, state, tuple(jnp.moveaxis(x, 2, 0) for x in (keep, mix, add)))
    starts = jnp.moveaxis(starts, 0, 2)  # [B, H, N, dk, dv]
    o = ((q_in - bm @ tk) @ starts + bm @ tv).astype(out_dtype)
    return last, o.transpose(0, 2, 3, 1, 4).reshape(b, length, h, -1)


def delta_rule_step(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    g: jnp.ndarray, beta: jnp.ndarray,
                    state: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """One token of the recurrence: q, k, g [B, H, dk], v [B, H, dv],
    beta [B, H], state [B, H, dk, dv] float32 -> (o [B, H, dv], the
    state after the token), float32."""
    f32 = jnp.float32
    q, k, v, g, beta = (x.astype(f32) for x in (q, k, v, g, beta))
    s = state * jnp.exp(g)[..., None]
    u = beta[..., None] * (v - (s * k[..., None]).sum(-2))
    s = s + k[..., None] * u[..., None, :]
    return (s * q[..., None]).sum(-2), s
