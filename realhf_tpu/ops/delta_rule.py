"""The gated delta rule with one decay a KEY CHANNEL (Kimi Delta
Attention), chunked over the row: operator "delta" of a layer pattern
(``models/config.py:DeltaConfig``, ``models/operators.py:_delta_op``).

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

**Two paths, one dispatch.** Where ``base/backend.pallas_enabled()``
(the chip; the TPU interpreter under the tests) and a head is whole
lanes wide (``kernel_takes``), the recurrence is two Pallas kernels
under one ``jax.custom_vjp`` (``delta_fwd``, ``delta_bwd``: the second
half of this file): a grid over (row, head, block of chunks) with the
blocks in order and the state in a VMEM scratch; a chunk's running decays,
pairs, inverse, ``U``, ``O`` and next state are made in VMEM from the
row's operands as they lie in HBM (a head's 128 columns of ``[L, H x
d]``, in the caller's dtype), and only ``o`` and the last state are
written, and under a gradient what the backward is handed
(``RESIDUAL_NAMES``): every chunk's START state and its masked pairs
and triangular inverse (``A``, ``B`` and ``(I + Diag(beta) A)^-1``,
the two parts of a chunk that the matrix unit helps least with). The
backward walks the chunks in reverse with the end state's cotangent in
VMEM, reads those, makes the chunk's other coefficients (the decays,
``Prepare``, ``U``) AGAIN from its operands and its start state, and
writes d of q, k, v, g and beta. A
layer's ``prepare`` handed over as a ``Prepare`` (data: the l2 norm's
epsilon and scale, the decay's rate and bias) is applied and
differentiated inside the kernels, so nothing of the row is ever
written in float32; any other callable (a decay a head and not a
channel, say: ROADMAP R4b (d)) runs as one XLA pass before them. The
equations, the sub-blocks, the exponents' sign, float32 inside and
the precisions are this docstring's on both paths: the
inverse and what goes back through it at the highest precision, the
running sums exact (ones times three bf16 pieces of a float32), the
other products at the caller's ``jax.default_matmul_precision`` as
read where the call is traced (the default: operands rounded to bf16,
float32 accumulation, as XLA:TPU multiplies float32 operands).

On a mesh of more than one device a bare Mosaic call does not lower
(GSPMD has no rule to partition it by), and a (row, head) is a
recurrence of its own: handed the mesh (``mesh=``, the engine's), the
kernels run on each device's own rows ("data") and heads ("model")
under ``shard_map`` (``_scan_over``).

Everywhere else (the CPU, a head of another width, rows or heads that
do not divide the mesh, a mesh that cuts a row along its length) the
XLA products below run, with JAX's own gradient: the row in
rematerialised SEGMENTS of ``SEGMENT_CHUNKS`` chunks, so the backward
holds one segment's coefficients at a time and runs a second forward
that keeps nothing.
"""

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from realhf_tpu.base.backend import pallas_enabled
from realhf_tpu.ops.hlo_text import (custom_call_operands,
                                     device_instructions)

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


class Prepare(NamedTuple):
    """A delta layer's ``prepare`` as data: ``(q, k, f) [..., H, dk]
    float32 -> (q, k, g)``, q and k l2-normed a head (``x rsqrt(sum x^2
    + eps)``, q then times ``scale``) and the log-decay a key channel
    from its pre-activation, ``g = rate softplus(f + dt_bias)``. Any
    callable serves ``chunked_delta_rule``; THIS one the kernels take
    in, gradient and all, so the row crosses HBM in the dtype the
    caller has it in and nothing of it is written in float32."""
    rate: jnp.ndarray     # [H] float32, < 0 (``-exp(A_log)``)
    dt_bias: jnp.ndarray  # [H, dk] float32
    scale: float
    eps: float

    def __call__(self, q, k, f):
        def unit(x):
            return x * jax.lax.rsqrt(
                jnp.square(x).sum(-1, keepdims=True) + self.eps)
        return (unit(q) * self.scale, unit(k),
                self.rate[:, None] * jax.nn.softplus(f + self.dt_bias))


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
                       prepare: Optional[Callable] = None, mesh=None
                       ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The recurrence of this module's docstring over packed rows.

    q, k [B, L, H, dk], v [B, L, H, dv], g [B, L, H, dk] (<= 0), beta
    [B, L, H], seg_ids [B, L] -> (o [B, L, H, dv] in v's dtype, the
    state after each row's last token [B, H, dk, dv] in float32).
    ``prepare``: ``(q, k, g) -> (q, k, g)`` in float32 (a layer's l2
    norm of q and k and its decay from a pre-activation), applied
    where the path computes: the row is handed over in the dtype the
    caller has it in. ``mesh``: the mesh the operands are sharded
    over (rows over "data", heads over "model"), None for one device.

    By the kernels where ``pallas_enabled()``, the heads are whole
    lanes wide (``kernel_takes``) and the mesh can be handed them
    (``_scan_over``), by the XLA products otherwise."""
    if pallas_enabled() and kernel_takes(k.shape[-1], v.shape[-1]):
        scan = _scan_over(mesh, k.shape[0], k.shape[2])
        if scan is not None:
            return _by_kernels(q, k, v, g, beta, seg_ids, prepare, scan)
    return _by_xla(q, k, v, g, beta, seg_ids, prepare)


def _by_xla(q, k, v, g, beta, seg_ids, prepare):
    """``chunked_delta_rule`` in XLA products with JAX's own gradient.
    The row goes through in SEGMENTS of at most ``SEGMENT_CHUNKS``
    chunks, a rematerialised scan that carries the state: the
    backward holds one segment's coefficients at a time, not the
    row's. ``prepare`` is applied to a segment's tokens where the
    segment is computed: what it makes in float32 then lives a
    segment long."""
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


# ----------------------------------------------------------------------
# The same recurrence as two Pallas kernels under one custom_vjp
# ----------------------------------------------------------------------
#: names of the two kernels in a compiled program's text and in a
#: device trace (the engine's ``delta_scan_kernel_calls``)
DELTA_FWD, DELTA_BWD = "delta_fwd", "delta_bwd"
#: what the forward kernel hands the backward one besides the operands
#: (``checkpoint_name``), float32, ``B x H x L / 64`` of each a layer:
#: every chunk's START state, transposed [dv, dk] (64 KB at heads of
#: 128), and its masked pairs and triangular inverse, three [64, 64]
#: laid out as one [96, 128] (``_hand_over``, 48 KB). A rematerialised
#: block that keeps them and the scan's output runs no forward kernel
#: in its backward (``models/transformer.py:DELTA_RESIDUALS``), and the
#: backward kernel makes neither the pairs nor the inverse again.
RESIDUAL_NAMES = ("delta_starts", "delta_pairs")
_LANES = 128
_M = CHUNK // SUB
#: operands of both kernels: meta, beta, q, k, v, g, decay
_OPERANDS = 7


def kernel_takes(dk: int, dv: int) -> bool:
    """Whether the kernels take heads of these widths: a head's
    columns are cut out of the row's ``[L, H x d]`` by the block
    specs, so both are whole lanes."""
    return dk % _LANES == 0 and dv % _LANES == 0


def scan_kernel_calls(hlo_text: str) -> int:
    """The custom calls of a compiled program that are this module's
    kernels (``Engine.compiled_text``): one forward and one backward a
    delta layer of a train program whose rematerialised blocks keep
    ``RESIDUAL_NAMES``, 0 on the XLA path."""
    return sum(opcode == "custom-call"
               and (DELTA_FWD in name or DELTA_BWD in name)
               for name, _, opcode in device_instructions(hlo_text))


def scan_handed(hlo_text: str) -> int:
    """The arrays a backward kernel of a compiled program
    (``Engine.compiled_text``) takes from the forward one, besides the
    scan's own operands and the two cotangents: ``len(RESIDUAL_NAMES)``
    (every chunk's start state; its pairs and inverse), 1 where only
    the start states are handed over and the backward makes the rest
    again, the least over the program's backward calls; 0 where it
    holds none (the XLA path, a program without a gradient)."""
    return min((operands - _OPERANDS - 2
                for name, operands in custom_call_operands(hlo_text)
                if DELTA_BWD in name), default=0)


def _one_pass() -> bool:
    """Whether the caller's ``jax.default_matmul_precision`` (read
    where the call is traced) asks for ONE bf16 pass with float32
    accumulation, XLA:TPU's default for float32 operands; anything
    else runs the kernels' products at the highest precision."""
    return jax.config.jax_default_matmul_precision in (
        None, "default", "fastest", "bfloat16")


def _dot(a, b, contract, one_pass):
    """``a`` and ``b`` (float32) contracted over ``contract`` = (axis
    of a, axis of b), float32 out. ``one_pass``: the operands rounded
    to bf16 (what the chip does to float32 operands at the default
    precision); otherwise the highest precision."""
    dims = (((contract[0],), (contract[1],)), ((), ()))
    if one_pass:
        return jax.lax.dot_general(
            a.astype(jnp.bfloat16), b.astype(jnp.bfloat16), dims,
            precision=jax.lax.Precision.DEFAULT,
            preferred_element_type=jnp.float32)
    return jax.lax.dot_general(a, b, dims, precision=_HIGHEST,
                               preferred_element_type=jnp.float32)


def _over_own_rows(body):
    """``body(j, rows)`` for every row j of a sub-block, ``rows`` the
    slice of a sub-block's rows at or after it that a turn computes:
    whole vregs of 8 rows, so the second half of the turns takes the
    second half of the rows. The turns lie side by side, no loop: each
    waits on its lane reductions, which the next ones' fill (a loop's
    turns of 85 bundles each are 30 so; the two kernels still compile
    in a second each)."""
    half = SUB // 2
    for j in range(SUB):
        body(j, slice(0 if j < half else half, SUB))


def _sums(zero_one, x):
    """``zero_one @ x`` for a matrix of zeros and ones (running sums):
    x in three bf16 pieces, which the ones multiply exactly, added up
    in float32: the sums of float32 values in half the passes of a
    product at the highest precision."""
    bf16, f32 = jnp.bfloat16, jnp.float32
    pieces, rest = [], x
    for _ in range(3):
        pieces.append(rest.astype(bf16))
        rest = rest - pieces[-1].astype(f32)
    zero_one = zero_one.astype(bf16)
    # (the caller's precision is no business of a product of bf16s)
    return sum(jnp.dot(zero_one, piece, preferred_element_type=f32,
                       precision=jax.lax.Precision.DEFAULT)
               for piece in reversed(pieces))


def _iota(shape, axis):
    return jax.lax.broadcasted_iota(jnp.int32, shape, axis)


def _column(row):
    """A [1, C] row as a [C, 1] column: a select and a sum, exact."""
    c = row.shape[-1]
    eye = _iota((c, c), 0) == _iota((c, c), 1)
    return jnp.sum(jnp.where(eye, row, 0.0), axis=1, keepdims=True)


def _rows3(x):
    """[C, d] -> [M, SUB, d]: a chunk's rows by sub-block."""
    return x.reshape(_M, SUB, x.shape[-1])


def _own_row(x3_ref, j):
    """Row j of every sub-block of a [M, SUB, d] scratch, [M, 1, d]."""
    return x3_ref[:, j:j + 1, :]


def _chunk_masks(meta_ref, n):
    """Chunk n's ``(same [C, C], began [C, 1], to_end [C, 1], through
    [1, 1], valid [C, 1])`` from the row's [4, N, C] int32 (each
    token's document, whether it began before the chunk, whether it is
    the chunk's last token's, whether it is no padding): which pairs a
    token's document reaches, which tokens the start state reaches,
    which reach the end state, and whether the start state lives to
    the end."""
    f32 = jnp.float32
    doc, began, to_end, valid = (meta_ref[i, pl.ds(n, 1), :].astype(f32)
                                 for i in range(4))
    same = _column(doc) == doc
    through = jnp.max(began * to_end, axis=1, keepdims=True) > 0
    return (same, _column(began) > 0, _column(to_end) > 0, through,
            _column(valid) > 0)


def _prepared(q, k, f, valid, decay_ref, scale, eps):
    """``Prepare.__call__`` on one head's chunk [C, dk] inside a
    kernel (``decay_ref`` [2, dk]: the head's rate on every lane, its
    ``dt_bias``), the decay of a padding token 0: ``(q, k, g)`` and
    what the backward needs of it."""
    def unit(x):
        r = jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=1, keepdims=True) + eps)
        return x * r, r
    (q, rq), (k, rk) = unit(q), unit(k)
    rate, at = decay_ref[0:1, :], f + decay_ref[1:2, :]
    soft = jax.nn.softplus(at)
    g = jnp.where(valid, rate * soft, 0.0)
    return q * scale, k, g, dict(unit_q=q, rq=rq * scale, rk=rk, soft=soft,
                                 slope=rate * jax.nn.sigmoid(at))


def _decays(g, g3_ref):
    """The running sum G [C, dk] of a chunk's log-decays (stored by
    sub-block in ``g3_ref``) and ``exp(G_t - G_r)``, r the first row of
    t's sub-block."""
    c = g.shape[0]
    big_g = _sums(_iota((c, c), 0) >= _iota((c, c), 1), g)
    g3_ref[...] = _rows3(big_g)
    to_first = jnp.exp(g3_ref[...] - g3_ref[:, 0:1, :]).reshape(g.shape)
    return big_g, to_first


def _later_first(g3_ref, big_g, i):
    """``exp(G_r - G_s)`` [C, dk] for the tokens s BEFORE sub-block i,
    r its first row; 0 from r on."""
    first = g3_ref[i, 0:1, :]
    earlier = _iota((big_g.shape[0], 1), 0) < i * SUB
    return jnp.exp(jnp.where(earlier, first - big_g, -jnp.inf))


def _own_decay(g3_ref, j, rows):
    """``exp(G_t - G_s)`` [M, rows, dk], s row j of t's sub-block, 0
    where t < s: the pairs inside a sub-block by their own exponent."""
    at_or_after = _iota((_M, rows.stop - rows.start, 1), 1) + rows.start >= j
    return jnp.exp(jnp.where(
        at_or_after, g3_ref[:, rows, :] - _own_row(g3_ref, j), -jnp.inf))


def _own_hit(j, rows, c):
    """[M, rows, C]: the column of row j of the row's own sub-block."""
    shape = (_M, rows.stop - rows.start, c)
    return _iota(shape, 2) == _iota(shape, 0) * SUB + j


def _lane_sum(x3):
    """[M, R, d] -> [M, R, 1]."""
    m, r, d = x3.shape
    return jnp.sum(x3.reshape(m * r, d), axis=1, keepdims=True).reshape(
        m, r, 1)


def _pair_operands(q, k, to_first, scratch):
    """What the pairs AND their gradient read of a chunk, into
    ``scratch``: q and k by sub-block (``k3``, ``q3``) and decayed from
    their sub-block's first row (``lk``, ``lq``)."""
    scratch["k3"][...], scratch["q3"][...] = _rows3(k), _rows3(q)
    scratch["lk"][...] = k * to_first
    scratch["lq"][...] = q * to_first


def _pairs(k, big_g, one_pass, scratch):
    """``_pair_products`` of one chunk (its ``_pair_operands`` in
    ``scratch``) into ``scratch["kk"]``, ``scratch["qk"]`` [M, SUB, C]
    (every s <= t; the caller masks)."""
    c = k.shape[0]
    g3_ref, k3_ref, q3_ref = (scratch[x] for x in ("g3", "k3", "q3"))
    kk_ref, qk_ref, lk_ref, lq_ref = (
        scratch[x] for x in ("kk", "qk", "lk", "lq"))
    kk_ref[...] = jnp.zeros_like(kk_ref)
    qk_ref[...] = jnp.zeros_like(qk_ref)

    def inside(j, rows):
        decayed = _own_row(k3_ref, j) * _own_decay(g3_ref, j, rows)
        hit = _own_hit(j, rows, c)
        for x3_ref, out_ref in ((k3_ref, kk_ref), (q3_ref, qk_ref)):
            out_ref[:, rows, :] = jnp.where(
                hit, _lane_sum(x3_ref[:, rows, :] * decayed),
                out_ref[:, rows, :])

    _over_own_rows(inside)

    for i in range(1, _M):  # across sub-blocks: the later one's rows
        right = k * _later_first(g3_ref, big_g, i)
        rows = slice(i * SUB, (i + 1) * SUB)
        for left_ref, out_ref in ((lk_ref, kk_ref), (lq_ref, qk_ref)):
            out_ref[i] += _dot(left_ref[rows, :], right, (1, 1), one_pass)


def _inverse(n, x3_ref):
    """``_unit_lower_inverse`` of one chunk: ``(I + n)^-1`` [C, C] for
    a strictly lower n, in ``x3_ref`` [M, SUB, C] and returned: forward
    substitution on the ``SUB``-wide diagonal blocks (all of them at
    once), then neighbouring blocks merged, twice as wide a turn:
    ``[[A, 0], [B, D]]^-1 = X - X [[0, 0], [B, 0]] X`` with ``X`` the
    inverses of A and D side by side."""
    c = n.shape[0]
    x3_ref[...] = _rows3((_iota((c, c), 0) == _iota((c, c), 1)).astype(
        jnp.float32))
    n3 = _rows3(n)

    def eliminate(j, rows):
        # row r + j of every diagonal block is final: take it off the
        # block's later rows
        column = _lane_sum(jnp.where(_own_hit(j, rows, c), n3[:, rows, :],
                                     0.0))
        x3_ref[:, rows, :] -= column * _own_row(x3_ref, j)

    _over_own_rows(eliminate)
    x = x3_ref[...].reshape(c, c)
    width = SUB
    while width < c:
        row, column = _iota((c, c), 0) // width, _iota((c, c), 1) // width
        below = jnp.where((row % 2 == 1) & (column == row - 1), n, 0.0)
        x = x - _dot(x, _dot(below, x, (1, 0), False), (1, 0), False)
        width *= 2
    return x


def _chunk_rows(r):
    """The rows of chunk r of a block of several."""
    return pl.ds(pl.multiple_of(r * CHUNK, CHUNK), CHUNK)


#: a chunk's block of what ``_hand_over`` lays out
_HANDED = (CHUNK + CHUNK // 2, 2 * CHUNK)


def _hand_over(ref, r, a, bm, x):
    """A chunk's masked pairs and its inverse, each [C, C], into block
    r of ``ref`` [per, *_HANDED]: x beside bm over a's upper half
    of rows beside its lower half, so that every lane of the 128 that
    float32 is tiled by holds a value (three arrays with a minor
    dimension of 64 would be padded to twice their bytes)."""
    c, half = CHUNK, CHUNK // 2
    ref[r, 0:c, 0:c] = x
    ref[r, 0:c, c:] = bm
    ref[r, c:, 0:c] = a[:half]
    ref[r, c:, c:] = a[half:]


def _handed(ref, r):
    """``(a, bm, x)`` as ``_hand_over`` laid them out."""
    c = CHUNK
    a = jnp.concatenate([ref[r, c:, 0:c], ref[r, c:, c:]], axis=0)
    return a, ref[r, 0:c, c:], ref[r, 0:c, 0:c]


def _chunk_forward(refs, n, r, static, scratch, handed=None):
    """What both kernels make of chunk n of the row, chunk r of the
    block in VMEM, from its operands and its start state
    (``scratch.state`` [dv, dk], TRANSPOSED: the decay then runs along
    lanes): a dict of the chunk's coefficients, all float32 values in
    VMEM. ``handed``: the chunk's ``(a, bm, x)`` where the forward
    kernel kept them (the backward's: neither the pairs' products nor
    the inverse run again), None where they are to be made."""
    f32 = jnp.float32
    one_pass, fused, scale, eps = static
    meta_ref, beta_ref, q_ref, k_ref, v_ref, g_ref, decay_ref = refs
    q, k, v, g = (x[_chunk_rows(r), :].astype(f32)
                  for x in (q_ref, k_ref, v_ref, g_ref))
    c = k.shape[0]
    same, began, to_end, through, valid = _chunk_masks(meta_ref, n)
    made = None
    if fused:
        q, k, g, made = _prepared(q, k, g, valid, decay_ref, scale, eps)
    beta_row = beta_ref[pl.ds(n, 1), :]
    beta = _column(beta_row)
    big_g, to_first = _decays(g, scratch["g3"])
    _pair_operands(q, k, to_first, scratch)
    strict = _iota((c, c), 0) > _iota((c, c), 1)
    lower = _iota((c, c), 0) >= _iota((c, c), 1)
    if handed is None:
        _pairs(k, big_g, one_pass, scratch)
        a = jnp.where(same & strict, scratch["kk"][...].reshape(c, c), 0.0)
        bm = jnp.where(same & lower, scratch["qk"][...].reshape(c, c), 0.0)
        x = _inverse(beta * a, scratch["x3"])
    else:
        a, bm, x = handed
    state = scratch["state"][...]
    decay = jnp.exp(big_g)
    k_in = jnp.where(began, k * decay, 0.0)
    q_in = jnp.where(began, q * decay, 0.0)
    rest = v - _dot(k_in, state, (1, 1), one_pass)  # V - (K exp G) S_0
    u = _dot(x * beta_row, rest, (1, 0), one_pass)
    g_end = big_g[c - 1:c, :]
    to_last = jnp.exp(g_end - big_g)
    k_out = jnp.where(to_end, k * to_last, 0.0)
    keep = jnp.where(through, jnp.exp(g_end), 0.0)
    return dict(q=q, k=k, made=made, valid=valid, same=same, strict=strict,
                lower=lower, began=began, to_end=to_end, beta=beta,
                big_g=big_g, to_first=to_first, a=a, bm=bm, x=x,
                state=state, decay=decay, k_in=k_in, q_in=q_in, rest=rest,
                u=u, to_last=to_last, k_out=k_out, keep=keep)


def _scratch_shapes(dk, dv, backward):
    """name -> float32 VMEM scratch of a kernel."""
    by_rows, square = (_M, SUB, dk), (_M, SUB, CHUNK)
    shapes = dict(state=(dv, dk), g3=by_rows, k3=by_rows, q3=by_rows,
                  lk=(CHUNK, dk), lq=(CHUNK, dk), kk=square, qk=square)
    if backward:
        shapes.update(dstate=(dv, dk), dkl=by_rows, dqp=by_rows,
                      dkr=by_rows, tf=by_rows)
    else:
        shapes.update(x3=square)
    return {name: pltpu.VMEM(shape, jnp.float32)
            for name, shape in shapes.items()}


def _forward_kernel(static, keep_starts, names, *refs):
    """Grid (row, head, block of ``per`` chunks), the blocks in order
    and a loop over a block's chunks inside, the state in VMEM: o, the
    state after the row (transposed) and, for the backward
    (``keep_starts``), every chunk's start state, masked pairs and
    inverse (``_hand_over``)."""
    ins, refs = refs[:_OPERANDS], refs[_OPERANDS:]
    n_out = 2 + (len(RESIDUAL_NAMES) if keep_starts else 0)
    outs, scratch = refs[:n_out], dict(zip(names, refs[n_out:]))
    o_ref, last_ref = outs[:2]
    one_pass = static[0]
    step = pl.program_id(2)
    per = o_ref.shape[0] // CHUNK

    @pl.when(step == 0)
    def _():
        scratch["state"][...] = jnp.zeros_like(scratch["state"])

    def chunk(r, carry):
        if keep_starts:
            outs[2][r] = scratch["state"][...]
        m = _chunk_forward(ins, step * per + r, r, static, scratch)
        if keep_starts:
            _hand_over(outs[3], r, m["a"], m["bm"], m["x"])
        o = _dot(m["q_in"], m["state"], (1, 1), one_pass) \
            + _dot(m["bm"], m["u"], (1, 0), one_pass)
        o_ref[_chunk_rows(r), :] = o.astype(o_ref.dtype)
        scratch["state"][...] = m["keep"] * m["state"] \
            + _dot(m["u"], m["k_out"], (0, 0), one_pass)
        return carry

    jax.lax.fori_loop(0, per, chunk, 0)

    @pl.when(step == pl.num_programs(2) - 1)
    def _():
        last_ref[...] = scratch["state"][...]


def _backward_kernel(static, names, *refs):
    """The blocks, and the chunks inside a block, in REVERSE with the
    end state's cotangent in VMEM: a chunk's masked pairs and inverse
    are read as the forward kernel kept them, its other coefficients
    made again from its operands and its start state, then d of q, k,
    v, g and beta (through ``Prepare`` where the kernel applied it:
    then also d of the decay's rate and ``dt_bias``, added up over the
    row's chunks)."""
    ins, refs = refs[:_OPERANDS], refs[_OPERANDS:]
    (starts_ref, pairs_ref, do_ref, dlast_ref), refs = refs[:4], refs[4:]
    outs, scratch = refs[:6], dict(zip(names, refs[6:]))
    step, steps = pl.program_id(2), pl.num_programs(2)
    per = do_ref.shape[0] // CHUNK

    @pl.when(step == 0)
    def _():
        scratch["dstate"][...] = dlast_ref[...]
        outs[5][...] = jnp.zeros_like(outs[5])

    def chunk(i, carry):
        r = per - 1 - i
        scratch["state"][...] = starts_ref[r]
        _chunk_backward(ins, (steps - 1 - step) * per + r, r, static,
                        scratch, _handed(pairs_ref, r), do_ref, outs)
        return carry

    jax.lax.fori_loop(0, per, chunk, 0)


def _chunk_backward(ins, n, r, static, scratch, handed, do_ref, outs):
    """Chunk n of the row, chunk r of the block: its coefficients
    again but for ``handed`` (its masked pairs and inverse, the
    forward kernel's), the end state's cotangent in ``scratch.dstate``
    taken to the start state's, and the chunk's rows of the
    gradients."""
    (dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, ddecay_ref) = outs
    one_pass = static[0]
    rows = _chunk_rows(r)
    m = _chunk_forward(ins, n, r, static, scratch, handed)
    q, k, u, x, state = m["q"], m["k"], m["u"], m["x"], m["state"]
    c, dk = k.shape
    do = do_ref[rows, :].astype(jnp.float32)
    dstate = scratch["dstate"][...]

    d_u = _dot(m["bm"], do, (0, 0), one_pass) \
        + _dot(m["k_out"], dstate, (1, 1), one_pass)
    d_qk = jnp.where(m["same"] & m["lower"], _dot(do, u, (1, 1), one_pass),
                     0.0)
    d_q_in = _dot(do, state, (1, 0), one_pass)
    d_k_out = _dot(u, dstate, (1, 0), one_pass)
    d_keep = jnp.sum(dstate * state, axis=0, keepdims=True)
    # through the inverse, at the highest precision: with u = X (beta
    # rest), d n = -X^T d X X^T = -(X^T d u) u^T
    z = _dot(x, d_u, (0, 0), False)
    d_n = -_dot(z, u, (1, 1), False)
    d_r = m["beta"] * z
    d_k_in = -_dot(d_r, state, (1, 0), one_pass)
    scratch["dstate"][...] = _dot(do, m["q_in"], (0, 0), one_pass) \
        + m["keep"] * dstate - _dot(d_r, m["k_in"], (0, 0), one_pass)
    d_kk = jnp.where(m["same"] & m["strict"], m["beta"] * d_n, 0.0)
    d_beta = jnp.sum(m["rest"] * z, axis=1, keepdims=True) \
        + jnp.sum(d_n * m["a"], axis=1, keepdims=True)

    # the pairs: inside a sub-block by their own exponent
    g3_ref, k3_ref, q3_ref = (scratch[x] for x in ("g3", "k3", "q3"))
    dkk_ref, dqk_ref, lk_ref, lq_ref = (
        scratch[x] for x in ("kk", "qk", "lk", "lq"))
    dkl_ref, dqp_ref, dkr_ref, tf_ref = (
        scratch[x] for x in ("dkl", "dqp", "dkr", "tf"))
    dkk_ref[...], dqk_ref[...] = _rows3(d_kk), _rows3(d_qk)
    tf_ref[...] = _rows3(m["to_first"])
    for ref in (dkl_ref, dqp_ref, dkr_ref):
        ref[...] = jnp.zeros_like(ref)

    def inside(j, rows):
        own = _own_decay(g3_ref, j, rows)
        decayed = _own_row(k3_ref, j) * own
        hit = _own_hit(j, rows, c)
        ck = _lane_sum(jnp.where(hit, dkk_ref[:, rows, :], 0.0))
        cq = _lane_sum(jnp.where(hit, dqk_ref[:, rows, :], 0.0))
        dkl_ref[:, rows, :] += ck * decayed
        dqp_ref[:, rows, :] += cq * decayed
        # what row r + j gets as the pairs' EARLIER token
        dkr_ref[:, j:j + 1, :] += jnp.sum(
            (ck * k3_ref[:, rows, :] + cq * q3_ref[:, rows, :]) * own,
            axis=1, keepdims=True)

    _over_own_rows(inside)

    dk_right = dkr_ref[...].reshape(c, dk)
    for i in range(1, _M):
        # pairs across sub-blocks, through the later one's first row
        later = _later_first(g3_ref, m["big_g"], i)
        right = k * later
        mine = slice(i * SUB, (i + 1) * SUB)
        d_kk_i, d_qk_i = dkk_ref[i], dqk_ref[i]
        dkl_ref[i] += tf_ref[i] * _dot(d_kk_i, right, (1, 0), one_pass)
        dqp_ref[i] += tf_ref[i] * _dot(d_qk_i, right, (1, 0), one_pass)
        dk_right = dk_right + later * (
            _dot(d_kk_i, lk_ref[mine, :], (0, 0), one_pass)
            + _dot(d_qk_i, lq_ref[mine, :], (0, 0), one_pass))
    dk_left = dkl_ref[...].reshape(c, dk)
    dq_pairs = dqp_ref[...].reshape(c, dk)

    in_decay = jnp.where(m["began"], m["decay"], 0.0)
    out_decay = jnp.where(m["to_end"], m["to_last"], 0.0)
    d_q = dq_pairs + in_decay * d_q_in
    d_k = dk_left + dk_right + in_decay * d_k_in + out_decay * d_k_out
    from_end = m["k_out"] * d_k_out
    d_big_g = q * dq_pairs + k * (dk_left - dk_right) \
        + m["k_in"] * d_k_in + m["q_in"] * d_q_in - from_end
    d_end = jnp.sum(from_end, axis=0, keepdims=True) + m["keep"] * d_keep
    d_big_g = d_big_g + jnp.where(_iota((c, 1), 0) == c - 1, d_end, 0.0)
    d_g = _sums(_iota((c, c), 0) <= _iota((c, c), 1), d_big_g)
    made = m["made"]
    if made is not None:  # through Prepare
        def from_unit(d, unit, r):
            return r * (d - unit * jnp.sum(unit * d, axis=1, keepdims=True))
        d_q = from_unit(d_q, made["unit_q"], made["rq"])
        d_k = from_unit(d_k, k, made["rk"])
        d_g = jnp.where(m["valid"], d_g, 0.0)
        ddecay_ref[0:1, :] += jnp.sum(d_g * made["soft"], axis=0,
                                      keepdims=True)
        d_g = d_g * made["slope"]
        ddecay_ref[1:2, :] += jnp.sum(d_g, axis=0, keepdims=True)
    dq_ref[rows, :] = d_q.astype(dq_ref.dtype)
    dk_ref[rows, :] = d_k.astype(dk_ref.dtype)
    dv_ref[rows, :] = d_r.astype(dv_ref.dtype)
    dg_ref[rows, :] = d_g.astype(dg_ref.dtype)
    eye = _iota((c, c), 0) == _iota((c, c), 1)
    dbeta_ref[pl.ds(n, 1), :] = jnp.sum(jnp.where(eye, d_beta, 0.0),
                                        axis=0, keepdims=True)


#: chunks a block (a grid step) at most. Worth little: at the cell's
#: shape 8 read 2.5% (forward) and 3.3% (gradient) under 1, and 16 and
#: 32 under 0.5% more (a step's copies run beside the block before's
#: arithmetic: PERF.md, PR 42)
BLOCK_CHUNKS = 8


def _blocks(n):
    """(blocks, chunks a block) for a row of n chunks: as few blocks
    as ``BLOCK_CHUNKS`` allows, of equal size (the row is padded up)."""
    blocks = -(-n // BLOCK_CHUNKS)
    return blocks, -(-n // blocks)


def _operand_specs(n, per, dk, dv, block_of):
    """Block specs of (meta [B, 4, N, C], beta [B, H, N, C], q, k [B,
    L, H x dk], v [B, L, H x dv], g [B, L, H x dk], decay [H, 2, dk])
    on the grid (row, head, step): a row's small arrays whole, a
    head's block ``block_of(step)`` of ``per`` chunks cut out of the
    row as it lies."""
    def rows(d):
        return pl.BlockSpec((None, per * CHUNK, d),
                            lambda i, j, s: (i, block_of(s), j))
    return [pl.BlockSpec((None, 4, n, CHUNK), lambda i, j, s: (i, 0, 0, 0)),
            pl.BlockSpec((None, None, n, CHUNK),
                         lambda i, j, s: (i, j, 0, 0)),
            rows(dk), rows(dk), rows(dv), rows(dk),
            pl.BlockSpec((None, 2, dk), lambda i, j, s: (j, 0, 0))]


def _state_spec(dk, dv):
    """A [dv, dk] block of [B, H, dv, dk]: a row's last state or its
    cotangent."""
    return pl.BlockSpec((None, None, dv, dk), lambda i, j, s: (i, j, 0, 0))


def _kept_shapes(dk, dv):
    """What the forward keeps of a chunk for the backward, in the
    order of ``RESIDUAL_NAMES``: its start state [dv, dk] and what
    ``_hand_over`` lays out."""
    return (dv, dk), _HANDED


def _kept_spec(shape, per, block_of):
    """``per`` chunks' blocks of a kept [B, H, N, *shape]."""
    return pl.BlockSpec((None, None, per) + shape,
                        lambda i, j, s: (i, j, block_of(s), 0, 0))


_SEMANTICS = ("parallel", "parallel", "arbitrary")


def _forward_call(static, keep_starts, *operands):
    _, beta, q, _, v, *_ = operands
    b, h, n, _ = beta.shape
    l = q.shape[1]
    dk, dv = q.shape[-1] // h, v.shape[-1] // h
    blocks, per = _blocks(n)
    f32 = jnp.float32
    scratch = _scratch_shapes(dk, dv, backward=False)
    out_shape = [jax.ShapeDtypeStruct((b, l, h * dv), v.dtype),
                 jax.ShapeDtypeStruct((b, h, dv, dk), f32)]
    out_specs = [pl.BlockSpec((None, per * CHUNK, dv),
                              lambda i, j, s: (i, s, j)),
                 _state_spec(dk, dv)]
    if keep_starts:
        for kept in _kept_shapes(dk, dv):
            out_shape.append(jax.ShapeDtypeStruct((b, h, n) + kept, f32))
            out_specs.append(_kept_spec(kept, per, lambda s: s))
    return pl.pallas_call(
        functools.partial(_forward_kernel, static, keep_starts,
                          tuple(scratch)),
        out_shape=out_shape,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(b, h, blocks),
            in_specs=_operand_specs(n, per, dk, dv, lambda s: s),
            out_specs=out_specs, scratch_shapes=list(scratch.values())),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        name=DELTA_FWD,
    )(*operands)


def _backward_call(static, operands, starts, pairs, do, dlast):
    _, beta, q, k, v, g, decay = operands
    b, h, n, _ = beta.shape
    dk, dv = q.shape[-1] // h, v.shape[-1] // h
    blocks, per = _blocks(n)
    scratch = _scratch_shapes(dk, dv, backward=True)

    def back(s):
        return blocks - 1 - s

    def rows(d):
        return pl.BlockSpec((None, per * CHUNK, d),
                            lambda i, j, s: (i, back(s), j))

    def whole(*shape):  # a (row, head)'s, resident over its chunks
        return pl.BlockSpec((None, None) + shape,
                            lambda i, j, s: (i, j, 0, 0))

    return pl.pallas_call(
        functools.partial(_backward_kernel, static, tuple(scratch)),
        out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                   for x in (q, k, v, g, beta)]
        + [jax.ShapeDtypeStruct((b,) + decay.shape, jnp.float32)],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=0, grid=(b, h, blocks),
            in_specs=_operand_specs(n, per, dk, dv, back) + [
                _kept_spec(kept, per, back)
                for kept in _kept_shapes(dk, dv)] + [
                rows(dv), _state_spec(dk, dv)],
            out_specs=[rows(dk), rows(dk), rows(dv), rows(dk),
                       whole(n, CHUNK), whole(2, dk)],
            scratch_shapes=list(scratch.values())),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=_SEMANTICS),
        name=DELTA_BWD,
    )(*operands, starts, pairs, do, dlast)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _scan(static, meta, beta, q, k, v, g, decay):
    """meta [B, 4, N, C] int32, beta [B, H, N, C], q, k, g [B, L, H x
    dk], v [B, L, H x dv], decay [H, 2, dk] (``Prepare``'s rate on
    every lane and its ``dt_bias``) -> (o [B, L, H x dv] in v's dtype,
    the state after the row TRANSPOSED [B, H, dv, dk] float32).
    ``static``: (products in one bf16 pass, whether the kernels apply
    ``Prepare`` to q, k, g, its scale, its eps)."""
    return tuple(_forward_call(static, False, meta, beta, q, k, v, g, decay))


def _scan_fwd(static, *operands):
    o, last, *kept = _forward_call(static, True, *operands)
    kept = tuple(map(checkpoint_name, kept, RESIDUAL_NAMES))
    return (o, last), (operands, kept)


def _scan_bwd(static, residuals, cotangents):
    operands, kept = residuals
    dq, dk, dv, dg, dbeta, ddecay = _backward_call(
        static, operands, *kept, *cotangents)
    return (np.zeros(operands[0].shape, jax.dtypes.float0), dbeta, dq, dk,
            dv, dg, ddecay.sum(0))


_scan.defvjp(_scan_fwd, _scan_bwd)


def _scan_over(mesh, b: int, h: int):
    """``_scan`` as ``mesh`` runs it over ``b`` rows of ``h`` heads,
    or None where it cannot. A Mosaic call has no rule by which GSPMD
    could partition it (jax refuses to lower one on a mesh of more
    than one device), and a (row, head) is a recurrence of its own: on
    a mesh of rows over "data" and heads over "model"
    (``models/sharding.py``) each device runs the kernels on its own
    rows and heads under ``shard_map``, whose transpose adds d of
    ``decay`` up over "data". Rows or heads that do not divide, and a
    mesh with another axis in use (a context-parallel row is cut along
    its length, which the recurrence is not), go by the XLA products,
    which GSPMD partitions."""
    if mesh is None or mesh.size == 1:
        return _scan
    from realhf_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
    dp, tp = mesh.shape.get(DATA_AXIS, 1), mesh.shape.get(MODEL_AXIS, 1)
    if dp * tp != mesh.size or b % dp or h % tp:
        return None
    rows, heads = P(DATA_AXIS), P(DATA_AXIS, MODEL_AXIS)
    wide = P(DATA_AXIS, None, MODEL_AXIS)  # [B, L, H x d]

    def scan(static, *operands):
        return jax.shard_map(
            functools.partial(_scan, static), mesh=mesh,
            in_specs=(rows, heads, wide, wide, wide, wide, P(MODEL_AXIS)),
            out_specs=(wide, heads),
            # (a pallas_call's outputs say nothing of how they vary)
            check_vma=False)(*operands)

    return scan


def _by_kernels(q, k, v, g, beta, seg_ids, prepare, scan=_scan):
    """``chunked_delta_rule`` by the two kernels (``scan``: ``_scan``
    as the mesh runs it, ``_scan_over``): every chunk's pairs,
    inverse, coefficients and the state's carry live in VMEM. A
    ``Prepare`` the kernels apply themselves, a chunk at a time, and
    differentiate; any other ``prepare`` and the decay's mask of
    padding run as one XLA pass before them, in float32, which JAX
    differentiates."""
    f32 = jnp.float32
    b, l, h, dk = k.shape
    valid = seg_ids != 0
    fused = isinstance(prepare, Prepare)
    if fused:
        decay = jnp.stack([jnp.broadcast_to(
            prepare.rate.astype(f32)[:, None], (h, dk)),
            prepare.dt_bias.astype(f32)], axis=1)
        static = (_one_pass(), True, float(prepare.scale),
                  float(prepare.eps))
    else:
        q, k, g = q.astype(f32), k.astype(f32), g.astype(f32)
        if prepare is not None:
            q, k, g = prepare(q, k, g)
        # a padding token leaves the state as it is
        g = jnp.where(valid[..., None, None], g, 0.0)
        decay = jnp.zeros((h, 2, dk), f32)
        static = (_one_pass(), False, 1.0, 0.0)
    beta = jnp.where(valid[..., None], beta.astype(f32), 0.0)
    doc = doc_index(seg_ids)
    n = -(-l // CHUNK)
    n = _blocks(n)[0] * _blocks(n)[1]
    pad = n * CHUNK - l
    if pad:
        q, k, v, g, beta = (
            jnp.pad(x, [(0, 0), (0, pad)] + [(0, 0)] * (x.ndim - 2))
            for x in (q, k, v, g, beta))
        doc = jnp.pad(doc, ((0, 0), (0, pad)), mode="edge")
        valid = jnp.pad(valid, ((0, 0), (0, pad)))
    doc = doc.reshape(b, n, CHUNK)
    # the document of the token before each chunk (none: -1)
    before = jnp.pad(doc[:, :-1, -1], ((0, 0), (1, 0)), constant_values=-1)
    meta = jnp.stack([doc, doc == before[..., None], doc == doc[..., -1:],
                      valid.reshape(b, n, CHUNK)], axis=1).astype(jnp.int32)
    beta = jnp.moveaxis(beta, 2, 1).reshape(b, h, n, CHUNK)
    o, last = scan(static, meta, beta,
                   *(x.reshape(b, n * CHUNK, -1) for x in (q, k, v, g)),
                   decay)
    return (o.reshape(b, n * CHUNK, h, -1)[:, :l],
            jnp.swapaxes(last, -1, -2))
