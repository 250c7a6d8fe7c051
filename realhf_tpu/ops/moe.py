"""Mixture-of-experts layer: routing, dispatch, expert GEMMs, losses.

TPU-native replacement for reference ``realhf/impl/model/modules/moe/``
(TopKRouter router.py:24, MoETokenDispatcher token_dispatcher.py:17,
GroupedMLP experts.py:98) and ``impl/model/utils/moe.py`` (aux losses
:13-166). Expert weights are stacked [E, H, F] / [E, F, H]; GSPMD
shards them over the "model" axis (TP-sharded experts, the
reference's layout) and can shard the expert axis for true EP.

Three dispatch modes (``dispatch_mode``):
- ``ragged`` (``capacity_factor=None`` + ``use_grouped_gemm``, the
  default): (token, k) pairs sorted by expert feed one grouped GEMM a
  projection over the stacked weights (the equivalent of reference
  experts.py:98 GroupedMLP), then a float32 scatter-add back to the
  tokens. Exact (no token dropping), top-k cost only. The grouped
  GEMM is ``ops/grouped_matmul.py``'s Pallas kernels where they
  engage (``_grouped_products``: a TPU, the expert stacks whole on
  the device), else ``jax.lax.ragged_dot`` (XLA:TPU lowers it to a
  grouped-matmul kernel of its own, PERF.md).
- ``dense`` (``capacity_factor=None`` + ``use_grouped_gemm=False``):
  every expert sees every token through one batched einsum a
  projection, weighted by its gate (exact; E/topk times the FLOPs;
  the correctness reference for tests; of a rank's share, below,
  every HELD expert).
- ``capacity`` (``capacity_factor=c``): dispatch by dense one-hot
  einsums over a static expert-capacity axis -- each expert processes
  at most c * T * topk / E tokens through the batched einsums;
  overflow tokens are dropped from that expert (standard Switch/GShard
  semantics, reference topk_softmax_with_capacity, utils/moe.py:310).

Beside the auxiliary LOSSES the layer returns one statistic,
``LOAD_STAT``: the largest expert's load over the mean load. It is
never added to a loss (``aux_loss``), is reduced by max, not by sum,
over layers and microbatches, and comes back with the train step's
statistics.

**A rank's share** (``MoEConfig.experts_held = (first, count)``): the
layer holds ``count`` experts' weights, routes over all
``num_experts`` and computes the part of the result its own experts
give, in the ragged mode (``_ragged_share``) or, where a config says
``use_grouped_gemm=False``, in the dense mode over the HELD stacks
(every held expert over every token, a gate of 0 where it is not among
the token's k: ``E / k`` times the FLOPs of even routing, and a cost
that does not move with the routing). A capacity
that drops pairs is refused. In the ragged mode the (token, k)
pairs are sorted with the held experts' first; the first ``rows`` of
them are gathered, multiplied in ``count`` groups and scattered back;
pairs of absent experts are never multiplied: what those experts would
have added is left out (the kernels visit the held pairs' row tiles
alone; ``lax.ragged_dot`` multiplies all ``rows``, the rest zeroed).
``rows`` is ``SHARE_ROWS_OVER_MEAN`` times the
pairs even routing would bring the held experts; where a batch routes
MORE to them, a ``lax.cond`` takes the same path over all ``T x k``
sorted rows instead, ``rows`` of them at a time (a rematerialised scan:
the branch a step rarely takes then holds one chunk's intermediates,
not four or eight chunks' beside the other branch's; PERF.md, PR 37),
so no pair of a held expert is ever dropped, whatever the imbalance.
There is no exchange and nothing stands in
for the other ranks. With every expert held the same code is the
uncut layer. A SHARED expert (``MoEConfig.shared_intermediate_dim``,
leaves ``m["shared"]``) is no part of a share: every token visits it,
every rank holds it whole, and it is added once, after the routed
part, in every dispatch mode. Three more statistics then come back, ``HELD_PAIRS_STAT``
(the pairs of held experts, added up over layers and microbatches),
``HELD_LOAD_STAT`` (the busiest HELD expert over the mean of all,
``T x k / E``; max) and ``SHARE_OVERFLOW_STAT`` (the layers, added up
likewise, whose held pairs passed ``rows`` and took the slow path).
"""

from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from realhf_tpu.base.backend import pallas_enabled
from realhf_tpu.models.config import MoEConfig, TransformerConfig
from realhf_tpu.obs import parts as P
from realhf_tpu.ops.grouped_matmul import GMM as GMM_KERNEL, grouped_matmul
from realhf_tpu.ops.hlo_text import device_instructions


#: key, in the layer's auxiliary dict, of the one entry that is a
#: statistic and not a loss: max over experts of the (token, k) pairs
#: an expert received over the mean (T * k / E), pads included (they
#: are computed like any token)
LOAD_STAT = "moe_load_max_over_mean"
#: of a layer that holds a share of the experts: the (token, k) pairs
#: routed to the experts it holds (the rows its grouped products
#: multiply), and the busiest held expert's pairs over T * k / E
HELD_PAIRS_STAT = "moe_held_pairs"
HELD_LOAD_STAT = "moe_held_load_max_over_mean"
#: and whether its held pairs passed the fast path's rows (0 or 1: the
#: layer then gathered and multiplied all T * k sorted rows)
SHARE_OVERFLOW_STAT = "moe_share_overflows"
#: entries of the auxiliary dict that are statistics: never in a loss;
#: how each is reduced over layers and over a step's microbatches
STATS = {LOAD_STAT: jnp.max, HELD_LOAD_STAT: jnp.max,
         HELD_PAIRS_STAT: jnp.sum, SHARE_OVERFLOW_STAT: jnp.sum}


def aux_loss(aux: Dict[str, jnp.ndarray]):
    """What a training objective adds of a forward's auxiliary dict:
    the losses, not the statistics."""
    return sum(v for k, v in aux.items() if k not in STATS)


def reduce_layers(auxs: Dict[str, jnp.ndarray]) -> Dict[str, jnp.ndarray]:
    """Per-layer auxiliary entries [n_layers] -> scalars: losses add
    up, a statistic by its own rule (``STATS``)."""
    return {k: STATS.get(k, jnp.sum)(v) for k, v in auxs.items()}


def router_probs(cfg_moe: MoEConfig, logits: jnp.ndarray,
                 key: Optional[jax.Array] = None,
                 expert_bias: Optional[jnp.ndarray] = None
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """[T, E] logits -> (top-k probs [T, k], indices [T, k]).

    ``score_fn="sigmoid"`` (LFM2-MoE): every expert's score is the
    sigmoid of its logit; the k are the largest of score +
    ``expert_bias`` [E], which moves the CHOICE and never the gate and
    takes no gradient; the gates are the chosen scores, divided by
    (their sum + ``norm_topk_eps``) under ``norm_topk_prob``, times
    ``routed_scaling_factor``.

    Default (aux_loss/none): softmax over all experts, take top-k,
    and with ``norm_topk_prob`` renormalize (Mixtral semantics,
    equivalent to the reference's topk_softmax_with_capacity);
    without it the k gates stay the softmax's own values, whose sum
    is under 1 (OLMoE). Sinkhorn routing selects indices from
    the sinkhorn-normalized logits WITHOUT gradient, while gate values
    come from the raw logits (sigmoid for k=1, softmax for k>1) --
    matching reference router.py:53-76.
    """
    logits = logits.astype(jnp.float32)
    if cfg_moe.input_jitter_eps and key is not None:
        noise = jax.random.uniform(
            key, logits.shape, minval=1.0 - cfg_moe.input_jitter_eps,
            maxval=1.0 + cfg_moe.input_jitter_eps)
        logits = logits * noise
    if cfg_moe.score_fn == "sigmoid":
        scores = jax.nn.sigmoid(logits)
        choice = scores if expert_bias is None else scores \
            + jax.lax.stop_gradient(expert_bias.astype(jnp.float32))
        _, top_idx = jax.lax.top_k(choice, cfg_moe.top_k)
        top_probs = jnp.take_along_axis(scores, top_idx, axis=-1)
        if cfg_moe.norm_topk_prob:
            top_probs = top_probs / (
                top_probs.sum(-1, keepdims=True) + cfg_moe.norm_topk_eps)
        return top_probs * cfg_moe.routed_scaling_factor, top_idx
    if cfg_moe.routing_type == "sinkhorn":
        routed = sinkhorn(jax.lax.stop_gradient(logits))
        _, top_idx = jax.lax.top_k(routed, cfg_moe.top_k)
        if cfg_moe.top_k == 1:
            top_probs = jax.nn.sigmoid(
                jnp.take_along_axis(logits, top_idx, axis=-1))
        else:
            sel = jnp.take_along_axis(logits, top_idx, axis=-1)
            top_probs = jax.nn.softmax(sel, axis=-1)
        return top_probs, top_idx
    probs = jax.nn.softmax(logits, axis=-1)
    top_probs, top_idx = jax.lax.top_k(probs, cfg_moe.top_k)
    if cfg_moe.norm_topk_prob:
        top_probs = top_probs / jnp.maximum(
            top_probs.sum(-1, keepdims=True), 1e-9)
    return top_probs, top_idx


def sinkhorn(logits: jnp.ndarray, n_iters: int = 8,
             tol: float = 1e-4) -> jnp.ndarray:
    """Sinkhorn normalization of routing logits (reference
    utils/moe.py:69), fixed iteration count for jit."""
    cost = jnp.exp(logits)
    d0 = jnp.ones(cost.shape[0], jnp.float32)
    d1 = jnp.ones(cost.shape[1], jnp.float32)

    def body(_, carry):
        d0, d1 = carry
        d0 = 1.0 / (cost.shape[0] * (cost @ d1.reshape(-1, 1))[:, 0] + 1e-8)
        d1 = 1.0 / (cost.shape[1] * (d0 @ cost) + 1e-8)
        return d0, d1

    d0, d1 = jax.lax.fori_loop(0, n_iters, body, (d0, d1))
    return jnp.log(d1[None, :] * cost * d0[:, None] + 1e-20)


def load_balancing_loss(probs: jnp.ndarray, top_idx: jnp.ndarray,
                        n_experts: int, top_k: int,
                        valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Switch-transformer aux loss (reference
    switch_load_balancing_loss_func, utils/moe.py:13), over valid
    tokens only."""
    t = probs.shape[0]
    if valid is None:
        valid = jnp.ones((t,), jnp.float32)
    n = jnp.maximum(valid.sum(), 1.0)
    counts = jnp.zeros(n_experts, jnp.float32).at[top_idx.reshape(-1)].add(
        jnp.repeat(valid, top_idx.shape[1]))
    fraction_tokens = counts / (n * top_k)
    fraction_probs = (probs * valid[:, None]).sum(axis=0) / n
    return n_experts * (fraction_tokens * fraction_probs).sum()


def z_loss(logits: jnp.ndarray,
           valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Router z-loss (reference z_loss_func, utils/moe.py:54)."""
    z = jax.scipy.special.logsumexp(logits.astype(jnp.float32), axis=-1) ** 2
    if valid is None:
        return z.mean()
    return (z * valid).sum() / jnp.maximum(valid.sum(), 1.0)


def _activation(cfg: TransformerConfig, x: jnp.ndarray) -> jnp.ndarray:
    if cfg.activation_function == "silu":
        return jax.nn.silu(x)
    if cfg.activation_function == "gelu":
        return jax.nn.gelu(x, approximate=False)
    if cfg.activation_function == "gelu_new":
        return jax.nn.gelu(x, approximate=True)
    if cfg.activation_function == "relu":
        return jax.nn.relu(x)
    if cfg.activation_function == "relu2":
        return jnp.square(jax.nn.relu(x))
    raise NotImplementedError(cfg.activation_function)


def _dense_mlp(cfg, m, x, cdt):
    """A dense feed-forward (``models/transformer.py:_mlp_with_aux``;
    the shared expert below): gated, or two products with their
    biases."""
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


def _expert_ffn(cfg: TransformerConfig, m: Dict, xs: jnp.ndarray
                ) -> jnp.ndarray:
    """Batched expert MLP of the dense and capacity modes: xs
    [E, C, H] -> [E, C, H] through stacked [E, H, F] weights, one
    batched einsum per projection (every expert over C rows): three
    where the feed-forward is gated, ``act(gate) * up``, two where it
    is not, ``act(up)``."""
    cdt = xs.dtype
    up = jnp.einsum("ech,ehf->ecf", xs, m["wu"].astype(cdt))
    if cfg.gated_mlp:
        gate = jnp.einsum("ech,ehf->ecf", xs, m["wg"].astype(cdt))
        mid = _activation(cfg, gate) * up
    else:
        mid = _activation(cfg, up)
    return jnp.einsum("ecf,efh->ech", mid, m["wd"].astype(cdt))


def dispatch_mode(cfg: TransformerConfig) -> Optional[str]:
    """Single source of truth for the dispatch a config's MoE layers
    take: "ragged", "dense" or "capacity"; None for a dense model."""
    if cfg.moe is None or not cfg.n_moe_layers:
        return None
    if cfg.moe.capacity_factor is not None:
        return "capacity"
    return "ragged" if cfg.moe.use_grouped_gemm else "dense"


#: ``ep_constraint`` of a model whose expert stacks do NOT lie whole on
#: each device (the engine's word for a tensor- or data-parallel mesh):
#: a bare ``pallas_call`` has no partitioning rule, so the grouped
#: products stay ``lax.ragged_dot``, which GSPMD partitions
SHARDED_STACKS = "sharded_stacks"


def _grouped_products(cfg: TransformerConfig, m: Dict, xs: jnp.ndarray,
                      sizes: jnp.ndarray, kernel: bool,
                      every_row_covered: bool = False) -> jnp.ndarray:
    """The grouped products of the sorted rows ``xs [rows, H]``
    through the stacks of ``m`` (three of a gated feed-forward, two of
    an ungated one): ``sizes`` are the rows each expert's
    group covers, and may add up to less than ``rows``. What becomes
    of the rows past them differs by path, so the group sizes each
    path is handed are built HERE: ``ops/grouped_matmul.py``'s kernels
    (``kernel``) return those rows as zero and do not multiply them;
    ``lax.ragged_dot`` leaves them UNWRITTEN on the chip, in the
    backward's products too (PERF.md, PR 31), so there they are
    counted into the last group: every row lies in a group (the
    caller zeroes them on the way in and gives them no gate).
    ``every_row_covered``: the caller's sizes add up to the rows."""
    cdt = xs.dtype
    if kernel:
        dot = grouped_matmul
    else:
        dot = jax.lax.ragged_dot
        if not every_row_covered:
            sizes = sizes.at[-1].add(xs.shape[0] - sizes.sum())
    if not cfg.gated_mlp:
        up = dot(xs, m["wu"].astype(cdt), sizes)
        return dot(_activation(cfg, up), m["wd"].astype(cdt), sizes)
    gate = dot(xs, m["wg"].astype(cdt), sizes)
    up = dot(xs, m["wu"].astype(cdt), sizes)
    return dot(_activation(cfg, gate) * up, m["wd"].astype(cdt), sizes)


def grouped_product_calls(hlo_text: str) -> Dict[str, object]:
    """Which grouped matmul a compiled program's experts really run,
    from its optimized text (``Engine.compiled_text``): the custom
    calls that are ``ops/grouped_matmul.py``'s kernels
    (``moe_gmm_calls``: twelve a sparse layer of a train program, 3
    forward, 3 rematerialised, 3 + 3 backward; eight where the experts
    are ungated, 2, 2 and 2 + 2), those that are the
    compiler's own ``ragged-dot`` kernel (``moe_ragged_dot_calls``;
    XLA:CPU has no such call: 0 there), and ``moe_products``: ``gmm``
    where the program holds a kernel, else ``ragged_dot``. A loop's
    body counts once."""
    calls = [name for name, _, opcode in device_instructions(hlo_text)
             if opcode == "custom-call"]
    gmm = sum(GMM_KERNEL in name for name in calls)
    return dict(moe_products="gmm" if gmm else "ragged_dot",
                moe_gmm_calls=gmm,
                moe_ragged_dot_calls=sum("ragged-dot" in name
                                         for name in calls))


def _ragged_moe(cfg: TransformerConfig, m: Dict, xt: jnp.ndarray,
                top_probs: jnp.ndarray, top_idx: jnp.ndarray,
                group_sizes: jnp.ndarray, kernel: bool) -> jnp.ndarray:
    """Grouped-GEMM dispatch: sort (token, k) pairs by expert, run one
    grouped product per projection over the stacked [E, H, F] weights
    (``_grouped_products``), scatter-add gate-weighted outputs back.
    Exact top-k MoE (reference GroupedMLP, experts.py:98) with static
    shapes."""
    t, h = xt.shape
    k = cfg.moe.top_k

    with jax.named_scope(P.GATHER):
        order = jnp.argsort(top_idx.reshape(-1))      # sort by expert
        tok_idx = order // k
        xs = xt[tok_idx]                              # [T*k, H] sorted

    with jax.named_scope(P.PRODUCTS):
        down = _grouped_products(cfg, m, xs, group_sizes, kernel,
                                 every_row_covered=True)
    with jax.named_scope(P.COMBINE):
        gates_sorted = top_probs.reshape(-1)[order]   # pads carry 0
        weighted = down.astype(jnp.float32) * gates_sorted[:, None]
        return jnp.zeros((t, h), jnp.float32).at[tok_idx].add(weighted)


#: rows the fast path of a share gathers, over the pairs that even
#: routing would bring its experts (T x k x held / E): the bound of
#: the GATHER and of the scatter-add (a bounded number of rows spares
#: three quarters of both), and of the grouped products' operands; no
#: longer of what is multiplied: ``ops/grouped_matmul.py``'s kernels
#: visit the row tiles the held pairs cover and return the rest as
#: zero. Where ``lax.ragged_dot`` runs instead (``_grouped_products``)
#: every gathered row still lies in a group and is multiplied: XLA:TPU's
#: grouped matmul does skip the row tiles past its last group (16,384
#: sorted rows of which 8 groups cover 2,048 cost what 2,048 rows
#: alone do), but it leaves those rows UNWRITTEN, zero only by chance,
#: in the forward and in the backward's products alike: with group
#: sizes over the held pairs alone the cell's forward agreed with the
#: reference and its gradient norm read 185,709 against 0.78, the loss
#: standing still (PERF.md, PR 31).
SHARE_ROWS_OVER_MEAN = 2


def share_rows(cfg: TransformerConfig, t: int) -> int:
    """The sorted rows a share's fast path gathers of ``t`` tokens'
    ``t x k``: ``SHARE_ROWS_OVER_MEAN`` times what even routing brings
    the held experts, and no more than all."""
    k, e = cfg.moe.top_k, cfg.moe.num_experts
    _, count = cfg.moe.experts_held
    return min(-(-SHARE_ROWS_OVER_MEAN * t * k * count // e), t * k)


def _ragged_share(cfg: TransformerConfig, m: Dict, xt: jnp.ndarray,
                  top_probs: jnp.ndarray, top_idx: jnp.ndarray,
                  held_sizes: jnp.ndarray, kernel: bool) -> jnp.ndarray:
    """``_ragged_moe`` for a rank that holds experts ``first .. first
    + count - 1`` (``m``'s stacks are theirs alone; ``held_sizes``
    [count] their loads): the pairs sorted with the held experts'
    first, in the stacks' order, and only the first ``rows`` of them
    gathered, multiplied and scattered back. Rows past the held pairs
    are zeroed on the way in, get no gate and lie in no expert's group:
    what the grouped products make of them is ``_grouped_products``'s
    to say (the kernels: zero, unmultiplied; ``lax.ragged_dot``: the
    last group's, multiplied) and adds nothing, to the result or to a
    gradient. ``rows`` is static: the fast path takes the first
    ``rows`` sorted rows, the slow path all ``T x k`` in chunks of
    ``rows`` (``every_row``)."""
    t, h = xt.shape
    k, e = cfg.moe.top_k, cfg.moe.num_experts
    first, _ = cfg.moe.experts_held
    with jax.named_scope(P.GATHER):
        order = jnp.argsort(((top_idx - first) % e).reshape(-1))
        n_held = held_sizes.sum()
        gates_flat = top_probs.reshape(-1)

    def part(rows, at=None):
        """The sorted rows ``[at, at + rows)`` (``at`` a traced start
        in the slow path's scan; None: the first ``rows``, in the form
        the fast path always had: it is the program every step runs,
        and its lowering stays what it was)."""
        with jax.named_scope(P.GATHER):
            if at is None:
                sel = order[:rows]
                mine = jnp.arange(rows) < n_held
                sizes = held_sizes
            else:
                sel = jax.lax.dynamic_slice_in_dim(padded, at, rows)
                mine = at + jnp.arange(rows) < n_held
                # each held expert's pairs inside the chunk
                ends = jnp.clip(jnp.cumsum(held_sizes), at, at + rows) - at
                sizes = jnp.diff(ends, prepend=0)
            tok_idx = sel // k
            xs = jnp.where(mine[:, None], xt[tok_idx], 0)
        with jax.named_scope(P.PRODUCTS):
            down = _grouped_products(cfg, m, xs, sizes, kernel)
        with jax.named_scope(P.COMBINE):
            gates = jnp.where(mine, gates_flat[sel], 0.0)
            weighted = down.astype(jnp.float32) * gates[:, None]
            return jnp.zeros((t, h), jnp.float32).at[tok_idx].add(
                weighted)

    rows = share_rows(cfg, t)
    if rows == t * k:
        return part(rows)
    chunks = -(-t * k // rows)
    with jax.named_scope(P.GATHER):  # (no operation where rows divide)
        padded = order if chunks * rows == t * k else jnp.pad(
            order, (0, chunks * rows - t * k))

    def every_row():
        """All ``T x k`` sorted rows, ``rows`` at a time, each chunk
        rematerialised in the backward: under a gradient the branch
        keeps its inputs and the running sum, not every chunk's
        gathered rows and products (the whole of them stood in the
        program's peak beside the fast path's: 2.1 of 14.7 GB at 24,576
        rows of 2048 and experts of 1408). Rows past ``T x k`` in the
        last chunk are no pair's (``mine`` is false there)."""
        out, _ = jax.lax.scan(
            jax.checkpoint(lambda acc, at: (acc + part(rows, at), None)),
            jnp.zeros((t, h), jnp.float32), jnp.arange(chunks) * rows)
        return out

    return jax.lax.cond(n_held <= rows, lambda: part(rows), every_row)


def moe_mlp_with_losses(cfg: TransformerConfig, m: Dict, x: jnp.ndarray,
                        rng: Optional[jax.Array] = None,
                        valid_mask: Optional[jnp.ndarray] = None,
                        ep_constraint=None,
                        route_on: Optional[jnp.ndarray] = None
                        ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
    """MoE feed-forward over [B, L, H]; ``valid_mask`` [B, L] excludes
    padding tokens from routing, expert capacity, and the aux losses
    (pad positions carry real hidden states in the packed layout).
    ``route_on`` [B, L, H]: what the ROUTER's product reads where that
    is another tensor than the experts' input ``x``
    (``MoEConfig.router_input``: the layer's input, before its
    operator); the product then stands under sub-part
    ``experts/router`` (it runs before attention, and the compiler may
    put it there). None: ``x``, and the program it always was.

    ``ep_constraint`` (models/sharding.py moe_ep_constraint) pins the
    expert-major intermediates to the expert-parallel axis so GSPMD
    lowers dispatch/combine to all-to-alls; requires the capacity or
    dense dispatch mode. In the ragged mode it is None, or
    ``SHARDED_STACKS`` where the expert stacks are sharded over a
    mesh: the grouped products then stay ``lax.ragged_dot``; without
    it they are the Pallas kernels wherever ``pallas_enabled()``."""
    moe = cfg.moe
    if moe.input_jitter_eps and rng is None:
        raise NotImplementedError(
            "input_jitter_eps requires threading an rng key through the "
            "forward pass, which is not wired yet; unset it.")
    b, l, h = x.shape
    t = b * l
    xt = x.reshape(t, h)
    if valid_mask is None:
        valid = jnp.ones((t,), jnp.float32)
    else:
        valid = valid_mask.reshape(t).astype(jnp.float32)
    e = moe.num_experts

    def router_logits(rows):
        return (rows.astype(jnp.float32)
                @ m["router"].astype(jnp.float32))  # [T, E]

    if route_on is not None:
        with jax.named_scope(P.ROUTER):
            logits = router_logits(route_on.reshape(t, h))
    with jax.named_scope(P.ROUTE):
        n_valid = jnp.maximum(valid.sum(), 1.0)
        if route_on is None:
            logits = router_logits(xt)
        probs_full = jax.nn.softmax(logits, axis=-1)
        top_probs, top_idx = router_probs(moe, logits, rng,
                                          m.get("expert_bias"))
        # pads contribute nothing: zero their gates everywhere below
        top_probs = top_probs * valid[:, None]
        # (token, k) pairs an expert receives, pads among them
        load = jnp.bincount(top_idx.reshape(-1),
                            length=e).astype(jnp.int32)
    ep = ep_constraint if ep_constraint is not None else (lambda a: a)
    mode = dispatch_mode(cfg)
    held = moe.experts_held
    if held is not None and mode not in ("ragged", "dense"):
        raise NotImplementedError(
            f"experts_held={held} of {e} needs the ragged or the dense "
            f"dispatch mode, not {mode!r}")
    if mode == "ragged":
        kernel = ep_constraint != SHARDED_STACKS and pallas_enabled()
        if callable(ep_constraint):
            raise ValueError(
                "expert_parallel requires the capacity or dense "
                "dispatch mode; ragged grouped GEMMs cannot shard the "
                "group dim (set capacity_factor or "
                "use_grouped_gemm=False).")
        if held is None:
            out = _ragged_moe(cfg, m, xt.astype(x.dtype), top_probs,
                              top_idx, load, kernel)
        else:
            out = _ragged_share(cfg, m, xt.astype(x.dtype), top_probs,
                                top_idx, load[held[0]:held[0] + held[1]],
                                kernel)
    elif mode == "dense":
        # Dense mode: every expert over all tokens, gate-weighted (a
        # share: every HELD expert, the stacks' own; what the absent
        # ones would have added is left out, as in ``_ragged_share``).
        n = moe.n_held
        with jax.named_scope(P.PRODUCTS):
            xs = ep(jnp.broadcast_to(xt[None], (n, t, h)).astype(x.dtype))
            expert_out = ep(_expert_ffn(cfg, m, xs))  # [E, T, H]
        with jax.named_scope(P.COMBINE):
            gates = jnp.zeros((t, e), jnp.float32)
            gates = jax.vmap(lambda g, idx, p: g.at[idx].add(p))(
                gates, top_idx, top_probs)
            if held is not None:
                gates = gates[:, held[0]:held[0] + n]
            out = jnp.einsum("eth,te->th",
                             expert_out.astype(jnp.float32), gates)
    else:
        cap = max(1, int(moe.capacity_factor * t * moe.top_k / e))
        # position of each (token, k) within its expert's capacity;
        # pads removed from the one-hot so they never occupy slots
        onehot = jax.nn.one_hot(top_idx, e, dtype=jnp.int32)  # [T, k, E]
        onehot = onehot * valid.astype(jnp.int32)[:, None, None]
        flat = onehot.reshape(t * moe.top_k, e)
        pos = jnp.cumsum(flat, axis=0) * flat - 1  # [T*k, E]
        pos = pos.reshape(t, moe.top_k, e)
        within = (pos < cap) & (onehot > 0)
        # Each (token, expert) pair occupies at most one k slot, so the
        # k axis collapses before the big einsums: dispatch/combine are
        # [T, E, C], not [T, k, E, C].
        disp = within[..., None] & (
            pos[..., None] == jnp.arange(cap)[None, None, None, :])
        disp_tec = disp.sum(axis=1).astype(x.dtype)  # [T, E, C]
        expert_in = ep(jnp.einsum("th,tec->ech", xt.astype(x.dtype),
                                  disp_tec))
        expert_out = ep(_expert_ffn(cfg, m, expert_in))  # [E, C, H]
        combine = (disp.astype(jnp.float32)
                   * top_probs[:, :, None, None]).sum(axis=1)  # [T, E, C]
        out = jnp.einsum("ech,tec->th", expert_out.astype(jnp.float32),
                         combine)

    if "shared" in m:
        # the expert every token visits: a dense feed-forward (gated
        # as the routed ones are) beside them, weight 1, outside the sort. Every
        # rank of an expert-parallel deployment holds it whole, so the
        # shares' routed parts and THIS, once, add up to the layer.
        with jax.named_scope(P.SHARED_EXPERT):
            out = out + _dense_mlp(cfg, m["shared"], xt.astype(x.dtype),
                                   x.dtype).astype(jnp.float32)

    with jax.named_scope(P.ROUTE):  # the load statistics and losses
        losses = _losses(cfg, logits, probs_full, top_idx, load, valid, t)
    return out.reshape(b, l, h).astype(x.dtype), losses


def _losses(cfg: TransformerConfig, logits, probs_full, top_idx, load,
            valid, t: int) -> Dict[str, jnp.ndarray]:
    """The layer's auxiliary dict: its statistics (``STATS``) and the
    router's losses."""
    moe = cfg.moe
    e, held = moe.num_experts, moe.experts_held
    losses = {LOAD_STAT: load.max().astype(jnp.float32)
              * (e / (t * moe.top_k))}
    if held is not None:
        mine = load[held[0]:held[0] + held[1]]
        losses[HELD_PAIRS_STAT] = mine.sum().astype(jnp.float32)
        losses[HELD_LOAD_STAT] = mine.max().astype(jnp.float32) \
            * (e / (t * moe.top_k))
        # (the dense mode has no rows to overflow)
        losses[SHARE_OVERFLOW_STAT] = (
            mine.sum() > share_rows(cfg, t)).astype(jnp.float32) \
            if dispatch_mode(cfg) == "ragged" else jnp.zeros(())
    if moe.routing_type == "aux_loss" and moe.aux_loss_coeff:
        losses["moe_aux_loss"] = moe.aux_loss_coeff * load_balancing_loss(
            probs_full, top_idx, e, moe.top_k, valid=valid)
    if moe.z_loss_coeff:
        losses["moe_z_loss"] = moe.z_loss_coeff * z_loss(logits, valid=valid)
    return losses
