"""A rematerialised block keeps the flash kernel's output and
log-sum-exp (``models/transformer.py:_remat``,
``ops/flash_attention.py:RESIDUAL_NAMES``) and what its two wide
attention projections made (``models/transformer.py:
PROJECTION_RESIDUALS``): the backward of a layer runs ``flash_bwd_dq``
and ``flash_bwd_dkv`` and NOT ``flash_fwd`` a second time, and
neither ``x @ wq`` nor ``attn @ wo``. The kernels are TRACED only: the Pallas interpreter cannot run under
``jax.checkpoint`` on this jax (its ``OrderedIOEffect`` is refused in
the partial evaluation of a remat), so the jaxpr of the gradient is
counted here, the kernels' numbers are held by
``tests/ops/test_flash_attention.py`` and the compiled programs by
``tests/ops/test_chip_compile.py``; the XLA attention path runs, so
its gradients are compared with the un-rematerialised model's."""

import collections

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.ops.attention import (
    make_sharded_attention,
    packed_attention_xla,
)
from realhf_tpu.ops.flash_attention import flash_attention
from realhf_tpu.parallel.mesh import ParallelismConfig, make_mesh

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
ROW = 128
POLICIES = ("nothing_saveable", "dots_with_no_batch_dims_saveable")
#: the weights' shapes of ``_cfg``'s layers: a product ``x @ w`` in
#: its FORWARD form is told by them (wk and wv share one)
HIDDEN, Q_WIDTH, KV_WIDTH, MLP_WIDTH = 64, 128, 32, 192
PROJECTIONS = {"wq": (HIDDEN, Q_WIDTH), "wk wv": (HIDDEN, KV_WIDTH),
               "wo": (Q_WIDTH, HIDDEN)}
FEED_FORWARD = {"wg wu": (HIDDEN, MLP_WIDTH), "wd": (MLP_WIDTH, HIDDEN)}


def _flash(q, k, v, seg, causal=True, scale=None, sliding_window=None):
    """The flash path forced: what ``packed_attention`` calls on a TPU."""
    return flash_attention(q, k, v, seg, causal=causal, scale=scale,
                           sliding_window=sliding_window)


def _cfg(stack, **kw):
    """Two layers: ``scanned`` (a model of one block, ``lax.scan`` over
    ``params["blocks"]``; ``scanned d2t2``: its kernels under
    ``shard_map`` on a 2 x 2 mesh, as the benchmark's four-chip cell
    trains) or ``a w`` (a full and a window layer, unrolled over
    ``params["layers"]``)."""
    pattern = {} if stack.startswith("scanned") else dict(
        layer_pattern=(("attention", "dense"), ("window", "dense")),
        sliding_window=32)
    return TransformerConfig(
        n_layers=2, n_kv_heads=2, n_q_heads=8, hidden_dim=HIDDEN,
        head_dim=16, intermediate_dim=MLP_WIDTH, vocab_size=64,
        apply_rotary=True,
        layer_norm_type="rms", mlp_type="llama", use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu", **pattern, **kw)


def _count(jaxpr, counts):
    """``primitive (a kernel by its name) -> equations`` of a jaxpr and
    every jaxpr inside it; a scan's body counts once. A product in
    the forward's form (``x @ w``: the last axis of ``x`` against the
    first of a matrix; the backward's two products of the same layer
    contract otherwise) also counts under its matrix's shape."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        counts[eqn.params["name"] if name == "pallas_call" else name] += 1
        if name == "dot_general":
            x, w = (v.aval for v in eqn.invars)
            if w.ndim == 2 and eqn.params["dimension_numbers"] == (
                    ((x.ndim - 1,), (0,)), ((), ())):
                counts[w.shape] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count(sub, counts)
    return counts


def _gradient_counts(cfg, stack, attn=_flash):
    params = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    ids = jax.ShapeDtypeStruct((2, ROW), jnp.int32)
    if stack.endswith("d2t2"):
        par = ParallelismConfig(data_parallel_size=2,
                                tensor_parallel_size=2)
        attn = make_sharded_attention(
            make_mesh(par, devices=jax.devices("cpu")[:4]), inner=_flash)

    def loss(p, ids, seg):
        hidden, _ = T.forward(cfg, p, ids, seg, attention_fn=attn)
        return hidden.astype(jnp.float32).sum()

    return _count(jax.make_jaxpr(jax.grad(loss))(params, ids, ids).jaxpr,
                  collections.Counter())


STACKS = pytest.mark.parametrize(
    "stack,layers", [("scanned", 1), ("a w", 2), ("scanned d2t2", 1)])


@pytest.mark.parametrize("policy", POLICIES)
@STACKS
def test_a_rematerialised_layer_runs_each_flash_kernel_once(stack, layers,
                                                            policy):
    """One ``flash_fwd``, one ``flash_bwd_dq`` and one
    ``flash_bwd_dkv`` a layer (a scanned stack holds its layers' one
    body), as with no rematerialisation at all; before the residuals
    were kept the recomputed block held a second ``flash_fwd``."""
    counts = _gradient_counts(_cfg(stack, gradient_checkpointing=True,
                                   remat_policy=policy), stack)
    assert {k: counts[k] for k in KERNELS} == dict.fromkeys(KERNELS, layers)
    plain = _gradient_counts(_cfg(stack), stack)
    assert {k: plain[k] for k in KERNELS} == dict.fromkeys(KERNELS, layers)


@pytest.mark.parametrize("stack", ["scanned", "a w"])
def test_a_remat_policy_still_keeps_what_it_kept(stack):
    """The names are kept BESIDES what ``remat_policy`` names: under
    ``dots_with_no_batch_dims_saveable`` the backward recomputes no
    product, under ``nothing_saveable`` it recomputes four of the
    block's seven: k, v and the feed-forward's gate and up (q and the
    projected output are kept by name; the last product's output is
    nobody's residual). Before the projections' residuals were named
    it recomputed six."""
    dots = {
        policy: _gradient_counts(_cfg(
            stack, gradient_checkpointing=True,
            remat_policy=policy), stack)["dot_general"]
        for policy in POLICIES}
    layers = 1 if stack == "scanned" else 2
    assert dots["nothing_saveable"] \
        - dots["dots_with_no_batch_dims_saveable"] == 4 * layers
    assert dots["dots_with_no_batch_dims_saveable"] \
        == _gradient_counts(_cfg(stack), stack)["dot_general"]


def _xla(q, k, v, seg, causal=True, scale=None, sliding_window=None):
    """The XLA attention path: what ``packed_attention`` calls off a
    TPU; q, k and v are its einsums' operands."""
    return packed_attention_xla(q, k, v, seg, causal=causal, scale=scale,
                                sliding_window=sliding_window)


@pytest.mark.parametrize("attn", [_flash, _xla], ids=["flash", "xla"])
@pytest.mark.parametrize("policy", POLICIES)
@STACKS
def test_a_rematerialised_layer_runs_each_wide_projection_once(
        stack, layers, policy, attn):
    """``x @ wq`` and ``attn @ wo`` stand ONCE a layer in the
    gradient's jaxpr, in the forward, as with no rematerialisation at
    all: the rematerialised body holds neither, whatever the policy
    and on either attention path. What nobody names, ``x @ wk``,
    ``x @ wv`` and the feed-forward's gate and up, is there a second
    time under ``nothing_saveable`` (the control: the count sees a
    recomputed product)."""
    counts = _gradient_counts(_cfg(stack, gradient_checkpointing=True,
                                   remat_policy=policy), stack, attn)
    plain = _gradient_counts(_cfg(stack), stack, attn)
    shapes = {**PROJECTIONS, **FEED_FORWARD}
    once = {"wq": layers, "wk wv": 2 * layers, "wo": layers,
            "wg wu": 2 * layers, "wd": layers}
    assert {k: plain[shape] for k, shape in shapes.items()} == once
    again = 1 + (policy == "nothing_saveable")
    assert {k: counts[shape] for k, shape in shapes.items()} == {
        **once, "wk wv": 2 * layers * again, "wg wu": 2 * layers * again}


@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("stack", ["scanned", "a w"])
def test_gradients_are_the_unrematerialised_models(stack, policy):
    """The kept arrays are the values the forward computed and the
    backward's equations are unchanged: loss and every gradient of the
    rematerialised model equal the plain model's (float32, the XLA
    attention path on the CPU; the scanned stack and a full and a
    window layer unrolled)."""
    key = jax.random.PRNGKey(0)
    ids = jax.random.randint(key, (2, ROW), 1, 64)
    seg = jnp.where(jnp.arange(ROW) < 100, 1 + (jnp.arange(ROW) >= 40), 0)
    seg = jnp.broadcast_to(seg, ids.shape).astype(jnp.int32)

    def grads(cfg):
        params = T.init_params(cfg, key)

        def loss(p):
            hidden, _ = T.forward(cfg, p, ids, seg)
            return (hidden.astype(jnp.float32) ** 2).mean()

        return jax.jit(jax.value_and_grad(loss))(params)

    (loss, got), (plain_loss, want) = (
        grads(_cfg(stack, compute_dtype="float32",
                   gradient_checkpointing=True, remat_policy=policy)),
        grads(_cfg(stack, compute_dtype="float32")))
    np.testing.assert_allclose(loss, plain_loss, rtol=1e-6)
    assert float(loss) > 0
    flat, want = jax.tree_util.tree_leaves_with_path(got), \
        jax.tree.leaves(want)
    assert len(flat) == len(want)
    for (path, g), w in zip(flat, want):
        size = np.abs(np.asarray(w)).max()
        # (forward stops at the hidden states: the head has no part)
        assert size > 0 or "head" in jax.tree_util.keystr(path), path
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-6 * size,
                                   err_msg=jax.tree_util.keystr(path))
