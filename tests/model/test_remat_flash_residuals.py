"""A rematerialised block keeps the flash kernel's output and
log-sum-exp (``models/transformer.py:_remat``,
``ops/flash_attention.py:RESIDUAL_NAMES``): the backward of a layer
runs ``flash_bwd_dq`` and ``flash_bwd_dkv`` and NOT ``flash_fwd`` a
second time. TRACED only: the Pallas interpreter cannot run under
``jax.checkpoint`` on this jax (its ``OrderedIOEffect`` is refused in
the partial evaluation of a remat), so the jaxpr of the gradient is
counted here, the kernels' numbers are held by
``tests/ops/test_flash_attention.py`` and the compiled programs by
``tests/ops/test_chip_compile.py``."""

import collections

import jax
import jax.numpy as jnp
import pytest

from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.ops.attention import make_sharded_attention
from realhf_tpu.ops.flash_attention import flash_attention
from realhf_tpu.parallel.mesh import ParallelismConfig, make_mesh

KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")
ROW = 128


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
        n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=64, head_dim=16,
        intermediate_dim=128, vocab_size=64, apply_rotary=True,
        layer_norm_type="rms", mlp_type="llama", use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu", **pattern, **kw)


def _count(jaxpr, counts):
    """``primitive (a kernel by its name) -> equations`` of a jaxpr and
    every jaxpr inside it; a scan's body counts once."""
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        counts[eqn.params["name"] if name == "pallas_call" else name] += 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _count(sub, counts)
    return counts


def _gradient_counts(cfg, stack):
    params = jax.eval_shape(
        lambda: T.init_params(cfg, jax.random.PRNGKey(0)))
    ids = jax.ShapeDtypeStruct((2, ROW), jnp.int32)
    attn = _flash
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


@pytest.mark.parametrize("policy", ["nothing_saveable",
                                    "dots_with_no_batch_dims_saveable"])
@pytest.mark.parametrize("stack,layers", [("scanned", 1), ("a w", 2),
                                          ("scanned d2t2", 1)])
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
    """The flash names are kept BESIDES what ``remat_policy`` names:
    under ``dots_with_no_batch_dims_saveable`` the backward recomputes
    no product, under ``nothing_saveable`` it recomputes six of the
    block's seven (q, k, v, o, gate and up; the last product's output
    is nobody's residual)."""
    dots = {
        policy: _gradient_counts(_cfg(
            stack, gradient_checkpointing=True,
            remat_policy=policy), stack)["dot_general"]
        for policy in ("nothing_saveable",
                       "dots_with_no_batch_dims_saveable")}
    layers = 1 if stack == "scanned" else 2
    assert dots["nothing_saveable"] \
        - dots["dots_with_no_batch_dims_saveable"] == 6 * layers
    assert dots["dots_with_no_batch_dims_saveable"] \
        == _gradient_counts(_cfg(stack), stack)["dot_general"]
