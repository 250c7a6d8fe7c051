"""Compiled-mode (non-interpret) Pallas kernel tier.

The interpret-mode tests elsewhere in tests/ops prove kernel MATH; an
interpret-only kernel is still a first-contact risk because nothing
exercises the Mosaic lowering until a chip window. This tier runs each
kernel with ``interpret=False`` wherever the backend can lower it and
skips WITH AN EXPLICIT REASON STRING everywhere else, so a TPU CI run
flips these from skipped to executed with no code change.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from realhf_tpu.base.backend import pallas_enabled

KERNELS = (
    "flash_attention",               # ops/flash_attention.py (packed fwd/bwd)
    "flash_decode_attention_stacked",  # ops/decode_attention.py
    "grouped_matmul",                # ops/grouped_matmul.py (the experts')
    "delta_rule_scan",               # ops/delta_rule.py (chunked scan fwd/bwd)
)


def _compiled_unavailable_reason(kernel: str):
    """None when `kernel` can run compiled here, else the skip reason:
    the program's own gate, outside the interpreter."""
    if pallas_enabled():
        return None
    return (f"compiled-mode {kernel} unavailable: backend "
            f"{jax.default_backend()!r} cannot lower Mosaic kernels")


@pytest.mark.parametrize("kernel", list(KERNELS))
def test_compiled_kernel_matches_reference(kernel):
    """Run the kernel with interpret=False against its XLA reference;
    on backends that cannot lower Mosaic this records the explicit
    per-kernel skip reason instead of silently not running."""
    reason = _compiled_unavailable_reason(kernel)
    if reason is not None:
        pytest.skip(reason)

    rng = np.random.default_rng(0)
    if kernel == "flash_attention":
        from realhf_tpu.ops.attention import packed_attention_xla
        from realhf_tpu.ops.flash_attention import flash_attention
        b, l, nq, nkv, hd = 2, 256, 8, 2, 128
        q = jnp.asarray(rng.standard_normal((b, l, nq, hd)), jnp.float32)
        k = jnp.asarray(rng.standard_normal((b, l, nkv, hd)), jnp.float32)
        v = jnp.asarray(rng.standard_normal((b, l, nkv, hd)), jnp.float32)
        seg = np.ones((b, l), np.int32)
        seg[:, l // 2:] = 2
        seg[-1, -l // 4:] = 0
        seg = jnp.asarray(seg)
        ref = packed_attention_xla(q, k, v, seg, causal=True)
        # flash_attention has no interpret switch: off-TPU it cannot
        # run at all, which is exactly what the skip above encodes
        got = flash_attention(q, k, v, seg, causal=True)
    elif kernel == "grouped_matmul":
        from realhf_tpu.ops.grouped_matmul import grouped_matmul
        sizes = jnp.asarray([200, 0, 312], jnp.int32)
        q = jnp.asarray(rng.standard_normal((512, 256)), jnp.float32)
        w = jnp.asarray(rng.standard_normal((3, 256, 384)), jnp.float32)
        ref = jax.lax.ragged_dot(q, w, sizes)
        got = grouped_matmul(q, w, sizes)
    elif kernel == "delta_rule_scan":
        from realhf_tpu.ops import delta_rule as D
        b, l, h, d = 2, 200, 2, 128
        unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
        q, k = (jnp.asarray(unit(rng.standard_normal((b, l, h, d))),
                            jnp.float32) for _ in range(2))
        v = jnp.asarray(rng.standard_normal((b, l, h, d)), jnp.float32)
        g = -jnp.asarray(np.log1p(np.exp(rng.standard_normal((b, l, h, d)))),
                         jnp.float32)
        beta = jnp.asarray(rng.uniform(size=(b, l, h)), jnp.float32)
        seg = np.ones((b, l), np.int32)
        seg[:, 90:] = 2
        seg[-1, -30:] = 0
        seg = jnp.asarray(seg)
        ref = D._by_xla(q, k, v, g, beta, seg, None)[0]
        got = D.chunked_delta_rule(q, k, v, g, beta, seg)[0]
    else:  # flash_decode_attention_stacked
        from realhf_tpu.ops.attention import decode_attention
        from realhf_tpu.ops.decode_attention import (
            flash_decode_attention_stacked,
        )
        b, s, nq, nkv, hd, nl = 4, 256, 8, 2, 128, 2
        q = jnp.asarray(rng.standard_normal((b, nq, hd)), jnp.float32)
        ks = jnp.asarray(rng.standard_normal((nl, b, nkv, s, hd)),
                         jnp.float32)
        vs = jnp.asarray(rng.standard_normal((nl, b, nkv, s, hd)),
                         jnp.float32)
        valid = np.zeros((b, s), bool)
        for i, n in enumerate(rng.integers(1, s + 1, size=b)):
            valid[i, :n] = True
        valid = jnp.asarray(valid)
        li = 1
        ref = decode_attention(q, ks[li], vs[li], valid)
        got = flash_decode_attention_stacked(
            q, ks, vs, valid, jnp.int32(li), interpret=False)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-2, rtol=2e-2)
