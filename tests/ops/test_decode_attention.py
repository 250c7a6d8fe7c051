"""Pallas flash-decode kernel vs the XLA decode reference, run in the
Pallas TPU interpreter on CPU (kernel-vs-reference tier). The kernel
takes the stacked cache ``[nl, B, nkv, S, hd]`` and a layer index; a
single layer's cache is the ``nl=1`` case."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from realhf_tpu.ops.attention import decode_attention
from realhf_tpu.ops.decode_attention import (
    decode_layer_copies,
    flash_decode_attention_stacked,
)
from realhf_tpu.ops.flash_attention import flash_fwd_per_bwd


def make_inputs(rng, b=4, s=96, nq=8, nkv=2, hd=128, n_valid=None, nl=1):
    # head-major cache layout [nl, B, nkv, S, hd]
    q = jnp.asarray(rng.standard_normal((b, nq, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((nl, b, nkv, s, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((nl, b, nkv, s, hd)), jnp.float32)
    valid = np.zeros((b, s), bool)
    lens = (n_valid if n_valid is not None
            else rng.integers(1, s + 1, size=b))
    for i in range(b):
        valid[i, :lens[i]] = True
    return q, k, v, jnp.asarray(valid), np.asarray(lens)


def check(got, ref):
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block_k", [32, 96])
def test_matches_xla(block_k):
    rng = np.random.default_rng(0)
    q, k, v, valid, _ = make_inputs(rng)
    ref = decode_attention(q, k[0], v[0], valid)
    got = flash_decode_attention_stacked(q, k, v, valid, 0,
                                         block_k=block_k, interpret=True)
    check(got, ref)


def test_gqa_group_padding():
    """group < 8 exercises the sublane padding path."""
    rng = np.random.default_rng(1)
    q, k, v, valid, _ = make_inputs(rng, nq=2, nkv=2)  # group=1
    ref = decode_attention(q, k[0], v[0], valid)
    got = flash_decode_attention_stacked(q, k, v, valid, 0, interpret=True)
    check(got, ref)


def test_ragged_s_is_refused():
    """A cache length no K block divides is refused, not padded: a pad
    would copy the whole stack for every token. The generation paths
    allocate caches pre-padded (``transformer.round_cache_len``)."""
    rng = np.random.default_rng(2)
    q, k, v, valid, _ = make_inputs(rng, s=70)
    with pytest.raises(ValueError, match="multiple of the K block"):
        flash_decode_attention_stacked(q, k, v, valid, 0, block_k=32,
                                       interpret=True)
    # a length under one block is its own block
    ref = decode_attention(q, k[0], v[0], valid)
    got = flash_decode_attention_stacked(q, k, v, valid, 0, interpret=True)
    check(got, ref)


def test_sliding_window():
    rng = np.random.default_rng(3)
    q, k, v, valid, lens = make_inputs(rng, n_valid=[40, 60, 96, 8])
    slot = jnp.asarray(lens - 1, jnp.int32)
    ref = decode_attention(q, k[0], v[0], valid, sliding_window=16,
                           slot=slot)
    got = flash_decode_attention_stacked(
        q, k, v, valid, 0, sliding_window=16, slot=slot, block_k=32,
        interpret=True)
    check(got, ref)


def test_empty_cache_rows_zero():
    rng = np.random.default_rng(4)
    q, k, v, valid, _ = make_inputs(rng, b=2)
    valid = valid.at[0].set(False)  # stream 0: nothing valid yet
    got = flash_decode_attention_stacked(q, k, v, valid, 0, interpret=True)
    assert np.all(np.asarray(got[0]) == 0.0)
    ref = decode_attention(q, k[0], v[0], valid)
    check(got[1], ref[1])


@pytest.mark.parametrize("layer", [0, 2])
def test_stacked_layer_index_matches_per_layer(layer):
    """A traced layer index (an array) selects that layer's rows."""
    rng = np.random.default_rng(5)
    q, k_all, v_all, valid, _ = make_inputs(
        rng, b=2, s=64, nl=3, n_valid=[40, 40])
    ref = decode_attention(q, k_all[layer], v_all[layer], valid)
    got = flash_decode_attention_stacked(
        q, k_all, v_all, valid, jnp.asarray(layer, jnp.int32),
        interpret=True)
    check(got, ref)


@pytest.mark.parametrize("layer", [0, 1, 2], ids=["first", "middle", "last"])
@pytest.mark.parametrize("hd,nq,nkv", [(64, 14, 2), (128, 32, 8)],
                         ids=["hd64_group7", "hd128_group4"])
def test_static_layer_index(layer, hd, nq, nkv):
    """The unrolled decode loop's call: a Python int for the layer, at
    the benchmark models' head size and query group (Qwen2.5-0.5B 14/2
    heads of 64, Mistral-7B 32/8 heads of 128)."""
    rng = np.random.default_rng(7)
    q, k_all, v_all, valid, _ = make_inputs(
        rng, b=2, s=32, nq=nq, nkv=nkv, hd=hd, nl=3)
    ref = decode_attention(q, k_all[layer], v_all[layer], valid)
    got = jax.jit(lambda *a: flash_decode_attention_stacked(
        *a, layer, interpret=True))(q, k_all, v_all, valid)
    check(got, ref)


def test_stacked_traced_layer_under_scan():
    """The layer index may be a traced scan value (the deep-model
    decode path)."""
    rng = np.random.default_rng(6)
    nl = 3
    q, k_all, v_all, _, _ = make_inputs(rng, b=2, s=32, nl=nl)
    valid = jnp.ones((2, 32), bool)

    def body(carry, li):
        out = flash_decode_attention_stacked(q, k_all, v_all, valid, li,
                                             interpret=True)
        return carry, out

    _, outs = jax.lax.scan(body, 0,
                           jnp.arange(nl, dtype=jnp.int32))
    for li in range(nl):
        ref = decode_attention(q, k_all[li], v_all[li], valid)
        check(outs[li], ref)


# Two decode loops over a [2, 4, 2, 16, 64] stack as the chip's
# compiler prints them: one slices each layer out and relayouts it for
# the kernel (what `k_all[l]` became), one hands the stack to the kernel.
_SLICING = """\
HloModule jit_generate, is_scheduled=true

%fused_computation.1 (param_0.1: bf16[2,4,2,16,64]) -> bf16[4,2,16,64] {
  %param_0.1 = bf16[2,4,2,16,64]{3,4,2,1,0:T(8,128)(2,1)} parameter(0)
  %slice.1 = bf16[1,4,2,16,64]{3,4,2,1,0:T(8,128)(2,1)} slice(%param_0.1), slice={[1:2], [0:4], [0:2], [0:16], [0:64]}
  ROOT %bitcast.1 = bf16[4,2,16,64]{2,3,1,0:T(8,128)(2,1)} bitcast(%slice.1)
}

%region_0.body (arg: (s32[], bf16[2,4,2,16,64])) -> (s32[], bf16[2,4,2,16,64]) {
  %arg = (s32[], bf16[2,4,2,16,64]{3,4,2,1,0:T(8,128)(2,1)}) parameter(0)
  %k_all = bf16[2,4,2,16,64]{3,4,2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %dynamic_update_slice.1 = bf16[2,4,2,16,64]{3,4,2,1,0:T(8,128)(2,1)} dynamic-update-slice(%k_all, %row, %c1, %c0, %c0, %slot, %c0)
  %slice_bitcast_fusion.1 = bf16[4,2,16,64]{2,3,1,0:T(8,128)(2,1)} fusion(%dynamic_update_slice.1), kind=kLoop, calls=%fused_computation.1
  %copy.1 = bf16[4,2,16,64]{3,2,1,0:T(8,128)(2,1)} copy(%slice_bitcast_fusion.1)
  %view = bf16[4,2,16,64]{3,2,1,0:T(8,128)(2,1)} bitcast(%copy.1)
  %decode_attn.1 = bf16[4,2,8,64]{3,2,1,0:T(8,128)(2,1)} custom-call(%q, %view, %keep), custom_call_target="tpu_custom_call"
  ROOT %tuple = (s32[], bf16[2,4,2,16,64]{3,4,2,1,0:T(8,128)(2,1)}) tuple(%i, %dynamic_update_slice.1)
}
"""

_IN_PLACE = """\
HloModule jit_generate, is_scheduled=true

%region_0.body (arg: (s32[], bf16[2,4,2,16,64])) -> (s32[], bf16[2,4,2,16,64]) {
  %arg = (s32[], bf16[2,4,2,16,64]{4,3,2,1,0:T(8,128)(2,1)}) parameter(0)
  %k_all = bf16[2,4,2,16,64]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %dynamic_update_slice.1 = bf16[2,4,2,16,64]{4,3,2,1,0:T(8,128)(2,1)} dynamic-update-slice(%k_all, %row, %c1, %c0, %c0, %slot, %c0)
  %decode_attn_stacked.1 = bf16[4,2,8,64]{3,2,1,0:T(8,128)(2,1)} custom-call(%l, %q, %dynamic_update_slice.1, %keep), custom_call_target="tpu_custom_call"
  ROOT %tuple = (s32[], bf16[2,4,2,16,64]{4,3,2,1,0:T(8,128)(2,1)}) tuple(%i, %dynamic_update_slice.1)
}
"""


def test_decode_layer_copies_counts_device_operations_of_a_layers_shape():
    """The fusion that slices the layer out and the copy that
    relayouts it count; the fusion's own body, the bitcast view and
    instructions of the stack's or the output's shape do not."""
    assert decode_layer_copies(_SLICING, (4, 2, 16, 64)) == 2
    assert decode_layer_copies(_IN_PLACE, (4, 2, 16, 64)) == 0
    # another device's share of the layer is another shape
    assert decode_layer_copies(_SLICING, (2, 2, 16, 64)) == 0


# A scanned stack's two loop bodies as the chip's compiler prints them
# (layouts and operands shortened): the forward's, and the backward's
# with and without the recomputed block's own run of the forward
# kernel. A tuple's elements and the fusions that read a kernel's
# output name the kernel too; only the custom calls count.
_FWD_BODY = """\
%region_fwd.body (arg: (s32[], bf16[1,4,1024,64])) -> (s32[], bf16[1,4,1024,64]) {
  %flash_fwd.6 = (bf16[1,4,1024,64]{3,2,1,0:T(8,128)(2,1)S(1)}, f32[1,4,1024,128]{3,2,1,0:T(8,128)S(1)}) custom-call(%lo, %hi, %q, %k, %v, %segq, %segk), custom_call_target="tpu_custom_call"
  %pallas_call.42 = bf16[1,4,1024,64]{3,2,1,0:T(8,128)(2,1)S(1)} get-tuple-element(%flash_fwd.6), index=0
  %pallas_call.43 = f32[1,4,1024,128]{3,2,1,0:T(8,128)S(1)} get-tuple-element(%flash_fwd.6), index=1
  ROOT %tuple.1 = (s32[], bf16[1,4,1024,64]{3,2,1,0:T(8,128)(2,1)}) tuple(%i, %pallas_call.42)
}
"""
_RECOMPUTED = """\
  %flash_fwd.7 = (bf16[1,4,1024,64]{3,2,1,0:T(8,128)(2,1)S(1)}, f32[1,4,1024,128]{3,2,1,0:T(8,128)S(1)}) custom-call(%lo, %hi, %q, %k, %v, %segq, %segk), custom_call_target="tpu_custom_call"
  %lse = f32[1,4,1024,128]{3,2,1,0:T(8,128)S(1)} get-tuple-element(%flash_fwd.7), index=1
"""
_KEPT = """\
  %lse = f32[1,4,1024,128]{3,2,1,0:T(8,128)S(1)} fusion(%kept_lse), kind=kLoop, calls=%fused_computation.7
"""
_BWD_BODY = """\
%fused_computation.7 (param_0.7: f32[1,4,1024]) -> f32[1,4,1024,128] {
  %param_0.7 = f32[1,4,1024]{2,1,0:T(4,128)} parameter(0)
  %flash_fwd.99 = f32[1,4,1024,128]{3,2,1,0:T(8,128)} custom-call(%param_0.7), custom_call_target="in_a_fusion_body"
  ROOT %broadcast.7 = f32[1,4,1024,128]{3,2,1,0:T(8,128)} broadcast(%param_0.7), dimensions={0,1,2}
}

%region_bwd.body (arg: (s32[], f32[1,4,1024,64])) -> (s32[], f32[1,4,1024,64]) {
<FORWARD>  %delta = f32[1,4,1024,128]{3,2,1,0:T(8,128)S(1)} fusion(%row_sums), kind=kLoop, calls=%fused_computation.7
  %flash_bwd_dq.12 = f32[1,4,1024,64]{3,2,1,0:T(8,128)S(1)} custom-call(%lo, %hi, %q, %k, %v, %segq, %segk, %do, %lse, %delta), custom_call_target="tpu_custom_call"
  %flash_bwd_dkv.12 = (f32[1,4,1024,64]{3,2,1,0:T(8,128)S(1)}, f32[1,4,1024,64]{3,2,1,0:T(8,128)S(1)}) custom-call(%lo2, %hi2, %q, %k, %v, %segq, %segk, %do, %lse, %delta), custom_call_target="tpu_custom_call"
  %convert_bitcast_fusion.6 = f32[1,1024,4,64]{3,1,2,0:T(8,128)S(1)} fusion(%flash_bwd_dq.12), kind=kLoop, calls=%fused_computation.8
  %custom-call.3 = f32[2,1,4,1024]{3,2,1,0:T(4,128)S(1)} custom-call(), custom_call_target="AllocateBuffer"
  ROOT %tuple.2 = (s32[], f32[1,4,1024,64]{3,2,1,0:T(8,128)}) tuple(%i, %flash_bwd_dq.12)
}
"""


@pytest.mark.parametrize("forward,want", [(_RECOMPUTED, 2.0), (_KEPT, 1.0)],
                         ids=["recomputed", "kept"])
def test_flash_fwd_per_bwd_counts_the_kernels_custom_calls(forward, want):
    """The ``flash_fwd`` custom calls over the ``flash_bwd_dq`` ones: 2
    where the backward's body runs the forward kernel again, 1 where
    it reads the kept log-sum-exp. What a fusion body holds, a tuple's
    elements, the consumers of a kernel's output and other custom
    calls are not counted."""
    text = "HloModule jit_train_step, is_scheduled=true\n\n" + _FWD_BODY \
        + "\n" + _BWD_BODY.replace("<FORWARD>", forward)
    assert flash_fwd_per_bwd(text) == want
    # an unrolled stack of three layers holds the pair three times
    assert flash_fwd_per_bwd(text * 3) == want


def test_flash_fwd_per_bwd_of_a_program_without_a_backward_is_none():
    """A logprobs program runs the forward kernel and no backward one;
    a program without the kernels neither."""
    assert flash_fwd_per_bwd(
        "HloModule jit_logprobs, is_scheduled=true\n\n" + _FWD_BODY) is None
    assert flash_fwd_per_bwd(_IN_PLACE) is None
