"""YaRN's frequencies and the partial rotation (``ops/rotary.py``)
against the formula written out, and against ``transformers``' own
``_compute_yarn_parameters`` where the installed version has it."""

import math

import jax.numpy as jnp
import numpy as np
import pytest

from realhf_tpu.ops import rotary

#: Laguna-XS.2's full-attention layers: r = 64 of a head's 128 values
LAGUNA = dict(dim=64, base=500000.0, factor=64.0,
              original_max_positions=4096, beta_fast=64.0, beta_slow=1.0)


def by_the_formula(dim, base, factor, original_max_positions, beta_fast,
                   beta_slow):
    def d(turns):
        return dim * math.log(original_max_positions / (turns * 2 * math.pi)) \
            / (2 * math.log(base))
    low, high = max(math.floor(d(beta_fast)), 0), min(math.ceil(d(beta_slow)),
                                                      dim - 1)
    out = []
    for j in range(dim // 2):
        ramp = min(max((j - low) / (high - low), 0.0), 1.0)
        plain = base ** (-2 * j / dim)
        out.append((1 - ramp) * plain + ramp * plain / factor)
    return np.asarray(out), low, high


@pytest.mark.parametrize("params", [
    LAGUNA, dict(LAGUNA, dim=128), dict(LAGUNA, beta_fast=32.0,
                                        original_max_positions=8192,
                                        factor=128.0)],
    ids=["laguna_xs2", "whole_head", "laguna_s"])
def test_yarn_frequencies_are_the_formulas(params):
    want, low, high = by_the_formula(**params)
    got = rotary.yarn_inv_freq(**params)
    assert got.dtype == np.float32 and got.shape == (params["dim"] // 2,)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # below ``low`` the plain frequency, above ``high`` divided by factor
    plain = params["base"] ** (-2 * np.arange(params["dim"] // 2)
                               / params["dim"])
    np.testing.assert_allclose(got[:low + 1], plain[:low + 1], rtol=2e-6)
    np.testing.assert_allclose(got[high:], plain[high:] / params["factor"],
                               rtol=2e-6)
    assert 0 < low < high < params["dim"] // 2  # the ramp is inside


def test_yarn_frequencies_are_transformers_own():
    rope_utils = pytest.importorskip("transformers.modeling_rope_utils")
    from transformers import PretrainedConfig
    config = PretrainedConfig(
        rope_theta=500000.0, head_dim=128, hidden_size=2048,
        num_attention_heads=16, partial_rotary_factor=0.5,
        max_position_embeddings=262144,
        rope_scaling=dict(rope_type="yarn", factor=64.0,
                          original_max_position_embeddings=4096,
                          beta_fast=64, beta_slow=1,
                          attention_factor=1.4158883083359672))
    inv_freq, factor = rope_utils._compute_yarn_parameters(config, "cpu")
    np.testing.assert_allclose(rotary.yarn_inv_freq(**LAGUNA),
                               inv_freq.numpy(), rtol=2e-6)
    assert factor == 1.4158883083359672


def test_yarn_tables_carry_the_attention_factor():
    pos = jnp.arange(40, dtype=jnp.int32).reshape(2, 20)
    def table(**kw):
        return rotary.rotary_freqs(
            pos, LAGUNA["dim"], LAGUNA["base"], LAGUNA["factor"], "yarn",
            LAGUNA["original_max_positions"],
            beta_fast=LAGUNA["beta_fast"], beta_slow=LAGUNA["beta_slow"],
            **kw)

    cos, sin = table(attention_factor=1.5)
    plain_cos, plain_sin = table()
    assert cos.shape == sin.shape == (2, 20, 32)
    np.testing.assert_allclose(cos, 1.5 * plain_cos, rtol=1e-6)
    np.testing.assert_allclose(sin, 1.5 * plain_sin, rtol=1e-6)
    ang = np.asarray(pos, np.float64)[..., None] \
        * by_the_formula(**LAGUNA)[0]
    np.testing.assert_allclose(plain_cos, np.cos(ang), atol=2e-5)


@pytest.mark.parametrize("r", [8, 16], ids=["half", "whole"])
def test_partial_rotation_turns_the_first_values_and_passes_the_rest(r):
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((2, 5, 3, 16)), jnp.float32)
    pos = jnp.arange(10, dtype=jnp.int32).reshape(2, 5)
    cos, sin = rotary.rotary_freqs(pos, r, 10000.0)
    got = np.asarray(rotary.apply_rotary(x, cos, sin))
    xn, c, s = np.asarray(x), np.asarray(cos)[:, :, None], \
        np.asarray(sin)[:, :, None]
    x1, x2 = xn[..., :r // 2], xn[..., r // 2:r]
    want = np.concatenate([x1 * c - x2 * s, x2 * c + x1 * s, xn[..., r:]],
                          axis=-1)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
    assert np.array_equal(got[..., r:], xn[..., r:])
    assert got.dtype == np.float32
