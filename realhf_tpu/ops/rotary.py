"""Rotary position embeddings.

Parity with reference ``realhf/impl/model/modules/rotary.py``
(RotaryEmbedding:121 + linear/dynamic-NTK scaling :175-242), computed
functionally: frequencies are derived from explicit position ids, so
packed sequences and KV-cache decode use the same code path.
"""

import math
from typing import Optional, Tuple

import jax.numpy as jnp
import numpy as np


def yarn_inv_freq(dim: int, base: float, factor: float,
                  original_max_positions: int, beta_fast: float = 32.0,
                  beta_slow: float = 1.0) -> np.ndarray:
    """YaRN's frequencies for ``dim`` rotated values (``dim // 2`` of
    them, float32), as ``transformers``' ``_compute_yarn_parameters``:
    value pair j keeps its frequency ``base^(-2j/dim)`` where it turns
    more than ``beta_fast`` times over the original context, is
    divided by ``factor`` where it turns fewer than ``beta_slow``
    times, and is blended linearly in between."""
    def turn_dim(turns):
        return (dim * math.log(original_max_positions
                               / (turns * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(turn_dim(beta_fast)), 0)
    high = min(math.ceil(turn_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001  # transformers: no division by zero
    j = np.arange(dim // 2, dtype=np.float32)
    ramp = np.clip((j - low) / (high - low), 0.0, 1.0)
    pos_freq = base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    return ((1.0 - ramp) / pos_freq + ramp / (factor * pos_freq)) \
        .astype(np.float32)


def rotary_freqs(positions: jnp.ndarray, head_dim: int, base: float,
                 scaling: Optional[float] = None,
                 scaling_type: Optional[str] = None,
                 max_positions: Optional[int] = None, *,
                 beta_fast: float = 32.0, beta_slow: float = 1.0,
                 attention_factor: float = 1.0
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """cos/sin tables for the given integer positions.

    positions: any integer array shape ``S``; returns cos/sin of shape
    ``S + (head_dim // 2,)`` in fp32. ``head_dim`` is how many values
    of a head ROTATE: fewer than the head has under a partial rotary
    embedding (``apply_rotary`` passes the rest through).
    ``scaling_type`` "yarn": :func:`yarn_inv_freq` with ``scaling`` its
    factor and ``max_positions`` the ORIGINAL context, cos and sin both
    multiplied by ``attention_factor`` (the temperature YaRN folds into
    the rotated queries and keys).
    """
    if scaling_type is not None and scaling is None:
        raise ValueError("rotary scaling_type set but scaling factor is None")
    if scaling_type == "yarn":
        if max_positions is None:
            raise ValueError("yarn rotary scaling requires max_positions")
        inv_freq = jnp.asarray(yarn_inv_freq(
            head_dim, base, scaling, max_positions, beta_fast, beta_slow))
        angles = positions.astype(jnp.float32)[..., None] * inv_freq
        return (jnp.cos(angles) * attention_factor,
                jnp.sin(angles) * attention_factor)
    if scaling_type == "linear":
        positions = positions / scaling
    elif scaling_type == "dynamic":
        if max_positions is None:
            raise ValueError("dynamic NTK rotary scaling requires max_positions")
        # Dynamic NTK: enlarge the base when sequences exceed the
        # trained context (reference rotary.py:206-242).
        seq_len = positions.max() + 1
        ratio = jnp.maximum(seq_len / max_positions, 1.0)
        dim = head_dim
        base = base * (scaling * ratio - (scaling - 1)) ** (dim / (dim - 2))
    elif scaling_type is not None:
        raise NotImplementedError(f"rotary scaling type {scaling_type}")
    inv_freq = 1.0 / (base ** (jnp.arange(0, head_dim, 2,
                                          dtype=jnp.float32) / head_dim))
    angles = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(angles), jnp.sin(angles)


def apply_rotary(x: jnp.ndarray, cos: jnp.ndarray, sin: jnp.ndarray,
                 interleaved: bool = False) -> jnp.ndarray:
    """Rotate q or k. x: [..., n_heads, head_dim]; cos/sin broadcast over
    the head axis: [..., r//2]. ``r`` (twice the tables' width) values
    of every head are rotated, the FIRST r; the rest pass through (a
    partial rotary embedding, ``r < head_dim``)."""
    r = 2 * cos.shape[-1]
    if r < x.shape[-1]:
        return jnp.concatenate(
            [apply_rotary(x[..., :r], cos, sin, interleaved), x[..., r:]],
            axis=-1)
    cos = cos[..., None, :]
    sin = sin[..., None, :]
    if interleaved:
        x1 = x[..., 0::2]
        x2 = x[..., 1::2]
        o1 = x1 * cos - x2 * sin
        o2 = x2 * cos + x1 * sin
        out = jnp.stack([o1, o2], axis=-1).reshape(x.shape)
    else:
        half = x.shape[-1] // 2
        x1 = x[..., :half]
        x2 = x[..., half:]
        o1 = x1 * cos - x2 * sin
        o2 = x2 * cos + x1 * sin
        out = jnp.concatenate([o1, o2], axis=-1)
    return out.astype(x.dtype)
