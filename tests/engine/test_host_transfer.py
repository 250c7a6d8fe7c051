"""Bundled host<->device transfer paths:
GenerationOutput.to_host materializes every field in one device_get,
and Engine._globalize_tree uploads a whole pytree in one device_put.
Parity-checked against the per-leaf paths they replace."""

import numpy as np

import jax
import jax.numpy as jnp

from realhf_tpu.api.config import ModelName
from realhf_tpu.engine import packing
from realhf_tpu.engine.engine import Engine
from realhf_tpu.engine.generation import GenerationOutput
from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.ops.sampling import GenerationHyperparameters
from realhf_tpu.parallel.mesh import (
    MeshContext,
    ParallelismConfig,
    make_mesh,
)


def tiny_cfg():
    return TransformerConfig(
        n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
        intermediate_dim=64, vocab_size=64, apply_rotary=True,
        layer_norm_type="rms", mlp_type="llama",
        use_attention_bias=False, use_attn_proj_bias=False,
        use_mlp_bias=False, activation_function="silu",
        compute_dtype="float32")


class TestGenerationOutputToHost:

    def test_fields_match_per_leaf_materialization(self):
        out = GenerationOutput(
            tokens=jnp.arange(12, dtype=jnp.int32).reshape(3, 4),
            logprobs=jnp.linspace(-2.0, 0.0, 12).reshape(3, 4),
            logits_mask=None,
            lengths=jnp.array([4, 2, 3], jnp.int32),
            no_eos_mask=jnp.array([True, False, False]))
        host = out.to_host()
        for f in ("tokens", "logprobs", "lengths", "no_eos_mask"):
            np.testing.assert_array_equal(
                np.asarray(getattr(host, f)),
                np.asarray(getattr(out, f)))
        assert host.logits_mask is None

    def test_logits_mask_included_when_present(self):
        mask = jnp.zeros((2, 3, 8), bool).at[0, 0, 1].set(True)
        out = GenerationOutput(
            tokens=jnp.zeros((2, 3), jnp.int32),
            logprobs=jnp.zeros((2, 3)),
            logits_mask=mask,
            lengths=jnp.array([3, 3], jnp.int32),
            no_eos_mask=jnp.array([False, True]))
        host = out.to_host()
        np.testing.assert_array_equal(np.asarray(host.logits_mask),
                                      np.asarray(mask))


class TestGlobalizeTree:

    def _engine(self):
        cfg = tiny_cfg()
        parallel = ParallelismConfig(data_parallel_size=2,
                                     tensor_parallel_size=4)
        ctx = MeshContext(ModelName("xfer", 0), make_mesh(parallel),
                          parallel)
        params = T.init_params(cfg, jax.random.PRNGKey(0))
        return Engine(cfg, ctx, params)

    def test_tree_roundtrip(self):
        eng = self._engine()
        tree = ({"a": np.arange(6, dtype=np.int32).reshape(2, 3),
                 "b": np.ones((4,), np.float32)},
                np.array([1.0, 2.0], np.float32))
        dev = eng._globalize_tree(tree)
        flat_in = jax.tree.leaves(tree)
        flat_out = jax.tree.leaves(dev)
        assert len(flat_in) == len(flat_out)
        for a, b in zip(flat_in, flat_out):
            np.testing.assert_array_equal(np.asarray(b), a)

    def test_generate_consumes_bundled_uploads(self):
        # end-to-end: generate() goes through _globalize_tree for its
        # prompt arrays and the result round-trips via to_host()
        cfg = tiny_cfg()
        eng = self._engine()
        rng = np.random.default_rng(0)
        prompts = [rng.integers(2, cfg.vocab_size, size=5).astype(np.int32)
                   for _ in range(2)]
        ids, seg, pos = packing.left_padded_prompts(prompts, pad_id=0)
        g = GenerationHyperparameters(max_new_tokens=4, min_new_tokens=4,
                                      greedy=True,
                                      force_no_logits_mask=True)
        out = eng.generate(ids, seg, pos, jax.random.PRNGKey(0), g,
                           eos_token_id=None, pad_token_id=0).to_host()
        assert np.asarray(out.tokens).shape == (2, 4)
        assert np.asarray(out.lengths).tolist() == [4, 4]
