"""Measure the rematerialization (recompute) tax single-chip
(VERDICT r4 #8).

Times the same SFT-shaped train step with gradient_checkpointing on
vs off on one device. The remat step recomputes each block's forward
in backward: ideal tax is 4/3 of the no-remat step (the accounting
bench.py applies); the measured ratio calibrates how much of that
ideal the chip actually pays. The pipeline's ``remat_tick`` nesting
adds one more block-forward recompute per tick boundary on top of
this per-block tax (memory numbers for that are pinned CPU-side in
tests/parallel/test_pipeline.py); bubble math lives in
docs/distributed.md.

Usage: python scripts/remat_tax.py [--layers 10] [--tokens 8192]
"""
import argparse
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from realhf_tpu.base.backend import enable_compile_cache  # noqa: E402
enable_compile_cache()


def timed_step(remat: bool, args):
    import jax
    import jax.numpy as jnp

    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.engine.optim import OptimizerConfig
    from realhf_tpu.models import transformer as T
    from realhf_tpu.models.config import TransformerConfig
    from realhf_tpu.ops import functional as F
    from realhf_tpu.parallel.mesh import (
        MeshContext,
        ParallelismConfig,
        make_mesh,
    )

    cfg = TransformerConfig(
        n_layers=args.layers, n_kv_heads=16, n_q_heads=16,
        hidden_dim=2048, intermediate_dim=5632, vocab_size=32000,
        n_positions=4096, apply_rotary=True, layer_norm_type="rms",
        mlp_type="llama", use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu", param_dtype="bfloat16",
        compute_dtype="bfloat16", gradient_checkpointing=remat)
    parallel = ParallelismConfig()
    mesh = make_mesh(parallel, devices=jax.devices()[:1])
    engine = Engine(cfg, MeshContext(ModelName("remat", 0), mesh,
                                     parallel),
                    T.init_params(cfg, jax.random.PRNGKey(0)),
                    optimizer=OptimizerConfig(
                        lr=1e-4, warmup_steps_proportion=0.0,
                        lr_scheduler_type="constant"),
                    total_train_steps=100)

    n_streams = 8
    stream_len = args.tokens // n_streams
    rng = np.random.default_rng(0)
    ids = rng.integers(2, cfg.vocab_size,
                       size=(n_streams, stream_len)).astype(np.int32)
    seg = np.ones_like(ids)
    mb = dict(input_ids=ids, seg_ids=seg)

    def loss_fn(p, h, mb):
        lp = F.shifted_logprobs_from_hidden(
            cfg, p, h, mb["input_ids"], mb["seg_ids"])
        seg_ = mb["seg_ids"]
        valid = jnp.concatenate(
            [(seg_[:, 1:] == seg_[:, :-1]) & (seg_[:, 1:] != 0),
             jnp.zeros_like(seg_[:, :1], bool)], axis=1)
        return -(lp * valid).sum() / jnp.maximum(valid.sum(), 1), {}

    for _ in range(2):
        engine.train_batch([mb], loss_fn, loss_fn_key="tax")
    jax.block_until_ready(engine.params)
    t0 = time.monotonic()
    for _ in range(args.steps):
        engine.train_batch([mb], loss_fn, loss_fn_key="tax")
    jax.block_until_ready(engine.params)
    return (time.monotonic() - t0) / args.steps


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--layers", type=int, default=10)
    ap.add_argument("--tokens", type=int, default=8192)
    ap.add_argument("--steps", type=int, default=5)
    args = ap.parse_args()

    t_plain = timed_step(False, args)
    t_remat = timed_step(True, args)
    print(f"plain={t_plain:.4f}s remat={t_remat:.4f}s "
          f"measured_tax={t_remat / t_plain:.3f}x (ideal 4/3 = 1.333x)")


if __name__ == "__main__":
    main()
