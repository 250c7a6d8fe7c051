"""Decode-phase profiling on the real chip.

Separates prefill from decode honestly (gn=1 vs gn=N difference, all
timings fenced by host materialization) and optionally dumps a
perfetto trace for op-level inspection.

Usage: python scripts/profile_decode.py [--trace DIR]
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

import jax  # noqa: E402

V5E_PEAK_FLOPS = 197e12
V5E_HBM_BW = 819e9


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default=None,
                    help="dump a jax.profiler trace to this dir")
    ap.add_argument("--layers", type=int, default=10)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--prompt", type=int, default=256)
    ap.add_argument("--gen", type=int, default=256)
    ap.add_argument("--no-pallas", action="store_true",
                    help="force the XLA fallback (A/B vs the kernels)")
    args = ap.parse_args()
    if args.no_pallas:
        os.environ["REALHF_TPU_DISABLE_PALLAS"] = "1"

    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine import packing
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.models import transformer as T
    from realhf_tpu.models.config import TransformerConfig
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    from realhf_tpu.parallel.mesh import (
        MeshContext, ParallelismConfig, make_mesh,
    )

    cfg = TransformerConfig(
        n_layers=args.layers, n_kv_heads=16, n_q_heads=16,
        hidden_dim=2048, intermediate_dim=5632, vocab_size=32000,
        n_positions=4096, apply_rotary=True, layer_norm_type="rms",
        mlp_type="llama", use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu", param_dtype="bfloat16",
        compute_dtype="bfloat16")
    parallel = ParallelismConfig()
    mesh = make_mesh(parallel, devices=jax.devices()[:1])
    ctx = MeshContext(ModelName("prof", 0), mesh, parallel)
    engine = Engine(cfg, ctx, T.init_params(cfg, jax.random.PRNGKey(0)))

    rng = np.random.default_rng(0)
    prompts = [rng.integers(2, cfg.vocab_size, size=args.prompt)
               .astype(np.int32) for _ in range(args.batch)]
    pids, pseg, ppos = packing.left_padded_prompts(prompts, pad_id=0)
    key = jax.random.PRNGKey(0)

    def timed(gn, reps=3):
        g = GenerationHyperparameters(
            max_new_tokens=gn, min_new_tokens=gn, greedy=False,
            top_k=50, top_p=0.95, force_no_logits_mask=True)
        out = engine.generate(pids, pseg, ppos, key, g,
                              eos_token_id=None, pad_token_id=0)
        np.asarray(out.tokens)  # compile + fence
        t0 = time.monotonic()
        for i in range(reps):
            out = engine.generate(pids, pseg, ppos,
                                  jax.random.fold_in(key, i), g,
                                  eos_token_id=None, pad_token_id=0)
            np.asarray(out.tokens)
        return (time.monotonic() - t0) / reps

    t1 = timed(1)
    tn = timed(args.gen)
    decode_s = tn - t1
    per_tok = decode_s / (args.gen - 1)
    kvb = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * 2
    kv_read = sum(args.batch * (args.prompt + t) * kvb
                  for t in range(1, args.gen))
    wbytes = (args.gen - 1) * 2 * cfg.n_params()
    roof_s = (wbytes + kv_read) / V5E_HBM_BW
    print(f"gen1={t1*1000:.1f}ms genN={tn*1000:.1f}ms "
          f"decode={decode_s*1000:.1f}ms ({per_tok*1e6:.0f} us/tok) "
          f"decode_tok_s={args.batch*(args.gen-1)/decode_s:.0f} "
          f"roofline_frac={roof_s/decode_s:.4f}")

    if args.trace:
        g = GenerationHyperparameters(
            max_new_tokens=16, min_new_tokens=16, greedy=False,
            top_k=50, top_p=0.95, force_no_logits_mask=True)
        with jax.profiler.trace(args.trace):
            out = engine.generate(pids, pseg, ppos, key, g,
                                  eos_token_id=None, pad_token_id=0)
            np.asarray(out.tokens)
        print("trace written to", args.trace)


if __name__ == "__main__":
    main()
