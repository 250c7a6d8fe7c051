"""Quick decode-path microbenchmark (task: decode_roofline_frac >= 0.35).

Runs the 650M serving-bench shape from bench.py:bench_sft on the real
chip and prints decode tokens/sec + HBM roofline fraction.
"""
import sys
import time

import numpy as np

import jax

sys.path.insert(0, __import__("os").path.dirname(__import__("os").path.dirname(__import__("os").path.abspath(__file__))))
from realhf_tpu.base.backend import enable_compile_cache  # noqa: E402
enable_compile_cache()

V5E_PEAK_FLOPS = 197e12
V5E_HBM_BW = 819e9


def main():
    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine import packing
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.models.config import TransformerConfig
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    from realhf_tpu.parallel.mesh import (
        MeshContext, ParallelismConfig, make_mesh,
    )
    from realhf_tpu.models import transformer as T

    cfg = TransformerConfig(
        n_layers=10, n_kv_heads=16, n_q_heads=16, hidden_dim=2048,
        intermediate_dim=5632, vocab_size=32000, n_positions=4096,
        apply_rotary=True, layer_norm_type="rms", mlp_type="llama",
        use_attention_bias=False, use_attn_proj_bias=False,
        use_mlp_bias=False, activation_function="silu",
        param_dtype="bfloat16", compute_dtype="bfloat16")
    parallel = ParallelismConfig()
    mesh = make_mesh(parallel, devices=jax.devices()[:1])
    ctx = MeshContext(ModelName("bench", 0), mesh, parallel)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    engine = Engine(cfg, ctx, params)

    rng = np.random.default_rng(0)
    gen_bs, gen_prompt_len, gen_new = 64, 256, 256
    gconfig = GenerationHyperparameters(
        max_new_tokens=gen_new, min_new_tokens=gen_new, greedy=False,
        top_k=50, top_p=0.95, force_no_logits_mask=True)
    prompts = [rng.integers(2, cfg.vocab_size, size=gen_prompt_len)
               .astype(np.int32) for _ in range(gen_bs)]
    pids, pseg, ppos = packing.left_padded_prompts(prompts, pad_id=0)
    key = jax.random.PRNGKey(0)
    t_c = time.monotonic()
    out = engine.generate(pids, pseg, ppos, key, gconfig,
                          eos_token_id=None, pad_token_id=0)
    # host materialization fences the timing
    np.asarray(out.tokens)
    print(f"compile+warmup: {time.monotonic() - t_c:.1f}s")

    g0 = time.monotonic()
    steps = 5
    for i in range(steps):
        out = engine.generate(pids, pseg, ppos, jax.random.fold_in(key, i),
                              gconfig, eos_token_id=None, pad_token_id=0)
        np.asarray(out.tokens)
    gdt = (time.monotonic() - g0) / steps

    kv_bytes_per_tok = 2 * cfg.n_layers * cfg.n_kv_heads * cfg.head_dim * 2
    kv_read = sum(gen_bs * (gen_prompt_len + t) * kv_bytes_per_tok
                  for t in range(gen_new))
    decode_bytes = gen_new * 2 * cfg.n_params() + kv_read
    roof_s = decode_bytes / V5E_HBM_BW
    print(f"gen wall: {gdt*1000:.1f} ms  "
          f"tok/s: {gen_bs*gen_new/gdt:.0f}  "
          f"roofline_frac: {roof_s/gdt:.4f} "
          f"(roof {roof_s*1000:.1f} ms)")


if __name__ == "__main__":
    main()
