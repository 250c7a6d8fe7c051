"""Pipeline-schedule micro-bench: GPipe vs 1F1B, analytic vs measured.

Times one pipelined TRAIN step (forward + backward through the
pipeline shard_map) under both schedules on a pp-only virtual-CPU
mesh and prints ONE JSON line. On the shared-substrate CPU backend
every virtual device executes on the same cores, so wall-clock is
proportional to TOTAL computed stage-steps -- which makes the
garbage compute GPipe burns on bubble ticks directly measurable:

    measured_bubble_fraction = 1 - t_1f1b / t_gpipe
                             ~ (S-1)/(M+S-1)   (the analytic fraction)

because GPipe computes 2*(M+S-1)*S stage-steps per train step while
the 1F1B schedule's cond-masked ticks compute exactly 2*M*S
(parallel/schedule.computed_stage_steps). On lockstep silicon the
masked ticks return energy/HBM slack instead of wall-clock; the tick
counts and analytic fractions in the payload are backend-independent.

bench.py runs this in a subprocess (CPU-forced) and merges the JSON
into the BENCH payload as ``pipeline_schedule_bench``.

Usage::

    python scripts/bench_pipeline.py [--stages 4] [--microbatches 4]
        [--layers 8] [--hidden 64] [--seqlen 64] [--reps 3] [--stream-mult 1]
"""
import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from realhf_tpu.base.backend import (  # noqa: E402
    enable_compile_cache,
    force_cpu_backend,
)


def run(stages: int, microbatches: int, layers: int, hidden: int,
        seqlen: int, reps: int, stream_mult: int = 1) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from realhf_tpu.models import sharding as shard_rules
    from realhf_tpu.models import transformer as T
    from realhf_tpu.models.config import TransformerConfig
    from realhf_tpu.parallel import schedule as sched_mod
    from realhf_tpu.parallel.mesh import ParallelismConfig, make_mesh
    from realhf_tpu.parallel.pipeline import PipelineContext

    S, M = stages, microbatches
    cfg = TransformerConfig(
        n_layers=layers, n_kv_heads=2, n_q_heads=4,
        hidden_dim=hidden, intermediate_dim=2 * hidden,
        vocab_size=128, apply_rotary=True, layer_norm_type="rms",
        mlp_type="llama", use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu", compute_dtype="float32")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    b = M * stream_mult
    ids = jnp.asarray(rng.integers(
        2, cfg.vocab_size, size=(b, seqlen)).astype(np.int32))
    seg = jnp.asarray(np.ones((b, seqlen), np.int32))

    parallel = ParallelismConfig(pipeline_parallel_size=S)
    mesh = make_mesh(parallel, devices=jax.devices("cpu")[:S])
    p_sharded = jax.device_put(params,
                               shard_rules.param_shardings(cfg, mesh))

    def time_schedule(schedule: str) -> float:
        pipe = PipelineContext(mesh=mesh, n_stages=S, n_microbatches=M,
                               schedule=schedule)

        def loss(p):
            h, _ = T.forward(cfg, p, ids, seg, pipeline=pipe)
            logits = T.lm_logits(cfg, p, h)
            return (jax.nn.log_softmax(logits) ** 2).mean()

        step = jax.jit(jax.grad(loss))
        jax.block_until_ready(step(p_sharded))  # compile + warmup
        t0 = time.monotonic()
        for _ in range(reps):
            jax.block_until_ready(step(p_sharded))
        return (time.monotonic() - t0) / reps

    out = dict(
        backend=jax.default_backend(),
        stages=S, microbatches=M,
        ticks_per_pass=sched_mod.ticks_per_pass(S, M),
        train_ticks=sched_mod.train_ticks(S, M),
        analytic_bubble_fraction=round(sched_mod.bubble_fraction(S, M),
                                       4),
        schedules={},
    )
    for schedule in ("gpipe", "1f1b"):
        t = time_schedule(schedule)
        out["schedules"][schedule] = dict(
            step_s=round(t, 4),
            computed_stage_steps=sched_mod.computed_stage_steps(
                S, M, schedule))
    t_g = out["schedules"]["gpipe"]["step_s"]
    t_f = out["schedules"]["1f1b"]["step_s"]
    # shared-substrate wall ratio ~= computed-stage-step ratio; on a
    # lockstep backend this would read ~0 while the analytic fraction
    # still describes the per-stage idle ticks
    out["measured_bubble_fraction"] = round(1 - t_f / max(t_g, 1e-9), 4)
    out["note"] = ("measured fraction = 1 - t_1f1b/t_gpipe on a "
                   "shared-substrate backend; compare to "
                   "analytic (S-1)/(M+S-1)")
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--stages", type=int, default=4)
    ap.add_argument("--microbatches", type=int, default=4)
    ap.add_argument("--layers", type=int, default=8)
    ap.add_argument("--hidden", type=int, default=64)
    ap.add_argument("--seqlen", type=int, default=64)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--stream-mult", type=int, default=1,
                    help="streams per microbatch")
    args = ap.parse_args(argv)
    if args.layers % args.stages:
        ap.error("--layers must divide evenly into --stages")

    force_cpu_backend(n_devices=max(args.stages, 1))
    enable_compile_cache()
    out = run(args.stages, args.microbatches, args.layers, args.hidden,
              args.seqlen, args.reps, args.stream_mult)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
