"""Run the search cost-model calibration on the current backend and
store the artifact (VERDICT r4 #7; reference profiler-driven
``search_engine/estimate.py:323``).

Builds a bench-shaped PPO spec, probes measured train MFU and decode
bandwidth through ``calibrate_cost_model``, writes the calibrated
``TPUCostModel`` to ``--out`` (JSON), and prints the heuristic vs
searched allocation with MODELED step times under the calibrated
model for an ``--devices``-chip slice. On real hardware the measured
numbers make the comparison meaningful; on CPU this exercises the
pipeline only.

Usage: python scripts/calibrate_tpu.py [--out calibration_tpu.json]
"""
import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
from realhf_tpu.base.backend import enable_compile_cache  # noqa: E402
enable_compile_cache()


def build_spec():
    from realhf_tpu.api.config import DatasetAbstraction
    from realhf_tpu.base import testing
    from realhf_tpu.engine.optim import OptimizerConfig
    from realhf_tpu.experiments.common import apply_overrides
    from realhf_tpu.experiments.ppo_exp import PPOConfig
    from realhf_tpu.parallel.mesh import ParallelismConfig

    model_cfg = dict(
        n_layers=8, n_kv_heads=5, n_q_heads=10, hidden_dim=1280,
        intermediate_dim=3456, vocab_size=32000, n_positions=4096,
        apply_rotary=True, layer_norm_type="rms", mlp_type="llama",
        use_attention_bias=False, use_attn_proj_bias=False,
        use_mlp_bias=False, activation_function="silu")
    cfg = PPOConfig(experiment_name="calib", trial_name="t0")
    apply_overrides(cfg, {
        "dataset.train_bs_n_seqs": "64",
        "dataset.max_seqlen": "256",
        "ppo.max_new_tokens": "256",
    })
    spec = cfg.build()
    spec.dataset = DatasetAbstraction(
        "random_prompt", args=dict(n_prompts=64, prompt_len_min=256,
                                   prompt_len_max=256,
                                   vocab_size=32000))
    for role, mspec in spec.models.items():
        mspec.path = None
        mspec.random_init_config = dict(model_cfg)
        mspec.bf16 = True
        mspec.parallel = ParallelismConfig()
        if mspec.optimizer is not None:
            mspec.optimizer = OptimizerConfig(
                lr=1e-6, warmup_steps_proportion=0.0,
                lr_scheduler_type="constant")
    spec.tokenizer = testing.IntegerTokenizer(vocab_size=32000)
    return spec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="calibration_tpu.json")
    ap.add_argument("--devices", type=int, default=8,
                    help="slice size the allocation comparison models")
    args = ap.parse_args()

    import jax

    from realhf_tpu.experiments.heuristic import heuristic_allocations
    from realhf_tpu.search.engine import (
        Candidate,
        TPUCostModel,
        calibrate_cost_model,
        search_rpc_allocations,
        simulate_named_assignment,
        workloads_from_spec,
    )

    spec = build_spec()
    backend = jax.default_backend()
    base = TPUCostModel()
    cal = calibrate_cost_model(spec, base=base)
    artifact = dict(backend=backend,
                    base=dataclasses.asdict(base),
                    calibrated=dataclasses.asdict(cal))
    with open(args.out, "w") as f:
        json.dump(artifact, f, indent=2)
    print(f"calibration ({backend}) -> {args.out}")
    print(json.dumps(artifact["calibrated"]))

    # Heuristic vs searched allocation under the calibrated model.
    workloads, deps = workloads_from_spec(spec)
    searched = search_rpc_allocations(workloads, deps, args.devices,
                                      cost_model=cal)
    role_layouts, mfc_overrides = heuristic_allocations(spec,
                                                        args.devices)
    roles = {w.name: w.role for w in workloads}
    hpicks = {
        name: Candidate(
            parallel=mfc_overrides.get(name, role_layouts[role]),
            dev_lo=0, dev_hi=args.devices, time=0.0)
        for name, role in roles.items()
    }
    hsim = simulate_named_assignment(workloads, deps, args.devices,
                                     hpicks, cost_model=cal)
    print(f"\nsearched allocation (modeled step {searched.time:.4f}s):")
    for name, cand in searched.assignment.items():
        print(f"  {name:<14} {cand.parallel} "
              f"devs[{cand.dev_lo}:{cand.dev_hi}]")
    print(f"heuristic allocation (modeled step {hsim:.4f}s):")
    for name, c in hpicks.items():
        print(f"  {name:<14} {c.parallel}")
    print(f"\nsearched/heuristic modeled speedup: "
          f"{hsim / max(searched.time, 1e-9):.3f}x")


if __name__ == "__main__":
    main()
