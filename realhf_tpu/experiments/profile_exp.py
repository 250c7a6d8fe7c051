"""Profile experiment: the full runtime on synthetic data.

Parity with reference ``realhf/experiments/benchmark/profile_exp.py``
(+ ``ModelInterface.mock``): run the 6-MFC PPO dataflow graph with
random-init models and random prompts through the real runtime (inline
or distributed), with per-MFC timing from the tracer's spans and,
given a directory, a ``jax.profiler`` trace of the same steps
(``mfc_timing_summary(run, profile_dir)``: ``obs.tracing.start``, the
one way to start a profile; ``python -m realhf_tpu.obs.parts
<profile_dir>`` then prints device time by part of the model).
REALHF_TPU_DUMP_MEMORY=1 saves a device-memory profile after each MFC
(base/monitor.py). Serves as both a system
test (everything wired, nothing real needed) and the measurement rig
for allocation decisions.

    python -m realhf_tpu.apps.quickstart profile \
        model_size=7b n_prompts=256 max_new_tokens=256 \
        benchmark_steps=3 actor_gen_alloc=d8t1
"""

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

from realhf_tpu.api.config import DatasetAbstraction
from realhf_tpu.api.experiment import ExperimentSpec, ModelSpec
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.experiments.common import register_experiment
from realhf_tpu.experiments.ppo_exp import PPOConfig

#: named model sizes (llama lineage; "tiny" for CI)
MODEL_SIZES: Dict[str, dict] = {
    "tiny": dict(n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
                 intermediate_dim=64, vocab_size=1000),
    "125m": dict(n_layers=12, n_kv_heads=12, n_q_heads=12,
                 hidden_dim=768, intermediate_dim=3072, vocab_size=32000),
    "1b": dict(n_layers=22, n_kv_heads=4, n_q_heads=32,
               hidden_dim=2048, intermediate_dim=5632, vocab_size=32000),
    "7b": dict(n_layers=32, n_kv_heads=32, n_q_heads=32,
               hidden_dim=4096, intermediate_dim=11008, vocab_size=32000),
}

_COMMON = dict(apply_rotary=True, layer_norm_type="rms", mlp_type="llama",
               use_attention_bias=False, use_attn_proj_bias=False,
               use_mlp_bias=False, activation_function="silu")


@dataclasses.dataclass
class ProfileConfig(PPOConfig):
    """PPO graph on synthetic data (inherits the 6 MFCs + per-MFC
    alloc/n_mbs knobs from PPOConfig)."""
    model_size: str = "tiny"
    n_prompts: int = 64
    prompt_len_min: int = 16
    prompt_len_max: int = 64
    bf16: bool = True
    lr: float = 1e-5

    def build(self) -> ExperimentSpec:
        if not self.benchmark_steps:
            self.benchmark_steps = 3
        spec = super().build()
        size = dict(MODEL_SIZES[self.model_size], **_COMMON)
        vocab = size["vocab_size"]
        for role, mspec in spec.models.items():
            is_critic = mspec.is_critic or role in ("critic", "reward")
            spec.models[role] = ModelSpec(
                hf_family="llama", path=None,
                random_init_config=dict(size),
                is_critic=is_critic,
                optimizer=(OptimizerConfig(
                    lr=self.lr, warmup_steps_proportion=0.0,
                    lr_scheduler_type="constant")
                    if mspec.optimizer is not None else None),
                parallel=mspec.parallel,
                bf16=self.bf16)
        spec.dataset = DatasetAbstraction(
            "random_prompt",
            args=dict(n_prompts=self.n_prompts,
                      prompt_len_min=self.prompt_len_min,
                      prompt_len_max=self.prompt_len_max,
                      vocab_size=vocab,
                      max_length=self.dataset.max_seqlen))
        # synthetic ids need no tokenizer beyond pad/eos conventions
        from realhf_tpu.base.testing import IntegerTokenizer
        spec.tokenizer = IntegerTokenizer(vocab_size=vocab - 2)
        return spec


register_experiment("profile", ProfileConfig)


def mfc_timing_summary(run: Callable[[], Any],
                       profile_dir: Optional[str] = None
                       ) -> Tuple[Any, Dict[str, float]]:
    """``run()`` (a runner's ``run``) under a synced capture of its
    own: what it returned, and per-MFC wall-clock totals in seconds
    (MFC name -> the sum of its ``mfc:<name>`` spans). With
    ``profile_dir`` the capture also records a ``jax.profiler`` trace
    there, ``programs.json`` beside it."""
    from realhf_tpu.obs import tracing
    tracing.start(profile_dir, sync=True)
    try:
        out = run()
    finally:
        capture = tracing.stop()
    secs: Dict[str, float] = {}
    for s in capture.named("mfc:"):
        name = s["name"][len("mfc:"):]
        secs[name] = secs.get(name, 0.0) + s["end"] - s["start"]
    return out, secs
