"""Epoch-qualified data ids (ISSUE 10 satellite / PR 9 known bug):
dataset sample ids repeat across epochs, so with
max_concurrent_batches > 1 a finishing batch's clear_data_cache used
to delete an id an in-flight next-epoch batch still needed (KeyError
at the data server -> bounded fetch_failed requeues -> fatal). Ids
are now qualified (epoch, raw_id) at the data owner's fetch reply, so
a 2-epoch concurrent run completes with zero epoch-id collisions."""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..",
                                "system"))
from tiny_model import TINY, write_jsonl  # noqa: E402

from realhf_tpu.api import data as data_api  # noqa: E402

WORKER_ENV = {
    "JAX_PLATFORMS": "cpu",
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
    "PYTHONPATH": "/root/repo",
}


def test_epoch_qualified_ids_round_trip():
    s = data_api.SequenceSample.from_default(
        ids=[3, 7], seqlens=[2, 2],
        data=dict(packed_prompts=np.arange(4, dtype=np.int32)))
    q0 = data_api.epoch_qualified(s, 0)
    q1 = data_api.epoch_qualified(s, 1)
    assert q0.ids == [(0, 3), (0, 7)]
    assert q1.ids == [(1, 3), (1, 7)]
    assert q0.ids[0] != q1.ids[0]          # no cross-epoch collision
    assert data_api.raw_ids(q1.ids) == [3, 7]
    assert data_api.raw_ids([3, 7]) == [3, 7]   # unqualified passthrough
    # the underlying tensors are shared views, not copies
    assert q0.data["packed_prompts"] is s.data["packed_prompts"]


@pytest.mark.slow  # full trial / multi-process, ~8-20 s (CHANGES.md, PR 22)
def test_two_epoch_concurrent_run_has_no_id_collisions(tmp_path):
    """SFT over 2 epochs with max_concurrent_batches=2: the epoch
    boundary keeps batches of BOTH epochs live at once (the exact
    geometry that was fatal before qualification). Completing with the
    exact step count means zero fetch_failed requeues ate a batch."""
    from realhf_tpu.apps.main import main_start
    from realhf_tpu.engine.optim import OptimizerConfig
    from realhf_tpu.experiments.common import apply_overrides
    from realhf_tpu.experiments.sft_exp import SFTConfig
    from realhf_tpu.parallel.mesh import ParallelismConfig

    rng = np.random.default_rng(0)
    path = tmp_path / "sft.jsonl"
    write_jsonl(path, [
        {"id": i,
         "prompt": " ".join(f"w{int(x)}"
                            for x in rng.integers(0, 50, 3)),
         "answer": " " + " ".join(["good"] * int(rng.integers(2, 6)))}
        for i in range(16)])

    cfg = SFTConfig(experiment_name="epochids", trial_name="t0",
                    total_train_epochs=2)
    apply_overrides(cfg, {"dataset.path": str(path),
                          "dataset.train_bs_n_seqs": "8",
                          "dataset.max_seqlen": "32"})
    spec = cfg.build()
    assert spec.max_concurrent_batches == 2
    for _role, mspec in spec.models.items():
        mspec.path = None
        mspec.random_init_config = dict(TINY)
        mspec.bf16 = False
        mspec.parallel = ParallelismConfig(data_parallel_size=2,
                                           tensor_parallel_size=4)
        if mspec.optimizer is not None:
            mspec.optimizer = OptimizerConfig(
                lr=1e-3, warmup_steps_proportion=0.0,
                lr_scheduler_type="constant")
    from realhf_tpu.base.testing import IntegerTokenizer
    spec.tokenizer = IntegerTokenizer()
    spec.n_model_workers = 1
    out = main_start(spec, env=WORKER_ENV, timeout=900)
    assert out["complete"]
    # 16 samples / bs 8 = 2 batches/epoch x 2 epochs, every one
    # trained exactly once (a pre-fix run dies or loses batches to
    # fetch_failed requeues at the epoch boundary)
    assert out["global_step"] == 4
    assert np.isfinite(out["stats"]["trainDefault"]["loss"])
