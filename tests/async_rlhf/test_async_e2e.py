"""ISSUE 10 acceptance e2e (slow): generation streams samples into
training at 2x the train batch through a real RolloutServer +
RolloutController + per-sample buffer; >= 2 train steps overlap with
in-flight generation (buffer/controller watermarks); the async reward
curve matches the synchronous run within tolerance; clipped-IS stats
(importance_weight) are reported per step.

Run directly: pytest -m slow tests/async_rlhf/test_async_e2e.py
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "..",
                                "scripts"))

STEPS = 4
TRAIN_BS = 4
GEN_BS = 2 * TRAIN_BS   # acceptance geometry: gen streams at 2x


def _run_mode(mode):
    """A fresh, identically-seeded stack per mode: same model init,
    same dataset order, greedy decoding + tiny lr, so the two reward
    curves are comparable point by point."""
    import bench_async

    runner = bench_async.build_runner(
        train_bs=TRAIN_BS, gen_bs=GEN_BS, prompt_len=8, new_tokens=4,
        steps=STEPS + 1, max_staleness=4, seed=0,
        name=f"asynce2e-{mode}")
    stack = bench_async._ServingStack(
        runner, n_slots=4, chunk=4, new_tokens=4, prompt_len=8,
        max_staleness=None)
    try:
        return bench_async.run_ppo_loop(
            runner, stack, mode=mode, steps=STEPS,
            train_bs=TRAIN_BS, gen_bs=GEN_BS, max_staleness=4)
    finally:
        stack.close()


@pytest.mark.slow
def test_async_overlap_matches_sync_reward_curve():
    sync = _run_mode("sync")
    async_ = _run_mode("async")

    # lockstep never overlaps; the pipeline overlaps >= 2 train steps
    # with generation still in flight (controller watermark sampled
    # around each train execution)
    assert sync["overlapped_steps"] == 0
    assert async_["overlapped_steps"] >= 2, async_

    # off-policy consumption really happened: some harvested samples
    # were generated under an older weight version...
    assert any(int(k) > 0 for k in async_["staleness_hist"]), async_
    # ...and generation streamed at the 2x geometry (more rollouts
    # completed than the train steps consumed)
    assert async_["rollouts_completed"] >= STEPS * TRAIN_BS

    # clipped-IS stats reported per step
    for row in async_["curve"]:
        assert np.isfinite(row["importance_weight"])
        assert row["stale_is_weight"] is not None
        assert np.isfinite(row["stale_is_weight"])
    assert any(row["staleness_mean"] > 0 for row in async_["curve"])

    # reward curve parity: greedy decode + 1e-4 lr keep the async
    # (bounded-staleness, IS-corrected) trajectory statistically on
    # top of the synchronous one
    r_sync = np.array([row["task_reward"] for row in sync["curve"]])
    r_async = np.array([row["task_reward"] for row in async_["curve"]])
    assert r_sync.shape == r_async.shape == (STEPS,)
    assert np.all(np.isfinite(r_sync)) and np.all(np.isfinite(r_async))
    assert abs(r_sync.mean() - r_async.mean()) < 0.15, (
        r_sync, r_async)

    # overlap must not cost throughput (generous CPU-walls bound)
    assert async_["steps_per_sec"] >= 0.6 * sync["steps_per_sec"], (
        sync["steps_per_sec"], async_["steps_per_sec"])
