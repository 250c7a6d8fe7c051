"""Sweep-file profiling workflow (reference ``examples/profiling/``:
jsonl sweeps over allocations/interface knobs driven by profile.sh)."""

import json
import sys

import pytest


@pytest.fixture
def sweep_file(tmp_path):
    path = tmp_path / "sweep.jsonl"
    path.write_text(
        json.dumps({"actor_gen_alloc": "d8t1"}) + "\n"
        + json.dumps({"actor_train_n_mbs": 2}) + "\n")
    return str(path)


@pytest.mark.slow  # full trial / multi-process, ~8-20 s (CHANGES.md, PR 22)
def test_profile_sweep_ranks_setups(sweep_file, tmp_path, capsys):
    sys.path.insert(0, "/root/repo/scripts")
    import profile_sweep

    out = str(tmp_path / "results.jsonl")
    results = profile_sweep.main([
        "--sweep", sweep_file, "--out", out,
        "model_size=tiny", "benchmark_steps=1", "n_prompts=8",
        "dataset.train_bs_n_seqs=4", "dataset.max_seqlen=16",
        "ppo.max_new_tokens=4", "ppo.min_new_tokens=4",
    ])
    assert len(results) == 2
    with open(out) as f:
        lines = [json.loads(l) for l in f]
    assert len(lines) == 2
    for rec in lines:
        assert rec["step_secs"] > 0
        # the 6 PPO MFCs all have per-MFC timings
        assert set(rec["mfc_secs"]) == {
            "actor_gen", "rew_inf", "ref_inf", "critic_inf",
            "actor_train", "critic_train"}
    # ranked ascending by step time in the stdout table
    assert "Best:" in capsys.readouterr().out
