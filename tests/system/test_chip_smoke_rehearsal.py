"""chip_smoke.py rehearsed on the CPU: a toy qwen2 model through the
same quickstart -> InlineRunner -> ModelHost -> Engine path, in this
process, on one device and on four virtual ones (actor d2t2, generation
replica d4t1, the reshard between them). It finds wrong paths,
arguments and shardings before a chip call is spent on them, and pins
the script's first rule: without a TPU it never reports ok."""

import argparse
import json
import os

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", ".."))


@pytest.fixture()
def chip_smoke(monkeypatch):
    monkeypatch.syspath_prepend(REPO)
    import chip_smoke
    return chip_smoke


@pytest.mark.parametrize("chips", [1, 4])
def test_rehearsal_runs_the_path_and_never_reports_ok(
        chip_smoke, chips, tmp_path, capsys):
    args = argparse.Namespace(seed=1, chips=chips, rehearse=True)
    rc = chip_smoke.run(args, str(tmp_path))
    assert rc != 0
    out = capsys.readouterr().out
    lines = [json.loads(l) for l in out.splitlines()
             if l.startswith("{")]
    assert not any(l.get("ok") for l in lines)
    assert '"ok": true' not in out
    assert lines[-1]["phase"] == "rehearsal_done"

    summary = next(l for l in lines if l.get("phase") == "summary")
    # the second step reuses the first step's programs
    assert summary["compiles_per_step"][1:] == [0]
    assert summary["params_changed"]["max_abs_delta"] > 0
    if chips == 4:
        four = next(l for l in lines if l.get("phase") == "four_chips")
        assert four["actor_shard_devices"] == [0, 1, 2, 3]
        assert four["gap_after_reshard"] <= four["tolerance_mean_abs_logprob"]
        assert four["gap_stale_replica"] > four["gap_after_reshard"]
        assert sum(four["train_collectives"].values()) > 0


def test_without_a_tpu_the_real_run_exits_nonzero(chip_smoke, tmp_path,
                                                  capsys):
    """No --rehearse on this CPU box: the script refuses before it
    builds anything, and prints no result line."""
    args = argparse.Namespace(seed=1, chips=1, rehearse=False)
    with pytest.raises(SystemExit) as exc:
        chip_smoke.run(args, str(tmp_path))
    assert exc.value.code not in (0, None)
    assert "needs a TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""
    assert not os.listdir(tmp_path)
