"""bench.py contract: it needs a TPU (exits non-zero without one), the
PPO headline is recorded FIRST, the payload file is flushed after every
phase, --headline-only prints a valid headline JSON line without
touching the later phases, and a failed phase exits non-zero with the
record flushed so far left on disk."""

import json
import os
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                    "..", ".."))


@pytest.fixture()
def bench_mod(monkeypatch, tmp_path):
    """bench.py with the device check steered from the test: the CPU
    box passes for a v5e, and the PPO phase is a stub."""
    monkeypatch.syspath_prepend(REPO)
    monkeypatch.setenv("REALHF_BENCH_PAYLOAD",
                       str(tmp_path / "BENCH_partial.json"))
    import bench
    monkeypatch.setattr(bench, "require_tpu",
                        lambda: (197e12, 819e9))
    monkeypatch.setattr(
        bench, "bench_ppo",
        lambda: (_headline(), {"ppo_step_time_s": 1.0}, object()))
    return bench


def _headline():
    return {"metric": "ppo_tokens_per_sec_per_chip", "value": 123.4,
            "unit": "tokens/s", "vs_baseline": 0.99}


def _read_payload():
    with open(os.environ["REALHF_BENCH_PAYLOAD"]) as f:
        return json.load(f)


def test_headline_only_prints_and_skips_nonheadline_phases(
        bench_mod, monkeypatch, capsys):
    ran = []

    def forbidden(name):
        def _f(*a, **k):
            ran.append(name)
            raise AssertionError(f"{name} must not run in "
                                 "--headline-only mode")
        return _f

    monkeypatch.setattr(bench_mod, "bench_sft", forbidden("sft"))
    monkeypatch.setattr(bench_mod, "_reshard_metrics",
                        forbidden("reshard"))
    monkeypatch.setattr(sys, "argv", ["bench.py", "--headline-only"])
    bench_mod.main()
    assert ran == []

    out_lines = [l for l in capsys.readouterr().out.splitlines()
                 if l.startswith("{")]
    assert len(out_lines) == 1
    rec = json.loads(out_lines[0])
    assert rec["metric"] == "ppo_tokens_per_sec_per_chip"
    assert rec["extra"]["headline_only"] is True
    assert rec["extra"]["time_to_first_headline_s"] >= 0
    assert set(rec["extra"]["device"]) == {"platform", "kind", "count"}

    payload = _read_payload()
    assert payload["phases_done"] == ["ppo_headline",
                                      "kernel_disposition"]
    assert "kernel_disposition" in payload["extra"]
    assert "sft_mfu" not in payload["extra"]


def test_partial_payload_flushed_before_each_nonheadline_phase(
        bench_mod, monkeypatch, capsys):
    """The full run flushes after EVERY phase; each later phase can
    observe the previous flush on disk."""
    seen_phases = {}

    def spy(name, ret=None, mutate=None):
        def _f(*a, **k):
            seen_phases[name] = _read_payload()["phases_done"]
            if mutate is not None:
                mutate(*a)
            return ret
        return _f

    monkeypatch.setattr(
        bench_mod, "_reshard_metrics",
        spy("reshard",
            mutate=lambda runner, extra: extra.update(
                reshard_latency_s=0.1)))
    monkeypatch.setattr(bench_mod, "bench_sft",
                        spy("sft", ret={"sft_mfu": 0.5}))
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    bench_mod.main()

    # headline (and disposition) were on disk before the first
    # non-headline phase ran
    assert seen_phases["reshard"] == ["ppo_headline",
                                      "kernel_disposition"]
    assert seen_phases["sft"][-1] == "reshard"

    final = _read_payload()
    assert final["phases_done"] == [
        "ppo_headline", "kernel_disposition", "reshard", "sft"]
    assert final["extra"]["sft_mfu"] == 0.5
    # final stdout line is the full headline record
    out_lines = [l for l in capsys.readouterr().out.splitlines()
                 if l.startswith("{")]
    rec = json.loads(out_lines[-1])
    assert rec["extra"]["reshard_latency_s"] == 0.1


def test_failed_phase_or_missing_tpu_exits_nonzero(
        bench_mod, monkeypatch, capsys):
    """No phase catches its failure: the run dies (non-zero exit) with
    the payload flushed so far on disk and no final record printed.
    With the real device check the CPU box is refused outright."""
    def boom(*a, **k):
        raise RuntimeError("phase died")

    monkeypatch.setattr(bench_mod, "_reshard_metrics", boom)
    monkeypatch.setattr(bench_mod, "bench_sft",
                        lambda: {"sft_mfu": 0.5})
    monkeypatch.setattr(sys, "argv", ["bench.py"])
    with pytest.raises(RuntimeError, match="phase died"):
        bench_mod.main()
    payload = _read_payload()
    assert payload["phases_done"] == ["ppo_headline",
                                      "kernel_disposition"]
    assert "sft_mfu" not in payload["extra"]
    assert not [l for l in capsys.readouterr().out.splitlines()
                if l.startswith("{")]

    monkeypatch.undo()  # the real require_tpu: this box has no TPU
    import bench
    with pytest.raises(SystemExit) as exc:
        bench.require_tpu()
    assert exc.value.code not in (0, None)
    assert "needs a TPU" in str(exc.value.code)


def test_bench_pipeline_script_payload_shape(monkeypatch):
    """The schedule micro-bench payload: exact analytics plus measured
    timings (run in-process at the smallest shape; the S=4/M=4
    acceptance geometry runs from bench.py and in the e2e above the
    tier)."""
    monkeypatch.syspath_prepend(os.path.join(REPO, "scripts"))
    import bench_pipeline

    out = bench_pipeline.run(stages=2, microbatches=2, layers=2,
                             hidden=32, seqlen=32, reps=1)
    assert out["ticks_per_pass"] == 3 and out["train_ticks"] == 6
    assert out["analytic_bubble_fraction"] == pytest.approx(1 / 3,
                                                            abs=1e-4)
    assert out["schedules"]["gpipe"]["computed_stage_steps"] == 12
    assert out["schedules"]["1f1b"]["computed_stage_steps"] == 8
    for sched in ("gpipe", "1f1b"):
        assert out["schedules"][sched]["step_s"] > 0
    # 1 - t_1f1b / t_gpipe of two single timed steps: below 1 by
    # construction, and as far below 0 as the host's noise takes it
    assert out["measured_bubble_fraction"] < 1.0
    json.dumps(out)  # payload-serializable
