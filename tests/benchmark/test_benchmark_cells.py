"""The tests' own one-chip cells, whole: each kind builds overrides and
data that ``quickstart`` accepts at a tiny size, a few steps, through
the wrappers a chip run uses."""

import pytest
from tiny_cells import check_line, go


@pytest.mark.parametrize("cell", ["tiny.sft", "tiny-gpt2.sft"])
def test_sft_cell_end_to_end(cell, tmp_path):
    """``tiny-gpt2.sft`` is of a family (weights' shapes, reference,
    arithmetic) that lives wholly in the tests' directory: a later
    PR's architecture needs new files only."""
    check_line(go(cell, False, tmp_path), trace=False)


def test_sft_cell_traced(tmp_path):
    out = go("tiny.sft", True, tmp_path)
    check_line(out, trace=True)
    m = out["metrics"]
    # the readers this cell lists, less those with nothing to read: no
    # generation, inference or reshard in SFT, no device plane on a CPU
    assert set(m) == {"tests.steps", "runner.gap_s", "mfc.train_s",
                      "train.mfu", "engine.window_compiles",
                      "engine.compile_s", "entry.load_s"}
    assert m["tests.steps"]["value"] == out["attempted"]
    assert m["engine.window_compiles"]["value"] == 0
    assert "busy_s" not in out["device"]  # nothing ran on a device


def test_grpo_cell_traced(tmp_path):
    out = go("tiny.grpo", True, tmp_path)
    check_line(out, trace=True)
    m = out["metrics"]
    assert {"mfc.gen_s", "mfc.inf_s", "mfc.train_s", "gen.hbm_share",
            "runner.gap_s"} <= set(m)
    assert "reshard.s" not in m
    parts = sum(m[k]["value"] for k in ("mfc.gen_s", "mfc.inf_s",
                                        "mfc.train_s", "runner.gap_s"))
    assert parts > 0 and m["runner.gap_s"]["value"] >= 0
