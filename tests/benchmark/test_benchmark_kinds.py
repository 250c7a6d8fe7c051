"""What each kind writes and the overrides it builds, and that the
command itself has no CPU path. The cells are the tests' own
(``manifest.json`` beside this file)."""

import os
import subprocess
import sys

from tiny_cells import MANIFEST

from benchmark import run


def test_data_is_what_the_kind_says(tmp_path):
    """Every prompt is cut to exactly the traffic's length, every
    document too, and the same seed writes the same files."""
    import json

    cell = run.load_cell(MANIFEST, "tiny.sft")
    t, hf = cell["traffic"], cell["hf"]
    a, b = tmp_path / "a", tmp_path / "b"
    a.mkdir(), b.mkdir()
    ov = cell["kind"].build(hf, cell["meta"], t, "/ckpt", str(a), 5)
    cell["kind"].build(hf, cell["meta"], t, "/ckpt", str(b), 5)
    assert (a / "documents.jsonl").read_text() \
        == (b / "documents.jsonl").read_text()
    rows = [json.loads(x) for x in open(a / "documents.jsonl")]
    assert len(rows) == t["docs_per_step"] * t["steps_of_data"]
    for r in rows:
        assert len(r["prompt"].split()) == t["prompt_len"]
        assert len((r["prompt"] + r["answer"]).split()) > t["doc_len"]
    assert f"n_mbs={t['docs_per_step'] // t['docs_per_row']}" in ov
    assert "model.type=qwen2" in ov and "model.path=/ckpt" in ov


def test_grpo_overrides_follow_the_layout(tmp_path):
    cell = run.load_cell(MANIFEST, "tiny.grpo-realloc")
    ov = cell["kind"].build(cell["hf"], cell["meta"], cell["traffic"],
                            "/ckpt", str(tmp_path), 5)
    assert "actor_gen_alloc=d4t1" in ov
    assert "actor.parallel.data_parallel_size=2" in ov
    assert "ref.parallel.tensor_parallel_size=2" in ov
    # 16 sequences, 4 to a row, 2 rows a stream batch: 2 microbatches
    # a forward, 1 a minibatch
    assert "ref_inf_n_mbs=2" in ov and "actor_train_n_mbs=1" in ov
    assert "grpo.min_new_tokens=8" in ov
    work = cell["kind"].work(cell["family"], cell["hf"], cell["meta"],
                             cell["traffic"])
    assert work["tokens_per_step"] == 16 * 24


def test_the_command_has_no_cpu_path():
    """``run.py`` exits non-zero and prints no result line where JAX's
    first device is not a TPU."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(run.ROOT, "benchmark", "run.py"),
         "--workload", "qwen2.5-0.5b.sft", "--seed", "1", "--seconds",
         "1", "--trace", "0"],
        env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "needs a TPU" in p.stderr
    assert '"correct"' not in p.stdout
