#!/usr/bin/env python3
"""A builder's run, no cell behind it: cell ``qwen2.5-0.5b.sft``'s
program on documents of ragged length, for what the flash kernels'
block ranges save when segment boundaries do not fall on the blocks
(ROADMAP R11 is the cell that will repeat it).

    chiprun --chips 1 -- python3 scripts/chip_ragged_sft.py \
        --seed <n> [--seconds 30]

Documents of lognormal length (median 350, sigma 1.0, cut to 32 ..
4096), 80 a step, shuffled and split into 20 token-balanced
microbatches by the program itself (``quickstart sft``), each one
packed row padded to 4096 so that the step has one shape. It is
``scripts/trace_ops_by_name.py`` on that traffic: same files under
``chiprun_out/`` (``ops_``, ``spans_``, ``hlo_`` of cell
``ragged-sft``) and, as the last line, the result line plus
``flash_blocks``: per ``engine:train`` call of the traced steps the
blocks visited, the blocks that hold an unmasked pair (brute force
from the run extents) and the blocks under the rows' diagonals.
"""

import argparse
import json
import os
import shutil
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "scripts")]

CELL, NAME = "qwen2.5-0.5b.sft", "ragged-sft"
ROW, DOCS, ROWS = 4096, 80, 20
TRAFFIC = dict(kind="sft", docs_per_step=DOCS, doc_len=ROW, prompt_len=8,
               docs_per_row=DOCS // ROWS, lr=1e-4, steps_of_data=8)


def doc_lengths(rng, n):
    return np.clip(rng.lognormal(np.log(350), 1.0, size=n).astype(int),
                   32, ROW)


def write_documents(path, n, doc_len, prompt_len, hf, seed):
    from benchmark import generate
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for i, length in enumerate(doc_lengths(rng, n)):
            ids = generate._token_ids(rng, int(length), hf)
            f.write(json.dumps(dict(
                id=i, prompt=generate._words(ids[:prompt_len]) + " ",
                answer=generate._words(ids[prompt_len:]))) + "\n")


def needed_blocks(seg, bq, bk):
    """Blocks of bq x bk that hold an unmasked causal pair, from each
    row's runs (no L x L mask)."""
    total = 0
    for row in seg.reshape(-1, seg.shape[-1]):
        need = np.zeros((len(row) // bq, len(row) // bk), bool)
        edges = np.flatnonzero(np.diff(row, prepend=-1, append=-1))
        for a, b in zip(edges[:-1], edges[1:]):
            if row[a] == 0:
                continue
            for qi in range(a // bq, (b - 1) // bq + 1):
                last_q = min((qi + 1) * bq, b) - 1
                need[qi, a // bk:last_q // bk + 1] = True
        total += int(need.sum())
    return total


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=30.0)
    args = p.parse_args()

    # as benchmark/run.py:main: what the run writes stays in the
    # checkout, fixed before realhf_tpu is imported
    work = os.path.join(ROOT, "benchmark", ".cache", "work", NAME)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    os.environ["REALHF_TPU_ROOT"] = os.path.join(work, "root")

    import trace_ops_by_name as keep
    from benchmark import arith, generate, run as bench_run
    from realhf_tpu.engine import packing
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.ops import flash_attention as fa

    generate.write_documents = write_documents
    plan = packing.plan_packing
    packing.plan_packing = lambda seqlens, n_streams, bucket=128, \
        min_len=None: plan(seqlens, n_streams, bucket, ROW)

    calls = []
    train_batch = Engine.train_batch

    def counted(self, microbatches, *a, **kw):
        seg = np.stack([np.asarray(mb["seg_ids"]) for mb in microbatches])
        assert seg.shape[-1] == ROW, seg.shape
        bq, bk = fa._blocks(ROW, fa.DEFAULT_BQ, fa.DEFAULT_BK)
        # (a tree from before the ranges visits every block)
        visited, causal, unmasked = getattr(
            fa, "block_counts", lambda seg: (None, None, None))(seg)
        calls.append(dict(
            visited=visited, needed=needed_blocks(seg, bq, bk),
            causal=causal, unmasked=unmasked,
            tokens=int(np.count_nonzero(seg)),
            documents=int(sum(len(np.unique(r[r != 0]))
                              for r in seg.reshape(-1, ROW)))))
        return train_batch(self, microbatches, *a, **kw)

    Engine.train_batch = counted

    cell = bench_run.load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    cell.update(name=NAME, traffic=TRAFFIC)
    keep.install(NAME)
    try:
        result = bench_run.run_cell(
            cell, args.seed, args.seconds, 2, work,
            arith.peaks(bench_run.device_line()["kind"]))
    finally:
        shutil.rmtree(work, ignore_errors=True)
    keep.keep_spans(NAME)
    result["flash_blocks"] = calls[-2 * bench_run.TRACE_STEPS:]
    print(json.dumps(result))
    return 0 if result["correct"] else 1


if __name__ == "__main__":
    sys.exit(main())
