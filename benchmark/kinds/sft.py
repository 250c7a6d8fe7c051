"""Traffic of kind ``sft``: whole supervised steps through quickstart's
``sft`` experiment (one train MFC, ``trainDefault``)."""

import os

from benchmark import arith, generate

EXPERIMENT = "sft"
MFCS = dict(trainDefault="train")
ON_POLICY = False


def build(hf, meta, traffic, ckpt, workdir, seed):
    """Write the documents and return quickstart's overrides."""
    t = traffic
    docs = os.path.join(workdir, "documents.jsonl")
    generate.write_documents(docs,
                             t["docs_per_step"] * t["steps_of_data"],
                             t["doc_len"], t["prompt_len"], hf, seed)
    dp, tp = generate.parallel_degrees(
        meta.get("layout", {}).get("roles", "d1t1"))
    rows = max(t["docs_per_step"] // t["docs_per_row"] // dp, 1)
    return [
        f"dataset.path={docs}",
        f"dataset.train_bs_n_seqs={t['docs_per_step']}",
        f"dataset.max_seqlen={t['doc_len']}",
        f"n_mbs={rows}",
        f"model.type={meta['family']}",
        f"model.path={ckpt}",
        f"model.parallel.data_parallel_size={dp}",
        f"model.parallel.tensor_parallel_size={tp}",
        f"model.optimizer.lr={t['lr']}",
        "model.optimizer.warmup_steps_proportion=0.0",
        "model.optimizer.lr_scheduler_type=constant",
    ]


def programs(runner):
    return [("train", runner.models["default"].engine, "train")]


def reference_engines(runner):
    return [("train_layout", runner.models["default"].engine, None)]


def work(family, hf, meta, traffic):
    seqlens = [traffic["doc_len"]] * traffic["docs_per_step"]
    return dict(tokens_per_step=sum(seqlens),
                train_flops=arith.train_flops(family, hf, seqlens))
