"""Traffic of kind ``grpo``: whole GRPO steps through quickstart's
``grpo`` experiment (actor_gen -> rew_inf, ref_inf -> actor_train).

A kind turns a configuration and a traffic file into the data it writes
and the overrides a user would type, and tells the harness what to
expect of the run.
"""

import os

from benchmark import arith, generate

EXPERIMENT = "grpo"
#: MFC name -> the harness span it is timed under
MFCS = dict(actor_gen="gen", rew_inf="inf", ref_inf="inf",
            actor_train="train")
#: each step's first minibatch is on-policy: its importance weight
#: says whether generation and training agree
ON_POLICY = True


def build(hf, meta, traffic, ckpt, workdir, seed):
    """Write the prompts and return quickstart's overrides."""
    t = traffic
    prompts = os.path.join(workdir, "prompts.jsonl")
    generate.write_prompts(prompts,
                           t["prompts_per_step"] * t["steps_of_data"],
                           t["prompt_len"], hf, seed)
    layout = meta.get("layout", {})
    dp, tp = generate.parallel_degrees(layout.get("roles", "d1t1"))
    n_seqs = t["prompts_per_step"] * t["group_size"]
    # every stream batch has dp rows, so a microbatch is dp packed rows
    rows_per_batch = max(n_seqs // t["seqs_per_row"] // dp, 1)
    rows_per_minibatch = max(rows_per_batch // t["minibatches"], 1)
    overrides = [
        f"tokenizer_path={ckpt}",
        f"dataset.path={prompts}",
        f"dataset.train_bs_n_seqs={t['prompts_per_step']}",
        f"dataset.max_seqlen={t['prompt_len']}",
        f"grpo.group_size={t['group_size']}",
        f"grpo.max_new_tokens={t['new_tokens']}",
        # a trained policy learns to end early; the cell's shapes stay
        f"grpo.min_new_tokens={t['new_tokens']}",
        f"grpo.ppo_n_minibatches={t['minibatches']}",
        f"actor_train_n_mbs={rows_per_minibatch}",
        f"ref_inf_n_mbs={rows_per_batch}",
        f"rew_inf_n_mbs={rows_per_batch}",
        f"actor.optimizer.lr={t['lr']}",
        "actor.optimizer.warmup_steps_proportion=0.0",
        "actor.optimizer.lr_scheduler_type=constant",
    ]
    for role in ("actor", "ref", "rew"):
        overrides += [f"{role}.type={meta['family']}",
                      f"{role}.path={ckpt}",
                      f"{role}.parallel.data_parallel_size={dp}",
                      f"{role}.parallel.tensor_parallel_size={tp}"]
    if layout.get("actor_gen"):
        overrides.append(f"actor_gen_alloc={layout['actor_gen']}")
    return overrides


def generation_engine(runner):
    replica = runner.replicas.get("actor_gen")
    return (replica or runner.models["actor"]).engine


def programs(runner):
    """(label, engine, program) of every program the cell runs; each
    has to hold a ``tpu_custom_call``."""
    return [("train", runner.models["actor"].engine, "train"),
            ("ref_inf", runner.models["ref"].engine, "logprobs"),
            ("rew_inf", runner.models["reward"].engine, "values"),
            ("generate", generation_engine(runner).decode_engine(),
             "generate")]


def reference_engines(runner):
    """(label, engine, refresh) whose log-probabilities are held to
    the reference: the training layout, and the generation replica
    after a reshard where there is one."""
    out = [("train_layout", runner.models["actor"].engine, None)]
    replica = runner.replicas.get("actor_gen")
    if replica is not None:
        def refresh():
            runner.host.replica_mgr.ensure_fresh(
                "actor", runner.models["actor"], replica)
        out.append(("gen_replica", replica.engine, refresh))
    return out


def work(family, hf, meta, traffic):
    """What one step needs, for the per-layer metrics."""
    t = traffic
    n_seqs = t["prompts_per_step"] * t["group_size"]
    seqlens = [t["prompt_len"] + t["new_tokens"]] * n_seqs
    layout = meta.get("layout", {})
    gen_dp, _ = generate.parallel_degrees(
        layout.get("actor_gen") or layout.get("roles", "d1t1"))
    return dict(
        tokens_per_step=sum(seqlens),
        train_flops=arith.train_flops(family, hf, seqlens),
        decode_bytes=family.decode_bytes(
            hf, n_seqs, t["prompt_len"], t["new_tokens"],
            replicas=gen_dp))
