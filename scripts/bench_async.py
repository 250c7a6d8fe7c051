#!/usr/bin/env python
"""Sync-vs-async PPO throughput harness (ISSUE 10 acceptance surface).

Drives the SAME components both ways -- a real ``RolloutServer``
(continuous batching + ``WeightSync`` hot-swap) generating on its own
thread, a :class:`~realhf_tpu.system.rollout.RolloutController`
feeding it, the per-sample :class:`~realhf_tpu.system.buffer.
SequenceBuffer` assembling train batches, and the real PPO interfaces
(with the staleness-aware clipped-IS correction) training -- in two
modes:

- **sync**: the lockstep baseline. Submit one train batch of prompts,
  wait for ALL of them, run the inference + train MFCs, push weights,
  repeat. Generation and training alternate; each phase idles the
  other.
- **async**: the pipeline. The controller keeps ``gen_ratio x`` the
  train batch in flight continuously; training drains the buffer the
  moment ``n_seqs`` samples are ready (off-policy, version-stamped,
  clipped-IS corrected); fresh weights hot-swap into the server
  between decode chunks.

Reports steps/s for both modes, the rollout-idle fraction, the
staleness histogram, how many train steps overlapped with in-flight
generation, and the per-step reward/importance-weight curves (the
slow e2e asserts reward parity on these) as one JSON line.

Usage::

    python scripts/bench_async.py [--steps 3] [--train-bs 4]
        [--gen-ratio 2] [--prompt-len 8] [--new-tokens 4]
        [--max-staleness 4] [--seed 0]
"""
import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY = dict(
    n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
    intermediate_dim=64, vocab_size=97, apply_rotary=True,
    layer_norm_type="rms", mlp_type="llama", use_attention_bias=False,
    use_attn_proj_bias=False, use_mlp_bias=False,
    activation_function="silu")


def build_runner(*, train_bs, gen_bs, prompt_len, new_tokens, steps,
                 max_staleness, seed, name="asyncbench"):
    """An InlineRunner over the real PPO experiment graph with tiny
    random-init roles -- the model/interfaces substrate both modes
    share."""
    from realhf_tpu.api.config import DatasetAbstraction
    from realhf_tpu.base import testing
    from realhf_tpu.engine.optim import OptimizerConfig
    from realhf_tpu.experiments.common import apply_overrides
    from realhf_tpu.experiments.ppo_exp import PPOConfig
    from realhf_tpu.parallel.mesh import ParallelismConfig
    from realhf_tpu.system.inline import InlineRunner

    cfg = PPOConfig(experiment_name=name, trial_name="t0",
                    total_train_epochs=1, seed=seed + 1)
    apply_overrides(cfg, {
        "dataset.train_bs_n_seqs": str(train_bs),
        "dataset.max_seqlen": str(prompt_len),
        "actor_gen_n_seqs": str(gen_bs),
        "ppo.max_new_tokens": str(new_tokens),
        "ppo.min_new_tokens": str(new_tokens),
        "ppo.greedy": "true",
        "ppo.ppo_n_minibatches": "1",
        "ppo.force_no_logits_mask": "true",
        "ppo.max_staleness": str(max_staleness),
    })
    spec = cfg.build()
    # enough prompts for warmup + both timed modes
    n_prompts = gen_bs + train_bs * (steps + 1)
    spec.dataset = DatasetAbstraction(
        "random_prompt",
        args=dict(n_prompts=n_prompts, prompt_len_min=prompt_len,
                  prompt_len_max=prompt_len,
                  vocab_size=TINY["vocab_size"]))
    for _role, mspec in spec.models.items():
        mspec.path = None
        mspec.random_init_config = dict(TINY)
        mspec.bf16 = False
        mspec.parallel = ParallelismConfig()
        if mspec.optimizer is not None:
            mspec.optimizer = OptimizerConfig(
                lr=1e-4, warmup_steps_proportion=0.0,
                lr_scheduler_type="constant")
    spec.tokenizer = testing.IntegerTokenizer(
        vocab_size=TINY["vocab_size"])
    return InlineRunner(spec)


class _ServingStack:
    """One RolloutServer over the actor's weights, serve loop on its
    own thread, weights hot-swapped through WeightSync."""

    def __init__(self, runner, *, n_slots, chunk, new_tokens,
                 prompt_len, max_staleness):
        from realhf_tpu.engine.inflight import InflightBatchingGenerator
        from realhf_tpu.ops.sampling import GenerationHyperparameters
        from realhf_tpu.serving.request_queue import RequestQueue
        from realhf_tpu.serving.server import RolloutServer
        from realhf_tpu.serving.weight_sync import WeightSync

        actor = runner.models["actor"]
        g = GenerationHyperparameters(
            max_new_tokens=new_tokens, min_new_tokens=new_tokens,
            greedy=True, force_no_logits_mask=True)
        backend = InflightBatchingGenerator(
            actor.config, actor.engine.params, g, n_slots=n_slots,
            max_prompt_len=prompt_len + 8, eos_token_id=None,
            pad_token_id=0, chunk_size=chunk)
        self.weight_sync = WeightSync(
            version=actor.version.global_step)
        self.server = RolloutServer(
            backend, server_name="async-bench/0",
            queue=RequestQueue(max_depth=512, n_slots=n_slots),
            weight_sync=self.weight_sync,
            max_staleness=max_staleness, stream_tokens=False)
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._loop, daemon=True)
        self._thread.start()

    def _loop(self):
        while not self._stop.is_set():
            self.server.serve_step(poll_timeout=0.002)

    def close(self):
        self._stop.set()
        self._thread.join(timeout=10.0)
        self.server.close()


def _prompt_source(runner, skip: int = 0):
    """Yield (id, prompt_tokens) pairs off the PPO dataloader."""
    from realhf_tpu.base.datapack import flat2d
    import numpy as np

    i = 0
    for batch in runner.dataloader:
        lens = flat2d(batch.seqlens["packed_prompts"])
        flat = batch.data["packed_prompts"]
        off = 0
        for sid, l in zip(batch.ids, lens):
            p = np.asarray(flat[off:off + l], np.int32)
            off += l
            if i >= skip:
                yield (sid, p)
            i += 1


def run_ppo_loop(runner, stack, *, mode, steps, train_bs, gen_bs,
                 max_staleness, skip_prompts=0, ttl=120.0):
    """One PPO run off the serving stack. ``mode`` = "sync" (lockstep:
    one train batch generated, fully drained, then trained) or "async"
    (controller keeps ``gen_bs`` in flight while training drains the
    per-sample buffer at ``train_bs``)."""
    from realhf_tpu.api.data import SequenceSample
    from realhf_tpu.serving.server import RolloutClient
    from realhf_tpu.system.buffer import SequenceBuffer
    from realhf_tpu.system.rollout import (
        RolloutController,
        trajectories_to_sample,
    )

    actor = runner.models["actor"]
    nodes = [n for n in runner.dfg.nodes if n.name != "actor_gen"]
    names = [n.name for n in nodes]
    produced = {k: n.name for n in nodes for k in n.output_keys}
    input_keys_of = {n.name: tuple(n.input_keys) for n in nodes}
    producers_of = {
        n.name: tuple(sorted({produced[k] for k in n.input_keys
                              if k in produced}))
        for n in nodes}
    buffer = SequenceBuffer(
        names, capacity=1_000_000,
        n_seqs_of={m: train_bs for m in names},
        input_keys_of=input_keys_of, producers_of=producers_of)

    client = RolloutClient(stack.server.address)
    ctl = RolloutController(
        [client], _prompt_source(runner, skip=skip_prompts),
        max_inflight=(train_bs if mode == "sync" else gen_bs),
        max_staleness=max_staleness,
        current_version=lambda: actor.version.global_step,
        ttl=ttl)

    curve = []           # per-train-step stats (reward, IS, staleness)
    overlapped = 0
    train_steps = 0
    step_times = []
    pending_wave = []
    deadline = time.monotonic() + 600.0
    t0 = time.monotonic()
    try:
        while train_steps < steps:
            if time.monotonic() > deadline:
                raise TimeoutError(
                    f"{mode} loop stalled: {train_steps}/{steps} "
                    f"steps, ctl={ctl.stats()}")
            if mode == "async":
                ctl.pump()
            elif (ctl.inflight == 0 and not pending_wave
                    and buffer.n_samples == 0):
                # lockstep: submit the next wave only once the
                # previous one fully trained out
                ctl.pump()
            pending_wave.extend(ctl.poll(timeout=0.002))
            if mode == "sync" and ctl.inflight:
                continue  # lockstep: drain the whole wave first
            if pending_wave:
                buffer.put_batch(trajectories_to_sample(pending_wave),
                                 "local", 0, False)
                pending_wave = []
            flush = names if ctl.exhausted else ()
            for asm in buffer.ready_assemblies(flush=flush):
                buffer.mark_assembly_dispatched(asm.aid)
                inp = buffer.gather_assembly(
                    asm.aid, input_keys_of[asm.mfc])
                busy_before = ctl.inflight > 0
                out = runner.host.execute(asm.mfc, inp)
                if isinstance(out, SequenceSample):
                    buffer.complete_assembly(asm.aid, out, "local")
                    continue
                buffer.complete_assembly(asm.aid, None, "local")
                if asm.mfc != "actor_train":
                    continue
                # actor trained: hot-swap the fresh weights into the
                # server (monotonic version = the actor's step count).
                # WeightSync.push snapshots the tree itself (the
                # owns_params contract), so the trainer is free to
                # DONATE its param buffers on the next optimizer step.
                train_steps += 1
                step_times.append(time.monotonic())
                if busy_before or ctl.inflight > 0:
                    overlapped += 1
                stack.weight_sync.push(actor.engine.params,
                                       actor.version.global_step)
                curve.append(dict(
                    step=train_steps,
                    task_reward=out.get("task_reward"),
                    importance_weight=out.get("importance_weight"),
                    stale_is_weight=out.get("stale_is_weight"),
                    staleness_mean=out.get("staleness_mean"),
                    n_dropped_stale=out.get("n_dropped_stale")))
            buffer.pop_retired()
        wall = time.monotonic() - t0
    finally:
        client.close()
    st = ctl.stats()
    # steady-state cadence: elapsed between the FIRST and LAST train
    # completion, excluding the one-off pipeline fill -- the quantity
    # overlap actually improves (async hides rollout latency behind
    # training; the fill is paid once per run, not per step)
    if len(step_times) > 1:
        steps_per_sec = (len(step_times) - 1) \
            / max(step_times[-1] - step_times[0], 1e-9)
    else:
        steps_per_sec = train_steps / max(wall, 1e-9)
    return dict(
        mode=mode, train_steps=train_steps,
        wall_s=round(wall, 3),
        steps_per_sec=round(steps_per_sec, 4),
        overlapped_steps=overlapped,
        rollout_idle_frac=round(st["idle_secs"] / max(wall, 1e-9), 4),
        staleness_hist=st["staleness_hist"],
        staleness_mean=round(st["staleness_mean"], 4),
        dropped_stale=st["dropped_stale"],
        rollouts_completed=st["completed"],
        curve=curve)


def run(args) -> dict:
    import jax

    runner = build_runner(
        train_bs=args.train_bs, gen_bs=args.train_bs * args.gen_ratio,
        prompt_len=args.prompt_len, new_tokens=args.new_tokens,
        steps=2 * args.steps + 1, max_staleness=args.max_staleness,
        seed=args.seed)
    stack = _ServingStack(
        runner, n_slots=args.slots, chunk=args.chunk,
        new_tokens=args.new_tokens, prompt_len=args.prompt_len,
        max_staleness=None)
    try:
        # warmup: one sync step pays every jit compile (generation
        # buckets, inference, train) so the timed windows compare
        # steady-state walls
        run_ppo_loop(runner, stack, mode="sync", steps=1,
                     train_bs=args.train_bs,
                     gen_bs=args.train_bs * args.gen_ratio,
                     max_staleness=args.max_staleness)
        skip = args.train_bs
        sync = run_ppo_loop(
            runner, stack, mode="sync", steps=args.steps,
            train_bs=args.train_bs,
            gen_bs=args.train_bs * args.gen_ratio,
            max_staleness=args.max_staleness, skip_prompts=skip)
        skip += args.steps * args.train_bs
        async_ = run_ppo_loop(
            runner, stack, mode="async", steps=args.steps,
            train_bs=args.train_bs,
            gen_bs=args.train_bs * args.gen_ratio,
            max_staleness=args.max_staleness, skip_prompts=skip)
    finally:
        stack.close()
    return dict(
        backend=jax.default_backend(),
        config=dict(steps=args.steps, train_bs=args.train_bs,
                    gen_ratio=args.gen_ratio,
                    prompt_len=args.prompt_len,
                    new_tokens=args.new_tokens,
                    max_staleness=args.max_staleness),
        sync={k: v for k, v in sync.items() if k != "curve"},
        async_={k: v for k, v in async_.items() if k != "curve"},
        sync_curve=sync["curve"], async_curve=async_["curve"],
        async_speedup=round(async_["steps_per_sec"]
                            / max(sync["steps_per_sec"], 1e-9), 4),
        note=("tiny-model CPU harness: the load-bearing signals are "
              "async steps/s >= sync (overlap never regresses), the "
              "staleness histogram, and overlapped_steps > 0"))


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=3)
    ap.add_argument("--train-bs", type=int, default=4)
    ap.add_argument("--gen-ratio", type=int, default=2,
                    help="in-flight generation as a multiple of the "
                         "train batch (async mode)")
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=4)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--chunk", type=int, default=4)
    ap.add_argument("--max-staleness", type=int, default=4)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    out = run(args)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
