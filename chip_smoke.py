#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

One process takes Qwen2.5-0.5B (published config, all 24 layers, random
weights made from ``--seed``) through three GRPO steps by the path a
user takes:

    realhf_tpu.apps.quickstart.main(["grpo", ...])
      -> system/inline.InlineRunner -> system/model_host.ModelHost
      -> engine/engine.Engine   (actor_gen -> rew_inf, ref_inf -> actor_train)

There is no network: the script writes the checkpoint directory
(``config_from_hf`` on the published config dict, ``init_params``,
``save_hf_checkpoint``, a tokenizer built in code) and a JSONL of
prompts, then hands quickstart ``actor.path=... dataset.path=...`` as a
user would. It checks that every optimizer step's loss and gradient
norm are finite, that generation and training agree on the sampled
tokens' log-probabilities (importance weight ~ 1 on each step's first,
still on-policy minibatch: decode kernels against the flash kernel),
that the saved actor differs from the initial checkpoint, and that the
train, inference and generation programs hold a ``tpu_custom_call``,
read from their compiled text. It counts the compiles of each step.

    python chip_smoke.py             # one TPU chip; what the driver runs
    python chip_smoke.py --chips 4   # only the path across four chips:
                                     # actor d2t2, generation on d4t1,
                                     # and the reshard between them
    python chip_smoke.py --rehearse  # tiny model on the CPU: finds wrong
                                     # paths; never prints "ok": true and
                                     # exits 3

Without a TPU it exits non-zero and prints no result line. The last
line of a good run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
"""

import argparse
import json
import logging
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))

# https://huggingface.co/Qwen/Qwen2.5-0.5B/blob/main/config.json
QWEN25_05B = dict(
    architectures=["Qwen2ForCausalLM"], model_type="qwen2",
    hidden_size=896, intermediate_size=4864, num_hidden_layers=24,
    num_attention_heads=14, num_key_value_heads=2, vocab_size=151936,
    max_position_embeddings=32768, max_window_layers=24,
    rms_norm_eps=1e-06, rope_theta=1000000.0, sliding_window=32768,
    use_sliding_window=False, tie_word_embeddings=True,
    hidden_act="silu", attention_dropout=0.0, initializer_range=0.02,
    bos_token_id=151643, eos_token_id=151643, torch_dtype="bfloat16")
# Same family and flags at toy widths, for --rehearse only.
TINY = dict(QWEN25_05B, hidden_size=64, intermediate_size=128,
            num_hidden_layers=2, num_attention_heads=4,
            num_key_value_heads=2, vocab_size=512,
            max_position_embeddings=512, max_window_layers=2,
            bos_token_id=1, eos_token_id=1)

# The run's size. Every prompt is cut to PROMPT_LEN tokens by
# dataset.max_seqlen and random weights all but never sample EOS, so a
# sequence is PROMPT_LEN + NEW_TOKENS = 512 tokens and every packed row
# of SEQS_PER_ROW sequences is 4096 long: a multiple of 128 (the flash
# kernel's shape gate), at its length limit, and the same in every
# step, so steps after the first compile nothing. (By the third step
# the trained policy does end a few sequences early; rows are padded
# to the next multiple of 128, which is still 4096.)
REAL = dict(n_prompts=32, prompt_len=256, new_tokens=256, group=4,
            minibatches=4, seqs_per_row=8, steps=3)
REHEARSAL = dict(n_prompts=8, prompt_len=16, new_tokens=8, group=2,
                 minibatches=2, seqs_per_row=4, steps=2)

#: mean |delta log-prob| (nats, one fixed batch) allowed between two
#: layouts of the same weights: bf16 partial sums meet in another order
#: under t2 than under t1. A replica one train step stale must exceed
#: it, or the comparison would prove nothing.
LAYOUT_TOL = 0.1
#: |importance weight - 1| allowed on a step's first minibatch
ON_POLICY_TOL = 0.05

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter",
               "collective-permute", "all-to-all")


def say(**fields):
    print(json.dumps(fields), flush=True)


class Fail(Exception):
    pass


def check(cond, msg):
    if not cond:
        raise Fail(msg)


# ----------------------------------------------------------------------
# What the script makes from --seed
# ----------------------------------------------------------------------
def write_checkpoint(path, hf_config, seed):
    """An HF-layout directory a user would pass as ``actor.path``:
    config.json, bf16 safetensors, tokenizer files."""
    import jax
    from realhf_tpu.models import transformer as T
    from realhf_tpu.models.hf import registry

    cfg = registry.config_from_hf("qwen2", hf_config)
    cfg.param_dtype = "bfloat16"  # as published
    params = T.init_params(cfg, jax.random.PRNGKey(seed))
    registry.save_hf_checkpoint(path, "qwen2", cfg,
                                jax.device_get(params),
                                tokenizer=build_tokenizer(hf_config))
    # what the loader reads back is the published dict, not our echo
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(hf_config, f, indent=2)
    return cfg


def build_tokenizer(hf_config):
    """A word-level tokenizer over the model's whole vocabulary: word
    ``t<i>`` is token i, and the published EOS id is EOS and pad."""
    import tokenizers
    import transformers

    eos = hf_config["eos_token_id"]
    vocab = {f"t{i}": i for i in range(hf_config["vocab_size"])}
    del vocab[f"t{eos}"]
    vocab["<|endoftext|>"] = eos
    tok = tokenizers.Tokenizer(tokenizers.models.WordLevel(
        vocab, unk_token="<|endoftext|>"))
    tok.pre_tokenizer = tokenizers.pre_tokenizers.WhitespaceSplit()
    return transformers.PreTrainedTokenizerFast(
        tokenizer_object=tok, eos_token="<|endoftext|>",
        pad_token="<|endoftext|>")


def write_prompts(path, n, prompt_len, vocab_size, eos, seed):
    """``n`` prompts of prompt_len..prompt_len+32 words; the dataset's
    max_seqlen cuts each to prompt_len tokens."""
    import numpy as np
    rng = np.random.default_rng(seed)
    with open(path, "w") as f:
        for i in range(n):
            ids = rng.integers(0, vocab_size,
                               size=prompt_len + int(rng.integers(0, 33)))
            ids[ids == eos] = 0
            f.write(json.dumps(dict(
                id=i, prompt=" ".join(f"t{int(t)}" for t in ids))) + "\n")


# ----------------------------------------------------------------------
# Watching the run from outside: compiles, steps, the runner
# ----------------------------------------------------------------------
class CompileWatch:
    """Every program JAX lowers (name and argument shapes), the seconds
    the backend spent compiling or loading each from the persistent
    cache, and the cache's hits and misses."""

    def __init__(self):
        import jax
        from jax import monitoring

        self.programs = []   # "name shapes" in order
        self.secs = 0.0
        self.hits = self.misses = 0
        self.open = True
        self._log_compiles = jax.config.jax_log_compiles
        jax.config.update("jax_log_compiles", True)
        self._filters = [
            (logging.getLogger("jax._src.interpreters.pxla"),
             self._on_log),
            (logging.getLogger("jax._src.dispatch"),
             lambda rec: not rec.getMessage().startswith("Finished "))]
        for logger, f in self._filters:
            logger.addFilter(f)
        monitoring.register_event_duration_secs_listener(self._on_secs)
        monitoring.register_event_listener(self._on_event)

    def close(self):
        """Stop counting (JAX keeps the listeners; they go quiet)."""
        import jax
        self.open = False
        jax.config.update("jax_log_compiles", self._log_compiles)
        for logger, f in self._filters:
            logger.removeFilter(f)

    def _on_log(self, rec):
        msg = rec.getMessage()
        if not msg.startswith("Compiling "):
            return True
        name = msg.split()[1]
        shapes = msg.split("global shapes and types ", 1)[-1] \
            .split(". Argument mapping", 1)[0]
        self.programs.append(f"{name} {shapes}")
        return False  # counted here, kept off the console

    def _on_secs(self, event, secs, **_):
        if self.open and \
                event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _on_event(self, event, **_):
        if not self.open:
            return
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def mark(self):
        return len(self.programs), self.secs

    def since(self, mark):
        n, secs = mark
        return self.programs[n:], self.secs - secs


def short(program, width=240):
    """Program name and the END of its argument list: weights come
    first, and the batch, whose shape is what moves, comes last."""
    if len(program) <= width:
        return program
    name = program.split(" ", 1)[0]
    return f"{name} (... {program[-(width - len(name)):]}"


def n_cache_entries(path):
    return len(os.listdir(path)) if os.path.isdir(path) else 0


def device_line():
    import jax
    dev = jax.devices()[0]
    return dict(platform=dev.platform, kind=dev.device_kind,
                count=len(jax.devices()))


def has_kernel(engine, program):
    return "tpu_custom_call" in engine.compiled_text(program)


# ----------------------------------------------------------------------
# The run
# ----------------------------------------------------------------------
def run(args, work):
    """The whole run; ``work`` is an empty directory for what it writes
    (``main`` points ``REALHF_TPU_ROOT`` into it before realhf_tpu is
    imported)."""
    rehearse = args.rehearse
    size = dict(REHEARSAL if rehearse else REAL)
    hf_config = TINY if rehearse else QWEN25_05B

    import jax
    import jaxlib
    import numpy as np

    dev = device_line()
    if not rehearse and dev["platform"] != "tpu":
        sys.exit(f"chip_smoke.py needs a TPU: jax.devices()[0] is "
                 f"{dev['platform']} ({dev['kind']}). "
                 "--rehearse runs a tiny model on the CPU.")
    check(dev["count"] >= args.chips,
          f"--chips {args.chips} needs {args.chips} devices, "
          f"JAX sees {dev['count']}")
    try:
        import libtpu
        libtpu_version = libtpu.__version__
    except ImportError:
        libtpu_version = None

    from realhf_tpu.apps import quickstart
    from realhf_tpu.base.backend import enable_compile_cache
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.system.inline import InlineRunner

    cache_dir = enable_compile_cache()
    entries_before = n_cache_entries(cache_dir)
    say(phase="start", jax=jax.__version__, jaxlib=jaxlib.__version__,
        libtpu=libtpu_version, device=dev, chips_used=args.chips,
        seed=args.seed, rehearsal=rehearse, cache_dir=cache_dir,
        cache_entries_before=entries_before,
        model="tiny qwen2 (rehearsal)" if rehearse else "Qwen2.5-0.5B",
        size=size)

    t0 = time.monotonic()
    ckpt = os.path.join(work, "ckpt")
    cfg = write_checkpoint(ckpt, hf_config, args.seed)
    prompts = os.path.join(work, "prompts.jsonl")
    write_prompts(prompts, size["n_prompts"] * size["steps"],
                  size["prompt_len"], cfg.vocab_size,
                  hf_config["eos_token_id"], args.seed)
    say(phase="setup", secs=round(time.monotonic() - t0, 1),
        params_m=round(cfg.n_params() / 1e6, 1), checkpoint=ckpt)

    watch = CompileWatch()
    four = args.chips == 4
    tokens = size["prompt_len"] + size["new_tokens"]
    n_seqs = size["n_prompts"] * size["group"]
    rows_per_minibatch = n_seqs // size["minibatches"] \
        // size["seqs_per_row"]
    rows_per_batch = n_seqs // size["seqs_per_row"]
    if four:
        # dp=2: every stream batch has two rows, so half as many
        # microbatches give the same [*, 4096] rows
        rows_per_minibatch //= 2
        rows_per_batch //= 2
    overrides = [
        "experiment_name=chip-smoke", f"trial_name=seed{args.seed}",
        f"seed={args.seed}", f"tokenizer_path={ckpt}",
        "total_train_epochs=1", f"benchmark_steps={size['steps']}",
        f"dataset.path={prompts}",
        f"dataset.train_bs_n_seqs={size['n_prompts']}",
        f"dataset.max_seqlen={size['prompt_len']}",
        f"grpo.group_size={size['group']}",
        f"grpo.max_new_tokens={size['new_tokens']}",
        f"grpo.ppo_n_minibatches={size['minibatches']}",
        f"actor_train_n_mbs={max(rows_per_minibatch, 1)}",
        f"ref_inf_n_mbs={max(rows_per_batch, 1)}",
        f"rew_inf_n_mbs={max(rows_per_batch, 1)}",
        # an update a bf16 weight can show: the default 1e-5 is below
        # the spacing of bf16 around 0.02
        "actor.optimizer.lr=1e-4",
        "actor.optimizer.warmup_steps_proportion=0.0",
        "actor.optimizer.lr_scheduler_type=constant",
    ]
    for role in ("actor", "ref", "rew"):
        overrides += [f"{role}.type=qwen2", f"{role}.path={ckpt}"]
        if four:
            overrides += [f"{role}.parallel.data_parallel_size=2",
                          f"{role}.parallel.tensor_parallel_size=2"]
    if four:
        overrides.append("actor_gen_alloc=d4t1")

    # -- observe the run without changing its path ----------------------
    seen = dict(runner=None, steps=[], opt_steps=[], layout=[])
    runner_init, run_step = InlineRunner.__init__, InlineRunner.run_step
    train_batch = Engine.train_batch

    def watched_init(self, *a, **kw):
        runner_init(self, *a, **kw)
        seen["runner"] = self

    def watched_train_batch(self, *a, **kw):
        out = train_batch(self, *a, **kw)
        seen["opt_steps"].append(out)
        return out

    def watched_run_step(self, batch):
        if four and not seen["steps"]:
            # before any train step: the replica was built from the
            # primary's weights
            seen["layout"].append(layout_gap(self, args.seed))
        if len(seen["steps"]) == size["steps"] - 1:
            # the policy before the last step, to size one step's shift
            seen["lp_before_last"] = fixed_logprobs(
                self.models["actor"].engine, args.seed)
        mark, first_opt = watch.mark(), len(seen["opt_steps"])
        t = time.monotonic()
        stats = run_step(self, batch)
        jax.block_until_ready([m.engine.params
                               for m in self.models.values()])
        secs = time.monotonic() - t
        programs, compile_secs = watch.since(mark)
        trained = batch.total_len("packed_input_ids")
        seen["steps"].append(dict(
            step=len(seen["steps"]) + 1, secs=round(secs, 2),
            compiles=len(programs), compile_secs=round(compile_secs, 2),
            tokens_trained=trained,
            tokens_generated=trained - size["group"]
            * batch.total_len("packed_prompts"),
            first_minibatch=seen["opt_steps"][first_opt],
            actor_train={k: v for k, v in stats["actor_train"].items()
                         if isinstance(v, (int, float))},
            programs=[short(p) for p in programs]))
        say(phase="step", **seen["steps"][-1])
        return stats

    InlineRunner.__init__ = watched_init
    InlineRunner.run_step = watched_run_step
    Engine.train_batch = watched_train_batch
    t_run = time.monotonic()
    try:
        quickstart.main(["grpo"] + overrides)
    finally:
        InlineRunner.__init__ = runner_init
        InlineRunner.run_step = run_step
        Engine.train_batch = train_batch
        watch.close()
    run_secs = time.monotonic() - t_run
    runner = seen["runner"]

    # -- what came out ---------------------------------------------------
    steps = seen["steps"]
    check(len(steps) == size["steps"],
          f"ran {len(steps)} steps, wanted {size['steps']}")
    for i, s in enumerate(seen["opt_steps"]):
        check(np.isfinite(s["loss"]) and np.isfinite(s["grad_norm"]),
              f"optimizer step {i}: loss {s['loss']}, "
              f"grad norm {s['grad_norm']}")
    check(len(seen["opt_steps"]) == size["steps"] * size["minibatches"],
          f"{len(seen['opt_steps'])} optimizer steps")
    for s in steps:
        # fewer than the full count means a sequence sampled EOS early:
        # random weights all but never do, a trained policy may; packed
        # rows then shrink, and a compile in that step is named above
        check(0 < s["tokens_trained"] <= n_seqs * tokens,
              f"step {s['step']} trained {s['tokens_trained']} tokens "
              f"of at most {n_seqs * tokens}")
        iw = s["first_minibatch"]["importance_weight"]
        check(abs(iw - 1.0) < ON_POLICY_TOL,
              f"step {s['step']}: importance weight {iw} on the "
              "on-policy minibatch: generation and training disagree "
              "on the sampled tokens' log-probabilities")

    changed = saved_actor_change(runner, ckpt)
    check(changed["finite"], "the saved actor holds non-finite values")
    check(changed["max_abs_delta"] > 0,
          "the saved actor equals the initial checkpoint")

    actor = runner.models["actor"].engine
    shift = float(np.abs(fixed_logprobs(actor, args.seed)
                         - seen["lp_before_last"]).mean())
    gen_engine = (runner.replicas["actor_gen"].engine if four
                  else actor).decode_engine()
    kernels = dict(
        train=has_kernel(actor, "train"),
        inference_logprobs=has_kernel(runner.models["ref"].engine,
                                      "logprobs"),
        inference_values=has_kernel(runner.models["reward"].engine,
                                    "values"),
        generation=has_kernel(gen_engine, "generate"))
    mem = [d.memory_stats() or {} for d in jax.devices()[:args.chips]]
    say(phase="summary", run_secs=round(run_secs, 1),
        compile_secs_total=round(watch.secs, 1),
        programs_lowered=len(watch.programs),
        persistent_cache_hits=watch.hits,
        persistent_cache_misses=watch.misses,
        cache_dir=cache_dir, cache_entries_before=entries_before,
        cache_entries_after=n_cache_entries(cache_dir),
        step_secs=[s["secs"] for s in steps],
        compiles_per_step=[s["compiles"] for s in steps],
        tokens_generated_per_step=steps[-1]["tokens_generated"],
        tokens_trained_per_step=steps[-1]["tokens_trained"],
        peak_bytes_in_use=[m.get("peak_bytes_in_use") for m in mem],
        bytes_in_use=[m.get("bytes_in_use") for m in mem],
        tpu_custom_call=kernels, params_changed=changed,
        logprob_shift_last_step=shift)
    if not rehearse:
        missing = [k for k, v in kernels.items() if not v]
        check(not missing,
              f"no tpu_custom_call in the compiled {missing} program(s): "
              "attention ran in XLA")

    if four:
        four_chip_checks(runner, args, seen["layout"][0], shift, rehearse)

    if rehearse:
        say(phase="rehearsal_done", ok=False,
            note="a CPU rehearsal proves paths, never the chip")
        return 3
    print(json.dumps(dict(ok=True, device=dev)), flush=True)
    return 0


def saved_actor_change(runner, init_ckpt):
    """The checkpoint the run saved at its end against the one it
    started from, on the host, tensor by tensor."""
    import numpy as np
    import safetensors.numpy
    from realhf_tpu.base import constants

    def load(path):
        out = {}
        for name in sorted(os.listdir(path)):
            if name.endswith(".safetensors") and "value_head" not in name:
                out.update(safetensors.numpy.load_file(
                    os.path.join(path, name)))
        return out

    saved = os.path.join(constants.run_save_path(), "actor")
    check(os.path.exists(os.path.join(saved, "config.json")),
          f"the run saved no actor under {saved}")
    old, new = load(init_ckpt), load(saved)
    check(sorted(old) == sorted(new), "saved actor has other tensors")
    delta, finite, n_changed = 0.0, True, 0
    for k in old:
        a = np.asarray(old[k], np.float32)
        b = np.asarray(new[k], np.float32)
        finite = finite and bool(np.isfinite(b).all())
        d = float(np.abs(a - b).max())
        delta = max(delta, d)
        n_changed += d > 0
    return dict(finite=finite, max_abs_delta=delta,
                tensors_changed=int(n_changed), tensors=len(old))


# ----------------------------------------------------------------------
# --chips 4: the path across chips and what it is compared with
# ----------------------------------------------------------------------
def fixed_logprobs(engine, seed):
    """Log-probabilities of one fixed batch (4 rows of random tokens)
    under ``engine``'s weights, [4, L-1] float32 on the host."""
    import numpy as np
    rng = np.random.default_rng(seed + 1)
    length = 512 if engine.cfg.vocab_size > 1000 else 32
    ids = rng.integers(0, engine.cfg.vocab_size,
                       size=(4, length)).astype(np.int32)
    lp = np.asarray(engine.forward_logprobs(ids, np.ones_like(ids)),
                    np.float32)[:, :-1]
    check(np.isfinite(lp).all(), "non-finite log-probabilities")
    return lp


def layout_gap(runner, seed):
    """mean |delta log-prob| of the fixed batch between the actor's
    training layout (d2t2) and its generation replica (d4t1)."""
    import numpy as np
    return float(np.abs(
        fixed_logprobs(runner.models["actor"].engine, seed)
        - fixed_logprobs(runner.replicas["actor_gen"].engine, seed)
    ).mean())


def four_chip_checks(runner, args, gap_before_train, shift, rehearse):
    import jax
    primary = runner.models["actor"]
    replica = runner.replicas["actor_gen"]
    tol = LAYOUT_TOL

    # the run's last act was a train step: the replica is one step stale
    stale = layout_gap(runner, args.seed)
    # the runtime's own pre-hook for a replica's MFC (model_host
    # _execute_locked): reallocate primary -> replica
    runner.host.replica_mgr.ensure_fresh("actor", primary, replica)
    reshard_secs = runner.host.replica_mgr.last_reshard_secs
    fresh = layout_gap(runner, args.seed)
    reshard_bytes = sum(x.size * x.dtype.itemsize for x in
                        jax.tree.leaves(primary.engine.params))

    devices = sorted({s.device.id
                      for leaf in jax.tree.leaves(primary.engine.params)
                      for s in leaf.addressable_shards})
    in_use = [(d.memory_stats() or {}).get("bytes_in_use")
              for d in jax.devices()[:4]]
    train_text = primary.engine.compiled_text("train")
    collectives = {c: train_text.count(c + "(") + train_text.count(
        c + "-start(") for c in COLLECTIVES}
    say(phase="four_chips", tolerance_mean_abs_logprob=tol,
        gap_before_train=gap_before_train, gap_stale_replica=stale,
        primary_shift_last_step=shift,
        gap_after_reshard=fresh, reshard_secs=round(reshard_secs, 4),
        reshard_bytes=reshard_bytes,
        reshard_gbytes_per_sec=round(reshard_bytes / reshard_secs / 1e9,
                                     2),
        actor_shard_devices=devices, bytes_in_use=in_use,
        train_collectives=collectives)
    check(gap_before_train <= tol,
          f"before any train step the layouts differ by {gap_before_train}")
    check(fresh <= tol,
          f"after the reshard the layouts differ by {fresh}")
    # the toy model of a rehearsal moves too little for the real
    # tolerance; there the stale replica must stand out from the noise
    check(stale > (2 * max(fresh, gap_before_train) if rehearse else tol),
          f"a replica one train step stale differs by only {stale}: the "
          "comparison would pass a stale replica")
    check(len(devices) == 4,
          f"actor shards live on devices {devices}, not on four")
    check(any(collectives.values()),
          "no collective in the compiled train program")
    if not rehearse:
        check(None not in in_use and max(in_use) <= 1.25 * min(in_use),
              f"bytes_in_use differ across chips: {in_use}")


def main():
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--seed", type=int, default=1)
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4: only the path across chips (actor d2t2, "
                        "generation d4t1, the reshard between them)")
    p.add_argument("--rehearse", action="store_true",
                   help="tiny model on the CPU; never ok, exits 3")
    args = p.parse_args()
    sys.path.insert(0, ROOT)
    if args.rehearse:
        os.environ["JAX_PLATFORMS"] = "cpu"
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") +
            f" --xla_force_host_platform_device_count={args.chips}"
        ).strip()
        # an XLA:CPU executable loaded from the persistent cache can
        # deadlock on multi-device collectives (tests/conftest.py)
        os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"
    # Everything the run writes stays inside the checkout, and is set
    # before realhf_tpu is imported: logs, checkpoints, recover info.
    work = os.path.join(ROOT, ".smoke_run")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    os.environ["REALHF_TPU_ROOT"] = os.path.join(work, "root")
    try:
        return run(args, work)
    except Fail as e:
        print(f"chip_smoke.py FAILED: {e}", file=sys.stderr, flush=True)
        return 1
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
