"""Benchmark entry: prints ONE JSON line with the headline metric.

Round-3 headline: **PPO end-to-end** -- the real 6-MFC PPO dataflow
graph (actor_gen -> {rew_inf, ref_inf, critic_inf} -> {actor_train,
critic_train}, reference ``experiments/common/ppo_exp.py:230-377``)
executed by the inline runner on one TPU chip with a tiny-but-real
llama-architecture model per role, sized so all four roles (actor +
critic with Adam state, frozen ref + reward) fit one v5e chip's HBM.

value        = PPO tokens/sec/chip: total actor tokens of one DFG step
               (prompts + generated, the tokens every train/inf MFC
               consumes) divided by the end-to-end step wall-clock.
vs_baseline  = reference-class-step-time / measured-step-time, where
               the reference class is modeled per phase from the same
               accounting the reference logs per step
               (master_worker.py:1461-1485 + base/monitor.py:277-353):
               train & inference MFCs at 40% MFU (the A100 Megatron
               efficiency class) and decode at 40% of the bf16
               weight+KV HBM-streaming roofline ("on par with vLLM",
               docs/source/arch.rst:128-135). >1.0 means this stack's
               end-to-end PPO step beats that reference class on this
               chip's specs.
extra        = per-phase wall-clock / MFU / roofline decomposition,
               reshard latency (parallel/realloc.py return value),
               decode throughput at serving batch, and the round-2 SFT
               MFU metric (kept for continuity).

Run: python bench.py on a machine with a TPU. With no TPU, or when any
phase fails, it exits non-zero: a CPU number is never written under a
device metric's name. Every phase runs in this one process, which
holds the chip.
"""

import json
import os
import sys
import time

# Process birth: the cold-window time-to-first-headline clock starts
# here, before any backend probe or compile.
_PROC_T0 = time.monotonic()

# Per-chip peaks keyed by ``device_kind`` (Google Cloud documentation,
# "TPU v5e": 197 TFLOP/s bf16, 819 GB/s HBM). A device that is not in
# the table is an error, not a default.
DEVICE_PEAKS = {
    "TPU v5 lite": dict(flops=197e12, hbm_bw=819e9),
}
REF_MFU = 0.40          # A100 Megatron-class train/inference MFU
REF_DECODE_ROOFLINE = 0.40  # vLLM-class fraction of HBM roofline


def require_tpu():
    """(peak FLOP/s, HBM bytes/s) of the attached TPU; exits non-zero
    when JAX's first device is not a TPU or has no row in
    DEVICE_PEAKS."""
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"bench.py needs a TPU; jax.devices()[0] is "
                 f"{dev.platform} ({dev.device_kind})")
    if dev.device_kind not in DEVICE_PEAKS:
        sys.exit(f"bench.py has no peaks for device kind "
                 f"{dev.device_kind!r}; add a sourced row to "
                 "DEVICE_PEAKS")
    peaks = DEVICE_PEAKS[dev.device_kind]
    return peaks["flops"], peaks["hbm_bw"]


def _flops_kw(cfg):
    return dict(n_layers=cfg.n_layers, hidden_dim=cfg.hidden_dim,
                n_q_heads=cfg.n_q_heads, n_kv_heads=cfg.n_kv_heads,
                head_dim=cfg.head_dim,
                intermediate_dim=cfg.intermediate_dim,
                vocab_size=cfg.vocab_size)


def _decode_roofline_s(cfg, batch, prompt_len, new_tokens, hbm_bw):
    """Ideal decode seconds: every step streams the bf16 weights plus
    each live stream's KV prefix from HBM."""
    kv_bytes_per_tok = (2 * cfg.n_layers * cfg.n_kv_heads
                        * cfg.head_dim * 2)
    kv_read = sum(batch * (prompt_len + t) * kv_bytes_per_tok
                  for t in range(new_tokens))
    decode_bytes = new_tokens * 2 * cfg.n_params() + kv_read
    return decode_bytes / hbm_bw


def bench_ppo():
    """Run the real 6-MFC PPO DFG; return (headline dict, extra dict,
    runner) -- the runner feeds the post-headline reshard phase."""
    import jax
    import numpy as np
    from realhf_tpu.api.config import DatasetAbstraction
    from realhf_tpu.base import monitor, testing
    from realhf_tpu.engine.optim import OptimizerConfig
    from realhf_tpu.experiments.common import apply_overrides
    from realhf_tpu.experiments.ppo_exp import PPOConfig
    from realhf_tpu.parallel.mesh import ParallelismConfig
    from realhf_tpu.system.inline import InlineRunner

    peak_flops, hbm_bw = require_tpu()
    # ~226M params/role: sized so all four roles (two trainable:
    # bf16 weights + fp32 master/Adam ~4.1 GB each at dp=1, two
    # frozen bf16 ~0.5 GB) fill most of the 16 GB chip while
    # leaving activation/KV headroom.
    model_cfg = dict(
        n_layers=8, n_kv_heads=5, n_q_heads=10, hidden_dim=1280,
        intermediate_dim=3456, vocab_size=32000, n_positions=4096,
        apply_rotary=True, layer_norm_type="rms", mlp_type="llama",
        use_attention_bias=False, use_attn_proj_bias=False,
        use_mlp_bias=False, activation_function="silu")

    def shape(env_key, builtin):
        return int(os.environ.get(env_key, builtin))

    n_seqs = shape("REALHF_BENCH_N_SEQS", 64)
    prompt_len = shape("REALHF_BENCH_PROMPT_LEN", 256)
    new_tokens = shape("REALHF_BENCH_NEW_TOKENS", 256)
    steps = max(1, shape("REALHF_BENCH_STEPS", 3))
    # Memory knobs for large-batch sweeps: remat trades 1/3 extra
    # train FLOPs (the baseline model gets the same 4/3 factor) for
    # activation memory; train_mbs accumulates gradients over SCANNED
    # on-device microbatches.
    if os.environ.get("REALHF_BENCH_REMAT", "0") == "1":
        model_cfg["gradient_checkpointing"] = True
    train_mbs = shape("REALHF_BENCH_TRAIN_MBS", 1)
    warmup = 1

    cfg = PPOConfig(experiment_name="benchppo", trial_name="t0",
                    total_train_epochs=100)
    apply_overrides(cfg, {
        "dataset.train_bs_n_seqs": str(n_seqs),
        "dataset.max_seqlen": str(prompt_len),
        "ppo.max_new_tokens": str(new_tokens),
        # fixed lengths => identical packed shapes every step, so the
        # timed steps reuse the warm compiled programs
        "ppo.min_new_tokens": str(new_tokens),
        "ppo.top_k": "50",
        "ppo.top_p": "0.95",
        "ppo.ppo_n_minibatches": "2",
        "ppo.force_no_logits_mask": "true",
    })
    if train_mbs > 1:
        apply_overrides(cfg, {
            "actor_train_n_mbs": str(train_mbs),
            "critic_train_n_mbs": str(train_mbs),
        })
    spec = cfg.build()
    spec.dataset = DatasetAbstraction(
        "random_prompt",
        args=dict(n_prompts=n_seqs * (2 * steps + warmup + 2),
                  prompt_len_min=prompt_len, prompt_len_max=prompt_len,
                  vocab_size=model_cfg["vocab_size"]))
    for role, mspec in spec.models.items():
        mspec.path = None
        mspec.random_init_config = dict(model_cfg)
        if mspec.optimizer is None:
            # frozen roles (ref / reward) store bf16 weights: halves
            # their HBM footprint and read traffic
            mspec.random_init_config["param_dtype"] = "bfloat16"
        mspec.bf16 = True
        mspec.parallel = ParallelismConfig()
        if mspec.optimizer is not None:
            mspec.optimizer = OptimizerConfig(
                lr=1e-6, warmup_steps_proportion=0.0,
                lr_scheduler_type="constant")
    spec.tokenizer = testing.IntegerTokenizer(
        vocab_size=model_cfg["vocab_size"])

    runner = InlineRunner(spec)
    acfg = runner.models["actor"].config
    ccfg = runner.models["critic"].config

    # In-memory span tracing over the timed steps (realhf_tpu/obs/):
    # the drained spans become the per-MFC wall-time breakdown in the
    # payload, making each round's perf trajectory attributable to a
    # phase rather than one opaque headline. No file path => spans
    # stay in the thread buffers until drained; overhead is a handful
    # of dict appends per multi-second step.
    from realhf_tpu.obs import metrics as obs_metrics
    from realhf_tpu.obs import tracing as obs_tracing
    obs_tracing.configure(process_name="bench", enabled=True)

    from realhf_tpu.api import data as data_api
    batches = iter(runner.dataloader)

    phase_hbm = {}

    def timed_step(batch, parallel=True):
        """One DFG step, level-parallel like the runtime: independent
        MFCs of a level execute concurrently (their host work
        overlaps; device compute still serializes on the one chip). Per-phase walls come from the host's per-node exec info;
        the step wall is end-to-end. ``parallel=False`` serializes --
        the honest denominator for per-phase MFU."""
        phase_secs = {}
        data = batch
        t_step = time.monotonic()
        with obs_tracing.span(
                "step", mode="parallel" if parallel else "serial"):
            for level in runner.dfg.topological_levels():
                named = [(node.name,
                          data.select([k for k in node.input_keys
                                       if k in data.keys]))
                         for node in level]
                outs = runner.host.execute_level(named,
                                                 parallel=parallel)
                for node, out in zip(level, outs):
                    info = runner.host.exec_infos.get(node.name) or {}
                    phase_secs[node.name] = info.get(
                        "secs", 0.0)
                    obs_metrics.observe("mfc_exec_secs",
                                        phase_secs[node.name],
                                        mfc=node.name)
                    # measured HBM profile: bytes in use right after
                    # each phase + process peak
                    if info.get("hbm_bytes_in_use"):
                        phase_hbm[node.name] = max(
                            phase_hbm.get(node.name, 0),
                            info["hbm_bytes_in_use"])
                        phase_hbm["proc_peak"] = max(
                            phase_hbm.get("proc_peak", 0),
                            info.get("proc_peak_hbm_bytes", 0))
                    if isinstance(out, data_api.SequenceSample):
                        data.update_(out)
        wall = time.monotonic() - t_step
        obs_metrics.observe(
            "ppo_step_secs", wall,
            mode="parallel" if parallel else "serial")
        return wall, phase_secs

    for _ in range(warmup):
        timed_step(next(batches), parallel=False)
    # Phase table from SERIALIZED steps first (serialized walls are the
    # honest per-phase MFU denominator). Phase walls average over all
    # serialized steps.
    per_phase = {}
    t0 = time.monotonic()
    for _ in range(steps):
        _, phases = timed_step(next(batches), parallel=False)
        for k, v in phases.items():
            per_phase[k] = per_phase.get(k, 0.0) + v
    serial_time = (time.monotonic() - t0) / steps
    per_phase = {k: v / steps for k, v in per_phase.items()}
    # Level-parallel steps (the runtime's real execution mode:
    # independent MFCs dispatch concurrently).
    parallel_time = None
    if os.environ.get("REALHF_BENCH_NO_PARALLEL") != "1":
        timed_step(next(batches), parallel=True)  # thread warmup
        t0 = time.monotonic()
        for _ in range(steps):
            timed_step(next(batches), parallel=True)
        parallel_time = (time.monotonic() - t0) / steps
    # Headline = the runtime-representative mode: level-parallel
    # dispatch is how the distributed runtime actually executes, so
    # its wall IS the headline; the serialized wall stands in only
    # when REALHF_BENCH_NO_PARALLEL=1 skipped it.
    step_time = parallel_time if parallel_time is not None \
        else serial_time

    # ---- reference-class per-phase model --------------------------------
    total_len = prompt_len + new_tokens
    seqlens = [total_len] * n_seqs
    fwd_flops = monitor.transformer_forward_flops(
        seqlens=seqlens, **_flops_kw(acfg))
    fwd_flops_c = monitor.transformer_forward_flops(
        seqlens=seqlens, **_flops_kw(ccfg))
    train_flops = 3 * fwd_flops * (4 / 3 if acfg.gradient_checkpointing
                                   else 1)
    train_flops_c = 3 * fwd_flops_c * (4 / 3 if ccfg.gradient_checkpointing
                                       else 1)
    gen_flops = monitor.generation_flops(
        prompt_lens=[prompt_len] * n_seqs, gen_len=new_tokens,
        **_flops_kw(acfg))
    prefill_flops = monitor.transformer_forward_flops(
        seqlens=[prompt_len] * n_seqs, **_flops_kw(acfg))

    decode_roof_s = _decode_roofline_s(acfg, n_seqs, prompt_len,
                                       new_tokens, hbm_bw)
    # Frozen roles and (since r4) trainable roles hold bf16 weights;
    # the decode roofline already assumes bf16 streaming.
    prefill_ref_s = prefill_flops / (REF_MFU * peak_flops)
    gen_ref_s = prefill_ref_s + decode_roof_s / REF_DECODE_ROOFLINE

    ref_model = {
        "actor_gen": gen_ref_s,
        "rew_inf": fwd_flops_c / (REF_MFU * peak_flops),
        "ref_inf": fwd_flops / (REF_MFU * peak_flops),
        "critic_inf": fwd_flops_c / (REF_MFU * peak_flops),
        "actor_train": train_flops / (REF_MFU * peak_flops),
        "critic_train": train_flops_c / (REF_MFU * peak_flops),
    }
    baseline_step = sum(ref_model.values())
    tokens_per_step = n_seqs * total_len
    phase_detail = {}
    for name, secs in per_phase.items():
        d = {"secs": round(secs, 4)}
        if name == "actor_gen":
            d["mfu"] = round(gen_flops / secs / peak_flops, 4)
            # decode wall = phase wall minus prefill modeled at the
            # reference MFU (advisor r3: modeling prefill at 100% MFU
            # overstated the decode denominator)
            d["decode_roofline_frac"] = round(
                decode_roof_s / max(secs - prefill_ref_s, 1e-9), 4)
        elif name.endswith("_train"):
            fl = train_flops if name.startswith("actor") else train_flops_c
            d["mfu"] = round(fl / secs / peak_flops, 4)
        else:
            fl = fwd_flops if name == "ref_inf" else fwd_flops_c
            d["mfu"] = round(fl / secs / peak_flops, 4)
        phase_detail[name] = d

    headline = {
        "metric": "ppo_tokens_per_sec_per_chip",
        "value": round(tokens_per_step / step_time, 1),
        "unit": "tokens/s",
        "vs_baseline": round(baseline_step / step_time, 4),
    }
    extra = {
        "ppo_step_time_s": round(step_time, 4),
        # which mode produced the headline step time
        "ppo_step_time_mode": ("parallel" if parallel_time is not None
                               else "serial"),
        "ppo_step_time_serial_s": round(serial_time, 4),
        "ppo_step_time_parallel_s": (round(parallel_time, 4)
                                     if parallel_time else None),
        "ppo_baseline_model_step_s": round(baseline_step, 4),
        # vs_baseline divides a MODELED reference-class step (40% MFU
        # train/inference, 40%-of-roofline decode) by the measured
        # step -- it is not a measured reference run (advisor r3).
        "baseline_note": "modeled reference class (40% MFU phases, "
                         "0.40-roofline decode), not a measured run",
        "ppo_n_seqs": n_seqs,
        "ppo_prompt_len": prompt_len,
        "ppo_new_tokens": new_tokens,
        "ppo_train_mbs": train_mbs,
        "ppo_remat": bool(model_cfg.get("gradient_checkpointing")),
        "ppo_actor_params_m": round(acfg.n_params() / 1e6, 1),
        "ppo_phases": phase_detail,
        "ppo_phase_hbm_gb": {k: round(v / 2 ** 30, 3)
                             for k, v in phase_hbm.items()},
    }

    # ---- observability payload (docs/observability.md) ------------------
    # step-span summary: per-span-name count/total/mean from the
    # drained tracer buffers (step + per-MFC compute spans), plus the
    # full metrics-registry snapshot -- the machine-diffable record
    # that makes BENCH_*.json perf regressions attributable per phase.
    span_agg = {}
    for s in obs_tracing.default_tracer().drain():
        d = span_agg.setdefault(s.name, dict(count=0, total_s=0.0))
        d["count"] += 1
        d["total_s"] += (s.end or s.start) - s.start
    for d in span_agg.values():
        d["total_s"] = round(d["total_s"], 4)
        d["mean_s"] = round(d["total_s"] / d["count"], 4)
    extra["ppo_step_spans"] = dict(sorted(span_agg.items()))
    extra["obs_metrics"] = obs_metrics.snapshot()
    obs_tracing.configure(enabled=False)

    return headline, extra, runner


def _reshard_metrics(runner, extra):
    """Mutates ``extra`` in place with reshard + cross-group sync
    metrics (returns nothing)."""
    import jax
    import numpy as np
    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.parallel import param_stream, realloc
    from realhf_tpu.parallel.mesh import (
        MeshContext,
        ParallelismConfig,
        make_mesh,
    )

    actor = runner.models["actor"]
    mesh = make_mesh(ParallelismConfig(),
                     devices=list(actor.engine.mesh.devices.flat))
    rep_engine = Engine(actor.config,
                        MeshContext(ModelName("actor_rep", 0), mesh,
                                    ParallelismConfig()),
                        jax.tree.map(np.copy, actor.engine.params_numpy()))
    lat = realloc.reallocate(actor.config, actor.engine.params,
                             rep_engine)
    lat = min(lat, realloc.reallocate(actor.config, actor.engine.params,
                                      rep_engine))
    param_bytes = sum(
        x.size * x.dtype.itemsize
        for x in jax.tree.leaves(actor.engine.params))
    extra["reshard_latency_s"] = round(lat, 4)
    extra["reshard_gbytes_per_s"] = round(param_bytes / lat / 1e9, 2)

    from realhf_tpu.base import name_resolve
    from realhf_tpu.system.data_plane import (
        DataClient,
        DataServer,
        DataStore,
    )

    name_resolve.reconfigure("memory")
    store = DataStore()
    server = DataServer("benchxg", "t0", "bench_worker", store)
    server.start()
    client = DataClient("benchxg", "t0")
    try:
        t0 = time.monotonic()
        host_params = actor.engine.params_numpy()  # collective gather
        flat = param_stream.flatten_params(host_params)
        plan = param_stream.plan_chunks(flat)
        for i, idxs in enumerate(plan):
            store.put_blob(f"__params__/actor/v1/chunk{i}", 1,
                           param_stream.chunk_payload(flat, idxs))

        def fetch(i):
            _, chunk = client.fetch_blob(
                "bench_worker", f"__params__/actor/v1/chunk{i}", 1)
            return chunk

        _, nbytes = realloc.install_param_chunks(
            actor.config, rep_engine, len(plan), fetch)
        sync_s = time.monotonic() - t0
        extra["cross_group_sync_s"] = round(sync_s, 4)
        extra["cross_group_sync_gbytes_per_s"] = round(
            nbytes / sync_s / 1e9, 2)
        extra["cross_group_sync_chunks"] = len(plan)
        extra["cross_group_sync_mbytes"] = round(nbytes / 1e6, 1)
    finally:
        client.close()
        server.stop()


def bench_sft():
    """Round-2 metric kept for continuity: SFT train MFU + batch decode
    throughput of a ~650M llama on one chip."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from realhf_tpu.api.config import ModelName
    from realhf_tpu.base import monitor
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.engine.optim import OptimizerConfig
    from realhf_tpu.models import transformer as T
    from realhf_tpu.models.config import TransformerConfig
    from realhf_tpu.ops import functional as F
    from realhf_tpu.parallel.mesh import (
        MeshContext,
        ParallelismConfig,
        make_mesh,
    )

    peak_flops, hbm_bw = require_tpu()
    cfg = TransformerConfig(
        n_layers=10, n_kv_heads=16, n_q_heads=16, hidden_dim=2048,
        intermediate_dim=5632, vocab_size=32000, n_positions=4096,
        apply_rotary=True, layer_norm_type="rms", mlp_type="llama",
        use_attention_bias=False, use_attn_proj_bias=False,
        use_mlp_bias=False, activation_function="silu",
        # bf16 weights (fp32 master lives in the ZeRO-sharded opt
        # state): the decode roofline assumes bf16 streaming, and
        # fp32 weights would halve the achievable fraction
        param_dtype="bfloat16",
        compute_dtype="bfloat16", gradient_checkpointing=True)
    n_streams, stream_len = 8, 1024
    steps, warmup = 5, 2

    parallel = ParallelismConfig()
    # one-chip phase by definition (dp=1), whatever the host holds
    mesh = make_mesh(parallel, devices=jax.devices()[:1])
    ctx = MeshContext(ModelName("bench", 0), mesh, parallel)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    engine = Engine(cfg, ctx, params,
                    optimizer=OptimizerConfig(
                        lr=1e-4, warmup_steps_proportion=0.0,
                        lr_scheduler_type="constant"),
                    total_train_steps=1000)

    rng = np.random.default_rng(0)
    ids = rng.integers(2, cfg.vocab_size,
                       size=(n_streams, stream_len)).astype(np.int32)
    # two packed sequences per stream (exercises segment masking)
    seg = np.concatenate(
        [np.full((n_streams, stream_len // 2), 1, np.int32),
         np.full((n_streams, stream_len - stream_len // 2), 2, np.int32)],
        axis=1)
    mb = dict(input_ids=ids, seg_ids=seg)

    def loss_fn(p, h, mb):
        lp = F.shifted_logprobs_from_hidden(
            cfg, p, h, mb["input_ids"], mb["seg_ids"])
        seg_ = mb["seg_ids"]
        valid = jnp.concatenate(
            [(seg_[:, 1:] == seg_[:, :-1]) & (seg_[:, 1:] != 0),
             jnp.zeros_like(seg_[:, :1], bool)], axis=1)
        loss = -(lp * valid).sum() / jnp.maximum(valid.sum(), 1)
        return loss, {}

    tokens_per_step = n_streams * stream_len
    for _ in range(warmup):
        engine.train_batch([mb], loss_fn, loss_fn_key="bench")
    jax.block_until_ready(engine.params)
    t0 = time.monotonic()
    for _ in range(steps):
        engine.train_batch([mb], loss_fn, loss_fn_key="bench")
    jax.block_until_ready(engine.params)
    dt = time.monotonic() - t0

    tok_per_sec = tokens_per_step * steps / dt
    half = stream_len // 2
    step_flops = monitor.transformer_train_flops(
        n_layers=cfg.n_layers, hidden_dim=cfg.hidden_dim,
        n_q_heads=cfg.n_q_heads, n_kv_heads=cfg.n_kv_heads,
        head_dim=cfg.head_dim, intermediate_dim=cfg.intermediate_dim,
        vocab_size=cfg.vocab_size,
        seqlens=[half, stream_len - half] * n_streams)
    # remat recomputes the forward pass once more in backward: 4x fwd
    step_flops = step_flops * 4 // 3 if cfg.gradient_checkpointing \
        else step_flops
    mfu = step_flops * steps / dt / peak_flops

    # ---- decode at serving batch (reference: "on par with vLLM") -------
    from realhf_tpu.engine import packing
    from realhf_tpu.ops.sampling import GenerationHyperparameters

    gen_bs = 64
    gen_prompt_len, gen_new = 256, 256
    gconfig = GenerationHyperparameters(
        max_new_tokens=gen_new, min_new_tokens=gen_new, greedy=False,
        top_k=50, top_p=0.95, force_no_logits_mask=True)
    prompts = [rng.integers(2, cfg.vocab_size, size=gen_prompt_len)
               .astype(np.int32) for _ in range(gen_bs)]
    pids, pseg, ppos = packing.left_padded_prompts(prompts, pad_id=0)
    key = jax.random.PRNGKey(0)
    gen_out = engine.generate(pids, pseg, ppos, key, gconfig,
                              eos_token_id=None, pad_token_id=0)
    np.asarray(gen_out.tokens)  # compile + warmup
    g0 = time.monotonic()
    gen_steps = 3
    for i in range(gen_steps):
        gen_out = engine.generate(pids, pseg, ppos,
                                  jax.random.fold_in(key, i), gconfig,
                                  eos_token_id=None, pad_token_id=0)
        np.asarray(gen_out.tokens)
    gdt = time.monotonic() - g0
    gen_tok_per_sec = gen_bs * gen_new * gen_steps / gdt

    # HBM roofline %: each decode step streams bf16 weights + KV
    decode_roof_s = _decode_roofline_s(cfg, gen_bs, gen_prompt_len,
                                       gen_new, hbm_bw)
    gdt_decode = gdt / gen_steps  # prefill is <3% of this wall time
    roofline_frac = decode_roof_s / gdt_decode

    return {
        "sft_tokens_per_sec_per_chip": round(tok_per_sec, 1),
        "sft_mfu": round(mfu, 4),
        "sft_vs_40pct_mfu": round(mfu / REF_MFU, 4),
        "sft_model_params_m": round(cfg.n_params() / 1e6, 1),
        "sft_step_time_s": round(dt / steps, 4),
        "gen_tokens_per_sec_per_chip": round(gen_tok_per_sec, 1),
        "gen_batch": gen_bs,
        "gen_prompt_len": gen_prompt_len,
        "gen_new_tokens": gen_new,
        "gen_hbm_roofline_frac": round(roofline_frac, 4),
    }


def payload_path() -> str:
    """Where the incrementally-flushed payload lands
    (REALHF_BENCH_PAYLOAD overrides; default next to bench.py)."""
    return os.environ.get(
        "REALHF_BENCH_PAYLOAD",
        os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "BENCH_partial.json"))


def _flush_payload(headline, extra, phases_done):
    """Atomically (re)write the partial payload file. Called after
    EVERY phase so a run that fails in a later phase (and exits
    non-zero) still leaves its latest complete record on disk."""
    record = dict(headline)
    record["extra"] = dict(extra)
    record["phases_done"] = list(phases_done)
    path = payload_path()
    tmp = path + ".tmp"
    try:
        with open(tmp, "w") as f:
            json.dump(record, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, path)
    except OSError as e:
        print(f"# payload flush failed ({e}); continuing",
              file=sys.stderr)


def main():
    headline_only = "--headline-only" in sys.argv[1:]
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

    from realhf_tpu.base.backend import enable_compile_cache
    enable_compile_cache()
    require_tpu()

    import jax

    # No phase catches its failure: an exception leaves the payload
    # flushed so far on disk and exits non-zero.
    headline, extra, runner = bench_ppo()

    dev = jax.devices()[0]
    extra["device"] = dict(platform=dev.platform, kind=dev.device_kind,
                           count=len(jax.devices()))
    extra["time_to_first_headline_s"] = round(
        time.monotonic() - _PROC_T0, 2)
    extra["headline_only"] = headline_only
    phases_done = ["ppo_headline"]
    _flush_payload(headline, extra, phases_done)
    if headline_only:
        # print the valid headline JSON line NOW; later enrichment
        # only updates the payload file
        headline_now = dict(headline)
        headline_now["extra"] = extra
        print(json.dumps(headline_now))
        sys.stdout.flush()

    # per-kernel backend gate (ops/dispositions.py; says nothing of
    # the shape gates -- chip_smoke.py reads the compiled programs)
    from realhf_tpu.ops.dispositions import kernel_dispositions
    extra["kernel_disposition"] = kernel_dispositions()
    phases_done.append("kernel_disposition")
    _flush_payload(headline, extra, phases_done)

    if headline_only:
        return

    # Reshard + cross-group sync
    _reshard_metrics(runner, extra)
    phases_done.append("reshard")
    _flush_payload(headline, extra, phases_done)

    # SFT train MFU + decode at serving batch
    extra.update(bench_sft())
    phases_done.append("sft")
    _flush_payload(headline, extra, phases_done)
    headline["extra"] = extra
    print(json.dumps(headline))


if __name__ == "__main__":
    main()
