#!/usr/bin/env python3
"""A builder's run on the chip for the ``olmoe`` family, outside the
benchmark: what sizes ``benchmark/families/olmoe.py``'s TOLERANCE, the
decode path held to the reference at published widths, and one
``quickstart gen`` run on the same checkpoint.

    chiprun --chips 1 -- python3 scripts/chip_check_olmoe.py \
        --seeds 2713340771 3190554277 [--skip-gen]

One process (the chip belongs to it). Prints one JSON line a phase and
writes them to ``chiprun_out/chip_check_olmoe.jsonl``:

- ``tolerance``: for each seed, the bf16 engine's log-probabilities on
  the benchmark's fixed 4 x 256 batch against the float32 reference,
  as a share of the reference's spread; the same for the reference at
  default matmul precision, with the EXPERT weights rounded to int8 by
  row, float8 e4m3 and e5m2, with every matrix so rounded, and with
  the gates renormalised; and how many of the 1,024 tokens change
  their set of 8 experts when the router's input is rounded to bf16
  and its product taken at default precision, as the engine does.
- ``decode``: prefill of 192 tokens, then 64 ``decode_step``s through
  the cache, teacher-forced, bf16: the log-probabilities of the true
  next tokens against the reference's full forward.
- ``gen``: ``quickstart gen`` whole (128 prompts of 256, 256 new
  tokens, two batches): the blocked seconds of the second
  ``engine:generate`` span, and those over 256 token steps (prefill
  inside).
"""

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

CELL = "olmoe-1b-7b-0125-l1.sft-2k"
OUT = os.path.join(ROOT, "chiprun_out", "chip_check_olmoe.jsonl")


def say(**fields):
    line = json.dumps(fields)
    print(line, flush=True)
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "a") as f:
        f.write(line + "\n")


def one_chip_engine(ckpt):
    import jax

    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.models.hf import registry
    from realhf_tpu.parallel import mesh as mesh_lib

    cfg, params = registry.load_hf_checkpoint(ckpt, "olmoe")
    cfg.param_dtype = "bfloat16"
    par = mesh_lib.ParallelismConfig()
    ctx = mesh_lib.MeshContext(
        ModelName("default", 0),
        mesh_lib.make_mesh(par, jax.devices()[:1]), par)
    return Engine(cfg, ctx, params)


def round_int8_by_row(x):
    import jax.numpy as jnp
    x = x.astype(jnp.float32)
    scale = jnp.abs(x).max(axis=-1, keepdims=True) / 127.0
    return jnp.round(x / scale) * scale


def round_to(dtype):
    import jax.numpy as jnp
    return lambda x: x.astype(dtype).astype(jnp.float32)


def experts_rounded(tensors, cast):
    """The checkpoint with only the experts' matrices rounded."""
    import numpy as np
    return {k: (np.asarray(cast(v), np.float32) if ".experts." in k else v)
            for k, v in tensors.items()}


def share(got, want):
    from benchmark import reference
    gap, spread = reference.gap(got, want)
    return dict(mean_abs_delta=gap, reference_std=spread,
                share_of_std=gap / spread)


def flipped_tokens(family, hf, tensors, ids):
    """Tokens whose set of top-k experts changes when the router sees
    its input rounded to bf16 and multiplies at default precision."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    with jax.default_matmul_precision("highest"):
        get = family._getter(tensors, None)
        x = get("model.embed_tokens.weight")[jnp.asarray(ids)].astype(
            jnp.float32)
        pre = "model.layers.0."
        names = ["input_layernorm.weight"] + [
            f"self_attn.{n}.weight" for n in (
                "q_proj", "k_proj", "v_proj", "o_proj", "q_norm",
                "k_norm")]
        x = family._attention(hf, x, {n: get(pre + n) for n in names})
        u, p, gates = family._route(
            hf, x, get(pre + "post_attention_layernorm.weight"),
            get(pre + "mlp.gate.weight"))
    k = hf["num_experts_per_tok"]
    w = get(pre + "mlp.gate.weight").astype(jnp.float32)
    low = jax.nn.softmax(
        u.astype(jnp.bfloat16).astype(jnp.float32) @ w.T, axis=-1)
    a = np.sort(np.asarray(jax.lax.top_k(p, k)[1]), axis=-1)
    b = np.sort(np.asarray(jax.lax.top_k(low, k)[1]), axis=-1)
    top9 = np.asarray(jax.lax.top_k(p, k + 1)[0])
    return dict(tokens=int(a.shape[0] * a.shape[1]),
                flipped=int((a != b).any(-1).sum()),
                gate_mass_mean=float(top9[..., :k].sum(-1).mean()),
                margin_8th_9th_median=float(
                    np.median(top9[..., k - 1] - top9[..., k])))


def tolerance(cell, seed, work):
    import jax.numpy as jnp
    import numpy as np

    from benchmark import generate, reference

    family, hf = cell["family"], cell["hf"]
    ckpt = os.path.join(work, f"ckpt{seed}")
    t = time.monotonic()
    generate.write_checkpoint(ckpt, family, hf, seed)
    ids = generate.fixed_batch(hf, seed)
    tensors = reference.load_tensors(ckpt)
    want = family.logprobs(hf, tensors, ids)
    engine = one_chip_engine(ckpt)
    got = np.asarray(engine.forward_logprobs(ids, np.ones_like(ids)),
                     np.float32)[:, :-1]
    rows = dict(engine_bf16=share(got, want))

    import jax
    with jax.default_matmul_precision("default"):
        # family.logprobs sets "highest" itself: run its pieces here
        get = family._getter(tensors, None)
        x, _ = family._blocks(hf, get, jnp.asarray(ids))
        low = np.asarray(family._head(
            hf, x, get("model.norm.weight"), family._head_weight(hf, get),
            jnp.asarray(ids)), np.float32)
    rows["reference_default_precision"] = share(low, want)
    casts = dict(int8_by_row=round_int8_by_row,
                 float8_e4m3=round_to(jnp.float8_e4m3fn),
                 float8_e5m2=round_to(jnp.float8_e5m2))
    for name, cast in casts.items():
        rows[f"experts_{name}"] = share(family.logprobs(
            hf, experts_rounded(tensors, cast), ids), want)
        rows[f"all_matrices_{name}"] = share(
            family.logprobs(hf, tensors, ids, cast=cast), want)
    rows["gates_renormalised"] = share(family.logprobs(
        dict(hf, norm_topk_prob=True), tensors, ids), want)
    say(phase="tolerance", seed=seed, tolerance=family.TOLERANCE,
        secs=round(time.monotonic() - t, 1),
        routing=flipped_tokens(family, hf, tensors, ids), **rows)
    return ckpt, engine, tensors, ids, want


def decode(cell, engine, ids, want, n_pre=192):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from realhf_tpu.models import transformer as T

    cfg, params = engine.cfg, engine.params
    t = time.monotonic()
    ids = jnp.asarray(ids)
    b, n = ids.shape
    hidden, cache = jax.jit(
        lambda p, i: T.prefill(cfg, p, i, jnp.ones_like(i),
                               total_len=n))(params, ids[:, :n_pre])

    def lp_of(hidden, nxt):
        logits = T.lm_logits(cfg, params, hidden)
        return jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), nxt[..., None], -1)[..., 0]

    got = [np.asarray(lp_of(hidden, ids[:, 1:n_pre + 1]))]
    step = jax.jit(lambda p, c, tok, pos: T.decode_step(
        cfg, p, c, tok, pos, uniform_slot=True, mesh=engine.mesh))
    for pos in range(n_pre, n - 1):
        h, cache = step(params, cache, ids[:, pos],
                        jnp.full((b,), pos, jnp.int32))
        got.append(np.asarray(lp_of(h, ids[:, pos + 1]))[:, None])
    got = np.concatenate(got, axis=1)
    say(phase="decode", prefill=n_pre, decoded=n - 1 - n_pre,
        secs=round(time.monotonic() - t, 1),
        all_positions=share(got, want),
        decoded_positions=share(got[:, n_pre:], want[:, n_pre:]),
        tolerance=cell["family"].TOLERANCE)


def gen(cell, ckpt, work, seed):
    from benchmark import generate
    from realhf_tpu.apps import quickstart
    from realhf_tpu.obs import tracing

    prompts = os.path.join(work, "prompts.jsonl")
    generate.write_prompts(prompts, 256, 256, cell["hf"], seed)
    os.environ["REALHF_TPU_ROOT"] = os.path.join(work, "root")
    tracing.start(sync=True)
    t = time.monotonic()
    quickstart.main([
        "gen", "experiment_name=chip-check-olmoe", f"trial_name=s{seed}",
        f"seed={seed}", "total_train_epochs=1",
        f"dataset.path={prompts}", "dataset.train_bs_n_seqs=128",
        "dataset.max_seqlen=256", "model.type=olmoe",
        f"model.path={ckpt}", "max_new_tokens=256", "min_new_tokens=256",
        f"output_file={os.path.join(work, 'gen.jsonl')}"])
    wall = time.monotonic() - t
    capture = tracing.stop()
    spans = capture.named("engine:generate")
    secs = [s["end"] - s["start"] for s in spans]
    say(phase="gen", wall_secs=round(wall, 1), generate_secs=secs,
        attributes=[s["attributes"] for s in spans],
        secs_per_token_step=(secs[-1] / 256 if secs else None),
        routed_pairs=capture.counters)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--skip-gen", action="store_true")
    p.add_argument("--rehearse", action="store_true",
                   help="the tests' tiny OLMoE cell, on any device: "
                        "finds faults, measures nothing")
    args = p.parse_args()

    import jax

    from benchmark import run
    from realhf_tpu.base.backend import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    say(phase="start", platform=dev.platform, kind=dev.device_kind)
    if args.rehearse:
        cell = run.load_cell(os.path.join(
            ROOT, "tests", "benchmark", "olmoe", "manifest.json"),
            "tiny-olmoe.sft")
    elif dev.platform != "tpu":
        sys.exit("needs a TPU")
    else:
        cell = run.load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
    work = os.path.join(ROOT, "benchmark", ".cache", "chip_check_olmoe")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        for i, seed in enumerate(args.seeds):
            ckpt, engine, tensors, ids, want = tolerance(cell, seed, work)
            if i == 0:
                decode(cell, engine, ids, want)
            del engine, tensors
        if not args.skip_gen:
            gen(cell, ckpt, work, args.seeds[-1])
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
