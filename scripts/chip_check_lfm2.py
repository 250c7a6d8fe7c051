#!/usr/bin/env python3
"""A builder's run on the chip for the ``lfm2_moe`` family, outside the
benchmark: what sizes ``benchmark/families/lfm2_moe.py``'s TOLERANCE,
the packed rows and the decode path held to the reference at the
cell's widths, whether XLA:TPU's grouped matmul skips the rows past
its last group, and one ``quickstart gen`` run on the same checkpoint.

    chiprun --chips 1 -- python3 scripts/chip_check_lfm2.py \
        --seeds 2713340771 3190554277 [--gen]

One process (the chip belongs to it). Prints one JSON line a phase and
writes them to ``chiprun_out/chip_check_lfm2.jsonl``:

- ``ragged``: ``lax.ragged_dot`` of 16,384 sorted rows of 2048 against
  8 experts of 2048 x 1536 with group sizes that cover 2,048 rows (an
  eighth: the cell), 8,192 and all 16,384, of 2,048 and 4,096 rows
  alone, and of 4,096 rows whose last group takes in 2,048 rows that
  belong to no expert: milliseconds a call, every case compiled and
  warmed before any is timed. The first costs what 2,048 rows alone
  do: row tiles past the last group are skipped, and left UNWRITTEN
  (``rows_past_last_group_are_zero`` is false where the memory was
  not zero already; PERF.md, PR 31).
- ``tolerance``: for each seed, the bf16 engine's log-probabilities on
  the benchmark's fixed 4 x 256 batch against the float32 reference,
  as a share of the reference's spread; the same for the reference at
  default matmul precision, with the EXPERT weights rounded to int8 by
  row and float8, with every matrix so rounded, and with each WRONG
  equation: softmax in place of sigmoid, the bias left out of the
  choice, gates not renormalised, whole-width query/key norm, the
  convolution's taps reversed, the convolution crossing a document
  boundary; and how many tokens change their set of 4 experts in the
  first sparse layer when the bias is left out.
- ``packed``: the same four documents as ONE packed row of 1,024 with
  three boundaries inside, through the engine, against the reference's
  four separate documents.
- ``decode``: prefill of 192 tokens, then ``decode_step``s through K/V
  and conv state, teacher-forced, bf16, against the reference's full
  forward.
- ``gen`` (``--gen``): ``quickstart gen`` whole (128 prompts of 256,
  256 new tokens, two batches): the ``engine:generate`` spans with
  ``kv_layers`` and ``conv_state_bytes``.
"""

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from chip_check_olmoe import (  # noqa: E402  (scripts/ is sys.path[0])
    experts_rounded,
    round_int8_by_row,
    round_to,
    share,
)

CELL = "lfm2-24b-a2b-l5-ep8.sft"
OUT = os.path.join(ROOT, "chiprun_out", "chip_check_lfm2.jsonl")


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

    cfg, params = registry.load_hf_checkpoint(ckpt, "lfm2_moe")
    cfg.param_dtype = "bfloat16"
    par = mesh_lib.ParallelismConfig()
    ctx = mesh_lib.MeshContext(
        ModelName("default", 0),
        mesh_lib.make_mesh(par, jax.devices()[:1]), par)
    return Engine(cfg, ctx, params)


def ragged(hf, calls=20, rounds=3):
    """Does the grouped matmul pay for rows no group covers? Every
    case is compiled and warmed first, then timed ``rounds`` times in
    turn (the least is kept): rows, the rows its 8 groups cover, and
    how the covered rows are split (``even``; ``uneven``: seeded
    random sizes; ``last``: 256 rows a group and the rest in the last
    one, as ``ops/moe.py:_ragged_share`` counts the rows that belong to
    no held expert)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    h, f, e = hf["hidden_size"], hf["moe_intermediate_size"], 8
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (e, h, f), jnp.bfloat16)
    dot = jax.jit(jax.lax.ragged_dot)
    rng = np.random.default_rng(0)

    def sizes_of(covered, split):
        if split == "even":
            return np.full(e, covered // e)
        if split == "last":
            return np.array([256] * (e - 1) + [covered - 256 * (e - 1)])
        cuts = np.sort(rng.integers(0, covered + 1, e - 1))
        return np.diff(np.concatenate([[0], cuts, [covered]]))

    cases = {}
    for rows, covered, split in (
            (16384, 2048, "even"), (16384, 2048, "uneven"),
            (16384, 8192, "uneven"), (16384, 16384, "even"),
            (16384, 16384, "uneven"), (2048, 2048, "even"),
            (2048, 2048, "uneven"), (4096, 4096, "even"),
            (4096, 4096, "uneven"), (4096, 4096, "last"),
            (4096, 2048, "uneven")):
        x = jax.random.normal(key, (rows, h), jnp.bfloat16)
        sizes = jnp.asarray(sizes_of(covered, split), jnp.int32)
        y = jax.block_until_ready(dot(x, w, sizes))
        cases[f"rows{rows}_covered{covered}_{split}"] = dict(
            args=(x, w, sizes), ms=[], covered=covered,
            rows_past_last_group_are_zero=bool(
                (np.asarray(y[covered:].astype(jnp.float32)) == 0).all()))
    for _ in range(rounds):
        for case in cases.values():
            t = time.monotonic()
            for _ in range(calls):
                y = dot(*case["args"])
            jax.block_until_ready(y)
            case["ms"].append((time.monotonic() - t) / calls * 1e3)
    say(phase="ragged", **{
        name: dict(ms=min(c["ms"]), ms_rounds=c["ms"],
                   tflops_of_covered_rows=2 * c["covered"] * h * f
                   / min(c["ms"]) / 1e9,
                   rows_past_last_group_are_zero=c[
                       "rows_past_last_group_are_zero"])
        for name, c in cases.items()})


def flipped_without_bias(family, hf, tensors, ids):
    """Tokens of the first sparse layer whose 4 experts change when the
    bias is left out of the choice, and what share of the routed pairs
    lands on the held experts."""
    import numpy as np
    layer = family.dims(hf)["dense"]
    with_bias = family.top_k_sets(hf, tensors, ids, layer)
    without = family.top_k_sets(dict(hf, use_expert_bias=False), tensors,
                                ids, layer)
    held = list(family.dims(hf)["held"])
    return dict(tokens=int(with_bias.shape[0] * with_bias.shape[1]),
                flipped=int((with_bias != without).any(-1).sum()),
                held_share_of_pairs=float(
                    with_bias[..., held].sum() / with_bias.sum()))


def tolerance(cell, seed, work):
    import jax
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

    with jax.default_matmul_precision("default"):
        # family.logprobs sets "highest" itself: run its pieces here
        get = family._getter(tensors, None)
        x, _ = family._blocks(hf, get, jnp.asarray(ids))
        low = np.asarray(family._token_logprobs(
            family._final(hf, x, get), jnp.asarray(ids)), np.float32)
    rows["reference_default_precision"] = share(low, want)
    casts = dict(int8_by_row=round_int8_by_row,
                 float8_e4m3=round_to(jnp.float8_e4m3fn),
                 float8_e5m2=round_to(jnp.float8_e5m2))
    for name, cast in casts.items():
        if name != "float8_e5m2":
            rows[f"experts_{name}"] = share(family.logprobs(
                hf, experts_rounded(tensors, cast), ids), want)
        rows[f"all_matrices_{name}"] = share(
            family.logprobs(hf, tensors, ids, cast=cast), want)
    for wrong in family.WRONG:
        rows[f"wrong_{wrong}"] = share(
            family.logprobs(hf, tensors, ids, wrong=(wrong,)), want)
    rows["wrong_bias_left_out"] = share(family.logprobs(
        dict(hf, use_expert_bias=False), tensors, ids), want)
    rows["wrong_gates_not_renormalised"] = share(family.logprobs(
        dict(hf, norm_topk_prob=False), tensors, ids), want)
    say(phase="tolerance", seed=seed, tolerance=family.TOLERANCE,
        secs=round(time.monotonic() - t, 1),
        routing=flipped_without_bias(family, hf, tensors, ids), **rows)
    return ckpt, engine, tensors, ids, want


def packed(cell, engine, ids, want):
    """The batch's documents as ONE packed row: the convolution and the
    flash kernel must stop at the three boundaries inside it."""
    import numpy as np
    b, n = ids.shape
    row = ids.reshape(1, b * n)
    seg = np.repeat(np.arange(1, b + 1, dtype=np.int32), n)[None]
    got = np.asarray(engine.forward_logprobs(row, seg), np.float32)
    got = got.reshape(b, n)[:, :-1]
    say(phase="packed", row=b * n, documents=b,
        tolerance=cell["family"].TOLERANCE, engine_bf16=share(got, want),
        first_two_tokens_after_a_boundary=share(got[1:, :2], want[1:, :2]))


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
        cache={k: list(v.shape) for k, v in cache.items()},
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
        "gen", "experiment_name=chip-check-lfm2", f"trial_name=s{seed}",
        f"seed={seed}", "total_train_epochs=1",
        f"dataset.path={prompts}", "dataset.train_bs_n_seqs=128",
        "dataset.max_seqlen=256", "model.type=lfm2_moe",
        f"model.path={ckpt}", "max_new_tokens=256", "min_new_tokens=256",
        f"output_file={os.path.join(work, 'gen.jsonl')}"])
    wall = time.monotonic() - t
    capture = tracing.stop()
    spans = capture.named("engine:generate")
    secs = [s["end"] - s["start"] for s in spans]
    say(phase="gen", wall_secs=round(wall, 1), generate_secs=secs,
        attributes=[s["attributes"] for s in spans],
        secs_per_token_step=(secs[-1] / 256 if secs else None),
        counters=capture.counters)


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--gen", action="store_true")
    p.add_argument("--only-ragged", action="store_true")
    p.add_argument("--rehearse", action="store_true",
                   help="the tests' tiny LFM2 cell, on any device: "
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
            ROOT, "tests", "benchmark", "lfm2", "manifest.json"),
            "tiny-lfm2.sft")
    elif dev.platform != "tpu":
        sys.exit("needs a TPU")
    else:
        cell = run.load_cell(os.path.join(ROOT, "BENCHMARK.json"), CELL)
        ragged(cell["hf"])
        if args.only_ragged:
            return
    work = os.path.join(ROOT, "benchmark", ".cache", "chip_check_lfm2")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        for i, seed in enumerate(args.seeds):
            ckpt, engine, tensors, ids, want = tolerance(cell, seed, work)
            if i == 0:
                packed(cell, engine, ids, want)
                decode(cell, engine, ids, want,
                       n_pre=ids.shape[1] * 3 // 4)
            del engine, tensors
        if args.gen:
            gen(cell, ckpt, work, args.seeds[-1])
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
