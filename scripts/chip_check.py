#!/usr/bin/env python3
"""A builder's run on the chip for a patterned sparse family
(``lfm2_moe``, ``laguna``, ``deepseek_v3``, ``kimi_linear``, ``keye_vl2``, ``nemotron_h``, ``smallthinker``) or a looped one (``ouro``), outside the benchmark: what sizes the
family's ``TOLERANCE`` in ``benchmark/families/<family>.py``, packed
rows and the decode path held to the reference at its cell's widths,
and one ``quickstart gen`` run on the same checkpoint.

    chiprun --chips 1 -- python3 scripts/chip_check.py laguna \
        --seeds 2713340771 3190554277 [--gen]

One process (the chip belongs to it). Prints one JSON line a phase and
writes them to ``chiprun_out/chip_check_<family>.jsonl``:

- ``tolerance``: for each seed, the bf16 engine's log-probabilities on
  the benchmark's fixed 4 x 256 batch against the float32 reference,
  as a share of the reference's spread; the same for the reference at
  default matmul precision, with the EXPERT weights rounded to int8 by
  row and float8, with every matrix so rounded, and with each WRONG
  equation the family names (``family.WRONG``, and the variants of
  ``FAMILIES`` below that are said by a config key).
- ``packed``: documents as ONE packed row through the engine, each
  against the reference's forward of that document alone. ``lfm2_moe``:
  the fixed batch's four documents in a row of 1,024, three boundaries
  for the convolution to stop at. ``laguna``: documents of 1,536,
  1,024, 1,024 and 512 tokens in a row of 4,096, so that the window's
  edge (512) lies inside three documents and three documents' edges
  lie inside a window's reach of the next one's first tokens; and what
  each wrong equation does to the longest of them (the fixed batch's
  256 tokens never reach a window of 512).
- ``exact`` (``deepseek_v3``): the program with FLOAT32 weights and
  products at the highest precision on ONE document of 4,096 tokens,
  the cell's row, through the COMPILED flash kernels at a key of 192
  and a value of 128, against the reference; and what each wrong
  equation reads there, reference against reference.
- ``slow_path`` (``deepseek_v3``): the share's fallback
  (``ops/moe.py:_ragged_share``: all ``T x k`` sorted rows, a
  rematerialised chunk at a time), which a benchmark run hardly ever
  takes, FORCED by handing the layer a quarter of its fast path's rows:
  the bf16 engine's log-probabilities against the reference, and loss
  and gradient of one 4,096-token SFT microbatch against the fast
  path's (on the chip a grouped product leaves a row no group covers
  unwritten, in the backward too: PERF.md, PR 31).
- ``uncovered_rows`` (``deepseek_v3``): the same microbatch with the
  rows of every grouped product that lie past its groups POISONED
  (NaN in the operands and in the cotangent) against the step as it
  is: ``ops/grouped_matmul.py``'s kernels return those rows as zero
  whatever lies there, so loss and gradient are equal; PR 31's
  failure (the rows left unwritten), which the CPU's zero hides.
- ``decode``: a prefill, then ``decode_step``s through the caches,
  teacher-forced, bf16, against the reference's full forward
  (log-probabilities, not tokens). ``laguna``: 2 sequences of 768, a
  prefill of 640: every decoded token's window (512) ends inside the
  cache, which keeps every row in window layers too.
- ``ragged`` (``lfm2_moe``, or any family with ``--only-ragged``):
  ``ops/grouped_matmul.py``'s kernels beside ``lax.ragged_dot``,
  forward and both gradients, at the four sparse cells' shapes with
  the groups covering all rows (``even``, ``uneven``) and half of
  them: milliseconds a call and TFLOP/s of the covered rows, every
  case compiled and warmed before any is timed. XLA's kernel skips the
  row tiles past its last group and leaves them UNWRITTEN (PERF.md, PR
  31), so the layer hands it every row; the repo's kernels visit the
  covered tiles alone and return the rest as zero.
- ``published``, ``exact_packed``, ``gradient``, ``scan``
  (``kimi_linear``): the harness's weights put the delta layers' decay
  near a half a token, so the same readings are taken again with the
  decay's two tensors as PUBLISHED (``family.published_decay``: a state
  lives hundreds of tokens): the bf16 engine and every wrong equation
  on the fixed batch; the FLOAT32 engine through the compiled program
  on ONE document of 2,048 tokens (``exact``) and on a packed row of
  four documents (each against the reference of it alone: the state's
  reset, the convolutions' stop), under both initialisations; loss and
  gradient of one SFT microbatch of 256 tokens through the engine
  against ``family.sft_loss_and_grad`` (the reference's gradient keeps
  a state a token: a row of 2,048 does not fit); and milliseconds of
  the chunked scan alone, forward and gradient, at the cell's shape by
  ``ops/delta_rule.py``'s kernels and by its XLA products side by side,
  the kernels' forward as a gradient runs it and their backward ALONE
  (``--against <tree>``: beside another checkout's kernels on the same
  operands, all nine outputs and gradients compared to the last bit),
  with ``scan_accuracy``: the chunked scan (through the kernels, and
  by the XLA products) and the recurrence token by token in float32 on
  the device, each against the recurrence in float64 on the host.
  ``--only <phases>`` runs these rows alone.
- ``selection`` (``keye_vl2``): the fixed batch's 256 tokens never
  reach the indexer's ``topk`` of 2,048, so everything about the
  selection is read on ONE document of 4,096 tokens: the bf16 engine
  through the compiled kernels against the reference; the reference
  with the INDEXER's terms rounded to bfloat16 (what the selection's
  precision alone costs: keys near the 2,048th score fall in or out)
  and how many selected pairs that moves in the first layer; the
  (query block, key block) pairs the forward kernel visits that hold
  no selected pair, counted on the host by the kernels' own rule; then
  ``exact`` (the float32 engine through the compiled kernels, every
  wrong equation there), a packed row whose first document passes
  ``topk``, and a prefill of 2,176 then 127 decode steps through the
  three attention caches.
- ``objective`` (``ouro``): the looped objective (every pass's loss
  weighed by the exit gate's distribution, less its entropy) and its
  gradient on one microbatch of 512 tokens, rematerialised as the
  experiments run it: the FLOAT32 engine against ``jax.grad`` of the
  reference's written-out passes (loss, every statistic, the relative
  gap of a shared matrix's gradient, of a norm's, of the gate's), the
  BF16 engine's gradient against the same (the sum of four passes'
  gradients is taken in the parameters' dtype: what that costs at
  published widths), and what the WRONG objective reads.
- ``gen`` (``--gen``): ``quickstart gen`` whole (128 prompts of 256,
  256 new tokens, two batches): the ``engine:generate`` spans with
  their attributes.
"""

import argparse
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

#: what differs by family: its cell, the tests' tiny cell
#: (``--rehearse``), the wrong equations that are said by a key of the
#: config (``family.WRONG`` names the others), the packed row's
#: documents (None: the fixed batch's rows), the decode check's batch
#: (rows, length, prefill; None: the fixed batch, three quarters of it
#: prefilled)
FAMILIES = {
    "lfm2_moe": dict(
        cell="lfm2-24b-a2b-l5-ep8.sft", tiny=("lfm2", "tiny-lfm2.sft"),
        wrong_keys=dict(bias_left_out=dict(use_expert_bias=False),
                        gates_not_renormalised=dict(norm_topk_prob=False)),
        packed_docs=None, decode=None),
    "laguna": dict(
        cell="laguna-xs.2-l5-ep16.sft-4k",
        tiny=("laguna", "tiny-laguna.sft"), wrong_keys={},
        packed_docs=(1536, 1024, 1024, 512), decode=(2, 768, 640)),
    "deepseek_v3": dict(
        cell="moonlight-16b-a3b-l5-ep8.sft-4k",
        tiny=("deepseek_v3", "tiny-deepseek-v3.sft"), wrong_keys={},
        packed_docs=(1536, 1024, 1024, 512), decode=(2, 768, 640),
        exact_doc=4096, slow_path=True, uncovered_rows=True),
    "kimi_linear": dict(
        cell="kimi-linear-48b-a3b-l5-ep32.sft-2k",
        tiny=("kimi_linear", "tiny-kimi-linear.sft"), wrong_keys={},
        packed_docs=(700, 600, 500, 248), decode=(2, 768, 640),
        exact_doc=2048, published_decay=True),
    "keye_vl2": dict(
        cell="keye-vl-2.0-30b-a3b-l5-ep8.sft-4k",
        tiny=("keye_vl2", "tiny-keye-vl2.sft"), wrong_keys={},
        packed_docs=(2560, 1024, 512), decode=(2, 2304, 2176),
        exact_doc=4096, selection=True),
    # published_decay: the family's ``published_init`` (the decay's two
    # tensors and D a Mamba layer)
    "nemotron_h": dict(
        cell="nemotron-3-nano-30b-a3b-l7-ep16.sft-1k",
        tiny=("nemotron_h", "tiny-nemotron-h.sft"), wrong_keys={},
        packed_docs=(1500, 1100, 1000, 496), decode=(2, 768, 640),
        exact_doc=4096, published_decay=True),
    # objective: the family's ``objective_and_grad`` (a looped model's)
    "ouro": dict(
        cell="ouro-2.6b-l6.sft-4k-x4", tiny=("ouro", "tiny-ouro.sft"),
        wrong_keys={}, packed_docs=(1536, 1024, 1024, 512),
        decode=(2, 768, 640), exact_doc=4096, objective=512),
    # rows past 4096: the packed row (16,384) and the long document go
    # through the flash kernels that STREAM K and V by block; the decode
    # check prefills 8,192 tokens (the stream forward) and decodes 127;
    # gradient: one SFT microbatch of that many tokens against
    # ``jax.grad`` of the reference
    "smallthinker": dict(
        cell="smallthinker-21b-a3b-l4-ep8.sft-16k",
        tiny=("smallthinker", "tiny-smallthinker.sft"), wrong_keys={},
        packed_docs=(4096, 8192, 2048, 2048), decode=(1, 8320, 8192),
        exact_doc=16384, gradient=256),
}
FAMILY = None  # set by main: the family's name, for say's file


def say(**fields):
    line = json.dumps(fields)
    print(line, flush=True)
    out = os.path.join(ROOT, "chiprun_out", f"chip_check_{FAMILY}.jsonl")
    os.makedirs(os.path.dirname(out), exist_ok=True)
    with open(out, "a") as f:
        f.write(line + "\n")


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


def one_chip_engine(ckpt, dtype="bfloat16"):
    import jax

    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.models.hf import registry
    from realhf_tpu.parallel import mesh as mesh_lib

    cfg, params = registry.load_hf_checkpoint(ckpt, FAMILY)
    cfg.param_dtype = cfg.compute_dtype = dtype
    par = mesh_lib.ParallelismConfig()
    ctx = mesh_lib.MeshContext(
        ModelName("default", 0),
        mesh_lib.make_mesh(par, jax.devices()[:1]), par)
    return Engine(cfg, ctx, params)


#: the grouped products' shapes in the four sparse cells: the sorted
#: rows a product takes, hidden, expert width, groups (cell 4 holds all
#: 64 experts: every row covered; cells 5 to 7 gather twice the rows
#: even routing brings their held experts)
RAGGED_SHAPES = {
    "olmoe": (16384, 2048, 1024, 64),
    "lfm2": (4096, 2048, 1536, 8),
    "laguna": (4096, 2048, 512, 16),
    "moonlight": (6144, 2048, 1408, 8),
}


def ragged(calls=4, inner=10, rounds=3, row_tiles=(None,)):
    """``ops/grouped_matmul.py``'s kernels beside ``lax.ragged_dot``:
    forward, gradient of the rows and gradient of the weights, each a
    program of its own, at the four cells' shapes (up / gate: rows x
    hidden x width; down: rows x width x hidden), bf16, with the
    groups covering ALL rows (``even``; ``uneven``: seeded random
    sizes) and HALF of them (what a share's fast path hands them:
    ``lax.ragged_dot`` is then timed as the layer calls it, the other
    half counted into the last group). A program runs the product
    ``inner`` times in a loop on the device, each turn's group sizes
    waiting on the turn before (one dispatch of a jitted call costs
    the host 0.2 ms here, more than most of these products: PR 38's
    first table read that floor). Every case is compiled and warmed
    first, then timed ``rounds`` times in turn (the least is kept):
    milliseconds a product and TFLOP/s of the COVERED rows.
    ``row_tiles``: the kernels' row tile to time them at (None: the
    file's own; a builder's sweep)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from realhf_tpu.ops import grouped_matmul as gm

    rng = np.random.default_rng(0)
    key = jax.random.PRNGKey(0)

    def sizes_of(covered, e, split):
        if split == "even":
            return np.full(e, covered // e)
        cuts = np.sort(rng.integers(0, covered + 1, e - 1))
        return np.diff(np.concatenate([[0], cuts, [covered]]))

    def looped(product):
        def turn(_, carry):
            x, w, ct, s = carry
            y = product(x, w, ct, s)
            # never true, and not known before the product has run
            return x, w, ct, s + (y.reshape(-1)[0] > 3e38).astype(s.dtype)
        return jax.jit(lambda *a: jax.lax.fori_loop(0, inner, turn, a)[3])

    def xla(which):
        dot = jax.lax.ragged_dot
        return looped({
            "fwd": lambda x, w, ct, s: dot(x, w, s),
            "d_rows": lambda x, w, ct, s: jax.vjp(
                lambda x: dot(x, w, s), x)[1](ct)[0],
            "d_weights": lambda x, w, ct, s: jax.vjp(
                lambda w: dot(x, w, s), w)[1](ct)[0]}[which])

    def kernels(which):
        return looped({
            "fwd": lambda x, w, ct, s: gm._gmm(x, w, s, False),
            "d_rows": lambda x, w, ct, s: gm._gmm(ct, w, s, True),
            "d_weights": lambda x, w, ct, s: gm._tgmm(x, ct, s)}[which])

    cases = {}
    own_tile = gm.ROW_TILE
    for cell, (rows, h, f, e) in RAGGED_SHAPES.items():
        for proj, (k, n) in (("up", (h, f)), ("down", (f, h))):
            x = jax.random.normal(key, (rows, k), jnp.bfloat16)
            w = jax.random.normal(key, (e, k, n), jnp.bfloat16)
            ct = jax.random.normal(key, (rows, n), jnp.bfloat16)
            for which in ("fwd", "d_rows", "d_weights"):
                fns = {"ragged_dot": (None, xla(which))}
                for tile in row_tiles:
                    fns["kernel" if tile is None
                        else f"kernel_tile{tile}"] = (tile, kernels(which))
                for split, covered in (("even", rows), ("uneven", rows),
                                       ("even", rows // 2),
                                       ("uneven", rows // 2)):
                    held = sizes_of(covered, e, split)
                    every = held.copy()
                    every[-1] += rows - covered
                    for name, (tile, fn) in fns.items():
                        gm.ROW_TILE = tile or own_tile
                        sizes = jnp.asarray(
                            every if name == "ragged_dot" else held,
                            jnp.int32)
                        jax.block_until_ready(fn(x, w, ct, sizes))
                        cases[f"{cell}_{proj}_{which}_covered{covered}"
                              f"_{split}_{name}"] = dict(
                            fn=fn, args=(x, w, ct, sizes), ms=[],
                            flops=2 * covered * k * n)
    gm.ROW_TILE = own_tile
    for _ in range(rounds):
        for case in cases.values():
            t = time.monotonic()
            for _ in range(calls):
                y = case["fn"](*case["args"])
            jax.block_until_ready(y)
            case["ms"].append(
                (time.monotonic() - t) / (calls * inner) * 1e3)
    say(phase="ragged", row_tile=own_tile, products_a_program=inner, **{
        name: dict(ms=min(c["ms"]),
                   tflops_of_covered_rows=c["flops"] / min(c["ms"]) / 1e9)
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


def tolerance(cell, seed, work, table=True):
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
    if not table:
        say(phase="tolerance", seed=seed, tolerance=family.TOLERANCE,
            secs=round(time.monotonic() - t, 1), **rows)
        return ckpt, engine, tensors, ids, want

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
    sparse = any(".experts." in k for k in tensors)
    for name, cast in casts.items():
        if name != "float8_e5m2" and sparse:
            rows[f"experts_{name}"] = share(family.logprobs(
                hf, experts_rounded(tensors, cast), ids), want)
        rows[f"all_matrices_{name}"] = share(
            family.logprobs(hf, tensors, ids, cast=cast), want)
    for wrong in family.WRONG:
        rows[f"wrong_{wrong}"] = share(
            family.logprobs(hf, tensors, ids, wrong=(wrong,)), want)
    for wrong, keys in FAMILIES[FAMILY]["wrong_keys"].items():
        rows[f"wrong_{wrong}"] = share(family.logprobs(
            dict(hf, **keys), tensors, ids), want)
    if FAMILY == "lfm2_moe":
        rows["routing"] = flipped_without_bias(family, hf, tensors, ids)
    say(phase="tolerance", seed=seed, tolerance=family.TOLERANCE,
        secs=round(time.monotonic() - t, 1), **rows)
    return ckpt, engine, tensors, ids, want


def packed(cell, engine, tensors, docs, ckpt=None):
    """``docs`` (1-D arrays of token ids) as ONE packed row through the
    engine, each against the reference's forward of it alone: the
    convolution, the flash kernels' ranges and windows, the rotary
    positions must all stop at the boundaries inside the row."""
    import numpy as np
    family, hf = cell["family"], cell["hf"]
    row = np.concatenate(docs)[None].astype(np.int32)
    seg = np.concatenate([np.full(len(d), j + 1, np.int32)
                          for j, d in enumerate(docs)])[None]
    got = np.asarray(engine.forward_logprobs(row, seg), np.float32)[0]
    rows, at = {}, 0
    window = hf.get("sliding_window") or hf.get("sliding_window_size")
    longest = max(docs, key=len)
    for j, doc in enumerate(docs):
        want = family.logprobs(hf, tensors, doc[None].astype(np.int32))[0]
        mine = got[at:at + len(doc) - 1]
        rows[f"document_{j}_of_{len(doc)}"] = dict(
            all=share(mine, want), first_two_tokens=share(mine[:2], want[:2]))
        if window and len(doc) > window + 1:
            rows[f"document_{j}_of_{len(doc)}"]["past_the_window"] = share(
                mine[window:], want[window:])
        at += len(doc)
    # the fixed batch's documents (256 tokens) are shorter than a
    # sliding window: what a window off by one, and every other wrong
    # equation, does to a document LONGER than the window is read here
    if window and len(longest) > window + 1:
        doc = longest[None].astype(np.int32)
        want = family.logprobs(hf, tensors, doc)
        rows["wrong_on_the_longest_document"] = {
            wrong: share(family.logprobs(hf, tensors, doc, wrong=(wrong,)),
                         want) for wrong in family.WRONG}
        # and the program with float32 weights and products at the
        # highest precision on that document: where bf16's noise hides
        # a window off by one, this reading is what it is held against
        import jax
        with jax.default_matmul_precision("highest"):
            exact = np.asarray(one_chip_engine(
                ckpt, "float32").forward_logprobs(doc, np.ones_like(doc)),
                np.float32)[:, :-1]
        rows["engine_float32_on_the_longest_document"] = share(exact, want)
    say(phase="packed", row=row.shape[1], documents=[len(d) for d in docs],
        tolerance=family.TOLERANCE, **rows)


def exact(cell, ckpt, tensors, doc):
    """The float32 engine at the highest precision on ONE document of
    the cell's row length, through the compiled kernels, against the
    reference; and every wrong equation there."""
    import jax
    import numpy as np
    family, hf = cell["family"], cell["hf"]
    t = time.monotonic()
    doc = doc[None].astype(np.int32)
    want = family.logprobs(hf, tensors, doc)
    with jax.default_matmul_precision("highest"):
        engine = one_chip_engine(ckpt, "float32")
        got = np.asarray(engine.forward_logprobs(doc, np.ones_like(doc)),
                         np.float32)[:, :-1]
        text = engine.compiled_text("logprobs")
        kernel = "tpu_custom_call" in text
    del engine
    say(phase="exact", document=doc.shape[1], flash_kernels=kernel,
        stream_kernels="flash_fwd_stream" in text,
        engine_float32=share(got, want),
        engine_float32_on_the_longest_document=share(got, want),
        wrong={wrong: share(family.logprobs(hf, tensors, doc,
                                            wrong=(wrong,)), want)
               for wrong in family.WRONG},
        secs=round(time.monotonic() - t, 1), tolerance=family.TOLERANCE)


def selection_live(cell, engine, tensors, doc):
    """``keye_vl2``: what the fixed batch cannot see, on ONE document
    long enough to pass the indexer's ``topk`` (see the module's
    docstring, ``selection``)."""
    import numpy as np

    from realhf_tpu.ops import sparse_index
    family, hf = cell["family"], cell["hf"]
    t = time.monotonic()
    doc = doc[None].astype(np.int32)
    want = family.logprobs(hf, tensors, doc)
    got = np.asarray(engine.forward_logprobs(doc, np.ones_like(doc)),
                     np.float32)[:, :-1]
    rounded = family.logprobs(hf, tensors, doc,
                              wrong=(family.INDEX_ROUNDED,))
    picked = family.selection(hf, tensors, doc, 0)
    moved = family.selection(hf, tensors, doc, 0,
                             wrong=(family.INDEX_ROUNDED,))
    empty, visited = sparse_index.unselected_blocks(
        picked, np.ones_like(doc))
    topk = family.dims(hf)["topk"]
    say(phase="selection", document=doc.shape[1], topk=topk,
        engine_bf16=share(got, want),
        past_topk=dict(engine_bf16=share(got[:, topk:], want[:, topk:]),
                       index_rounded=share(rounded[:, topk:],
                                           want[:, topk:])),
        index_rounded_to_bf16=share(rounded, want),
        first_layer=dict(selected_pairs=int(picked.sum()),
                         pairs_moved_by_rounding=int(
                             (picked != moved).sum() // 2),
                         blocks_visited=visited,
                         blocks_without_a_selected_pair=empty),
        flash_mask_calls=engine.program_facts("logprobs").attributes.get(
            "flash_mask_calls"),
        secs=round(time.monotonic() - t, 1), tolerance=family.TOLERANCE)


def with_published_decay(cell, ckpt, seed):
    """The checkpoint at ``ckpt`` with its delta layers' decay as
    published (``family.published_decay``; a family of ssm layers calls
    it ``published_init``), in a directory beside it: (that directory,
    its tensors)."""
    import safetensors.numpy

    from benchmark import reference
    family, hf = cell["family"], cell["hf"]
    out = ckpt + "-published"
    shutil.copytree(ckpt, out)
    redraw = getattr(family, "published_init", None) \
        or family.published_decay
    tensors = redraw(hf, reference.load_tensors(ckpt), seed)
    safetensors.numpy.save_file(
        tensors, os.path.join(out, "model.safetensors"))
    return out, tensors


def published(cell, ckpt, tensors, ids):
    """The bf16 engine and every wrong equation on the fixed batch
    under the published decay."""
    import numpy as np
    family, hf = cell["family"], cell["hf"]
    t = time.monotonic()
    want = family.logprobs(hf, tensors, ids)
    got = np.asarray(one_chip_engine(ckpt).forward_logprobs(
        ids, np.ones_like(ids)), np.float32)[:, :-1]
    say(phase="published", engine_bf16=share(got, want),
        **{f"wrong_{w}": share(family.logprobs(hf, tensors, ids,
                                               wrong=(w,)), want)
           for w in family.WRONG},
        secs=round(time.monotonic() - t, 1), tolerance=family.TOLERANCE)


def exact_packed(cell, ckpt, tensors, docs, decay):
    """The float32 engine at the highest precision on ``docs`` as ONE
    packed row, each document against the reference of it alone."""
    import jax
    import numpy as np
    family, hf = cell["family"], cell["hf"]
    t = time.monotonic()
    row = np.concatenate(docs)[None].astype(np.int32)
    seg = np.concatenate([np.full(len(d), j + 1, np.int32)
                          for j, d in enumerate(docs)])[None]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(one_chip_engine(ckpt, "float32").forward_logprobs(
            row, seg), np.float32)[0]
    rows, at = {}, 0
    for j, doc in enumerate(docs):
        want = family.logprobs(hf, tensors, doc[None].astype(np.int32))[0]
        rows[f"document_{j}_of_{len(doc)}"] = share(
            got[at:at + len(doc) - 1], want)
        at += len(doc)
    # the wrong equations that are about documents show on a packed
    # row only: reference against reference, the row's real pairs
    import jax.numpy as jnp
    real = np.asarray(seg[0, 1:] == seg[0, :-1])

    def of_the_row(wrong):
        out = family._token_logprobs(jnp.asarray(family.logits(
            hf, tensors, row, seg, wrong=wrong)), jnp.asarray(row))
        return np.asarray(out)[:, real]

    want = of_the_row(())
    # (a boundary touches the tokens just after it: the whole row's
    # mean dilutes them, so also the 8 that follow each boundary alone)
    starts = np.cumsum([len(d) for d in docs])[:-1]
    after = np.zeros(row.shape[1], bool)
    for at in starts:
        after[at:at + 8] = True
    after = after[1:][real]
    for wrong in ("state_over_documents", "conv_over_documents"):
        got = of_the_row((wrong,))
        rows[f"wrong_{wrong}"] = share(got, want)
        rows[f"wrong_{wrong}_8_tokens_after_a_boundary"] = share(
            got[:, after], want[:, after])
    say(phase="exact_packed", decay=decay, row=row.shape[1],
        secs=round(time.monotonic() - t, 1), **rows)


def gradient(cell, ckpt, tensors, seed, length=256):
    """Loss and gradient of one SFT microbatch of ``length`` tokens:
    the float32 engine's own objective against the reference's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from realhf_tpu.models import hf as hf_models
    family, hf = cell["family"], cell["hf"]
    t = time.monotonic()
    ids = np.random.default_rng(seed + 5).integers(
        0, hf["vocab_size"], (1, length)).astype(np.int32)
    mb = dict(input_ids=jnp.asarray(ids),
              seg_ids=jnp.ones((1, length), jnp.int32),
              prompt_mask=jnp.arange(length)[None] < length // 8)
    with jax.default_matmul_precision("highest"):
        engine = one_chip_engine(ckpt, "float32")
        from realhf_tpu.interfaces import sft
        engine.cfg.gradient_checkpointing = True
        (loss, _), grads = jax.jit(jax.value_and_grad(
            engine._objective(sft._make_loss_fn(engine.cfg)),
            has_aux=True))(engine.params, mb)
        got = hf_models.params_to_hf(
            FAMILY, jax.tree.map(np.asarray, grads), engine.cfg)
        del engine, grads
        ref_loss, _, want = family.sft_loss_and_grad(
            hf, tensors, ids, length // 8)
    norm = lambda g: float(np.sqrt(sum(
        np.square(v.astype(np.float64)).sum() for v in g.values())))
    gap = float(np.sqrt(sum(np.square(
        got[k].reshape(want[k].shape).astype(np.float64) - want[k]).sum()
        for k in want)))
    say(phase="gradient", tokens=length, loss=float(loss),
        reference_loss=ref_loss, grad_norm=norm(got),
        reference_grad_norm=norm(want), relative_l2=gap / norm(want),
        secs=round(time.monotonic() - t, 1))


def _timed(fn, args, rounds):
    """(``fn(*args)`` once, compiled and warm; milliseconds a call over
    ``rounds`` more)."""
    import jax
    first = jax.block_until_ready(fn(*args))
    t = time.monotonic()
    for _ in range(rounds):
        out = fn(*args)
    jax.block_until_ready(out)
    return first, round((time.monotonic() - t) / rounds * 1e3, 3)


def _relative_gaps(names, got, want):
    import jax.numpy as jnp
    import numpy as np
    f32 = jnp.float32
    rows = {}
    for name, a, b in zip(names, got, want):
        a, b = np.asarray(a.astype(f32)), np.asarray(b.astype(f32))
        rows[f"{name}_relative_gap"] = float(
            np.linalg.norm((a - b).ravel()) / np.linalg.norm(b.ravel()))
    return rows


def scan_alone(cell, rounds=20):
    """Milliseconds of the chunked recurrence alone at the cell's shape
    (one row of 2,048, 32 heads of 128, bf16 operands, a layer's
    ``Prepare`` applied where each path applies it), forward and
    gradient, by the kernels and by the XLA products, every case
    compiled and warmed before any is timed; and how far the two lie
    apart."""
    import jax
    import jax.numpy as jnp
    from realhf_tpu.ops import delta_rule as D
    lin = cell["hf"]["linear_attn_config"]
    shape = (1, cell["traffic"]["doc_len"], lin["num_heads"],
             lin["head_dim"])
    keys = jax.random.split(jax.random.PRNGKey(0), 5)
    bf16 = jnp.bfloat16
    q, k, v, g = (jax.random.normal(key, shape).astype(bf16)
                  for key in keys[:4])
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], shape[:3]))
    seg = jnp.ones(shape[:2], jnp.int32)
    prepare = D.Prepare(rate=-jnp.ones(shape[2]),
                        dt_bias=jnp.zeros(shape[2:]),
                        scale=shape[3] ** -0.5, eps=1e-6)
    rows, outs = {}, {}
    for path, scan in (("kernel", D._by_kernels), ("xla", D._by_xla)):
        fwd = jax.jit(lambda *a, scan=scan: scan(*a, seg, prepare)[0])
        bwd = jax.jit(jax.grad(lambda *a, scan=scan: scan(
            *a, seg, prepare)[0].astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3, 4)))
        for name, fn in (("forward", fwd), ("gradient", bwd)):
            outs[path, name] = jax.block_until_ready(fn(q, k, v, g, beta))
        for name, fn in (("forward", fwd), ("gradient", bwd)):
            t = time.monotonic()
            for _ in range(rounds):
                out = fn(q, k, v, g, beta)
            jax.block_until_ready(out)
            rows[f"{name}_ms_{path}"] = round(
                (time.monotonic() - t) / rounds * 1e3, 3)
    f32 = jnp.float32
    rows["forward_max_gap"] = float(jnp.abs(
        outs["kernel", "forward"].astype(f32)
        - outs["xla", "forward"].astype(f32)).max())
    rows.update(_relative_gaps(
        "dq dk dv dg dbeta".split(), outs["kernel", "gradient"],
        outs["xla", "gradient"]))
    rows.update(_scan_by_pass(D, (q, k, v, g, beta), seg, rounds))
    say(phase="scan", shape=list(shape), **rows)
    if len(jax.devices()) >= 4:
        scan_on_mesh(shape, rounds)
    scan_accuracy(shape[1], lin["head_dim"])


#: ``--against``: another checkout of this repo (the parent's, say)
AGAINST = None


def _scan_by_pass(D, x, seg, rounds):
    """The kernels' forward as a gradient runs it (keeping what the
    backward is handed) and their backward ALONE, milliseconds of
    each; given ``--against``, the same by that checkout's
    ``ops/delta_rule.py`` on the same operands, and which of the nine
    outputs and gradients (o, the last state, d of q, k, v, the decay's
    pre-activation, beta, and of the decay's rate and ``dt_bias``)
    differ from it in any bit."""
    import importlib.util

    import jax
    import jax.numpy as jnp
    import numpy as np
    h, d = x[0].shape[2:]
    x = (*x, -jnp.ones((h,)), jnp.zeros((h, d)))

    def passes(D):
        def scan(q, k, v, f, beta, rate, dt_bias):
            return D._by_kernels(q, k, v, f, beta, seg, D.Prepare(
                rate=rate, dt_bias=dt_bias, scale=d ** -0.5, eps=1e-6))
        (outs, pull), kept_ms = _timed(
            jax.jit(lambda *a: jax.vjp(scan, *a)), x, rounds)
        grads, back_ms = _timed(
            jax.jit(lambda pull, ct: pull(ct)),
            (pull, jax.tree.map(jnp.ones_like, outs)), rounds)
        return (*outs, *grads), kept_ms, back_ms

    mine, kept_ms, back_ms = passes(D)
    rows = dict(kept_forward_ms_kernel=kept_ms, backward_ms_kernel=back_ms)
    if AGAINST is not None:
        spec = importlib.util.spec_from_file_location(
            "delta_rule_against", os.path.join(
                AGAINST, "realhf_tpu", "ops", "delta_rule.py"))
        other = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(other)
        theirs, kept_ms, back_ms = passes(other)
        rows.update(
            kept_forward_ms_against=kept_ms, backward_ms_against=back_ms,
            differ_from_against=[
                name for name, a, b in zip(
                    "o last dq dk dv df dbeta drate ddt_bias".split(),
                    mine, theirs)
                if not np.array_equal(np.asarray(a.astype(jnp.float32)),
                                      np.asarray(b.astype(jnp.float32)))])
    return rows


def scan_on_mesh(shape, rounds):
    """Given four chips (``chiprun --chips 4``): the kernels on the
    engine's dp2 x tp2 mesh, two rows of the cell's shape with each
    device taking one row's half of the heads under ``shard_map``
    (``ops/delta_rule.py:_scan_over``: a bare Mosaic call does not
    lower on a mesh), against the kernels on ONE chip on the same
    values: outputs, last states and every gradient, those of the
    decay's two tensors (summed over "data") among them, and the
    milliseconds of both."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from realhf_tpu.ops import delta_rule as D
    from realhf_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS,
                                          ParallelismConfig, make_mesh)
    mesh = make_mesh(ParallelismConfig(data_parallel_size=2,
                                       tensor_parallel_size=2),
                     jax.devices()[:4])
    _, l, h, d = shape
    keys = jax.random.split(jax.random.PRNGKey(1), 7)
    wide = (2, l, h, d)
    x = [jax.random.normal(key, wide).astype(jnp.bfloat16)
         for key in keys[:4]]
    x.append(jax.nn.sigmoid(jax.random.normal(keys[4], wide[:3])))
    x.append(-jnp.exp(jax.random.uniform(keys[5], (h,))))
    x.append(jax.random.normal(keys[6], (h, d)) - 3)
    seg = jnp.ones(wide[:2], jnp.int32)
    specs = [P(DATA_AXIS, None, MODEL_AXIS)] * 5 + [P(MODEL_AXIS)] * 2

    def grads(mesh):
        def loss(q, k, v, f, beta, rate, dt_bias):
            o, last = D.chunked_delta_rule(
                q, k, v, f, beta, seg, D.Prepare(
                    rate=rate, dt_bias=dt_bias, scale=d ** -0.5, eps=1e-6),
                mesh=mesh)
            return o.astype(jnp.float32).sum() + last.sum(), (o, last)
        return jax.jit(jax.grad(loss, argnums=tuple(range(7)),
                                has_aux=True))

    rows, outs = {}, {}
    for name, fn, args in (
            ("one_chip", grads(None), x),
            ("mesh", grads(mesh), [jax.device_put(a, NamedSharding(mesh, s))
                                   for a, s in zip(x, specs)])):
        outs[name], rows[f"gradient_ms_{name}"] = _timed(fn, args, rounds)
    flat = lambda out: (*out[0], *out[1])
    rows.update(_relative_gaps(
        "dq dk dv df dbeta drate ddt_bias o last".split(),
        flat(outs["mesh"]), flat(outs["one_chip"])))
    say(phase="scan_on_mesh", shape=list(wide), mesh="d2t2", **rows)


SSM_NAMES = "x dt b c rate dt_bias skip".split()


def _ssm_operands(cell, rows, key):
    """The Mamba-2 scan's operands at the cell's shape (``rows`` rows
    of the traffic's row length, 64 heads of 64 in 8 groups, a state of
    128, bf16), the rate and the step as the harness draws them (a
    state halves every token), and the cell's rows of equal documents
    (four of 1024)."""
    import jax
    import jax.numpy as jnp
    hf, traffic = cell["hf"], cell["traffic"]
    l = traffic["doc_len"] * traffic["docs_per_row"]
    h, p = hf["mamba_num_heads"], hf["mamba_head_dim"]
    g, n = hf["n_groups"], hf["ssm_state_size"]
    keys = jax.random.split(jax.random.PRNGKey(key), 7)
    bf16 = jnp.bfloat16
    normal = jax.random.normal
    ops = [normal(keys[0], (rows, l, h, p)).astype(bf16),
           (0.5 * normal(keys[1], (rows, l, h))).astype(bf16),
           normal(keys[2], (rows, l, g, n)).astype(bf16),
           normal(keys[3], (rows, l, g, n)).astype(bf16),
           -jnp.exp(0.02 * normal(keys[4], (h,))),
           0.02 * normal(keys[5], (h,)), 1 + 0.02 * normal(keys[6], (h,))]
    seg = jnp.broadcast_to(
        1 + jnp.arange(l, dtype=jnp.int32) // traffic["doc_len"], (rows, l))
    return ops, seg


def _ssm_gradient(scan, seg):
    """The jitted gradient of a loss over the outputs and the last
    states by every operand and leaf, (y, last) beside it; ``scan``:
    ``ops/ssm_scan.py:_by_kernels`` or ``_by_xla``, or anything of
    their signature."""
    import jax
    import jax.numpy as jnp

    def loss(x, dt, b, c, rate, dt_bias, skip):
        y, last = scan(x, dt, b, c, seg, rate, dt_bias, skip)
        return y.astype(jnp.float32).sum() + last.sum(), (y, last)
    return jax.jit(jax.grad(loss, argnums=tuple(range(7)), has_aux=True))


def ssm_scan_alone(cell, rounds=20):
    """Milliseconds of the Mamba-2 scan alone at the cell's shape (one
    row of 4,096 of four documents, 64 heads of 64 in 8 groups, state
    128, bf16 operands), forward and gradient, by the kernels and by
    the XLA products, every case compiled and warmed before it is
    timed; and how far the two lie apart."""
    import jax
    import jax.numpy as jnp
    from realhf_tpu.ops import ssm_scan as S
    ops, seg = _ssm_operands(cell, 1, 0)
    rows, outs = {}, {}
    for path, scan in (("kernel", S._by_kernels), ("xla", S._by_xla)):
        fwd = jax.jit(lambda *a, scan=scan: scan(*a[:4], seg, *a[4:])[0])
        for name, fn in (("forward", fwd),
                         ("gradient", _ssm_gradient(scan, seg))):
            outs[path, name], rows[f"{name}_ms_{path}"] = _timed(
                fn, ops, rounds)
    f32 = jnp.float32
    rows["forward_max_gap"] = float(jnp.abs(
        outs["kernel", "forward"].astype(f32)
        - outs["xla", "forward"].astype(f32)).max())
    names = [f"d{name}" for name in SSM_NAMES] + ["y", "last"]
    flat = lambda out: (*out[0], *out[1])
    rows.update(_relative_gaps(names, flat(outs["kernel", "gradient"]),
                               flat(outs["xla", "gradient"])))
    say(phase="scan", shape=list(ops[0].shape), **rows)
    # whose bf16 is it: both paths against the XLA products on float32
    # operands at the highest precision
    with jax.default_matmul_precision("highest"):
        want = _ssm_gradient(S._by_xla, seg)(*(t.astype(f32) for t in ops))
    for path in ("kernel", "xla"):
        say(phase="scan_against_float32", path=path, **_relative_gaps(
            names, flat(outs[path, "gradient"]), flat(want)))
    if len(jax.devices()) >= 4:
        ssm_scan_on_mesh(cell, rounds)


def ssm_scan_on_mesh(cell, rounds):
    """Given four chips (``chiprun --chips 4``): the kernels on the
    engine's dp2 x tp2 mesh, two rows of the cell's shape with each
    device taking one row's half of the GROUPS under ``shard_map``
    (``ops/ssm_scan.py:_scan_over``), against the kernels on ONE chip
    on the same values: outputs, last states and every gradient, those
    of the three leaves a head (summed over "data") among them, and
    the milliseconds of both."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from realhf_tpu.ops import ssm_scan as S
    from realhf_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS,
                                          ParallelismConfig, make_mesh)
    mesh = make_mesh(ParallelismConfig(data_parallel_size=2,
                                       tensor_parallel_size=2),
                     jax.devices()[:4])
    ops, seg = _ssm_operands(cell, 2, 1)
    specs = [P(DATA_AXIS, None, MODEL_AXIS)] * 4 + [P(MODEL_AXIS)] * 3
    rows, outs = {}, {}
    for name, args, over in (
            ("one_chip", ops, None),
            ("mesh", [jax.device_put(a, NamedSharding(mesh, s))
                      for a, s in zip(ops, specs)], mesh)):
        def scan(x, dt, b, c, seg, rate, dt_bias, skip, over=over):
            return S.chunked_ssm_scan(x, dt, b, c, seg, rate=rate,
                                      dt_bias=dt_bias, skip=skip, mesh=over)
        outs[name], rows[f"gradient_ms_{name}"] = _timed(
            _ssm_gradient(scan, seg), args, rounds)
    flat = lambda out: (*out[0], *out[1])
    rows.update(_relative_gaps(
        [f"d{name}" for name in SSM_NAMES] + ["y", "last"],
        flat(outs["mesh"]), flat(outs["one_chip"])))
    say(phase="scan_on_mesh", shape=list(ops[0].shape), mesh="d2t2", **rows)


#: row ``scan`` by family: the operator whose chunked scan has kernels
SCAN_ROWS = {"kimi_linear": scan_alone, "nemotron_h": ssm_scan_alone}


def scan_accuracy(length, hd, heads=4):
    """Whose float32 is it: the chunked scan and the recurrence token by
    token (``delta_rule_step``), both on this device in float32 at the
    highest precision, each against the recurrence in FLOAT64 on the
    host, under a decay of a half a token and under the published one
    (a state that lives hundreds of tokens): shares of the truth's
    spread."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from realhf_tpu.ops import delta_rule as D
    rng = np.random.default_rng(0)
    shape = (1, length, heads, hd)
    unit = lambda x: x / np.linalg.norm(x, axis=-1, keepdims=True)
    q = unit(rng.normal(size=shape)) * hd ** -0.5
    k, v = unit(rng.normal(size=shape)), rng.normal(size=shape)
    beta = 1 / (1 + np.exp(-rng.normal(size=shape[:3])))
    rate = rng.uniform(1, 16, (heads, 1)) * np.exp(rng.uniform(
        np.log(1e-3), np.log(1e-1), (heads, hd)))
    f32 = lambda *xs: [jnp.asarray(x, jnp.float32) for x in xs]

    def token_by_token(q, k, v, g, beta):
        def step(s, x):
            o, s = D.delta_rule_step(*x, s)
            return s, o
        xs = tuple(jnp.moveaxis(x, 1, 0) for x in (q, k, v, g, beta))
        return jnp.moveaxis(jax.lax.scan(
            step, jnp.zeros((1, heads, hd, hd), jnp.float32), xs)[1], 0, 1)

    for decay, g in (("half_a_token", np.full(shape, -np.log(2.0))),
                     ("published", np.broadcast_to(-rate, shape))):
        s, truth = np.zeros((heads, hd, hd)), np.zeros(shape[1:])
        for t in range(length):
            s = s * np.exp(g[0, t])[..., None]
            u = beta[0, t, :, None] * (v[0, t] - np.einsum(
                "nkv,nk->nv", s, k[0, t]))
            s = s + k[0, t][..., None] * u[:, None, :]
            truth[t] = np.einsum("nkv,nk->nv", s, q[0, t])
        seg = jnp.ones(shape[:2], jnp.int32)
        with jax.default_matmul_precision("highest"):
            args = f32(q, k, v, g, beta)
            # (``chunked_delta_rule`` takes the kernels on the chip)
            chunked = jax.jit(lambda *a: D.chunked_delta_rule(
                *a, seg)[0])(*args)
            by_xla = jax.jit(lambda *a: D._by_xla(*a, seg, None)[0])(*args)
            stepped = jax.jit(token_by_token)(*args)
        say(phase="scan_accuracy", decay=decay, shape=list(shape),
            chunked=share(np.asarray(chunked)[0], truth),
            chunked_xla=share(np.asarray(by_xla)[0], truth),
            token_by_token=share(np.asarray(stepped)[0], truth))


#: what ``under_published_decay`` runs, in this order (``--only``)
PUBLISHED_PHASES = ("exact_packed", "published", "exact", "scan",
                    "gradient")


def under_published_decay(cell, ckpt, tensors, ids, seed, docs, long,
                          phases, rehearse):
    """``kimi_linear``'s own rows: ``phases`` of ``PUBLISHED_PHASES``
    on the checkpoint at ``ckpt`` and on a copy of it with the decay as
    published; ``docs``: the packed row's documents, ``long``: ONE
    document of the cell's row length."""
    ckpt_p, tensors_p = with_published_decay(cell, ckpt, seed)
    for phase in phases:
        if phase == "exact_packed":
            exact_packed(cell, ckpt, tensors, docs, "harness")
            exact_packed(cell, ckpt_p, tensors_p, docs, "published")
        elif phase == "published":
            published(cell, ckpt_p, tensors_p, ids)
        elif phase == "exact":
            exact(cell, ckpt_p, tensors_p, long)
        elif phase == "scan":
            SCAN_ROWS[FAMILY](cell)
        elif phase == "gradient":
            try:  # the reference's gradient is the largest program
                gradient(cell, ckpt_p, tensors_p, seed,
                         64 if rehearse else 256)
            except Exception as e:  # noqa: BLE001 - out of memory
                say(phase="gradient", failed=repr(e)[:400])


def objective(cell, ckpt, tensors, seed, length):
    """A looped model's objective and its gradient on one microbatch of
    ``length`` tokens (an eighth of it the prompt), rematerialised: the
    float32 engine and the bf16 engine against ``jax.grad`` of the
    reference, tensor by tensor."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from realhf_tpu.interfaces import sft
    from realhf_tpu.models import hf as hf_models
    family, hf = cell["family"], cell["hf"]
    t = time.monotonic()
    ids = np.random.default_rng(seed + 5).integers(
        0, hf["vocab_size"], (1, length)).astype(np.int32)
    mb = dict(input_ids=jnp.asarray(ids),
              seg_ids=jnp.ones((1, length), jnp.int32),
              prompt_mask=jnp.arange(length)[None] < length // 8)
    ref_loss, parts, want = family.objective_and_grad(
        hf, tensors, ids, length // 8)
    wrong_loss = {w: float(family.objective(
        hf, tensors, ids, length // 8, wrong=(w,))[0])
        for w in family.WRONG}

    def through_the_engine(dtype):
        with jax.default_matmul_precision(
                "highest" if dtype == "float32" else "default"):
            engine = one_chip_engine(ckpt, dtype)
            engine.cfg.gradient_checkpointing = True
            (loss, stats), grads = jax.jit(jax.value_and_grad(
                engine._objective(sft._make_loss_fn(engine.cfg)),
                has_aux=True))(engine.params, mb)
            got = hf_models.params_to_hf(FAMILY, jax.tree.map(
                lambda g: np.asarray(g, np.float32), grads), engine.cfg)
        gaps = {k: float(
            np.linalg.norm(got[k].reshape(want[k].shape).astype(np.float64)
                           - want[k]) / np.linalg.norm(want[k]))
                for k in want}
        layers = [v for k, v in gaps.items() if ".layers." in k
                  and "norm" not in k]
        norms = [v for k, v in gaps.items() if "layernorm" in k]
        return dict(
            loss=float(loss),
            stats={k: float(v) for k, v in stats.items()},
            shared_matrices=[min(layers), max(layers)],
            shared_norms=[min(norms), max(norms)],
            q_proj_0=gaps["model.layers.0.self_attn.q_proj.weight"],
            gate_weight=gaps["model.early_exit_gate.weight"],
            gate_bias=gaps["model.early_exit_gate.bias"],
            final_norm=gaps["model.norm.weight"],
            head=gaps["lm_head.weight"],
            embedding=gaps["model.embed_tokens.weight"])

    say(phase="objective", tokens=length, reference_loss=ref_loss,
        reference={k: np.asarray(v).tolist() for k, v in parts.items()},
        wrong_loss=wrong_loss,
        engine_float32=through_the_engine("float32"),
        engine_bf16=through_the_engine("bfloat16"),
        secs=round(time.monotonic() - t, 1))


def sft_microbatch(hf, seed):
    """One SFT microbatch at the cell's row length, from the seed."""
    import jax.numpy as jnp
    import numpy as np
    row = min(hf.get("max_position_embeddings", 4096), 4096)
    rng = np.random.default_rng(seed + 5)
    return dict(input_ids=jnp.asarray(rng.integers(
        0, hf["vocab_size"], (1, row)), jnp.int32),
        seg_ids=jnp.ones((1, row), jnp.int32),
        prompt_mask=jnp.arange(row)[None] < row // 8)


def loss_and_gradient(engine, mb):
    """``(loss, the gradient as one float32 vector, statistics)`` of
    one rematerialised SFT microbatch through the engine's own
    objective, compiled anew (what ``ops/moe.py`` holds NOW is what
    runs)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from realhf_tpu.interfaces import sft

    engine.cfg.gradient_checkpointing = True
    objective = engine._objective(sft._make_loss_fn(engine.cfg))
    (loss, stats), grads = jax.jit(jax.value_and_grad(
        objective, has_aux=True))(engine.params, mb)
    flat = jnp.concatenate([g.astype(jnp.float32).reshape(-1)
                            for g in jax.tree.leaves(grads)])
    return float(loss), np.asarray(flat), stats


def slow_path(cell, ckpt, ids, want, fast, seed, divide=4):
    """The share's fallback forced (``share_rows`` cut to a ``divide``th
    so the held pairs pass it): the engine's log-probabilities on the
    fixed batch, and one SFT microbatch's loss and gradient against the
    fast path's."""
    import numpy as np

    from realhf_tpu.ops import moe as moe_ops

    family, hf = cell["family"], cell["hf"]
    real = moe_ops.share_rows
    mb = sft_microbatch(hf, seed)

    def reading(engine):
        got = np.asarray(engine.forward_logprobs(ids, np.ones_like(ids)),
                         np.float32)[:, :-1]
        loss, flat, stats = loss_and_gradient(engine, mb)
        return got, loss, flat, float(stats[moe_ops.SHARE_OVERFLOW_STAT])

    t = time.monotonic()
    _, loss_f, grad_f, over_f = reading(fast)
    moe_ops.share_rows = lambda cfg, t_: max(real(cfg, t_) // divide, 8)
    try:
        got, loss_s, grad_s, over_s = reading(one_chip_engine(ckpt))
    finally:
        moe_ops.share_rows = real
    norm_f, norm_s = np.linalg.norm(grad_f), np.linalg.norm(grad_s)
    say(phase="slow_path", rows_divided_by=divide,
        layers_on_the_slow_path=dict(fast=over_f, forced=over_s),
        engine_bf16_forced=share(got, want),
        loss=dict(fast=loss_f, forced=loss_s),
        grad_norm=dict(fast=float(norm_f), forced=float(norm_s)),
        grad_cosine=float(grad_f @ grad_s / (norm_f * norm_s)),
        grad_relative_l2=float(np.linalg.norm(grad_f - grad_s) / norm_f),
        finite=bool(np.isfinite(grad_s).all()),
        secs=round(time.monotonic() - t, 1), tolerance=family.TOLERANCE)


def uncovered_rows(cell, engine, seed):
    """PR 31's failure, held on the chip (the CPU's zero hides it): one
    SFT microbatch's loss and gradient with the rows of every grouped
    product that lie past its groups POISONED, NaN in the rows the
    layer gathered past the held pairs, in the activations handed to
    the down projection and in the cotangent that comes back, against
    the same step unpoisoned. ``ops/grouped_matmul.py``'s contract
    makes them equal; under ``lax.ragged_dot`` (``kernels`` false: the
    products off the kernels) every number is NaN."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from realhf_tpu.ops import moe as moe_ops

    real = moe_ops.grouped_matmul

    @jax.custom_vjp
    def poisoned_back(y, total):
        return y

    poisoned_back.defvjp(
        lambda y, total: (y, total),
        lambda total, ct: (poison(ct, total), None))

    def poison(a, total):
        return jnp.where(jnp.arange(a.shape[0])[:, None] < total, a,
                         jnp.nan)

    def poisoned(lhs, rhs, sizes):
        total = sizes.sum()
        return poisoned_back(real(poison(lhs, total), rhs, sizes), total)

    t = time.monotonic()
    mb = sft_microbatch(cell["hf"], seed)
    loss, grad, stats = loss_and_gradient(engine, mb)
    moe_ops.grouped_matmul = poisoned
    try:
        loss_p, grad_p, stats_p = loss_and_gradient(engine, mb)
    finally:
        moe_ops.grouped_matmul = real
    norm, norm_p = np.linalg.norm(grad), np.linalg.norm(grad_p)
    held = float(stats[moe_ops.HELD_PAIRS_STAT])
    say(phase="uncovered_rows", kernels=moe_ops.pallas_enabled(),
        held_pairs=held, rows_gathered=float(
            moe_ops.share_rows(engine.cfg, mb["input_ids"].size)
            * engine.cfg.n_moe_layers),
        loss=dict(clean=loss, poisoned=loss_p),
        grad_norm=dict(clean=float(norm), poisoned=float(norm_p)),
        grad_relative_l2=float(np.linalg.norm(grad - grad_p) / norm),
        equal=bool(loss == loss_p and (grad == grad_p).all()),
        finite=bool(np.isfinite(grad_p).all()),
        secs=round(time.monotonic() - t, 1))


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
        "gen", f"experiment_name=chip-check-{FAMILY.replace('_', '-')}",
        f"trial_name=s{seed}",
        f"seed={seed}", "total_train_epochs=1",
        f"dataset.path={prompts}", "dataset.train_bs_n_seqs=128",
        "dataset.max_seqlen=256", f"model.type={FAMILY}",
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
    global FAMILY, AGAINST
    p = argparse.ArgumentParser()
    p.add_argument("family", choices=sorted(FAMILIES))
    p.add_argument("--seeds", type=int, nargs="+", required=True)
    p.add_argument("--gen", action="store_true")
    p.add_argument("--only-ragged", action="store_true")
    p.add_argument("--row-tiles", type=int, nargs="+",
                   help="with --only-ragged: time the kernels at these "
                        "row tiles (a builder's sweep; the file's own "
                        "otherwise)")
    p.add_argument("--no-table", action="store_true",
                   help="the engine's reading alone, no lower precision "
                        "and no wrong equation")
    p.add_argument("--table-only", action="store_true",
                   help="the fixed batch's table for every seed and "
                        "nothing else: no packed row, no decode, no "
                        "long document")
    p.add_argument("--only", nargs="+", default=None,
                   help="after the first seed's engine reading, "
                        "these rows alone: of PUBLISHED_PHASES for a "
                        "family with a published decay (kimi_linear, "
                        "nemotron_h), else of packed, decode, "
                        "slow_path, uncovered_rows, selection, exact, "
                        "gradient, objective")
    p.add_argument("--against", default=None,
                   help="kimi_linear's row scan: another checkout of "
                        "this repo (the parent's, unpacked under a "
                        "directory .gitignore lists); its delta "
                        "kernels run on the same operands, the "
                        "backward's ms of both, and every output and "
                        "gradient compared to the last bit")
    p.add_argument("--rehearse", action="store_true",
                   help="the tests' tiny cell of the family, on any "
                        "device: finds faults, measures nothing")
    args = p.parse_args()
    FAMILY, AGAINST = args.family, args.against
    spec = FAMILIES[FAMILY]

    import jax
    import numpy as np

    from benchmark import generate, run
    from realhf_tpu.base.backend import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    say(phase="start", family=FAMILY, platform=dev.platform,
        kind=dev.device_kind)
    if args.rehearse:
        sub, name = spec["tiny"]
        cell = run.load_cell(os.path.join(
            ROOT, "tests", "benchmark", sub, "manifest.json"), name)
    elif dev.platform != "tpu":
        sys.exit("needs a TPU")
    else:
        cell = run.load_cell(os.path.join(ROOT, "BENCHMARK.json"),
                             spec["cell"])
        if FAMILY == "lfm2_moe" or args.only_ragged:
            ragged(row_tiles=args.row_tiles or (None,))
        if args.only_ragged:
            return
    if args.only == ["scan"]:  # the scan's operands alone: no checkpoint
        SCAN_ROWS[FAMILY](cell)
        return
    work = os.path.join(ROOT, "benchmark", ".cache", f"chip_check_{FAMILY}")
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    try:
        for i, seed in enumerate(args.seeds):
            ckpt, engine, tensors, ids, want = tolerance(
                cell, seed, work, table=not args.no_table)
            if i == 0:
                rng = np.random.default_rng(seed + 2)
                vocab = cell["hf"]["vocab_size"]
                lens = spec["packed_docs"]
                if args.rehearse and lens:  # toy rows: an eighth
                    lens = tuple(n // 8 for n in lens)
            def asked(row):
                return not args.only or row in args.only

            # (a family with a published decay spends --only below)
            if i == 0 and not args.table_only and not (
                    args.only and spec.get("published_decay")):
                if asked("packed"):
                    packed(cell, engine, tensors, list(ids) if lens is None
                           else [rng.integers(0, vocab, n) for n in lens],
                           ckpt)
                if asked("decode") and spec["decode"] is None:
                    decode(cell, engine, ids, want,
                           n_pre=ids.shape[1] * 3 // 4)
                elif asked("decode"):
                    b, n, n_pre = spec["decode"]
                    if args.rehearse:
                        n, n_pre = n // 8, n_pre // 8
                    long = generate.fixed_batch(cell["hf"], seed + 3, b, n)
                    decode(cell, engine, long, cell["family"].logprobs(
                        cell["hf"], tensors, long), n_pre=n_pre)
                if spec.get("slow_path") and asked("slow_path"):
                    slow_path(cell, ckpt, ids, want, engine, seed)
                if spec.get("uncovered_rows") and asked("uncovered_rows"):
                    uncovered_rows(cell, engine, seed)
                if spec.get("exact_doc") and (asked("exact")
                                              or asked("selection")):
                    n = spec["exact_doc"] // (8 if args.rehearse else 1)
                    long = rng.integers(0, vocab, n)
                    if spec.get("selection") and asked("selection"):
                        selection_live(cell, engine, tensors, long)
                    engine = None  # the bf16 weights go before float32's come
                    if asked("exact"):
                        exact(cell, ckpt, tensors, long)
                if spec.get("gradient") and asked("gradient"):
                    engine = None
                    gradient(cell, ckpt, tensors, seed,
                             spec["gradient"] // (4 if args.rehearse
                                                  else 1))
                if spec.get("objective") and asked("objective"):
                    engine = None
                    objective(cell, ckpt, tensors, seed,
                              spec["objective"] // (8 if args.rehearse
                                                    else 1))
            if i == 0 and spec.get("published_decay"):
                engine = None
                under_published_decay(
                    cell, ckpt, tensors, ids, seed,
                    [rng.integers(0, vocab, n) for n in lens],
                    rng.integers(0, vocab, spec["exact_doc"] // (
                        8 if args.rehearse else 1)),
                    args.only or spec.get("published_phases",
                                          PUBLISHED_PHASES),
                    args.rehearse)
            del engine, tensors
        if args.gen:
            gen(cell, ckpt, work, args.seeds[-1])
    finally:
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
