"""Coverage of the PALLAS decode wiring at the transformer level.

The kernel gates key on ``pallas_enabled()`` (a TPU backend, or a
trace made under the TPU interpreter). With
``interpreted_kernels()`` active, ``T.prefill`` +
``T.decode_step`` run the SAME plumbing a TPU runs -- the decode
partitioning chooser and the heads-sharded / KV-sequence-split
shard_map kernel wrappers -- with interpret-mode kernels on the
virtual CPU mesh, instead of CI only ever exercising the XLA
fallbacks. One eager step keeps interpret-mode cost tractable (a full
jitted generate loop under interpret is minutes per case; the deep
scalar-prefetch stacked kernel is covered at kernel level in
tests/ops/test_sharded_kernels.py)."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.experimental.pallas import tpu as pltpu

from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.obs import parts
from realhf_tpu.parallel.mesh import ParallelismConfig, make_mesh

# Capability detect: interpret-mode coverage of the Pallas decode
# wiring needs jax's force_tpu_interpret_mode (newer Pallas API). On
# older jax these tests cannot run the kernel plumbing at all --
# report an attributed skip, not a permanent expected failure; the
# XLA-fallback paths stay covered by tests/engine/test_inflight.py
# and the kernel-level compiled tier in tests/ops.
pytestmark = pytest.mark.skipif(
    not hasattr(pltpu, "force_tpu_interpret_mode"),
    reason="jax.experimental.pallas.tpu lacks force_tpu_interpret_mode "
           "(old Pallas API): interpret-mode kernel plumbing cannot "
           "be exercised on this jax; XLA fallbacks covered elsewhere")


def _cfg(**kw):
    # head_dim 64: the kernel gates require hd >= 64
    return TransformerConfig(**dict(dict(
        n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=256,
        head_dim=64, intermediate_dim=512, vocab_size=128,
        apply_rotary=True, layer_norm_type="rms", mlp_type="llama",
        use_attention_bias=False, use_attn_proj_bias=False,
        use_mlp_bias=False, activation_function="silu",
        compute_dtype="float32"), **kw))


def _mesh(dp, tp):
    par = ParallelismConfig(data_parallel_size=dp,
                            tensor_parallel_size=tp)
    return make_mesh(par, devices=jax.devices("cpu")[:par.world_size])


def _one_decode_step(cfg, params, mesh, uniform_slot=True):
    rng = np.random.default_rng(0)
    b, lp = 4, 8
    ids = jnp.asarray(rng.integers(1, 120, size=(b, lp)), jnp.int32)
    seg = jnp.ones((b, lp), jnp.int32)
    pos = jnp.tile(jnp.arange(lp, dtype=jnp.int32), (b, 1))
    tok = jnp.asarray(rng.integers(1, 120, size=(b,)), jnp.int32)

    # one jitted program, as generation runs it (eagerly, the
    # interpret-mode kernels under shard_map run op by op); traced
    # inside the caller's interpret-mode context and env
    @jax.jit
    def step(params, ids, seg, pos, tok):
        _, cache = T.prefill(cfg, params, ids, seg, pos,
                             total_len=lp + 8)
        if not uniform_slot:
            # the slot engine's streams: each at its own write slot
            cache["length"] = cache["length"] + jnp.arange(b) % 3
        new_hidden, _ = T.decode_step(cfg, params, cache, tok,
                                      cache["length"],
                                      uniform_slot=uniform_slot, mesh=mesh)
        return new_hidden

    return np.asarray(step(params, ids, seg, pos, tok))


@pytest.mark.parametrize("dp,tp,path", [(4, 2, "heads"), (2, 4, "seq")])
def test_decode_step_via_pallas_kernels(dp, tp, path, interpreted_kernels):
    cfg = _cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0))

    ref = _one_decode_step(cfg, params, mesh=None)  # XLA path

    from realhf_tpu.ops.decode_attention import (
        choose_decode_partitioning,
    )
    mesh = _mesh(dp, tp)
    # assert with the REAL cache length the decode below runs with
    # (round_cache_len(8 + 8) = 16), so this cannot silently claim a
    # path the exercised step does not take
    assert choose_decode_partitioning(
        mesh, 4, cfg.n_q_heads, cfg.n_kv_heads, 16) == path

    with interpreted_kernels():
        got = _one_decode_step(cfg, params, mesh=mesh)
    np.testing.assert_allclose(got, ref, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("uniform_slot", [True, False],
                         ids=["uniform_slot", "slot_per_stream"])
@pytest.mark.parametrize("hd,nq,window,mesh", [
    (64, 14, None, None), (128, 8, None, None),
    (64, 14, 4, (2, 2)), (128, 8, 4, (1, 1))],
    ids=["hd64_group7", "hd128_group4", "hd64_group7_window_d2t2",
         "hd128_group4_window_d1t1"])
def test_decode_step_unrolled_reads_the_stack_in_place(
        hd, nq, window, mesh, uniform_slot, monkeypatch,
        interpreted_kernels):
    """The unrolled layer loop (every model of 48 layers or fewer)
    hands the stacked kernel the whole cache and a static layer
    index: equal to the XLA path on a layer sliced out, at the
    benchmark models' head size and query group, for the batch
    generate path's one write slot and the slot engine's slot a
    stream, with and without a sliding window, bare and under
    ``shard_map``."""
    cfg = _cfg(head_dim=hd, n_q_heads=nq, sliding_window=window)
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ref = _one_decode_step(cfg, params, None, uniform_slot)  # XLA path

    calls = []
    from realhf_tpu.ops import decode_attention as D
    kernel = D.flash_decode_attention_stacked
    monkeypatch.setattr(
        D, "flash_decode_attention_stacked",
        lambda *a, **kw: calls.append(a[4]) or kernel(*a, **kw))
    with interpreted_kernels():
        got = _one_decode_step(cfg, params, mesh and _mesh(*mesh),
                               uniform_slot)
    np.testing.assert_allclose(got, ref, atol=2e-4, rtol=2e-4)
    assert len(calls) == cfg.n_layers  # the kernel, once a layer


@pytest.mark.parametrize("dp,tp", [(4, 2), (2, 4)])
def test_decode_step_stacked_scan_path(dp, tp, monkeypatch,
                                       interpreted_kernels):
    """Deep-model wiring: dropping the unroll threshold forces the
    layer lax.scan with a TRACED layer index, so decode_step routes
    through the scalar-prefetch stacked kernel
    (flash_decode_attention_stacked) under both shard_map
    partitionings -- the exact path an 80-layer model decodes with."""
    cfg = _cfg()
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ref = _one_decode_step(cfg, params, mesh=None)  # unrolled XLA path

    monkeypatch.setattr(T, "_DECODE_UNROLL_MAX_LAYERS", 0)
    with interpreted_kernels():
        got = _one_decode_step(cfg, params, mesh=_mesh(dp, tp))
    np.testing.assert_allclose(got, ref, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("dp,tp", [(1, 1), (2, 2)])
def test_generate_span_says_how_the_decode_loop_reads_the_cache(
        dp, tp, interpreted_kernels):
    """Every ``engine:generate`` span carries ``decode_kernel`` and
    ``decode_layer_copies``, read once from the program's compiled
    text: ``stacked`` and 0 where the kernel reads the stack in
    place, ``xla`` on the einsum path."""
    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.obs import tracing
    from realhf_tpu.ops.sampling import GenerationHyperparameters
    from realhf_tpu.parallel.mesh import MeshContext

    cfg = _cfg()
    par = ParallelismConfig(data_parallel_size=dp, tensor_parallel_size=tp)
    ctx = MeshContext(ModelName("default", 0), _mesh(dp, tp), par)
    b, lp = 4, 8
    ids = np.random.default_rng(0).integers(
        1, 120, size=(b, lp)).astype(np.int32)
    seg = np.ones((b, lp), np.int32)
    pos = np.tile(np.arange(lp, dtype=np.int32), (b, 1))
    g = GenerationHyperparameters(max_new_tokens=3, min_new_tokens=3,
                                  greedy=True, force_no_logits_mask=True)

    def run():
        engine = Engine(cfg, ctx, T.init_params(cfg, jax.random.PRNGKey(0)))
        tracing.start()
        tokens = [np.asarray(engine.generate(
            ids, seg, pos, jax.random.PRNGKey(0), g, None, 0).tokens)
            for _ in range(2)]
        spans = tracing.stop().named("engine:generate")
        np.testing.assert_array_equal(*tokens)
        return tokens[0], [s["attributes"] for s in spans]

    ref, xla = run()
    assert [a["decode_kernel"] for a in xla] == ["xla", "xla"]
    assert [a["compiled"] for a in xla] == [True, False]
    with interpreted_kernels():
        got, stacked = run()
    np.testing.assert_array_equal(got, ref)
    for attrs in stacked:
        assert attrs["decode_kernel"] == "stacked"
        assert attrs["decode_layer_copies"] == 0


def test_engine_counts_the_key_blocks_its_flash_kernels_visit(
        interpreted_kernels):
    """A packed batch through ``train_batch`` and ``forward_logprobs``
    with the flash kernels engaged: each ``engine:*`` span carries
    ``flash_block_share`` and ``flash_kv_blocks_total{kind}`` grows by
    ``block_counts`` x layers. An engine whose rows take the XLA path
    counts nothing."""
    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.engine.optim import OptimizerConfig
    from realhf_tpu.obs import tracing
    from realhf_tpu.ops import functional as F
    from realhf_tpu.ops.flash_attention import block_counts
    from realhf_tpu.parallel.mesh import MeshContext

    cfg = _cfg()
    par = ParallelismConfig()
    ctx = MeshContext(ModelName("default", 0), _mesh(1, 1), par)
    rng = np.random.default_rng(0)

    def engine():  # its own weights: the train step donates them
        return Engine(cfg, ctx, T.init_params(cfg, jax.random.PRNGKey(0)),
                      optimizer=OptimizerConfig())

    # rows of 1024 at the default blocks of 256 x 512: four sequences
    # of 256 a row, and one of 512 beside padding
    seg = np.zeros((2, 1, 1024), np.int32)
    seg[0, 0] = np.repeat([2, 4, 1, 3], 256)
    seg[1, 0, :512] = 1
    ids = rng.integers(1, 120, size=seg.shape).astype(np.int32)
    assert block_counts(seg) == (4 + 2, 6 + 6, 0)

    def loss_fn(params, h, mb):
        lp = F.shifted_logprobs_from_hidden(
            cfg, params, h, mb["input_ids"], mb["seg_ids"])
        return -lp.mean(), {}

    def run(engine):
        tracing.start()
        engine.train_batch(
            [dict(input_ids=ids[i], seg_ids=seg[i]) for i in range(2)],
            loss_fn, loss_fn_key="nll")
        engine.forward_logprobs(ids[0], seg[0])
        return tracing.stop()

    xla = run(engine())
    assert not any(k.startswith("flash_kv_blocks_total")
                   for k in xla.counters)
    assert "flash_block_share" not in xla.named(
        "engine:train")[0]["attributes"]

    with interpreted_kernels():
        capture = run(engine())
    [train] = capture.named("engine:train")
    [logprobs] = capture.named("engine:logprobs")
    assert train["attributes"]["flash_block_share"] == 6 / 12
    assert logprobs["attributes"]["flash_block_share"] == 4 / 6
    # sequences of 256 and 512 at blocks of 256 x 512: an edge crosses
    # every visited pair
    assert train["attributes"]["flash_unmasked_share"] == 0
    for kind, blocks in (("visited", 6 + 4), ("causal", 12 + 6),
                         ("unmasked", 0)):
        assert capture.counter("flash_kv_blocks_total", role="default",
                               kind=kind) == blocks * cfg.n_layers


@pytest.mark.parametrize("window,visited,unmasked", [(None, 72, 56),
                                                     (512, 30, 0)])
def test_engine_counts_the_pairs_its_flash_kernels_build_no_mask_for(
        window, visited, unmasked, interpreted_kernels):
    """One document of 4,096 tokens a row, as the benchmark's cells 6,
    7 and 9 pack them: a full layer visits 72 block pairs (256 x 512)
    and builds no mask for the 56 off the diagonal; under a window of
    512 every one of a layer's 30 lies on the diagonal or on the
    window's edge. What the engine counts before a program runs
    (``_count_batch``: no row of 4,096 goes through the interpreter
    here) and what it hands the ``engine:*`` span."""
    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.obs import tracing
    from realhf_tpu.parallel.mesh import MeshContext

    cfg = _cfg(sliding_window=window)
    ctx = MeshContext(ModelName("default", 0), _mesh(1, 1),
                      ParallelismConfig())
    with interpreted_kernels():  # the rows go to the kernels
        engine = Engine(cfg, ctx, T.init_params(cfg, jax.random.PRNGKey(0)))
    tracing.start()
    attrs = engine._count_batch(np.ones((2, 1, 4096), np.int32))
    capture = tracing.stop()
    assert attrs["flash_block_share"] == visited / 72
    assert attrs["flash_unmasked_share"] == unmasked / visited
    for kind, blocks in (("visited", visited), ("causal", 72),
                         ("unmasked", unmasked)):
        assert capture.counter("flash_kv_blocks_total", role="default",
                               kind=kind) == 2 * blocks * cfg.n_layers


@pytest.fixture(scope="module")
def xla_path_spans():
    """(program, dense) -> the spans of the XLA-path engine."""
    return {}


def _train_spans(program, text, monkeypatch, interpreted_kernels,
                 xla_path_spans, cfg=None):
    """The attributes of the two ``engine:<program>`` spans of an
    engine that takes two steps of ``program`` under a capture, with
    its rows on the XLA path (no text is read; ``text`` has no part
    in that run, so it is made once a program and model) and on the
    flash kernels (ONE read of the program's compiled text, which is
    ``text``: a text of the chip's, since the interpreter's CPU
    program holds no custom call and no product under a scope's name;
    ``tests/ops/test_chip_compile.py`` reads the real ones)."""
    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.engine.optim import OptimizerConfig
    from realhf_tpu.obs import tracing
    from realhf_tpu.ops import functional as F
    from realhf_tpu.parallel.mesh import MeshContext

    dense = cfg is None
    cfg = cfg or _cfg()
    ctx = MeshContext(ModelName("default", 0), _mesh(1, 1),
                      ParallelismConfig())
    seg = np.ones((2, 1, 512), np.int32)
    ids = np.random.default_rng(0).integers(
        1, 120, size=seg.shape).astype(np.int32)
    mbs = [dict(input_ids=ids[i], seg_ids=seg[i]) for i in range(2)]
    read = []

    class Compiled:  # what Engine._compiled returns, as far as read
        as_text = staticmethod(lambda: text)
        memory_analysis = staticmethod(lambda: None)

    def compiled(self, name, call=None):
        read.append(name)
        return Compiled

    monkeypatch.setattr(Engine, "_compiled", compiled)

    def loss_fn(params, h, mb):
        lp = F.shifted_logprobs_from_hidden(
            cfg, params, h, mb["input_ids"], mb["seg_ids"])
        return -lp.mean(), {}

    def run():
        engine = Engine(cfg, ctx, T.init_params(cfg, jax.random.PRNGKey(0)),
                        optimizer=OptimizerConfig())
        tracing.start()
        for _ in range(2):
            if program == "train":
                engine.train_batch(mbs, loss_fn, loss_fn_key="nll")
            else:
                engine.train_minibatches([mbs[:1], mbs[1:]], loss_fn,
                                         loss_fn_key="nll")
        return [s["attributes"]
                for s in tracing.stop().named(f"engine:{program}")]

    if (program, dense) not in xla_path_spans:
        xla_path_spans[program, dense] = run()
        assert read == []
    off = xla_path_spans[program, dense]
    with interpreted_kernels():
        on = run()
    assert read == [program]
    return off, on


@pytest.mark.parametrize("program,ratio", [("train", 1.0), ("train", 2.0),
                                           ("train_seq", 1.0)])
def test_train_span_says_how_often_the_forward_kernel_runs(
        program, ratio, monkeypatch, interpreted_kernels, xla_path_spans):
    """Every ``engine:train`` / ``engine:train_seq`` span of a program
    whose rows go to the flash kernels carries ``flash_fwd_per_bwd``,
    read ONCE from the program's compiled text after its first call.
    An engine whose rows take the XLA path reads no text."""
    call = "custom-call(%q), custom_call_target=\"tpu_custom_call\"\n"
    text = "%body (q: f32[8]) -> f32[8] {\n" \
        + "".join(f"  %flash_fwd.{i} = f32[8]{{0}} {call}"
                  for i in range(int(ratio))) \
        + f"  ROOT %flash_bwd_dq.1 = f32[8]{{0}} {call}}}\n"
    off, on = _train_spans(program, text, monkeypatch,
                           interpreted_kernels, xla_path_spans)
    assert all("flash_fwd_per_bwd" not in attrs for attrs in off)
    assert [attrs["flash_fwd_per_bwd"] for attrs in on] == [ratio] * 2


@pytest.mark.parametrize("program,products", [("train", 0), ("train", 4),
                                              ("train_seq", 0),
                                              ("train_seq", 4)])
def test_train_span_counts_the_projections_run_a_second_time(
        program, products, monkeypatch, interpreted_kernels,
        xla_path_spans):
    """The sibling of ``flash_fwd_per_bwd`` from the same ONE read of
    the text: ``attn_proj_remat_products``, the products under part
    ``attn_proj`` in the rematerialised forward
    (``obs.parts.count_products``), on every ``engine:train`` /
    ``engine:train_seq`` span of a program whose rows go to the
    kernels; 0 is said too (the blocks keep what the projections
    made), and a text without a backward kernel has the count and no
    ratio."""
    scope = "jit(train_step)/jit(main)/forward_backward/transpose(jvp())" \
        "/checkpoint/rematted_computation"

    def product(i, part):
        return (f"  %convolution.{i} = bf16[8,8]{{1,0}} convolution(%a, %b),"
                f" dim_labels=bf_io->bf, metadata={{op_name=\"{scope}/"
                f"{part}/dot_general\"}}\n")

    text = "%body (q: bf16[8,8]) -> bf16[8,8] {\n" \
        + "".join(product(i, "attn_proj") for i in range(products)) \
        + product(9, "mlp") + "}\n"
    off, on = _train_spans(program, text, monkeypatch,
                           interpreted_kernels, xla_path_spans)
    assert all("attn_proj_remat_products" not in attrs for attrs in off)
    assert [attrs["attn_proj_remat_products"] for attrs in on] \
        == [products] * 2
    assert all("flash_fwd_per_bwd" not in attrs for attrs in on)


@pytest.mark.parametrize("program,loss,products", [
    ("train", "sft", 0), ("train", "grpo", 1), ("train_seq", "sft", 0)])
def test_train_span_counts_the_heads_logits_made_a_second_time(
        program, loss, products, monkeypatch, interpreted_kernels,
        xla_path_spans):
    """``head_remat_products`` beside it, from the same read: the
    products under part ``vocab_head`` in the rematerialised forward.
    The ``op_name``s are the chip's compiled texts' own
    (``tests/ops/test_chip_compile.py`` reads them off the real
    programs): a loss over ``shifted_logprobs_from_hidden`` (GRPO's)
    makes a chunk's logits again in its backward, 1, the loop body's;
    the SFT head (``weighted_logprob_sum``) runs its two gradient
    products in the chunk that has the logits, scope ``gradient``: 0,
    which is said too."""
    fb = "jit(train_step)/jit(main)/forward_backward"
    body = "while/body/closed_call"
    names = {
        "sft": [f"{fb}/jvp(vocab_head)/{body}/slh,hv->slv/dot_general",
                f"{fb}/jvp(vocab_head)/{body}/gradient/dot_general",
                f"{fb}/jvp(vocab_head)/{body}/gradient/dot_general"],
        "grpo": [f"{fb}/jvp(vocab_head)/{body}/slh,hv->slv/dot_general",
                 f"{fb}/transpose(jvp(vocab_head))/{body}/checkpoint/"
                 "rematted_computation/slh,hv->slv/dot_general",
                 f"{fb}/transpose(jvp(vocab_head))/{body}/checkpoint/"
                 "slh,hv->slv/dot_general",
                 f"{fb}/transpose(jvp(vocab_head))/{body}/checkpoint/"
                 "slh,hv->slv/dot_general"]}[loss]
    text = "%body (q: f32[8,8]) -> f32[8,8] {\n" + "".join(
        f"  %convolution.{i} = f32[8,8]{{1,0}} convolution(%a, %b), "
        f"dim_labels=bf_io->bf, metadata={{op_name=\"{name}\"}}\n"
        for i, name in enumerate(names)) + "}\n"
    assert [parts.classify(name)[:2] for name in names] == [
        ("vocab_head", "fwd")] + {
            "sft": [("vocab_head", "bwd")] * 2,
            "grpo": [("vocab_head", "remat")] + [("vocab_head", "bwd")] * 2
        }[loss]
    off, on = _train_spans(program, text, monkeypatch,
                           interpreted_kernels, xla_path_spans)
    assert all("head_remat_products" not in attrs for attrs in off)
    assert [attrs["head_remat_products"] for attrs in on] == [products] * 2


def test_train_span_says_which_grouped_matmul_the_experts_run(
        monkeypatch, interpreted_kernels, xla_path_spans):
    """Every ``engine:train`` span of a sparse model in the ragged mode
    whose program's text has been read carries ``moe_products`` (``gmm``
    where the program holds ``ops/grouped_matmul.py``'s kernels, else
    ``ragged_dot``), ``moe_gmm_calls`` and ``moe_ragged_dot_calls``
    (``ops.moe.grouped_product_calls``), from the ONE read that gives
    ``flash_fwd_per_bwd``; the step itself runs the kernels in
    interpret mode through the engine (the layer's own tests hold
    their values)."""
    from realhf_tpu.models.config import MoEConfig

    call = "custom-call(%q), custom_call_target=\"tpu_custom_call\"\n"
    names = ["jvp_gmm_", "gmm", "transpose_jvp_gmm_t__",
             "transpose_jvp_tgmm__", "flash_fwd", "flash_bwd_dq"]
    text = "%body (q: f32[8]) -> f32[8] {\n" + "".join(
        f"  %{name}.{i} = f32[8]{{0}} {call}"
        for i, name in enumerate(names)) + "}\n"
    cfg = _cfg(mlp_type="moe", moe=MoEConfig(num_experts=4, top_k=2,
                                             routing_type="none"))
    off, on = _train_spans("train", text, monkeypatch,
                           interpreted_kernels, xla_path_spans, cfg)
    assert all("moe_products" not in attrs for attrs in off)
    for attrs in on:
        assert attrs["moe_dispatch"] == "ragged"
        assert (attrs["moe_products"], attrs["moe_gmm_calls"],
                attrs["moe_ragged_dot_calls"]) == ("gmm", 4, 0)
        assert attrs["flash_fwd_per_bwd"] == 1.0


@pytest.mark.parametrize("dp,tp,sharded", [(1, 1, False), (2, 1, True),
                                           (1, 2, True)])
def test_over_a_mesh_the_grouped_products_stay_ragged_dot(dp, tp, sharded):
    """The engine's word to the experts' layer (``moe_constraint``):
    nothing where the stacks lie whole on one device (the kernels run
    wherever Pallas is enabled), ``SHARDED_STACKS`` over a data- or
    tensor-parallel mesh (a bare ``pallas_call`` has no partitioning
    rule; ``lax.ragged_dot`` has GSPMD's)."""
    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.models.config import MoEConfig
    from realhf_tpu.ops import moe as moe_ops
    from realhf_tpu.parallel.mesh import MeshContext

    cfg = _cfg(mlp_type="moe", moe=MoEConfig(num_experts=4, top_k=2,
                                             routing_type="none"))
    par = ParallelismConfig(data_parallel_size=dp, tensor_parallel_size=tp)
    engine = Engine(cfg, MeshContext(ModelName("default", 0),
                                     _mesh(dp, tp), par),
                    T.init_params(cfg, jax.random.PRNGKey(0)))
    assert engine._moe_constraint == (
        moe_ops.SHARDED_STACKS if sharded else None)
