"""The main path's Pallas kernels, asked of the TPU v5e compiler without
a chip (section 2 of /opt/skills/guides/on-chip-measurement/SKILL.md).

Interpret mode cannot show a slice that misses the tiling or a kernel
that wants more VMEM than it may use; the chip's compiler can, and it is
installed here. Shapes are Qwen2.5-0.5B's (14 query and 2 key/value
heads of 64, bf16) at the lengths the benchmark's first cell runs:
packed train and inference rows of 4096 tokens, a generation prefill of
128 prompts of 256, and a decode cache of 512 slots.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU's library, and
under pytest-xdist every worker imports this file. All such compiles
live in this one file, run in the test's own process, and go around the
persistent compile cache (a described-device executable is written to
it but cannot be read back without a chip).
"""

import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

from realhf_tpu.ops.attention import make_sharded_attention, packed_attention
from realhf_tpu.ops.decode_attention import (
    KERNEL_NAME,
    decode_layer_copies,
    flash_decode_attention_stacked,
)
from realhf_tpu.ops.flash_attention import (FLASH_MAX_LEN,
                                            FLASH_STREAM_MAX_LEN,
                                            flash_attention)
from realhf_tpu.ops.hlo_text import device_instructions

NQ, NKV, HD = 14, 2, 64       # Qwen2.5-0.5B attention widths
N_LAYERS = 24
ROW_LEN = 4096                # packed train / inference row of the smoke run
GEN_BATCH, PROMPT_LEN, CACHE_LEN = 128, 256, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or its lock held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(fn, *args):
    """Compile for the described chip; the Mosaic kernel must be in
    the program."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _qkv(sharding, b, l, nq=NQ, nkv=NKV, hd=HD):
    """Abstract q, k, v, seg_ids of one packed batch on ``sharding``."""
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    return (sds((b, l, nq, hd), jnp.bfloat16),
            sds((b, l, nkv, hd), jnp.bfloat16),
            sds((b, l, nkv, hd), jnp.bfloat16),
            sds((b, l), jnp.int32))


def _flash_grads(q, k, v, seg):
    def loss(q, k, v):
        return flash_attention(q, k, v, seg).astype(jnp.float32).sum()
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


@pytest.mark.parametrize("b,l", [(1, ROW_LEN), (GEN_BATCH, PROMPT_LEN)],
                         ids=["train_row", "gen_prefill"])
def test_flash_forward_compiles(one_chip, b, l):
    _compile(flash_attention, *_qkv(one_chip, b, l))


def test_flash_backward_compiles(one_chip):
    _compile(_flash_grads, *_qkv(one_chip, 1, ROW_LEN))


@pytest.mark.parametrize("nq,nkv,hd", [(NQ, NKV, HD), (32, 8, 128),
                                       (32, 8, 64)],
                         ids=["hd64", "hd128", "lfm2_32x8x64"])
def test_flash_compiles_at_its_stated_limit(one_chip, nq, nkv, hd):
    """FLASH_MAX_LEN is a promise about the compiler: forward and
    backward both fit at it, for both head sizes the families use
    (and LFM2-24B-A2B's 32 query and 8 key/value heads of 64, which
    the benchmark's fifth cell packs to rows of that length)."""
    args = _qkv(one_chip, 1, FLASH_MAX_LEN, nq, nkv, hd)
    _compile(flash_attention, *args)
    _compile(_flash_grads, *args)


@pytest.mark.parametrize("nq,window", [(64, 512), (48, None)],
                         ids=["window_64x8x128", "full_48x8x128"])
def test_flash_compiles_at_lagunas_two_kinds_of_layer(one_chip, nq,
                                                      window):
    """The sixth cell's rows of 4096 at both of Laguna-XS.2's layer
    kinds: the WINDOWED forward and backward at 64 query and 8
    key/value heads of 128 (a window takes nothing off VMEM: K and V
    stay whole a head), and the full ones at 48."""
    args = _qkv(one_chip, 1, FLASH_MAX_LEN, nq, 8, 128)

    def fwd(q, k, v, seg):
        return flash_attention(q, k, v, seg, sliding_window=window)

    def grads(q, k, v, seg):
        return jax.grad(lambda q, k, v: fwd(q, k, v, seg).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    _compile(fwd, *args)
    text = _compile(grads, *args).as_text()
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert kernel in text


def _model(one_chip, config_name, family):
    """``(cfg, params, sds, attn)``: a benchmark configuration's WHOLE
    model at published widths, bf16, rematerialised as every
    experiment runs it, its parameters abstract on the described chip,
    and the flash kernels as its attention."""
    import json
    import os

    from benchmark import generate, run
    from realhf_tpu.models import hf as hf_models
    from realhf_tpu.models import transformer as T

    with open(os.path.join(run.ROOT, "BENCHMARK.json")) as f:
        config = next(c for c in json.load(f)["configs"]
                      if c["name"] == config_name)
    hf, _ = generate.load_config(os.path.join(run.ROOT, config["file"]))
    cfg = hf_models.config_from_hf(family, hf)
    cfg.param_dtype = cfg.compute_dtype = "bfloat16"
    cfg.gradient_checkpointing = True

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    params = jax.tree.map(
        lambda a: sds(a.shape, jnp.bfloat16),
        jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0))))

    def attn(q, k, v, seg, causal=True, scale=None, sliding_window=None,
             **select):
        return flash_attention(q, k, v, seg, causal=causal, scale=scale,
                               sliding_window=sliding_window, **select)

    return cfg, params, sds, attn


def _as_on_a_tpu(fn):
    """``fn`` traced with ``pallas_enabled()`` true where the experts'
    layer and the delta and ssm layers' scans ask: the backend here is
    the CPU, and the chip's program holds ``ops/grouped_matmul.py``'s,
    ``ops/delta_rule.py``'s and ``ops/ssm_scan.py``'s kernels."""
    from unittest import mock

    from realhf_tpu.ops import delta_rule, ssm_scan
    from realhf_tpu.ops import moe as moe_ops

    def traced(*args):
        with mock.patch.object(moe_ops, "pallas_enabled", lambda: True), \
                mock.patch.object(delta_rule, "pallas_enabled",
                                  lambda: True), \
                mock.patch.object(ssm_scan, "pallas_enabled", lambda: True):
            return fn(*args)
    return traced


def _sft_microbatch(one_chip, config_name, family, kernels=True):
    """``(step, params, mb)``: one microbatch's SFT forward and
    backward of a benchmark configuration's WHOLE model at published
    widths, a row of 4096, bf16, rematerialised as every experiment
    runs it, with abstract arguments on the described chip.
    ``kernels``: the experts' grouped products as the chip runs them
    (``ops/grouped_matmul.py``), else as ``lax.ragged_dot``."""
    from realhf_tpu.interfaces import sft
    from realhf_tpu.models import transformer as T
    from realhf_tpu.ops import moe as moe_ops

    cfg, params, sds, attn = _model(one_chip, config_name, family)
    mb = dict(input_ids=sds((1, FLASH_MAX_LEN), jnp.int32),
              seg_ids=sds((1, FLASH_MAX_LEN), jnp.int32),
              prompt_mask=sds((1, FLASH_MAX_LEN), jnp.bool_))
    loss_fn = sft._make_loss_fn(cfg)
    sparse = cfg.n_moe_layers > 0

    def objective(p, mb):
        h, _, aux = T.forward(cfg, p, mb["input_ids"], mb["seg_ids"],
                              return_aux=True, attention_fn=attn)
        aux = aux if sparse else {}
        loss, stats = loss_fn(p, h, mb)
        return loss + moe_ops.aux_loss(aux), {**stats, **aux}

    def step(p, mb):
        return jax.value_and_grad(objective, has_aux=True)(p, mb)

    return (_as_on_a_tpu(step) if kernels else step), params, mb


def _sft_train_step(one_chip, config_name, family, microbatches,
                    row_len=FLASH_MAX_LEN):
    """``(step, params, optimizer state, microbatches, weights)``: the
    engine's WHOLE train step (``Engine._train_step_body``: the scan
    over ``microbatches`` rows of ``row_len`` (4096), the float32
    accumulation, Adam on float32 master weights) of a benchmark
    configuration, abstract on the described chip. The engine is bare:
    it holds what the step body reads and no array."""
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.engine.optim import OptimizerConfig, make_optimizer
    from realhf_tpu.interfaces import sft

    cfg, params, sds, attn = _model(one_chip, config_name, family)
    engine = object.__new__(Engine)
    engine.cfg, engine._attention_fn, engine.mesh = cfg, attn, None
    engine._pipeline_ctx = engine._constrain = None
    engine._moe_constraint = None
    engine._grad_shardings = engine._opt_shardings = None
    engine._tx = make_optimizer(OptimizerConfig(), 100, master_weights=True)
    opt_state = jax.tree.map(lambda a: sds(a.shape, a.dtype),
                             jax.eval_shape(engine._tx.init, params))
    rows = (microbatches, 1, row_len)
    mbs = dict(input_ids=sds(rows, jnp.int32), seg_ids=sds(rows, jnp.int32),
               prompt_mask=sds(rows, jnp.bool_))
    body = engine._train_step_body(sft._make_loss_fn(cfg))
    return (_as_on_a_tpu(body), params, opt_state, mbs,
            sds((microbatches,), jnp.float32))


@functools.lru_cache(maxsize=None)
def _compiled_microbatch(one_chip, config_name, family):
    """Compiled once a file: Laguna's takes two minutes here, and two
    tests read it."""
    return _compile(*_sft_microbatch(one_chip, config_name, family))


@pytest.mark.slow
@pytest.mark.parametrize("limit", [True, False],
                         ids=["as_it_is", "without_vmem_limit"])
def test_lagunas_whole_microbatch_compiles(one_chip, monkeypatch, limit):
    """The sixth cell's train program as the chip compiles it: one
    microbatch's SFT forward and backward of ALL FIVE of
    ``laguna-xs.2-l5-ep16``'s layers at published widths, a row of
    4096, bf16, rematerialised. The kernels alone (the test above) and
    any stack of one or two layers fit the default 16 MiB of scoped
    VMEM; inside the whole program the dkv pass at (48, 8, 128) asks
    for 16.17 MB of it, because what XLA schedules around a kernel
    takes from the same 16 MiB, and PR 33's first chip call died of
    that. ``flash_attention._vmem_limit`` gives such a call its
    ``vmem_limit_bytes``. ``without_vmem_limit`` takes it away from
    the program as it was then, whose backward recomputes q, k and v
    (the policy keeps the kernel's residuals alone): the producers'
    fusions feed the kernel, and that program must be refused for
    VMEM (with the experts' products as they were then too,
    ``lax.ragged_dot``: beside ``ops/grouped_matmul.py``'s kernels
    even that program compiles, so near the line does it stand).
    Since the blocks keep q (``PROJECTION_RESIDUALS``) the
    kernel reads a kept array and the program as it IS compiles
    without the limit too, by 1% of the 16 MiB: what XLA schedules
    around the kernel decides, so the limit stays. The day the
    recomputing program compiles without it, ``_vmem_limit`` holds
    nothing up and can go."""
    from realhf_tpu.models import transformer as T
    from realhf_tpu.ops import flash_attention as fa

    if not limit:
        monkeypatch.setattr(fa, "_vmem_limit", lambda *a: None)
        monkeypatch.setattr(T, "KEPT_RESIDUALS", fa.RESIDUAL_NAMES)
        T._remat_policy.cache_clear()
        try:
            with pytest.raises(Exception, match="(?i)vmem"):
                _compile(*_sft_microbatch(
                    one_chip, "laguna-xs.2-l5-ep16", "laguna",
                    kernels=False))
        finally:
            T._remat_policy.cache_clear()
        return
    text = _compiled_microbatch(one_chip, "laguna-xs.2-l5-ep16",
                                "laguna").as_text()
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert kernel in text


@pytest.mark.parametrize("config,family,calls,gate,q_products,gigabytes", [
    # (Laguna's whole microbatch, two minutes to compile: with the
    # test above that shares it)
    pytest.param("laguna-xs.2-l5-ep16", "laguna", 5, 5, 10, 3.15,
                 marks=pytest.mark.slow),
    ("qwen2.5-0.5b", "qwen2", 1, 0, None, None),
], ids=["laguna_unrolled_5", "qwen_scanned_24"])
def test_rematerialised_stack_runs_the_forward_kernel_once_a_layer(
        one_chip, config, family, calls, gate, q_products, gigabytes):
    """The blocks keep the flash kernel's output and log-sum-exp
    (``models/transformer.py:_remat``), so the compiled backward holds
    as many ``flash_fwd`` custom calls as ``flash_bwd_dq`` ones: five
    and five in Laguna's unrolled stack, one and one in the loop
    bodies of Qwen2.5-0.5B's scanned 24 layers. Before the residuals
    were kept each held twice as many ``flash_fwd``.

    They keep what the two wide attention projections made too
    (``PROJECTION_RESIDUALS``), so the rematerialised forward holds
    neither ``x @ wq`` nor ``attn @ wo`` (``obs.parts.count_products``,
    the engine's ``attn_proj_remat_products``): of a layer's 4
    products 2 are left, k's and v's, in Qwen's loop body (where the
    forward holds 4 and the backward 8), and in Laguna's five layers
    those 10 and the head gates' small ``[4096,2048] x [2048,64 or
    48]`` (15 of 25 before; 25 forward, 50 backward); Laguna's program
    holds 10 products that make a ``[4096,64 or 48,128]``, the
    forward's q and the backward's gradient of the attention output
    (15 before: 175 -> 165 products in the text, 11.148 -> 9.911
    TFLOP as XLA counts them). What Laguna's program keeps for both
    (the kernel's 0.31 GB a microbatch and the projections' 0.39:
    arguments + temporaries 2.960 -> 3.047 GB as the compiler counts
    them; Qwen's scan stacks 24 layers' residuals, 3.277 -> 3.860 GB)
    stays within 0.1 GB of that."""
    import re

    from realhf_tpu.obs import parts
    from realhf_tpu.ops.flash_attention import flash_fwd_per_bwd
    from realhf_tpu.ops.hlo_text import device_instructions

    compiled = _compiled_microbatch(one_chip, config, family)
    text = compiled.as_text()
    # (under jax.grad an unrolled layer's forward is jvp_flash_fwd_.N)
    names = [name for name, _, opcode in device_instructions(text)
             if opcode == "custom-call"]
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert sum(kernel in name for name in names) == calls, kernel
    assert flash_fwd_per_bwd(text) == 1.0
    layers = calls  # a scanned stack holds its layers' one body
    products = {p: parts.count_products(text, parts.ATTN_PROJ, p)
                for p in (parts.FWD, parts.REMAT, parts.BWD)}
    assert products == {parts.FWD: 4 * layers + gate,
                        parts.REMAT: 2 * layers + gate,
                        parts.BWD: 2 * (4 * layers + gate)}
    if q_products is not None:
        assert len(re.findall(
            r"= bf16\[4096,(?:64|48),128\]\S* (?:convolution|dot)\(",
            text)) == q_products
    if gigabytes is not None:
        memory = compiled.memory_analysis()
        assert (memory.argument_size_in_bytes
                + memory.temp_size_in_bytes) < gigabytes * 1e9


def _qkv_two_widths(sharding, l, heads, hd, hv):
    q, k, _, seg = _qkv(sharding, 1, l, heads, heads, hd)
    return q, k, _qkv(sharding, 1, l, heads, heads, hv)[2], seg


def test_flash_compiles_at_moonlights_two_widths(one_chip):
    """The seventh cell's rows of 4096 at latent attention's widths: 16
    heads, keys 192 wide (128 + 64 rotary: no multiple of the 128
    lanes, so a block takes the whole last axis and VMEM holds it over
    256), values and output 128 wide, forward and backward, at the
    kernels' stated limit."""
    args = _qkv_two_widths(one_chip, FLASH_MAX_LEN, 16, 192, 128)
    _compile(flash_attention, *args)
    _compile(_flash_grads, *args)


@pytest.mark.slow
def test_moonlights_whole_microbatch_compiles(one_chip):
    """The seventh cell's train program as the chip compiles it: one
    microbatch's SFT forward and backward of ALL FIVE of
    ``moonlight-16b-a3b-l5-ep8``'s latent layers at published widths,
    a row of 4096, bf16, rematerialised. The three kernels once a
    layer (the blocks keep ``flash_out`` at the value's width and q at
    the key's); no product of ``attn_proj`` itself is run again, and
    what makes k and v from the latent (sub-part ``attn_proj/latent``:
    the compression and the expansion) is, twice a layer; the experts'
    grouped products as ``ops/grouped_matmul.py``'s kernels; the
    compiler's count of the microbatch's memory."""
    from realhf_tpu.obs import parts
    from realhf_tpu.ops import moe as moe_ops
    from realhf_tpu.ops.flash_attention import flash_fwd_per_bwd
    from realhf_tpu.ops.hlo_text import device_instructions

    compiled = _compiled_microbatch(one_chip, "moonlight-16b-a3b-l5-ep8",
                                    "deepseek_v3")
    text = compiled.as_text()
    names = [name for name, _, opcode in device_instructions(text)
             if opcode == "custom-call"]
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert sum(kernel in name for name in names) == 5, kernel
    assert flash_fwd_per_bwd(text) == 1.0
    latent = f"{parts.ATTN_PROJ}/{parts.LATENT}"
    assert {p: parts.count_products(text, parts.ATTN_PROJ, p)
            for p in (parts.FWD, parts.REMAT)} == {
        parts.FWD: 2 * 5, parts.REMAT: 0}
    assert {p: parts.count_products(text, latent, p)
            for p in (parts.FWD, parts.REMAT)} == {
        parts.FWD: 2 * 5, parts.REMAT: 2 * 5}
    # the experts' grouped products are the repo's kernels: twelve a
    # sparse layer (3 forward, 3 rematerialised, 3 + 3 backward) in
    # each of the share's two branches, none of the compiler's own
    assert moe_ops.grouped_product_calls(text) == dict(
        moe_products="gmm", moe_gmm_calls=4 * 2 * 12,
        moe_ragged_dot_calls=0)
    # 1.14 GB of bf16 weights and 0.82 of temporaries: 3.42 GB while
    # the share's slow branch took all 24,576 sorted rows at once
    # (``ops/moe.py:_ragged_share``), 1.96 since it takes them a
    # rematerialised chunk at a time (the whole step 14.74 -> 13.63 GB)
    memory = compiled.memory_analysis()
    assert 1.8e9 < (memory.argument_size_in_bytes
                    + memory.temp_size_in_bytes) < 2.2e9


@pytest.mark.parametrize("rows,hidden,width,groups", [
    (16384, 2048, 1024, 64), (4096, 2048, 1536, 8), (4096, 2048, 512, 16),
    (6144, 2048, 1408, 8)], ids=["olmoe", "lfm2", "laguna", "moonlight"])
@pytest.mark.parametrize("down", [False, True], ids=["up", "down"])
def test_grouped_matmul_compiles_at_the_cells_shapes(
        one_chip, rows, hidden, width, groups, down):
    """``ops/grouped_matmul.py``'s three kernels (the product, the
    rows' gradient with the weights read transposed, the weights'
    gradient) at the sorted rows, widths and groups of the four sparse
    cells' grouped products, the up / gate projection and the down
    projection, bf16: a group's weights whole in VMEM (2048 x 1536 in
    bf16 twice: 12.6 MB) and ``tgmm``'s float32 accumulator beside
    them ask for more than the default scoped limit, which
    ``vmem_limit_bytes`` gives."""
    from realhf_tpu.ops.grouped_matmul import GMM, GMM_T, TGMM
    from realhf_tpu.ops.grouped_matmul import grouped_matmul
    from realhf_tpu.ops.hlo_text import device_instructions

    k, n = (width, hidden) if down else (hidden, width)

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    def grads(x, w, sizes):
        return jax.value_and_grad(
            lambda x, w: grouped_matmul(x, w, sizes).astype(
                jnp.float32).sum(), argnums=(0, 1))(x, w)

    text = _compile(grads, sds((rows, k), jnp.bfloat16),
                    sds((groups, k, n), jnp.bfloat16),
                    sds((groups,), jnp.int32)).as_text()
    # (under a gradient: jvp_gmm_.N, transpose_jvp_gmm_t_.N, ..._tgmm.N)
    names = [name for name, _, opcode in device_instructions(text)
             if opcode == "custom-call" and GMM in name]
    assert len(names) == 3
    assert sum(GMM_T in name for name in names) == 1
    assert sum(TGMM in name for name in names) == 1


def test_grouped_matmul_compiles_in_float32_at_moonlights_shape(one_chip):
    """``scripts/chip_check.py deepseek_v3``'s row ``exact`` runs the
    float32 engine through the compiled kernels: the forward product
    with float32 operands (a group's weights 11.5 MB, twice)."""
    from realhf_tpu.ops.grouped_matmul import grouped_matmul

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    _compile(grouped_matmul, sds((6144, 2048), jnp.float32),
             sds((8, 2048, 1408), jnp.float32), sds((8,), jnp.int32))


@pytest.mark.slow
def test_moonlights_whole_train_step_compiles(one_chip):
    """The seventh cell's WHOLE train step for the described chip: 32
    microbatches of one row of 4096 scanned, accumulated in float32,
    Adam on float32 masters, the parameters and optimizer state
    donated. One microbatch's program (the test above) is not enough,
    a third time: with ``gmm_t``'s blocks at 15.1 MB the microbatch
    compiled and THIS program was refused (16.09 MB of scoped VMEM
    asked of the default 16 MiB: what XLA schedules around a kernel
    differs by program), on the chip and here alike
    (``ops/grouped_matmul.py:_params``; PERF.md, PR 38). And the
    compiler's count of the step's memory, which the chip's own count
    (``engine.program_gb``) follows to four digits: 13.35 GB, of the
    13.9 a program is held to (13.64 with ``lax.ragged_dot``)."""
    from realhf_tpu.ops import moe as moe_ops

    step, *args = _sft_train_step(one_chip, "moonlight-16b-a3b-l5-ep8",
                                  "deepseek_v3", 32)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    assert moe_ops.grouped_product_calls(compiled.as_text()) == dict(
        moe_products="gmm", moe_gmm_calls=4 * 2 * 12,
        moe_ragged_dot_calls=0)
    # the SFT head makes a chunk's logits once (head_remat_products)
    assert _head_products(compiled.as_text())["remat"] == 0
    memory = compiled.memory_analysis()
    print("moonlight whole step GB", (memory.argument_size_in_bytes
                                      + memory.temp_size_in_bytes) / 1e9)
    assert 13.0e9 < (memory.argument_size_in_bytes
                     + memory.temp_size_in_bytes) < 13.6e9


@pytest.mark.parametrize("dtype,precision", [
    (jnp.bfloat16, "default"), (jnp.float32, "highest")],
    ids=["bf16_one_pass", "float32_highest"])
@pytest.mark.parametrize("through", ["prepare_inside", "prepare_before"])
def test_delta_scan_kernels_compile_at_the_cells_shape(one_chip, dtype,
                                                       precision, through):
    """``ops/delta_rule.py``'s two kernels at the eighth cell's shape
    (one row of 2048, 32 heads of 128), forward and gradient: as the
    bf16 engine runs them (operands bf16, products in one pass) and as
    ``chip_check.py kimi_linear``'s float32 rows do (operands float32,
    every product at the highest precision: the caller's precision
    must not reach the kernels' own products of bf16 pieces, which
    Mosaic refuses: "Bad lhs type", PERF.md, PR 42), with a layer's
    ``Prepare`` applied inside the kernels and with any other callable
    applied before them."""
    from realhf_tpu.ops import delta_rule as D
    b, l, h, d = 1, 2048, 32, 128

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    prepare = D.Prepare(rate=-jnp.ones((h,)), dt_bias=jnp.zeros((h, d)),
                        scale=d ** -0.5, eps=1e-6)
    if through == "prepare_before":
        prepare = lambda q, k, f, made=prepare: made(q, k, f)

    def loss(q, k, v, f, beta, seg):
        o, last = D._by_kernels(q, k, v, f, beta, seg, prepare)
        return o.astype(jnp.float32).sum() + last.sum()

    args = (*(sds((b, l, h, d), dtype) for _ in range(4)),
            sds((b, l, h), jnp.float32), sds((b, l), jnp.int32))
    with jax.default_matmul_precision(precision):
        text = _compile(jax.grad(loss, argnums=(0, 1, 2, 3, 4)),
                        *args).as_text()
    assert D.scan_kernel_calls(text) == 2
    assert D.scan_handed(text) == len(D.RESIDUAL_NAMES) == 2


def test_a_rematerialised_delta_block_runs_each_kernel_once(one_chip):
    """Two blocks rematerialised under the policy every experiment's
    blocks have (``models/transformer.py:_remat_policy``), each the
    delta scan between two products: the policy keeps the scan's
    output and BOTH arrays its forward kernel hands its backward one
    (every chunk's start state; its masked pairs and inverse), so the
    program holds one forward and one backward kernel a block, none
    for the rematerialised pass, and every backward kernel takes both
    (``scan_handed``: with the pairs named and not kept the block
    would run a third kernel to remake them)."""
    from jax.ad_checkpoint import checkpoint_name

    from realhf_tpu.models import transformer as T
    from realhf_tpu.ops import delta_rule as D
    l, h, d, blocks = 512, 2, 128, 2
    prepare = D.Prepare(rate=-jnp.ones((h,)), dt_bias=jnp.zeros((h, d)),
                        scale=d ** -0.5, eps=1e-6)

    def block(x, w, seg):
        q, k, v, f = (z.reshape(1, l, h, d) for z in jnp.split(
            x @ w["in"], 4, axis=-1))
        o, _ = D._by_kernels(q, k, v, f, jnp.full((1, l, h), 0.5), seg,
                             prepare)
        o = checkpoint_name(o, T.DELTA_RESIDUALS[0])
        return x + o.reshape(1, l, h * d) @ w["out"]

    def loss(ws, x, seg):
        for w in ws:
            x = jax.checkpoint(block, policy=T._remat_policy(
                "nothing_saveable"))(x, w, seg)
        return x.astype(jnp.float32).sum()

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    bf16 = jnp.bfloat16
    ws = [{"in": sds((h * d, 4 * h * d), bf16),
           "out": sds((h * d, h * d), bf16)} for _ in range(blocks)]
    text = _compile(jax.value_and_grad(loss), ws, sds((1, l, h * d), bf16),
                    sds((1, l), jnp.int32)).as_text()
    assert D.scan_kernel_calls(text) == 2 * blocks
    assert D.scan_handed(text) == len(D.RESIDUAL_NAMES)
    assert set(D.RESIDUAL_NAMES) < set(T.KEPT_RESIDUALS)


@pytest.mark.parametrize("dtype,precision", [
    (jnp.bfloat16, "default"), (jnp.float32, "highest")],
    ids=["bf16_one_pass", "float32_highest"])
@pytest.mark.parametrize("passes", ["forward", "gradient"])
def test_ssm_scan_kernels_compile_at_the_cells_shape(one_chip, dtype,
                                                     precision, passes):
    """``ops/ssm_scan.py``'s two kernels at the tenth cell's shape (one
    row of 4096, 64 heads of 64 in 8 groups, a state of 128): the
    forward alone as prefill runs it (no start state kept) and forward
    and gradient, as the bf16 engine runs them (operands bf16, products
    in one pass, blocks of 4 chunks) and as ``chip_check.py
    nemotron_h``'s float32 rows do (operands float32, every product at
    the highest precision, blocks of 2 chunks: the backward's blocks
    and scratch at 4 are refused for VMEM)."""
    from realhf_tpu.ops import ssm_scan as S
    b, l, h, p, g, n = 1, 4096, 64, 64, 8, 128

    def sds(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)

    def scan(x, dt, bb, cc, rate, dt_bias, skip, seg):
        return S._by_kernels(x, dt, bb, cc, seg, rate, dt_bias, skip)

    def loss(*a):
        y, last = scan(*a)
        return y.astype(jnp.float32).sum() + last.sum()

    args = (sds((b, l, h, p), dtype), sds((b, l, h), dtype),
            sds((b, l, g, n), dtype), sds((b, l, g, n), dtype),
            *(sds((h,), jnp.float32) for _ in range(3)),
            sds((b, l), jnp.int32))
    with jax.default_matmul_precision(precision):
        text = _compile(scan if passes == "forward" else jax.grad(
            loss, argnums=tuple(range(7))), *args).as_text()
    assert S.scan_kernel_calls(text) == (1 if passes == "forward" else 2)


@pytest.mark.slow
def test_kimis_whole_train_step_compiles(one_chip):
    """The eighth cell's WHOLE train step for the described chip: 32
    microbatches of one row of 2048 through four delta layers (the
    chunked scan by ``ops/delta_rule.py``'s two kernels, a chunk's
    coefficients in VMEM) and one latent layer without a rotary,
    accumulated in float32, Adam on float32 masters, parameters and
    optimizer state donated. The kernels' scoped VMEM is counted inside
    the whole program, and a rematerialised block keeps the scan's
    output, the chunks' start states and their masked pairs and
    inverses: ONE forward and ONE backward kernel a delta layer, none
    in the rematerialised pass, and the backward kernel takes both kept
    arrays (``scan_handed``). The
    compiler's count of the step's memory is what the cell's size hangs
    on: 602 M parameters are 12.05 GB at 20 bytes before a row's
    activations, and a program is held to 13.9 GB. By the XLA products
    (PR 39) the step read 13.19 GB with a rematerialised segment of 4
    chunks (15.6 to 17.4 with the row's coefficients kept at once); by
    the kernels 13.19 with ``Prepare`` applied inside them (13.58 with
    q, k and the decay written in float32 by an XLA pass before them:
    PERF.md, PR 42); 13.48 (arguments 8.435 + temporaries 5.046) since
    a chunk's pairs and inverse are handed to the backward kernel, 201
    MB a microbatch beside the 268 MB of start states (PERF.md, PR
    52)."""
    from realhf_tpu.ops import delta_rule

    step, *args = _sft_train_step(
        one_chip, "kimi-linear-48b-a3b-l5-ep32", "kimi_linear", 32,
        row_len=2048)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    # all three kinds of kernel
    assert "flash_fwd" in text and "gmm" in text
    assert delta_rule.DELTA_FWD in text and delta_rule.DELTA_BWD in text
    assert delta_rule.scan_kernel_calls(text) == 4 * 2
    assert delta_rule.scan_handed(text) == len(delta_rule.RESIDUAL_NAMES)
    memory = compiled.memory_analysis()
    print("kimi whole step GB", (memory.argument_size_in_bytes
                                 + memory.temp_size_in_bytes) / 1e9)
    assert 12.8e9 < (memory.argument_size_in_bytes
                     + memory.temp_size_in_bytes) < 13.58e9


@pytest.mark.slow
def test_kimis_float32_forward_compiles(one_chip):
    """What ``scripts/chip_check.py kimi_linear``'s FLOAT32 rows run (the
    gate for ``ops/delta_rule.py``): the eighth cell's model in float32
    with every product at the highest precision, forward on one row of
    2048, all three kinds of kernel in one program. With the delta
    scan's kernels in it the experts' ``gmm`` at a held expert's
    float32 ``[2304, 1024]`` was refused on the chip for 1.83 MiB of
    scoped VMEM over its own limit, where the program without them
    compiled (``ops/grouped_matmul.py:_params``; PERF.md, PR 42)."""
    from realhf_tpu.models import transformer as T
    from realhf_tpu.ops import delta_rule

    cfg, params, sds, attn = _model(one_chip, "kimi-linear-48b-a3b-l5-ep32",
                                    "kimi_linear")
    cfg.param_dtype = cfg.compute_dtype = "float32"
    params = jax.tree.map(lambda a: sds(a.shape, jnp.float32), params)

    def forward(p, ids, seg):
        return T.forward(cfg, p, ids, seg, attention_fn=attn)

    with jax.default_matmul_precision("highest"):
        text = _compile(_as_on_a_tpu(forward), params,
                        sds((1, 2048), jnp.int32),
                        sds((1, 2048), jnp.int32)).as_text()
    assert "gmm" in text and "flash_fwd" in text
    assert delta_rule.scan_kernel_calls(text) == 4


@pytest.mark.parametrize("row,suffix", [(2048, ""), (8192, "_stream")],
                         ids=["cell_3s_rows", "rows_of_8192_streamed"])
def test_flash_compiles_under_shard_map(topo, row, suffix):
    """Cell 3's layout: rows over "data", heads over "model" on a 2x2
    mesh, each shard's kernels taking their ranges from the local
    segment ids (Mistral's heads, the cell's rows of 2048); and rows
    of 8192 there, which each shard hands the kernels that stream K and
    V by block (4 of a shard's 16 query heads a key/value head: one
    fetch serves four)."""
    import numpy as np

    from realhf_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
    mesh = Mesh(np.array(topo.devices[:4]).reshape(2, 2),
                (DATA_AXIS, MODEL_AXIS))
    heads = NamedSharding(mesh, P(DATA_AXIS, None, MODEL_AXIS, None))
    rows = NamedSharding(mesh, P(DATA_AXIS, None))
    q, k, v, seg = _qkv(None, 2, row, 32, 8, 128)
    q, k, v = (jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=heads)
               for x in (q, k, v))
    seg = jax.ShapeDtypeStruct(seg.shape, seg.dtype, sharding=rows)
    attn = make_sharded_attention(
        mesh, inner=lambda *a, **kw: flash_attention(
            *a, causal=kw["causal"], scale=kw["scale"],
            sliding_window=kw["sliding_window"]))

    def grads(q, k, v, seg):
        def loss(q, k, v):
            return attn(q, k, v, seg).astype(jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compile(grads, q, k, v, seg).as_text()
    for kernel in ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv"):
        assert kernel + suffix in text
    assert ("_stream" in text) == bool(suffix)


def _mesh_of_four(topo):
    """The described chips as the engine's dp2 x tp2 mesh."""
    import numpy as np

    from realhf_tpu.parallel.mesh import MESH_AXES
    return Mesh(np.array(topo.devices[:4]).reshape(1, 2, 1, 2), MESH_AXES)


def test_delta_scan_compiles_under_shard_map(topo):
    """``ops/delta_rule.py``'s two kernels on a dp2 x tp2 mesh, two
    rows of the eighth cell's shape: a bare Mosaic call is refused at
    lowering there ("Mosaic kernels cannot be automatically
    partitioned"), so ``chunked_delta_rule`` hands each device its own
    row and its own 16 of the 32 heads under ``shard_map``; d of the
    decay's two tensors is summed over "data", and nothing is gathered."""
    from realhf_tpu.ops import delta_rule as D
    from realhf_tpu.ops.hlo_text import device_instructions
    from realhf_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
    mesh = _mesh_of_four(topo)
    b, l, h, d = 2, 2048, 32, 128

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    def loss(q, k, v, f, beta, seg, rate, dt_bias):
        prepare = D.Prepare(rate=rate, dt_bias=dt_bias, scale=d ** -0.5,
                            eps=1e-6)
        o, last = D.chunked_delta_rule(q, k, v, f, beta, seg, prepare,
                                       mesh=mesh)
        return o.astype(jnp.float32).sum() + last.sum()

    args = (*(sds((b, l, h, d), jnp.bfloat16, DATA_AXIS, None, MODEL_AXIS)
              for _ in range(4)),
            sds((b, l, h), jnp.float32, DATA_AXIS, None, MODEL_AXIS),
            sds((b, l), jnp.int32, DATA_AXIS),
            sds((h,), jnp.float32, MODEL_AXIS),
            sds((h, d), jnp.float32, MODEL_AXIS))
    text = _compile(_as_on_a_tpu(jax.grad(
        loss, argnums=(0, 1, 2, 3, 4, 6, 7))), *args).as_text()
    assert D.scan_kernel_calls(text) == 2
    assert D.scan_handed(text) == len(D.RESIDUAL_NAMES)
    opcodes = {opcode for _, _, opcode in device_instructions(text)}
    assert "all-reduce" in opcodes  # d of the decay, over "data"
    assert not opcodes & {"all-gather", "all-to-all", "collective-permute"}


def test_ssm_scan_compiles_under_shard_map(topo):
    """``ops/ssm_scan.py``'s two kernels on a dp2 x tp2 mesh, two rows
    of the tenth cell's shape: ``chunked_ssm_scan`` hands each device
    its own row and its own 4 of the 8 GROUPS of heads (their columns
    of x, B and C) under ``shard_map``; d of the three leaves a head is
    summed over "data", and nothing is gathered."""
    from realhf_tpu.ops import ssm_scan as S
    from realhf_tpu.ops.hlo_text import device_instructions
    from realhf_tpu.parallel.mesh import DATA_AXIS, MODEL_AXIS
    mesh = _mesh_of_four(topo)
    b, l, h, p, g, n = 2, 4096, 64, 64, 8, 128

    def sds(shape, dtype, *spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, P(*spec)))

    def loss(x, dt, bb, cc, rate, dt_bias, skip, seg):
        y, last = S.chunked_ssm_scan(x, dt, bb, cc, seg, rate=rate,
                                     dt_bias=dt_bias, skip=skip, mesh=mesh)
        return y.astype(jnp.float32).sum() + last.sum()

    heads = (DATA_AXIS, None, MODEL_AXIS)
    args = (sds((b, l, h, p), jnp.bfloat16, *heads),
            sds((b, l, h), jnp.bfloat16, *heads),
            sds((b, l, g, n), jnp.bfloat16, *heads),
            sds((b, l, g, n), jnp.bfloat16, *heads),
            *(sds((h,), jnp.float32, MODEL_AXIS) for _ in range(3)),
            sds((b, l), jnp.int32, DATA_AXIS))
    text = _compile(_as_on_a_tpu(jax.grad(loss, argnums=tuple(range(7)))),
                    *args).as_text()
    assert S.scan_kernel_calls(text) == 2
    opcodes = {opcode for _, _, opcode in device_instructions(text)}
    assert "all-reduce" in opcodes  # d of the leaves, over "data"
    assert not opcodes & {"all-gather", "all-to-all", "collective-permute"}


@pytest.mark.slow
def test_kimis_whole_microbatch_compiles_on_a_mesh(topo):
    """Kimi-Linear's WHOLE model (the eighth cell's configuration) as
    the engine lays it over a dp2 x tp2 mesh, two rows of 2048, one
    microbatch's forward and backward: the delta layers' heads, their
    convolutions' channels and the decay's leaves by "model", the rows
    by "data" (``models/sharding.py``), the flash kernels and the
    delta scan's each under its ``shard_map``, the experts' products
    ``lax.ragged_dot`` (``SHARDED_STACKS``). A rematerialised block
    keeps the scan's output, the chunks' start states and their
    pairs and inverses through the ``shard_map``: one forward and one
    backward kernel a delta layer, the backward handed both."""
    from realhf_tpu.interfaces import sft
    from realhf_tpu.models import sharding as shard_rules
    from realhf_tpu.models import transformer as T
    from realhf_tpu.ops import delta_rule
    from realhf_tpu.ops import moe as moe_ops
    from realhf_tpu.parallel.mesh import DATA_AXIS

    mesh = _mesh_of_four(topo)
    cfg, params, _, flash = _model(None, "kimi-linear-48b-a3b-l5-ep32",
                                   "kimi_linear")
    params = jax.tree.map(
        lambda a, sharding: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                                 sharding=sharding),
        params, shard_rules.param_shardings(cfg, mesh))
    rows = NamedSharding(mesh, P(DATA_AXIS))
    mb = {name: jax.ShapeDtypeStruct((2, 2048), dtype, sharding=rows)
          for name, dtype in (("input_ids", jnp.int32),
                              ("seg_ids", jnp.int32),
                              ("prompt_mask", jnp.bool_))}
    attn = make_sharded_attention(mesh, inner=flash)
    loss_fn = sft._make_loss_fn(cfg)

    def objective(p, mb):
        h, _, aux = T.forward(cfg, p, mb["input_ids"], mb["seg_ids"],
                              return_aux=True, attention_fn=attn,
                              moe_constraint=moe_ops.SHARDED_STACKS,
                              mesh=mesh)
        loss, _ = loss_fn(p, h, mb)
        return loss + moe_ops.aux_loss(aux)

    text = _compile(_as_on_a_tpu(jax.grad(objective)), params, mb).as_text()
    assert "flash_fwd" in text and "gmm" not in text
    assert delta_rule.scan_kernel_calls(text) == 4 * 2
    assert delta_rule.scan_handed(text) == len(delta_rule.RESIDUAL_NAMES)


def test_row_above_the_limit_raises_not_xla():
    """One bucket above the limit of the kernels that stream K and V,
    ``packed_attention`` raises a clear error; it does not drop to the
    O(L^2) XLA path in silence. Nor does a row past ``FLASH_MAX_LEN``
    that those kernels do not take: one with a learned selection, one
    whose key is wider than its value."""
    q, k, v, seg = _qkv(None, 1, FLASH_STREAM_MAX_LEN + 128)
    with pytest.raises(ValueError, match="FLASH_STREAM_MAX_LEN"):
        jax.eval_shape(
            lambda *a: packed_attention(*a, use_flash=True), q, k, v, seg)
    n = FLASH_MAX_LEN + 128
    q, k, v, seg = _qkv(None, 1, n)
    select = jax.ShapeDtypeStruct((1, n, n), jnp.int8)
    with pytest.raises(NotImplementedError, match="no learned selection"):
        jax.eval_shape(lambda *a: packed_attention(
            *a[:4], use_flash=True, select=a[4]), q, k, v, seg, select)
    wide = jax.ShapeDtypeStruct((1, n, NQ, 192), jnp.bfloat16)
    with pytest.raises(NotImplementedError, match="key 192, value 64"):
        jax.eval_shape(
            lambda *a: packed_attention(*a, use_flash=True),
            wide, jax.ShapeDtypeStruct((1, n, NKV, 192), jnp.bfloat16),
            v, seg)


#: SmallThinker-21BA3B's attention widths and its own context
ST_HEADS, ST_ROW, ST_WINDOW = (28, 4, 128), 16384, 4096


@pytest.mark.parametrize("l,window,dtype", [
    (ST_ROW, None, jnp.bfloat16), (ST_ROW, ST_WINDOW, jnp.bfloat16),
    (ST_ROW, ST_WINDOW, jnp.float32),
    (FLASH_STREAM_MAX_LEN, None, jnp.bfloat16),
    (FLASH_MAX_LEN + 256, 512, jnp.bfloat16)],
    ids=["full_16k", "window_16k", "window_16k_float32", "at_the_limit",
         "one_block_past_the_whole_row_kernels"])
def test_stream_kernels_compile_past_the_longest_held_row(one_chip, l,
                                                          window, dtype):
    """The three kernels that stream their blocks
    (``flash_fwd_stream``, ``flash_bwd_dq_stream``,
    ``flash_bwd_dkv_stream``) for the described chip at SmallThinker's
    (28 q, 4 kv, 128): a row of 16,384 with and without the window of
    4096 (the twelfth cell's two kinds of layer), in float32 too (the
    gate ``chip_check.py smallthinker``'s long document runs), at
    ``FLASH_STREAM_MAX_LEN``, which is a promise about the compiler as
    ``FLASH_MAX_LEN`` is, and one key block past the whole-row
    kernels' limit under a window narrower than a pair (one body). No
    whole-row kernel is in the program, and what the program keeps
    beside the kernels does not hold K and V a query head."""
    q, k, v, seg = (jax.ShapeDtypeStruct(a.shape, dtype if a.dtype
                                         == jnp.bfloat16 else a.dtype,
                                         sharding=one_chip)
                    for a in _qkv(one_chip, 1, l, *ST_HEADS))

    def fwd(q, k, v, seg):
        return flash_attention(q, k, v, seg, sliding_window=window)

    def grads(q, k, v, seg):
        return jax.grad(lambda q, k, v: fwd(q, k, v, seg).astype(
            jnp.float32).sum(), argnums=(0, 1, 2))(q, k, v)

    text = _compile(fwd, q, k, v, seg).as_text()
    assert "flash_fwd_stream" in text
    compiled = _compile(grads, q, k, v, seg)
    calls = [name for name, _, opcode in device_instructions(
        compiled.as_text()) if opcode == "custom-call"]
    assert sorted(c.rstrip(".0123456789") for c in calls
                  if "flash" in c) == [
        "flash_bwd_dkv_stream", "flash_bwd_dq_stream", "flash_fwd_stream"]
    # q, k, v, the output, three gradients and the two lane-broadcast
    # float32 copies of lse and delta: nothing a (query head, key)
    per_token = compiled.memory_analysis().temp_size_in_bytes / l
    assert per_token < 60e3, per_token


def _float32_arrays_made(text, elems):
    """What the compiled program's instructions OUTSIDE fusion bodies
    make of ``elems`` float32 elements: ``(products, others)``, the
    names of the fusions around a product and the opcodes of the rest
    (tuples, their elements and bitcasts move nothing and are left
    out)."""
    import math
    import re
    bodies, name = {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%?([\w.\-]+) \(.*\{\s*$", line)
        if head:
            name = head.group(1)
            bodies[name] = []
        elif name is not None and line.startswith(" "):
            bodies[name].append(line)
    fused = set(re.findall(r"\bfusion\(.*calls=%?([\w.\-]+)", text))
    products, others = [], []
    for name, lines in bodies.items():
        if name in fused:
            continue
        for line in lines:
            inst = re.match(
                r"\s*(?:ROOT )?%?([\w.\-]+) = (.*?) ([\w\-]+)\(", line)
            if inst is None or not any(
                    math.prod(map(int, dims.split(","))) == elems
                    for dims in re.findall(r"f32\[([\d,]+)\]",
                                           inst.group(2))):
                continue
            op = inst.group(3)
            callee = re.search(r"calls=%?([\w.\-]+)", line)
            if op == "fusion" and any(
                    " convolution(" in l or " dot(" in l
                    for l in bodies[callee.group(1)]):
                products.append(inst.group(1))
            elif op not in ("tuple", "get-tuple-element", "bitcast"):
                others.append(op)
    return products, others


def _qwens_head(one_chip, loss):
    """The compiled text of ``jax.value_and_grad(loss(cfg, chunk))``
    with respect to the weight and the states at Qwen2.5-0.5B's head
    (896 x 151,936, tied, one row of 4096, chunks of 1024)."""
    from realhf_tpu.models.config import TransformerConfig

    hidden, vocab, chunk = 896, 151936, 1024
    cfg = TransformerConfig(
        n_layers=N_LAYERS, n_kv_heads=NKV, n_q_heads=NQ, hidden_dim=hidden,
        head_dim=HD, intermediate_dim=4864, vocab_size=vocab,
        tied_embedding=True, param_dtype="bfloat16",
        compute_dtype="bfloat16")

    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    compiled = jax.jit(jax.value_and_grad(
        loss(cfg, chunk), argnums=(0, 1))).lower(
        {"embed": {"wte": sds((vocab, hidden), jnp.bfloat16)}},
        sds((1, ROW_LEN, hidden), jnp.bfloat16),
        sds((1, ROW_LEN), jnp.int32), sds((1, ROW_LEN), jnp.int32)).compile()
    return compiled.as_text(), chunk * vocab


def _head_products(text):
    """The head's matrix products by pass, as the engine's
    ``head_remat_products`` counts them."""
    from realhf_tpu.obs import parts
    return {p: parts.count_products(text, parts.VOCAB_HEAD, p)
            for p in (parts.FWD, parts.REMAT, parts.BWD)}


def test_head_makes_no_chunk_of_logits_but_its_products(one_chip):
    """Loss and gradients over ``shifted_logprobs_from_hidden`` at
    Qwen2.5-0.5B's head: the label is picked by a select, so the
    program holds no scatter, and outside fusion bodies only the
    forward's product and the rematerialised one make a chunk's
    float32 logits (622 MB): four products a chunk, one of them in
    pass ``remat`` (the engine's ``head_remat_products`` 1, as GRPO's
    train program reads). With ``log_softmax`` + ``take_along_axis``
    (before PR 32) the forward wrote the whole log-softmax for a gather
    to read, and the backward a broadcast of zeros, a scatter into them
    and a relayout copy."""
    from realhf_tpu.ops.functional import shifted_logprobs_from_hidden

    def loss(cfg, chunk):
        return lambda params, h, ids, seg: -shifted_logprobs_from_hidden(
            cfg, params, h, ids, seg, chunk=chunk).sum() / ROW_LEN

    text, logits = _qwens_head(one_chip, loss)
    assert "scatter" not in text
    products, others = _float32_arrays_made(text, logits)
    assert others == []
    assert len(products) == 2
    assert _head_products(text) == {"fwd": 1, "remat": 1, "bwd": 2}


def test_weighted_head_makes_a_chunk_of_logits_once(one_chip):
    """The same head under ``weighted_logprob_sum``, as the SFT losses
    call it (weights ``1 / 4096`` a position): no scatter, and outside
    fusion bodies ONE product makes a chunk's float32 logits: three
    products a chunk, none in pass ``remat`` (``head_remat_products``
    0), the two gradient products, which the forward rule runs, in pass
    ``bwd`` (scope ``gradient``); and what the backward rule does, three
    scalings traced apart from the call, lies under the head's scope
    too, so ``train.unscoped_s`` does not grow."""
    import re

    from realhf_tpu.obs import parts
    from realhf_tpu.ops.functional import weighted_logprob_sum

    def loss(cfg, chunk):
        return lambda params, h, ids, seg: -weighted_logprob_sum(
            cfg, params, h, ids, seg,
            jnp.full(ids.shape, 1 / ROW_LEN, jnp.float32), chunk=chunk)[0]

    text, logits = _qwens_head(one_chip, loss)
    assert "scatter" not in text
    products, others = _float32_arrays_made(text, logits)
    assert others == []
    assert len(products) == 1
    assert _head_products(text) == {"fwd": 1, "remat": 0, "bwd": 2}
    transposed = [name for name in re.findall(r'op_name="([^"]*)"', text)
                  if "transpose(" in name]
    assert transposed and all(
        parts.classify(name)[0] == parts.VOCAB_HEAD for name in transposed)


def test_decode_stacked_compiles(one_chip):
    def sds(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)
    cache = (N_LAYERS, GEN_BATCH, NKV, CACHE_LEN, HD)
    _compile(flash_decode_attention_stacked,
             sds((GEN_BATCH, NQ, HD), jnp.bfloat16),
             sds(cache, jnp.bfloat16), sds(cache, jnp.bfloat16),
             sds((GEN_BATCH, CACHE_LEN), jnp.bool_), sds((), jnp.int32))


@pytest.mark.parametrize("chips,nq,nkv,hd,hidden", [
    (1, NQ, NKV, HD, 896), (4, 32, 8, 128, 4096)],
    ids=["qwen_one_chip", "mistral_d4t1"])
def test_decode_loop_touches_the_stacked_cache_in_place(
        topo, monkeypatch, chips, nq, nkv, hd, hidden):
    """``decode_step`` as ``generate`` runs it, four tokens of a scan
    over an unrolled two-layer model at the cells' attention widths
    (cell 1 on one chip, cell 3's d4t1 replica under ``shard_map``):
    the kernel is the cache's consumer, so the compiler keeps the
    loop's carry row-major, writes the token's rows in place, and
    makes no operation of one layer's cache shape. Before PR 30 the
    loop sliced ``k_all[l]`` out: a slice and a transposing copy a
    layer and a token, and a slot-minor stack."""
    import re

    import numpy as np

    from realhf_tpu.models import operators
    from realhf_tpu.models import transformer as T
    from realhf_tpu.models.config import TransformerConfig
    from realhf_tpu.parallel.mesh import DATA_AXIS

    monkeypatch.setattr(operators, "pallas_enabled", lambda: True)
    cfg = TransformerConfig(
        n_layers=2, n_kv_heads=nkv, n_q_heads=nq, hidden_dim=hidden,
        head_dim=hd, intermediate_dim=1024, vocab_size=1024,
        apply_rotary=True, layer_norm_type="rms", mlp_type="llama",
        use_attention_bias=False, use_attn_proj_bias=False,
        use_mlp_bias=False, activation_function="silu",
        param_dtype="bfloat16", compute_dtype="bfloat16")
    if chips == 1:
        mesh = None
        rep = SingleDeviceSharding(topo.devices[0])
        shard_rows = lambda cache: cache
    else:
        mesh = Mesh(np.array(topo.devices[:chips]).reshape(chips, 1),
                    (DATA_AXIS, "model"))
        rep = NamedSharding(mesh, P())

        def shard_rows(cache):  # the batch axis over "data"
            return {k: jax.lax.with_sharding_constraint(a, NamedSharding(
                mesh, P(*([None] * (a.ndim == 5)), DATA_AXIS)))
                for k, a in cache.items()}

    params = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=rep),
        jax.eval_shape(lambda: T.init_params(cfg, jax.random.PRNGKey(0))))

    def loop(params):
        # the cache is born inside the program, as generate's is in its
        # prefill: its layout is the compiler's to choose
        cache = shard_rows(T.init_kv_cache(cfg, GEN_BATCH, CACHE_LEN))

        def step(cache, token):
            hidden, cache = T.decode_step(
                cfg, params, cache, token, cache["length"],
                uniform_slot=True, mesh=mesh)
            return cache, hidden[:, 0]
        return jax.lax.scan(
            step, cache, jnp.ones((4, GEN_BATCH), jnp.int32))[1]

    text = _compile(loop, params).as_text()
    assert KERNEL_NAME in text
    local = (GEN_BATCH // chips, nkv, CACHE_LEN, hd)
    assert decode_layer_copies(text, local) == 0
    stack = ",".join(map(str, (cfg.n_layers,) + local))
    layouts = set(re.findall(rf"bf16\[{stack}\]{{([\d,]+)", text))
    assert layouts == {"4,3,2,1,0"}


def test_flash_compiles_with_a_selection(one_chip):
    """The three kernels with a sparse layer's selection as one more
    blocked operand, at Keye-VL-2.0's heads (32 query and 4 key/value
    heads of 128) and the cell's row of 4096: the int8 ``[1, L, L]``
    mask goes in by the rows of a query block (forward, dq: 256 x 4096)
    and by the columns of a key block (dkv: 4096 x 512), is widened to
    int32 inside and joins the mask of segments and causality. Their
    names end in ``_sel``: what ``flash_mask_calls`` counts."""
    from realhf_tpu.ops.flash_attention import flash_mask_calls
    q, k, v, seg = _qkv(one_chip, 1, FLASH_MAX_LEN, 32, 4, 128)
    select = jax.ShapeDtypeStruct((1, FLASH_MAX_LEN, FLASH_MAX_LEN),
                                  jnp.int8, sharding=one_chip)

    def grads(q, k, v, seg, select):
        def loss(q, k, v):
            return flash_attention(q, k, v, seg, select=select).astype(
                jnp.float32).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    text = _compile(grads, q, k, v, seg, select).as_text()
    assert flash_mask_calls(text) == 3
    for name in ("flash_fwd_sel", "flash_bwd_dq_sel", "flash_bwd_dkv_sel"):
        assert name in text
    # and without one the kernels are the ones they were
    assert flash_mask_calls(_compile(_flash_grads, q, k, v,
                                     seg).as_text()) == 0


@pytest.mark.slow
def test_keyes_whole_train_step_compiles(one_chip):
    """The ninth cell's WHOLE train step for the described chip: 32
    microbatches of one row of 4096 through five sparse layers (the
    indexer's scores a block of 512 queries at a time, the exact top
    2048 by bisection, the int8 selection handed to the three flash
    kernels), accumulated in float32, Adam on float32 masters,
    parameters and optimizer state donated. A rematerialised block
    keeps the selection beside the forward kernel's residuals: THREE
    kernels that take it a layer (15), none a second time, and nothing
    of part ``index`` in the rematerialised pass. The compiler's count
    of the step's memory is what the cell's size hangs on: 562 M
    parameters are 11.25 GB at 20 bytes before a row's activations and
    its five kept selections (16.8 MB each), and a program is held to
    13.9 GB: 13.79 as committed."""
    from realhf_tpu.obs import parts
    from realhf_tpu.ops.flash_attention import (flash_fwd_per_bwd,
                                                flash_mask_calls)

    step, *args = _sft_train_step(
        one_chip, "keye-vl-2.0-30b-a3b-l5-ep8", "keye_vl2", 32)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    assert "gmm" in text
    assert flash_mask_calls(text) == 5 * 3
    assert flash_fwd_per_bwd(text) == 1.0
    ops = parts.parse_program(text)
    index = {(part, pass_) for part, pass_, *_ in ops.values()
             if (part or "").startswith("index")}
    # (the loop over blocks of queries and the mask's layout are the
    # part's own, under no sub-step)
    assert {part for part, _ in index} - {"index"} == {
        "index/project", "index/scores", "index/select"}
    assert {pass_ for _, pass_ in index} == {"fwd"}
    memory = compiled.memory_analysis()
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print("keye whole step GB", total / 1e9)
    assert 13.3e9 < total < 13.9e9


@pytest.mark.slow
def test_nemotrons_whole_train_step_compiles(one_chip):
    """The tenth cell's WHOLE train step for the described chip: 16
    microbatches of one row of 4096 (four documents of 1024) through
    ``E M E M E M *``: three Mamba-2 layers (the chunked scan by
    ``ops/ssm_scan.py``'s two kernels, a chunk's decays and ``C B^T``
    in VMEM; a rematerialised block keeps the scan's output and the
    chunks' start states, 67 MB a layer a row: ONE forward and ONE
    backward kernel a layer, none in the rematerialised pass; by the
    XLA products, PR 48, the step read 11.99 GB), three layers of
    ungated experts (TWO grouped
    products forward: eight ``gmm`` kernels a layer in each branch of
    the share's ``cond``, not twelve) and one GQA layer at 16 query heads a key head, accumulated
    in float32, Adam on float32 masters, parameters and optimizer state
    donated. The compiler's count of the step's memory is what the
    cell's size hangs on: 528 M parameters are 10.56 GB at 20 bytes
    before a row's activations, and a program is held to 13.9 GB."""
    from realhf_tpu.obs import parts
    from realhf_tpu.ops import moe as moe_ops
    from realhf_tpu.ops import ssm_scan
    from realhf_tpu.ops.flash_attention import flash_fwd_per_bwd

    step, *args = _sft_train_step(
        one_chip, "nemotron-3-nano-30b-a3b-l7-ep16", "nemotron_h", 16)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    assert "flash_fwd" in text and flash_fwd_per_bwd(text) == 1.0
    assert ssm_scan.scan_kernel_calls(text) == 3 * 2
    # 2 forward, 2 rematerialised, 2 + 2 backward, in the share's fast
    # path and in its slow one
    assert moe_ops.grouped_product_calls(text)["moe_gmm_calls"] \
        == 3 * 2 * 8
    ops = parts.parse_program(text)
    by_part = {part for part, *_ in ops.values()}
    assert {"ssm", "ssm/scan", "attn", "attn_proj", "experts/products",
            "shared_expert"} <= by_part
    # a layer is ONE part: no dense feed-forward anywhere
    assert "mlp" not in by_part and "conv" not in by_part
    memory = compiled.memory_analysis()
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print("nemotron whole step GB", total / 1e9)
    assert 11.0e9 < total < 13.9e9


@pytest.mark.slow
def test_ouros_whole_train_step_compiles(one_chip):
    """The eleventh cell's WHOLE train step for the described chip: four
    microbatches of one 4096-token row through a LOOPED model, six
    layers walked four times over one set of weights (a scan of passes
    around the scan of layers), a norm before and after each operator,
    the final norm, the head and the exit gate once a pass, accumulated
    in float32, Adam on float32 masters, parameters and optimizer state
    donated. Each pass's layer scan holds the flash kernels once: a
    forward, and in the backward the two backward kernels and no second
    forward (a block in a loop keeps the kernel's residuals and makes q
    and the projected output again). The compiler's count of the step's
    memory is what the cell's size hangs on: 509.7 M parameters are
    10.19 GB at 20 bytes, and what the backward keeps is 24 layer
    applications' residuals, four times a six-layer stack's: 13.72 GB
    of the 13.9 a program is held to (15.28 with q and the projected
    output kept too, 16.14 with the passes a scan, 17.86 with the
    shared weights' gradients left to the scans' transposes)."""
    from realhf_tpu.obs import parts
    from realhf_tpu.ops.flash_attention import flash_fwd_per_bwd

    step, *args = _sft_train_step(one_chip, "ouro-2.6b-l6", "ouro", 4)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    assert "flash_fwd" in text and flash_fwd_per_bwd(text) == 1.0
    ops = parts.parse_program(text)
    by_part = {part for part, *_ in ops.values()}
    assert {"layers", "layers/loop", "exit", "attn", "attn_proj", "mlp",
            "vocab_head", "loss", "grad_accum", "optimizer"} <= by_part
    # four passes' heads, each chunk's logits made once
    assert _head_products(text) == {"fwd": 1, "remat": 0, "bwd": 2}
    memory = compiled.memory_analysis()
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print("ouro whole step GB", total / 1e9)
    assert 13.2e9 < total < 13.9e9


@pytest.mark.slow
def test_smallthinkers_whole_train_step_compiles(one_chip):
    """The twelfth cell's WHOLE train step for the described chip:
    eight microbatches of ONE 16,384-token row through SmallThinker's
    four layers (a NoPE full layer and three rotary layers under a
    window of 4096, the router on the layer's input, 8 of 64 ReLU-gated
    experts held, each run over every token, as the cell's file asks:
    ``expert_dispatch: "dense"``),
    accumulated in float32, Adam on float32 masters, parameters and
    optimizer state donated. Every attention layer runs the three
    STREAM kernels once (a rematerialised block keeps the forward
    kernel's residuals) and no whole-row kernel; the router's product
    stands under a part of its own. The compiler's count of the step's
    memory is what the cell's size hangs on (370.5 M parameters are
    7.4 GB at 20 bytes; a 16,384-token microbatch's residuals and the
    backward's temporaries on top)."""
    from realhf_tpu.obs import parts
    from realhf_tpu.ops.flash_attention import flash_fwd_per_bwd

    step, *args = _sft_train_step(
        one_chip, "smallthinker-21b-a3b-l4-ep8", "smallthinker", 8,
        row_len=ST_ROW)
    compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*args).compile()
    text = compiled.as_text()
    calls = [name for name, _, opcode in device_instructions(text)
             if opcode == "custom-call" and "flash" in name]
    assert len(calls) == 3 * 4 and all("_stream" in c for c in calls)
    # (the dense mode over the held stacks: no sort, no gather and no
    # grouped-matmul kernel)
    from realhf_tpu.ops.moe import grouped_product_calls
    assert flash_fwd_per_bwd(text) == 1.0
    assert grouped_product_calls(text)["moe_gmm_calls"] == 0
    by_part = {part for part, *_ in parts.parse_program(text).values()}
    assert {"attn/stream", "attn_proj", "experts/router", "experts/route",
            "experts/products", "experts/combine", "vocab_head",
            "grad_accum", "optimizer"} <= by_part
    assert "experts/gather" not in by_part
    assert _head_products(text) == {"fwd": 1, "remat": 0, "bwd": 2}
    memory = compiled.memory_analysis()
    total = memory.argument_size_in_bytes + memory.temp_size_in_bytes
    print("smallthinker whole step GB", total / 1e9)
    assert 8e9 < total < 13.9e9
