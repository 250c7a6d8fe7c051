"""What an engine's compiled programs say of themselves
(``Engine.program_facts``, ``obs/parts.py``): the parts the model names
reach the compiled text of every family's train and generate programs,
a profiled capture carries the facts of every program that ran in it,
and with tracing off nothing new is read."""

import collections
import json
import os

import jax
import numpy as np
import pytest

from benchmark import generate
from realhf_tpu.api.config import ModelName
from realhf_tpu.engine.engine import Engine
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.interfaces import sft
from realhf_tpu.models import hf as hf_models
from realhf_tpu.models import transformer as T
from realhf_tpu.obs import metrics, parts, tracing
from realhf_tpu.ops.sampling import GenerationHyperparameters
from realhf_tpu.parallel import mesh as mesh_lib

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
#: family -> its tiny configuration under tests/benchmark/, and the
#: parts it has beside the ones every family has
FAMILIES = {
    "qwen2": ("tests/benchmark/configs/tiny-qwen2.json", {"mlp"}),
    "mistral": ("tests/benchmark/configs/tiny-mistral-d2t2.json",
                {"mlp"}),
    "olmoe": ("tests/benchmark/olmoe/configs/tiny-olmoe.json",
              {"experts"}),
    "lfm2_moe": ("tests/benchmark/lfm2/configs/tiny-lfm2.json",
                 {"conv", "mlp", "experts"}),
    "laguna": ("tests/benchmark/laguna/configs/tiny-laguna.json",
               {"mlp", "experts", "shared_expert"}),
    "kimi_linear": (
        "tests/benchmark/kimi_linear/configs/tiny-kimi-linear.json",
        {"delta", "mlp", "experts", "shared_expert"}),
    "keye_vl2": (
        "tests/benchmark/keye_vl2/configs/tiny-keye-vl2.json",
        {"index", "experts"}),
    "nemotron_h": (
        "tests/benchmark/nemotron_h/configs/tiny-nemotron-h.json",
        {"ssm", "experts", "shared_expert"}),
}
#: how the op_name of a loop's own operations ends
PLUMBING = {"add", "lt", "closed_call", "dynamic_slice",
            "dynamic_update_slice", "squeeze", "broadcast_in_dim",
            "reduce_sum"}
#: these tests read scopes out of compiled programs (tests/conftest.py)
pytestmark = pytest.mark.usefixtures("programs_compiled_by_this_tree")
COMMON = {"embed", "attn_proj", "attn", "vocab_head", "loss",
          "grad_accum", "optimizer"}


def engine_of(family, train=True):
    hf, _ = generate.load_config(os.path.join(ROOT, FAMILIES[family][0]))
    cfg = hf_models.config_from_hf(family, hf)
    cfg.param_dtype = "bfloat16"
    cfg.gradient_checkpointing = True
    par = mesh_lib.ParallelismConfig()
    ctx = mesh_lib.MeshContext(
        ModelName("default", 0),
        mesh_lib.make_mesh(par, jax.devices()[:1]), par)
    params = jax.tree.map(np.asarray,
                          T.init_params(cfg, jax.random.PRNGKey(0)))
    return cfg, Engine(cfg, ctx, params, optimizer=OptimizerConfig(
        lr=1e-4, warmup_steps_proportion=0.0,
        lr_scheduler_type="constant") if train else None,
        total_train_steps=10)


def batch(rows=2, length=64):
    ids = np.ones((rows, length), np.int32)
    return dict(input_ids=ids, seg_ids=np.ones_like(ids),
                prompt_mask=np.zeros(ids.shape, bool))


def train(cfg, engine):
    mb = batch()
    return engine.train_batch([mb, mb], sft._make_loss_fn(cfg),
                              loss_fn_key="sft")


def generate_(engine, new_tokens=4):
    ids = np.ones((2, 8), np.int32)
    g = GenerationHyperparameters(max_new_tokens=new_tokens,
                                  min_new_tokens=new_tokens, greedy=True,
                                  force_no_logits_mask=True)
    return engine.generate(ids, np.ones_like(ids),
                           np.tile(np.arange(8, dtype=np.int32), (2, 1)),
                           jax.random.PRNGKey(0), g, None, 0)


@pytest.fixture(autouse=True)
def fresh_tracer():
    """A tracer and a metrics registry of the test's own: the gauges
    are process-wide, and an engine that a file earlier on the same
    xdist worker built left its programs' series in them."""
    tracing.reset_default()
    metrics.reset_default()
    yield
    tracing.reset_default()
    metrics.reset_default()


@pytest.fixture
def reads(monkeypatch):
    """The programs whose compiled text an engine read, in order."""
    seen, compiled = [], Engine._compiled

    def counted(self, name, call=None):
        seen.append(name)
        return compiled(self, name, call)
    monkeypatch.setattr(Engine, "_compiled", counted)
    return seen


@pytest.mark.parametrize("family", FAMILIES)
def test_train_program_names_every_part_the_family_has(family):
    cfg, engine = engine_of(family)
    stats = train(cfg, engine)
    assert np.isfinite(stats["loss"])
    facts = engine.program_facts("train")
    assert facts.module == "jit_train_step"
    assert facts is engine.program_facts("train")  # read once
    ops = facts.ops.values()
    have = {(op[0] or "").split("/")[0] for op in ops} - {""}
    assert have - {"layers"} == COMMON | FAMILIES[family][1]
    # a stacked model's layer scan has work of its own: a layer's
    # weights out of the stack, what the backward keeps a layer
    assert "layers" in have or cfg.layer_pattern is not None
    assert {op[3] for op in ops} == {None, "forward_backward"}
    passes = collections.defaultdict(set)
    for op in ops:
        passes[op[0]].add(op[1])
    assert {"fwd", "bwd"} <= passes["attn_proj"]
    assert "remat" in passes["attn_proj"] | passes["attn"]
    assert passes["optimizer"] == passes["grad_accum"] == {"fwd"}
    if "experts" in have:
        assert {f"experts/{s}" for s in ("route", "products", "combine")} \
            <= set(passes)
        # which grouped matmul the experts' products are, from the same
        # read (here lax.ragged_dot, and XLA:CPU makes no custom call
        # of it; on the chip the repo's kernels: test_chip_compile.py)
        assert facts.attributes["moe_products"] == "ragged_dot"
        assert facts.attributes["moe_gmm_calls"] == 0
    else:
        assert "moe_products" not in facts.attributes
    # whether the delta layers' scan is the kernels', from the same
    # read (here the XLA products: the backend is the CPU, and the
    # tiny heads are no whole lane; on the chip one forward and one
    # backward kernel a layer: test_chip_compile.py)
    if "delta" in have:
        assert "delta/scan" in passes
        assert facts.attributes["delta_scan_kernel_calls"] == 0
        # and what a backward kernel takes from the forward one (the
        # chunks' start states, their pairs and inverses: 2 on the
        # chip), a train program's alone
        assert facts.attributes["delta_scan_handed"] == 0
    else:
        assert "delta_scan_kernel_calls" not in facts.attributes
        assert "delta_scan_handed" not in facts.attributes
    # and the ssm layers' (likewise: ``kernel_takes`` refuses the tiny
    # cell's group of 2 heads of 8; on the chip 6 in the tenth cell)
    if "ssm" in have:
        assert "ssm/scan" in passes
        assert facts.attributes["ssm_scan_kernel_calls"] == 0
    else:
        assert "ssm_scan_kernel_calls" not in facts.attributes
    # whether the flash kernels take a sparse layer's selection (here
    # the XLA path takes it: none does), and the indexer forward only
    if "index" in have:
        assert {"index/project", "index/scores", "index/select"} \
            <= set(passes)
        assert passes["index/scores"] == passes["index/select"] == {"fwd"}
        assert facts.attributes["flash_mask_calls"] == 0
    else:
        assert "flash_mask_calls" not in facts.attributes
    # what does the work is put down to a part: no product is left
    # out, and the fusions that carry an op_name and no part are the
    # loops' own plumbing (counters, a layer's slice out of the stack,
    # what the backward keeps, broadcast constants), under a tenth of
    # them (XLA:CPU's own fusions of layout changes carry no op_name;
    # on the chip the share is measured in seconds: train.unscoped_s)
    work = [op for op in ops if op[2] in ("dot", "fusion") and op[4]]
    unscoped = [op for op in work if op[0] is None]
    assert work and len(unscoped) < 0.1 * len(work)
    assert not [op for op in unscoped if op[2] == "dot"]
    assert {op[4].split("/")[-1] for op in unscoped} <= PLUMBING
    assert all(facts.memory[f] > 0 for f in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes"))


@pytest.mark.parametrize("family", FAMILIES)
def test_generate_program_nests_the_parts_in_its_phases(family):
    _, engine = engine_of(family, train=False)
    generate_(engine)
    facts = engine.program_facts("generate")
    assert facts.module == "jit_generate"
    sparse = {"moe_products", "moe_gmm_calls", "moe_ragged_dot_calls"} \
        if "experts" in FAMILIES[family][1] else set()
    delta = {"delta_scan_kernel_calls"} \
        if "delta" in FAMILIES[family][1] else set()
    masked = {"flash_mask_calls"} \
        if "index" in FAMILIES[family][1] else set()
    ssm = {"ssm_scan_kernel_calls"} \
        if "ssm" in FAMILIES[family][1] else set()
    assert set(facts.attributes) == {
        "decode_kernel", "decode_layer_copies"} | sparse | delta | masked \
        | ssm
    seen = {(op[3], (op[0] or "").split("/")[0])
            for op in facts.ops.values()}
    assert {phase for phase, _ in seen} >= {"prefill", "decode", "sample"}
    for phase in ("prefill", "decode"):
        assert {(phase, "attn"), (phase, "attn_proj")} <= seen
    assert ("decode", "vocab_head") in seen
    assert {op[1] for op in facts.ops.values()} == {"fwd"}
    for part in FAMILIES[family][1] - {"shared_expert"}:
        assert ("decode", part) in seen, part


def test_profiled_capture_carries_its_programs(tmp_path, reads):
    cfg, engine = engine_of("qwen2")
    mb = batch()
    tracing.start(str(tmp_path))
    train(cfg, engine)
    engine.forward_logprobs(mb["input_ids"], mb["seg_ids"])
    generate_(engine)
    assert reads == ["generate"]  # as before this table: the decode facts
    first = tracing.stop()
    # the train and logprobs texts were read at the stop, once each
    assert sorted(reads) == ["generate", "logprobs", "train"]
    spans = first.named("engine:")
    # the generate program's text was read while spans were on: that
    # read has a span of its own (the two read at the stop have none)
    facts_read = spans.pop()
    assert facts_read["name"] == "engine:facts"
    assert facts_read["attributes"]["program"] == "jit_generate"
    assert facts_read["attributes"]["bytes"] > 0
    assert [s["name"] for s in spans] == [
        "engine:train", "engine:logprobs", "engine:generate"]
    for span in spans:
        facts = first.programs[span["attributes"]["program_fingerprint"]]
        assert facts["module"] == span["attributes"]["program"]
        assert facts["memory"]["argument_size_in_bytes"] > 0
        assert facts["memory"]["temp_size_in_bytes"] > 0
        assert facts["ops"]
    assert {f["module"] for f in first.programs.values()} == {
        "jit_train_step", "jit_logprobs", "jit_generate"}
    path = parts.programs_path(str(tmp_path))
    assert os.path.dirname(path) == os.path.dirname(
        parts.newest_profile(str(tmp_path)))
    with open(path) as f:
        assert json.load(f) == json.loads(json.dumps(first.programs))
    assert first.end <= first.spans[-1]["end"] + 60  # a clock reading
    # the gauge: one series a program and kind; in the capture those
    # written while it ran (the generate program's, read after its
    # first call), on /metrics all of them
    series = {k: v for k, v in first.gauges.items()
              if k.startswith("engine_program_bytes")}
    assert len(series) == 4 and all("jit_generate" in k for k in series)
    written = metrics.snapshot()["engine_program_bytes"]["values"]
    assert len(written) == 3 * 4
    assert engine.program_facts("train").memory["temp_size_in_bytes"] \
        in written.values()
    assert "engine_program_bytes" in metrics.to_prometheus()

    # a second capture, profiled or not, reads no text again
    tracing.start()
    train(cfg, engine)
    engine.forward_logprobs(mb["input_ids"], mb["seg_ids"])
    second = tracing.stop()
    assert len(reads) == 3
    assert second.programs.keys() == {
        s["attributes"]["program_fingerprint"]
        for s in second.named("engine:")} < first.programs.keys()
    assert second.profile_dir is None


def test_an_unprofiled_capture_reads_no_text(reads):
    cfg, engine = engine_of("qwen2")
    mb = batch()
    tracing.start(sync=True)
    train(cfg, engine)
    engine.forward_logprobs(mb["input_ids"], mb["seg_ids"])
    capture = tracing.stop()
    assert reads == [] and capture.programs == {}
    for span in capture.named("engine:"):
        assert span["attributes"]["program"].startswith("jit_")
        assert "program_fingerprint" not in span["attributes"]


def test_tracing_off_reads_what_the_parent_read(reads):
    """Three steps with tracing off: no text is read for a train
    program off the flash kernels, one for a generate program (its
    decode facts, after its first call), as before the facts."""
    cfg, engine = engine_of("qwen2")
    mb = batch()
    for _ in range(3):
        train(cfg, engine)
        engine.forward_logprobs(mb["input_ids"], mb["seg_ids"])
        generate_(engine)
    assert reads == ["generate"]
    assert not engine._unread
    generate_(engine, new_tokens=5)  # another program under the name
    assert reads == ["generate", "generate"]


def test_worker_profiler_command_is_the_one_control(tmp_path):
    from realhf_tpu.system.worker_base import Worker
    worker = Worker.__new__(Worker)
    cfg, engine = engine_of("qwen2")
    said = worker._handle_command("profiler", dict(
        action="start", path=str(tmp_path)))
    assert said == dict(ok=True, path=str(tmp_path)) and tracing.enabled()
    with tracing.span("step"):
        train(cfg, engine)
    done = worker._handle_command("profiler", dict(action="stop"))
    assert done == dict(ok=True, path=str(tmp_path), spans=2, programs=1)
    capture = tracing.last_capture()
    assert [s["name"] for s in capture.spans] == ["step", "engine:train"]
    assert os.path.exists(parts.programs_path(str(tmp_path)))
    assert parts.newest_profile(str(tmp_path)) is not None
    # nothing running: said so, not raised
    assert worker._handle_command("profiler", dict(action="stop")) == dict(
        ok=False, error="no capture is running")
    with pytest.raises(ValueError):
        worker._handle_command("profiler", dict(action="pause"))
