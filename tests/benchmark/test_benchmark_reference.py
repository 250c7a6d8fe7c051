"""Each family's plain reference against the engine at a tiny width on
the CPU: the float32 forward, reading the checkpoint the benchmark
wrote under Hugging Face's names, agrees with the program's forward on
the weights its own loader read. The families are found as a run finds
them, by the ``family`` of the cell's configuration; ``gpt2`` lives
wholly in the tests' directory."""

import jax
import numpy as np
import pytest
from tiny_cells import MANIFEST

from benchmark import generate, reference, run

CELLS = {"qwen2": "tiny.sft", "mistral": "tiny.grpo-realloc",
         "gpt2": "tiny-gpt2.sft"}


def engine_logprobs(family, ckpt, ids):
    from realhf_tpu.api.config import ModelName
    from realhf_tpu.engine.engine import Engine
    from realhf_tpu.models.hf import registry
    from realhf_tpu.parallel import mesh as mesh_lib

    cfg, params = registry.load_hf_checkpoint(ckpt, family)
    cfg.param_dtype = "bfloat16"
    par = mesh_lib.ParallelismConfig()
    ctx = mesh_lib.MeshContext(
        ModelName("default", 0),
        mesh_lib.make_mesh(par, jax.devices()[:1]), par)
    engine = Engine(cfg, ctx, params)
    return np.asarray(engine.forward_logprobs(ids, np.ones_like(ids)),
                      np.float32)[:, :-1]


@pytest.fixture(scope="module", params=sorted(CELLS))
def case(request, tmp_path_factory):
    cell = run.load_cell(MANIFEST, CELLS[request.param])
    hf, family = cell["hf"], cell["family"]
    assert cell["meta"]["family"] == request.param
    ckpt = str(tmp_path_factory.mktemp(request.param))
    generate.write_checkpoint(ckpt, family, hf, seed=7)
    ids = generate.fixed_batch(hf, seed=7, rows=2, length=64)
    tensors = reference.load_tensors(ckpt)
    kinds = {name.format(i): kind
             for name, (shape, kind) in family.shapes(hf).items()
             for i in (range(shape[0]) if "{}" in name else [0])}
    return dict(hf=hf, name=request.param, family=family, ckpt=ckpt,
                ids=ids, tensors=tensors, kinds=kinds,
                want=family.logprobs(hf, tensors, ids))


def test_engine_agrees_with_the_reference(case):
    got = engine_logprobs(case["name"], case["ckpt"], case["ids"])
    gap, spread = reference.gap(got, case["want"])
    tolerance = case["family"].TOLERANCE
    assert got.shape == case["want"].shape == (2, 63)
    assert np.isfinite(case["want"]).all()
    # at toy widths bf16 against float32 is a few 1e-4 nat; the
    # log-probabilities themselves vary by far more
    assert gap < 0.005 < 0.05 < spread
    assert reference.within_tolerance(got, case["want"], tolerance)
    assert not reference.within_tolerance(
        got + (tolerance + 0.01) * spread, case["want"], tolerance)


def test_the_weights_are_what_the_seed_says(case):
    family, hf = case["family"], case["hf"]
    again = generate.make_weights(family, hf, seed=7)
    other = generate.make_weights(family, hf, seed=8)
    assert set(again) == set(case["tensors"]) == set(case["kinds"])
    for name in again:
        assert (np.asarray(case["tensors"][name], np.float32)
                == np.asarray(again[name], np.float32)).all()
        assert (np.asarray(again[name], np.float32)
                != np.asarray(other[name], np.float32)).any()
    assert all(v.dtype.name == "bfloat16" for v in again.values())
    assert sum(v.size for v in again.values()) == family.n_params(hf)


@pytest.mark.parametrize("dropped", ["norm", "bias"])
def test_a_dropped_piece_of_the_mathematics_shows(case, dropped):
    """Norm scales and biases are made away from 1 and 0 so that a
    forward without them leaves the tolerance."""
    mine = [n for n, kind in case["kinds"].items() if kind == dropped]
    if not mine:
        pytest.skip(f"{case['name']} has no {dropped}")
    broken = dict(case["tensors"])
    for name in mine:
        fill = np.ones_like if dropped == "norm" else np.zeros_like
        broken[name] = fill(broken[name])
    got = case["family"].logprobs(case["hf"], broken, case["ids"])
    gap, _ = reference.gap(got, case["want"])
    assert gap > 1e-3


def test_a_lower_precision_stands_out(case):
    """Weights rounded to float8 (e4m3), the next precision down, are
    several times farther from the reference than bf16 compute is."""
    import jax.numpy as jnp

    def to_f8(x):
        return x.astype(jnp.float8_e4m3fn).astype(jnp.bfloat16)
    got = engine_logprobs(case["name"], case["ckpt"], case["ids"])
    bf16_gap, _ = reference.gap(got, case["want"])
    f8 = case["family"].logprobs(case["hf"], case["tensors"], case["ids"],
                                 cast=to_f8)
    f8_gap, _ = reference.gap(f8, case["want"])
    assert f8_gap > 3 * bf16_gap


def test_a_window_is_refused():
    cell = run.load_cell(MANIFEST, CELLS["qwen2"])
    hf = dict(cell["hf"], use_sliding_window=True)
    with pytest.raises(NotImplementedError):
        cell["family"].logprobs(hf, {}, np.zeros((1, 4), np.int32))
