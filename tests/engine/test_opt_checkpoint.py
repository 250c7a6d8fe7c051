"""Optimizer-state checkpoint round-trip (EXCEEDS reference §5.4,
which restarts Adam from zero after recovery)."""

import numpy as np

import jax

from realhf_tpu.api.config import ModelName
from realhf_tpu.engine import opt_checkpoint
from realhf_tpu.engine.engine import Engine
from realhf_tpu.engine.optim import OptimizerConfig
from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.parallel.mesh import MeshContext, ParallelismConfig, make_mesh


def _cfg(param_dtype="float32"):
    return TransformerConfig(
        n_layers=2, n_kv_heads=2, n_q_heads=4, hidden_dim=32,
        intermediate_dim=64, vocab_size=64, apply_rotary=True,
        layer_norm_type="rms", mlp_type="llama", use_attention_bias=False,
        use_attn_proj_bias=False, use_mlp_bias=False,
        activation_function="silu", compute_dtype="float32",
        param_dtype=param_dtype)


def _engine(cfg, seed=0):
    parallel = ParallelismConfig(data_parallel_size=4,
                                 tensor_parallel_size=2)
    ctx = MeshContext(ModelName("oc", 0), make_mesh(parallel), parallel)
    return Engine(cfg, ctx, T.init_params(cfg, jax.random.PRNGKey(seed)),
                  optimizer=OptimizerConfig(lr=1e-2,
                                            warmup_steps_proportion=0.0,
                                            lr_scheduler_type="constant"),
                  total_train_steps=100)


def _loss(cfg):
    def f(p, h, mb):
        return (T.lm_logits(cfg, p, h) ** 2).mean(), {}
    return f


def test_roundtrip_resumes_identically(tmp_path):
    """Save after step 1; a FRESH engine restoring the state and the
    weights must produce bit-matching params after step 2."""
    # bf16 exercises the uint16 view round-trip and the fp32 master
    cfg = _cfg(param_dtype="bfloat16")
    rng = np.random.default_rng(0)
    ids = rng.integers(2, 60, size=(8, 16)).astype(np.int32)
    mb = dict(input_ids=ids, seg_ids=np.ones_like(ids))

    e1 = _engine(cfg)
    e1.train_batch([mb], _loss(cfg), loss_fn_key="oc")
    opt_checkpoint.save_opt_state(str(tmp_path), e1.opt_state_numpy())
    saved_params = e1.params_numpy()
    e1.train_batch([mb], _loss(cfg), loss_fn_key="oc")  # step 2 (truth)

    e2 = _engine(cfg, seed=1)  # different init
    e2.set_params(saved_params)
    assert opt_checkpoint.restore_engine_opt_state(e2, str(tmp_path))
    e2.train_batch([mb], _loss(cfg), loss_fn_key="oc")  # step 2 (resumed)

    for a, b in zip(jax.tree.leaves(e1.params), jax.tree.leaves(e2.params)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_structure_mismatch_skips(tmp_path):
    cfg = _cfg()
    e1 = _engine(cfg)
    opt_checkpoint.save_opt_state(str(tmp_path), e1.opt_state_numpy())
    cfg2 = _cfg(param_dtype="bfloat16")  # master-weights state differs
    e2 = _engine(cfg2)
    assert not opt_checkpoint.restore_engine_opt_state(e2, str(tmp_path))


def test_missing_file_returns_false(tmp_path):
    e = _engine(_cfg())
    assert not opt_checkpoint.restore_engine_opt_state(e, str(tmp_path))


def test_corrupt_file_surfaces_reason_not_silence(tmp_path, caplog):
    """ISSUE 4 satellite: a corrupt/short optimizer-state file must
    name the shard path and why it is unusable, both in the log and
    to the caller -- never a bare None."""
    import logging as _logging

    f = tmp_path / opt_checkpoint.FILENAME
    f.write_bytes(b"PK\x03\x04 definitely not a real zip")
    # the realhf_tpu root logger sets propagate=False; let caplog see it
    root = _logging.getLogger("realhf_tpu")
    root.propagate = True
    try:
        with caplog.at_level(_logging.WARNING):
            leaves, reason = opt_checkpoint.load_opt_state_checked(
                str(tmp_path))
    finally:
        root.propagate = False
    assert leaves is None
    assert reason is not None
    assert str(f) in reason  # the shard path is named
    assert any(str(f) in r.getMessage() for r in caplog.records)
    # legacy API still degrades to None (reason already logged)
    assert opt_checkpoint.load_opt_state(str(tmp_path)) is None


def test_short_file_reports_expected_vs_actual_leaves(tmp_path):
    """Truncate the member list (drop the last leaf): the reason names
    how many leaves were present vs expected."""
    import zipfile

    e = _engine(_cfg())
    opt_checkpoint.save_opt_state(str(tmp_path), e.opt_state_numpy())
    src = tmp_path / opt_checkpoint.FILENAME
    n_leaves = len(e.opt_state_numpy())
    # rewrite the npz without its last leaf member
    tmp = tmp_path / "short.npz"
    with zipfile.ZipFile(str(src)) as zin, \
            zipfile.ZipFile(str(tmp), "w") as zout:
        for item in zin.infolist():
            if item.filename == f"l{n_leaves - 1}.npy":
                continue
            zout.writestr(item, zin.read(item.filename))
    tmp.replace(src)
    leaves, reason = opt_checkpoint.load_opt_state_checked(str(tmp_path))
    assert leaves is None
    assert f"{n_leaves - 1} of {n_leaves}" in reason
    e2 = _engine(_cfg())
    assert not opt_checkpoint.restore_engine_opt_state(e2, str(tmp_path))
