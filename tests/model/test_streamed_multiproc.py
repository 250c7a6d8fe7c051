"""Streamed checkpoint IO across PROCESS boundaries (VERDICT r4 #5):
a d2t2 mesh spanning two OS processes streams a load (per-layer
collective placement) and a save (per-layer collective gathers,
leader-only writes) with host RSS bounded well under the full model.
Reference analog: per-rank shard reads, ``conversion/hf_registry.py``.
"""

import os
import socket
import subprocess
import sys

import numpy as np
import pytest

import jax

from realhf_tpu.models import transformer as T
from realhf_tpu.models.config import TransformerConfig
from realhf_tpu.models.hf import save_hf_checkpoint

CHILD = os.path.join(os.path.dirname(__file__),
                     "streamed_multiproc_child.py")


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("", 0))
        return s.getsockname()[1]


@pytest.mark.slow  # full trial / multi-process, ~8-20 s (CHANGES.md, PR 22)
def test_streamed_roundtrip_two_processes(tmp_path):
    # ~29M params (~115 MB fp32): big enough that a full-model host
    # materialization visibly breaks the child's RSS bound, small
    # enough to keep the test fast.
    cfg = TransformerConfig(
        n_layers=6, n_kv_heads=4, n_q_heads=8, hidden_dim=512,
        intermediate_dim=1536, vocab_size=8192, n_positions=256,
        layer_norm_type="rms", mlp_type="llama",
        activation_function="silu", apply_rotary=True,
        use_attention_bias=False, use_attn_proj_bias=False,
        use_mlp_bias=False, compute_dtype="float32")
    params = T.init_params(cfg, jax.random.PRNGKey(0))
    ckpt = str(tmp_path / "ckpt")
    save_hf_checkpoint(ckpt, "llama", cfg,
                       jax.tree.map(np.asarray, params))
    out = str(tmp_path / "saved")

    coordinator = f"127.0.0.1:{_free_port()}"
    env = dict(
        os.environ,
        PYTHONPATH="/root/repo",
        JAX_PLATFORMS="cpu",
        XLA_FLAGS="--xla_force_host_platform_device_count=2",
    )
    procs = [
        subprocess.Popen(
            [sys.executable, CHILD, str(rank), "2", coordinator,
             ckpt, out],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
            text=True)
        for rank in range(2)
    ]
    try:
        outs = []
        for rank, p in enumerate(procs):
            stdout, _ = p.communicate(timeout=600)
            outs.append(stdout)
            assert p.returncode == 0, (
                f"child {rank} failed:\n{stdout}")
        assert all(f"CHILD{r} OK" in outs[r] for r in range(2)), outs
    finally:
        for p in procs:  # a deadlocked child must not outlive the test
            if p.poll() is None:
                p.kill()
