"""Holding the engine to a family's plain reference: reading the
checkpoint's tensors without the program's loader, and the comparison
that decides this part of ``correct``. The forward itself and its
tolerance are the family's (``families/<family>.py``)."""

import os

import numpy as np


def load_tensors(ckpt_dir):
    """HF name -> bf16 numpy array, from the directory's safetensors."""
    import safetensors.numpy
    out = {}
    for name in sorted(os.listdir(ckpt_dir)):
        if name.endswith(".safetensors"):
            out.update(safetensors.numpy.load_file(
                os.path.join(ckpt_dir, name)))
    return out


def gap(engine_lp, ref_lp):
    """mean |delta log-prob| over the batch, and the spread of the
    reference's own log-probabilities, which the gap is held against."""
    d = np.abs(np.asarray(engine_lp, np.float32) - ref_lp)
    return float(d.mean()), float(ref_lp.std())


def within_tolerance(engine_lp, ref_lp, tolerance):
    """``tolerance`` is the family's: the allowed mean |delta| as a
    share of the reference's spread."""
    mean_abs_delta, spread = gap(engine_lp, ref_lp)
    return bool(np.isfinite(engine_lp).all()
                and mean_abs_delta <= tolerance * spread)
