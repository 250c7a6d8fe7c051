"""The chip's published peaks, and the arithmetic that is the same for
every architecture. What depends on one (parameters, the FLOPs of a
forward, the bytes a decode step streams) is its family's
(``families/<family>.py``). ``PEAKS`` was copied from ``bench.py``
(``DEVICE_PEAKS``); PERF.md lists the original for a later PR to
delete.
"""

#: Per-chip peaks keyed by ``jax.devices()[0].device_kind``. Source:
#: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16, 16 GB of
#: HBM at 819 GB/s. A device that is not in the table is an error, not
#: a default.
PEAKS = {
    "TPU v5 lite": dict(flops=197e12, hbm_bw=819e9, hbm_bytes=16e9),
}


def peaks(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks for device kind {device_kind!r}: add a sourced "
            "row to benchmark/arith.py PEAKS")
    return PEAKS[device_kind]


def train_flops(family, hf, seqlens):
    """Forward and backward, the backward at twice the forward; the
    forward that rematerialization repeats is NOT counted (model FLOPs,
    not hardware FLOPs)."""
    return 3 * family.forward_flops(hf, seqlens)
