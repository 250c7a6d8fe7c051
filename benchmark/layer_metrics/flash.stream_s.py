"""Own device seconds a step and chip of the flash kernels that STREAM
their blocks, the operations of the profiled steps' trace whose name
holds ``_stream`` (``flash_fwd_stream``, ``flash_bwd_dq_stream``,
``flash_bwd_dkv_stream``: the kernels a packed row past 4096 tokens
takes, which hold one block of K and V, or of Q and dO, a grid step;
``realhf_tpu/ops/flash_attention.py``). The kernels alone: what XLA
puts around them is in ``train.attn_s``, which holds these seconds too.
Nothing where nothing was profiled or the trace holds no such kernel (a
commit before them, a cell whose rows the whole-row kernels take)."""

from benchmark import stream_kernels


def read(record):
    got = stream_kernels.seconds_and_calls()
    if got is None:
        return None
    capture, ran = got
    steps = len(capture.named("step"))
    if not steps:
        return None
    return sum(secs for secs, _ in ran.values()) / steps / record["chips"]
