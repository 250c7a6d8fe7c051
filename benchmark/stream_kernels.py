"""The flash kernels that stream their blocks (a packed row past the
longest one whose K and V the kernels hold whole:
``realhf_tpu/ops/flash_attention.py``, names that end in ``_stream``),
read from the profiled steps' trace file: what
``layer_metrics/flash.stream_s.py`` and ``flash.stream_hbm_share.py``
share. Like ``flash.mxu_share`` it reads the trace file itself: the
reduced trace keeps ten operations, and an unrolled stack names each
layer's kernel apart."""

import glob
import os

from benchmark import program_capture, trace_reduce

#: what the name of a kernel that streams its blocks holds
MARK = "_stream"
KERNELS = dict(fwd="flash_fwd" + MARK, dq="flash_bwd_dq" + MARK,
               dkv="flash_bwd_dkv" + MARK)


def seconds_and_calls():
    """``(capture, {kernel: (own seconds, calls)})`` over the chips of
    the profiled capture's trace; None where nothing was profiled,
    there is no trace file, or it holds no such kernel (a commit
    before them, rows that the whole-row kernels take, the XLA
    path)."""
    capture = program_capture.last(program_capture.profiled)
    if capture is None:
        return None
    files = sorted(glob.glob(os.path.join(
        capture.profile_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        return None
    out = {k: [0.0, 0] for k in KERNELS}
    for dev in trace_reduce.read_xplane(files[-1])["devices"].values():
        # an operation's name is its whole HLO line: what stands before
        # " = " is the operation itself (flash.mxu_share.py)
        ops = [(n.partition(" = ")[0], s, e) for n, s, e in dev["ops"]]
        own = trace_reduce.self_seconds(ops)
        for key, name in KERNELS.items():
            out[key][0] += sum(s for n, s in own.items() if name in n)
            out[key][1] += sum(1 for n, _, _ in ops if name in n)
    if not any(calls for _, calls in out.values()):
        return None
    return capture, {k: tuple(v) for k, v in out.items()}
