"""The flash kernels' share of the chip's matrix peak, in percent: the
FLOPs of the matrix products the three kernels run over the block pairs
they VISIT (``family.flash_flops``: forward 2 products a block pair, dq
pass 3, dkv pass 4, whole blocks, every layer at its own heads and
window) over the own device seconds of every operation whose name holds
``flash_fwd``, ``flash_bwd_dq`` or ``flash_bwd_dkv`` in the profiled
steps' trace, times the peak. A compute roofline share: at head size
128 a block pair's products outweigh its bytes by far.

How often the kernels ran is read from the trace itself: every call of
a kernel is one device operation, the dq pass runs once a layer and
microbatch, and under rematerialisation the forward kernel runs more
often than that (twice where the backward recomputes the layer), so the
forward's FLOPs count ``forward calls / dq calls`` times. The reduced
trace keeps ten operations and an unrolled stack names each layer's
kernel apart, so this reads the trace file again. Nothing where the
family counts no such FLOPs, nothing was profiled, or the trace holds
no such kernel (a commit before them, the XLA path)."""

import glob
import os

from benchmark import program_capture, trace_reduce

KERNELS = dict(fwd="flash_fwd", dq="flash_bwd_dq", dkv="flash_bwd_dkv")


def kernel_seconds_and_calls(trace):
    """kernel -> (own seconds, calls), over the chips of the trace. An
    operation's name in a v5e trace is its whole HLO line, operands
    and all (``%fusion.3 = ... fusion(... %flash_bwd_dq.65)``): only
    what stands before `` = `` is the operation itself."""
    out = {k: [0.0, 0] for k in KERNELS}
    for dev in trace["devices"].values():
        ops = [(n.partition(" = ")[0], s, e) for n, s, e in dev["ops"]]
        own = trace_reduce.self_seconds(ops)
        for key, name in KERNELS.items():
            out[key][0] += sum(s for n, s in own.items() if name in n)
            out[key][1] += sum(1 for n, _, _ in ops if name in n)
    return out


def read(record):
    flops_of = getattr(record["family"], "flash_flops", None)
    capture = program_capture.last(program_capture.profiled)
    if flops_of is None or capture is None:
        return None
    files = sorted(glob.glob(os.path.join(
        capture.profile_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        return None
    ran = kernel_seconds_and_calls(trace_reduce.read_xplane(files[-1]))
    secs = sum(s for s, _ in ran.values())
    if not secs or not ran["dq"][1]:
        return None
    traffic, hf = record["traffic"], record["hf"]
    rows = [traffic["doc_len"] * traffic["docs_per_row"]] \
        * (traffic["docs_per_step"] // traffic["docs_per_row"])
    step = flops_of(hf, rows)  # one forward and one backward of a step
    layers = len(hf.get("layer_types") or [None] * hf["num_hidden_layers"])
    steps = ran["dq"][1] / (layers * len(rows))
    flops = steps * (step["fwd"] * ran["fwd"][1] / ran["dq"][1]
                     + step["dq"] + step["dkv"])
    return 100.0 * flops / (secs * record["peaks"]["flops"])
