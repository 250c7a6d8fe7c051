"""The state-space recurrence's share of the chip's matrix peak, in
percent: the FLOPs of the recurrence AS WRITTEN for one step's forward
and backward (``family.ssm_flops`` x 3: two products of 2 x P x N a head
a token a layer, 2.10 MFLOP a token a layer at 64 heads of 64 and a
state of 128, the backward at twice the forward, the rematerialised
forward not counted) over the own device seconds a step of sub-part
``ssm/scan`` (``ssm.scan_s.py``'s reading, whose reader this calls) and
the chip's peak. WHATEVER implements the recurrence is read by this
yardstick: the chunked form runs more than those FLOPs in products of
other shapes, a later kernel may run fewer, and neither can read over
100%. Low single digits say that the scan's time is not its products'.
Nothing where the family counts no such FLOPs, nothing was profiled, or
the program has no such sub-part (a commit before it)."""

import importlib.util
import os


def _scan_seconds():
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        "ssm.scan_s.py")
    spec = importlib.util.spec_from_file_location(
        "benchmark_file_ssm_scan_s", path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def read(record):
    flops_of = getattr(record["family"], "ssm_flops", None)
    seconds = _scan_seconds().read(record)
    if flops_of is None or not seconds:
        return None
    traffic = record["traffic"]
    seqlens = [traffic["doc_len"]] * traffic["docs_per_step"]
    flops = 3 * flops_of(record["hf"], seqlens) / record["chips"]
    return 100.0 * flops / (seconds * record["peaks"]["flops"])
