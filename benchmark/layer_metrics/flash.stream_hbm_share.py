"""The stream kernels' share of the chip's HBM bandwidth, in percent:
the bytes they must move by the mathematics' count of the kernels as
built (``family.flash_stream_bytes``: Q, dO and O once, the
log-sum-exp and delta as the forward writes and the dq pass reads
them, over 128 lanes; K and V one block a visit of a key block by a
query block's GROUP of heads, and in the dkv pass Q and dO a visit
likewise) over the own device seconds of
the operations whose name holds ``_stream`` (``flash.stream_s``), over
the peak in ``arith.PEAKS``. The memory roofline beside
``flash.mxu_share``'s compute one: a kernel that fetched K and V once
a query HEAD would sit at the chip's ridge (256 FLOP a byte against
240), one that serves a key/value head's query heads from one fetch
reads a few percent here and is bound by its products.

Every call of a kernel is one (layer, row); the family's count is over
all layers and the step's rows, so a call moves its mean, and a
forward kernel that rematerialisation runs twice counts twice. Nothing
where the family counts no such bytes, nothing was profiled, or the
trace holds no such kernel."""

from benchmark import stream_kernels


def read(record):
    bytes_of = getattr(record["family"], "flash_stream_bytes", None)
    got = stream_kernels.seconds_and_calls()
    if bytes_of is None or got is None:
        return None
    _, ran = got
    secs = sum(s for s, _ in ran.values())
    traffic, hf = record["traffic"], record["hf"]
    rows = [traffic["doc_len"] * traffic["docs_per_row"]] \
        * (traffic["docs_per_step"] // traffic["docs_per_row"])
    step = bytes_of(hf, rows)  # one forward and one backward of a step
    calls_a_pass = hf["num_hidden_layers"] * len(rows)
    moved = sum(step[k] * calls / calls_a_pass
                for k, (_, calls) in ran.items())
    if not secs or not moved:
        return None
    return 100.0 * moved / (secs * record["peaks"]["hbm_bw"])
