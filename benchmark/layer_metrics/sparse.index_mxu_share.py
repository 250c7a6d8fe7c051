"""The indexer's scores' share of the chip's matrix peak, in percent:
the FLOPs of the score products AS WRITTEN for one step's forward
(``family.index_flops``: ``heads x dim x 2`` a CAUSAL (query, key) pair
a layer; no backward, no gradient reaches an indexer) over the own
device seconds a step of sub-part ``index/scores`` in the train program
of the profiled steps and the chip's peak. WHATEVER implements the
scores is read by this yardstick: the program multiplies whole blocks
of 512 queries by every key of the row, under the diagonal or not, and
sums the heads outside the product, so it cannot read over 50% at one
document a row, and a later kernel that skips what lies above the
diagonal cannot read over 100%. Nothing where the family counts no such
FLOPs, nothing was profiled, or the program has no such sub-part (a
commit before it)."""

from benchmark import program_parts


def read(record):
    flops_of = getattr(record["family"], "index_flops", None)
    if flops_of is None:
        return None
    seconds = program_parts.seconds_a_step(
        record, "jit_train_",
        lambda part, pass_, opcode, phase: part == "index/scores")
    if not seconds:
        return None
    traffic = record["traffic"]
    seqlens = [traffic["doc_len"]] * traffic["docs_per_step"]
    flops = flops_of(record["hf"], seqlens) / record["chips"]
    return 100.0 * flops / (seconds * record["peaks"]["flops"])
