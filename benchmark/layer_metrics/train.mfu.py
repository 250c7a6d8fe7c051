"""Model FLOP utilization of the train MFC, in percent: the FLOPs
that the forward and backward of one step need (``arith.train_flops``,
recomputation not counted) over the MFC's blocked wall, the chips and
the chip's peak. A wall-clock utilization of one MFC: the MFC's host
work is inside, and it is not a kernel's roofline share."""


def read(record):
    peak = record["chips"] * record["peaks"]["flops"]
    return 100.0 * record["work"]["train_flops"] \
        / (record["medians"]["train"] * peak)
