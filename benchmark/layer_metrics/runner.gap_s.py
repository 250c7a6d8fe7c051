"""Seconds of a step's blocked wall that lie in none of its MFCs: the
runner's own work between them (selecting inputs, merging outputs,
threads). Median over the steady steps."""


def read(record):
    return record["medians"]["gap"]
