"""Blocked wall of the inference MFCs (``ref_inf``, ``rew_inf``): the
union of their intervals, since the runner runs one level of the graph
in threads. Median over the steady steps."""


def read(record):
    return record["medians"].get("inf")
