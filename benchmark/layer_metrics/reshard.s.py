"""Blocked wall of the move of the actor's weights from the training
layout to the generation replica (``ReplicaManager.ensure_fresh``).
Median over the steady steps; nothing where no replica exists."""


def read(record):
    return record["medians"]["reshard"] or None
