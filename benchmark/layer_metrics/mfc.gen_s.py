"""Blocked wall of the generation MFC (``actor_gen``), without the
reshard that runs inside it, which ``reshard.s`` reports. Median over
the steady steps; nothing where the cell generates nothing."""


def read(record):
    return record["medians"].get("gen")
