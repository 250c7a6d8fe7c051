"""Blocked wall of the train MFC (``actor_train``, or SFT's
``trainDefault``). Median over the steady steps."""


def read(record):
    return record["medians"]["train"]
