"""The honesty metric of the ``setup.*`` family, as ``train.unscoped_s``
is of the step's: seconds of the set-up capture before its warm-up
``step`` span that no ``setup:*`` span covers. With ``setup.import_s``,
``setup.data_s`` and ``setup.weights_s`` it is the inside reading of
``entry.load_s``; over 5% of ``setup.program_s`` says a span is
missing. Moves ``setup_s``."""

from benchmark import setup_capture


def read(record):
    return setup_capture.read("unattributed_s")
