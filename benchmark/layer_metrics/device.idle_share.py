"""Share, in percent, of the traced window in which no operation ran
on the device: 1 - busy / window, busy the union of the device
operations' intervals, the mean over the chips used."""


def read(record):
    if record["trace"] is None:
        return None
    return 100.0 * record["trace"]["idle_share"]
