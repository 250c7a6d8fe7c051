"""A reader that lives wholly in the tests' directory: how many steps
the record holds."""


def read(record):
    return len(record["steps"])
