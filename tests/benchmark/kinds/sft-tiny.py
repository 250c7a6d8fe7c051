"""A kind that lives wholly in the tests' directory: the harness finds
it by the name in the traffic file, as it would find one a later PR
adds. It is ``sft`` under another name."""

from benchmark.kinds.sft import *  # noqa: F401,F403
