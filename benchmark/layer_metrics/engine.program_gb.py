"""The largest compiled program of the profiled steps as the COMPILER
counts it, in GB on one chip: ``argument_size_in_bytes`` +
``temp_size_in_bytes`` of ``compiled.memory_analysis()``, the largest
among the programs that ran under an ``engine:*`` span of the capture
(``Capture.programs``; gauge ``engine_program_bytes``). The device's
``peak_bytes_in_use`` is blind to a program's temporaries; this is what
a program needs to run at all, and what a remat decision moves. Nothing
where the capture has no ``programs`` (a commit before them) or nothing
was profiled."""

from benchmark import program_parts


def read(record):
    got = program_parts.capture()
    if got is None:
        return None
    return max(f["memory"]["argument_size_in_bytes"]
               + f["memory"]["temp_size_in_bytes"]
               for f in got.programs.values()) / 1e9
