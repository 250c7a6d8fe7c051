"""Own device seconds of the profiled steps by what the PROGRAM says
each operation is: the join of the capture's ``programs`` (the engine's
``ProgramFacts``: instruction -> part, pass, opcode, phase, read from
each compiled program's ``op_name``s; ``realhf_tpu/obs/parts.py``) and
the device operations of the capture's trace file.

The readers ``layer_metrics/train.*_s.py``, ``gen.*_s.py`` and
``engine.program_gb.py`` come here. The trace file is parsed ONCE a
capture, whatever the number of readers. Everything returns None where
the capture has no ``programs`` (a commit before them), nothing was
profiled, or the trace holds no device operation (the CPU).
"""

import collections
import glob
import os

from benchmark import program_capture, trace_reduce

#: profile_dir -> rows(), of the newest capture only
_CACHE = {}
COLLECTIVE = ("all-reduce", "reduce-scatter", "all-gather", "all-to-all",
              "collective-permute", "collective-broadcast")


def capture():
    """The profiled capture, if the program explains its programs."""
    got = program_capture.last(program_capture.profiled)
    if got is None or not getattr(got, "programs", None):
        return None
    return got


def facts_by_module(programs):
    """Module name -> facts, for the module names under which the
    capture holds ONE text (a trace cannot tell two apart)."""
    seen = collections.defaultdict(list)
    for facts in programs.values():
        seen[facts["module"]].append(facts)
    return {mod: fs[0] for mod, fs in seen.items() if len(fs) == 1}


def rows(got):
    """[(module, part, pass, opcode, phase, own seconds)], summed over
    the chips of the capture's trace, one row an operation; None where
    there is no trace file or no device operation in it. An operation
    its program's text does not name has part, opcode and phase None
    and pass "?"."""
    if got.profile_dir in _CACHE:
        return _CACHE[got.profile_dir]
    files = sorted(glob.glob(os.path.join(
        got.profile_dir, "**", "*.xplane.pb"), recursive=True))
    out = None
    if files:
        trace = trace_reduce.read_xplane(files[-1])
        known = facts_by_module(got.programs)
        own = collections.defaultdict(float)
        for dev in trace["devices"].values():
            named = trace_reduce.with_module(dev["ops"], dev["modules"])
            for name, secs in trace_reduce.self_seconds(named).items():
                own[name] += secs
        out = []
        for name, secs in own.items():
            module, _, op = name.partition("/")
            facts = known.get(module)
            if facts is None:
                continue
            part, pass_, opcode, phase = (
                facts["ops"].get(op.split(" ")[0])
                or (None, "?", None, None, ""))[:4]
            out.append((module, part, pass_, opcode, phase, secs))
        out = out or None
    _CACHE.clear()
    _CACHE[got.profile_dir] = out
    return out


def seconds_a_step(record, module_prefix, where):
    """Own device seconds a step and chip of the operations of the
    programs whose module starts with ``module_prefix`` for which
    ``where(part, pass, opcode, phase)`` holds; None where there is
    nothing to read or the cell ran no such program."""
    got = capture()
    if got is None:
        return None
    table = rows(got)
    steps = len(got.named("step"))
    if not table or not steps:
        return None
    mine = [r for r in table if r[0].startswith(module_prefix)]
    if not mine:
        return None
    return sum(r[5] for r in mine if where(*r[1:5])) \
        / steps / record["chips"]


def train(record, *parts):
    """Seconds a step of ``parts`` in the train program(s)."""
    return seconds_a_step(
        record, "jit_train_",
        lambda part, pass_, opcode, phase:
        (part or "").split("/")[0] in parts)


def generate(record, where):
    return seconds_a_step(record, "jit_generate", where)


def is_collective(opcode):
    return (opcode or "").replace("-start", "").replace("-done", "") \
        in COLLECTIVE
