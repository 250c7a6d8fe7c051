"""The program names its parts, and a compiled program says which of
its device operations belongs to which (docs/observability.md).

Three things live here, none of which imports a model or the
benchmark:

- the NAMES: one ``jax.named_scope`` a part of the model's step
  (:data:`PARTS`), a phase of a program (:data:`PHASES`) and a step of
  the experts' dispatch (:data:`EXPERT_STEPS`). ``models/``, ``ops/``,
  ``engine/`` enter them; a scope is metadata and compiles to nothing.
- the RULE that reads them back (:func:`parse_program`): from the
  compiled program's text, every instruction the device runs as an
  operation of its own -> ``(part, pass, opcode, phase, tail)``; and
  :class:`ProgramFacts`, what the engine keeps of a compiled program
  (:func:`read_program`): that table, the XLA module's name, a
  fingerprint of the text and the compiler's count of its memory.
- the operator's READER: ``python -m realhf_tpu.obs.parts
  <profile_dir>`` joins the ``programs.json`` that ``tracing.stop()``
  wrote beside a profile to the device operations of its
  ``.xplane.pb`` and prints, a program, own device seconds by part and
  pass, the largest operations and the memory line.
"""

import collections
import dataclasses
import glob
import hashlib
import json
import os
import re
import sys
from typing import Any, Callable, Dict, Iterable, List, Optional, Tuple

EMBED = "embed"
ATTN_PROJ = "attn_proj"
ATTN = "attn"
CONV = "conv"
#: a delta layer's mixer (models/config.py:DeltaConfig): the norm
#: before it, its projections, short convolutions and gates, the
#: output's norm and gate, ``wo``; the recurrence is its sub-scope
DELTA = "delta"
#: an ssm layer's mixer (models/config.py:SsmConfig): the norm before
#: it, ``w_in``, its short convolution, the gate and the grouped norm,
#: ``w_out``; the chunked scan is its sub-scope
SSM = "ssm"
#: a sparse layer's indexer (models/config.py:IndexerConfig): what
#: picks the keys its attention runs over; three sub-scopes
INDEX = "index"
MLP = "mlp"
SHARED_EXPERT = "shared_expert"
EXPERTS = "experts"
VOCAB_HEAD = "vocab_head"
#: a looped model's exit gate (models/config.py:
#: TransformerConfig.exit_gate): its projection of every pass's final
#: hidden state, the exit distribution over the passes, the passes'
#: losses weighed by it and its entropy
#: (``ops/functional.py:exit_log_distribution``, ``interfaces/sft.py``)
EXIT = "exit"
LOSS = "loss"
GRAD_ACCUM = "grad_accum"
OPTIMIZER = "optimizer"
#: the layer loop's own work: a layer's weights sliced out of their
#: stack, what the backward keeps stacked a layer and read back
LAYERS = "layers"
#: what a device operation can be put down to: the INNERMOST of these
#: in its ``op_name`` is its part
PARTS = (EMBED, LAYERS, ATTN_PROJ, ATTN, CONV, DELTA, SSM, INDEX, MLP,
         SHARED_EXPERT, EXPERTS, VOCAB_HEAD, EXIT, LOSS, GRAD_ACCUM,
         OPTIMIZER)

FORWARD_BACKWARD = "forward_backward"
PREFILL, DECODE, SAMPLE = "prefill", "decode", "sample"
#: the stretches of a program the parts nest in: the OUTERMOST of
#: these in an ``op_name`` is the operation's phase (a decode step's
#: attention is part ``attn`` in phase ``decode``)
PHASES = (FORWARD_BACKWARD, PREFILL, DECODE, SAMPLE)

ROUTE, GATHER, PRODUCTS, COMBINE = "route", "gather", "products", "combine"
#: the router's product ALONE, where it reads the layer's input and
#: so runs before the layer's operator (``MoEConfig.router_input``:
#: ``ops/moe.py``); elsewhere it is part of ``experts/route``
ROUTER = "router"
#: sub-scopes of ``experts``: the part then reads ``experts/route``
EXPERT_STEPS = (ROUTE, GATHER, PRODUCTS, COMBINE, ROUTER)
#: sub-scope of ``attn``: the flash kernels that stream their blocks
#: (a row past ``ops/flash_attention.py:FLASH_MAX_LEN``) and what the
#: program does around them; the part then reads ``attn/stream``
STREAM = "stream"
#: sub-scope of ``attn_proj`` in a latent layer: what makes keys and
#: values from the compressed row (its projection, the norm, the
#: expansion a head, the shared rotary key's rotation and broadcast);
#: the part then reads ``attn_proj/latent``
LATENT = "latent"
#: sub-scope of ``delta`` and of ``ssm``: the chunked recurrence alone
#: (``ops/delta_rule.py``, ``ops/ssm_scan.py``); the part then reads
#: ``delta/scan``, ``ssm/scan``
SCAN = "scan"
#: sub-scope of ``layers`` in a looped model (``TransformerConfig.
#: n_passes``): the loop of passes' own work around the layer scans
#: (every pass's final hidden state and gate logit stacked for the
#: objective, a pass's carry); the part then reads ``layers/loop``, and
#: a layer scan's own work inside a pass (weights out of their stack,
#: kept residuals, the shared weights' gradients added into their
#: accumulator's rows) stays ``layers``
LOOP = "loop"
PROJECT, SCORES, SELECT = "project", "scores", "select"
#: sub-scopes of ``index``: the indexer's projections, norm and rotary
#: (``index/project``), its scores of every (query, key) pair
#: (``index/scores``) and the choice of the ``topk`` best a query
#: (``index/select``; ``ops/sparse_index.py``)
INDEX_STEPS = (PROJECT, SCORES, SELECT)
#: part -> the sub-scopes that may stand inside it
SUB_STEPS = {EXPERTS: EXPERT_STEPS, ATTN_PROJ: (LATENT,), ATTN: (STREAM,),
             DELTA: (SCAN,), SSM: (SCAN,), INDEX: INDEX_STEPS,
             LAYERS: (LOOP,)}

FWD, REMAT, BWD = "fwd", "remat", "bwd"
#: scope of gradient arithmetic that runs in a FORWARD rule (the head
#: of a weighted sum forms ``dlogits`` and runs its two gradient
#: products in the chunk that has the logits,
#: ``ops/functional.py:weighted_logprob_sum``): pass ``bwd``, whatever
#: the path says of transposition
GRADIENT = "gradient"
#: the pass of an operation whose ``op_name`` the compiler wrote
UNKNOWN = "?"
#: opcodes that hold the device's operation line for communication
COLLECTIVES = tuple(
    op + suffix for op in ("all-reduce", "reduce-scatter", "all-gather",
                           "all-to-all", "collective-permute",
                           "collective-broadcast")
    for suffix in ("", "-start", "-done"))
#: opcodes of a matrix product in a compiled program's text (XLA:TPU
#: writes most of a model's dots as convolutions)
PRODUCTS_OPCODES = ("dot", "convolution")
#: instructions that are never an operation of the device's line
_NO_OPERATION = ("parameter", "constant", "get-tuple-element", "tuple")
#: fields of ``CompiledMemoryStats`` a program's facts keep
MEMORY_FIELDS = ("argument_size_in_bytes", "output_size_in_bytes",
                 "alias_size_in_bytes", "temp_size_in_bytes",
                 "generated_code_size_in_bytes")
#: characters kept of the end of an ``op_name``
TAIL = 64
#: file ``tracing.stop()`` writes beside a profile's ``.xplane.pb``
PROGRAMS_FILE = "programs.json"

_INSTRUCTION = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = ")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .*\{$")
_OPCODE = re.compile(r"[ )]([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
#: computations whose instructions are no operations of their own: a
#: fusion's (or an async wrapper's) body, and the scalar functions a
#: reduce, sort, scatter or all-reduce applies
_INNER = re.compile(r"\b(calls|to_apply|select|scatter)=%?([\w.\-]+)")
#: the computations a line without an ``op_name`` calls
_CALLED = re.compile(r"\b(?:calls|true_computation|false_computation)="
                     r"%?([\w.\-]+)")
_BRANCHES = re.compile(r"\bbranch_computations=\{([^}]*)\}")
_OPERAND = re.compile(r"%([\w.\-]+)")
_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
#: transformations jax wraps around a scope's name in an ``op_name``
_WRAPPER = re.compile(r"^(?:jvp|transpose|checkpoint|remat\d*|vmap)"
                      r"\((.*)\)$")
#: what this jax (0.9.0) puts in the path of a rematerialised forward
_REMAT_MARK = "rematted_computation"
#: ``op_name``s XLA:TPU writes ITSELF on operations it makes of ours,
#: dropping the path: ``lax.ragged_dot`` becomes a kernel of the
#: compiler's own, and the experts' grouped products are the only
#: ``ragged_dot`` in the tree
COMPILER_MADE = {"ragged-dot-none": f"{EXPERTS}/{PRODUCTS}",
                 "ragged-dot-metadata": f"{EXPERTS}/{PRODUCTS}"}
#: how far from its user an operation made to feed it may stand
FEEDS_HOPS = 4


def components(op_name: str) -> List[str]:
    """The path components of an ``op_name``, split at the slashes
    outside parentheses, each with its ``jvp(...)``,
    ``transpose(...)``, ``checkpoint`` / ``remat`` wrappers taken
    off: ``transpose(jvp(attn))`` -> ``attn``."""
    out, depth, at = [], 0, 0
    for i, c in enumerate(op_name + "/"):
        if c == "(":
            depth += 1
        elif c == ")":
            depth -= 1
        elif c == "/" and depth <= 0:
            out.append(op_name[at:i])
            at = i + 1
    bare = []
    for comp in out:
        m = _WRAPPER.match(comp)
        while m:
            comp = m.group(1)
            m = _WRAPPER.match(comp)
        bare.append(comp)
    return bare


def classify(op_name: str) -> Tuple[Optional[str], str, Optional[str]]:
    """``(part, pass, phase)`` of one ``op_name``.

    ``part``: the innermost component that is one of :data:`PARTS`
    (with the :data:`SUB_STEPS` scope inside it, where the part has
    them and there is one: ``experts/route``, ``attn_proj/latent``);
    None where no part claims the operation. ``pass``: ``remat`` in a
    rematerialised forward, else
    ``bwd`` where the path holds a ``transpose(`` or the scope
    :data:`GRADIENT`, else ``fwd``; ``?``
    for an ``op_name`` of :data:`COMPILER_MADE`.
    ``phase``: the outermost component of :data:`PHASES`, or None."""
    if op_name in COMPILER_MADE:  # the path, and the pass with it, is lost
        return COMPILER_MADE[op_name], UNKNOWN, None
    comps = components(op_name)
    part = phase = None
    for i, comp in enumerate(comps):
        if comp in PARTS:
            part = comp
            steps = SUB_STEPS.get(comp)
            if steps:
                step = next((c for c in comps[i + 1:]
                             if c in steps + PARTS), None)
                if step in steps:
                    part = f"{comp}/{step}"
        elif phase is None and comp in PHASES:
            phase = comp
    if _REMAT_MARK in comps:
        pass_ = REMAT
    elif "transpose(" in op_name or GRADIENT in comps:
        pass_ = BWD
    else:
        pass_ = FWD
    return part, pass_, phase


def opcode_of(line: str) -> str:
    """The HLO opcode of an instruction's line: the first lower-case
    word before a ``(`` after the `` = `` (shapes and tiled layouts
    hold none)."""
    m = _OPCODE.search(" " + line.partition(" = ")[2])
    return m.group(1) if m else ""


def parse_program(text: str) -> Dict[str, Tuple]:
    """Instruction name -> ``(part, pass, opcode, phase, tail)`` for
    every instruction of a compiled program's text that the device
    runs as an operation of its own (``tail``: the end of its
    ``op_name``, for a reader's eyes).

    The instruction is what stands before `` = `` on its line. A
    fusion takes the ``op_name`` of its CALLING line, so the
    instructions inside fused computations (``calls=``) are left out,
    as are the scalar functions a reduce or a sort applies
    (``to_apply=``); operations of a ``while`` body, a called
    computation or a conditional's branch are keyed by their own
    names. Parameters, constants and tuple plumbing are no operations.

    What the COMPILER made carries no scope of ours, so three rules
    stand behind :func:`classify`, in this order, each only where the
    one before found no part: a line the compiler left no ``op_name``
    (a fusion it cloned, a conditional) takes the last ``op_name``
    inside the computation it calls; an ``op_name`` the compiler wrote
    itself is looked up in :data:`COMPILER_MADE`; and an operation
    whose ``op_name`` holds NO name of ours, part or phase, and that
    was made to FEED another (a copy into another memory space, a zero
    buffer jax fills a conditional's unused residuals with) takes the
    part and phase of the first operation that uses it, through tuples
    and out of a branch into its conditional, at most
    :data:`FEEDS_HOPS` steps away; where no user has a part either (a
    copy out of fast memory into a loop's carry: its user is the
    body's tuple), those of the first operation it was made FROM, as
    far back. (What lies in a phase and in no part stays so: the draw
    of ``sample`` is not the next step's embedding lookup.)"""
    rows, inner, computation = [], set(), None
    inside = {}  # computation -> the last op_name among its lines
    known = {}   # instruction -> (part, pass, phase), operations or not
    users, makers = collections.defaultdict(list), {}
    roots, callers = {}, collections.defaultdict(list)
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        if m is None:
            c = _COMPUTATION.match(line)
            if c:
                computation = c.group(1)
            continue
        name, opcode = m.group(1), opcode_of(line)
        for attr, called in _INNER.findall(line):
            if not (attr == "to_apply" and opcode == "call"):
                inner.add(called)
        meta = _OP_NAME.search(line)
        op_name = meta.group(1) if meta else ""
        if op_name:
            inside[computation] = op_name
        calls = _CALLED.findall(line) + [
            c for found in _BRANCHES.findall(line)
            for c in _OPERAND.findall(found)]
        for called in calls:
            callers[called].append(name)
        if line.lstrip().startswith("ROOT "):
            roots[computation] = name
        head = line.partition(" = ")[2].partition(", metadata=")[0]
        makers[name] = _OPERAND.findall(head)
        for operand in makers[name]:
            users[operand].append(name)
        rows.append((computation, name, opcode, op_name,
                     () if op_name else calls))
    for called, root in roots.items():  # what a branch returns feeds
        users[root].extend(callers[called])  # the conditional
    ops = {}
    for computation, name, opcode, op_name, calls in rows:
        for called in calls:  # the compiler left the line no op_name
            op_name = op_name or inside.get(called, "")
        known[name] = classify(op_name)
        if computation not in inner and opcode not in _NO_OPERATION:
            ops.setdefault(name, known[name] + (
                opcode, "/".join(op_name.split("/")[-2:])[-TAIL:]))

    def near(name, towards):
        """(part, phase) of the first operation ``towards`` (the users
        or the makers of) ``name`` that has a part, breadth first."""
        level = [name]
        for _ in range(FEEDS_HOPS):
            level = [u for n in level for u in towards.get(n, ())]
            for other in level:
                if known.get(other, (None,))[0] is not None:
                    return known[other][0], known[other][2]
        return None, None

    out = {}
    for name, (part, pass_, phase, opcode, tail) in ops.items():
        if part is None and phase is None:  # no name of ours at all
            part, phase = near(name, users)
            if part is None:
                part, phase = near(name, makers)
        out[name] = (part, pass_, opcode, phase, tail)
    return out


def count_products(text: str, part: str, pass_: str) -> int:
    """The matrix products (:data:`PRODUCTS_OPCODES`) of a compiled
    program's text whose ``op_name`` :func:`classify` puts under
    ``part`` in ``pass_``, those inside fusion bodies included (a
    product is nearly always fused with what surrounds it); a loop's
    body counts once. ``count_products(text, ATTN_PROJ, REMAT)`` is
    the engine's ``attn_proj_remat_products``: the attention
    projections a rematerialised block runs a second time."""
    called = tuple(f" {opcode}(" for opcode in PRODUCTS_OPCODES)
    n = 0
    for line in text.splitlines():
        if not any(c in line for c in called) \
                or opcode_of(line) not in PRODUCTS_OPCODES:
            continue  # (the substrings first: a text has 1e5 lines)
        meta = _OP_NAME.search(line)
        if meta and classify(meta.group(1))[:2] == (part, pass_):
            n += 1
    return n


@dataclasses.dataclass
class ProgramFacts:
    """What one compiled program says of itself, read once.

    ``module``: the XLA module's name as a device trace prints it
    (``jit_train_step``); ``fingerprint``: of the compiled text (two
    roles that run one program share it; two texts under one module
    name do not); ``ops``: :func:`parse_program`; ``memory``: the
    compiler's own count, bytes on one device (:data:`MEMORY_FIELDS`);
    ``attributes``: what else the owner read from the same text (the
    engine's ``decode_kernel``, ``decode_layer_copies``,
    ``flash_fwd_per_bwd``, ``attn_proj_remat_products``);
    ``text_bytes``: the length of the text that was read (span
    ``engine:facts``; no part of ``as_dict``)."""
    module: str
    fingerprint: str
    ops: Dict[str, Tuple]
    memory: Dict[str, int]
    attributes: Dict[str, Any] = dataclasses.field(default_factory=dict)
    text_bytes: int = 0

    def as_dict(self) -> Dict[str, Any]:
        return dict(module=self.module, fingerprint=self.fingerprint,
                    ops={k: list(v) for k, v in self.ops.items()},
                    memory=dict(self.memory),
                    attributes=dict(self.attributes))


def read_program(compiled, derive: Optional[Callable[[str], Dict]] = None
                 ) -> ProgramFacts:
    """The facts of a ``jax.stages.Compiled``. ``derive(text)`` gives
    the owner's own attributes from the same read of the text."""
    text = compiled.as_text()
    module = _MODULE.match(text)
    stats = compiled.memory_analysis()
    return ProgramFacts(
        module=module.group(1) if module else "",
        fingerprint=hashlib.blake2b(text.encode(),
                                    digest_size=8).hexdigest(),
        ops=parse_program(text),
        memory={f: int(getattr(stats, f, 0) or 0) for f in MEMORY_FIELDS},
        attributes=derive(text) if derive is not None else {},
        text_bytes=len(text))


# ----------------------------------------------------------------------
# The operator's reader: a profile on disk explains itself
# ----------------------------------------------------------------------
_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")


def newest_profile(profile_dir: str) -> Optional[str]:
    files = sorted(glob.glob(os.path.join(
        profile_dir, "**", "*.xplane.pb"), recursive=True))
    return files[-1] if files else None


def programs_path(profile_dir: str) -> str:
    """Where a capture's ``programs.json`` lies: beside the newest
    ``.xplane.pb`` under ``profile_dir``, else in it."""
    newest = newest_profile(profile_dir)
    return os.path.join(os.path.dirname(newest) if newest
                        else profile_dir, PROGRAMS_FILE)


def read_device_lines(path: str) -> Dict[int, Dict[str, List[Tuple]]]:
    """Chip -> its ``XLA Ops`` and ``XLA Modules`` lines as ``(name,
    start_s, end_s)`` lists, from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    devices = {}
    for plane in ProfileData.from_file(path).planes:
        m = _DEVICE_PLANE.match(plane.name)
        if not m:
            continue
        dev = devices.setdefault(int(m.group(1)), dict(ops=[], modules=[]))
        for line in plane.lines:
            key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                line.name)
            if key:
                dev[key] = [(e.name, e.start_ns * 1e-9,
                             (e.start_ns + e.duration_ns) * 1e-9)
                            for e in line.events]
    return devices


def self_seconds(events: Iterable[Tuple]) -> Dict[Any, float]:
    """name -> the events' OWN seconds: a nested event's time is taken
    off the event that holds it (a ``while`` spans its body's
    operations). Events on one line nest or follow each other."""
    out = collections.defaultdict(float)
    stack = []  # [name, end, children's seconds, start]

    def close(upto):
        while stack and stack[-1][1] <= upto:
            name, end, kids, start = stack.pop()
            out[name] += (end - start) - kids
            if stack:
                stack[-1][2] += end - start

    for name, s, e in sorted(events, key=lambda x: (x[1], -x[2])):
        close(s)
        stack.append([name, e, 0.0, s])
    close(float("inf"))
    return dict(out)


def operation_seconds(devices, own=self_seconds) -> Dict[Tuple, float]:
    """``(module, instruction)`` -> own device seconds, summed over the
    chips. An operation's event is named by its whole HLO line: the
    instruction is what stands before `` = ``; its module is the
    ``XLA Modules`` event it starts in, found by time (``jit_f(12)``
    -> ``jit_f``). ``own``: the nesting rule
    (:func:`self_seconds`; the benchmark hands in its reducer's)."""
    import bisect
    out = collections.defaultdict(float)
    for dev in devices.values():
        mods = sorted((s, e, re.sub(r"\(\d+\)$", "", n))
                      for n, s, e in dev["modules"])
        starts = [m[0] for m in mods]
        keyed = []
        for name, s, e in dev["ops"]:
            i = bisect.bisect_right(starts, s) - 1
            mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
            keyed.append(
                ((mod, name.partition(" = ")[0].lstrip("%")), s, e))
        for key, secs in own(keyed).items():
            out[key] += secs
    return dict(out)


def facts_by_module(programs: Dict[str, Dict]) -> Dict[str, Dict]:
    """Module name -> the one program's facts a trace can be joined
    to; a module name under which a capture holds two DIFFERENT texts
    is left out (a trace does not tell them apart)."""
    seen = collections.defaultdict(list)
    for facts in programs.values():
        seen[facts["module"]].append(facts)
    return {mod: fs[0] for mod, fs in seen.items() if len(fs) == 1}


#: an operation of the trace that its program's text does not name
_UNKNOWN = (None, UNKNOWN, "?", None, "")


def table(profile_dir: str, top: int = 20, out=sys.stdout):
    """Print, a program of the profile, busy seconds, own seconds by
    part and pass, the ``top`` largest operations and the memory
    line."""
    path = newest_profile(profile_dir)
    if path is None:
        raise SystemExit(f"no .xplane.pb under {profile_dir}")
    try:
        with open(programs_path(profile_dir)) as f:
            programs = json.load(f)
    except OSError:
        raise SystemExit(
            f"no {PROGRAMS_FILE} beside {path}: the capture was not "
            "made by tracing.start(profile_dir) .. stop()")
    devices = read_device_lines(path)
    if not devices:
        raise SystemExit(f"{path} holds no /device:TPU plane")
    seconds = operation_seconds(devices)
    known = facts_by_module(programs)
    chips = len(devices)
    for module in sorted({m for m, _ in seconds},
                         key=lambda m: -sum(
                             v for (mm, _), v in seconds.items()
                             if mm == m)):
        mine = {op: v for (m, op), v in seconds.items() if m == module}
        busy = sum(mine.values())
        print(f"== {module}: {busy / chips:.4f} s busy a chip "
              f"({chips} chip{'s' * (chips > 1)}, {len(mine)} "
              "operations)", file=out)
        facts = known.get(module)
        if facts is None:
            print("   (no facts: not an engine program, or two texts "
                  "under one name)\n", file=out)
            continue
        by = collections.defaultdict(float)
        for op, secs in mine.items():
            part, pass_, _, phase, _ = facts["ops"].get(op, _UNKNOWN)
            by[(phase or "-", part or "unscoped", pass_)] += secs
        for (phase, part, pass_), secs in sorted(by.items(),
                                                 key=lambda x: -x[1]):
            print(f"{secs / chips:10.4f} s {100 * secs / busy:5.1f}%  "
                  f"{phase:<16} {part:<18} {pass_}", file=out)
        print(file=out)
        for op, secs in sorted(mine.items(), key=lambda x: -x[1])[:top]:
            part, pass_, opcode, phase, tail = facts["ops"].get(
                op, _UNKNOWN)
            print(f"{secs / chips:10.4f} s  {op} {opcode}  "
                  f"[{phase or '-'}/{part or 'unscoped'}/{pass_}]  {tail}",
                  file=out)
        mem = facts["memory"]
        needs = mem.get("argument_size_in_bytes", 0) \
            + mem.get("temp_size_in_bytes", 0)
        print("   memory (one device, bytes): "
              + ", ".join(f"{f.replace('_size_in_bytes', '')} "
                          f"{mem.get(f, 0):,}" for f in MEMORY_FIELDS)
              + f"; arguments + temporaries {needs / 1e9:.3f} GB\n",
              file=out)


if __name__ == "__main__":
    if len(sys.argv) != 2:
        raise SystemExit(__doc__)
    table(sys.argv[1])
