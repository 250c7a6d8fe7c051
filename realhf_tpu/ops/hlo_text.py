"""Reading a compiled program's optimized HLO text
(``Engine.compiled_text``): what the kernels' counters
(``ops.decode_attention.decode_layer_copies``,
``ops.flash_attention.flash_fwd_per_bwd``) say a program really does,
as pure functions of that text."""

import re
from typing import Iterator, Tuple

_CALLS = re.compile(r"\bfusion\(.*\bcalls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*->.*\{\s*$")
_INSTRUCTION = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = (.*?)\s([\w\-]+)\(")
_CUSTOM_CALL = re.compile(
    r"^\s+(?:ROOT\s+)?%?([\w.\-]+) = .*?\scustom-call\((.*?)\), "
    r"custom_call_target")


def device_instructions(hlo_text: str) -> Iterator[Tuple[str, str, str]]:
    """``(name, result type, opcode)`` of every instruction of the
    program's computations that is an operation of its own on the
    device: instructions inside fusion bodies are left out (their
    fusion is the operation)."""
    lines = hlo_text.splitlines()
    fused = {m.group(1) for m in map(_CALLS.search, lines) if m}
    in_fusion = False
    for line in lines:
        head = _COMPUTATION.match(line)
        if head:
            in_fusion = head.group(1) in fused
            continue
        m = None if in_fusion else _INSTRUCTION.match(line)
        if m:
            yield m.groups()


def custom_call_operands(hlo_text: str) -> Iterator[Tuple[str, int]]:
    """``(name, number of operands)`` of every custom call of the
    program (a kernel is one; none lies inside a fusion)."""
    for line in hlo_text.splitlines():
        m = _CUSTOM_CALL.match(line) if "custom-call(" in line else None
        if m:
            # the commas outside any bracket (a shape has its own)
            depth = commas = 0
            for ch in m.group(2):
                depth += (ch in "([{") - (ch in ")]}")
                commas += ch == "," and not depth
            yield m.group(1), commas + 1
