"""From a profiler trace to device busy time, idle gaps and the
operations that took most time.

``read_xplane`` turns the ``.xplane.pb`` that ``jax.profiler`` writes
into plain lists; everything after it works on those lists, so the
tests hold the arithmetic to a constructed trace with known answers.

What a v5e trace looks like (looked at by hand, PR 23): one plane
``/device:TPU:<n>`` a chip, whose line ``XLA Modules`` has one event a
program run and whose line ``XLA Ops`` has one event an operation,
NESTED: a ``while`` spans the operations of its body. So busy time is
the union of the intervals, never the sum of the durations, and an
operation's own time is its duration less its children's. Host threads
are lines of the plane ``/host:CPU``; ``TraceAnnotation`` spans sit
there under their own names, on the same clock as the device lines
(nanoseconds from the start of the trace).
"""

import bisect
import collections
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
HOST_PLANE = "/host:CPU"
#: spans the harness writes: bench:step, bench:mfc:<name>, bench:reshard
SPAN_PREFIX = "bench:"
#: spans the program writes (monitor.mfc_profile_region)
MFC_PREFIX = "mfc:"

_OPCODE = re.compile(r"[ )]([a-z][a-z0-9\-]*)\(")


def read_xplane(path):
    """{"devices": {n: {"ops": [(name, start_s, end_s)], "modules":
    [...]}}, "spans": [(name, start_s, end_s)]} of one trace file."""
    from jax.profiler import ProfileData

    devices, spans = {}, []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)),
                                     dict(ops=[], modules=[]))
            for line in plane.lines:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(
                    line.name)
                if key:
                    dev[key] = [
                        (e.name, e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9)
                        for e in line.events]
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith((SPAN_PREFIX, MFC_PREFIX)):
                        spans.append((e.name, e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    return dict(devices=devices, spans=spans)


def clip(events, window):
    """The events' parts that lie inside ``window`` = (t0, t1)."""
    t0, t1 = window
    return [(n, max(s, t0), min(e, t1)) for n, s, e in events
            if e > t0 and s < t1]


def merged(events):
    """The union of the events' intervals, as sorted disjoint
    (start, end) pairs."""
    out = []
    for s, e in sorted((s, e) for _, s, e in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def busy_seconds(events, window):
    return sum(e - s for s, e in merged(clip(events, window)))


def idle_gaps(events, window):
    """(start, end) of every stretch of ``window`` in which no event
    ran."""
    gaps, at = [], window[0]
    for s, e in merged(clip(events, window)):
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if window[1] > at:
        gaps.append((at, window[1]))
    return gaps


def self_seconds(events):
    """name -> seconds of the events' OWN time: a nested event's time
    is taken off the event that contains it. Events on one line nest
    or follow each other; they never straddle."""
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


def short_op(name):
    """``%fusion.3 = bf16[..] fusion(...)`` -> ``fusion.3 fusion``: the
    operation's name in its program and its opcode."""
    head, _, rest = name.partition(" = ")
    head = head.lstrip("%")
    m = _OPCODE.search(" " + rest) if rest else None
    return f"{head} {m.group(1)}" if m else head


def with_module(ops, modules):
    """Prefix each operation with the program it ran in, found by time:
    ``jit_step/fusion.3 fusion``."""
    mods = sorted((s, e, re.sub(r"\(\d+\)$", "", n))
                  for n, s, e in modules)
    starts = [m[0] for m in mods]
    out = []
    for name, s, e in ops:
        i = bisect.bisect_right(starts, s) - 1
        mod = mods[i][2] if i >= 0 and s < mods[i][1] else "?"
        out.append((f"{mod}/{short_op(name)}", s, e))
    return out


def label_of(t, spans, categories):
    """What the host was doing at time ``t``, from the harness's spans:
    ``reshard``, an MFC's category (``gen``, ``inf``, ``train``; an MFC
    the kind does not name keeps ``mfc:<name>``), ``between-mfcs``
    inside a step, else ``between-steps``."""
    inside = [n for n, s, e in spans if s <= t < e]
    if SPAN_PREFIX + "reshard" in inside:
        return "reshard"
    for prefix in (SPAN_PREFIX + MFC_PREFIX, MFC_PREFIX):
        for n in inside:
            if n.startswith(prefix):
                mfc = n[len(prefix):]
                return categories.get(mfc, MFC_PREFIX + mfc)
    if SPAN_PREFIX + "step" in inside:
        return "between-mfcs"
    return "between-steps"


def labeller(spans, categories):
    """``label_of`` for many times: the label is constant between two
    neighbouring span boundaries, so it is worked out once a stretch
    and found by bisection."""
    cuts = sorted({t for _, s, e in spans for t in (s, e)})
    labels = [label_of((a + b) / 2, spans, categories)
              for a, b in zip(cuts, cuts[1:])]

    def label(t):
        i = bisect.bisect_right(cuts, t) - 1
        if 0 <= i < len(labels):
            return labels[i]
        return "between-steps"
    return label


def window_of(spans, n_steps=None):
    """From the first traced step's start to the last one's end."""
    steps = sorted((s, e) for n, s, e in spans
                   if n == SPAN_PREFIX + "step")
    if not steps:
        return None
    if n_steps:
        steps = steps[:n_steps]
    return steps[0][0], steps[-1][1]


def reduce(trace, categories, chips=None, top=10, longest=4):
    """The traced window's numbers: busy and window seconds (busy is
    the mean over the chips used), idle share, the operations with most
    own time, and idle time by what the host was doing. None where the
    trace holds no step span or no device operation."""
    window = window_of(trace["spans"])
    devices = {n: d for n, d in sorted(trace["devices"].items())
               if d["ops"]}
    if chips:
        devices = dict(list(devices.items())[:chips])
    if window is None or not devices:
        return None
    window_s = window[1] - window[0]
    busy = [busy_seconds(d["ops"], window) for d in devices.values()]
    busy_s = sum(busy) / len(busy)

    own = collections.defaultdict(float)
    for d in devices.values():
        named = with_module(clip(d["ops"], window), d["modules"])
        for name, secs in self_seconds(named).items():
            own[name] += secs / len(devices)
    device_ops = sorted(own.items(), key=lambda x: -x[1])[:top]

    first = next(iter(devices.values()))
    by_label = collections.defaultdict(float)
    gaps = []
    label_at = labeller(trace["spans"], categories)
    for s, e in idle_gaps(first["ops"], window):
        label = label_at((s + e) / 2)
        by_label[label] += e - s
        gaps.append((label, e - s))
    sums = sorted(by_label.items(), key=lambda x: -x[1])
    gaps.sort(key=lambda x: -x[1])
    idle = [[f"sum:{k}", v] for k, v in sums[:top - longest]] \
        + [[f"gap:{k}", v] for k, v in gaps[:longest]]
    return dict(
        busy_s=busy_s, window_s=window_s,
        busy_s_per_chip=busy,
        idle_share=1.0 - busy_s / window_s,
        idle_by_label=dict(by_label),
        breakdown=dict(device_ops=[[k, v] for k, v in device_ops],
                       idle_gaps=idle))
