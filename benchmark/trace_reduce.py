"""From a profiler trace to numbers: device busy union, idle share,
per-name device time, and the idle gaps labelled by what the host was
doing. Part of the yardstick: every PR reduces a trace the same way.

A trace is reduced from plain events ``(name, start_ns, dur_ns)``:
``device`` events per chip (the device plane's operation line) and
``host`` events (the benchmark's own ``TraceAnnotation``s, whose names
start with ``bench:``). ``read_xplane`` makes those from the
``.xplane.pb`` file the JAX profiler writes; ``benchmark/data/`` keeps
a small recorded trace in the same form, on which the tests check the
arithmetic.
"""

import glob
import os
import re

DEVICE_PLANE_PREFIX = "/device:TPU:"
#: the device plane's line that holds one event per operation run
OP_LINE = "XLA Ops"
HOST_PREFIX = "bench:"
WINDOW_NAME = "bench:trace_window"


def find_xplane(trace_dir):
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def read_xplane(path):
    """-> {"device": {plane: [(name, start_ns, dur_ns)]},
           "host": [(name, start_ns, dur_ns)],
           "lines": {plane: {line: count}},
           "kinds": {name: the group its time is added up under}}
    from a ``.xplane.pb``."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    device, host, lines, kinds = {}, [], {}, {}
    for plane in data.planes:
        seen = lines.setdefault(plane.name, {})
        is_dev = plane.name.startswith(DEVICE_PLANE_PREFIX)
        for line in plane.lines:
            n = 0
            for ev in line.events:
                n += 1
                if is_dev:
                    if line.name == OP_LINE:
                        device.setdefault(plane.name, []).append(
                            (op_name(ev.name), int(ev.start_ns),
                             int(ev.duration_ns)))
                        kinds[op_name(ev.name)] = op_kind(ev.name)
                elif ev.name.startswith(HOST_PREFIX):
                    host.append((ev.name, int(ev.start_ns),
                                 int(ev.duration_ns)))
            seen[line.name] = n
    return {"device": device, "host": host, "lines": lines, "kinds": kinds}


_RESULT = re.compile(r" = \(?([a-z]+[0-9]*\[[0-9,]*\])")


def op_name(text):
    """The trace names an operation by its whole HLO instruction,
    ``%fusion.12 = bf16[4,1024]{...} fusion(...)``: keep the
    instruction's name and, for a custom call, its target, which is
    what tells a Mosaic kernel from the library's own calls."""
    name = text.split(" = ", 1)[0].lstrip("%").strip()
    if "custom_call_target=" in text:
        target = text.split("custom_call_target=", 1)[1].split(",", 1)[0]
        name += "[" + target.strip('"} ') + "]"
    return name[:120]


def op_kind(text):
    """What the breakdown groups by: the instruction's name without its
    number, with the (first) result's type and shape, so that the 36
    unrolled layers' copies of one operation add up under one name and
    an attention fusion can be told from the head's:
    ``fusion bf16[4,16,1024,64]``, ``copy bf16[36,513,20,16,64]``,
    ``closed_call[tpu_custom_call] bf16[8,20,1,64]``."""
    name = op_name(text)
    head, _, tail = name.partition("[")
    kind = re.sub(r"\.\d+", "", head) + ("[" + tail if tail else "")
    m = _RESULT.search(text)
    return (kind + " " + m.group(1) if m else kind)[:120]


def union(intervals):
    """Merge (start, end) intervals; -> sorted disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def self_times(events):
    """{name: ns} with each event's time less what its children on the
    same line cover (a ``while`` holds its body's operations), so the
    names add up to the busy time instead of counting it twice."""
    out = {}
    stack = []          # [name, end, self_ns]

    def close():
        name, _, self_ns = stack.pop()
        out[name] = out.get(name, 0) + max(self_ns, 0)

    for name, s, d in sorted(events, key=lambda e: (e[1], -e[2])):
        while stack and s >= stack[-1][1]:
            close()
        if stack:
            stack[-1][2] -= min(d, stack[-1][1] - s)
        stack.append([name, s + d, d])
    while stack:
        close()
    return out


def _clip(events, lo, hi):
    out = []
    for name, s, d in events:
        a, b = max(s, lo), min(s + d, hi)
        if b > a:
            out.append((name, a, b - a))
    return out


def _label(gap, host):
    """The host annotation that covers most of the gap; among equals the
    shortest (the most specific)."""
    gs, ge = gap
    best, best_key = "unannotated", (0, 0)
    for name, s, d in host:
        ov = min(ge, s + d) - max(gs, s)
        if ov > 0 and (ov, -d) > best_key:
            best, best_key = name, (ov, -d)
    return best


def reduce(trace, top=10):
    """-> busy_s and window_s (seconds, busy averaged over the chips),
    ``per_name`` seconds of device self time summed over chips,
    ``device_ops`` (self time added up by ``kinds``, where the trace
    has them) and ``idle_gaps`` for the breakdown. The window is
    the ``bench:trace_window`` annotation where the trace has one, else
    the span of the device events."""
    device = trace["device"]
    if not device or not any(device.values()):
        raise ValueError("the trace holds no device operation")
    host = [e for e in trace["host"] if e[0] != WINDOW_NAME]
    win = [e for e in trace["host"] if e[0] == WINDOW_NAME]
    if win:
        lo, hi = win[0][1], win[0][1] + win[0][2]
    else:
        lo = min(s for evs in device.values() for _, s, _ in evs)
        hi = max(s + d for evs in device.values() for _, s, d in evs)
    busy_ns, per_name, gaps = 0, {}, {}
    for evs in device.values():
        evs = _clip(evs, lo, hi)
        merged = union((s, s + d) for _, s, d in evs)
        busy_ns += sum(e - s for s, e in merged)
        for name, ns in self_times(evs).items():
            per_name[name] = per_name.get(name, 0) + ns
        edges = [lo] + [t for se in merged for t in se] + [hi]
        for gs, ge in zip(edges[0::2], edges[1::2]):
            if ge > gs:
                lab = _label((gs, ge), host)
                gaps[lab] = gaps.get(lab, 0) + (ge - gs)
    n = len(device)
    ranked = lambda d: [[k, v / 1e9] for k, v in sorted(
        d.items(), key=lambda kv: -kv[1])[:top]]
    kinds, per_kind = trace.get("kinds", {}), {}
    for name, ns in per_name.items():
        k = kinds.get(name, name)
        per_kind[k] = per_kind.get(k, 0) + ns
    return {"busy_s": busy_ns / n / 1e9, "window_s": (hi - lo) / 1e9,
            "chips": n,
            "per_name": {k: v / 1e9 for k, v in per_name.items()},
            "device_ops": ranked(per_kind),
            "idle_gaps": ranked({k: v / n for k, v in gaps.items()})}
