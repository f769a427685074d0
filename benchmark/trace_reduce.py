"""From a profiler trace to busy time, idle gaps and the operations
that took most of the device's time.

Input is what `jax.profiler.ProfileData` gives: planes, their lines,
events with a start and a duration in nanoseconds. `load_trace` turns
an `.xplane.pb` file into `Line`s (numpy arrays of starts and ends,
names interned); everything else works on those, so the tests feed it
a small recorded trace (a JSON file) and need no profiler.

A device plane is named `/device:TPU:<n>`. Its `XLA Ops` line holds
one event per executed HLO operation (millions in a window of
seconds) and its `XLA Modules` line one per program launch; where a
plane has no `XLA Ops` line every line of it but the modules' counts.
Busy is the union of the operations' intervals, clipped to the window.
An idle gap is attributed to the innermost host event (the shortest
one) open at the gap's middle: the harness wraps each query in a
`TraceAnnotation`, the program's own `annotate` ranges and the
runtime's events fall inside it.
"""

import glob
import heapq
import os
import re
from typing import NamedTuple

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
CPU_CLIENT_LINE = "tf_XLAPjRtCpuClient"
#: Gaps shorter than this are launch latency between two operations of
#: one program, not the host's doing: they count as idle, unnamed.
MIN_NAMED_GAP_NS = 20_000
_MODULE_ID = re.compile(r"\(\d+\)$")


class Line(NamedTuple):
    """The events of one line of a plane, in no particular order."""
    names: list          # the distinct names
    name_id: np.ndarray  # per event, its index into `names`
    start: np.ndarray    # ns
    end: np.ndarray      # ns


def short_name(name: str) -> str:
    """An XLA op's event is named by its whole HLO instruction
    ("%fusion.3 = f32[...] fusion(...)"): keep what stands before the
    "=", without the "%", at most 80 characters."""
    return name.split(" = ", 1)[0].lstrip("%")[:80]


def line_of(events) -> Line:
    """A Line from an iterable of (name, start_ns, end_ns)."""
    ids, names, name_id, start, end = {}, [], [], [], []
    for name, s, e in events:
        k = ids.get(name)
        if k is None:
            k = ids[name] = len(names)
            names.append(short_name(name))
        name_id.append(k)
        start.append(s)
        end.append(e)
    return Line(names, np.asarray(name_id, dtype=np.int64),
                np.asarray(start, dtype=np.int64),
                np.asarray(end, dtype=np.int64))


def _events(line):
    return ((e.name, e.start_ns, e.start_ns + e.duration_ns)
            for e in line.events)


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_trace(path: str, cpu_stand_in: bool = False) -> dict:
    """{"device": {chip: {line name: Line}}, "host": [(name, start_ns,
    end_ns)]} from an .xplane.pb file. `cpu_stand_in` is for rehearsals
    off the chip only: the CPU client's executor threads then stand
    for device 0."""
    from jax.profiler import ProfileData

    device, host, stand_in = {}, [], []
    for plane in ProfileData.from_file(path).planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            device[int(m.group(1))] = {
                line.name: line_of(_events(line)) for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                if cpu_stand_in and line.name.startswith(CPU_CLIENT_LINE):
                    stand_in.extend(_events(line))
                else:
                    host.extend((n, int(s), int(e))
                                for n, s, e in _events(line))
    if stand_in:
        device[0] = {OPS_LINE: line_of(stand_in)}
    return {"device": device, "host": host}


def ops_of(lines: dict) -> Line:
    """The line of executed operations of a device plane."""
    if OPS_LINE in lines:
        return lines[OPS_LINE]
    rest = [ln for name, ln in lines.items() if name != MODULES_LINE]
    return line_of((ln.names[k], s, e) for ln in rest
                   for k, s, e in zip(ln.name_id, ln.start, ln.end))


def busy_intervals(line: Line, t0: int, t1: int) -> np.ndarray:
    """Union of the events' intervals clipped to [t0, t1]: an array of
    [start, end] rows, sorted."""
    s, e = np.maximum(line.start, t0), np.minimum(line.end, t1)
    keep = e > s
    s, e = s[keep], e[keep]
    if not len(s):
        return np.zeros((0, 2), dtype=np.int64)
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    first = np.ones(len(s), dtype=bool)
    first[1:] = s[1:] > e[:-1]  # starts after all before it have ended
    starts = s[first]
    ends = np.append(e[np.flatnonzero(first)[1:] - 1], e[-1])
    return np.stack([starts, ends], axis=1)


def idle_gaps(busy: np.ndarray, t0: int, t1: int) -> np.ndarray:
    """The intervals of [t0, t1] that `busy` leaves, as [start, end]
    rows."""
    starts = np.append(t0, busy[:, 1])
    ends = np.append(busy[:, 0], t1)
    keep = ends > starts
    return np.stack([starts[keep], ends[keep]], axis=1)


def attribute_gaps(gaps: np.ndarray, host_events: list) -> dict:
    """{host event name: idle ns} with each gap of MIN_NAMED_GAP_NS or
    more given to the shortest host event open at its middle; shorter
    gaps go to "(between operations)", uncovered ones to "(no host
    event)"."""
    length = gaps[:, 1] - gaps[:, 0]
    short = length < MIN_NAMED_GAP_NS
    out = {}
    if short.any():
        out["(between operations)"] = int(length[short].sum())
    events = sorted(host_events, key=lambda ev: ev[1])
    open_events, i = [], 0  # heap of (duration, end, name)
    for s, e in gaps[~short].tolist():  # sorted, as `busy` is
        mid = (s + e) // 2
        while i < len(events) and events[i][1] <= mid:
            n, es, ee = events[i]
            heapq.heappush(open_events, (ee - es, ee, n))
            i += 1
        while open_events and open_events[0][1] <= mid:
            heapq.heappop(open_events)
        name = open_events[0][2] if open_events else "(no host event)"
        out[name] = out.get(name, 0) + (e - s)
    return out


def op_seconds(lines: dict, t0: int, t1: int) -> dict:
    """{"<module>/<op>": ns} over the window, the module being the
    program launch that the operation ran in (its id stripped, so the
    name is the same from run to run)."""
    ops = ops_of(lines)
    s, e = np.maximum(ops.start, t0), np.minimum(ops.end, t1)
    keep = e > s
    s, e, op_id = s[keep], e[keep], ops.name_id[keep]
    op_names = list(ops.names)
    modules = lines.get(MODULES_LINE)
    key = op_id
    if modules is not None and len(modules.start):
        order = np.argsort(modules.start, kind="stable")
        m_start, m_end = modules.start[order], modules.end[order]
        k = np.maximum(np.searchsorted(m_start, s, side="right") - 1, 0)
        inside = (m_start[k] <= s) & (m_end[k] >= e)
        m_id = np.where(inside, modules.name_id[order][k], -1)
        key = (m_id + 1) * len(op_names) + op_id
    out = {}
    uniq, inverse = np.unique(key, return_inverse=True)
    total = np.bincount(inverse, weights=(e - s).astype(np.float64))
    for u, ns in zip(uniq.tolist(), total.tolist()):
        m, o = divmod(u, len(op_names))
        name = op_names[o]
        if m:
            name = f"{_MODULE_ID.sub('', modules.names[m - 1])}/{name}"
        out[name] = out.get(name, 0) + int(ns)
    return out


def reduce_trace(trace: dict, t0: int, t1: int, chips: int = 1,
                 top: int = 10) -> dict:
    """The numbers the result line carries, over the window [t0, t1]
    in the trace's own nanoseconds: `busy_s` averaged over the chips,
    `window_s`, the `top` operations and the `top` causes of idle
    time. Raises if fewer than `chips` device planes ran anything."""
    ran = {c: lines for c, lines in trace["device"].items()
           if len(ops_of(lines).start)}
    if len(ran) < chips:
        raise ValueError(
            f"the trace shows operations on {len(ran)} device(s), the "
            f"cell uses {chips}")
    busy_ns, ops, gaps_by = 0, {}, {}
    for lines in ran.values():
        busy = busy_intervals(ops_of(lines), t0, t1)
        busy_ns += int((busy[:, 1] - busy[:, 0]).sum())
        for name, ns in op_seconds(lines, t0, t1).items():
            ops[name] = ops.get(name, 0) + ns
        for name, ns in attribute_gaps(idle_gaps(busy, t0, t1),
                                       trace["host"]).items():
            gaps_by[name] = gaps_by.get(name, 0) + ns
    n = len(ran)

    def ranked(d):
        return [[k, v / n / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_ns / n / 1e9, "window_s": (t1 - t0) / 1e9,
            "device_ops": ranked(ops), "idle_gaps": ranked(gaps_by)}


def window_of(trace: dict, mark: str) -> tuple:
    """[t0, t1] in the trace's clock: the harness's annotation that
    wraps the window."""
    found = [(s, e) for n, s, e in trace["host"] if n == mark]
    if len(found) != 1:
        raise ValueError(f"the trace has {len(found)} {mark!r} events")
    return found[0]
