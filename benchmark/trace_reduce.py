"""Reduce a profiler trace (.xplane.pb) to the numbers the readers use.

Per device plane (`/device:TPU:<id>`) it takes the op events of the line
named "XLA Ops" and gives:
- busy_s: the union of the op intervals, averaged over the traced chips;
- ops: seconds per op ("<instruction> <opcode>"), summed over the chips and
  averaged per chip (a while op's time includes its body's ops);
- device_ops: the ten op names that took most time;
- idle_gaps: the ten longest gaps between busy intervals on the first chip,
  each named by the innermost host span (a TraceAnnotation whose name holds
  a "/", such as host/pump or bench/tick) that covers the gap's middle.
The device and host events of one trace share the profiler's clock.
"""

from __future__ import annotations

import glob
import os
import re

OPS_LINE = "XLA Ops"
COLLECTIVE_MARKS = ("all-reduce", "all-gather", "reduce-scatter",
                    "collective-permute", "all-to-all")


_KIND = re.compile(r" ([a-z][a-z0-9_-]*)\(")


def op_name(hlo: str) -> str:
    """'%batch.1 = (s32[...]...) custom-call(...)' -> '%batch.1 custom-call':
    the instruction's name and its opcode, without shapes or operands."""
    name, sep, rest = hlo.partition(" = ")
    if not sep:
        return hlo
    kind = _KIND.search(" " + rest)
    return f"{name} {kind.group(1)}" if kind else name


def op_kind(name: str) -> str:
    return name.rpartition(" ")[2]


def _device_plane_id(name: str):
    prefix = "/device:TPU:"
    if not name.startswith(prefix):
        return None
    tail = name[len(prefix):]
    return int(tail) if tail.isdigit() else None


def merge(intervals):
    """Sorted union of [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def is_collective(name: str) -> bool:
    kind = op_kind(name)
    return any(kind.startswith(mark) for mark in COLLECTIVE_MARKS)


def is_kernel(name: str) -> bool:
    """A Pallas kernel: on the TPU it is a custom-call op."""
    return op_kind(name) == "custom-call"


def reduce_planes(planes, device_ids, window_s: float) -> dict:
    """`planes`: iterable of objects with .name and .lines (each line with
    .name and .events, each event with .name, .start_ns, .duration_ns), as
    jax.profiler.ProfileData gives them."""
    wanted = set(device_ids)
    per_device = {}
    host_spans = []
    for plane in planes:
        dev = _device_plane_id(plane.name)
        if dev is not None and dev in wanted:
            evs = []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs += [(op_name(e.name), e.start_ns,
                             e.start_ns + e.duration_ns) for e in line.events]
            per_device[dev] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host_spans += [
                    (e.start_ns, e.start_ns + e.duration_ns, e.name)
                    for e in line.events
                    if "/" in e.name and e.duration_ns > 0
                ]
    if not per_device:
        return None
    n = len(per_device)
    busy_ns = 0.0
    ops = {}
    for evs in per_device.values():
        busy_ns += sum(e - s for s, e in merge((s, e) for _, s, e in evs))
        for name, s, e in evs:
            ops[name] = ops.get(name, 0.0) + (e - s) / 1e9 / n
    first = per_device[min(per_device)]
    busy = merge((s, e) for _, s, e in first)
    gaps = [(b[0] - a[1], a[1], b[0]) for a, b in zip(busy, busy[1:])
            if b[0] > a[1]]
    gaps.sort(reverse=True)
    idle = []
    for length, s, e in gaps[:10]:
        mid = (s + e) / 2
        covering = [(hs_e - hs_s, name) for hs_s, hs_e, name in host_spans
                    if hs_s <= mid <= hs_e]
        idle.append([min(covering)[1] if covering else "no_host_span",
                     length / 1e9])
    device_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {
        "busy_s": busy_ns / 1e9 / n,
        "window_s": window_s,
        "devices": n,
        "ops": ops,
        "device_ops": [[k, v] for k, v in device_ops],
        "idle_gaps": idle,
    }


def reduce_file(path: str, device_ids, window_s: float) -> dict:
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, device_ids,
                         window_s)


def reduce_dir(directory: str, device_ids, window_s: float) -> dict:
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        return None
    return reduce_file(paths[0], device_ids, window_s)


def op_seconds(trace: dict, match) -> float:
    """Seconds per chip of the ops whose name satisfies `match(name)`."""
    return sum(v for k, v in trace["ops"].items() if match(k))
