"""From a profiler trace (.xplane.pb) to device busy time, device time by
program and the longest idle gaps. Read with jax's own ProfileData.

A TPU's plane is named "/device:TPU:<n>". Its line "XLA Ops" carries one
event per operation the device ran, and "XLA Modules" one per program
execution, named "<jit name>(<fingerprint>)". Busy time is the union of the
operations' intervals; time by program is the union, within each program's
executions, of the same."""

from __future__ import annotations

import bisect
import glob
import json
import os
import re
from array import array

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "plugins/profile/*/*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def load_planes(path: str) -> dict:
    """{device index: {"XLA Modules": [(start_ns, end_ns, name), ...],
    "XLA Ops": [(start_ns, end_ns), ...] merged}}. A request of this program
    leaves some 400,000 operation events, so they are merged as they are
    read and their names are not kept."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m is None:
            continue
        lines = {}
        for line in plane.lines:
            if line.name == MODULES_LINE:
                lines[line.name] = [
                    (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                    for e in line.events
                ]
            elif line.name == OPS_LINE:
                starts, ends = array("d"), array("d")
                for e in line.events:
                    starts.append(e.start_ns)
                    ends.append(e.start_ns + e.duration_ns)
                lines[line.name] = union_arrays(starts, ends)
        out[int(m.group(1))] = lines
    return out


def union_arrays(starts, ends) -> list:
    """`union` of a great many intervals, given as two arrays."""
    if not len(starts):
        return []
    s = np.asarray(starts, dtype=np.float64)
    order = np.argsort(s, kind="stable")
    s = s[order]
    e = np.maximum.accumulate(np.asarray(ends, dtype=np.float64)[order])
    first = np.concatenate(([True], s[1:] > e[:-1]))
    last = np.concatenate((first[1:], [True]))
    return [(int(a), int(b)) for a, b in zip(s[first], e[last])]


def union(intervals) -> list:
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def covered(intervals) -> int:
    return sum(e - s for s, e in union(intervals))


def module_of(event_name: str) -> str:
    """XLA's module name, less the fingerprint."""
    return re.sub(r"\(\d+\)$", "", event_name)


def program_of(event_name: str, table: dict) -> str:
    """The program-name table (data, programs/*.json) maps a prefix of XLA's
    module name to a group; a module it does not know keeps its own name."""
    base = module_of(event_name)
    for prefix, group in table.items():
        if base.startswith(prefix):
            return group
    return base


def reduce_planes(planes: dict, table: dict) -> dict | None:
    """None where no operation ran on a device. Seconds are averaged over
    the device planes present; gaps are of the busiest device."""
    per_device = []
    for _dev, lines in sorted(planes.items()):
        ops = lines.get(OPS_LINE) or []
        if not ops:
            continue
        busy = union((op[0], op[1]) for op in ops)
        starts = [s for s, _e in busy]
        modules = sorted(lines.get(MODULES_LINE) or [])
        module_starts = [ms for ms, _me, _n in modules]

        def module_at(t_ns: int) -> str:
            i = bisect.bisect_right(module_starts, t_ns) - 1
            if i >= 0 and t_ns < modules[i][1]:
                return program_of(modules[i][2], table)
            return "none"

        by_program, by_module = {}, {}
        for ms, me, name in modules:
            lo = max(bisect.bisect_right(starts, ms) - 1, 0)
            hi = bisect.bisect_left(starts, me)
            inside = sum(
                max(0, min(e, me) - max(s, ms)) for s, e in busy[lo:hi]
            )
            group = program_of(name, table)
            by_program[group] = by_program.get(group, 0) + inside
            by_module[module_of(name)] = by_module.get(module_of(name), 0) + inside
        gaps = {}
        for (_s0, e0), (s1, _e1) in zip(busy, busy[1:]):
            key = f"{module_at(e0 - 1)}->{module_at(s1)}"
            gaps[key] = gaps.get(key, 0) + (s1 - e0)
        per_device.append((sum(e - s for s, e in busy), by_program, gaps, by_module))
    if not per_device:
        return None
    n = len(per_device)
    programs, by_name = {}, {}
    for _b, by_program, _g, by_module in per_device:
        for k, v in by_program.items():
            programs[k] = programs.get(k, 0) + v / n / 1e9
        for k, v in by_module.items():
            by_name[k] = by_name.get(k, 0) + v / n / 1e9
    busiest = max(per_device, key=lambda d: d[0])
    return {
        "busy_s": sum(d[0] for d in per_device) / n / 1e9,
        "device_s_by_program": programs,
        "device_s_by_module": by_name,
        "idle_s_by_gap": {k: v / 1e9 for k, v in busiest[2].items()},
        "executions": sum(len(ln.get(MODULES_LINE) or []) for ln in planes.values()),
    }


def load_table(programs_dir: str) -> dict:
    """{prefix: group} from every file of programs/ (one group a file)."""
    table = {}
    for path in sorted(glob.glob(os.path.join(programs_dir, "*.json"))):
        with open(path) as f:
            entry = json.load(f)
        for prefix in entry["prefixes"]:
            table[prefix] = entry["group"]
    # the longest prefix first, so that a later, more exact entry wins
    return dict(sorted(table.items(), key=lambda kv: -len(kv[0])))


def reduce_file(path: str, programs_dir: str) -> dict | None:
    return reduce_planes(load_planes(path), load_table(programs_dir))
