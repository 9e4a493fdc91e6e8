"""Reduction of a profiler trace (`.xplane.pb`) to the device's busy and idle
time, the time of named operations, and collective time that no other
operation hides.

Read with `jax.profiler.ProfileData`. A device is a plane named
`/device:TPU:<n>`; its operations are the events of its `XLA Ops` line, in
nanoseconds. The host's planes (`/host:...`) hold the Python and runtime
events that say what the host was doing while the device idled.

The traced window is the device timeline from the first operation's start to
the last operation's end over all devices: the host's own work before the
first dispatch reached the chip is not part of it.
"""
from __future__ import annotations

import glob
import os
import re
from typing import Dict, List, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
#: operation names of a collective (the `-start`/`-done` halves of an async
#: one included)
COLLECTIVE = re.compile(r"all-gather|all-reduce|reduce-scatter|collective-permute|all-to-all")

Interval = Tuple[int, int]


def find(trace_dir: str) -> str:
    """The `.xplane.pb` file a `jax.profiler` trace wrote under `trace_dir`."""
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def union(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: List[Interval]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(intervals: List[Interval], cover: List[Interval]) -> int:
    """Length of the parts of `intervals` (a union) outside `cover` (a union)."""
    left, j = 0, 0
    for a, b in intervals:
        while j < len(cover) and cover[j][1] <= a:
            j += 1
        x, k = a, j
        while k < len(cover) and cover[k][0] < b:
            if cover[k][0] > x:
                left += cover[k][0] - x
            x = max(x, cover[k][1])
            k += 1
        if x < b:
            left += b - x
    return left


def short(name: str) -> str:
    """`%fusion.462 = bf16[...] fusion(...)` -> `fusion.462`."""
    return name.split(" ", 1)[0].lstrip("%")


def describe(name: str) -> str:
    """An operation by its short name and result type: `fusion.462
    bf16[8,1024,4096]` (layouts and long tuples cut)."""
    head, _, rest = name.partition(" = ")
    kind = rest.split("{", 1)[0].split(" ", 1)[0][:48]
    return f"{short(head)} {kind}".strip()


class Trace:
    """Per-device operations and host events of one trace.

    A device's `XLA Ops` line nests: a loop or a called computation is an
    event that contains the events of the operations it runs. `leaf` marks
    the operations that contain none, and `self_ns` is an event's duration
    less that of the events directly inside it."""

    def __init__(self, path: str):
        from jax.profiler import ProfileData

        pd = ProfileData.from_file(path)
        self.ops: Dict[int, list] = {}
        self.host: List[Tuple[str, int, int]] = []
        for plane in pd.planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                evs = []
                for line in plane.lines:
                    if line.name == OPS_LINE:
                        evs.extend((e.name, int(e.start_ns), int(e.end_ns)) for e in line.events)
                if evs:
                    self.ops[int(m.group(1))] = _nest(evs)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    self.host.extend((e.name, int(e.start_ns), int(e.end_ns)) for e in line.events)

    @property
    def devices(self) -> List[int]:
        return sorted(self.ops)

    def window(self) -> Interval:
        return (min(e[0]["start"] for e in self.ops.values()),
                max(x["end"] for e in self.ops.values() for x in e))

    def window_s(self) -> float:
        a, b = self.window()
        return (b - a) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        return sum(total(union([(x["start"], x["end"]) for x in e]))
                   for e in self.ops.values()) * 1e-9 / len(self.ops)

    def op_seconds(self, pattern) -> float:
        """Summed duration of the operations whose text matches `pattern`,
        averaged over the devices."""
        rx = re.compile(pattern)
        return sum(x["end"] - x["start"] for e in self.ops.values() for x in e
                   if rx.search(x["name"])) * 1e-9 / len(self.ops)

    def collective_exposed_s(self) -> float:
        """Collective time during which no other operation (of those that
        contain none) runs on the same device, averaged over the devices."""
        out = 0
        for e in self.ops.values():
            leaves = [x for x in e if x["leaf"]]
            coll = union([(x["start"], x["end"]) for x in leaves if COLLECTIVE.search(short(x["name"]))])
            other = union([(x["start"], x["end"]) for x in leaves
                           if not COLLECTIVE.search(short(x["name"]))])
            out += subtract(coll, other)
        return out * 1e-9 / len(self.ops)

    def top_ops(self, n: int = 10) -> list:
        """The operations that took the most device time of their own:
        [[name, s]], summed over the window and averaged over the devices."""
        acc: Dict[str, int] = {}
        for e in self.ops.values():
            for x in e:
                k = describe(x["name"])
                acc[k] = acc.get(k, 0) + x["self_ns"]
        top = sorted(acc.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9 / len(self.ops)] for k, v in top]

    def idle_gaps(self, n: int = 10) -> list:
        """The longest stretches of the window in which the first device ran
        nothing, each named by the host event that overlaps it most (the
        shortest such event where several overlap it alike): [[name, s]]."""
        d = self.devices[0]
        busy = union([(x["start"], x["end"]) for x in self.ops[d]])
        gaps = [(busy[i][1], busy[i + 1][0]) for i in range(len(busy) - 1)
                if busy[i + 1][0] > busy[i][1]]
        gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:n]
        out = []
        for a, b in gaps:
            best, best_key = "unattributed", None
            for name, s, e in self.host:
                ov = min(b, e) - max(a, s)
                if ov > 0:
                    key = (ov, -(e - s))
                    if best_key is None or key > best_key:
                        best, best_key = name, key
            out.append([f"host: {best}", (b - a) * 1e-9])
        return out


def _nest(evs) -> list:
    """Events as dicts with their nesting: `leaf`, and `self_ns`: the time of
    the event that none of the events inside it covers."""
    evs = sorted(evs, key=lambda e: (e[1], -e[2]))
    out, stack = [], []
    for name, a, b in evs:
        x = {"name": name, "start": a, "end": b, "leaf": True, "inner": []}
        # leave the events that end before this one starts, or that it
        # outlasts (an overlapping sibling, not a parent)
        while stack and (stack[-1]["end"] <= a or stack[-1]["end"] < b):
            stack.pop()
        if stack:
            stack[-1]["leaf"] = False
            stack[-1]["inner"].append((a, b))
        stack.append(x)
        out.append(x)
    for x in out:
        x["self_ns"] = x["end"] - x["start"] - total(union(x.pop("inner")))
    return out
