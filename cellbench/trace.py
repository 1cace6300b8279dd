"""From a profiler trace to device numbers. The yardstick: kept here so
that every PR computes the same number in the same way.

A TPU trace (``*.xplane.pb``) has one plane per chip, ``/device:TPU:n``.
Its line ``XLA Modules`` holds one event per program execution (a
dispatch), named ``jit_<fn>(<hash>)``; its line ``XLA Ops`` holds the
operations, named by their whole HLO text, NESTED where an operation
such as ``while`` contains others; ``Async XLA Ops`` holds copies that
overlap them and is not counted as busy time. ``/host:CPU`` holds the
host threads.

The reduction works on plain tuples ``(plane, line, name, start_ns,
dur_ns)`` so that it can be checked on a small recorded trace
(``tests/recorded_trace.json``).
"""

from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
import time
from typing import Iterable, List, Tuple

import numpy as np

Event = Tuple[str, str, str, int, int]
NAME_CHARS = 300  # of an operation's HLO text kept as its name


def read_xplane(logdir: str) -> List[Event]:
    """Every event of the newest trace under ``logdir``."""
    import jax

    paths = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no xplane.pb under {logdir}")
    data = jax.profiler.ProfileData.from_file(paths[-1])
    out: List[Event] = []
    for plane in data.planes:
        device = plane.name.startswith("/device:TPU")
        if not device and plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if device and line.name not in ("XLA Modules", "XLA Ops"):
                continue
            for e in line.events:
                out.append((plane.name, line.name, e.name[:NAME_CHARS],
                            int(e.start_ns), int(e.duration_ns)))
    return out


def union_seconds(intervals: Iterable[Tuple[int, int]]) -> float:
    """Length of the union of ``(start_ns, end_ns)`` intervals."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total / 1e9


def self_times(ops: List[Tuple[str, int, int]]) -> List[Tuple[str, int, int]]:
    """``(name, start_ns, self_ns)`` per operation of one line: its
    duration less what the operations nested inside it cover."""
    out, stack = [], []  # stack of [name, start, end, child_ns]

    def close(upto: int) -> None:
        while stack and stack[-1][2] <= upto:
            name, s, e, child = stack.pop()
            out.append((name, s, max(e - s - child, 0)))
            if stack:
                stack[-1][3] += e - s

    for name, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        close(s)
        stack.append([name, s, s + d, 0])
    close(1 << 62)
    return out


_HEAD = re.compile(r"^%?([^\s=]+?)(?:\.\d+)?\s*=\s*(?:\(?\s*)?([a-z0-9]+\[[^\]]*\])?")


def op_label(text: str) -> str:
    """A short, stable-ish label for an operation's HLO text: its name
    without the numeric suffix, and for a bare ``fusion`` its output
    shape, since the compiler's name says nothing."""
    m = _HEAD.match(text)
    if not m:
        return text.split(" ")[0].lstrip("%")[:60]
    head, shape = m.group(1), m.group(2)
    return f"{head}:{shape}" if head == "fusion" and shape else head


def program_label(module: str) -> str:
    return re.sub(r"\(\d+\)$", "", module)


def reduce_device(events: List[Event], window_s: float) -> dict:
    """Busy time, dispatches and per-operation time of a traced window.

    Returns ``window_s``, ``busy_s`` (union of operation intervals,
    averaged over the chips seen), ``programs`` ``{label: {"dispatches",
    "busy_s"}}`` and ``ops`` ``[(program, op text, self seconds)]``.
    Raises where the parts do not add up: every busy second has to
    belong to a dispatch, and nothing can be busier than the window."""
    planes = sorted({e[0] for e in events if e[0].startswith("/device:")})
    if not planes:
        raise ValueError("the trace holds no device plane")
    busy, programs, ops_out = 0.0, {}, []
    for plane in planes:
        mods = sorted((e[3], e[3] + e[4], e[2]) for e in events
                      if e[0] == plane and e[1] == "XLA Modules")
        ops = [(e[2], e[3], e[4]) for e in events
               if e[0] == plane and e[1] == "XLA Ops"]
        plane_busy = union_seconds((s, s + d) for _, s, d in ops)
        busy += plane_busy
        starts = np.array([m[0] for m in mods], dtype=np.int64)
        per_mod: List[list] = [[] for _ in mods]
        owner = {}
        for name, s, d in ops:
            i = int(np.searchsorted(starts, s, side="right")) - 1
            if i < 0 or s >= mods[i][1]:
                continue  # an operation outside every dispatch
            per_mod[i].append((s, s + d))
            owner[(name, s)] = program_label(mods[i][2])
        attributed = 0.0
        for (s, e, name), ivs in zip(mods, per_mod):
            p = programs.setdefault(program_label(name),
                                    {"dispatches": 0, "busy_s": 0.0})
            p["dispatches"] += 1
            b = union_seconds(ivs)
            p["busy_s"] += b
            attributed += b
        if abs(attributed - plane_busy) > 0.02 * plane_busy + 1e-4:
            raise AssertionError(
                f"{plane}: dispatches account for {attributed:.6f} s of "
                f"{plane_busy:.6f} s busy")
        for name, s, self_ns in self_times(ops):
            ops_out.append((owner.get((name, s), "?"), name,
                            self_ns / 1e9))
    busy /= len(planes)
    if busy > window_s * 1.0001:
        raise AssertionError(
            f"busy {busy:.6f} s exceeds the traced window {window_s:.6f} s")
    if busy <= 0:
        raise AssertionError("no operation ran on the device in the "
                             "traced window")
    for p in programs.values():
        p["busy_s"] /= len(planes)
    return {"window_s": window_s, "busy_s": busy, "programs": programs,
            "ops": ops_out, "chips": len(planes)}


def op_seconds(reduced: dict, program: str, op: str) -> float:
    """Summed self time of the operations whose program label and HLO
    text match the two patterns, averaged over the chips."""
    pr, opr = re.compile(program), re.compile(op)
    return sum(sec for prog, text, sec in reduced["ops"]
               if pr.search(prog) and opr.search(text)) / reduced["chips"]


def dispatches(reduced: dict, program: str) -> Tuple[int, float]:
    """``(count, busy seconds)`` of the programs matching the pattern."""
    pr = re.compile(program)
    n = sum(p["dispatches"] for k, p in reduced["programs"].items()
            if pr.search(k))
    b = sum(p["busy_s"] for k, p in reduced["programs"].items()
            if pr.search(k))
    return n, b


def top_device_ops(reduced: dict, n: int = 10) -> list:
    acc = {}
    for prog, text, sec in reduced["ops"]:
        key = f"{prog}/{op_label(text)}"
        acc[key] = acc.get(key, 0.0) + sec / reduced["chips"]
    return [[k, v] for k, v in
            sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def idle_gaps(events: List[Event], n: int = 10, longest: int = 200,
              floor_ns: int = 20_000) -> list:
    """The device's idle gaps by what the host was doing: each of the
    ``longest`` gaps between operations goes to the host event that
    covers most of it (the shortest such, so the innermost frame), the
    rest are summed as not attributed."""
    planes = sorted({e[0] for e in events if e[0].startswith("/device:")})
    if not planes:
        return []
    ivs = sorted((e[3], e[3] + e[4]) for e in events
                 if e[0] == planes[0] and e[1] == "XLA Ops")
    gaps, end = [], None
    for s, e in ivs:
        if end is not None and s - end > floor_ns:
            gaps.append((end, s))
        end = e if end is None else max(end, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    host = [e for e in events if e[0] == "/host:CPU" and e[4] > 0]
    acc = {}
    rest = sum(e - s for s, e in gaps[longest:])
    if host:
        hs = np.array([e[3] for e in host], dtype=np.int64)
        he = hs + np.array([e[4] for e in host], dtype=np.int64)
        hd = he - hs
        for s, e in gaps[:longest]:
            cover = np.minimum(he, e) - np.maximum(hs, s)
            full = cover >= 0.9 * (e - s)
            if full.any():
                idx = np.flatnonzero(full)
                i = int(idx[np.argmin(hd[idx])])
            else:
                i = int(np.argmax(cover))
                if cover[i] <= 0:
                    rest += e - s
                    continue
            key = re.sub(r"[^A-Za-z0-9_.:<>$-]+", "_", host[i][2])[:80]
            acc[key] = acc.get(key, 0) + (e - s)
    else:
        rest += sum(e - s for s, e in gaps[:longest])
    out = [[k, v / 1e9] for k, v in
           sorted(acc.items(), key=lambda kv: -kv[1])[:n - 1]]
    out.append([f"{max(len(gaps) - longest, 0)}_shorter_gaps_"
                f"not_attributed", rest / 1e9])
    return out


class Capture:
    """One traced stretch. ``python=False`` keeps the profiler's Python
    tracer off, so the host is not slowed and the device numbers are
    those of an untraced run; ``python=True`` is for the idle gaps'
    host attribution only."""

    def __init__(self, python: bool):
        self.python = python
        self.events: List[Event] = []
        self.window_s = 0.0

    def __enter__(self):
        import jax

        self._dir = tempfile.mkdtemp(prefix="cellbench_trace_")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 1 if self.python else 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self._dir, profiler_options=opts)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        import jax

        self.window_s = time.perf_counter() - self._t0
        jax.profiler.stop_trace()
        try:
            if exc[0] is None:
                self.events = read_xplane(self._dir)
        finally:
            shutil.rmtree(self._dir, ignore_errors=True)
        return False
