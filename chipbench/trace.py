"""Reduction of a profiler trace (``.xplane.pb``) to device busy time,
device time per operation, and idle gaps labelled by the benchmark's host
spans.

Device planes are named ``/device:<KIND>:<n>``; their operations are the
events of the line named ``XLA Ops``, each named by its HLO instruction
text (``%attention.17 = (bf16[...]) custom-call(...), custom_call_target=
"tpu_custom_call"``).  An op is keyed by its instruction name
(``attention.17``); a Pallas kernel is a ``tpu_custom_call`` whose
instruction name, without the numeric suffix, is the name of the jitted
function that called it (``attention``, ``quantize``, ``quantize_delta``).
Busy time is the union of the op intervals inside the traced window
(operations that overlap count once); idle is the rest of the window.  A
gap is labelled by the innermost benchmark span (host events named
``bench/<name>``) that covers its midpoint, or ``outside spans``.
"""
from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
SPAN_PREFIX = "bench/"
KERNEL_TARGET = 'custom_call_target="tpu_custom_call"'
# ops whose time is the time of the ops inside them
CONTAINERS = (" while(", " conditional(", " call(")


def op_name(text: str) -> str:
    """``%fusion.470 = f32[...] fusion(...)`` -> ``fusion.470``."""
    return text.split(" = ", 1)[0].lstrip("%").strip()


def base_name(name: str) -> str:
    """``attention.17`` -> ``attention``."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


@dataclasses.dataclass
class Reduced:
    busy_s: float                         # mean over device planes
    window_s: float
    ops: Dict[str, Tuple[int, float]]     # op name -> (count, seconds)
    gaps: List[Tuple[str, float]]         # (label, seconds), longest first
    planes: List[str]
    spans: List[Tuple[str, float, float]]  # (name, start s, end s)
    kernels: frozenset = frozenset()      # op names that are Pallas kernels
    containers: frozenset = frozenset()   # while loops and calls

    def kernel_seconds(self, *bases: str) -> Tuple[int, float]:
        """(events, seconds) of the Pallas kernels called from the jitted
        functions named ``bases``."""
        n, s = 0, 0.0
        for name, (c, t) in self.ops.items():
            if name in self.kernels and base_name(name) in bases:
                n += c
                s += t
        return n, s

    def breakdown(self, top: int = 10) -> dict:
        ops = sorted(((k, v) for k, v in self.ops.items()
                      if k not in self.containers),
                     key=lambda kv: -kv[1][1])[:top]
        return {"device_ops": [[k, v[1]] for k, v in ops],
                "idle_gaps": [[k, s] for k, s in self.gaps[:top]]}


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[Tuple[float, float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1] = (out[-1][0], hi)
        else:
            out.append((lo, hi))
    return out


def reduce_events(device: Dict[str, List[Tuple[str, float, float]]],
                  spans: List[Tuple[str, float, float]],
                  window: Tuple[float, float]) -> Reduced:
    """The reduction itself, on plain tuples (op text, start s, end s):
    ``device`` maps a plane to its op events, ``spans`` are the host
    spans, ``window`` the traced interval."""
    w0, w1 = window
    ops: Dict[str, Tuple[int, float]] = {}
    kernels, containers = set(), set()
    busy_total = 0.0
    first_busy = None
    for plane, events in device.items():
        clipped = []
        for text, lo, hi in events:
            lo, hi = max(lo, w0), min(hi, w1)
            if hi <= lo:
                continue
            name = op_name(text)
            if KERNEL_TARGET in text:
                kernels.add(name)
            if any(c in text for c in CONTAINERS):
                containers.add(name)
            c, t = ops.get(name, (0, 0.0))
            ops[name] = (c + 1, t + (hi - lo))
            clipped.append((lo, hi))
        busy = _union(clipped)
        busy_total += sum(hi - lo for lo, hi in busy)
        if first_busy is None:
            first_busy = busy
    n_planes = max(len(device), 1)
    gaps = []
    cursor = w0
    for lo, hi in (first_busy or []) + [(w1, w1)]:
        if lo > cursor:
            gaps.append((cursor, lo))
        cursor = max(cursor, hi)
    labelled = []
    for lo, hi in gaps:
        mid = (lo + hi) / 2
        cover = [s for s in spans if s[1] <= mid <= s[2]]
        label = min(cover, key=lambda s: s[2] - s[1])[0] if cover \
            else "outside spans"
        labelled.append((label, hi - lo))
    labelled.sort(key=lambda g: -g[1])
    return Reduced(busy_s=busy_total / n_planes, window_s=w1 - w0, ops=ops,
                   gaps=labelled, planes=sorted(device), spans=spans,
                   kernels=frozenset(kernels),
                   containers=frozenset(containers))


def read(path: str, window: Optional[Tuple[float, float]] = None) -> Reduced:
    """Reduce one ``.xplane.pb``.  ``window`` (seconds on the trace's
    clock) defaults to the span from the first to the last benchmark span;
    without any, to the device events' extent."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    device: Dict[str, List[Tuple[str, float, float]]] = {}
    spans: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        is_device = plane.name.startswith("/device:") \
            and "CPU" not in plane.name
        for line in plane.lines:
            if is_device and line.name == OPS_LINE:
                evs = device.setdefault(plane.name, [])
                for e in line.events:
                    evs.append((e.name, e.start_ns * 1e-9,
                                (e.start_ns + e.duration_ns) * 1e-9))
            elif not is_device:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name[len(SPAN_PREFIX):],
                                      e.start_ns * 1e-9,
                                      (e.start_ns + e.duration_ns) * 1e-9))
    if window is None:
        marked = [s for s in spans if s[0] == "window"]
        if marked:
            window = (marked[0][1], marked[0][2])
        elif spans:
            window = (min(s[1] for s in spans), max(s[2] for s in spans))
        else:
            allev = [e for evs in device.values() for e in evs]
            window = (min(e[1] for e in allev), max(e[2] for e in allev))
    return reduce_events(device, spans, window)
