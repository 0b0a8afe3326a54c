"""Reduce a JAX profiler trace of a run to what the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
planes are ``/device:TPU:<n>``; on each, the ``XLA Modules`` line holds one
event per program run (``jit_decode(…)``) and the ``XLA Ops`` line one per
operation.  The harness's own spans (``jax.profiler.TraceAnnotation``:
``window``, ``generate/<model>``) are events on a host plane, on the same
clock as the device's.

  * busy: the union of a device's program runs inside the window;
  * programs: each program run of the window, in order, with its device
    time;
  * all-reduce: time of the operations named ``all-reduce…`` (the
    collective itself, its start and done halves, or a fusion around it);
  * breakdown: the operations that took most time, and the longest idle
    gaps, labelled by the harness span open at the time (``wait`` outside
    every ``generate`` span).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIXES = ("window", "generate/")
MODULES, OPS = "XLA Modules", "XLA Ops"


@dataclasses.dataclass
class Device:
    ops: list = dataclasses.field(default_factory=list)       # (start, end, name)
    modules: list = dataclasses.field(default_factory=list)   # (start, end, name)


@dataclasses.dataclass
class Trace:
    devices: dict[int, Device]
    spans: list  # (start_ns, end_ns, name) of the harness's host spans


def find(directory: str) -> str:
    """The one ``.xplane.pb`` under ``directory``."""
    paths = glob.glob(os.path.join(directory, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one .xplane.pb under {directory}, "
                                f"found {len(paths)}")
    return paths[0]


def load(path: str, ops_of: int = 0) -> Trace:
    """Program runs of every device, operations of device ``ops_of`` only
    (reading every device's operations would take minutes)."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices: dict[int, Device] = {}
    spans = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = devices.setdefault(int(m.group(1)), Device())
            for line in plane.lines:
                dest = {MODULES: dev.modules, OPS: dev.ops}.get(line.name)
                if line.name == OPS and int(m.group(1)) != ops_of:
                    continue
                if dest is None:
                    continue
                for e in line.events:
                    dest.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIXES):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                      e.name))
    if not devices:
        raise ValueError(f"{path}: no TPU device plane in the trace")
    spans.sort()
    return Trace(devices, spans)


def _union(intervals: list, lo: float, hi: float) -> np.ndarray:
    """Disjoint sorted (start, end) intervals covering ``intervals`` in [lo, hi]."""
    if not intervals:
        return np.zeros((0, 2))
    iv = np.array([(s, e) for s, e, _ in intervals], dtype=np.float64)
    iv = np.clip(iv, lo, hi)
    iv = iv[np.argsort(iv[:, 0])]
    iv = iv[iv[:, 1] > iv[:, 0]]
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.array(out).reshape(-1, 2)


def _covered(union: np.ndarray, lo: float, hi: float) -> float:
    if not len(union):
        return 0.0
    return float(np.clip(np.minimum(union[:, 1], hi) - np.maximum(union[:, 0], lo),
                         0, None).sum())


def reduce(trace: Trace, devices: list[int] | None = None) -> dict:
    """The numbers the metrics read, over the trace's ``window`` span."""
    windows = [s for s in trace.spans if s[2] == "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one 'window' span, found {len(windows)}")
    lo, hi, _ = windows[0]
    ids = sorted(devices if devices is not None else trace.devices)
    gens = [s for s in trace.spans if s[2].startswith("generate/")]
    busy_s, in_spans_s = [], []
    for i in ids:
        u = _union(trace.devices[i].modules, lo, hi)
        busy_s.append(_covered(u, lo, hi) / 1e9)
        in_spans_s.append(sum(_covered(u, s, e) for s, e, _ in gens) / 1e9)
        if i == ids[0]:
            union0 = u
    dev0 = trace.devices[ids[0]]
    # program runs of the window, in the order the device ran them: the
    # device's clock may stand a fraction of a millisecond off the host's,
    # so runs are matched to the harness's batches by order, not by time.
    programs = [[name.split("(")[0], (e - s) / 1e9]
                for s, e, name in sorted(dev0.modules) if lo - 1e6 <= s < hi]
    allreduce = sum(min(e, hi) - max(s, lo) for s, e, n in dev0.ops
                    if op_name(n).startswith("all-reduce") and e > lo and s < hi) / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": float(np.mean(busy_s)),
        "busy_in_spans_s": float(np.mean(in_spans_s)),
        "spans_s": sum(e - s for s, e, _ in gens) / 1e9,
        "busy_s_dev0": busy_s[0],
        "allreduce_s_dev0": allreduce,
        "programs": programs,
        "events": sum(len(trace.devices[i].ops) + len(trace.devices[i].modules)
                      for i in ids),
        "breakdown": breakdown(trace, union0, gens, lo, hi),
    }


def op_name(event_name: str) -> str:
    """``%fusion.72 = bf16[...] fusion(...)`` -> ``fusion.72``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


#: Operations that contain others on the same line; their time is their body's.
CONTAINERS = ("while", "conditional", "call")


def _label(t: float, gens: list) -> str:
    return next((n for s, e, n in gens if s <= t < e), "wait")


def breakdown(trace: Trace, union0: np.ndarray, gens: list, lo: float,
              hi: float, top: int = 10) -> dict:
    """The device operations that took most time on the first device, each
    named ``<span>:<program>/<operation>``, and the longest idle gaps, each
    named by the harness span open at its middle."""
    dev0 = trace.devices[min(trace.devices)]
    mods = sorted(dev0.modules)
    mod_starts = np.array([m[0] for m in mods], dtype=np.float64)
    per_op = collections.Counter()
    for s, e, name in dev0.ops:
        op = op_name(name)
        if e <= lo or s >= hi or op.split(".")[0] in CONTAINERS:
            continue
        k = int(np.searchsorted(mod_starts, s, side="right")) - 1
        program = mods[k][2].split("(")[0] if k >= 0 and s < mods[k][1] else "?"
        span = _label(s, gens).removeprefix("generate/")
        per_op[f"{span}:{program}/{op}"] += (min(e, hi) - max(s, lo)) / 1e9
    edges = np.concatenate([[lo], union0.ravel(), [hi]]).reshape(-1, 2)
    gaps = sorted(((e - s, s) for s, e in edges if e > s), reverse=True)[:top]
    return {"device_ops": [[n, t] for n, t in per_op.most_common(top)],
            "idle_gaps": [[_label(start + length / 2, gens), length / 1e9]
                          for length, start in gaps]}
