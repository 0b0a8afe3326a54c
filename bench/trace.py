"""Reduce a JAX profiler trace of a run to what the per-layer metrics read.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes.  Device
planes are ``/device:TPU:<n>``; on each, the ``XLA Modules`` line holds one
event per program run (``jit_decode(…)``) and the ``XLA Ops`` line one per
operation.  Host spans (``jax.profiler.TraceAnnotation``) are events on a
host plane, on the same clock as the device's: the harness's ``window``,
and the program's ``serve.sleep``, ``serve.form`` and
``serve.batch/<model>`` (``repro.obs.served``).

  * busy: the union of a device's program runs inside the window;
  * in batches: the part of that union inside ``serve.batch/`` spans;
  * programs: each program run of the window, in order, with its device
    time;
  * all-reduce: time of the operations named ``all-reduce…`` (the
    collective itself, its start and done halves, or a fusion around it);
  * breakdown: the operations that took most time, each named by the model
    whose batch ran it, and the longest idle gaps, each labelled by what
    the program was doing at its middle (``Labels``).
"""
from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
SPAN_PREFIXES = ("window", "serve.sleep", "serve.form", "serve.batch/")
BATCH = "serve.batch/"
MODULES, OPS = "XLA Modules", "XLA Ops"


@dataclasses.dataclass
class Device:
    ops: list = dataclasses.field(default_factory=list)       # (start, end, name)
    modules: list = dataclasses.field(default_factory=list)   # (start, end, name)


@dataclasses.dataclass
class Trace:
    devices: dict[int, Device]
    spans: list      # (start_ns, end_ns, name) of the host spans read
    start_ns: int    # the profile's start (its ``profile_start_time``)


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
    start_ns = 0
    for plane in data.planes:
        start_ns = dict(plane.stats).get("profile_start_time", start_ns)
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
    return Trace(devices, spans, start_ns)


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


def late_wakes(window, start_ns: int) -> list:
    """The intervals, on a trace's clock (ns after ``start_ns``), in which
    the replay loop slept past the next due time: each ``serve.sleep`` of
    the program's ``window`` from its ``due_ms`` to its ``woke_ms``."""
    return [(window.trace_ns(s.due_ms, start_ns), window.trace_ns(s.woke_ms, start_ns))
            for s in window.sleeps if s.woke_ms > s.due_ms]


def reduce(trace: Trace, devices: list[int] | None = None,
           late: list = ()) -> dict:
    """The numbers the metrics read, over the trace's ``window`` span;
    ``late`` as ``late_wakes`` gives them."""
    windows = [s for s in trace.spans if s[2] == "window"]
    if len(windows) != 1:
        raise ValueError(f"expected one 'window' span, found {len(windows)}")
    lo, hi, _ = windows[0]
    ids = sorted(devices if devices is not None else trace.devices)
    program = [s for s in trace.spans if s[2] != "window"]
    batches = [s for s in program if s[2].startswith(BATCH)]
    busy_s, in_spans_s = [], []
    for i in ids:
        u = _union(trace.devices[i].modules, lo, hi)
        busy_s.append(_covered(u, lo, hi) / 1e9)
        in_spans_s.append(sum(_covered(u, s, e) for s, e, _ in batches) / 1e9)
        if i == ids[0]:
            union0 = u
    dev0 = trace.devices[ids[0]]
    # program runs of the window, in the order the device ran them: the
    # device's clock may stand a fraction of a millisecond off the host's,
    # so runs are matched to the window's batches by order, not by time.
    programs = [[name.split("(")[0], (e - s) / 1e9]
                for s, e, name in sorted(dev0.modules) if lo - 1e6 <= s < hi]
    allreduce = sum(min(e, hi) - max(s, lo) for s, e, n in dev0.ops
                    if op_name(n).startswith("all-reduce") and e > lo and s < hi) / 1e9
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": float(np.mean(busy_s)),
        "busy_in_spans_s": float(np.mean(in_spans_s)),
        "spans_s": sum(e - s for s, e, _ in batches) / 1e9,
        "busy_s_dev0": busy_s[0],
        "allreduce_s_dev0": allreduce,
        "programs": programs,
        "events": sum(len(trace.devices[i].ops) + len(trace.devices[i].modules)
                      for i in ids),
        "breakdown": breakdown(trace, union0, Labels(program, late), lo, hi),
    }


def op_name(event_name: str) -> str:
    """``%fusion.72 = bf16[...] fusion(...)`` -> ``fusion.72``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


#: Operations that contain others on the same line; their time is their body's.
CONTAINERS = ("while", "conditional", "call")


class Labels:
    """What the program was doing at a time on the trace's clock: the name
    of its span open then (``serve.sleep``, ``serve.form``,
    ``serve.batch/<model>``, which never overlap), ``serve.sleep.late``
    inside a sleep past its due time, and ``loop`` outside every span."""

    def __init__(self, spans: list, late: list = ()):
        self.spans = sorted(spans)
        self.starts = [s for s, _, _ in self.spans]
        self.late = sorted(late)
        self.late_starts = [s for s, _ in self.late]

    def __call__(self, t: float) -> str:
        k = bisect.bisect_right(self.starts, t) - 1
        if k < 0 or t >= self.spans[k][1]:
            return "loop"
        name = self.spans[k][2]
        if name == "serve.sleep":
            j = bisect.bisect_right(self.late_starts, t) - 1
            if j >= 0 and t < self.late[j][1]:
                return "serve.sleep.late"
        return name


def breakdown(trace: Trace, union0: np.ndarray, label: Labels, lo: float,
              hi: float, top: int = 10) -> dict:
    """The device operations that took most time on the first device, each
    named ``<model>:<program>/<operation>`` by the batch that ran it, and
    the longest idle gaps, each named by what the program was doing at its
    middle."""
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
        model = label(s).removeprefix(BATCH)
        per_op[f"{model}:{program}/{op}"] += (min(e, hi) - max(s, lo)) / 1e9
    edges = np.concatenate([[lo], union0.ravel(), [hi]]).reshape(-1, 2)
    gaps = sorted(((e - s, s) for s, e in edges if e > s), reverse=True)[:top]
    return {"device_ops": [[n, t] for n, t in per_op.most_common(top)],
            "idle_gaps": [[label(start + length / 2), length / 1e9]
                          for length, start in gaps]}
