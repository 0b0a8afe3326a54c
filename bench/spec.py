"""Find the benchmark's pieces by name: cells, configurations, traffic, metrics.

Everything that belongs to one configuration, one cell's traffic or one
metric sits in a file of its own under ``bench/``; this module is the only
place that maps a name to a file, so a later change adds a piece by adding
its file and its entry in ``BENCHMARK.json``, and edits nothing else.

  * ``BENCHMARK.json``               cells (``workloads``) and metrics
  * ``bench/configs/<config>.json``  models, shapes, chips, fixed SLOs
  * ``bench/workloads/<traffic>.json``  the cell's traffic: arrivals,
    rates, lengths, the scheduler's plan rates, when the window stops
  * ``bench/metrics/<quantity>.py``  ``read(record, arg)`` of one metric;
    a metric ``<quantity>.<arg>`` (``decode_step_ms.yi-9b``) is read by
    ``<quantity>.py`` with the part after the first dot as ``arg``
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from collections.abc import Callable


@dataclasses.dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    end_to_end: bool
    workloads: tuple[str, ...] | None = None   # None: every cell

    def applies(self, cell: str) -> bool:
        return self.workloads is None or cell in self.workloads


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config: str
    traffic: str
    chips: int
    why: str


class Benchmark:
    """``BENCHMARK.json`` of a checkout rooted at ``root``."""

    def __init__(self, root: str):
        self.root = os.path.abspath(root)
        with open(os.path.join(self.root, "BENCHMARK.json")) as f:
            self.data = json.load(f)
        self.cells = {w["name"]: Cell(w["name"], w["config"], w["traffic"],
                                      int(w["chips"]), w["why"])
                      for w in self.data["workloads"]}
        self.metrics = [self._metric(m, True) for m in self.data["end_to_end"]]
        self.metrics += [self._metric(m, False) for m in self.data["per_layer"]]

    @staticmethod
    def _metric(m: dict, end_to_end: bool) -> Metric:
        wl = m.get("workloads")
        return Metric(m["name"], m["unit"], m["better"], m["source"],
                      end_to_end, tuple(wl) if wl is not None else None)

    def cell(self, name: str) -> Cell:
        if name not in self.cells:
            raise KeyError(f"no cell {name!r} in BENCHMARK.json; "
                           f"known: {sorted(self.cells)}")
        return self.cells[name]

    def cell_metrics(self, cell: str, trace: bool) -> list[Metric]:
        """The metrics a run of ``cell`` prints: end to end, or per layer
        with ``trace``."""
        return [m for m in self.metrics
                if m.end_to_end != trace and m.applies(cell)]

    def config(self, name: str) -> dict:
        return _load_json(self.root, "configs", name)

    def traffic(self, name: str) -> dict:
        return _load_json(self.root, "workloads", name)

    def reader(self, metric: str) -> Callable[[dict, str | None], float | None]:
        """``read(record, arg)`` of ``metric``, from ``bench/metrics/``."""
        quantity, _, _ = metric.partition(".")
        path = os.path.join(self.root, "bench", "metrics", quantity + ".py")
        if not os.path.isfile(path):
            raise KeyError(f"metric {metric!r}: no reader {path}")
        spec = importlib.util.spec_from_file_location(
            f"bench_metric_{quantity}", path)
        module = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(module)
        return module.read

    def read(self, metric: str, record: dict) -> float | None:
        """The value of ``metric`` in ``record``, or None where the run
        has nothing for it to read."""
        _, _, arg = metric.partition(".")
        return self.reader(metric)(record, arg or None)


def _load_json(root: str, kind: str, name: str) -> dict:
    path = os.path.join(root, "bench", kind, name + ".json")
    if not os.path.isfile(path):
        raise KeyError(f"no {kind[:-1]} {name!r}: {path} does not exist")
    with open(path) as f:
        return json.load(f)
