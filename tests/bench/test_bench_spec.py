"""BENCHMARK.json keeps to its contract, and every piece loads by name."""
import dataclasses
import json
import os
import re
import shutil

import pytest

from bench_helpers import ROOT

from bench.spec import Benchmark

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


@pytest.fixture(scope="module")
def bench():
    return Benchmark(ROOT)


def _line(text):
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_benchmark_json_keeps_to_the_contract(bench):
    d = bench.data
    assert set(d) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= len(d["command"]) <= 32 and all(_line(w) for w in d["command"])
    assert 1 <= len(d["paths"]) <= 16
    for p in d["paths"]:
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p) and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    assert 1 <= d["run_seconds"] <= 51
    runs = 2 + 14 * 24
    assert runs * (d["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200
    config_names = set()
    for c in d["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert c["file"].startswith("bench/") and os.path.isfile(os.path.join(ROOT, c["file"]))
        assert len(c["reduced"]) <= 16
        config_names.add(c["name"])
    cells = {}
    for w in d["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["traffic"])
        assert w["config"] in config_names and w["chips"] in (1, 4) and _line(w["why"])
        cells[w["name"]] = w
    assert len({(w["config"], w["traffic"]) for w in cells.values()}) == len(cells)
    assert sum(w["chips"] == 4 for w in cells.values()) <= max(1, len(cells) // 2)
    metrics = d["end_to_end"] + d["per_layer"]
    assert len({m["name"] for m in metrics}) == len(metrics)
    e2e = {m["name"]: m for m in d["end_to_end"]}
    for m in d["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in {"host_clock", "device_trace"}
        assert 0.01 <= m["bound"] <= 0.25
    assert e2e["setup_s"]["bound"] <= 0.25 and "workloads" not in e2e["setup_s"]
    layers = set()
    for m in d["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert _line(m["layer"]) and m["moves"] in e2e
        layers.add(m["layer"])
        moved = e2e[m["moves"]].get("workloads", list(cells))
        assert set(m.get("workloads", moved)) <= set(moved)
    for m in metrics:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher") and m["source"] in SOURCES
        assert set(m.get("workloads", cells)) <= set(cells)
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    for cell in cells:
        reported = [m for m in bench.metrics if m.applies(cell)]
        assert any(m.name == "setup_s" for m in reported)
        assert any(m.end_to_end and m.name != "setup_s" for m in reported)
        assert any(not m.end_to_end for m in reported)


def test_every_cell_config_traffic_and_metric_loads_by_name(bench):
    for name, cell in bench.cells.items():
        assert bench.config(cell.config)["chips"] == cell.chips
        traffic = bench.traffic(cell.traffic)
        assert traffic["arrivals"] in ("poisson", "backlog")
        assert traffic["stop"] in ("drain", "window")
    for m in bench.metrics:
        assert callable(bench.reader(m.name))
    with pytest.raises(KeyError):
        bench.cell("no-such-cell")
    with pytest.raises(KeyError):
        bench.reader("no_such_metric")


def test_configuration_files_hold_the_published_sizes(bench):
    """``reduced`` is empty: every size the program's config also has is the
    published one, and the configuration names its source."""
    from repro.configs import get_config

    for c in bench.data["configs"]:
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"] and c["reduced"] == cfg["reduced"] == []
        for m in cfg["models"]:
            program = get_config(m["arch"])
            published = dict(dataclasses.asdict(program), d_head=program.head_dim)
            shared = {k: v for k, v in m["sizes"].items() if k in published}
            assert shared == {k: published[k] for k in shared}, m["arch"]
            assert m["slo_ms"] > 0 and m["departures"] and m["checks"]
            assert all(v > 0 for v in m["checks"].values())


def test_a_new_cell_and_a_new_metric_are_found_with_no_edit(tmp_path):
    """Dropping in a traffic file, a reader and their entries is enough."""
    shutil.copytree(os.path.join(ROOT, "bench"), tmp_path / "bench")
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        d = json.load(f)
    with open(tmp_path / "bench" / "workloads" / "mix-burst.json", "w") as f:
        json.dump({"schedule_seed": 3, "arrivals": "poisson", "rates_req_s": {"chatglm3-6b": 0.5},
                   "plan_rates_req_s": {"chatglm3-6b": 0.5}, "stop": "drain",
                   "prompt_lens": [128], "gen_range": [16, 16]}, f)
    (tmp_path / "bench" / "metrics" / "requests_done.py").write_text(
        "def read(record, arg):\n"
        "    return sum(r['done_ms'] is not None for r in record['requests'])\n")
    d["workloads"].append({"name": "mix-burst", "config": "chatglm3-6b_mamba2-780m",
                           "traffic": "mix-burst", "chips": 1, "why": "test"})
    d["per_layer"].append({"name": "requests_done", "unit": "req", "better": "higher",
                           "source": "program_counter", "layer": "replay loop",
                           "moves": "latency_p90_ms", "workloads": ["mix-burst"]})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(d))
    b = Benchmark(str(tmp_path))
    assert b.traffic(b.cell("mix-burst").traffic)["rates_req_s"] == {"chatglm3-6b": 0.5}
    assert [m.name for m in b.cell_metrics("mix-burst", trace=True)] == ["requests_done"]
    record = {"requests": [{"done_ms": 1.0}, {"done_ms": None}]}
    assert b.read("requests_done", record) == 1
