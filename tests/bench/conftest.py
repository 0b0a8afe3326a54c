"""Fixtures of the benchmark's CPU tests."""
import pytest

from bench_helpers import steer_to_cpu, write_smoke_bench


@pytest.fixture
def on_cpu(monkeypatch):
    steer_to_cpu(monkeypatch)


@pytest.fixture(scope="module")
def smoke_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench_copy"))
    write_smoke_bench(root)
    return root
