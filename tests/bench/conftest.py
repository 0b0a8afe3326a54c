"""Fixtures of the benchmark's CPU tests."""
import pytest

from bench_helpers import write_smoke_bench


@pytest.fixture
def on_cpu(monkeypatch):
    """Let the harness serve on the CPU: its look for a chip and its table
    of peaks are steered here, in the test."""
    import jax

    from bench import peaks, run
    monkeypatch.setattr(run, "accelerator_devices", lambda chips: jax.devices()[:chips])
    monkeypatch.setitem(peaks.PEAKS, "cpu", {"bf16_flops": 1e12, "hbm_bytes_s": 1e11,
                                             "hbm_bytes": 1e10})


@pytest.fixture(scope="module")
def smoke_root(tmp_path_factory):
    root = str(tmp_path_factory.mktemp("bench_copy"))
    write_smoke_bench(root)
    return root
