#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` on the local chip(s) and print its result.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One process, from the root of a checkout, through the program's own
serving layers (``repro.launch.serve``):

  1. set-up: one ``ModelRunner`` per model of the cell's configuration on a
     (1, chips) serving mesh, with weights made from ``--seed`` on the
     device (``bench.weights``); ``compile()`` warms every shape the
     configuration serves, then ``measure()`` times L(b);
  2. Elastic Partitioning over the measured L(b), at the cell's fixed plan
     rates and the configuration's fixed SLOs; batch caps from the
     placement, as ``serve.run`` takes them;
  3. the window: the cell's traffic (``bench.traffic``) for ``--seconds``,
     served by ``serve.replay``, which logs what it did in the program's
     recorder (``repro.obs.served.RECORDER``): requests, batches and their
     spans, on the window's one clock.  The harness calls no
     ``ModelRunner`` method in the window; its one hook
     (``Watch.on_launch``) runs right before each batch starts, starts the
     profiler where the trace begins, and refuses a batch that would
     start after the window's end (``"stop": "window"``), or a minute
     after it (``"stop": "drain"``);
  4. ``--trace 1`` records the profiler trace of the window's last
     ``TRACE_S`` seconds and reports the per-layer metrics, ``--trace 0``
     the end-to-end ones; each metric is read by its own file in
     ``bench/metrics``;
  5. once the window has closed, the peak memory is read and the program's
     state freed, served tokens are checked against the plain float32
     reference (``bench.check``).

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, then ``checks``: each number compared with its limit); the
same numbers close standard error.  With no TPU, or fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for _p in (os.path.join(ROOT, "src"), ROOT):
    if _p not in sys.path:
        sys.path.insert(0, _p)

import jax  # noqa: E402

from bench import check, peaks, traffic, weights  # noqa: E402
from bench import trace as trace_mod  # noqa: E402
from bench.spec import Benchmark  # noqa: E402

#: How long after the window's end a ``drain`` cell still starts batches.
DRAIN_S = 60.0


class NoChip(RuntimeError):
    """JAX finds no TPU, or fewer chips than the cell asks for."""


class WindowClosed(Exception):
    """A batch would start after the window's end."""


def accelerator_devices(chips: int) -> list:
    """The first ``chips`` TPU devices; anything else is an error."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX finds no TPU (platform {devices[0].platform!r})")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX finds {len(devices)}")
    return devices[:chips]


def program_config(model: dict):
    """The program's ``ModelConfig`` of a configuration file's model: its
    published config with the file's sizes laid over it."""
    from repro.configs import get_config

    cfg = get_config(model["arch"])
    fields = {f.name for f in dataclasses.fields(cfg)}
    return dataclasses.replace(
        cfg, **{k: v for k, v in model["sizes"].items() if k in fields})


def _same_layout(ours, theirs, name: str) -> None:
    a = jax.tree_util.tree_structure(ours)
    b = jax.tree_util.tree_structure(theirs)
    if a != b:
        raise ValueError(f"{name}: the benchmark's weight tree {a} is not the "
                         f"program's {b}")
    for x, y in zip(jax.tree.leaves(ours), jax.tree.leaves(theirs)):
        if (x.shape, x.dtype) != (y.shape, y.dtype):
            raise ValueError(f"{name}: weight {x.shape} {x.dtype} where the "
                             f"program has {y.shape} {y.dtype}")


@dataclasses.dataclass
class Setup:
    """A cell ready to serve: what set-up built, and the window's inputs."""
    bench: Benchmark
    config: dict
    traffic: dict
    devices: list
    runners: list
    caps: dict
    latency: object            # serve.MeasuredLatency over the measured L(b)
    profiles: dict


def shapes_of(config: dict):
    from repro.launch import serve

    s = config["shapes"]
    return serve.ServeShapes(prompt_lens=tuple(s["prompt_lens"]),
                             gen_range=tuple(s["gen_range"]),
                             batch_buckets=tuple(s["batch_buckets"]))


def build(bench: Benchmark, cell_name: str, seed: int) -> Setup:
    """Runners on the device, compiled, warmed and measured; the batch caps."""
    from repro.launch import serve
    from repro.launch import sharding as shr
    from repro.launch.mesh import make_serving_mesh
    from repro.models.model import Model

    cell = bench.cell(cell_name)
    config, traffic_spec = bench.config(cell.config), bench.traffic(cell.traffic)
    if config["chips"] != cell.chips:
        raise ValueError(f"{cell_name}: BENCHMARK.json gives {cell.chips} chips, "
                         f"configuration {cell.config} {config['chips']}")
    shapes = shapes_of(config)
    if (not set(traffic_spec["prompt_lens"]) <= set(shapes.prompt_lens)
            or traffic_spec["gen_range"][0] < shapes.gen_range[0]
            or traffic_spec["gen_range"][1] > shapes.gen_range[1]):
        raise ValueError(f"{cell.traffic}: lengths outside what {cell.config} "
                         "compiles")
    devices = accelerator_devices(cell.chips)
    mesh = make_serving_mesh(devices)
    runners = []
    for i, m in enumerate(config["models"]):
        cfg = program_config(m)
        layout = Model(cfg).param_shapes()
        _same_layout(weights.shapes(m["sizes"]), layout, m["arch"])
        params = weights.make(m["sizes"], weights.root_key(seed, i),
                              shr.param_shardings(cfg, layout, mesh, fsdp=False))
        runners.append(serve.ModelRunner(cfg, mesh, shapes, seed, params=params))
    for r in runners:
        r.compile()
        r.measure()
    caps, lat, profiles, _ = plan(
        runners, {m["arch"]: m["slo_ms"] for m in config["models"]},
        traffic_spec["plan_rates_req_s"], devices[0])
    return Setup(bench, config, traffic_spec, devices, runners, caps, lat, profiles)


def plan(runners, slo_ms: dict, rates: dict, device):
    """Elastic Partitioning of the measured runners at fixed rates and SLOs.

    Returns the batch cap of each model (its largest placed batch), the
    latency provider, the profiles and the placement.
    """
    from repro.core.elastic import ElasticPartitioning
    from repro.core.hardware import AcceleratorSpec, ClusterSpec
    from repro.launch import serve

    profiles = {r.name: serve.measured_profile(r, slo_ms[r.name]) for r in runners}
    lat = serve.MeasuredLatency({r.name: r.latency_ms for r in runners})
    acc = AcceleratorSpec(name=device.device_kind, peak_tflops=0.0, hbm_gbs=0.0,
                          hbm_gb=0.0)
    sched = ElasticPartitioning(profiles, cluster=ClusterSpec(acc, n_devices=1),
                                lat=lat)
    placement = sched.schedule(rates)
    caps = {r.name: 1 for r in runners}
    for let in placement.gpulets:
        for a in let.assignments:
            caps[a.model] = max(caps[a.model], a.batch)
    return caps, lat, profiles, placement


class Launches(list):
    """A window's ``forms`` log that calls ``hook(ms)`` once each form has
    entered it, with the form's end in ms since the window's 0.

    ``serve.replay`` logs a batch's ``serve.form`` span right before the
    batch starts (before its ``serve.batch/<model>`` span opens), so the
    hook runs where work for newly formed requests is about to start.  An
    exception from the hook leaves ``replay`` there: the formed batch never
    starts, and no batch of it enters the log.
    """

    def __init__(self, hook):
        super().__init__()
        self.hook = hook

    def append(self, span) -> None:
        super().append(span)
        self.hook(span.end_ms)


class Watch:
    """The harness's hook on one window of ``recorder``: its deadline, and
    the profiler trace of its last ``TRACE_S`` seconds.

    The trace starts at the first batch due to start at or after
    ``trace_from_ms`` (before the window, where that is 0), so that no
    export stalls the window: it stops once the window has closed.  Its
    ``window`` span brackets the traced part.
    """

    def __init__(self, recorder, deadline_ms: float, trace_dir: str | None = None,
                 trace_from_ms: float = 0.0):
        self.recorder, self.deadline_ms = recorder, deadline_ms
        self.trace_dir, self.trace_from_ms = trace_dir, trace_from_ms
        self.traced_from = None     # index of the first traced batch
        self._span = None

    @contextlib.contextmanager
    def on_launch(self):
        """``launch`` right before each batch of the ``serve.replay``
        windows opened inside starts (``Launches``); cleared once they have
        closed.

        The program has no hook of its own: this one rides on the window
        log that ``serve.replay`` writes, and changes nothing the loop does.
        """
        recorder = self.recorder
        open_window = recorder.open_window

        def opened(reqs):
            window = open_window(reqs)
            window.forms = Launches(self.launch)
            return window

        recorder.open_window = opened
        try:
            yield
        finally:
            del recorder.open_window
            if recorder.window is not None:
                recorder.window.forms = list(recorder.window.forms)

    def launch(self, ms: float) -> None:
        """A batch is about to start ``ms`` after the window's 0."""
        if ms >= self.deadline_ms:
            raise WindowClosed
        self.start_trace(ms)

    def start_trace(self, ms: float) -> None:
        if self.trace_dir and self._span is None and ms >= self.trace_from_ms:
            jax.profiler.start_trace(self.trace_dir)
            self._span = jax.profiler.TraceAnnotation("window")
            self._span.__enter__()
            window = self.recorder.window
            self.traced_from = (len(window.batches) if window is not None
                                and window.open else 0)

    def stop_trace(self) -> None:
        if self._span is not None:
            self._span.__exit__(None, None, None)
            jax.profiler.stop_trace()
            self._span = None


#: Seconds of each traced run's window that the profiler records: its end.
TRACE_S = 10.0


def serve_window(setup: Setup, seed: int, seconds: float, trace_dir: str | None,
                 traffic_spec: dict | None = None, caps: dict | None = None):
    """Serve the cell's traffic for one window.  Returns (requests, the
    program's window log, error, index of the first traced batch):
    ``error`` names what stopped the window early."""
    from repro.launch import serve
    from repro.obs.served import RECORDER

    spec = traffic_spec or setup.traffic
    reqs = [serve.ServeRequest(model=m, arrival_ms=t, slo_ms=slo, prompt=p,
                               max_new=g)
            for m, t, p, g, slo in traffic.requests(
                spec, setup.config["models"], seed, seconds)]
    deadline_s = seconds + (DRAIN_S if spec["stop"] == "drain" else 0.0)
    watch = Watch(RECORDER, deadline_s * 1e3, trace_dir, (seconds - TRACE_S) * 1e3)
    error = None
    watch.start_trace(0.0)
    try:
        with watch.on_launch():
            serve.replay(setup.runners, reqs, caps or setup.caps)
    except WindowClosed:
        pass
    except FloatingPointError as e:   # replay's check of the logits
        error = str(e)
    finally:
        watch.stop_trace()
    return reqs, RECORDER.window, error, watch.traced_from


def batch_rows(reqs, window) -> list[dict]:
    """The window's batches, in the order they ran, with their requests'
    own generation lengths (``max_new``)."""
    return [{"id": b.id, "model": b.span.model, "launch_ms": b.span.launch_ms,
             "done_ms": b.span.done_ms, "size": b.span.n,
             "prompt_len": b.prompt_len, "steps": b.steps,
             "request_ids": b.request_ids,
             "max_new": [reqs[i].max_new for i in b.request_ids]}
            for b in window.batches]


def request_rows(reqs, window, stop: str) -> list[dict]:
    """Each request the window attempted, with the launch of the batch that
    answered it (the window's own link); ``stop: window`` attempts only the
    requests whose batch started."""
    rows = []
    for r, bid in zip(reqs, window.batch):
        if bid < 0 and stop == "window":
            continue
        served = None if r.output is None else len(r.output)
        rows.append({"model": r.model, "due_ms": r.arrival_ms,
                     "done_ms": r.completion_ms, "slo_ms": r.slo_ms,
                     "prompt_len": len(r.prompt), "max_new": r.max_new,
                     "served": served,
                     "batch_start_ms": (None if bid < 0
                                        else window.batches[bid].span.launch_ms)})
    return rows


def record_of(setup: Setup, rows, batches, seconds, setup_s,
              reduced_trace) -> dict:
    """What the metric readers read."""
    for b in batches:
        b["predicted_ms"] = setup.latency.latency_ms(
            setup.profiles[b["model"]], b["size"], 1.0)
    models = {}
    for m, r in zip(setup.config["models"], setup.runners):
        models[r.name] = {"sizes": m["sizes"], "prefill_ms": dict(r.prefill_ms),
                          "decode_ms": dict(r.decode_ms),
                          "latency_ms": dict(r.latency_ms)}
    return {"seconds": seconds, "setup_s": setup_s,
            "chips": len(setup.devices),
            "peaks": peaks.peaks(setup.devices[0].device_kind),
            "requests": rows, "batches": batches, "models": models,
            "trace": reduced_trace}


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


def free(setup: Setup) -> None:
    """Drop the program's weights and programs from the device."""
    setup.runners.clear()
    gc.collect()


def correctness(setup: Setup, reqs, seed: int, control: bool = False) -> dict:
    """Per model: the widest gap of the served tokens below the reference's
    best logit, over a sample of finished requests."""
    out = {}
    for i, m in enumerate(setup.config["models"]):
        done = [r for r in reqs if r.model == m["arch"] and r.output is not None]
        if not done:
            continue
        out[m["arch"]] = check.gaps(m["sizes"], weights.root_key(seed, i),
                                    check.sample(done, seed), control,
                                    setup.devices[0])
    return out


def run(root: str, cell_name: str, seed: int, seconds: float, trace: bool) -> dict:
    """One run of one cell: the result object the last line prints."""
    bench = Benchmark(root)
    setup = build(bench, cell_name, seed)
    for r in setup.runners:
        print(f"measured {r.name} L_ms {r.latency_ms} prefill_ms {r.prefill_ms} "
              f"decode_ms {r.decode_ms} cap {setup.caps[r.name]}", file=sys.stderr)
    trace_dir = tempfile.mkdtemp(prefix="bench_trace_") if trace else None
    try:
        setup_s = time.perf_counter() - PROCESS_START
        reqs, window, error, traced_from = serve_window(setup, seed, seconds,
                                                        trace_dir)
        memory = peak_bytes(setup.devices)
        reduced = None
        if trace:
            t0 = time.perf_counter()
            t = trace_mod.load(trace_mod.find(trace_dir))
            reduced = trace_mod.reduce(t, [d.id for d in setup.devices],
                                       trace_mod.late_wakes(window, t.start_ns))
            reduced["first_batch"] = traced_from
            print(f"trace: {reduced['events']} device events read in "
                  f"{time.perf_counter() - t0:.1f} s", file=sys.stderr)
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    rows = request_rows(reqs, window, setup.traffic["stop"])
    record = record_of(setup, rows, batch_rows(reqs, window), seconds, setup_s,
                       reduced)
    metrics = {}
    for m in bench.cell_metrics(cell_name, trace):
        value = bench.read(m.name, record)
        if value is not None:
            metrics[m.name] = {"value": value, "unit": m.unit}
    failed = sum(r["served"] != r["max_new"] for r in rows)
    dev = setup.devices[0]
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(setup.devices), "memory_peak_bytes": memory}
    if reduced:
        device["busy_s"] = reduced["busy_s"]
        device["window_s"] = reduced["window_s"]
    models = setup.config["models"]
    free(setup)
    checks = {"failed": {"value": failed, "limit": 0}}
    if error:
        checks["finite_logits"] = {"value": 0, "limit": 1}
    else:
        readings = correctness(setup, reqs, seed)
        for m in models:
            g = readings.get(m["arch"])
            for name, limit in m["checks"].items():
                value = None if g is None else check.number(g["served"], name)
                checks[f"{name}.{m['arch']}"] = {"value": value, "limit": limit}
            if g is not None:
                print(f"reading {m['arch']}: " + " ".join(
                    f"{n} {check.number(g['served'], n)}" for n in check.NUMBERS)
                    + f" over {g['positions']} tokens of {g['requests']} requests",
                    file=sys.stderr)
    correct = failed == 0 and not error and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for k, c in checks.items() if k.startswith("gap"))
    result = {"correct": bool(correct), "attempted": len(rows), "failed": failed,
              "metrics": metrics, "device": device}
    if reduced:
        result["breakdown"] = reduced["breakdown"]
    result["checks"] = checks
    return result


def use_compile_cache(root: str) -> None:
    """JAX's persistent compile cache inside the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program kept, so that only a
    checkout's first run compiles."""
    from repro.launch import serve

    serve.use_compile_cache(root)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def emit(result: dict) -> None:
    """The numbers compared, each with its limit, close standard error; the
    result is the last line of standard output."""
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)


def main(argv=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    use_compile_cache(root)
    try:
        result = run(root, args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    emit(result)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
