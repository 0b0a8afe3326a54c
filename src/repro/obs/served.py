"""Recorder of the served path: spans and counters of ``serve.replay`` and
``ModelRunner``.

It is always on and in memory.  :data:`RECORDER` holds

  * ``window``: the latest replay window.  ``replay`` opens a fresh one, so
    memory stays bounded by one window.  It holds each request (id = its
    index in the replayed list), each sleep of the loop, each batch
    formation and each batch, and ``compiles``: the programs compiled
    while it was open;
  * ``setup``: per model, the set-up spans of its latest build.

Window stamps are ``time.perf_counter`` reads in ms since the window's 0,
the clock of ``ServeRequest.arrival_ms``; a set-up span is in ms on
``perf_counter``'s own clock.  Every span is also a
``jax.profiler.TraceAnnotation`` of the same name, so under a profiler it
lands in the ``.xplane.pb`` on the device trace's clock.  The window's
anchor pair (``t0_s`` on ``perf_counter``, ``wall_ns`` on the profiler's
host clock, read together) converts its stamps to that clock
(:meth:`Window.trace_ns`).  Only ``replay`` writes into a window, so
warm-up and ``measure()`` leave it as it is.

Spans (no name starts with ``window`` or ``generate/``, which are the
benchmark harness's own):

  ==========================================  ===========================
  ``serve.sleep``, ``serve.form``,            ``serve.replay``
  ``serve.batch/<model>``
  ``serve.prefill/<model>``                   ``ModelRunner.generate``:
  ``serve.decode/<model>``                    dispatch, first token on the
                                              host, last token on the host
  ``serve.compile/<model>``,                  ``ModelRunner.compile``
  ``serve.warmup/<model>``
  ``serve.measure/<model>``                   ``ModelRunner.measure``
  ==========================================  ===========================

``compiles`` counts JAX's ``/jax/core/compile/backend_compile_duration``
events.  JAX records one for every program it compiles or fetches from the
persistent cache (a fetch also records ``/jax/compilation_cache/cache_hits``,
which is not counted again).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
import time

import jax

from repro.obs.spans import HostSpan, ServedBatch, ServedRequest, SleepSpan

#: JAX's event for each program compiled, or fetched from the persistent
#: compile cache.
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


@dataclasses.dataclass(eq=False)
class Window:
    """What one ``serve.replay`` call did.  Per request (``id`` = index):
    ``models``, ``due_ms``, ``admitted_ms``, ``batch``, ``done_ms``."""

    t0_s: float                  # its 0 on time.perf_counter's clock
    wall_ns: int                 # time.time_ns() at its 0
    models: list[str]
    due_ms: list[float]
    admitted_ms: list[float]
    batch: list[int]
    done_ms: list[float]
    sleeps: list[SleepSpan] = dataclasses.field(default_factory=list)
    forms: list[HostSpan] = dataclasses.field(default_factory=list)
    batches: list[ServedBatch] = dataclasses.field(default_factory=list)
    compiles: int = 0
    open: bool = True

    def ms(self, t_s: float) -> float:
        """A ``time.perf_counter`` reading in ms since the window's 0."""
        return (t_s - self.t0_s) * 1e3

    def trace_ns(self, ms: float, profile_start_ns: int) -> float:
        """``ms`` since the window's 0 on a profiler trace's clock: ns after
        ``profile_start_ns``, the trace's ``profile_start_time``."""
        return self.wall_ns - profile_start_ns + ms * 1e6

    def requests(self) -> list[ServedRequest]:
        return [ServedRequest(i, *row) for i, row in enumerate(zip(
            self.models, self.due_ms, self.admitted_ms, self.batch,
            self.done_ms))]

    def first_token_p90_ms(self) -> float | None:
        """p90 (nearest rank) of first token on the host minus due time
        over the answered requests."""
        first = {b.id: b.first_token_ms for b in self.batches}
        waits = sorted(first[r.batch] - r.due_ms for r in self.requests()
                       if r.batch >= 0)
        return waits[max(0, math.ceil(0.9 * len(waits)) - 1)] if waits else None

    def wake_late_max_ms(self) -> float | None:
        """How late the loop woke from its latest-waking sleep."""
        return max((s.woke_ms - s.due_ms for s in self.sleeps), default=None)


class Recorder:
    """The served path's window and set-up spans (see the module)."""

    def __init__(self):
        self.window: Window | None = None
        self.setup: dict[str, dict[str, HostSpan]] = {}
        self._listening = False

    def open_window(self, reqs) -> Window:
        """A fresh window over ``reqs``; its 0 is now."""
        if not self._listening:
            jax.monitoring.register_event_duration_secs_listener(self._on_duration)
            self._listening = True
        n = len(reqs)
        t0_s, wall_ns = time.perf_counter(), time.time_ns()
        self.window = Window(t0_s, wall_ns, [r.model for r in reqs],
                             [r.arrival_ms for r in reqs], [math.nan] * n,
                             [-1] * n, [math.nan] * n)
        return self.window

    def _on_duration(self, event: str, duration: float, **kwargs) -> None:
        w = self.window
        if event == COMPILE_EVENT and w is not None and w.open:
            w.compiles += 1

    @contextlib.contextmanager
    def setup_span(self, phase: str, model: str):
        """Span ``serve.<phase>/<model>`` of a build; ``compile`` starts the
        model's build afresh."""
        name = f"serve.{phase}/{model}"
        with jax.profiler.TraceAnnotation(name):
            start = time.perf_counter()
            yield
            end = time.perf_counter()
        if phase == "compile":
            self.setup[model] = {}
        self.setup.setdefault(model, {})[phase] = HostSpan(name, start * 1e3,
                                                          end * 1e3)


#: The process's recorder: ``serve.replay`` and ``ModelRunner`` write it,
#: ``serve.print_run`` and the benchmark's readers read it.
RECORDER = Recorder()
