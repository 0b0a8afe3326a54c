"""Typed span records: the engine event log, and the served path's recorder.

The engine's ``self.log`` (gated by ``EngineConfig.event_log``) used to
hold untyped tuples — ``("batch", epoch, let, launch, done, model, n)``
and friends — that every consumer indexed positionally.  These records
replace them with ``NamedTuple`` subclasses whose field order matches
the legacy tuples exactly, so positional access (``e[0] == "batch"``,
``e[3] < t_apply``) keeps working while new code gets named fields.

Every record's first field is its ``kind`` tag (the ``make_*`` helpers
fill it); ``SPAN_KINDS`` maps tag → type.  Records are plain tuples
underneath: they pickle cheaply across forked node workers and
sort/compare like the tuples they replace.

The served path (``repro.obs.served``) reuses :class:`BatchSpan` for a
batch that ran on the chip, and adds the records below it for what the
engine's log has no field for: a served batch's requests and host
stamps, the replay loop's sleeps, host work, and requests.
"""
from __future__ import annotations

from typing import NamedTuple


class BatchSpan(NamedTuple):
    """One opaque batch launch on a gpu-let: occupies ``[launch, done)``."""

    kind: str
    epoch: int
    let: int
    launch_ms: float
    done_ms: float
    model: str
    n: int


class DecodeSpan(NamedTuple):
    """One streaming decode chunk: ``n`` pool members advance ``k`` steps."""

    kind: str
    epoch: int
    let: int
    launch_ms: float
    done_ms: float
    model: str
    n: int
    steps: int


class DropSpan(NamedTuple):
    """A request dropped at batch formation (SLO already expired)."""

    kind: str
    t_ms: float
    model: str


class PreemptSpan(NamedTuple):
    """An in-flight batch of ``n`` requests cancelled and re-queued."""

    kind: str
    t_ms: float
    let: int
    model: str
    n: int


class ApplySpan(NamedTuple):
    """A staged schedule installed (gpu-let re-partition committed)."""

    kind: str
    t_ms: float


class TickSpan(NamedTuple):
    """A controller tick fired; ``resched`` marks a placement change."""

    kind: str
    t_ms: float
    resched: bool


class ServedBatch(NamedTuple):
    """One batch ``serve.replay`` ran, in ms since its window's 0.

    ``span`` is the batch as the engine's log holds one (epoch 0 and let 0:
    one schedule, the whole chip; ``n`` its size; launch when formed, done
    when ``generate`` returned); ``dispatch_ms``, ``first_token_ms`` and
    ``last_token_ms`` are ``ModelRunner.generate``'s host stamps.
    """

    span: BatchSpan
    id: int
    prompt_len: int
    steps: int
    request_ids: tuple[int, ...]
    dispatch_ms: float
    first_token_ms: float
    last_token_ms: float


class SleepSpan(NamedTuple):
    """The replay loop slept at ``start_ms`` to wake at ``due_ms`` (the next
    arrival) and woke at ``woke_ms``."""

    kind: str
    start_ms: float
    due_ms: float
    woke_ms: float


class HostSpan(NamedTuple):
    """Host work named ``kind`` between ``start_ms`` and ``end_ms``."""

    kind: str
    start_ms: float
    end_ms: float


class ServedRequest(NamedTuple):
    """One request of a served window (``id``: its index in the replayed
    list); ``admitted_ms`` is when the loop queued it, ``batch`` the id of
    the batch that answered it (-1 and NaN stamps: not admitted or not
    answered)."""

    id: int
    model: str
    due_ms: float
    admitted_ms: float
    batch: int
    done_ms: float


#: tag -> record type of the engine's event log, for validators and exporters
SPAN_KINDS = {
    "batch": BatchSpan,
    "decode": DecodeSpan,
    "drop": DropSpan,
    "preempt": PreemptSpan,
    "apply": ApplySpan,
    "tick": TickSpan,
}


def make_batch(epoch: int, let: int, launch_ms: float, done_ms: float,
               model: str, n: int) -> BatchSpan:
    return BatchSpan("batch", epoch, let, launch_ms, done_ms, model, n)


def make_decode(epoch: int, let: int, launch_ms: float, done_ms: float,
                model: str, n: int, steps: int) -> DecodeSpan:
    return DecodeSpan("decode", epoch, let, launch_ms, done_ms, model, n,
                      steps)


def make_drop(t_ms: float, model: str) -> DropSpan:
    return DropSpan("drop", t_ms, model)


def make_preempt(t_ms: float, let: int, model: str, n: int) -> PreemptSpan:
    return PreemptSpan("preempt", t_ms, let, model, n)


def make_apply(t_ms: float) -> ApplySpan:
    return ApplySpan("apply", t_ms)


def make_tick(t_ms: float, resched: bool) -> TickSpan:
    return TickSpan("tick", t_ms, resched)
