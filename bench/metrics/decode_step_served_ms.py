"""Decode step of model ``arg`` as served: the median over its window
batches of (last token on the host - first token on the host) / (steps - 1),
from ``ModelRunner.generate``'s stamps (``serve.decode/<model>``)."""
import statistics

from bench.metrics._served import window


def read(record, arg):
    w = window()
    if w is None:
        return None
    steps = [(b.last_token_ms - b.first_token_ms) / (b.steps - 1)
             for b in w.batches if b.span.model == arg and b.steps > 1]
    return statistics.median(steps) if steps else None
