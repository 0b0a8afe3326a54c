"""p90 (nearest rank) of first token on the host minus due time over the
window's answered requests (program spans: ``serve.replay``'s requests and
``ModelRunner.generate``'s stamp, the whole window)."""
from bench.metrics._common import nearest_rank
from bench.metrics._served import window


def read(record, arg):
    w = window()
    if w is None:
        return None
    first = {b.id: b.first_token_ms for b in w.batches}
    waits = [first[r.batch] - r.due_ms for r in w.requests() if r.batch >= 0]
    return nearest_rank(waits, 0.9) if waits else None
