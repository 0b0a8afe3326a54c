"""How late the replay loop woke from its latest-waking sleep: the largest
actual minus intended wake of a ``serve.sleep`` span (a host stall shows
here, and delays every request due meanwhile)."""
from bench.metrics._served import window


def read(record, arg):
    w = window()
    if w is None:
        return None
    return max((s.woke_ms - s.due_ms for s in w.sleeps), default=None)
