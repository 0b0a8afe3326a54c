"""Share of the answered requests' summed wait, due to batch start, spent
while another model's batch ran (``serve.batch/<model>`` spans): the cost
of time-sharing one chip, in the replay loop's queue."""
from bench.metrics._served import window


def read(record, arg):
    w = window()
    if w is None:
        return None
    batches = {b.id: b.span for b in w.batches}
    wait = cross = 0.0
    for r in w.requests():
        if r.batch < 0:
            continue
        lo, hi = r.due_ms, batches[r.batch].launch_ms
        wait += hi - lo
        cross += sum(max(0.0, min(b.done_ms, hi) - max(b.launch_ms, lo))
                     for b in batches.values() if b.model != r.model)
    return 100.0 * cross / wait if wait > 0 else None
