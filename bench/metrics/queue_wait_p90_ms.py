"""p90 of batch start minus due time over the requests that were served
(replay loop: time a request waits in its model's queue); a request's
batch and its launch are the program's window log's."""
from bench.metrics._common import nearest_rank


def read(record, arg):
    waits = [r["batch_start_ms"] - r["due_ms"] for r in record["requests"]
             if r["batch_start_ms"] is not None]
    return nearest_rank(waits, 0.9) if waits else None
