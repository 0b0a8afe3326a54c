"""p90 of completion minus due time over every request due in the window,
all models together; a request left unanswered counts as infinitely late."""
import math

from bench.metrics._common import nearest_rank


def read(record, arg):
    lat = [math.inf if r["done_ms"] is None else r["done_ms"] - r["due_ms"]
           for r in record["requests"]]
    if not lat:
        return None
    p90 = nearest_rank(lat, 0.9)
    return None if math.isinf(p90) else p90
