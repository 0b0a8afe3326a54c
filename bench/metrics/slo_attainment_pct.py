"""Share of the requests due in the window that were answered within their
model's fixed SLO; an unanswered request misses."""


def read(record, arg):
    reqs = record["requests"]
    if not reqs:
        return None
    met = sum(r["done_ms"] is not None and r["done_ms"] - r["due_ms"] <= r["slo_ms"]
              for r in reqs)
    return 100.0 * met / len(reqs)
