"""Decode steps run for rows already past their request's ``max_new``, as
a share of all row decode steps (a batch runs to its longest request)."""


def read(record, arg):
    total = wasted = 0
    for b in record["batches"]:
        steps = b["steps"] - 1          # the first token comes from prefill
        own = b.get("max_new", [])
        total += b["size"] * steps
        wasted += sum(b["steps"] - g for g in own)
        wasted += (b["size"] - len(own)) * steps   # rows never answered
    return 100.0 * wasted / total if total else None
