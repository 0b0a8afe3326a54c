"""Requests per batch, over the window's batches."""


def read(record, arg):
    sizes = [b["size"] for b in record["batches"]]
    return sum(sizes) / len(sizes) if sizes else None
