"""Prefill of model ``arg`` at its largest batch bucket and longest prompt,
from ``ModelRunner.measure()`` (host clock around ``block_until_ready``)."""


def read(record, arg):
    table = record["models"].get(arg, {}).get("prefill_ms")
    return table[max(table)] if table else None
