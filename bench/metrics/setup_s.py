"""Set-up time: process start until the window opens (device, weights,
compile from the cache, warm-up, ``measure()``, scheduling)."""


def read(record, arg):
    return record["setup_s"]
