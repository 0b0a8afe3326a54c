"""Set-up time in the program's build of each model of the run: the sum of
its ``serve.compile/``, ``serve.warmup/`` and ``serve.measure/<model>``
spans (compile from the cache, one run of each program, ``measure()``)."""
from bench.metrics._served import recorder


def read(record, arg):
    rec = recorder()
    if rec is None:
        return None
    spans = [s for m in record["models"] for s in rec.setup.get(m, {}).values()]
    return sum(s.end_ms - s.start_ms for s in spans) / 1e3 if spans else None
