"""Median over batches of |scheduler's L(b) at the batch's size - measured
batch time| / measured (scheduler: ``serve.MeasuredLatency``); a batch's
time is its ``serve.batch/<model>`` span, launch to done."""
import statistics


def read(record, arg):
    errs = [abs(b["predicted_ms"] - (b["done_ms"] - b["launch_ms"]))
            / (b["done_ms"] - b["launch_ms"]) for b in record["batches"]]
    return 100.0 * statistics.median(errs) if errs else None
