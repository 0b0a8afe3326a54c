"""Median over batches of |scheduler's L(b) at the batch's size - measured
``generate`` time| / measured (scheduler: ``serve.MeasuredLatency``)."""
import statistics


def read(record, arg):
    errs = [abs(b["predicted_ms"] - (b["end_ms"] - b["start_ms"]))
            / (b["end_ms"] - b["start_ms"]) for b in record["batches"]]
    return 100.0 * statistics.median(errs) if errs else None
