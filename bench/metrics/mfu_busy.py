"""Model FLOPs of the window's batches over (summed ``serve.batch/<model>``
span time, launch to done, x chips x peak bf16 FLOP/s): the step's share
of the chip's peak while it serves."""
from bench.metrics._flops import window_flops


def read(record, arg):
    busy_s = sum(b["done_ms"] - b["launch_ms"] for b in record["batches"]) / 1e3
    if not busy_s:
        return None
    peak = record["chips"] * record["peaks"]["bf16_flops"]
    return 100.0 * window_flops(record) / (busy_s * peak)
