"""Model FLOPs of the tokens prefilled and usefully decoded in the window,
over (window x chips x peak bf16 FLOP/s).  The window runs from its start
to the end of its last batch, where that is later than ``seconds``."""
from bench.metrics._flops import window_flops


def read(record, arg):
    if not record["batches"]:
        return None
    span_s = max(record["seconds"], max(b["done_ms"] for b in record["batches"]) / 1e3)
    peak = record["chips"] * record["peaks"]["bf16_flops"]
    return 100.0 * window_flops(record) / (span_s * peak)
