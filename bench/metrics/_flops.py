"""Model FLOPs of the window's batches (no metric of its own)."""
from bench import counts


def batch_flops(sizes: dict, b: dict) -> float:
    """Prefill of every row, and the decode steps of rows still short of
    their own ``max_new`` (the useful ones)."""
    flops = counts.prefill_flops(sizes, b["size"], b["prompt_len"])
    own = b.get("max_new", [])
    for j in range(b["steps"] - 1):
        rows = sum(g - 1 > j for g in own)
        if rows:
            flops += counts.decode_flops(sizes, rows, b["prompt_len"] + j)
    return flops


def window_flops(record) -> float:
    return sum(batch_flops(record["models"][b["model"]]["sizes"], b)
               for b in record["batches"])
