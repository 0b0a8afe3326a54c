"""Generated tokens of the requests completed inside the window, divided
by the window's length."""


def read(record, arg):
    end_ms = record["seconds"] * 1e3
    tokens = sum(r["served"] for r in record["requests"]
                 if r["done_ms"] is not None and r["done_ms"] <= end_ms)
    return tokens / record["seconds"] if tokens else None
