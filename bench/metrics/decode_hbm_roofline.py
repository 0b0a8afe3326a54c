"""Share of the HBM roofline reached by model ``arg``'s decode steps.

The least time the steps could take, the bytes each must move (weights
plus cache at its batch and length, per chip: ``bench.counts``) over the
chip's HBM bandwidth, divided by the device time of those ``jit_decode``
runs (trace, from its first traced batch on).  A batch runs one ``jit_prefill`` and ``steps - 1``
``jit_decode`` in order, which ties each run to its batch and model."""
from bench import counts


def read(record, arg):
    tr = record.get("trace")
    if not tr or arg not in record["models"]:
        return None
    runs = iter(tr["programs"])
    need_s = device_s = 0.0
    for b in record["batches"][tr["first_batch"]:]:
        want = ["jit_prefill"] + ["jit_decode"] * (b["steps"] - 1)
        got = [next(runs, (None, 0.0)) for _ in want]
        if [name for name, _ in got] != want:
            return None
        if b["model"] != arg:
            continue
        sizes = record["models"][arg]["sizes"]
        need_s += sum(counts.decode_bytes(sizes, b["size"], b["prompt_len"] + j,
                                          record["chips"])
                      for j in range(b["steps"] - 1)) / record["peaks"]["hbm_bytes_s"]
        device_s += sum(t for _, t in got[1:])
    return 100.0 * need_s / device_s if device_s else None
