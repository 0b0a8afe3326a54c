"""Share of the traced window in which no operation ran on the device,
averaged over the chips used (trace)."""


def read(record, arg):
    tr = record.get("trace")
    if not tr or not tr["window_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
