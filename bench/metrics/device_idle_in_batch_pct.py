"""Share of the time inside ``serve.batch/<model>`` spans in which no
operation ran on the device (trace): host work between the steps of a
batch."""


def read(record, arg):
    tr = record.get("trace")
    if not tr or not tr["spans_s"]:
        return None
    return 100.0 * (1.0 - tr["busy_in_spans_s"] / tr["spans_s"])
