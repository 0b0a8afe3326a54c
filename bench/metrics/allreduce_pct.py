"""Share of the first device's busy time spent in all-reduce operations
(trace)."""


def read(record, arg):
    tr = record.get("trace")
    if not tr or record["chips"] < 2 or not tr["busy_s_dev0"]:
        return None
    return 100.0 * tr["allreduce_s_dev0"] / tr["busy_s_dev0"]
