"""Share of the window's batches whose rows hold more than one prompt length
(the program's ``serve.batch/<model>`` records, each row's own prompt
length in ``row_lens``): batches that took queued requests of other lengths
instead of leaving them to wait, the whole window."""
from bench.metrics._served import window


def read(record, arg):
    w = window()
    if w is None or not w.batches:
        return None
    lens = [getattr(b, "row_lens", ()) for b in w.batches]
    if not all(lens):   # a program that logs no row's own prompt length
        return None
    return 100.0 * sum(len(set(rows)) > 1 for rows in lens) / len(lens)
