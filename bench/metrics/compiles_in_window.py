"""Programs compiled, or fetched from the persistent compile cache, while
the window was open (the program's counter of JAX's compile events):
every shape is compiled in set-up, so this should read 0."""
from bench.metrics._served import window


def read(record, arg):
    w = window()
    return None if w is None else w.compiles
