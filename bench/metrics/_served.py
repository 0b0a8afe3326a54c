"""The program's served-path recorder (``repro.obs.served``), which the
readers of its spans and counters share (no metric of its own).

The recorder is imported when a metric is read.  A program without it,
or a process that has served no window, gives None: nothing to read.
"""


def recorder():
    """The recorder, or None where there is no window to read."""
    try:
        from repro.obs.served import RECORDER
    except ImportError:
        return None
    return RECORDER if RECORDER.window is not None else None


def window():
    """The recorder's latest window (a whole ``serve.replay``), or None."""
    rec = recorder()
    return None if rec is None else rec.window
