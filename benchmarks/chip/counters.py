"""The program's own work counters over a traced window (``repro.obs``:
they count only while the profiler traces, and the harness traces only
the window). A program without them gives no totals."""


def totals():
    try:
        from repro import obs
    except ImportError:
        return {}
    return obs.totals()
