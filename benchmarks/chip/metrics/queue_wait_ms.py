"""Mean milliseconds a request admitted in the traced window waited in
the scheduler's queue, from when it was queued to its admission
(``serve.queue_wait_s`` over ``serve.admitted``). A mean: a 10 s window
admits about twenty requests, too few for a tail."""
import counters


def read(ctx):
    t = counters.totals()
    admitted = t.get("serve.admitted")
    if not admitted:
        return None
    return 1e3 * t.get("serve.queue_wait_s", 0.0) / admitted
