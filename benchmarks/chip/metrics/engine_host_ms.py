"""Host milliseconds per engine step: the wall time of the benchmark's
``bench.step`` span around ``ServingEngine.step()``, less the device busy
time inside it."""

SPAN = "bench.step"


def read(ctx):
    red = ctx["trace"]
    spans = red.spans(SPAN)
    if not spans:
        return None
    host = sum((e.end - e.start) * 1e-9 - red.busy_within(e.start, e.end)
               for e in spans)
    return 1e3 * host / len(spans)
