"""Seconds per placement in which the device ran nothing while the
program's ``partition`` span (the whole V-cycle of one placement) was
open: the span's duration less the device busy time inside it. The host
stages of the level loop that the device waits on."""

SPAN = "partition"


def read(ctx):
    n = ctx.get("placements")
    red = ctx["trace"]
    spans = red.spans(SPAN)
    if not n or not spans:
        return None
    idle = sum((e.end - e.start) * 1e-9 - red.busy_within(e.start, e.end)
               for e in spans)
    return idle / n
