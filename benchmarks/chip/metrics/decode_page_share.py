"""Percent of the KV pages the paged decode step gathers that hold a
position the step attends to: the pages of positions [0, pos] of each
active slot (``decode.pages_live``) over every page-table entry of every
slot, each step (``decode.pages_gathered``)."""
import counters


def read(ctx):
    t = counters.totals()
    gathered = t.get("decode.pages_gathered")
    if not gathered:
        return None
    return 100.0 * t.get("decode.pages_live", 0) / gathered
