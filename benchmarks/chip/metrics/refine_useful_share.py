"""Percent of the refinement rounds run in the traced window that the
results needed: over every level and slot, the rounds up to the first
that reached the partition refinement returned
(``refine.rounds_to_best``), over the rounds run (``refine.rounds``).
Later rounds could not change any answer, so 100 less this is the most
refinement time an early stop could save."""
import counters


def read(ctx):
    t = counters.totals()
    rounds = t.get("refine.rounds")
    if not rounds:
        return None
    return 100.0 * t.get("refine.rounds_to_best", 0) / rounds
