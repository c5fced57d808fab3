"""Share of the HBM roofline reached by refinement: the least bytes every
refinement round of every level must move (``work.refine_bytes``, from the
level sizes, the bin count and the rounds) over the chip's bandwidth,
divided by the measured refinement device time. Bound by bandwidth, since
the rounds do almost no arithmetic per byte."""
import work

PROGRAMS = ("_refine_batch_jit",)


def read(ctx):
    n = ctx.get("placements")
    levels = ctx.get("refine_levels")
    if not n or not levels:
        return None
    t = ctx["trace"].program_time(PROGRAMS) / n
    if t <= 0:
        return None
    least = work.refine_bytes(levels, ctx["k"], ctx["refine_rounds"]) \
        / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / t
