"""Device seconds per placement of the coarsening programs: the jitted
matching-and-contraction step of ``core/coarsen.py`` (``jit_step``)."""

PROGRAMS = ("jit_step",)


def read(ctx):
    n = ctx.get("placements")
    if not n:
        return None
    t = ctx["trace"].program_time(PROGRAMS)
    return t / n if t > 0 else None
