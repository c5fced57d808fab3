"""Device seconds per placement of the refinement programs of
``core/refine.py`` (``_refine_batch_jit``, one call per level)."""

PROGRAMS = ("_refine_batch_jit",)


def read(ctx):
    n = ctx.get("placements")
    if not n:
        return None
    t = ctx["trace"].program_time(PROGRAMS)
    return t / n if t > 0 else None
