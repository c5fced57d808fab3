"""Device milliseconds per engine step of the MLA/MoE paged decode
program (``serving/paged_decode.py:paged_decode_step_mla``, named
``jit_paged_decode_step_mla`` in a profile)."""

PROGRAMS = ("paged_decode_step_mla",)


def read(ctx):
    steps = ctx.get("steps")
    if not steps:
        return None
    t = ctx["trace"].program_time(PROGRAMS)
    return 1e3 * t / steps if t > 0 else None
