"""Device milliseconds per engine step of the paged decode program
(``serving/paged_decode.py:paged_decode_step``). The engine jits it
through ``functools.partial``, so the runtime names it ``jit__unknown``;
the only other program of a serving step is the sampler (``jit_sample``).
"""

PROGRAMS = ("paged_decode_step", "jit__unknown")


def read(ctx):
    steps = ctx.get("steps")
    if not steps:
        return None
    t = ctx["trace"].program_time(PROGRAMS)
    return 1e3 * t / steps if t > 0 else None
