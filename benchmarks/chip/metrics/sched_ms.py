"""Host milliseconds per engine step spent on scheduling: the program's
``serve.admit``, ``serve.inputs`` and ``serve.advance`` spans (admission,
building the step's inputs, consuming its samples) summed, over the
``serve.step`` spans."""

STEP = "serve.step"
PARTS = ("serve.admit", "serve.inputs", "serve.advance")


def read(ctx):
    red = ctx["trace"]
    steps = red.spans(STEP)
    if not steps:
        return None
    ns = sum(e.end - e.start for name in PARTS for e in red.spans(name))
    return 1e-6 * ns / len(steps)
