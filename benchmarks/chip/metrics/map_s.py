"""Host seconds per placement of the benchmark's ``bench.map`` span: the
block-pair traffic and ``mapping.search``, ended by their host pull."""

SPAN = "bench.map"


def read(ctx):
    spans = ctx["trace"].spans(SPAN)
    if not spans:
        return None
    return sum(e.end - e.start for e in spans) * 1e-9 / len(spans)
