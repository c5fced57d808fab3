"""Share of the chip's bf16 peak in the serving window: the forward FLOPs
of this chip's share for every token processed in it, prompt and
generated (``work_mla_moe.flops``: weights but the routed experts,
absorbed attention over their contexts, and the routed experts of the
pairs on held experts, ``moe.pairs_local``), over the window's seconds
times the peak."""
import counters
import work_mla_moe


def read(ctx):
    tokens, win, model = ctx.get("tokens"), ctx.get("window"), \
        ctx.get("model")
    pairs = counters.totals().get("moe.pairs_local")
    if not tokens or win is None or win.seconds <= 0 or model is None \
            or pairs is None:
        return None
    flops = work_mla_moe.flops(model, tokens, ctx["contexts"], pairs)
    return 100.0 * flops / (win.seconds * ctx["peaks"]["bf16_flops"])
