"""Share of the chip's bf16 peak in the serving window: the forward FLOPs
of every token processed in it, prompt and generated
(``work.lm_flops`` over their context lengths), over the window's
seconds times the peak."""
import work


def read(ctx):
    tokens, win = ctx.get("tokens"), ctx.get("window")
    if not tokens or win is None or win.seconds <= 0:
        return None
    flops = work.lm_flops(ctx["model"], tokens, ctx["contexts"])
    return 100.0 * flops / (win.seconds * ctx["peaks"]["bf16_flops"])
