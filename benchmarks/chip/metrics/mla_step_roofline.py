"""Share of the HBM roofline reached by the MLA/MoE decode steps: the
least bytes the window's steps must move (``work_mla_moe.window_bytes``:
the weights but the embedding, each held expert a step routes a pair to
(``moe.experts_hit``), the live latent pages (``decode.pages_live``) and
the latent writes) over the chip's bandwidth, divided by the step
program's device time. Bound by bandwidth: a step does about 60 FLOPs a
byte, far below the chip's 240."""
import counters
import work_mla_moe

PROGRAMS = ("paged_decode_step_mla",)


def read(ctx):
    t = counters.totals()
    steps, model = ctx.get("steps"), ctx.get("model")
    if not steps or model is None or "moe.experts_hit" not in t \
            or "decode.pages_live" not in t:
        return None
    busy = ctx["trace"].program_time(PROGRAMS)
    if busy <= 0:
        return None
    least = work_mla_moe.window_bytes(
        model, steps, t["moe.experts_hit"], t["decode.pages_live"],
        ctx["page_size"], ctx["tokens"]) / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / busy
