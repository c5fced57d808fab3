"""How unevenly the held experts' pairs fall: 100 x the busiest held
expert's pairs (``moe.pairs_max``, summed over steps and layers) x the
experts held / the pairs on held experts (``moe.pairs_local``). 100 is
even; 200 means the busiest expert has twice the mean, and a grouped
expert product waits for it."""
import counters


def read(ctx):
    t = counters.totals()
    local, model = t.get("moe.pairs_local"), ctx.get("model")
    if not local or model is None:
        return None
    return 100.0 * t.get("moe.pairs_max", 0) * model["n_routed_experts"] \
        / local
