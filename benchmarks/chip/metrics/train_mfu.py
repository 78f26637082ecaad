"""Model FLOPs of the steps not followed by a save over their time, as a
share of the chips' bf16 peak (percent).  The FLOPs are the forward and
backward passes' matrix products and causal attention, recomputation
excluded (``lm.model_flops_per_token``)."""


def read(rec):
    times = rec.counters.get("step_s_nosave", [])
    if not times:
        return None
    flops = rec.counters["flops_per_step"] * len(times)
    peak = rec.peaks["bf16_flops_per_s"] * rec.counters["chips"]
    return 100.0 * flops / sum(times) / peak
