"""Model FLOP utilization of the traced window: the operations of every
prompt and output token processed in it (from the configuration's shapes
and the lengths the benchmark sent), over the window x chips x peak.
The traced window, not the whole one: stopping the profiler holds the
engine for tens of seconds after it."""


def read(r):
    if not r.trace or not r.counts.get("traced_flops"):
        return None
    return 100.0 * r.counts["traced_flops"] / (
        r.trace.window_s * r.chips * r.peak["bf16_flops_per_s"])
