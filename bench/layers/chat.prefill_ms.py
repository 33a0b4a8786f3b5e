"""Median device time of one prefill call: the durations of the program
named ``prefill`` (the engine's jitted ``transformer.prefill``) in the
traced window.  Silent when no program of that name ran."""
import statistics


def read(r):
    if not r.trace:
        return None
    calls = r.trace.modules(r"\bjit_prefill\b|\bprefill\(")
    if not calls:
        return None
    return statistics.median(d for _, _, d in calls) / 1e6
