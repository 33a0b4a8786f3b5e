"""Host time per engine tick: each ``bench.tick`` span (the engine's step,
run as an executor kernel) less the device-busy time inside it, averaged
over the ticks of the traced window.  It is the time the engine and the
executor keep the device waiting within a tick: admission, scheduling,
dispatch and the host reads of each token."""


def read(r):
    return r.trace.host_ms_per_span("bench.tick") if r.trace else None
