"""Share of the traced window in which no operation ran on the device:
1 - (union of device-operation intervals / window)."""


def read(r):
    return r.trace.idle_pct() if r.trace else None
