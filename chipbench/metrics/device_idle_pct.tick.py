"""1 - (union of the device's operation intervals over the traced window), %."""

from chipbench import trace


def read(observed):
    return trace.idle_pct(observed.get("trace"))
