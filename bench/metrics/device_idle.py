"""Percent of the traced window in which no program ran on the device,
averaged over the chips: 1 - (union of busy intervals) / window."""


def read(w):
    return 100.0 * (1.0 - w.trace.busy_s / w.trace.window_s)
