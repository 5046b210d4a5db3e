"""Idle share of the device in a traced steady sub-window of training (whole steps):
100 * (1 - busy / window), busy the union of device op intervals
(bench/trace.py), averaged over the chips."""


def read(run):
    tr = run.trace
    if tr is None or not tr.devices:
        return None
    return 100.0 * (1.0 - tr.busy_s() / tr.window_s)
