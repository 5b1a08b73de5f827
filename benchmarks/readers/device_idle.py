"""1 - (union of device-operation intervals) / window, in %, mean over chips."""

from harness import trace_reduce


def read(run, name):
    if run.trace is None:
        return None
    busy = trace_reduce.busy_seconds(run.trace, run.window)
    if not busy:
        return None
    window_s = (run.window[1] - run.window[0]) / 1e9
    return 100.0 * (1.0 - sum(busy.values()) / len(busy) / window_s)
