"""Device time of the collective operations over device busy time, in %, on
the chip where that share is largest."""

from harness import trace_reduce


def read(run, name, pattern=trace_reduce.COLLECTIVE):
    if run.trace is None:
        return None
    hit = trace_reduce.matching_seconds(run.trace, run.window, pattern)
    busy = trace_reduce.busy_seconds(run.trace, run.window)
    if not sum(h["events"] for h in hit.values()):
        return None
    return max(100.0 * hit[c]["seconds"] / busy[c] for c in hit if busy[c] > 0)
