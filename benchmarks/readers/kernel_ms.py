"""Device milliseconds per step of the operations whose name matches
`pattern`, averaged over the chips, from the traced window."""

from harness import trace_reduce


def per_step_seconds(run, pattern, per):
    """(seconds per step, mean over chips; events matched), or None."""
    if run.trace is None or not run.facts.get(per):
        return None
    hit = trace_reduce.matching_seconds(run.trace, run.window, pattern)
    events = sum(h["events"] for h in hit.values())
    if not events:
        return None
    mean_s = sum(h["seconds"] for h in hit.values()) / len(hit)
    return mean_s / run.facts[per], events


def read(run, name, pattern, per="traced_steps"):
    found = per_step_seconds(run, pattern, per)
    return None if found is None else 1e3 * found[0]
