"""A kernel's share of its roofline, in %: the least time the cell's chips
could take for what the algorithm needs in one step (harness/flops.py's
function `need`, from shapes) over the kernel's measured device time per
step (mean over the chips, which each hold 1/chips of the work)."""

from harness import flops
from readers.kernel_ms import per_step_seconds


def read(run, name, pattern, need, per="traced_steps"):
    found = per_step_seconds(run, pattern, per)
    if found is None:
        return None
    measured, events = found
    needed = getattr(flops, need)(run.cell.config, run.facts["batch"],
                                  run.facts["seq"])
    least = flops.roofline_seconds(needed, run.peaks, chips=run.cell.chips)
    run.note(metric=name, bound=least["bound"], least_ms=1e3 * least["seconds"],
             measured_ms=1e3 * measured, events=events)
    return 100.0 * least["seconds"] / measured
