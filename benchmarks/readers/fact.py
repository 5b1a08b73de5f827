"""A number the cell's run recorded under `key` (default: the metric's name)."""


def read(run, name, key=None, scale=1.0):
    value = run.facts.get(key or name)
    return None if value is None else value * scale
