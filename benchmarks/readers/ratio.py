"""facts[num] / facts[den] * scale: a metric that only combines counters and
clocks the run already recorded is data, not code."""


def read(run, name, num, den, scale=1.0):
    n, d = run.facts.get(num), run.facts.get(den)
    if n is None or not d:
        return None
    return scale * n / d
