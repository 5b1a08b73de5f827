"""The front page cites only what the checkout holds: every path with a
directory that README.md writes in backticks, every root file it names that
starts with BENCH or PERF, and every `--flag` it writes."""

import os
import re

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _read(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return f.read()


def test_every_path_the_readme_names_exists():
    README = _read("README.md")
    paths = set()
    for span in re.findall(r"`([^`\n]+)`", README):
        paths.update(re.findall(
            r"(?:tools|tests|benchmarks|flexflow_tpu|examples|docs)/[\w./-]+",
            span))
    paths.update(re.findall(r"\b(?:BENCH|PERF)\w*\.(?:jsonl|json|md)\b",
                            README))
    assert paths
    # a `.so` is a build product the README names as such, not a file of
    # the checkout
    missing = sorted(p for p in paths if not p.endswith(".so")
                     and not os.path.exists(os.path.join(ROOT, p.rstrip("."))))
    assert not missing, missing


def test_every_flag_the_readme_names_is_parsed_somewhere():
    README = _read("README.md")
    text = _read("flexflow_tpu", "config.py") + "".join(
        _read(d, f) for d in ("tools", "benchmarks", "")
        for f in sorted(os.listdir(os.path.join(ROOT, d)))
        if f.endswith(".py"))
    known = set(re.findall(r"""["'](--[a-z][a-z0-9-]*)["']""", text))
    # `--no-x` is written by argparse for a BooleanOptionalAction `--x`
    known |= {"--no-" + m[2:] for m in re.findall(
        r"""["'](--[a-z][a-z0-9-]*)["'][^)]*?BooleanOptionalAction""", text)}
    # (`--xla_force_...` inside XLA_FLAGS is the compiler's, not ours)
    flags = set(re.findall(r"(?<![\w-])--[a-z][a-z0-9-]*(?![\w-])", README))
    assert flags
    unknown = sorted(flags - known)
    assert not unknown, unknown
