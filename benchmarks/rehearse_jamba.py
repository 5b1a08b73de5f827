"""rehearse.py for the tiny cells of benchmarks/rehearsal_jamba.json
(the `jamba` family's own rehearsal manifest: rehearsal.json is not a
model PR's to edit). The control flow of run.py on whatever backend JAX has,
the facts and whether the outputs were correct, and NEVER a metric.

    JAX_PLATFORMS=cpu python benchmarks/rehearse_jamba.py --workload jamba-tiny.serve --seconds 2
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402

REHEARSAL = Path(__file__).with_name("rehearsal_jamba.json")

if __name__ == "__main__":
    sys.exit(run.main(manifest_path=REHEARSAL, rehearsal=True))
