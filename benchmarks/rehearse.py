"""The control flow of run.py on whatever backend JAX has (here: the CPU,
tiny cells of benchmarks/rehearsal.json). It prints the facts and whether the
outputs were correct, and NEVER a metric: a number from a CPU run is not a
device number.

    JAX_PLATFORMS=cpu python benchmarks/rehearse.py --workload gpt2-tiny.train --seconds 2
"""

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import run  # noqa: E402

if __name__ == "__main__":
    sys.exit(run.main(manifest_path=Path(__file__).with_name("rehearsal.json"),
                      rehearsal=True))
