"""Launcher: `python -m flexflow_tpu user_script.py [flags]`.

Reference analog: the `flexflow_python` binary + flexflow_top.py top-level
task (F5; python/flexflow/flexflow_python, python/flexflow/core/
flexflow_top.py:164): the launcher owns runtime bring-up (flag parsing,
platform/mesh selection, optional multi-process init) and then runs the user
script, which reads its FFConfig from `flexflow_tpu.get_launch_config()`.

Flags before the script path belong to the launcher/FFConfig; everything
after the script path goes to the script's own argv.
"""

from __future__ import annotations

import os
import runpy
import sys

from flexflow_tpu.config import FFConfig

def split_argv(argv, value_flags=None):
    """Split launcher argv at the script path: the script is the first
    STANDALONE token (not a flag and not the value of a value-taking flag —
    e.g. `--machine-model-file mach.py train.py` must pick train.py).
    `value_flags` defaults to the set DERIVED from the FFConfig parser
    (FFConfig.launcher_value_flags), so newly added flags are covered
    without touching this module. Returns (script, launcher_args,
    script_args); script is None when argv holds no standalone token."""
    if value_flags is None:
        value_flags = FFConfig.launcher_value_flags()
    i = 0
    while i < len(argv):
        a = argv[i]
        if a.startswith("-"):
            if "=" not in a and a in value_flags:
                i += 1  # consume the flag's value token
        else:
            return a, argv[:i], argv[i + 1:]
        i += 1
    return None, argv, []


def main(argv=None):
    argv = list(sys.argv[1:] if argv is None else argv)
    script, launcher_args, script_args = split_argv(argv)
    if script is None:
        print("usage: python -m flexflow_tpu [flags] script.py [script args]\n"
              "flags: the FFConfig CLI (-b, --budget, --mesh data=4,model=2, ...)",
              file=sys.stderr)
        return 2
    # expose to the script via flexflow_tpu.get_launch_config()
    import flexflow_tpu

    # the launcher IS a real CLI invocation: honor FF_LAUNCH_ARGS (jupyter
    # kernelspec / wrapper-injected machine config) here, with explicit
    # launcher flags overriding it — parse_args itself only reads the env
    # for argv=None so programmatic configs stay untouched
    import shlex

    env_args = shlex.split(os.environ.get("FF_LAUNCH_ARGS", ""))
    flexflow_tpu._launch_config = FFConfig.parse_args(env_args + launcher_args)
    sys.argv = [script] + script_args
    runpy.run_path(script, run_name="__main__")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
