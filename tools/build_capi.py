"""Build the C API shared library (and optionally the C example).

Reference analog: the reference builds libflexflow + flexflow_c via CMake;
here one translation unit embeds CPython:

    python tools/build_capi.py                # -> flexflow_tpu/capi/libflexflow_tpu_c.so
    python tools/build_capi.py --run-example  # + compile & run examples/c/mlp_train.c
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import sysconfig

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CAPI = os.path.join(ROOT, "flexflow_tpu", "capi")
LIB = os.path.join(CAPI, "libflexflow_tpu_c.so")


def build_lib() -> str:
    inc = sysconfig.get_paths()["include"]
    libdir = sysconfig.get_config_var("LIBDIR")
    ver = f"python{sys.version_info.major}.{sys.version_info.minor}"
    src = os.path.join(CAPI, "flexflow_c.cc")
    if os.path.exists(LIB) and os.path.getmtime(LIB) >= os.path.getmtime(src):
        return LIB
    tmp = f"{LIB}.{os.getpid()}.tmp"  # pid-unique: concurrent builds can't race
    cmd = ["c++", "-O2", "-shared", "-fPIC", "-std=c++17", src,
           f"-I{inc}", f"-L{libdir}", f"-l{ver}",
           f"-Wl,-rpath,{libdir}", "-o", tmp]
    subprocess.run(cmd, check=True)
    os.replace(tmp, LIB)
    return LIB


def build_example() -> str:
    exe = os.path.join(ROOT, "examples", "c", "mlp_train")
    src = os.path.join(ROOT, "examples", "c", "mlp_train.c")
    cmd = ["cc", "-O2", src, f"-I{CAPI}", f"-L{CAPI}", "-lflexflow_tpu_c",
           f"-Wl,-rpath,{CAPI}", "-o", exe]
    subprocess.run(cmd, check=True)
    return exe


def run_example(n_devices: int = 4) -> str:
    exe = build_example()
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "")
                        + f" --xla_force_host_platform_device_count={n_devices}")
    env["PYTHONPATH"] = ROOT + os.pathsep + env.get("PYTHONPATH", "")
    out = subprocess.run([exe, "-b", "32"], env=env, capture_output=True,
                         text=True, timeout=600)
    if out.returncode != 0:
        raise RuntimeError(f"example failed rc={out.returncode}:\n"
                           f"{out.stdout}\n{out.stderr[-3000:]}")
    return out.stdout


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--run-example", action="store_true")
    args = ap.parse_args()
    print("built", build_lib())
    if args.run_example:
        print(run_example(), end="")
