"""Every kernel module is named by the code that would call it: a module of
flexflow_tpu/ outside kernels/ imports it (`flexflow_tpu.kernels.<name>` or
`from flexflow_tpu.kernels import <name>`). The imports are lazy, inside
functions, so the sources are read, not imported. A kernel that only its
own tests and the package's re-export reach is dead code."""

import os
import re

import pytest

PKG = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                   "flexflow_tpu")
KERNELS = sorted(f[:-3] for f in os.listdir(os.path.join(PKG, "kernels"))
                 if f.endswith(".py") and f != "__init__.py")


def _sources_outside_kernels():
    for root, _dirs, files in os.walk(PKG):
        if os.path.basename(root) == "kernels":
            continue
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    yield os.path.join(root, f), fh.read()


@pytest.mark.parametrize("name", KERNELS)
def test_a_lowering_names_the_kernel(name):
    pat = re.compile(r"flexflow_tpu\.kernels\.%s\b"
                     r"|from flexflow_tpu\.kernels import [^\n]*\b%s\b"
                     % (name, name))
    users = [path for path, src in _sources_outside_kernels()
             if pat.search(src)]
    assert users, f"no module of flexflow_tpu/ outside kernels/ names {name}"
