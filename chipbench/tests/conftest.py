"""Puts the repository root (for ``chipbench``) and ``src`` (for the
program) on the import path of the benchmark's own tests:

    JAX_PLATFORMS=cpu python3 -m pytest chipbench/tests
"""
import pathlib
import sys

_REPO = pathlib.Path(__file__).resolve().parents[2]
for p in (str(_REPO / "src"), str(_REPO)):
    if p not in sys.path:
        sys.path.insert(0, p)
