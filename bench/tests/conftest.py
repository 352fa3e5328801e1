"""The benchmark's tests run on the CPU, from the root of the checkout:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for p in (ROOT, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

import jax  # noqa: E402

# CPU programs have no place in the checkout's cache of chip programs
jax.config.update("jax_enable_compilation_cache", False)
