"""Tests of the benchmark itself, on the CPU at small sizes:
``pytest benchmarks/chip/tests``."""

import os
import sys
import tempfile

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# Compiled CPU programs stay out of the checkout's cache.
os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(prefix="jaxc_")
HERE = os.path.dirname(os.path.abspath(__file__))
CHIP = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(CHIP))
sys.path[:0] = [CHIP, os.path.join(ROOT, "src")]

import run  # noqa: E402

# CPU programs compile in a second; the persistent cache stays off here.
run.enable_compile_cache = lambda: "off"
