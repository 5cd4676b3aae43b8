"""The benchmark's own tests run on the CPU at tiny sizes: the platform is
pinned before jax is imported, as tests/conftest.py does for the program's."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
