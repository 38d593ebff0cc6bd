"""The benchmark's own tests: run them with
``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q`` from the root of
the checkout. They are not part of the repository's tier-1 suite."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))
