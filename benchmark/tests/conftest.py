"""The benchmark's own tests run on the CPU and never take the chip:

    python -m pytest benchmark/tests -q
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)
