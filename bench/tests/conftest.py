"""Harness checks: ``python -m pytest bench/tests`` from the checkout.

They run on the CPU, with four host devices for the collective, and
never write a compile cache into the checkout.
"""
import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                           + " --xla_force_host_platform_device_count=4")
os.environ["JAX_COMPILATION_CACHE_DIR"] = ""

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
