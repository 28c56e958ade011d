"""Repo-level pytest configuration.

* Puts ``src/`` on ``sys.path`` so ``import repro`` works without an
  editable install (mirrors the tier-1 ``PYTHONPATH=src`` invocation).
* Points the persistent compile cache (``repro.obs.telemetry``) at a
  fresh per-session temporary directory so tests are hermetic: runs
  never hit executables a previous session left behind, never write
  into the checkout's ``.jax_cache``, and the cold-compile assertions
  stay meaningful.  Tests that need a specific directory (or a disabled
  cache) still override ``JAX_COMPILATION_CACHE_DIR`` themselves.
"""
import os
import sys
import tempfile

_SRC = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
if _SRC not in sys.path:
    sys.path.insert(0, _SRC)

os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="lacin-test-cache-")
