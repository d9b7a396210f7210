"""CPU tests of the benchmark.  Four virtual CPU devices let the 2 x 2 cell's
mesh run here; the flag has to be set before JAX is imported."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


import pytest  # noqa: E402


@pytest.fixture(autouse=True, scope="session")
def own_compile_cache(tmp_path_factory):
    """CPU programs go to a cache of the test session, not the benchmark's."""
    from bench import harness

    harness.CACHE_DIR = str(tmp_path_factory.mktemp("jax_cache"))
    yield
