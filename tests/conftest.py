"""Test harness setup.

Mirrors the reference's "many redis-servers on localhost" trick for testing
distribution without a real cluster (SURVEY.md §4): we force 8 virtual CPU
devices so every Mesh/shard_map test runs the real multi-chip code path on
one host.  Must run before jax is imported anywhere.
"""

import os
import sys

# Tests run on the CPU backend; env vars only count before jax loads.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402

from redisson_tpu.utils.compile_cache import configure_compile_cache  # noqa: E402

configure_compile_cache()


@pytest.fixture(autouse=True)
def _lock_witness_guard():
    """Lock-order witness (ISSUE 8): under RTPU_LOCK_WITNESS=1 every
    test fails if it produced a lock-order cycle or a blocking call
    under a witness-named lock — the report carries the offending
    stack pairs.  Free when the witness is off (active() is False
    until the first lock is wrapped)."""
    yield
    from redisson_tpu.analysis import witness

    if witness.active():
        vs = witness.take_violations()
        if vs:
            pytest.fail(
                "lock-order witness found %d violation(s):\n\n%s"
                % (len(vs), "\n\n".join(v.format() for v in vs)),
                pytrace=False,
            )
