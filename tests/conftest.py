"""Test configuration.

Forces JAX onto a virtual 8-device CPU platform BEFORE jax is imported, so
multi-chip sharding tests (the analog of the reference's in-process
multi-node clusters, test/pilosa.go:343-399) run anywhere.  Also pins a
small shard width so fragments stay tiny, mirroring the reference's
SHARD_WIDTH build-tag CI matrix (.circleci/config.yml:52-56).
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"  # tests are CPU-only by design
os.environ.setdefault("PILOSA_TPU_SHARD_WIDTH_EXP", "16")

# jax may already be imported by a pytest plugin, and JAX_PLATFORMS is
# captured at import time — so also override via jax.config, which takes
# effect any time before backend initialization.
# test_environment.py asserts the 8-device CPU platform stuck.
import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_num_cpu_devices", 8)
# Tests call the `python -m pilosa_tpu` entry points in-process, and
# those place JAX's persistent compile cache in the checkout
# (runtime/startup.py); a test run must not fill it.
jax.config.update("jax_enable_compilation_cache", False)

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _hermetic_residency_accounting():
    """Reset the process-wide residency manager after every test.

    Tests that don't close their holders leak accounting entries into
    the global manager; individually harmless, but the accumulated
    total eventually trips budget gates in later tests (first seen:
    prewarm declining work at the shard-width-22 matrix leg, where
    stacks are 4x bigger).  Real servers close their holders on
    shutdown; per-test reset restores that hermeticity.  Orphaned cache
    entries stay functional (generation checks still validate) — they
    merely stop being tracked/evictable, which is fine for test
    lifetimes."""
    yield
    from pilosa_tpu.runtime import prewarm, residency

    # drain BEFORE reset: an in-flight background prewarm from the
    # finished test would otherwise admit into the next test's fresh
    # manager (the cross-test leak this fixture exists to stop, made
    # timing-dependent).  A timeout must fail HERE, pinned to the
    # offending test, not surface as a nondeterministic budget trip
    # three tests later.
    assert prewarm.drain(timeout=30), "prewarm drain timed out in teardown"
    residency.reset()
    # the query result cache is process-wide too; holder uids make
    # cross-test hits impossible, but a test that shrinks the budget
    # or disables it must not leak that config into the next test
    from pilosa_tpu.runtime import resultcache

    resultcache.reset()
    # streaming-ingest state is process-wide as well: a test that
    # enables delta planes (any in-process Server does) must not leak
    # delta semantics — or a running compactor thread — into the next
    # test's bare fragments
    from pilosa_tpu import ingest
    from pilosa_tpu.ingest import compactor

    ingest.reset()
    compactor.reset()
    # the [replication] write-policy / hint-queue config is
    # process-wide too: a test that flips write_policy="available"
    # must not leak degraded-write semantics into the next test
    from pilosa_tpu.parallel import hints

    hints.reset()
    # the [tenants] isolation policy is process-wide as well: a test
    # that enables quotas must not leak weighted-fair scheduling (or
    # per-tenant cache/residency accounting) into the next test
    from pilosa_tpu.serve import tenant

    tenant.reset()
