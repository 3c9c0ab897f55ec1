"""Test configuration: run the suite on a virtual 8-device CPU mesh.

Mirrors the reference's "distributed without a cluster" strategy (SURVEY.md
§5.4: launcher-local multi-process PS tests) using XLA's host-platform
device-count flag, so KVStore/mesh/sharding tests exercise real collectives
on 8 virtual devices with no TPU pod.

TPU lane (reference: tests/python/gpu/ — the CPU-vs-GPU consistency oracle,
SURVEY.md §5.2): ``MXNET_TEST_TPU=1 pytest -m tpu`` keeps the real chip as
the default platform and runs the ``tpu``-marked tests.  Asked for
explicitly, a missing chip is an error, not a skip.
"""
import os

_TPU_LANE = os.environ.get("MXNET_TEST_TPU", "") == "1"

if not _TPU_LANE:
    # must run before jax initializes
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

if _TPU_LANE and jax.devices()[0].platform != "tpu":
    raise pytest.UsageError(
        "MXNET_TEST_TPU=1 asks for the chip and JAX has none "
        f"(jax.devices()[0].platform == {jax.devices()[0].platform!r})")


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "tpu: needs the real TPU chip (MXNET_TEST_TPU=1 lane)")
    config.addinivalue_line(
        "markers", "slow: multi-process / long-running integration test")


def pytest_collection_modifyitems(config, items):
    if _TPU_LANE:
        return
    skip_tpu = pytest.mark.skip(
        reason="TPU lane disabled (set MXNET_TEST_TPU=1 and run on hardware)")
    for item in items:
        if "tpu" in item.keywords:
            item.add_marker(skip_tpu)


@pytest.fixture(autouse=True)
def _seed_everything():
    """Seed discipline (reference: tests/python/unittest/common.py @with_seed):
    every test runs with a fixed, reproducible seed."""
    np.random.seed(0)
    import mxnet_tpu as mx

    mx.random.seed(0)
    yield


@pytest.fixture(autouse=True)
def _isolate_leaked_globals():
    """Every test starts from the same process-wide gluon/parallel state.

    Two globals leak across tests and made tier-1 order-dependent:

    - the gluon auto-name counter (``block._NAME_SCOPE.counters``): a test
      whose net gets ``dense9``/``dense10`` sees ``sorted(param names)``
      diverge from structural order — whether that digit boundary is
      straddled depended on how many layers EARLIER tests created (the
      ``test_train_step_fsdp_mesh_matches_single_device`` flake);
    - the session default mesh (``parallel.mesh._DEFAULT``), set as a side
      effect by any dist-kvstore test that touches collectives.

    Resetting both per test makes name assignment and mesh discovery a
    function of the test alone, not of the suite prefix that ran before.
    """
    from mxnet_tpu.gluon import block as _block
    from mxnet_tpu.parallel import mesh as _mesh

    _block._NAME_SCOPE.counters.clear()
    del _block._NAME_SCOPE.scope_stack[:]
    _mesh._DEFAULT = None
    yield
