"""Test configuration: fake-slice JAX backend.

The reference could not test its multi-worker GPU paths without renting
hardware (SURVEY.md §4 — it created GCE VMs per CI run).  We do better:
every test runs on a virtual 8-device CPU "slice" via
``--xla_force_host_platform_device_count``, so SPMD sharding, collectives,
and gang logic are exercised hermetically.  chip_smoke.py
intentionally does NOT import this — it runs on the attached TPU chip.
"""

import os

# Must be set before jax initializes its backends.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()
# The persistent compile cache stays off for the suite and, through the
# environment, for every process a test spawns: the entrypoints would
# otherwise fill <checkout>/.jax_cache with CPU entries on every run, and
# XLA:CPU warns on loading its own cached executables here (a machine-
# feature mismatch that "could lead to SIGILL").  Tests of the cache's
# placement (tests/test_chip_path.py) read the config, not the cache.
os.environ["JAX_ENABLE_COMPILATION_CACHE"] = "false"

import jax  # noqa: E402
import pytest  # noqa: E402


# Lock-order sanitizer (KFT_LOCKCHECK=1): the serving/fleet suites
# construct the heavily-threaded objects (engine, batchers, registry,
# router), and the scheduler/supervisor suites are the most
# lock-heavy ones added since (policy + queue + rate-limiter locks;
# supervisor heartbeat/watchdog state), so all four run with
# threading.Lock instrumented.  The sanitizer installs ONCE and the
# acquisition graph accumulates across tests — an inconsistent
# nesting order between two different tests still closes a cycle,
# and the test that closed it fails with both paths spelled out.
# Off by default: instrumentation taxes every acquire, and the
# tier-1 budget is tight.
_LOCKCHECK_MODULES = {"test_serving", "test_fleet", "test_scheduler",
                      "test_supervisor"}


@pytest.fixture(autouse=True)
def _lockcheck(request):
    from kubeflow_tpu.testing import lockcheck

    module = getattr(request, "module", None)
    name = getattr(module, "__name__", "").rsplit(".", 1)[-1]
    if not lockcheck.enabled_in_env() \
            or name not in _LOCKCHECK_MODULES:
        yield
        return
    sanitizer = lockcheck.install()  # idempotent; graph persists
    before = len(sanitizer.violations())
    yield
    new = sanitizer.violations()[before:]
    assert not new, (
        "lock-order inversions recorded (KFT_LOCKCHECK):\n"
        + "\n".join(repr(v) for v in new))


@pytest.fixture(scope="session")
def devices():
    devs = jax.devices()
    assert len(devs) == 8, f"expected 8 fake-slice devices, got {len(devs)}"
    return devs


@pytest.fixture()
def mesh8(devices):
    """A 2x4 {data, model} mesh over the fake slice."""
    import numpy as np
    from jax.sharding import Mesh

    return Mesh(np.array(devices).reshape(2, 4), ("data", "model"))


@pytest.fixture()
def interpreted_paged_kernel(monkeypatch):
    """ops/paged_attention.py in the Pallas interpreter: this process has
    no chip to lower the kernel for, and product code reaches it through
    the module attribute (models/generate.py)."""
    import functools

    from kubeflow_tpu.ops import paged_attention

    monkeypatch.setattr(
        paged_attention, "paged_decode_attention",
        functools.partial(paged_attention.paged_decode_attention,
                          interpret=True))


@pytest.fixture()
def interpreted_grouped_kernel(monkeypatch):
    """ops/grouped_matmul.py in the Pallas interpreter (product code
    reaches it through the module attribute, models/generate.py
    ``_experts``); yields the list of its calls' (rows, matrices) shapes."""
    from kubeflow_tpu.ops import grouped_matmul

    calls = []
    real = grouped_matmul.grouped_matmul

    def interpreted(rows, weights, sizes):
        calls.append((rows.shape, weights.shape))
        return real(rows, weights, sizes, interpret=True)

    monkeypatch.setattr(grouped_matmul, "grouped_matmul", interpreted)
    return calls
