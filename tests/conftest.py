"""Test harness configuration.

"Cluster without a cluster" (reference TestSparkContext's local[2] Spark,
utils/.../test/TestSparkContext.scala:36): tests run on a virtual 8-device
CPU mesh so multi-chip sharding logic is exercised without TPU hardware.
Must set flags before jax initializes.
"""
import os
import sys

# The suite is the CPU half of the record: eight virtual CPU devices,
# float64. The chip runs float32 on the matmul/mask/device-binned tree
# branches these settings never take; chip_smoke.py covers those. The
# environment variables are for the subprocesses tests spawn; this
# process is configured through jax.config.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
os.environ.setdefault("JAX_ENABLE_X64", "1")

import jax

jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_enable_x64", True)

import numpy as np
import pytest

from transmogrifai_tpu.utils.uid import reset as _reset_uids


@pytest.fixture(autouse=True)
def _deterministic_uids():
    _reset_uids(deterministic=True)
    yield


@pytest.fixture(autouse=True)
def _isolated_profile_store(tmp_path, monkeypatch):
    """Hermetic profile store: the autotuning layer (tuning/policy.py)
    consults the persisted ProfileStore from serving/search/prepare, so
    tests must neither READ the repo-level seeded ``BENCH_STATE.json``
    (tuned decisions would leak into behavior assertions) nor WRITE
    test profiles into it. Tests that need a specific store re-point
    TX_PROFILE_STORE themselves (monkeypatch wins inside the test)."""
    monkeypatch.setenv("TX_PROFILE_STORE",
                       str(tmp_path / "profile_store.json"))
    yield


@pytest.fixture(autouse=True)
def _isolated_audit_cache(tmp_path, monkeypatch):
    """Hermetic audit cache: the plan auditor (analysis/cache.py) and
    the save/load fingerprint hooks default to a shared per-checkout
    cache file under the system tempdir — tests must not read or seed
    it. Tests that assert hit/miss behavior pass cache_path
    explicitly (wins over the env)."""
    monkeypatch.setenv("TX_AUDIT_CACHE",
                       str(tmp_path / "audit_cache.json"))
    yield


@pytest.fixture(autouse=True)
def _no_aot_export_by_default(monkeypatch):
    """AOT artifact export off by default (artifacts/store.py): the
    production default is ON, but every ``model.save`` in the suite
    would otherwise AOT-compile the full 11-bucket ladder (~seconds
    per save, and real mmap pressure — see _mmap_guard). Tests that
    exercise the export/load path set TX_AOT_EXPORT=on themselves
    (monkeypatch inside the test wins)."""
    monkeypatch.setenv("TX_AOT_EXPORT", "off")
    yield


@pytest.fixture(autouse=True)
def _fresh_prepare_registry():
    """The AOT prepare-segment registry (artifacts/loader.py) is
    process-global; a seeded executable leaking across tests would
    make an unrelated train dispatch through another test's program."""
    yield
    from transmogrifai_tpu.artifacts.loader import clear_prepare_registry
    clear_prepare_registry()


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def retired_tree_switches(monkeypatch):
    """Every environment variable that used to select a tree-kernel path
    (ISSUE 30), set to a value that would flip the choice if anything
    still read it. The resolvers in ``models/trees/forms.py`` work their
    answer out from the platform and the sizes alone."""
    for name, value in (("TREE_HIST", "matmul_bf16"), ("TREE_SUB", "1"),
                        ("TREE_DEPTH", "bogus"), ("TREE_BINNING", "bogus"),
                        ("TREE_BLOCK_MB", "1"),
                        ("DEVICE_BIN_MIN_ELEMS", "1")):
        monkeypatch.setenv("TX_" + name, value)


# ---------------------------------------------------------------------------
# the tree tests' tables and environments (tests/tree_cases.py holds their
# cases and spies)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def binary_data():
    rng = np.random.default_rng(0)
    n = 400
    X = rng.normal(size=(n, 6))
    # axis-aligned interaction a tree can represent exactly
    y = ((X[:, 0] > 0.3) & (X[:, 2] < 0.5)).astype(np.float64)
    return X, y


@pytest.fixture(scope="module")
def regression_data():
    rng = np.random.default_rng(1)
    n = 400
    X = rng.normal(size=(n, 5))
    y = np.where(X[:, 0] > 0, 3.0, -1.0) + np.where(X[:, 1] > 1, 2.0, 0.0)
    y = y + 0.01 * rng.normal(size=n)
    return X, y


@pytest.fixture
def infit_env(monkeypatch):
    """``configure(binning)``: host or device binning, depth blocks in one
    program, and an empty design memo on either side of the test."""
    from transmogrifai_tpu.models import trees
    from transmogrifai_tpu.models.trees import forms

    def configure(binning):
        monkeypatch.setattr(forms, "_bin_on_device",
                            lambda elems: binning == "device")
        monkeypatch.setattr(forms, "_depth_mode", lambda: "blocks")
        trees.clear_design_cache()
    yield configure
    trees.clear_design_cache()


@pytest.fixture
def head_env(monkeypatch):
    """The chip's choices on the CPU: the ``matmul`` histogram family (so
    the head form applies), depth blocks in one program, host binning."""
    from transmogrifai_tpu.models import trees
    from transmogrifai_tpu.models.trees import forms
    monkeypatch.setattr(forms, "_hist_mode", lambda n, tb: "matmul")
    monkeypatch.setattr(forms, "_depth_mode", lambda: "blocks")
    monkeypatch.setattr(forms, "_bin_on_device", lambda elems: False)
    trees.clear_design_cache()
    yield monkeypatch
    trees.clear_design_cache()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running parity tests (TX_RUN_SLOW=1)")


# ---------------------------------------------------------------------------
# memory-map exhaustion guard
#
# One pytest process compiles hundreds of XLA CPU executables; each adds
# several mmap regions, and the suite crosses the kernel's default
# vm.max_map_count (65530) around 70-80% of the run — the mmap failure
# then surfaces as a SIGSEGV inside backend_compile (observed r4,
# always in whatever large tree compile came next). Two defenses:
# best-effort raise of the limit (root containers), and dropping
# compiled-executable references every N tests so their mappings are
# actually released.
# ---------------------------------------------------------------------------

def _ensure_map_count(minimum: int = 262144) -> None:
    # system-wide sysctl write — opt out with TX_RAISE_MAP_COUNT=0
    if os.environ.get("TX_RAISE_MAP_COUNT", "1") == "0":
        return
    try:
        with open("/proc/sys/vm/max_map_count") as fh:
            current = int(fh.read())
        if current >= minimum:
            return
        with open("/proc/sys/vm/max_map_count", "w") as fh:
            fh.write(str(minimum))
        print(f"\n[conftest] raised sysctl vm.max_map_count "
              f"{current} -> {minimum} (persists on this host; set "
              f"TX_RAISE_MAP_COUNT=0 to forbid)", file=sys.stderr)
    except (OSError, ValueError, PermissionError):
        pass  # not privileged: the periodic cache clear still bounds growth


_ensure_map_count()

_CLEAR_EVERY = 60
_test_counter = {"n": 0}


def pytest_runtest_teardown(item):
    _test_counter["n"] += 1
    if _test_counter["n"] % _CLEAR_EVERY == 0:
        jax.clear_caches()
