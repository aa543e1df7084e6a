"""Test configuration.

Force JAX onto a virtual 8-device CPU mesh so sharding/collective tests run
without TPU hardware (the driver separately dry-run-compiles the multi-chip
path via __graft_entry__.dryrun_multichip).
"""

import os

# The test suite always runs on a virtual 8-device CPU mesh;
# tests/test_chip_compile.py compiles for a described v5e.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import pytest  # noqa: E402

# Lock-order witness (S3SHUFFLE_LOCK_WITNESS=1): must install BEFORE any
# product import — module-level locks (metric registries, gc_paused in
# utils/__init__, the shared fetch-executor guard, trace state) are
# constructed at import time and can only be witnessed if threading is
# already patched. A plain `from s3shuffle_tpu.utils import lockwitness`
# would run the package __init__s FIRST (constructing gc_paused's lock raw),
# so the module — deliberately stdlib-only — is loaded straight from its
# file and pre-registered in sys.modules under its canonical name: the later
# package import reuses this exact module object (one _installed, one
# witness). The session fixture at the bottom fails the run on cycles.
import importlib.util as _ilu  # noqa: E402
import sys as _sys  # noqa: E402

_LW_NAME = "s3shuffle_tpu.utils.lockwitness"
_spec = _ilu.spec_from_file_location(
    _LW_NAME,
    os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "s3shuffle_tpu", "utils", "lockwitness.py",
    ),
)
lockwitness = _ilu.module_from_spec(_spec)
_sys.modules[_LW_NAME] = lockwitness
_spec.loader.exec_module(lockwitness)

_WITNESS = lockwitness.install_from_env()

# Happens-before race witness (S3SHUFFLE_RACE_WITNESS=1): same early-load
# constraint and same spec-loading idiom — it layers on lockwitness's
# interposition (racewitness.install() installs the lock witness itself if
# the env didn't), so it too must be in place before product imports.
_RW_NAME = "s3shuffle_tpu.utils.racewitness"
_rw_spec = _ilu.spec_from_file_location(
    _RW_NAME,
    os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "s3shuffle_tpu", "utils", "racewitness.py",
    ),
)
racewitness = _ilu.module_from_spec(_rw_spec)
_sys.modules[_RW_NAME] = racewitness
_rw_spec.loader.exec_module(racewitness)

_RACE_WITNESS = racewitness.install_from_env()

from s3shuffle_tpu.storage.dispatcher import Dispatcher  # noqa: E402

# Mode matrix (the analog of the reference CI's second run with
# USE_SPARK_SHUFFLE_FETCH=true, ci.yml:58-65): S3SHUFFLE_TEST_MODE overrides
# default config fields for the whole suite.
_TEST_MODE = os.environ.get("S3SHUFFLE_TEST_MODE", "default")
_MODE_OVERRIDES = {
    "default": {},
    "fallback-fetch": {"use_fallback_fetch": True},
    "listing": {"use_block_manager": False},
}.get(_TEST_MODE, {})

if _MODE_OVERRIDES:
    import dataclasses as _dc

    from s3shuffle_tpu import config as _config_mod

    _orig_init = _config_mod.ShuffleConfig.__init__

    def _mode_init(self, *args, **kwargs):
        for field, value in _MODE_OVERRIDES.items():
            kwargs.setdefault(field, value)
        _orig_init(self, *args, **kwargs)

    _config_mod.ShuffleConfig.__init__ = _mode_init  # type: ignore[method-assign]


def pytest_configure(config):
    config.addinivalue_line("markers", "slow: spawns worker processes / long-running")
    # Strictness: leaked handles and background-thread deaths become FAILURES
    # instead of warnings — the dynamic complement to shuffle-lint's EXC01 /
    # THR01 (a ResourceWarning is a leaked open_ranged/create handle; an
    # unraisable or thread excepthook error is a helper thread dying silently,
    # which no static rule can prove).
    config.addinivalue_line("filterwarnings", "error::ResourceWarning")
    config.addinivalue_line(
        "filterwarnings", "error::pytest.PytestUnraisableExceptionWarning"
    )
    config.addinivalue_line(
        "filterwarnings", "error::pytest.PytestUnhandledThreadExceptionWarning"
    )
    # The batched TLZ encode kernels donate their staged input (ops/tlz.py);
    # XLA:CPU often can't alias uint8 staging buffers and jax warns per
    # compilation — an expected no-op on the test backend, not a leak.
    config.addinivalue_line(
        "filterwarnings",
        "ignore:Some donated buffers were not usable:UserWarning",
    )


@pytest.fixture(autouse=True)
def _reset_dispatcher_singleton():
    Dispatcher.reset()
    yield
    Dispatcher.reset()


@pytest.fixture(scope="session", autouse=True)
def _lock_witness_verdict():
    """With S3SHUFFLE_LOCK_WITNESS=1: fail the session if the lock-order
    witness observed an acquisition-order cycle anywhere in the run (the
    stress + fault-soak tests are the interesting coverage)."""
    yield
    if _WITNESS is not None:
        report = _WITNESS.format_report()
        print("\n" + report)
        assert not _WITNESS.find_cycles(), report


@pytest.fixture(scope="session", autouse=True)
def _race_witness_verdict():
    """With S3SHUFFLE_RACE_WITNESS=1: fail the session if the happens-before
    witness saw an unsynchronized access pair on any watched structure, and
    fold its tallies into race_witness_{checks,reports}_total."""
    yield
    if _RACE_WITNESS is not None:
        report = _RACE_WITNESS.format_report()
        print("\n" + report)
        racewitness.publish_metrics(_RACE_WITNESS)
        assert not _RACE_WITNESS.reports, report


# Product import is safe here: the lock witness installed above, at module
# top, before any s3shuffle_tpu import.
from s3shuffle_tpu.storage.fault import FlakyBackend  # noqa: E402


class RecordingBackend(FlakyBackend):
    """FlakyBackend that records every (op, path) it sees — the request
    pattern the store would bill for. Shared by the op-for-op regression
    gates (coalesce gap=0, composite off, autotune off, parity=0): one
    definition, so a change to FlakyBackend's _check hook or the
    op-multiset convention cannot silently weaken one gate."""

    def __init__(self, inner):
        super().__init__(inner)
        self.ops = []

    def _check(self, op: str, path: str, nbytes: int = 0) -> None:
        self.ops.append((op, path))
        super()._check(op, path, nbytes=nbytes)

    def count(self, op: str, needle: str = "") -> int:
        return sum(1 for o, p in self.ops if o == op and needle in p)
