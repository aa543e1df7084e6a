"""Guards for the driver entry points (__graft_entry__.py).

The round driver compile-checks ``entry()`` single-chip and executes
``dryrun_multichip(n)`` on a virtual CPU mesh; a regression here fails the
whole round, so the suite pins both contracts. Each runs in a subprocess:
device-count flags must be set before JAX initializes a backend.
"""

import os
import subprocess
import sys

import pytest

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, env_extra: dict) -> subprocess.CompletedProcess:
    env = dict(os.environ, **env_extra)
    env.pop("S3SHUFFLE_TEST_MODE", None)
    return subprocess.run(
        [sys.executable, "-c", code],
        cwd=_REPO,
        env=env,
        capture_output=True,
        text=True,
        timeout=540,
    )


@pytest.mark.slow
def test_entry_returns_jittable_fn_and_args():
    code = (
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        "import __graft_entry__ as g\n"
        "fn, args = g.entry()\n"
        "out = jax.jit(fn)(*args)\n"
        "jax.tree_util.tree_map(lambda x: x.block_until_ready(), out)\n"
        "print('ENTRY_OK')\n"
    )
    r = _run(code, {"JAX_PLATFORMS": "cpu"})
    assert r.returncode == 0, r.stderr[-2000:]
    assert "ENTRY_OK" in r.stdout


@pytest.mark.slow
def test_dryrun_multichip_8_devices():
    code = (
        "import __graft_entry__ as g\n"
        "g.dryrun_multichip(8)\n"
        "print('DRYRUN_OK')\n"
    )
    r = _run(
        code,
        {
            "JAX_PLATFORMS": "cpu",
            "XLA_FLAGS": "--xla_force_host_platform_device_count=8",
        },
    )
    assert r.returncode == 0, r.stderr[-2000:]
    assert "DRYRUN_OK" in r.stdout
