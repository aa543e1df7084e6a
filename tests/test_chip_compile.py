"""Compile the device kernels of the main path for a described TPU v5e.

The TPU compiler is installed here and compiles for a chip that is
described, not attached: these tests catch what the CPU backend cannot (TPU
layouts, device memory limits, collectives the partitioner cannot place)
at no chip time. Nothing runs, so they say nothing
about results or speed. The topology is described inside a module fixture —
never at import — so every xdist worker collects the same tests and only the
worker given this file loads the TPU library.

Also here: chip_smoke.py refuses a CPU backend, and the compile-cache helper
picks its directory.
"""

import os
import re
import shutil
import subprocess
import sys

import pytest

from s3shuffle_tpu.ops import tlz
from s3shuffle_tpu.ops.checksum import POLY_CRC32C

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BLOCK = 256 * 1024  # TpuCodec default block_size
ROWS = 64  # TpuCodec default batch_blocks
N_GROUPS = BLOCK // tlz.GROUP
UNSHIFT = (BLOCK.bit_length(), 32, 32)  # the fused CRC's unshift table


@pytest.fixture(scope="module")
def topo():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    had_log_dir = "TPU_LOG_DIR" in os.environ
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without one: keep the cache off around them
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2"
            )
        except Exception as e:  # noqa: BLE001 — no TPU compiler here
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", was_enabled)
        compilation_cache.reset_cache()
        if not had_log_dir:
            os.environ.pop("TPU_LOG_DIR", None)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(topo.devices[0])


def _arg(sharding, shape, dtype):
    import jax

    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    import jax

    return jax.jit(fn).lower(*args).compile()


def _decode_args(sharding, rows):
    import jax.numpy as jnp

    return (
        _arg(sharding, (rows, N_GROUPS), jnp.bool_),  # is_match
        _arg(sharding, (rows, N_GROUPS), jnp.bool_),  # is_cont
        _arg(sharding, (rows, N_GROUPS), jnp.bool_),  # is_split
        _arg(sharding, (rows, N_GROUPS), jnp.int32),  # offs
        _arg(sharding, (rows, N_GROUPS), jnp.int32),  # ks
        _arg(sharding, (rows, N_GROUPS, tlz.GROUP), jnp.uint8),  # lits
    )


def test_crc_fold_pallas_compiles_at_fused_encode_width(one_chip):
    import jax.numpy as jnp

    from s3shuffle_tpu.ops import checksum

    b = 2 * ROWS  # the fused encode CRCs raw blocks AND literal planes
    fn, _tables = checksum.raw_crc_graph_fn(POLY_CRC32C, BLOCK)
    compiled = _compile(
        fn,
        _arg(one_chip, (b, BLOCK), jnp.uint8),
        _arg(one_chip, (b,), jnp.int32),
        _arg(one_chip, (BLOCK * 8, 32), jnp.int8),
        _arg(one_chip, UNSHIFT, jnp.int8),
    )
    text = compiled.as_text()
    # the int8 dot_general lowers to an MXU convolution with int32 output
    assert re.search(
        r"s32\[128,32\]\S* convolution\(.*op_name=\"[^\"]*dot_general", text
    )
    assert "s8[2097152,32]" in text
    assert "tpu_custom_call" not in text


def test_gf_parity_pallas_compiles(one_chip):
    import jax.numpy as jnp

    from s3shuffle_tpu.coding import gf

    compiled = _compile(
        gf._device_kernel(2, 4),
        _arg(one_chip, (4, 4, 1 << 20), jnp.uint8),
        _arg(one_chip, (2, 4), jnp.uint8),
    )
    assert compiled.memory_analysis() is not None


def test_device_decode_compiles(one_chip):
    import functools

    compiled = _compile(
        functools.partial(tlz._decode_counted_math, n_groups=N_GROUPS),
        *_decode_args(one_chip, ROWS),
    )
    assert compiled.memory_analysis() is not None


@pytest.mark.parametrize("crc", ["xla"])  # the node id predates the one CRC
def test_fused_decode_compiles(one_chip, crc):
    import jax.numpy as jnp

    from s3shuffle_tpu.ops import checksum

    # the CRC tables ride as arguments: as a closure constant the
    # bit-matmul's 64 MiB weight table takes ~50 s of constant folding
    crc_fn, _tables = checksum.raw_crc_graph_fn(POLY_CRC32C, BLOCK)

    def fused(m, c, s, o, k, lits, n_lits, w_bits, unshift):
        return tlz._decode_fused_math(
            m, c, s, o, k, lits, n_lits, N_GROUPS,
            lambda data, n: crc_fn(data, n, w_bits, unshift),
        )

    compiled = _compile(
        fused, *_decode_args(one_chip, ROWS),
        _arg(one_chip, (ROWS,), jnp.int32),
        _arg(one_chip, (BLOCK * 8, 32), jnp.int8),
        _arg(one_chip, UNSHIFT, jnp.int8),
    )
    assert compiled.memory_analysis() is not None


def test_fused_encode_program_holds_the_bit_matmul(one_chip, monkeypatch):
    """The codec's own fused encode program at the terasort.r8 launch shape,
    lowered (not compiled): its CRC is the int8 bit-matmul against the
    weight-table argument, and nothing in it is a custom call."""
    import jax
    import jax.numpy as jnp

    programs = []
    real_jit = jax.jit

    def recording_jit(*args, **kwargs):
        programs.append(real_jit(*args, **kwargs))
        return programs[-1]

    monkeypatch.setattr(jax, "jit", recording_jit)
    tlz._batch_kernel.__wrapped__(ROWS, N_GROUPS, POLY_CRC32C)
    (program,) = programs
    text = program.lower(
        _arg(one_chip, (ROWS, BLOCK), jnp.uint8),
        _arg(one_chip, (BLOCK * 8, 32), jnp.int8),
        _arg(one_chip, UNSHIFT, jnp.int8),
    ).as_text()
    assert re.search(
        r"stablehlo\.dot_general .*%arg1.*: \(tensor<128x2097152xi8>, "
        r"tensor<2097152x32xi8>\) -> tensor<128x32xi32>", text
    )
    assert "custom_call" not in text


def test_tlz_xla_encode_compiles_at_block_width(one_chip):
    import functools

    import jax.numpy as jnp

    # one row keeps this to ~15 s; the block width is the codec's
    compiled = _compile(
        functools.partial(tlz._encode_math, n_groups=N_GROUPS),
        _arg(one_chip, (1, BLOCK), jnp.uint8),
    )
    assert compiled.memory_analysis() is not None


def test_repartition_step_compiles_on_four_chips_with_all_to_all(topo):
    import jax
    import jax.numpy as jnp
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from s3shuffle_tpu.parallel.repartition import plan_capacity, sharded_step

    import numpy as np

    mesh = Mesh(np.asarray(topo.devices[:4]), ("data",))
    n, row_bytes = 4 * 4096, 101  # flag byte + a terasort record
    step = sharded_step(mesh, "data", plan_capacity(n // 4, 4), row_bytes)
    compiled = step.lower(
        jax.ShapeDtypeStruct((n, row_bytes), jnp.uint8,
                             sharding=NamedSharding(mesh, P("data", None))),
        jax.ShapeDtypeStruct((n,), jnp.int32,
                             sharding=NamedSharding(mesh, P("data"))),
    ).compile()
    assert "all-to-all" in compiled.as_text()


# ---------------------------------------------------------------------------
# chip_smoke.py never falls back to the CPU
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_cpu(tmp_path, where):
    script = os.path.join(REPO, "chip_smoke.py")
    if where == "alone":  # a directory holding chip_smoke.py and nothing else
        script = shutil.copy(script, tmp_path / "chip_smoke.py")
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run(
        [sys.executable, str(script)],
        cwd=tmp_path, env=env, capture_output=True, text=True, timeout=300,
    )
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


# ---------------------------------------------------------------------------
# compile-cache helper: JAX_COMPILATION_CACHE_DIR wins, else <repo>/.jax_cache
# ---------------------------------------------------------------------------


@pytest.fixture
def recorded_config(monkeypatch):
    import jax
    from jax.experimental.compilation_cache import compilation_cache

    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: updates.__setitem__(name, value))
    monkeypatch.setattr(compilation_cache, "reset_cache",
                        lambda: updates.__setitem__("reset", True))
    return updates


def test_compile_cache_honours_env_dir(monkeypatch, tmp_path, recorded_config):
    from s3shuffle_tpu.utils.compile_cache import enable_compile_cache

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert "jax_compilation_cache_dir" not in recorded_config
    assert recorded_config["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_compile_cache_defaults_to_repo_dir(monkeypatch, recorded_config):
    from s3shuffle_tpu.utils.compile_cache import enable_compile_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert enable_compile_cache() == want
    assert recorded_config["jax_compilation_cache_dir"] == want
    assert recorded_config["jax_persistent_cache_min_compile_time_secs"] == 0.0


def test_library_cache_keeps_a_dir_set_through_jax_config(tmp_path,
                                                           recorded_config):
    import jax

    from s3shuffle_tpu.utils.compile_cache import default_compile_cache

    real_update = type(jax.config).update
    before = jax.config.jax_compilation_cache_dir
    real_update(jax.config, "jax_compilation_cache_dir", str(tmp_path))
    try:
        assert default_compile_cache() == str(tmp_path)
    finally:
        real_update(jax.config, "jax_compilation_cache_dir", before)
    assert recorded_config == {}  # no dir, no reset, no min compile time


def test_library_cache_fills_in_the_repo_dir_only(monkeypatch, recorded_config):
    import jax

    from s3shuffle_tpu.utils.compile_cache import default_compile_cache

    real_update = type(jax.config).update
    before = jax.config.jax_compilation_cache_dir
    real_update(jax.config, "jax_compilation_cache_dir", None)
    try:
        want = os.path.join(REPO, ".jax_cache")
        assert default_compile_cache() == want
    finally:
        real_update(jax.config, "jax_compilation_cache_dir", before)
    assert recorded_config == {"jax_compilation_cache_dir": want}
