"""Pallas TPU kernel for batched GF(2^8) parity encode.

The jitted table-gather kernel in :mod:`s3shuffle_tpu.coding.gf` expresses
``gfmul`` through the log/exp tables — three gathers per (group, j, byte)
term. Gathers are the one thing the VPU does badly; the chip probe's device
codec numbers (tpu-probes/bench_tpu_last_good.json) made the same point for
the TLZ planes.

This kernel removes the gathers entirely. ``gfmul(c, ·)`` with a FIXED
coefficient is GF(2)-linear over the bits of its argument:

    gfmul(c, d) = XOR_a  bit_a(d) * gfmul(c, 1 << a)

and every ``gfmul(c_ij, 1 << a)`` is a compile-time byte constant (the
coefficient matrix is static per (m, k) stripe config — Vandermonde rows).
So one parity byte is 8·k predicated selects + XOR accumulates of scalar
constants — pure element-wise VPU work, no table traffic, no gathers:

    P_i = XOR_j XOR_a  where(bit_a(D_j), gfmul(c_ij, 1 << a), 0)

Grid is (G / TG, L / TL): each step holds a (TG, k, TL) data tile and its
(TG, m, TL) parity tile in VMEM. Zero padding of G and L is exact (zero
data -> zero parity), so callers pad outside and slice.

Like every device codec kernel, correctness is CI-proven in interpret mode
(byte-identical to the numpy host encoder over every k/m, see the property
suite) and the path only RUNS in production when the measured-rate gate says
the chip beats the host (ops/rates.py, metric ``tpu_gf_encode_mb_s``).
"""

from __future__ import annotations

import functools

import numpy as np

from s3shuffle_tpu.coding.gf import gf_mul

#: tile sizes: TG stripe groups x TL payload bytes per grid step. A (TG, k,
#: TL) uint8 data tile is k KiB of VMEM at these sizes.
_TG = 8
_TL = 128

#: kernel-size caps: the unrolled select/XOR chain is 8*k*m ops per tile —
#: beyond these the program gets silly and real configs never go there.
_MAX_M = 8
_MAX_K = 64


def _jax():
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    return jax, jnp, pl


def supported(m: int, k: int) -> bool:
    return 1 <= m <= _MAX_M and 1 <= k <= _MAX_K


def _bit_constants(coefs: np.ndarray):
    """``consts[i][j][a] = gfmul(coefs[i, j], 1 << a)`` as a hashable nested
    tuple — baked into the kernel closure, one program per coefficient
    matrix (stripe configs are few and static)."""
    m, k = coefs.shape
    return tuple(
        tuple(
            tuple(gf_mul(int(coefs[i, j]), 1 << a) for a in range(8))
            for j in range(k)
        )
        for i in range(m)
    )


def _make_kernel(consts):
    m = len(consts)
    k = len(consts[0])

    def kernel(d_ref, out_ref):
        import jax
        import jax.numpy as jnp

        d = d_ref[:].astype(jnp.int32)  # (TG, k, TL)
        outs = []
        for i in range(m):
            acc = jnp.zeros((_TG, _TL), jnp.int32)
            for j in range(k):
                dj = d[:, j, :]
                for a in range(8):
                    c = consts[i][j][a]
                    if c:
                        acc = acc ^ jnp.where(((dj >> a) & 1) != 0, c, 0)
            outs.append(acc)
        out_ref[:] = jnp.stack(outs, axis=1).astype(jnp.uint8)

    return kernel


def _pallas_call(gp: int, lp: int, consts, interpret: bool):
    """The bare pallas_call for ``[gp, k, lp]`` groups (tests compile it for
    a described chip; :func:`_encode_call` jits and times it)."""
    jax, jnp, pl = _jax()
    from jax.experimental.pallas import tpu as pltpu

    m = len(consts)
    k = len(consts[0])
    return pl.pallas_call(
        _make_kernel(consts),
        out_shape=jax.ShapeDtypeStruct((gp, m, lp), jnp.uint8),
        grid=(gp // _TG, lp // _TL),
        in_specs=[
            pl.BlockSpec(
                (_TG, k, _TL), lambda g, l: (g, 0, l), memory_space=pltpu.VMEM
            ),
        ],
        out_specs=pl.BlockSpec(
            (_TG, m, _TL), lambda g, l: (g, 0, l), memory_space=pltpu.VMEM
        ),
        interpret=interpret,
    )


@functools.lru_cache(maxsize=8)
def _encode_call(gp: int, lp: int, consts, interpret: bool):
    jax, _jnp, _pl = _jax()
    from s3shuffle_tpu.ops import rates

    return rates.timed_first_call(
        "gf_encode_pallas", jax.jit(_pallas_call(gp, lp, consts, interpret))
    )


def encode_groups_pallas(
    chunks: np.ndarray, coefs: np.ndarray, interpret: bool = False
) -> np.ndarray:
    """``[G, k, L] x [m, k] -> [G, m, L]`` through the Pallas kernel,
    byte-identical to ``gf._encode_host``. (m, k) must satisfy
    :func:`supported`; G and L are zero-padded to tile multiples here."""
    _jax_mod, _jnp, _pl = _jax()
    chunks = np.ascontiguousarray(chunks, dtype=np.uint8)
    groups, k, length = chunks.shape
    m = coefs.shape[0]
    if not supported(m, k):
        raise ValueError(f"unsupported GF kernel config m={m}, k={k}")
    gp = -(-groups // _TG) * _TG
    lp = -(-length // _TL) * _TL
    if (gp, lp) != (groups, length):
        padded = np.zeros((gp, k, lp), dtype=np.uint8)
        padded[:groups, :, :length] = chunks
        chunks = padded
    out = _encode_call(gp, lp, _bit_constants(coefs), interpret)(chunks)
    return np.asarray(out)[:groups, :, :length]
