"""Seeded weights and tables, made by the benchmark and handed to the program.

Every leaf is a pure function of ``(seed, leaf name, row, column)``: a
32-bit integer hash (murmur3's finalizer, twice) mapped to a uniform
value.  The same few lines run under ``jax.numpy`` (the whole leaf, on
the device, in one jitted call, in its target sharding) and under
``numpy`` (any subset of rows, for a reference that cannot hold a
16 GB table).  Nothing here imports the program.
"""

from __future__ import annotations

import zlib

import numpy as np

_M1 = 0x85EBCA6B
_M2 = 0xC2B2AE35
_GOLD = 0x9E3779B1
_COL = 0x85EBCA77


def _mix(x, xp):
    u = xp.uint32
    x = x ^ (x >> u(16))
    x = x * u(_M1)
    x = x ^ (x >> u(13))
    x = x * u(_M2)
    return x ^ (x >> u(16))


def split_seed(seed: int):
    """``--seed`` as two unsigned 32-bit words ``(low, high)``: what the
    jitted makers take as an argument, so that one compiled program
    serves every seed."""
    seed = int(seed)
    return np.uint32(seed & 0xFFFFFFFF), np.uint32((seed >> 32) & 0xFFFFFFFF)


def leaf_key(seed, name: str, xp=np):
    """32-bit key of leaf ``name`` under ``seed`` (a whole number, or the
    ``(low, high)`` words of ``split_seed``, which may be traced)."""
    lo, hi = split_seed(seed) if isinstance(seed, int) else seed
    u = xp.uint32
    x = _mix(xp.asarray(lo, dtype=u).reshape(1) * u(_GOLD) + u(zlib.crc32(name.encode()) & 0xFFFFFFFF), xp)
    return _mix(x ^ (xp.asarray(hi, dtype=u).reshape(1) * u(_COL) + u(0x27D4EB2F)), xp)


def unit_uniform(key, rows, cols, xp=np):
    """Uniform values in [-1, 1) at ``(rows, cols)`` (broadcast
    together; unsigned 32-bit index arrays) of the leaf ``key``."""
    u = xp.uint32
    x = _mix(rows.astype(u) * u(_GOLD) + key, xp)
    x = _mix(x ^ (cols.astype(u) * u(_COL) + u(0xC2B2AE3D)), xp)
    return (x >> u(8)).astype(xp.float32) * xp.float32(2.0 ** -23) - xp.float32(1.0)


def leaf_values(seed, name: str, shape, half_width: float, offset: float = 0.0, xp=np):
    """The whole leaf ``name`` of ``shape``: ``offset + half_width * U[-1, 1)``.
    Rows are the flattened leading dimensions, columns the last one."""
    shape = tuple(int(s) for s in shape)
    cols_n = shape[-1] if shape else 1
    rows_n = int(np.prod(shape[:-1])) if len(shape) > 1 else 1
    rows = xp.arange(rows_n, dtype=xp.uint32)
    return leaf_rows(seed, name, rows, cols_n, half_width, offset, xp).reshape(shape)


def leaf_rows(seed, name: str, row_ids, cols_n: int, half_width: float, offset: float = 0.0, xp=np):
    """Rows ``row_ids`` (flattened leading index) of leaf ``name``."""
    rows = xp.asarray(row_ids).astype(xp.uint32)[:, None]
    cols = xp.arange(cols_n, dtype=xp.uint32)[None, :]
    v = unit_uniform(leaf_key(seed, name, xp), rows, cols, xp)
    return xp.float32(offset) + xp.float32(half_width) * v


def round_to(x, dtype, xp=np):
    """``x`` (float32) rounded to ``dtype``'s values and held in float32.
    Under ``jax.numpy`` this is ``lax.reduce_precision``, which XLA keeps:
    a pair of converts there and back is dropped by the compiler's
    excess-precision rule, on the TPU, and rounds nothing."""
    name = np.dtype(dtype).name if not isinstance(dtype, str) else dtype
    bits = {"float32": None, "bfloat16": (8, 7), "float16": (5, 10), "float8_e4m3fn": (4, 3)}[name]
    if bits is None:
        return x
    if xp is np:
        import ml_dtypes

        return x.astype(getattr(ml_dtypes, name)).astype(np.float32)
    import jax

    return jax.lax.reduce_precision(x, exponent_bits=bits[0], mantissa_bits=bits[1])
