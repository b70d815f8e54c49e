"""What the chip would refuse, found without the chip.

- AOT compiles for a DESCRIBED TPU v5e (``on-chip-measurement`` guide
  §2, rehearsal 3): the Pallas kernels of the main path at the shapes
  ``chip_smoke.py`` runs them at, forced out of interpret mode, through
  the TPU compiler that is installed here.  Interpret-mode tests
  (test_pallas.py, test_serving.py) cannot see a block shape Mosaic
  rejects or a kernel past scoped VMEM; these can.  Nothing runs, so
  they say nothing about results or times.  Skipped where the
  topology cannot be described.  tests/conftest.py keeps the suite off
  the persistent compilation cache, which such a compile could write
  to but never read back.
- The CPU rehearsal of ``chip_smoke.py``: its real phases at tiny
  sizes, with the two things only a chip can answer (the device check,
  the Mosaic call in compiled text) stubbed HERE, not by an option of
  the script.
- The compile-cache rule (apps/common.enable_compile_cache).
"""

import functools
import math
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest

from flexflow_tpu.apps import common
from flexflow_tpu.ops import pallas_kernels as pk

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

F32, BF16 = jnp.float32, jnp.bfloat16


# -- AOT compiles for a described v5e ----------------------------------------


@functools.lru_cache(maxsize=None)
def _four_chips():
    """The four described devices of one v5e host (a 2x2 mesh)."""
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"cannot describe a v5e topology: {e}")
    return list(topo.devices)


def _one_chip():
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(_four_chips()[0])


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=_one_chip())


def _flash(shape, dtype, grad):
    def fwd(q, k, v):
        return pk.flash_attention_lse_auto(q, k, v, True, interpret=False)[0]

    fn = jax.grad(lambda q, k, v: fwd(q, k, v).astype(F32).sum(),
                  argnums=(0, 1, 2)) if grad else fwd
    x = _sds(shape, dtype)
    return (lambda: pk.flash_any_supported(shape, dtype)), fn, (x, x, x)


def _xent(n, v, dtype, grad):
    def fwd(logits, labels):
        return pk.softmax_xent(logits, labels, interpret=False)[0]

    fn = jax.grad(lambda lg, lb: fwd(lg, lb).sum()) if grad else fwd
    return (lambda: pk.xent_supported(n, v)), fn, (
        _sds((n, v), dtype), _sds((n,), jnp.int32))


def _decode(b, s, h, hd, dtype):
    return _decode_grouped_major(b, s, h, h, hd, dtype)


def _flash_uneven(t, h, qk, dv, h_kv=None):
    q, k = _sds((1, h, t, qk), BF16), _sds((1, h_kv or h, t, qk), BF16)
    fn = functools.partial(pk.flash_fwd_uneven, scale=qk ** -0.5,
                           interpret=False)
    return (lambda: pk.flash_uneven_supported((1, h, t, qk), dv)), fn, (
        q, k, _sds((1, h_kv or h, t, dv), BF16))


def _attend_kept(t, h, h_kv, c, own, dv, shared=None):
    """A selected prefill's last chunk of ``c`` rows against all ``t``
    keys: the widest run of the walk."""
    q = _sds((1, h, c, own + (shared or 0)), BF16)
    k, v = _sds((1, h_kv, t, own), BF16), _sds((1, h_kv, t, dv), BF16)
    keep, start = _sds((1, c, t), jnp.bool_), _sds((), jnp.int32)
    scale = q.shape[-1] ** -0.5
    gate = lambda: pk.attend_kept_supported(q.shape, k.shape, dv, shared)
    if shared is None:
        return gate, lambda q, k, v, keep, start: pk.attend_kept(
            q, k, v, keep, start, scale, interpret=False), (q, k, v, keep, start)
    return gate, lambda q, k, v, sk, keep, start: pk.attend_kept(
        q, k, v, keep, start, scale, shared_k=sk, interpret=False), (
            q, k, v, _sds((1, t, shared), BF16), keep, start)


def _decode_grouped(b, s, h, h_kv, hd, dtype):
    """Grouped queries over a positions-last cache, as the op declares it."""
    fn = functools.partial(pk.flash_decode, interpret=False, positions_last=True)
    new, cache = _sds((b, h_kv, hd), dtype), _sds((b, h_kv, hd, s), dtype)
    gate = lambda: pk.flash_decode_supported((b, s, h_kv, hd), dtype, h // h_kv)
    return gate, fn, (_sds((b, h, hd), dtype), new, new, cache, cache,
                      _sds((b,), jnp.int32))


def _decode_grouped_major(b, s, h, h_kv, hd, dtype):
    """``h`` query heads over ``h_kv`` cached ones on a positions-major
    cache: heads narrower than a lane tile, as the op declares them (the
    chip stores that order as the kernel reads it: no cache-sized copy)."""
    fn = functools.partial(pk.flash_decode, interpret=False)
    new, cache = _sds((b, h_kv, hd), dtype), _sds((b, s, h_kv, hd), dtype)
    gate = lambda: pk.flash_decode_supported((b, s, h_kv, hd), dtype, h // h_kv)
    return gate, fn, (_sds((b, h, hd), dtype), new, new, cache, cache,
                      _sds((b,), jnp.int32))


def _flash_window(t, h, h_kv, hd, window):
    q, k = _sds((1, h, t, hd), BF16), _sds((1, h_kv, t, hd), BF16)
    fn = functools.partial(pk.flash_fwd_window, scale=hd ** -0.5,
                           window=window, interpret=False)
    return (lambda: pk.flash_window_supported((1, h, t, hd), window)), fn, (
        q, k, k)


def _decode_ring(b, w, h, h_kv, hd, dtype):
    """Grouped queries over a ring of ``w`` positions: the write index a
    second scalar operand."""
    fn = functools.partial(pk.flash_decode, interpret=False, positions_last=True)
    new, cache = _sds((b, h_kv, hd), dtype), _sds((b, h_kv, hd, w), dtype)
    gate = lambda: pk.flash_decode_supported((b, w, h_kv, hd), dtype, h // h_kv)
    vec = _sds((b,), jnp.int32)
    return gate, lambda q, k1, v1, ck, cv, n, at: fn(
        q, k1, v1, ck, cv, n, write_at=at), (
            _sds((b, h, hd), dtype), new, new, cache, cache, vec, vec)


def _kda_chunk(t, n, d):
    x = _sds((t, n, d), F32)
    fn = functools.partial(pk.kda_chunk, interpret=False)
    return (lambda: pk.kda_supported(d, d)), fn, (
        x, x, x, x, _sds((t, n), F32), _sds((n, d, d), F32))


def _kda_decode(b, h, d):
    x = _sds((b, h, d), F32)
    fn = functools.partial(pk.kda_decode, interpret=False)
    return (lambda: pk.kda_supported(d, d)), fn, (
        x, x, x, x, _sds((b, h), F32), _sds((b, h, d, d), F32))


def _mla_decode(b, h, row, dv, s):
    fn = lambda q, col, c, n: pk.mla_decode(q, col, c, n, dv, 0.07,
                                            interpret=False)
    return (lambda: pk.mla_decode_supported((b, row, s), dv)), fn, (
        _sds((b, h, row), BF16), _sds((b, row), BF16),
        _sds((b, row, s), BF16), _sds((b,), jnp.int32))


def _grouped(rows, e, k, n, tm, gated):
    """One grouped product as the expert layer calls it: ``rows`` the
    padded bound of ``grouped_tile_rows``'s tile at that many
    assignments."""
    x, w = _sds((rows, k), BF16), _sds((e, k, n), BF16)
    tiles = _sds((rows // tm,), jnp.int32)
    used = _sds((), jnp.int32)
    gate = lambda: pk.grouped_matmul_supported(k, n, BF16)
    if gated:
        fn = lambda x, w, u, te, nu: pk.grouped_matmul(
            x, w, te, nu, tm, w_up=u, interpret=False)
        return gate, fn, (x, w, w, tiles, used)
    fn = lambda x, w, te, nu: pk.grouped_matmul(x, w, te, nu, tm,
                                                interpret=False)
    return gate, fn, (x, w, tiles, used)


def _rows(kind, shape, n_ids, addressing):
    """A row kernel over a table of ``shape`` ((R, D) or stacked
    (T, V, D)); the gate also holds the shape to the addressing it is
    listed under."""
    table, ids = _sds(shape, F32), _sds((n_ids,), jnp.int32)
    gate = lambda: pk.rows_addressing(n_ids, shape, F32, kind) == addressing
    if kind == "gather":
        return gate, functools.partial(pk.gather_rows, interpret=False), (
            table, ids)
    return gate, functools.partial(pk.scatter_add_rows, interpret=False), (
        table, ids, _sds((n_ids, shape[-1]), F32))


#: name -> () -> (gate, fn, abstract args): the kernels of the smoke's
#: phases at their real shapes (transformer b8 x 8 heads x seq 512 x
#: hd 64 and its 4096 x 32768 logits; serve's 8 x 512 x 8 x 64 cache and
#: the ``gpt2m.serve.closed48`` cell's 48 x 1024 x 16 x 64;
#: DLRM's 4 stacked 1M-row d=64 tables, whose last 128-row block is
#: partial), plus the long-context flash shape, the ``gpt2m.train.b8s1024``
#: cell's flash shape, the largest decode
#: shape ISSUE 21 names, and the row kernels in both addressings: the
#: ``dlrm.random.b1024`` cell's 8 x 2M x 64 at 8192 ids, 2-D narrow
#: tables, and the row-major widths (128, and GPT-2's 1024).
CASES = {
    "flash_fwd-8x8x512x64-bf16": lambda: _flash((8, 8, 512, 64), BF16, False),
    "flash_grad-8x8x512x64-bf16": lambda: _flash((8, 8, 512, 64), BF16, True),
    "flash_fwd-2x8x8192x64-bf16": lambda: _flash((2, 8, 8192, 64), BF16, False),
    "flash_grad-2x8x8192x64-bf16": lambda: _flash((2, 8, 8192, 64), BF16, True),
    # The gpt2m.train.b8s1024 cell's own shape: batch 8, 16 heads of 64.
    "flash_fwd-8x16x1024x64-bf16": lambda: _flash((8, 16, 1024, 64), BF16, False),
    "flash_grad-8x16x1024x64-bf16": lambda: _flash((8, 16, 1024, 64), BF16, True),
    "xent_fwd-4096x32768-bf16": lambda: _xent(4096, 32768, BF16, False),
    "xent_grad-4096x32768-bf16": lambda: _xent(4096, 32768, BF16, True),
    "decode-8x512x8x64-f32": lambda: _decode(8, 512, 8, 64, F32),
    "decode-8x512x8x64-bf16": lambda: _decode(8, 512, 8, 64, BF16),
    "decode-48x1024x16x64-bf16": lambda: _decode(48, 1024, 16, 64, BF16),
    "decode-16x4096x16x128-bf16": lambda: _decode(16, 4096, 16, 128, BF16),
    # The kanana2.serve.closed16.p4k-15k cell's kernels at its widths
    # (32 heads, q.k 192 against v 128, a 576-value column, 128 experts
    # of 2048 x 768): the 4608 and 16384 prefill buckets, 16 slots of
    # 16384 positions, 96 assignments a decode step (16-row tiles) and
    # a 16k prefill's 98304 (128-row tiles); the smoke preset's; and the
    # xing4.serve.closed96.p256-2k cell's 96 slots of 4096 positions.
    "flash_uneven-32x4608x192v128-bf16":
        lambda: _flash_uneven(4608, 32, 192, 128),
    "flash_uneven-32x16384x192v128-bf16":
        lambda: _flash_uneven(16384, 32, 192, 128),
    "flash_uneven-4x256x96v64-bf16": lambda: _flash_uneven(256, 4, 96, 64),
    # xing4.serve's shortest bucket: one block, the diagonal's static walk alone
    "flash_uneven-32x512x192v128-bf16":
        lambda: _flash_uneven(512, 32, 192, 128),
    "mla_decode-16x32x576x16384-bf16":
        lambda: _mla_decode(16, 32, 576, 512, 16384),
    "mla_decode-96x32x576x4096-bf16":
        lambda: _mla_decode(96, 32, 576, 512, 4096),
    "mla_decode-4x4x160x256-bf16": lambda: _mla_decode(4, 4, 160, 128, 256),
    "grouped_matmul-gated-decode-1536x2048x768":
        lambda: _grouped(1536, 128, 2048, 768, 16, True),
    "grouped_matmul-down-decode-1536x768x2048":
        lambda: _grouped(1536, 128, 768, 2048, 16, False),
    "grouped_matmul-gated-prefill-114560x2048x768":
        lambda: _grouped(114560, 128, 2048, 768, 128, True),
    "grouped_matmul-down-prefill-114560x768x2048":
        lambda: _grouped(114560, 128, 768, 2048, 128, False),
    "grouped_matmul-gated-smoke-128x256x128":
        lambda: _grouped(128, 8, 256, 128, 16, True),
    "grouped_matmul-down-smoke-128x128x256":
        lambda: _grouped(128, 8, 128, 256, 16, False),
    # The solar2.serve.closed32.p4k-31k cell's kernels at its widths (64
    # query heads over 8 key/value heads of 128, 64 delta-rule heads of
    # 128, 40 held experts of 4096 x 1280): the 4608 and 32768 prefill
    # buckets, a 2048-token segment of the chunked scan, 32 slots of
    # 32768 positions, 256 assignments a decode step (16-row tiles) and
    # a 4096-token segment's 32768 (128-row tiles); and the smoke preset's.
    "flash_uneven-gqa64x8-4608x128-bf16":
        lambda: _flash_uneven(4608, 64, 128, 128, h_kv=8),
    "flash_uneven-gqa64x8-32768x128-bf16":
        lambda: _flash_uneven(32768, 64, 128, 128, h_kv=8),
    "flash_uneven-gqa4x2-256x128-bf16":
        lambda: _flash_uneven(256, 4, 128, 128, h_kv=2),
    "decode_grouped-32x32768x64x8x128-bf16":
        lambda: _decode_grouped(32, 32768, 64, 8, 128, BF16),
    "decode_grouped-4x256x4x2x128-bf16":
        lambda: _decode_grouped(4, 256, 4, 2, 128, BF16),
    # The laguna.serve.closed16.p8k-31k cell's kernels at its widths (72
    # and 48 query heads over 8 key/value heads of 128: groups of 9 and
    # 6; a window of 512; 64 held experts of 3072 x 1024): the banded
    # prefill at the 8704 and 32768 buckets, the causal one of the full
    # layers, 16 slots of 32768 positions and of a 512-position ring,
    # 160 assignments a decode step (1,120 rows of 16-row tiles) and a
    # prefill segment's 40,960 (4,096 tokens; 128-row tiles); and the
    # smoke preset's.
    "flash_window-gqa72x8-8704x128w512-bf16":
        lambda: _flash_window(8704, 72, 8, 128, 512),
    "flash_window-gqa72x8-32768x128w512-bf16":
        lambda: _flash_window(32768, 72, 8, 128, 512),
    "flash_window-gqa18x2-1280x128w512-bf16":
        lambda: _flash_window(1280, 18, 2, 128, 512),
    "flash_uneven-gqa48x8-32768x128-bf16":
        lambda: _flash_uneven(32768, 48, 128, 128, h_kv=8),
    "decode_grouped-16x32768x48x8x128-bf16":
        lambda: _decode_grouped(16, 32768, 48, 8, 128, BF16),
    "decode_ring-16x512x72x8x128-bf16":
        lambda: _decode_ring(16, 512, 72, 8, 128, BF16),
    "decode_ring-4x512x18x2x128-bf16":
        lambda: _decode_ring(4, 512, 18, 2, 128, BF16),
    "decode_ring-4x1024x12x2x128-bf16":
        lambda: _decode_ring(4, 1024, 12, 2, 128, BF16),
    "grouped_matmul-gated-decode-1120x3072x1024":
        lambda: _grouped(1120, 64, 3072, 1024, 16, True),
    "grouped_matmul-down-decode-1120x1024x3072":
        lambda: _grouped(1120, 64, 1024, 3072, 16, False),
    "grouped_matmul-gated-prefill-49152x3072x1024":
        lambda: _grouped(49152, 64, 3072, 1024, 128, True),
    "grouped_matmul-down-prefill-49152x1024x3072":
        lambda: _grouped(49152, 64, 1024, 3072, 128, False),
    "kda_chunk-2048x64x128": lambda: _kda_chunk(2048, 64, 128),
    "kda_chunk-1536x64x128": lambda: _kda_chunk(1536, 64, 128),
    "kda_chunk-256x2x128": lambda: _kda_chunk(256, 2, 128),
    "kda_decode-32x64x128": lambda: _kda_decode(32, 64, 128),
    "kda_decode-4x2x128": lambda: _kda_decode(4, 2, 128),
    "grouped_matmul-gated-decode-864x4096x1280":
        lambda: _grouped(864, 40, 4096, 1280, 16, True),
    "grouped_matmul-down-decode-864x1280x4096":
        lambda: _grouped(864, 40, 1280, 4096, 16, False),
    "grouped_matmul-gated-prefill-37888x4096x1280":
        lambda: _grouped(37888, 40, 4096, 1280, 128, True),
    "grouped_matmul-down-prefill-37888x1280x4096":
        lambda: _grouped(37888, 40, 1280, 4096, 128, False),
    # The axk2.serve.closed8.p8k-31k cell's expert products (16 held
    # experts of 7168 x 2048: blocks of 512 and 3584 columns, PR 48).
    "grouped_matmul-gated-decode-304x7168x2048":
        lambda: _grouped(304, 16, 7168, 2048, 16, True),
    "grouped_matmul-down-decode-304x2048x7168":
        lambda: _grouped(304, 16, 2048, 7168, 16, False),
    "grouped_matmul-gated-prefill-3584x7168x2048":
        lambda: _grouped(3584, 16, 7168, 2048, 128, True),
    "grouped_matmul-down-prefill-3584x2048x7168":
        lambda: _grouped(3584, 16, 2048, 7168, 128, False),
    # A selected prefill's masked chunks (PR 49) at the two cells' shapes,
    # 512 rows against a 32768 bucket: axk2's 64 heads of 128 + 64 against
    # 128 with the rotary key shared, keye2's 32 query heads over 4; and
    # the two smoke presets' (a key part of 64 cut inside a lane tile).
    "attend_kept-64x512x128s64v128-32768-bf16":
        lambda: _attend_kept(32768, 64, 64, 512, 128, 128, shared=64),
    "attend_kept-gqa32x4-512x128-32768-bf16":
        lambda: _attend_kept(32768, 32, 4, 512, 128, 128),
    "attend_kept-4x512x64s32v64-1024-bf16":
        lambda: _attend_kept(1024, 4, 4, 512, 64, 64, shared=32),
    "attend_kept-gqa4x2-128x128-512-bf16":
        lambda: _attend_kept(512, 4, 2, 128, 128, 128),
    # The lfm2.serve.closed192.p256-2k cell's kernels at its widths (32
    # query heads over 8 key/value heads of 64, 64 experts of 2048 x
    # 1536): 192 slots of 3072 positions, the 512 and 2048 prefill
    # buckets, 768 assignments a decode step (1,728 rows of 16-row
    # tiles) and a 2048-token prefill's 8192 (16,384 rows of 128-row
    # tiles); and the smoke preset's.
    "decode_grouped_major-192x3072x32x8x64-bf16":
        lambda: _decode_grouped_major(192, 3072, 32, 8, 64, BF16),
    "decode_grouped_major-4x512x8x2x64-bf16":
        lambda: _decode_grouped_major(4, 512, 8, 2, 64, BF16),
    "flash_uneven-gqa32x8-512x64-bf16":
        lambda: _flash_uneven(512, 32, 64, 64, h_kv=8),
    "flash_uneven-gqa32x8-2048x64-bf16":
        lambda: _flash_uneven(2048, 32, 64, 64, h_kv=8),
    "grouped_matmul-gated-decode-1728x2048x1536":
        lambda: _grouped(1728, 64, 2048, 1536, 16, True),
    "grouped_matmul-down-decode-1728x1536x2048":
        lambda: _grouped(1728, 64, 1536, 2048, 16, False),
    "grouped_matmul-gated-prefill-16384x2048x1536":
        lambda: _grouped(16384, 64, 2048, 1536, 128, True),
    "grouped_matmul-down-prefill-16384x1536x2048":
        lambda: _grouped(16384, 64, 1536, 2048, 128, False),
    "gather_rows-1Mx64-1024ids":
        lambda: _rows("gather", (1 << 20, 64), 1024, "lane_major"),
    "scatter_add_rows-1Mx64-1024ids":
        lambda: _rows("scatter", (1 << 20, 64), 1024, "lane_major"),
    "gather_rows-8x2Mx64-8192ids":
        lambda: _rows("gather", (8, 2000000, 64), 8192, "lane_major"),
    "scatter_add_rows-8x2Mx64-8192ids":
        lambda: _rows("scatter", (8, 2000000, 64), 8192, "lane_major"),
    "gather_rows-4x1000000x64-4096ids":
        lambda: _rows("gather", (4, 1000000, 64), 4096, "lane_major"),
    "scatter_add_rows-4x1000000x64-4096ids":
        lambda: _rows("scatter", (4, 1000000, 64), 4096, "lane_major"),
    "gather_rows-1000000x16-1024ids":
        lambda: _rows("gather", (1000000, 16), 1024, "lane_major"),
    "scatter_add_rows-1000000x16-1024ids":
        lambda: _rows("scatter", (1000000, 16), 1024, "lane_major"),
    "gather_rows-1Mx128-1024ids":
        lambda: _rows("gather", (1 << 20, 128), 1024, "row_major"),
    "scatter_add_rows-1Mx128-1024ids":
        lambda: _rows("scatter", (1 << 20, 128), 1024, "row_major"),
    "gather_rows-50257x1024-1024ids":
        lambda: _rows("gather", (50257, 1024), 1024, "row_major"),
    "scatter_add_rows-50257x1024-1024ids":
        lambda: _rows("scatter", (50257, 1024), 1024, "row_major"),
}


@functools.lru_cache(maxsize=None)
def _compiled_text(name: str) -> str:
    _gate, fn, args = CASES[name]()
    # The table donated, as the train step donates its parameters; the
    # caches, as the decode superstep donates them.
    donate = {"scatter_add_rows": (0,), "decode": (3, 4),
              "decode_grouped": (3, 4), "decode_ring": (3, 4),
              "decode_grouped_major": (3, 4),
              "kda_chunk": (5,),
              "kda_decode": (5,), "mla_decode": (2,)}.get(
                  name.split("-")[0], ())
    return jax.jit(fn, donate_argnums=donate).lower(*args).compile().as_text()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name):
    """Mosaic accepts the kernel at this shape, and the compiled
    program holds it (not an interpreted lowering)."""
    assert chip_smoke.has_mosaic_call(_compiled_text(name))


#: The kernel cases whose table the compiled program must not move: the
#: lane-major ones and D = 128.  (Wider row-major tables still pay a
#: reshape to the (P, 128) view: PERF.md §7.)
_IN_PLACE = sorted(
    n for n in CASES
    if "rows-" in n and "50257x1024" not in n
)


@pytest.mark.parametrize("name", _IN_PLACE)
def test_row_kernel_reads_the_table_where_it_lies(name):
    """Every view between the program's table argument and the kernel
    is a bitcast: no ``copy``, ``reshape``, ``transpose`` or fusion of
    the table's size in the optimised HLO."""
    _gate, _fn, args = CASES[name]()
    assert chip_smoke.table_sized_relayouts(
        _compiled_text(name), math.prod(args[0].shape)) == []


def _dlrm_step_text(monkeypatch, tables, rows, batch, chips):
    """The sparse train step at ``dlrm-random``'s widths (benchmark/
    configs/dlrm-random*.json: plain SGD, ``dlrm_strategy`` over
    ``chips`` devices), compiled for the described v5e."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.dlrm import DLRMConfig, build_dlrm, dlrm_strategy
    from flexflow_tpu.optim import SGDOptimizer
    from flexflow_tpu.runtime.executor import Executor

    devices = _four_chips()[:chips]
    # Steer the code that asks where it runs (.claude/skills/verify).
    monkeypatch.setattr(pk, "_interpret_default", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = FFConfig(batch_size=batch, sparse_embedding_updates=True)
    arch = DLRMConfig(
        sparse_feature_size=64, embedding_size=[rows] * tables,
        mlp_bot=[64, 512, 512, 64],
        mlp_top=[64 * (tables + 1), 1024, 1024, 1024, 1],
    )
    ex = Executor(build_dlrm(batch_size=batch, dlrm=arch, config=cfg),
                  strategy=dlrm_strategy(chips, arch), config=cfg,
                  optimizer=SGDOptimizer(lr=0.01), devices=devices)
    assert [op.name for op in ex._sparse_ops] == ["embeddings"]

    def placed(tree, shardings):
        return jax.tree.map(
            lambda a, sh: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=sh),
            tree, shardings)

    params, opt_state, state = ex._abstract_init()
    assert not jax.tree.leaves((opt_state, state))  # plain SGD, no op state
    return ex.train_step.lower(
        placed(params, ex.params_shardings()), opt_state, state,
        placed(ex._abstract_batch(), ex.batch_shardings()),
    ).compile().as_text()


@pytest.mark.parametrize(
    "tables, rows, batch, chips",
    [(8, 2000000, 1024, 1), (4, 1000000, 1024, 1), (8, 8000000, 4096, 4)],
    ids=["dlrm-random", "readme-4x1M", "dlrm-random-8m-c4"])
def test_sparse_dlrm_step_holds_no_table_sized_relayout(
        monkeypatch, tables, rows, batch, chips):
    """The relayout cannot come back unseen (PERF.md §6, PR 28: four
    of them were 76 of the one-chip step's 79 ms; PR 30: five were 88%
    of the four-chip step): besides the parameter, its bitcasts and the
    aliased scatter call, nothing in the compiled step has the element
    count of the table a chip holds (on four chips its ``T/c`` tables),
    and no all-gather assembles a table."""
    text = _dlrm_step_text(monkeypatch, tables, rows, batch, chips)
    assert chip_smoke.has_mosaic_call(text)
    for name in ("ff_gather_rows", "ff_scatter_add_rows"):
        assert re.search(rf"^\s*(ROOT )?%{name}\S* = .* custom-call\(",
                         text, re.M), name
    whole = tables * rows * 64
    assert chip_smoke.table_sized_relayouts(text, whole // chips) == []
    for elements in {whole, whole // chips}:
        assert chip_smoke.table_sized_relayouts(
            text, elements, ops=("all-gather", "all-gather-start")) == []


def test_table_sized_relayouts_names_what_pr28_removed():
    """The detector on the four instructions the parent's step held
    (PERF.md §6, PR 28), a fusion with a tuple result, and what must
    NOT count: the bitcast views and the aliased kernel call."""
    text = """
  %copy.28 = f32[8,2000000,64]{2,1,0:T(8,128)} copy(f32[8,2000000,64]{1,2,0:T(8,128)} %p)
  %reshape.32 = f32[8000000,128]{1,0:T(8,128)} reshape(%copy.28), metadata={op_name="x"}
  %reshape.33 = f32[8,2000000,64]{2,1,0:T(8,128)} reshape(%ff_scatter_add_rows.1)
  ROOT %copy.34 = f32[8,2000000,64]{1,2,0:T(8,128)} copy(%reshape.33)
  %fusion.9 = (f32[4]{0}, f32[8,2000000,64]{1,2,0:T(8,128)S(1)}) fusion(%a), kind=kLoop
  %bitcast = f32[8,64,2000000]{2,1,0:T(8,128)} bitcast(%p)
  %ff_scatter_add_rows.1 = f32[8,64,2000000]{2,1,0:T(8,128)} custom-call(%c, %bitcast)
  %fusion.2 = s32[8192]{0:T(1024)S(1)} fusion(%ids), kind=kLoop
"""
    found = chip_smoke.table_sized_relayouts(text, 8 * 2000000 * 64)
    assert [re.search(r"%(\S+) =", line).group(1) for line in found] == [
        "copy.28", "reshape.32", "reshape.33", "copy.34", "fusion.9"]


def test_supported_gates_match_the_compiler():
    """No ``*_supported`` gate says True for a shape the compiler
    refuses: every case above is admitted by its gate AND compiles,
    and so does every decode shape of the issue's (h, hd, dtype) grid
    that the gate admits (``flash_decode_supported`` once said True at
    every shape and Mosaic refused them all)."""
    for name, case in CASES.items():
        gate, _fn, _args = case()
        assert gate(), f"{name}: gate refuses a shape the smoke runs"
        _compiled_text(name)
    for h in (8, 16):
        for hd in (64, 128):
            for dtype in (F32, BF16):
                gate, fn, args = _decode(4, 512, h, hd, dtype)
                assert gate(), (h, hd, dtype)
                jax.jit(fn).lower(*args).compile()
    # The decode kernel blocks over whole 128-position lane tiles with
    # d_head on whole sublane tiles; what it refuses takes the einsum.
    assert not pk.flash_decode_supported((4, 1030, 8, 64), F32)
    assert not pk.flash_decode_supported((4, 512, 8, 8), BF16)
    # Grouped queries want d_head in halves of a lane tile (PR 51: the
    # matrix-unit body at 64), the delta rule's kernels whole lane tiles.
    assert pk.flash_decode_supported((4, 512, 2, 64), BF16, group=4)
    assert not pk.flash_decode_supported((4, 512, 2, 32), BF16, group=4)
    assert not pk.kda_supported(64, 64)
    # The latent kernels work on whole 128-position lane tiles and
    # whole 128-lane expert widths, and say so.
    assert not pk.mla_decode_supported((4, 160, 200), 128)
    assert not pk.flash_uneven_supported((1, 4, 200, 96), 64)
    assert not pk.grouped_matmul_supported(64, 32, BF16)
    # A masked chunk streams in whole 128-row blocks of queries and keys.
    assert not pk.attend_kept_supported((1, 4, 96, 24), (1, 4, 96, 24), 16, None)
    assert not pk.attend_kept_supported((1, 4, 128, 32), (1, 4, 200, 24), 16, 8)


#: The padded caches of the three cells whose decode step is
#: ``ff_flash_decode``: (slots, max_seq, cached heads, d_head), query
#: heads, the model width that gives them.
_DECODE_CELLS = {
    "gpt2m.serve.closed48": ((48, 1024, 16, 64), 16, 1024),
    "solar2.serve.closed32.p4k-31k": ((32, 32768, 8, 128), 64, 4096),
    "lfm2.serve.closed192.p256-2k": ((192, 3072, 8, 64), 32, 2048),
}


@pytest.mark.parametrize("cell", sorted(_DECODE_CELLS))
def test_decode_gate_and_granule_hold_for_the_cells(cell):
    """Both cells' caches pass ``flash_decode_supported`` and get a
    chunk of whole lane tiles that divides the cache and whose two
    rings leave the scoped VMEM the kernel asks for half empty."""
    (slots, seq, h, hd), heads, _ = _DECODE_CELLS[cell]
    group = heads // h
    assert pk.flash_decode_supported((slots, seq, h, hd), BF16, group)
    chunk = pk.flash_decode_chunk(seq, h, hd, BF16, group)
    assert chunk in ((512,) if group > 1 else (128, 256))
    assert seq % chunk == 0 and chunk % 128 == 0
    assert 2 * pk._DECODE_RING * h * hd * chunk * 2 <= pk._DECODE_VMEM_LIMIT // 2


@pytest.mark.parametrize("cell,c", [("gpt2m.serve.closed48", 1),
                                    ("gpt2m.serve.closed48", 2),
                                    ("solar2.serve.closed32.p4k-31k", 1),
                                    ("lfm2.serve.closed192.p256-2k", 1)])
def test_decode_fetch_block_is_the_kernels_granule(cell, c):
    """``serve_kv_fetch_pct`` rounds a slot's length up to
    ``Op.decode_fetch_block``: for ``MultiHeadAttention``, with and
    without grouped queries and with the heads split over ``c``, that
    is the chunk the kernel moves (``flash_decode_chunk`` at the local
    shape), and the whole cache where the kernel is off."""
    from flexflow_tpu.ops.attention import MultiHeadAttention
    from flexflow_tpu.ops.base import TensorSpec

    (slots, seq, h, hd), heads, d = _DECODE_CELLS[cell]
    x = TensorSpec("x", (slots, seq, d), BF16, ("n", "s", None))
    op = MultiHeadAttention("attn", x, heads, num_kv_heads=h, head_dim=hd)
    want = pk.flash_decode_chunk(seq, h // c, hd, BF16, heads // h)
    assert want >= 128
    assert op.decode_fetch_block(slots, seq, None, c) == want
    assert op.decode_fetch_block(slots, seq, True, c) == want
    assert op.decode_fetch_block(slots, seq, False, c) == seq


def test_sharded_decode_kernel_compiles_for_four_chips():
    """``chip_smoke.py --chips 4``'s ``serve4`` phase: ``flash_decode``
    under ``shard_map``, the batch on ``n`` and the cached heads on
    ``c``, its caches in HBM and aliased, lowers through Mosaic for the
    four described devices."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    mesh = Mesh(np.array(_four_chips()).reshape(2, 2), ("n", "c"))
    b, s, h, hd = 8, 512, 8, 64

    def sds(shape, dtype, spec):
        return jax.ShapeDtypeStruct(shape, dtype,
                                    sharding=NamedSharding(mesh, spec))

    q_spec, kv_spec = P("n", "c", None), P("n", None, "c", None)
    fn = jax.shard_map(
        lambda q, k1, v1, ck, cv, pos: pk.flash_decode(
            q, k1, v1, ck, cv, pos + 1, interpret=False),
        mesh=mesh,
        in_specs=(q_spec, q_spec, q_spec, kv_spec, kv_spec, P("n")),
        out_specs=(q_spec, kv_spec, kv_spec), check_vma=False)
    small, cache = sds((b, h, hd), F32, q_spec), sds((b, s, h, hd), F32, kv_spec)
    text = jax.jit(fn, donate_argnums=(3, 4)).lower(
        small, small, small, cache, cache,
        sds((b,), jnp.int32, P("n"))).compile().as_text()
    assert chip_smoke.has_kernel(text, "ff_flash_decode")


@pytest.mark.parametrize("slots,seq", [(16, 16384), (96, 4096)])
def test_latent_decode_reads_the_cache_where_it_lies(slots, seq):
    """No copy of the latent cache stands in front of the decode kernel
    or behind it: ``(slots, 576, max_seq)`` is the order the chip holds
    it in, and the kernel, which writes the step's column into it,
    hands back the buffer it was given.  (A ``(slots, max_seq, 576)``
    cache is held positions-major and copied into the kernel's order
    every call: 302 MB a layer a step at the cell's size.)"""
    text = _compiled_text(f"mla_decode-{slots}x32x576x{seq}-bf16")
    assert chip_smoke.table_sized_relayouts(
        text, slots * 576 * seq, chip_smoke.CACHE_RELAYOUT_OPS) == []
    cache = f"bf16[{slots},576,{seq}]{{2,1,0:T(8,128)(2,1)}}"
    layout = re.search(r"entry_computation_layout=\{(.*)\}\n", text).group(1)
    ins, outs = layout.split(")->(")
    assert ins.count(cache) == outs.count(cache) == 1
    # The cache is argument 2 and result 1, one buffer.
    assert re.search(r"input_output_alias=\{[^\n]*\{1\}: \(2, \{\}", text)
    assert "dynamic-update-slice" not in text


def test_solar_decode_superstep_holds_no_cache_or_state_sized_relayout(
        monkeypatch):
    """The scanned decode step of ``solar2.serve.closed32.p4k-31k`` over
    one period (grouped-query, delta) at the cell's widths, 8 slots:
    the KV cache is declared positions-last, since the chip would hold
    ``(max_seq, 8, 128)`` row-major and pay PR 32's two relayouts, and
    both it and the float32 recurrent state go from parameter to kernel
    to result where they lie; and no weight is concatenated a step."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import build_lm
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.serving import ServingExecutor

    dev = _four_chips()[0]
    monkeypatch.setattr(pk, "_interpret_default", lambda: False)
    from benchmark import common

    model = common.load_json(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "solar-open2-250b-l4e40.json")
    model.update(num_hidden_layers=2, vocab_size=1024)
    slots, seq = 8, 2048   # sizes no weight of the model shares
    cfg = FFConfig(batch_size=slots, compute_dtype="bfloat16")
    lm = build_lm(model, slots, seq, cfg)
    sex = ServingExecutor(lm, cfg, max_batch=slots, max_seq=seq,
                          buckets=(seq,), decode_kernel=True, device=dev)
    params, _opt, state = Executor(lm, config=cfg,
                                   devices=[dev])._abstract_init()
    placed = lambda a: _sds(a.shape, a.dtype)
    caches = sex._cache_tree(
        sex._cache_specs,
        lambda ce: _sds((slots,) + tuple(ce.shape), ce.dtype))
    assert caches["blk0_attn"]["k"].shape == (slots, 8, 128, seq)
    assert caches["blk1_kda"]["state"].shape == (slots, 64, 128, 128)
    vec = _sds((slots,), jnp.int32)
    compiled = sex.build_decode_superstep(8).lower(
        jax.tree.map(placed, params), jax.tree.map(placed, state), caches,
        vec, vec).compile()
    text = compiled.as_text()
    for name in ("ff_flash_decode", "ff_kda_decode", "ff_grouped_matmul"):
        assert chip_smoke.has_kernel(text, name), name
    assert chip_smoke.cache_or_state_relayouts(text, caches) == []
    layout = re.search(r"entry_computation_layout=\{(.*)\}\n", text).group(1)
    ins, outs = layout.split(")->(")
    kv = f"bf16[{slots},8,128,{seq}]{{3,2,1,0:T(8,128)(2,1)}}"
    st = f"f32[{slots},64,128,128]{{3,2,1,0:T(8,128)}}"
    assert ins.count(kv) == outs.count(kv) == 2
    assert ins.count(st) == outs.count(st) == 1
    assert "concatenate" not in "".join(
        l for l in text.splitlines() if "bf16[4096,24576]" in l)


def test_lfm2_decode_superstep_folds_its_heads_and_moves_no_cache(monkeypatch):
    """The scanned decode step of ``lfm2.serve.closed192.p256-2k`` over
    its first three layers (convolution, convolution, grouped-query
    attention over 8 cached heads of 64 under 32) at the cell's widths
    and slots: Mosaic takes the decode kernel's folded body (four cached
    heads a step, announced by the program's event) under the name the
    benchmark's metrics read, and both caches go from parameter to
    kernel to result positions-major, where they lie."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import build_lm
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.serving import ServingExecutor

    dev = _four_chips()[0]
    monkeypatch.setattr(pk, "_interpret_default", lambda: False)
    from benchmark import common

    model = common.load_json(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "configs",
        "lfm2-24b-a2b-l10.json")
    model.update(num_hidden_layers=3, vocab_size=1024)
    slots, seq = 192, 3072
    cfg = FFConfig(batch_size=slots, compute_dtype="bfloat16")
    lm = build_lm(model, slots, seq, cfg)
    sex = ServingExecutor(lm, cfg, max_batch=slots, max_seq=seq,
                          buckets=(seq,), decode_kernel=True, device=dev)
    assert sex.decode_heads_per_step() == {"decode_heads_per_step": 4}
    params, _opt, state = Executor(lm, config=cfg,
                                   devices=[dev])._abstract_init()
    placed = lambda a: _sds(a.shape, a.dtype)
    caches = sex._cache_tree(
        sex._cache_specs,
        lambda ce: _sds((slots,) + tuple(ce.shape), ce.dtype))
    assert caches["blk2_attn"]["k"].shape == (slots, seq, 8, 64)
    vec = _sds((slots,), jnp.int32)
    text = sex.build_decode_superstep(8).lower(
        jax.tree.map(placed, params), jax.tree.map(placed, state), caches,
        vec, vec).compile().as_text()
    for name in ("ff_flash_decode", "ff_grouped_matmul"):
        assert chip_smoke.has_kernel(text, name), name
    assert chip_smoke.cache_or_state_relayouts(text, caches) == []
    layout = re.search(r"entry_computation_layout=\{(.*)\}\n", text).group(1)
    ins, outs = layout.split(")->(")
    kv = f"bf16[{slots},{seq},8,64]{{1,3,2,0:T(8,128)(2,1)}}"
    assert ins.count(kv) == outs.count(kv) == 2


def test_solar_smoke_prefill_prepares_the_scan_inside_its_kernel(monkeypatch):
    """``chip_smoke.py``'s ``serve/solar`` prefill (the smoke preset: a
    bucket of 256 tokens, three delta layers of 2 heads of 128)
    compiled for the described chip.  Under every ``blk<i>_kda`` scope
    the scan is one ``ff_kda_chunk`` call and nothing of XLA's round it
    prepares its operands: no ``copy`` or ``transpose`` of a float32
    array of (tokens, heads, head_dim) elements (the parent moved q, k,
    v, g in and o out, five a layer), and no product with a dimension
    of ``_KDA_SUB`` = 16 rows (its batched triangular solve: thirty
    ``convolution``s)."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import SOLAR_OPEN2_SMOKE, build_lm
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.serving import ServingExecutor

    dev = _four_chips()[0]
    monkeypatch.setattr(pk, "_interpret_default", lambda: False)
    slots, seq = 4, 256
    cfg = FFConfig(batch_size=slots, compute_dtype="bfloat16")
    lm = build_lm(SOLAR_OPEN2_SMOKE, slots, seq, cfg)
    sex = ServingExecutor(lm, cfg, max_batch=slots, max_seq=seq,
                          buckets=(seq,), decode_kernel=True, device=dev)
    params, _opt, state = Executor(lm, config=cfg,
                                   devices=[dev])._abstract_init()
    placed = lambda a: _sds(a.shape, a.dtype)
    text = sex.build_prefill(seq).lower(
        jax.tree.map(placed, params), jax.tree.map(placed, state),
        _sds((1, seq), jnp.int32), _sds((), jnp.int32)).compile().as_text()
    lin = SOLAR_OPEN2_SMOKE["linear_attn_config"]
    delta = [f"blk{i}_kda" for i in range(SOLAR_OPEN2_SMOKE["num_hidden_layers"])
             if i not in SOLAR_OPEN2_SMOKE["gqa_layers"]]
    assert len(delta) == 3
    scoped = [l for l in text.splitlines()
              if re.search(r'op_name="[^"]*/blk\d+_kda/', l)]
    for name in delta:
        calls = [l for l in scoped if f"/{name}/" in l
                 and "custom_call_target=\"tpu_custom_call\"" in l]
        assert len(calls) == 1 and "%ff_kda_chunk" in calls[0], (name, calls)
    operand = seq * lin["num_heads"] * lin["head_dim"]
    moved = [l for l in scoped if re.search(r" (copy|transpose)\(", l)
             and any(math.prod(map(int, d.split(","))) == operand
                     for d in re.findall(r"f32\[([\d,]+)\]", l.split("(")[0]))]
    assert moved == []
    narrow = [l for l in scoped if re.search(r" (dot|convolution)\(", l)
              and re.search(rf"\[(\d+,)*{pk._KDA_SUB}(,\d+)*\]", l.split("(")[0])]
    assert narrow == []


def test_xing_smoke_programs_compile_for_the_chip(monkeypatch):
    """``chip_smoke.py``'s ``serve/xing`` programs at the smoke preset's
    widths, compiled for the described chip: the decode superstep and
    the prefill hold the three latent kernels round four hyper-connected
    streams, the latent cache goes from parameter to kernel to result
    where it lies, and the stream between the blocks is an activation
    (no entry parameter or result carries it)."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import XING4_SMOKE, build_lm
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.serving import ServingExecutor

    dev = _four_chips()[0]
    monkeypatch.setattr(pk, "_interpret_default", lambda: False)
    slots, seq = 4, 256
    cfg = FFConfig(batch_size=slots, compute_dtype="bfloat16")
    lm = build_lm(XING4_SMOKE, slots, seq, cfg)
    sex = ServingExecutor(lm, cfg, max_batch=slots, max_seq=seq,
                          buckets=(seq,), decode_kernel=True, device=dev)
    params, _opt, state = Executor(lm, config=cfg,
                                   devices=[dev])._abstract_init()
    placed = lambda a: _sds(a.shape, a.dtype)
    params, state = jax.tree.map(placed, params), jax.tree.map(placed, state)
    caches = sex._cache_tree(
        sex._cache_specs,
        lambda ce: _sds((slots,) + tuple(ce.shape), ce.dtype))
    assert sorted(caches) == ["blk0_attn", "blk1_attn", "blk2_attn"]
    vec = _sds((slots,), jnp.int32)
    step = sex.build_decode_superstep(8).lower(
        params, state, caches, vec, vec).compile().as_text()
    first = sex.build_prefill(seq).lower(
        params, state, _sds((1, seq), jnp.int32), _sds((), jnp.int32)
    ).compile().as_text()
    for text, kernels in ((step, ("ff_mla_decode", "ff_grouped_matmul")),
                          (first, ("ff_flash_fwd_uneven", "ff_grouped_matmul"))):
        for name in kernels:
            assert chip_smoke.has_kernel(text, name), name
    # One causal call a layer, over live blocks alone (one block here).
    assert _kernel_calls(first, "ff_flash_fwd_uneven") == 3
    assert sex.causal_blocks(seq) == dict(causal_blocks=1, causal_steps=1)
    assert chip_smoke.table_sized_relayouts(
        step, slots * 160 * seq, chip_smoke.CACHE_RELAYOUT_OPS) == []
    # The step's column is the kernel's to write: no update of XLA's on
    # a cache (the parent made one a slot a layer).
    assert [l for l in step.splitlines() if "dynamic-update-slice(" in l
            and f"bf16[{slots},160,{seq}]" in l] == []
    layout = re.search(r"entry_computation_layout=\{(.*)\}\n", step).group(1)
    assert f"[{slots},1,4,256]" not in layout and "hc_defect" not in layout
    assert layout.count(f"bf16[{slots},160,{seq}]") == 6


def _kernel_calls(compiled_text: str, name: str) -> int:
    """How many instructions of a compiled program call the Pallas
    kernel ``name`` (``chip_smoke.has_kernel``'s pattern, counted)."""
    return len(re.findall(rf"%{re.escape(name)}[.\d]* = ", compiled_text))


def _head_score_products(jaxpr, rows: int, stack: str = ""):
    """The matrix products of a traced program, outside any kernel and
    outside the selector's own scopes, whose float32 result is ``rows``
    query rows by at least ``2 * rows`` keys: a masked chunk's scores
    written out a head (``_attend_kept_heads``' first einsum)."""
    found = []
    for eqn in jaxpr.eqns:
        here = f"{stack}/{eqn.source_info.name_stack}"
        if eqn.primitive.name == "pallas_call":
            continue
        if eqn.primitive.name == "dot_general":
            out = eqn.outvars[0].aval
            if out.dtype == F32 and out.ndim >= 2 and out.shape[-2] == rows \
                    and out.shape[-1] >= 2 * rows \
                    and "ff_index" not in here and "ff_select" not in here:
                found.append((here, out.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _head_score_products(sub, rows, here)
    return found


def _kept_prefill(sex, seq, args, tmp_path, chunk):
    """The prefill program of ``seq`` rows built under a telemetry
    stream: ``(compiled text, its serving_program event)``, after the
    checks every selected prefill passes: the masked chunks' kernel is
    in the compiled text, no head's scores are written out, and the
    event says so with the walk's block counts."""
    import json

    from flexflow_tpu.runtime import telemetry

    with telemetry.Telemetry(directory=str(tmp_path)) as tel:
        fn = sex.build_prefill(seq)
    with open(tel.path) as f:
        (event,) = [e for e in map(json.loads, f)
                    if e["ev"] == "serving_program"]
    text = fn.lower(*args).compile().as_text()
    assert chip_smoke.has_kernel(text, "ff_attend_kept")
    assert _head_score_products(jax.make_jaxpr(fn)(*args).jaxpr, chunk) == []
    assert event["kept_kernel"] is True and event["bucket"] == seq
    assert 0 < event["kept_key_blocks"] <= event["kept_key_blocks_square"]
    assert {k: event[k] for k in event if k.startswith("kept_")} \
        == sex.kept_blocks(seq)
    return text, event


def test_keye_smoke_programs_compile_for_the_chip(monkeypatch, tmp_path):
    """``chip_smoke.py``'s ``serve/keye`` programs at the smoke preset's
    widths, compiled for the described chip: the prefill holds the
    streamed forward kernel (its leading ``topk`` rows) and the grouped
    product beside the masked chunks and no sort of a row of scores (its
    threshold is searched), the decode superstep the grouped
    product, a sort a layer (the top-k) and a row gather of K and of V;
    the three caches go from parameter to result where they lie, K and V
    a position a row, updated a slot at a time in place."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import KEYE_VL2_SMOKE, build_lm
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.serving import ServingExecutor

    dev = _four_chips()[0]
    monkeypatch.setattr(pk, "_interpret_default", lambda: False)
    slots, seq = 4, 512
    topk = KEYE_VL2_SMOKE["sa_config"]["topk"]
    cfg = FFConfig(batch_size=slots, compute_dtype="bfloat16")
    lm = build_lm(KEYE_VL2_SMOKE, slots, seq, cfg)
    sex = ServingExecutor(lm, cfg, max_batch=slots, max_seq=seq,
                          buckets=(seq,), decode_kernel=True, device=dev)
    params, _opt, state = Executor(lm, config=cfg,
                                   devices=[dev])._abstract_init()
    placed = lambda a: _sds(a.shape, a.dtype)
    params, state = jax.tree.map(placed, params), jax.tree.map(placed, state)
    caches = sex._cache_tree(
        sex._cache_specs,
        lambda ce: _sds((slots,) + tuple(ce.shape), ce.dtype))
    assert {e: c.shape for e, c in caches["blk1_attn"].items()} == {
        "k": (slots, seq, 256), "v": (slots, seq, 256), "idx": (slots, seq, 64)}
    vec = _sds((slots,), jnp.int32)
    step = sex.build_decode_superstep(8).lower(
        params, state, caches, vec, vec).compile().as_text()
    # 512 rows under a topk of 256 in chunks of 128: two masked chunks,
    # one run of width 512 = one key block each.
    first, event = _kept_prefill(
        sex, seq, (params, state, _sds((1, seq), jnp.int32),
                   _sds((), jnp.int32)), tmp_path, 128)
    assert (event["kept_key_blocks"], event["kept_key_blocks_square"]) == (2, 2)
    assert chip_smoke.has_kernel(step, "ff_grouped_matmul")
    assert not chip_smoke.has_kernel(step, "ff_flash_decode")
    for name in ("ff_flash_fwd_uneven", "ff_grouped_matmul"):
        assert chip_smoke.has_kernel(first, name), name
    # The prefill's threshold is searched, not sorted (PR 43): its only
    # sorts are the routers' (tokens, top-8).  The step still sorts a
    # slot's whole row for its indices.
    row_sorts = lambda text: [l for l in text.splitlines() if " sort(" in l
                              and re.search(rf"f32\[[0-9,]*\b{seq}\]", l)]
    assert row_sorts(first) == []
    assert len(row_sorts(step)) == KEYE_VL2_SMOKE["num_hidden_layers"]
    assert chip_smoke.cache_shaped_relayouts(step, caches) == []
    # The selection: K and V of topk rows a slot, gathered a layer.
    gathers = [l for l in step.splitlines() if " gather(" in l
               and f"bf16[{slots},{topk},256]" in l]
    assert len(gathers) == 2 * KEYE_VL2_SMOKE["num_hidden_layers"]
    layout = re.search(r"entry_computation_layout=\{(.*)\}\n", step).group(1)
    ins, outs = layout.split(")->(")
    kv = f"bf16[{slots},{seq},256]{{2,1,0:T(8,128)(2,1)}}"
    assert ins.count(kv) == outs.count(kv) == 2 * KEYE_VL2_SMOKE["num_hidden_layers"]


def test_axk2_smoke_programs_compile_for_the_chip(monkeypatch, tmp_path):
    """``chip_smoke.py``'s ``serve/axk2`` programs at the smoke preset's
    widths, compiled for the described chip: the prefill holds the
    streamed forward kernel (its leading ``index_topk`` rows) and the
    grouped product beside the masked chunk, the decode superstep the
    grouped product, a sort a layer (the top-k) and ONE row gather a layer
    (the latent rows, for every head); both caches go from parameter to
    result where they lie, a position a row of whole lane tiles."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import AXK2_SMOKE, build_lm
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.serving import ServingExecutor

    dev = _four_chips()[0]
    monkeypatch.setattr(pk, "_interpret_default", lambda: False)
    slots, seq = 4, 1024
    topk, layers = AXK2_SMOKE["index_topk"], AXK2_SMOKE["num_hidden_layers"]
    cfg = FFConfig(batch_size=slots, compute_dtype="bfloat16")
    lm = build_lm(AXK2_SMOKE, slots, seq, cfg)
    sex = ServingExecutor(lm, cfg, max_batch=slots, max_seq=seq,
                          buckets=(seq,), decode_kernel=True, device=dev)
    params, _opt, state = Executor(lm, config=cfg,
                                   devices=[dev])._abstract_init()
    placed = lambda a: _sds(a.shape, a.dtype)
    params, state = jax.tree.map(placed, params), jax.tree.map(placed, state)
    caches = sex._cache_tree(
        sex._cache_specs,
        lambda ce: _sds((slots,) + tuple(ce.shape), ce.dtype))
    # 128 + 32 values a position, filled up to two lane tiles.
    assert {e: c.shape for e, c in caches["blk1_attn"].items()} == {
        "ckr": (slots, seq, 256), "idx": (slots, seq, 64)}
    vec = _sds((slots,), jnp.int32)
    step = sex.build_decode_superstep(8).lower(
        params, state, caches, vec, vec).compile().as_text()
    # 1024 rows under an index_topk of 512 in chunks of 512: one masked
    # chunk, whose run is the whole bucket.
    first, event = _kept_prefill(
        sex, seq, (params, state, _sds((1, seq), jnp.int32),
                   _sds((), jnp.int32)), tmp_path, 512)
    assert (event["kept_key_blocks"], event["kept_key_blocks_square"]) == (2, 2)
    assert chip_smoke.has_kernel(step, "ff_grouped_matmul")
    assert not chip_smoke.has_kernel(step, "ff_mla_decode")
    for name in ("ff_flash_fwd_uneven", "ff_grouped_matmul"):
        assert chip_smoke.has_kernel(first, name), name
    row_sorts = lambda text: [l for l in text.splitlines() if " sort(" in l
                              and re.search(rf"f32\[[0-9,]*\b{seq}\]", l)]
    assert row_sorts(first) == []
    assert len(row_sorts(step)) == layers
    assert chip_smoke.cache_shaped_relayouts(step, caches) == []
    gathers = [l for l in step.splitlines() if " gather(" in l
               and f"bf16[{slots},{topk},256]" in l]
    assert len(gathers) == layers
    layout = re.search(r"entry_computation_layout=\{(.*)\}\n", step).group(1)
    ins, outs = layout.split(")->(")
    row = f"bf16[{slots},{seq},256]{{2,1,0:T(8,128)(2,1)}}"
    assert ins.count(row) == outs.count(row) == layers


def test_laguna_smoke_programs_compile_for_the_chip(monkeypatch):
    """``chip_smoke.py``'s ``serve/laguna`` programs at the smoke preset's
    widths, compiled for the described chip: the prefill holds the banded
    forward kernel (window layers) beside the causal one (full layers)
    and the grouped product, the decode superstep ``ff_flash_decode``
    over rings of 512 and full caches of 2048 positions at groups of 9
    and 6; every cache goes from parameter to result where it lies, the
    rings 512 positions long, and no XLA update or scatter touches one."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import LAGUNA_SMOKE, build_lm
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.serving import ServingExecutor

    dev = _four_chips()[0]
    monkeypatch.setattr(pk, "_interpret_default", lambda: False)
    slots, seq, w = 4, 2048, LAGUNA_SMOKE["sliding_window"]
    cfg = FFConfig(batch_size=slots, compute_dtype="bfloat16")
    lm = build_lm(LAGUNA_SMOKE, slots, seq, cfg)
    sex = ServingExecutor(lm, cfg, max_batch=slots, max_seq=seq,
                          buckets=(1280, seq), decode_kernel=True, device=dev)
    params, _opt, state = Executor(lm, config=cfg,
                                   devices=[dev])._abstract_init()
    placed = lambda a: _sds(a.shape, a.dtype)
    params, state = jax.tree.map(placed, params), jax.tree.map(placed, state)
    caches = sex._cache_tree(
        sex._cache_specs,
        lambda ce: _sds((slots,) + tuple(ce.shape), ce.dtype))
    assert [caches[f"blk{i}_attn"]["k"].shape[-1] for i in range(5)] == [
        seq, w, w, w, seq]
    assert [op.decode_fetch_block(slots, seq, True) for op in sex.attn_ops] \
        == [512] * 5
    vec = _sds((slots,), jnp.int32)
    step = sex.build_decode_superstep(8).lower(
        params, state, caches, vec, vec).compile().as_text()
    for name in ("ff_flash_decode", "ff_grouped_matmul"):
        assert chip_smoke.has_kernel(step, name), name
    for bucket in (1280, seq):
        first = sex.build_prefill(bucket).lower(
            params, state, _sds((1, bucket), jnp.int32), _sds((), jnp.int32)
        ).compile().as_text()
        for name in ("ff_flash_fwd_window", "ff_flash_fwd_uneven",
                     "ff_grouped_matmul"):
            assert chip_smoke.has_kernel(first, name), (bucket, name)
        # The two full layers' call, over live blocks alone: five blocks
        # of 256 at 1,280 rows, two of 1,024 at 2,048.
        assert _kernel_calls(first, "ff_flash_fwd_uneven") == 2
        live = {1280: 15, seq: 3}[bucket]
        assert sex.causal_blocks(bucket) == dict(causal_blocks=live,
                                                 causal_steps=live)
    assert chip_smoke.cache_shaped_relayouts(step, caches) == []
    assert [l for l in step.splitlines()
            if ("dynamic-update-slice(" in l or " scatter(" in l)
            and re.search(rf"bf16\[{slots},2,128,({w}|{seq})\]", l)] == []
    layout = re.search(r"entry_computation_layout=\{(.*)\}\n", step).group(1)
    ins, outs = layout.split(")->(")
    for positions, layers in ((w, 3), (seq, 2)):
        kv = f"bf16[{slots},2,128,{positions}]{{3,2,1,0:T(8,128)(2,1)}}"
        assert ins.count(kv) == outs.count(kv) == 2 * layers, (positions, layout)


def test_held_quarter_segment_sizes_its_rows_by_the_held_share(monkeypatch):
    """One prefill-sized segment of an expert layer that holds four of
    sixteen experts, compiled for the described chip (PR 45): no result
    is as long as the segment's assignments or as the rows that would
    take them all (the gathered rows, both products, the ``(T, k, d)``
    combine), the rows are ``held_rows_bound``'s, and the loop's body
    holds ``ff_grouped_matmul`` as a Mosaic call."""
    from flexflow_tpu.ops.base import TensorSpec
    from flexflow_tpu.ops.moe import MixtureOfExperts

    monkeypatch.setattr(pk, "_interpret_default", lambda: False)
    T, d, f, k, e, eh = 1024, 256, 128, 8, 16, 4
    op = MixtureOfExperts(
        "moe", TensorSpec("x", (1, T, d), BF16, ("n", "s", None)), e, f,
        top_k=k, dispatch="sorted", router="sigmoid", gated=True,
        activation="silu", shared_experts=1, held_experts=list(range(eh)))
    params = {n: _sds(s.shape, s.dtype) for n, s in op.param_specs().items()}
    text = jax.jit(lambda p, x: op._sorted_tokens(p, x, True)).lower(
        params, _sds((T, d), BF16)).compile().as_text()
    A = T * k
    rows, held_rows = (-(-(n + eh * 127) // 128) * 128
                       for n in (A, op.held_rows_bound(A)))
    assert (A, rows, held_rows) == (8192, 8704, 3584)
    wide = lambda n: [l for l in text.splitlines() if re.search(
        rf" = \(?\w+\[{n},({d}|{f})\]", l)]
    assert wide(A) == wide(rows) == [] and wide(held_rows)
    assert not re.search(rf" = \w+\[{T},{k},{d}\]", text)
    assert len(re.findall(r"%ff_grouped_matmul[.\d]* = ", text)) == 2
    assert chip_smoke.has_mosaic_call(text)


_CACHE = (48, 1024, 16, 64)
#: A cache as the chip stores it (positions along the lanes), and as a
#: row-major Mosaic operand wants it (hd 64 padded to a 128-lane tile).
_CHIP_ORDER = "bf16[48,1024,16,64]{1,3,2,0:T(8,128)(2,1)}"
_ROW_MAJOR = "bf16[48,1024,16,64]{3,2,1,0:T(8,128)(2,1)}"


def _gpt2_superstep(monkeypatch):
    """The decode superstep of ``gpt2m.serve.closed48`` at its widths
    (48 slots x 1024 positions x 16 heads of 64, bf16, K 8) over two
    blocks, caches donated, compiled for the described v5e."""
    from flexflow_tpu.config import FFConfig
    from flexflow_tpu.models.transformer import build_transformer_lm
    from flexflow_tpu.runtime.executor import Executor
    from flexflow_tpu.runtime.serving import ServingExecutor

    dev = _four_chips()[0]
    monkeypatch.setattr(pk, "_interpret_default", lambda: False)
    cfg = FFConfig(batch_size=48, compute_dtype="bfloat16")
    lm = build_transformer_lm(
        batch_size=48, seq_len=1024, vocab_size=1024, d_model=1024,
        num_heads=16, num_layers=2, config=cfg)
    sex = ServingExecutor(lm, max_batch=48, max_seq=1024, buckets=(1024,),
                          device=dev)
    params, _opt, state = Executor(lm, config=cfg,
                                   devices=[dev])._abstract_init()
    placed = lambda a: _sds(a.shape, a.dtype)
    caches = sex._cache_tree(
        sex._cache_specs, lambda ce: _sds((48,) + tuple(ce.shape), ce.dtype))
    assert {c.shape for c in jax.tree.leaves(caches)} == {_CACHE}
    vec = _sds((48,), jnp.int32)
    return sex.build_decode_superstep(8).lower(
        jax.tree.map(placed, params), jax.tree.map(placed, state), caches,
        vec, vec).compile()


def test_gpt2_decode_superstep_holds_no_cache_sized_relayout(monkeypatch):
    """The scanned decode step reads and writes the caches where they
    lie (PERF.md §6, PR 32: the parent's superstep copied every cache
    to row-major before the scan and back after it, 19.7% of the
    cell's device time, and held them lane-padded in between): no
    ``copy``, ``transpose``, ``scatter`` or fusion of a cache's size, no
    temporary, every cache ``{1,3,2,0}`` from parameter to result, and
    the kernel's cache operands are bitcasts of the loop's carry."""
    compiled = _gpt2_superstep(monkeypatch)
    text = compiled.as_text()
    assert chip_smoke.has_kernel(text, "ff_flash_decode")
    assert chip_smoke.table_sized_relayouts(
        text, math.prod(_CACHE), ops=chip_smoke.CACHE_RELAYOUT_OPS) == []
    assert _ROW_MAJOR not in text
    layout = re.search(r"entry_computation_layout=\{(.*)\}\n", text).group(1)
    params, result = layout.split(")->(")
    assert params.count(_CHIP_ORDER) == result.count(_CHIP_ORDER) == 4
    calls = re.findall(r"= \((.*?)\) custom-call\((.*?)\), custom_call_target="
                       r"\"tpu_custom_call\"", text)
    assert len(calls) == 2  # one a layer, inside the loop's body
    for outs, operands in calls:
        assert outs.count("bf16[48,16,64,1024]{3,2,1,0:T(8,128)(2,1)}") == 2
        views = re.sub(r"/\*.*?\*/", "", operands).split(", ")[-2:]
        assert all(v.startswith("%bitcast") for v in views), views
    assert compiled.memory_analysis().temp_size_in_bytes < math.prod(_CACHE)


def test_cache_sized_relayouts_names_what_pr32_removed():
    """The detector on what the parent's superstep held around and
    inside its scan (PERF.md §6, PR 32: the entry copies to row-major,
    the scatter that wrote the step's column, the exit copies back) and
    what must NOT count: the loop's carry, the bitcast views and the
    aliased kernel call."""
    text = """
  %copy.8 = bf16[48,1024,16,64]{3,2,1,0:T(8,128)(2,1)} copy(bf16[48,1024,16,64]{1,3,2,0:T(8,128)(2,1)} %caches__blk0_attn____k__.1)
  ROOT %scatter.33 = bf16[48,1024,16,64]{3,2,1,0:T(8,128)(2,1)} scatter(%param_0.531, %custom-call.23, %transpose.94), update_window_dims={1,2}
  %fusion.7 = bf16[48,1024,16,64]{3,2,1,0:T(8,128)(2,1)} fusion(%get-tuple-element.3, %p.2, %p.3), kind=kLoop, calls=%fused_computation.2
  %copy.14 = bf16[48,1024,16,64]{1,3,2,0:T(8,128)(2,1)} copy(%get-tuple-element.41)
  %while.2 = (s32[]{:T(128)}, bf16[48,1024,16,64]{1,3,2,0:T(8,128)(2,1)}) while(%tuple.59), condition=%cond, body=%body
  %bitcast.12 = bf16[48,16,64,1024]{3,2,1,0:T(8,128)(2,1)} bitcast(%get-tuple-element.5)
  %ff_flash_decode.1 = (bf16[48,64,16]{2,1,0:T(8,128)(2,1)S(1)}, bf16[48,16,64,1024]{3,2,1,0:T(8,128)(2,1)}, bf16[48,16,64,1024]{3,2,1,0:T(8,128)(2,1)}) custom-call(%lengths, %bitcast.12)
  %bitcast.126 = bf16[48,1024,16,64]{1,3,2,0:T(8,128)(2,1)} bitcast(%pallas_call.34)
"""
    found = chip_smoke.table_sized_relayouts(
        text, math.prod(_CACHE), ops=chip_smoke.CACHE_RELAYOUT_OPS)
    assert [re.search(r"%(\S+) =", line).group(1) for line in found] == [
        "copy.8", "scatter.33", "fusion.7", "copy.14"]


# -- chip_smoke.py, rehearsed on the CPU --------------------------------------

_TINY_LM = ("--vocab", "256", "--d-model", "32", "--heads", "2",
            "--layers", "2")
_TINY = chip_smoke.Sizes(
    alexnet=("-b", "4", "-i", "3", "--image-size", "67", "-ll:tpu", "1"),
    # seq 128: the smallest the flash kernel's gate takes.
    transformer=("-b", "2", "--seq", "128", *_TINY_LM, "-i", "3",
                 "-ll:tpu", "1"),
    dlrm=("-b", "16", "-i", "3", "--momentum", "0", "--wd", "0",
          "-ll:tpu", "1",
          "--arch-sparse-feature-size", "8",
          "--arch-embedding-size", "100-100-100-100",
          "--arch-mlp-bot", "8-16-8", "--arch-mlp-top", "40-16-1"),
    # 128 positions: the smallest the decode kernel's gate takes.
    serve=("--max-seq", "128", "--max-batch", "2", "--requests", "3",
           "--max-new", "6", *_TINY_LM),
    # 128 positions: the smallest the latent kernels' gates take.
    serve_latent=("--model-config", "deepseek-v3-tiny", "--max-seq", "128",
                  "--max-batch", "2", "--requests", "3", "--max-new", "6",
                  "--prompt-len", "20:60", "--buckets", "128"),
    serve_solar=("--model-config", "solar-open2-tiny", "--max-seq", "128",
                 "--max-batch", "2", "--requests", "3", "--max-new", "6",
                 "--prompt-len", "20:60", "--buckets", "128"),
    serve_xing=("--model-config", "xing4-tiny", "--max-seq", "128",
                "--max-batch", "2", "--requests", "3", "--max-new", "6",
                "--prompt-len", "20:60", "--buckets", "128"),
    # topk 16 under prompts of 40-100: the selector selects.
    serve_keye=("--model-config", "keye-vl2-tiny", "--max-seq", "128",
                "--max-batch", "2", "--requests", "3", "--max-new", "6",
                "--prompt-len", "40:100", "--buckets", "128"),
    # A window of 16 under prompts of 40-100: every ring wraps twice.
    serve_laguna=("--model-config", "laguna-tiny", "--max-seq", "128",
                  "--max-batch", "2", "--requests", "3", "--max-new", "6",
                  "--prompt-len", "40:100", "--buckets", "64,128"),
    # index_topk 16 under prompts of 40-100: the selector selects.
    serve_axk2=("--model-config", "axk2-tiny", "--max-seq", "128",
                "--max-batch", "2", "--requests", "3", "--max-new", "6",
                "--prompt-len", "40:100", "--buckets", "128"),
    # Prompts of 20-60 end inside the 128 bucket: the windows are taken
    # at the prompt's length.
    serve_lfm2=("--model-config", "lfm2-tiny", "--max-seq", "128",
                "--max-batch", "2", "--requests", "3", "--max-new", "6",
                "--prompt-len", "20:60", "--buckets", "128"),
    dlrm4=("-b", "16", "-i", "3", "--momentum", "0", "--wd", "0",
           "--arch-sparse-feature-size", "8",
           "--arch-embedding-size", "100-100-100-100",
           "--arch-mlp-bot", "8-16-8", "--arch-mlp-top", "40-16-1"),
    alexnet4=("-b", "4", "-i", "2", "--image-size", "67"),
    alexnet4_strategy=chip_smoke.FULL.alexnet4_strategy,
    transformer4=("-b", "4", "--seq", "128", *_TINY_LM, "-i", "2"),
    transformer4_mesh=("--dp", "2", "--tp", "2"),
    serve4_shard=("--shard", "2,2"),
)


@pytest.fixture
def on_a_pretend_chip(monkeypatch):
    """Stub what only a chip can answer.  The CPU lowers Pallas calls
    through the interpreter, so no compiled text here holds a Mosaic
    call; the real check is exercised by the AOT cases above."""
    monkeypatch.setattr(chip_smoke, "has_mosaic_call", lambda text: True)
    monkeypatch.setattr(chip_smoke, "has_kernel", lambda text, name: True)
    # ... and the CPU's XLA scatter is a fusion of the table's size.
    monkeypatch.setattr(chip_smoke, "table_sized_relayouts",
                        lambda text, elements, ops=(): [])
    # ... and the tiny presets' chunks of 8 rows are under the gate of
    # ``ff_attend_kept`` (whole 128-row blocks): the smoke's own sizes
    # are held to it by the two ``*_smoke_programs_compile`` cases above.
    monkeypatch.setattr(chip_smoke, "check_kept_kernel",
                        lambda phase, run: None)


def _phases(which):
    return dict(which(_TINY))


@pytest.mark.parametrize(
    "phase", ["native", "train/alexnet", "train/transformer", "train/dlrm",
              "serve", "serve/latent", "serve/solar", "serve/xing",
              "serve/keye", "serve/laguna", "serve/axk2", "serve/lfm2"])
def test_chip_smoke_one_chip_phase(phase, on_a_pretend_chip, capsys):
    """Each one-chip phase runs to its end at a tiny size: the apps'
    mains, the replayed loss trajectories, the sparse-vs-dense DLRM
    comparison, the four serve runs and the oracle token parity."""
    _phases(chip_smoke.one_chip_phases)[phase]()
    assert f"[{phase}" in capsys.readouterr().out


@pytest.mark.slow  # ~50 s: six app runs and their replays
def test_chip_smoke_four_chip_phases(on_a_pretend_chip, monkeypatch):
    """--chips 4's phases on four of the virtual devices: the README's
    layer-wise AlexNet strategy, the dp2 x tp2 transformer and the
    2,2-sharded server, each against one device."""
    four = jax.devices()[:4]
    monkeypatch.setattr(jax, "devices", lambda *a: four)
    failed = chip_smoke.run_phases(
        [p for p in chip_smoke.four_chip_phases(_TINY) if p[0] != "native"]
    )
    assert failed == []


def test_chip_smoke_refuses_without_a_tpu(capsys):
    """No accelerator: a non-zero exit and no result line."""
    with pytest.raises(SystemExit) as e:
        chip_smoke.main([])
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_failed_phase_is_a_nonzero_exit(monkeypatch, capsys):
    """A phase that raises fails the run, the later phases still run,
    and the result line is not printed."""
    ran = []

    def boom():
        raise RuntimeError("planted")

    monkeypatch.setattr(chip_smoke, "require_tpu", lambda: {
        "platform": "tpu", "kind": "stub", "count": 1})
    monkeypatch.setattr(chip_smoke, "one_chip_phases", lambda sz: [
        ("bad", boom), ("after", lambda: ran.append(True))])
    monkeypatch.setattr(common, "enable_compile_cache", lambda: "/nowhere")
    assert chip_smoke.main([]) == 1
    assert ran == [True]
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_tolerance_is_the_serving_suite_s():
    import test_serving

    assert chip_smoke.DECODE_TOL == test_serving.DECODE_TOL


# -- the compile-cache rule ---------------------------------------------------


@pytest.fixture
def cache_dir_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_env_set_sets_nothing(monkeypatch, cache_dir_config):
    """JAX_COMPILATION_CACHE_DIR set: jax reads it itself, the code
    sets no directory of its own."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    jax.config.update("jax_compilation_cache_dir", None)
    assert common.enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir is None


def test_compile_cache_env_unset_is_checkout_ffcache(monkeypatch,
                                                     cache_dir_config):
    """Unset: <checkout>/.ffcache — the path ffcompile.sh's launcher
    exports and .gitignore lists; never a temp name, pid or time."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(ROOT, ".ffcache")
    assert common.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert common.enable_compile_cache() == want  # stable across calls
