"""Pallas TPU kernels for the framework's elementwise server-update paths.

Two kernels (reference analogs: the server's FTRLEntry update loop —
HOT LOOP #2 of the async-SGD path — and filter/fixing_float.h's
randomized rounding):

- ``ftrl_delta_pallas``: the fused FTRL-proximal delta over gathered
  rows. One VMEM pass computes w(z, n), sigma, and both deltas — no f32
  intermediates spill to HBM between the ~10 elementwise ops.
- ``quantize_stochastic_pallas``: int8/int16 fixed-point quantization
  with hardware-PRNG stochastic rounding (the DCN codec's device path).

Both are parity-checked against the jnp implementations: CPU tests run
them in interpret mode, chip_smoke.py compiles them with Mosaic on the
chip. Asking for a kernel on a backend that cannot run it raises.

Layout note: tables are (rows, vdim); the kernels flatten to (M, 128)
lanes and pad the tail, because the VPU wants a 128-wide last dimension
and vdim is often 1 (sparse LR) — tiling over rows alone would waste
127/128 lanes.

No fused gather->update->scatter push kernel lives here: Mosaic only
DMAs HBM slices whose minor dimension is a multiple of 128 lanes, so a
per-row copy out of a (rows, vdim) table with vdim 1 or 64 does not
compile (PERF.md, Findings, PR 21). A future one must move whole
128-lane rows and resolve touched rows that share one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

_LANES = 128
_SUBLANES = 8
# rows per grid step: VMEM is ~16 MiB scoped; 5 live (TILE_M, 128) f32
# refs at 1024 rows = 2.5 MiB, leaving room for double-buffered pipelining
_TILE_M = 1024


def _pad_to_tiles(x: jax.Array, row_multiple: int = _SUBLANES) -> tuple[jax.Array, int]:
    """Flatten to 1-D and pad so it reshapes to (M, 128) with
    M % row_multiple == 0 (grids tile rows in row_multiple chunks)."""
    flat = x.reshape(-1)
    n = flat.shape[0]
    tile = _LANES * row_multiple
    padded = (n + tile - 1) // tile * tile
    if padded != n:
        flat = jnp.pad(flat, (0, padded - n))
    return flat.reshape(-1, _LANES), n


def _tiled(x: jax.Array) -> tuple[jax.Array, int, int, int]:
    """Pad + reshape to (M, 128) and pick a row tiling: small arrays run
    as one block; large ones pad M to a _TILE_M multiple and grid over
    row tiles (an ungridded call would stage the WHOLE array into VMEM
    and OOM its ~16 MiB scoped limit on real hardware)."""
    row_mult = _TILE_M if x.size > _TILE_M * _LANES else _SUBLANES
    mat, count = _pad_to_tiles(x, row_mult)
    tile_m = min(_TILE_M, mat.shape[0])
    return mat, count, tile_m, mat.shape[0] // tile_m


def _unpad(mat: jax.Array, n: int, shape) -> jax.Array:
    return mat.reshape(-1)[:n].reshape(shape)


def _ftrl_delta_kernel(z_ref, n_ref, g_ref, dz_ref, dn_ref, *, alpha, beta, l1, l2):
    z = z_ref[:]
    n = n_ref[:]
    g = g_ref[:]
    # lazy weight w(z, n)
    shrunk = jnp.sign(z) * jnp.maximum(jnp.abs(z) - l1, 0.0)
    denom = (beta + jnp.sqrt(n)) / alpha + l2
    w = -shrunk / denom
    g2 = g * g
    sigma = (jnp.sqrt(n + g2) - jnp.sqrt(n)) / alpha
    dz_ref[:] = g - sigma * w
    dn_ref[:] = g2


@functools.partial(jax.jit, static_argnames=("alpha", "beta", "l1", "l2"))
def ftrl_delta_pallas(
    z: jax.Array,
    n: jax.Array,
    g: jax.Array,
    *,
    alpha: float,
    beta: float,
    l1: float,
    l2: float,
) -> tuple[jax.Array, jax.Array]:
    """Fused FTRL delta (dz, dn) over row slices of any shape."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    zm, count, tile_m, grid = _tiled(z)
    nm, _, _, _ = _tiled(n)
    gm, _, _, _ = _tiled(g)
    kernel = functools.partial(
        _ftrl_delta_kernel, alpha=alpha, beta=beta, l1=l1, l2=l2
    )
    row_block = pl.BlockSpec(
        (tile_m, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM
    )
    dz, dn = pl.pallas_call(
        kernel,
        grid=(grid,),
        out_shape=(
            jax.ShapeDtypeStruct(zm.shape, zm.dtype),
            jax.ShapeDtypeStruct(nm.shape, nm.dtype),
        ),
        in_specs=[row_block, row_block, row_block],
        out_specs=(row_block, row_block),
    )(zm, nm, gm)
    return _unpad(dz, count, z.shape), _unpad(dn, count, n.shape)


def _quantize_kernel(seed_ref, params_ref, x_ref, q_ref, *, levels):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    # per-grid-step seed, decorrelated across calls: seed+1 must not
    # reproduce this call's tile streams shifted by one (callers pass
    # consecutive per-step seeds)
    pltpu.prng_seed(seed_ref[0] * pl.num_programs(0) + pl.program_id(0))
    lo = params_ref[0]
    scale = params_ref[1]
    t = (x_ref[:] - lo) / scale  # in [0, levels]
    floor = jnp.floor(t)
    frac = t - floor
    bits = pltpu.bitcast(pltpu.prng_random_bits(x_ref.shape), jnp.uint32)
    # uniform in [0, 1) from the top 24 bits (fits in int32, which Mosaic
    # can cast to float32; a direct uint32->float32 cast is unsupported)
    top24 = pltpu.bitcast(bits >> jnp.uint32(8), jnp.int32)
    u = top24.astype(jnp.float32) * (1.0 / (1 << 24))
    q = floor + (u < frac).astype(jnp.float32)
    q_ref[:] = (q - levels // 2).astype(q_ref.dtype)


@functools.partial(jax.jit, static_argnames=("num_bytes",))
def quantize_stochastic_pallas(
    seed: jax.Array, x: jax.Array, num_bytes: int = 1
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Device-side fixed-point encode: (q, lo, scale). Hardware PRNG does
    the unbiased rounding (ref: fixing_float randomized rounding). The
    min/max reduction happens outside the kernel (on the unpadded array,
    fused by XLA); the kernel does the bandwidth-heavy rounding pass."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    levels = (1 << (8 * num_bytes)) - 1
    dtype = jnp.int8 if num_bytes == 1 else jnp.int16
    lo = jnp.min(x).astype(jnp.float32)
    hi = jnp.max(x).astype(jnp.float32)
    scale = jnp.maximum(hi - lo, 1e-30) / levels
    xm, count, tile_m, grid = _tiled(x)
    kernel = functools.partial(_quantize_kernel, levels=levels)
    q = pl.pallas_call(
        kernel,
        grid=(grid,),
        out_shape=jax.ShapeDtypeStruct(xm.shape, dtype),
        in_specs=[
            pl.BlockSpec((1,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((2,), lambda i: (0,), memory_space=pltpu.SMEM),
            pl.BlockSpec((tile_m, _LANES), lambda i: (i, 0),
                         memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec(
            (tile_m, _LANES), lambda i: (i, 0), memory_space=pltpu.VMEM
        ),
    )(
        jnp.asarray([seed], dtype=jnp.int32),
        jnp.stack([lo, scale]),
        xm,
    )
    return _unpad(q, count, x.shape), lo, scale


def tpu_available() -> bool:
    return jax.devices()[0].platform == "tpu"


def require_tpu(what: str) -> None:
    """Raise unless the default backend can run a compiled Pallas TPU
    kernel (CPU tests enter ``pltpu.force_tpu_interpret_mode`` and call
    the kernels directly instead)."""
    if not tpu_available():
        raise RuntimeError(
            f"{what} needs a TPU backend, found "
            f"{jax.devices()[0].platform!r}; use the jnp path, or call the "
            "kernel under force_tpu_interpret_mode"
        )
