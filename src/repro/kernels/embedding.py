"""Sparse embedding-row gather as a Pallas TPU kernel (paper §4.2).

The paper's Gather op — "extracts a sparse set of rows from a tensor,
colocated with the variable it reads" — done TPU-style: token ids are
scalar-prefetched into SMEM and drive the BlockSpec index_map, so each grid
step DMAs one aligned tile of ``ROWS`` table rows HBM->VMEM — the one
holding the token's row — and selects that row in registers. No one-hot
matmul, no full-table read: bytes moved = rows_touched x ROWS x d x
itemsize, independent of table size (the §6.2 "Sparse" curve's defining
property).

Why a tile and not one row: a TPU array is laid out in (8, 128) tiles, and
Mosaic refuses a block or DMA slice whose second-minor extent is not a
whole tile. The table is viewed as ``(V / ROWS, ROWS, d)`` — the same bytes
in the same tiled layout, so the view is free — and each block is one
whole ``(ROWS, d)`` tile.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ROWS = 8


def _gather_kernel(ids_ref, tile_ref, o_ref):
    off = ids_ref[pl.program_id(0)] % ROWS
    # select the row as integer bits: exact for every value, -0.0 included
    bits = jax.lax.bitcast_convert_type(
        tile_ref[...].astype(jnp.float32), jnp.int32)          # (ROWS, d)
    row = jax.lax.broadcasted_iota(jnp.int32, bits.shape, 0)
    sel = jnp.sum(jnp.where(row == off, bits, 0), axis=0, keepdims=True)
    o_ref[...] = jax.lax.bitcast_convert_type(
        sel, jnp.float32).astype(o_ref.dtype)


def gather(table, ids, *, interpret=False):
    """table: (V, d); ids: int32 of any shape -> (*ids.shape, d)."""
    shape = ids.shape
    flat = ids.reshape(-1).astype(jnp.int32)
    T = flat.shape[0]
    V, d = table.shape
    if V % ROWS:
        # model tables are padded to a multiple of 256 rows; only odd test
        # sizes pay for this copy
        table = jnp.pad(table, ((0, -V % ROWS), (0, 0)))
    tiles = table.reshape(-1, ROWS, d)

    out = pl.pallas_call(
        _gather_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(T,),
            in_specs=[pl.BlockSpec((None, ROWS, d),
                                   lambda i, ids: (ids[i] // ROWS, 0, 0))],
            out_specs=pl.BlockSpec((None, 1, d), lambda i, ids: (i, 0, 0)),
        ),
        out_shape=jax.ShapeDtypeStruct((T, 1, d), table.dtype),
        interpret=interpret,
    )(flat, tiles)
    return out.reshape(*shape, d)
