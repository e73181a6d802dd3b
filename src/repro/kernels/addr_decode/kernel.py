"""Pallas TPU kernel: batched XOR-folded Skylake address decode.

Paper Sec. 4 / Fig. 6a: fidelity needs the reverse-engineered XOR
address mapping, applied to *every* memory request — in a vectorized
simulator that is a bulk bit-twiddling pass over millions of cache-line
indices per simulated window.  The kernel packs all five DRAM
coordinates into one uint32 per line (row 17b | col 7b | bank 4b |
rank 1b | channel 3b), keeping the output lane-aligned and letting the
caller unpack only the fields it needs.

Tiling: the 1-D stream is zero-padded and reshaped to (rows, 1024)
with rows a multiple of 8; each grid step takes an (8, 1024) block, the
(8 sublanes x 128 lanes) tiling the TPU compiler requires of the last
two block dimensions.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

BLOCK = 1024        # lanes per row
ROWS = 8            # sublanes per grid step

# packed-field shifts / widths
CH_SH, CH_W = 0, 3
RANK_SH, RANK_W = 3, 1
BANK_SH, BANK_W = 4, 4
COL_SH, COL_W = 8, 7
ROW_SH, ROW_W = 15, 17


def _bit(x, i):
    return (x >> jnp.uint32(i)) & jnp.uint32(1)


def _decode_kernel(line_ref, out_ref):
    line = line_ref[...].astype(jnp.uint32)
    mc = _bit(line, 0) ^ _bit(line, 6) ^ _bit(line, 11) ^ _bit(line, 17)
    ch3 = ((line >> 1) ^ (line >> 7) ^ (line >> 13) ^ (line >> 19)) % 3
    ch = mc * 3 + ch3
    bg0 = _bit(line, 2) ^ _bit(line, 12)
    bg1 = _bit(line, 3) ^ _bit(line, 14)
    ba0 = _bit(line, 4) ^ _bit(line, 15)
    ba1 = _bit(line, 5) ^ _bit(line, 16)
    bank = bg0 | (bg1 << 1) | (ba0 << 2) | (ba1 << 3)
    rank = _bit(line, 8) ^ _bit(line, 18)
    col = (line ^ (line >> 9)) % jnp.uint32(128)
    row = (line >> 9) & jnp.uint32(0x1FFFF)
    out_ref[...] = (ch
                    | (rank << RANK_SH)
                    | (bank << BANK_SH)
                    | (col << COL_SH)
                    | (row << ROW_SH)).astype(jnp.uint32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def decode_packed(lines, *, interpret: bool = False):
    """Decode (N,) uint32 cache-line indices -> (N,) packed coordinates.

    ``interpret=True`` runs the Pallas interpreter (CPU callers).
    """
    n = lines.shape[0]
    tile = ROWS * BLOCK
    n_pad = -(-n // tile) * tile
    x = jnp.pad(lines.astype(jnp.uint32), (0, n_pad - n))
    x = x.reshape(n_pad // BLOCK, BLOCK)
    spec = pl.BlockSpec((ROWS, BLOCK), lambda i: (i, 0))
    out = pl.pallas_call(
        _decode_kernel,
        grid=(n_pad // tile,),
        in_specs=[spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((n_pad // BLOCK, BLOCK), jnp.uint32),
        interpret=interpret,
    )(x)
    return out.reshape(n_pad)[:n]


def unpack(packed):
    """Packed uint32 -> (channel, rank, bank, row, col) int32 fields."""
    p = packed.astype(jnp.uint32)
    field = lambda sh, w: ((p >> jnp.uint32(sh))
                           & jnp.uint32((1 << w) - 1)).astype(jnp.int32)
    return (field(CH_SH, CH_W), field(RANK_SH, RANK_W),
            field(BANK_SH, BANK_W), field(ROW_SH, ROW_W),
            field(COL_SH, COL_W))
