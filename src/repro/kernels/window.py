"""Windowed stream extraction: in-kernel gathers that stay in one lane row.

Both stream kernels (``stream_matmul``, ``kvcache.kernels.stream_attention``)
read fields straight out of a packed uint32 word stream.  Mosaic lowers a
data-dependent gather only as ``take_along_axis`` along the lanes of a
``(rows, 128)`` value whose index has the same shape; a free gather
across a whole stream does not lower.  So the stream is staged as
``(R, 128)`` word rows and every field is addressed by *which source row*
holds its low and high word plus its lane and shift.

An output tile is built from a short list of source rows per output row
(``srcs[u]``: row ``u`` of that list, one 128-word row per output row):
each field selects ``srcs[lo_u]`` at ``lane`` for its low word and
``srcs[hi_u]`` at ``lane + 1`` (wrapping into the next row) for the word
it straddles into.  The per-field *entry* packs those four numbers into
one uint32:

    shift | lane << 5 | lo_u << 12 | hi_u << 18

:func:`row_window_tables` builds entries plus the per-(row, 128-lane
chunk) list of distinct source rows for a 2-D table of global bit
offsets (the stream matmul's ``(K, N)`` operand tables);
:func:`window_extract` is the in-kernel funnel shift over those sources.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

#: lanes of one staged stream row (one VREG row of uint32 words)
LANES = 128
#: bits of an entry's ``lo_u`` / ``hi_u`` source-row index fields
_U_BITS = 6
#: most distinct source rows a 128-field chunk may draw from
MAX_SOURCES = 1 << _U_BITS


def split_offsets(gbit: np.ndarray, width: int
                  ) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Global bit offsets -> (low word, high word, shift).

    The high word equals the low word unless the field straddles a word
    boundary (``shift + width > 32``).
    """
    g = np.asarray(gbit, dtype=np.int64)
    lo = g >> 5
    sh = g & 31
    hi = np.where(sh + width > 32, lo + 1, lo)
    return lo, hi, sh


def encode_entries(lo_word: np.ndarray, sh: np.ndarray, lo_u: np.ndarray,
                   hi_u: np.ndarray) -> np.ndarray:
    """Pack (shift, lane, lo_u, hi_u) into the uint32 entry format."""
    if lo_u.size and max(int(lo_u.max()), int(hi_u.max())) >= MAX_SOURCES:
        raise ValueError(
            f"a 128-field chunk draws from more than {MAX_SOURCES} "
            "stream rows; the window entry format cannot address it")
    lane = lo_word & (LANES - 1)
    ent = sh | (lane << 5) | (lo_u << 12) | (hi_u << (12 + _U_BITS))
    return ent.astype(np.uint32)


def row_window_tables(gbit: np.ndarray, width: int, col_tile: int
                      ) -> tuple[np.ndarray, np.ndarray]:
    """Window tables for a ``(R, C)`` table of global bit offsets.

    The columns are cut into tiles of ``col_tile`` columns, each tile
    widened to whole 128-lane chunks (``fill = round_up(col_tile, 128)``;
    the lanes past a tile's columns and past ``C`` repeat column 0, a
    valid field).  Returns ``ent`` ``(R, n_tiles * fill)`` uint32
    entries and ``rows`` ``(R, n_tiles * fill / 128, U)`` int32: the
    distinct staged stream rows (``word >> 7``) each chunk of each row
    reads, padded by repeating its first row.  ``U`` is the largest such
    count — small, because a layout emits each array's elements in order
    across bus rows.
    """
    gbit = np.asarray(gbit)
    r, c = gbit.shape
    n_tiles = -(-c // col_tile)
    fill = -(-col_tile // LANES) * LANES
    n_ch = n_tiles * fill // LANES
    col = (np.arange(n_tiles)[:, None] * col_tile
           + np.arange(fill)[None, :])
    col = np.where((np.arange(fill)[None, :] < col_tile) & (col < c), col, 0)
    g = gbit.astype(np.int64)[:, col.reshape(-1)]
    lo, hi, sh = split_offsets(g, width)
    lo_row = (lo >> 7).reshape(r, n_ch, LANES)
    hi_row = (hi >> 7).reshape(r, n_ch, LANES)
    srt = np.sort(np.concatenate([lo_row, hi_row], axis=-1), axis=-1)
    first = np.ones_like(srt, dtype=bool)
    first[..., 1:] = srt[..., 1:] != srt[..., :-1]
    n_u = int(first.sum(axis=-1).max()) if srt.size else 1
    rank = np.cumsum(first, axis=-1) - 1
    rows = np.repeat(srt[..., :1], n_u, axis=-1)
    ii, jj, kk = np.nonzero(first)
    rows[ii, jj, rank[ii, jj, kk]] = srt[ii, jj, kk]
    lo_u = (rows[..., None, :] == lo_row[..., :, None]).argmax(-1)
    hi_u = (rows[..., None, :] == hi_row[..., :, None]).argmax(-1)
    ent = encode_entries(lo, sh, lo_u.reshape(r, -1), hi_u.reshape(r, -1))
    return ent, rows.astype(np.int32)


def window_extract(srcs, ent: jax.Array, width: int) -> jax.Array:
    """Funnel-shift ``width``-bit fields out of per-row source windows.

    ``srcs``: sequence of ``(R, 128)`` uint32 word rows; ``ent``:
    ``(R, 128)`` uint32 entries (module docstring).  Every gather is a
    lane gather within one row, the form Mosaic lowers.
    """
    sh = ent & jnp.uint32(31)
    lane = ((ent >> 5) & jnp.uint32(LANES - 1)).astype(jnp.int32)
    hlane = (lane + 1) & (LANES - 1)
    umask = jnp.uint32(MAX_SOURCES - 1)
    lo_u = (ent >> 12) & umask
    hi_u = (ent >> (12 + _U_BITS)) & umask
    lo = hi = None
    for u, src in enumerate(srcs):
        a = jnp.take_along_axis(src, lane, axis=1)
        b = jnp.take_along_axis(src, hlane, axis=1)
        lo = a if lo is None else jnp.where(lo_u == u, a, lo)
        hi = b if hi is None else jnp.where(hi_u == u, b, hi)
    v = lo >> sh
    # (32 - sh) & 31 is exact when sh > 0; sh == 0 contributes nothing
    hi_part = hi << ((jnp.uint32(32) - sh) & jnp.uint32(31))
    v = v | jnp.where(sh > 0, hi_part, jnp.uint32(0))
    mask = jnp.uint32((1 << width) - 1 if width < 32 else 0xFFFFFFFF)
    return v & mask


__all__ = ["LANES", "MAX_SOURCES", "encode_entries", "row_window_tables",
           "split_offsets", "window_extract"]
