"""Compiled execution plans: lower a :class:`Layout` once into flat tables.

The per-slot paths in :mod:`repro.core.codegen` walk the layout with a
Python loop per (interval, slot, lane); a real LM layer bundle has
hundreds of decode units, so execution cost is dominated by interpreter
and launch overhead instead of bandwidth — the exact failure the paper's
single ``read_data`` module (one II=1 loop over bus words) avoids.  This
module compiles the layout *once* into numpy index tables so that
executing it is a handful of whole-buffer vectorized passes:

* :func:`pack_compiled` / :func:`unpack_compiled` — host-side pack and
  its inverse with zero per-lane Python loops.  Packing shifts every
  piece into word position at once, then ORs contributions into the
  destination words in *rank layers* (layer r holds each word's (r+1)-th
  contribution, so indices within a layer are unique and every pass is a
  conflict-free vectorized ``|=``); unpacking is a flat gather + funnel
  shift.
* :class:`KernelTable` — the static slot encoding consumed by the fused
  Pallas decode kernel (``repro.kernels.layout_decode.decode_layout_fused``):
  one ``(c_max, lanes)`` uint32 table holding ``bit_offset | width << 20``
  per decoded element per bus row, plus per-array gather indices that
  rearrange the kernel's row-major output grid into element streams.

**Element granularity.**  A program is lowered at a chosen *piece* width
per array (``elem_widths``).  ``None`` means one piece per element
(requires ``width <= 64``).  Model bundles schedule multi-element *units*
whose widths exceed 64 bits; lowering them at their natural sub-element
width (``BundleTensor.width_bits``) lets the same tables pack and decode
bundle data directly at element granularity — absorbing the per-unit
merge loop ``pack_bundle`` used to run, and making >64-bit-unit bundles
packable at all.

Programs contain **no array names** (indices only), so one program is
shared by every :meth:`Layout.rebind` of the same scheduling instance —
a :class:`~repro.core.iris.LayoutCache` hit returns a layout whose
``_exec_cache`` already holds the lowered program, and the lowering cost
is paid once per cache entry, not per consumer.

Bit conventions match :mod:`repro.core.codegen`: bus cycle = one row of
``m`` bits, element LSB at its bit offset, rows little-endian in bytes.
The uint64 word views below rely on the host being little-endian, like
the byte views in ``codegen._scatter_bits``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .layout import Layout
from .util import round_up as _round_up

#: Piece widths above this go to the host path instead of the Pallas
#: kernel (u32 funnel shifts decode at most 32-bit pieces).
KERNEL_MAX_WIDTH = 32

#: Kernel slot-table encoding: ``bit_offset | width << _TAB_WIDTH_SHIFT``.
_TAB_WIDTH_SHIFT = 20


@dataclasses.dataclass(eq=False)
class KernelTable:
    """Static per-row slot table for the fused Pallas decode kernel."""

    words32: int                 # u32 words per bus row
    lanes: int                   # table width: max decoded pieces per row
    tab: np.ndarray              # (c_max, lanes) uint32, 0 = empty lane
    #: (array_index, flat indices ``row * lanes + col`` in piece order)
    gathers: tuple[tuple[int, np.ndarray], ...]


@dataclasses.dataclass(eq=False)
class ExecProgram:
    """A lowered layout: flat destination tables plus the pack program.

    All tables are in *global piece order* (arrays concatenated in
    problem order, each array's pieces in element order).
    """

    m: int
    c_max: int
    row_bytes: int
    wpr: int                             # uint64 words per row
    elem_widths: tuple[int, ...]         # piece width per array
    piece_depths: tuple[int, ...]        # pieces per array
    piece_base: tuple[int, ...]          # prefix sums, len n_arrays + 1
    # index dtypes are downcast to int32 when the program fits (they
    # almost always do); shifts are uint8 — numpy promotes uint64 OP
    # uint8 to uint64, and the narrow tables halve index memory traffic
    word: np.ndarray                     # int[P] dest uint64-word index
    shift: np.ndarray                    # uint8[P] bit shift within word
    # pack program.  Contribution vector cv = [each piece's shifted lo
    # part (piece order), hi parts of word-straddling pieces (piece
    # order, grouped per array)].  Building cv is sequential; each rank
    # layer then ORs every word's (r+1)-th contribution into place —
    # word indices within a layer are unique, so the passes are
    # conflict-free vectorized ``|=``, and the single random-access pass
    # per layer (the cv gather) is the information-theoretic minimum for
    # the piece-order -> word-order permutation.
    hi_tabs: tuple[tuple[np.ndarray, np.ndarray], ...]
    # per array: (local piece idx int[h_i], shr uint8[h_i])
    hi_base: tuple[int, ...]             # prefix sums of h_i, len n+1
    pack_layers: tuple[tuple[np.ndarray, np.ndarray], ...]
    # per rank layer: (sel int (contribution ids), words int)
    n_contribs: int
    kernel: KernelTable
    host_arrays: tuple[int, ...]         # arrays with piece width > 32

    #: decode-side jit memo, keyed by (tile_rows, interpret) — filled by
    #: repro.kernels.layout_decode so one trace serves every decode of
    #: this layout signature
    jit_cache: dict = dataclasses.field(default_factory=dict)

    @property
    def n_pieces(self) -> int:
        return self.piece_base[-1]

    @property
    def n_pallas_calls(self) -> int:
        """Fused-decode kernel launches: 1, or 0 if everything is host-side."""
        return 1 if self.kernel.gathers else 0

    # ------------------------------------------------------------------
    # host execution (index space; named wrappers below)
    # ------------------------------------------------------------------
    def pack_indexed(self, data: list[np.ndarray]) -> np.ndarray:
        """Pack per-array piece vectors into the ``(c_max, m/8)`` buffer."""
        flat = np.zeros(self.c_max * self.wpr, dtype=np.uint64)
        n = self.n_pieces
        if len(self.pack_layers) == 1 and self.n_contribs == n:
            # no word is shared and nothing straddles: shift straight
            # into place, one pass per array, no contribution vector
            for i, a in enumerate(data):
                sl = slice(self.piece_base[i], self.piece_base[i + 1])
                flat[self.word[sl]] = a << self.shift[sl]
        else:
            cv = np.empty(self.n_contribs, dtype=np.uint64)
            for i, a in enumerate(data):
                sl = slice(self.piece_base[i], self.piece_base[i + 1])
                np.left_shift(a, self.shift[sl], out=cv[sl])
                loc, shr = self.hi_tabs[i]
                if loc.shape[0]:
                    cv[n + self.hi_base[i]:n + self.hi_base[i + 1]] = \
                        a[loc] >> shr
            sel0, words0 = self.pack_layers[0]
            flat[words0] = cv[sel0]      # rank 0 covers every used word
            for sel, words in self.pack_layers[1:]:
                flat[words] |= cv[sel]
        return flat.view(np.uint8).reshape(
            self.c_max, self.wpr * 8)[:, :self.row_bytes]

    def unpack_array(self, flat: np.ndarray, i: int) -> np.ndarray:
        """Gather array ``i``'s pieces from the flat uint64 word vector."""
        lo, hi = self.piece_base[i], self.piece_base[i + 1]
        w, sh = self.word[lo:hi], self.shift[lo:hi]
        ew = self.elem_widths[i]
        v = flat[w] >> sh
        straddle = sh > np.uint64(64 - ew)
        if straddle.any():
            # (64 - sh) & 63 is exact where straddle holds (sh >= 1 there)
            part = flat[np.minimum(w + 1, flat.shape[0] - 1)] \
                << ((np.uint64(64) - sh) & np.uint64(63))
            v |= np.where(straddle, part, np.uint64(0))
        if ew < 64:
            v &= np.uint64((1 << ew) - 1)
        return v

    def unpack_indexed(self, buf: np.ndarray,
                       arrays: tuple[int, ...] | None = None,
                       ) -> dict[int, np.ndarray]:
        flat = self.buffer_words64(buf)
        idxs = range(len(self.piece_depths)) if arrays is None else arrays
        return {i: self.unpack_array(flat, i) for i in idxs}

    # ------------------------------------------------------------------
    def buffer_words64(self, buf: np.ndarray) -> np.ndarray:
        """(c_max, m/8) uint8 rows -> flat little-endian uint64 words."""
        if buf.shape != (self.c_max, self.row_bytes):
            raise ValueError(
                f"buffer shape {buf.shape} != "
                f"({self.c_max}, {self.row_bytes})"
            )
        padded = np.zeros((self.c_max, self.wpr * 8), dtype=np.uint8)
        padded[:, :self.row_bytes] = buf
        return padded.view(np.uint64).reshape(-1)

    def buffer_words32(self, buf: np.ndarray) -> np.ndarray:
        """(c_max, m/8) uint8 rows -> (c_max, words32) uint32 rows."""
        if buf.shape != (self.c_max, self.row_bytes):
            raise ValueError(
                f"buffer shape {buf.shape} != "
                f"({self.c_max}, {self.row_bytes})"
            )
        padded = np.zeros((self.c_max, self.kernel.words32 * 4),
                          dtype=np.uint8)
        padded[:, :self.row_bytes] = np.asarray(buf, dtype=np.uint8)
        return padded.view(np.uint32)

    def stream_bit_offsets(self, i: int) -> np.ndarray:
        """Global bit offset of each of array ``i``'s pieces, in the
        flattened :meth:`buffer_words32` view.

        The u64 pack view pads each row to ``wpr * 8`` bytes while the
        u32 kernel view pads to ``words32 * 4``, so offsets must be
        rebuilt from (row, bit-within-row) rather than scaled from the
        u64 word index.  Returned as uint32 — one table entry addresses
        up to 2^32 stream bits (512 MiB), validated here.
        """
        lo, hi = self.piece_base[i], self.piece_base[i + 1]
        w = self.word[lo:hi].astype(np.int64)
        row, w_in_row = np.divmod(w, self.wpr)
        gbit = (row * (self.kernel.words32 * 32)
                + w_in_row * 64 + self.shift[lo:hi].astype(np.int64))
        if gbit.size and int(gbit.max()) + self.elem_widths[i] > (1 << 32):
            raise ValueError(
                "stream exceeds the 2^32-bit addressing range of the "
                "uint32 stream tables"
            )
        return gbit.astype(np.uint32)


@dataclasses.dataclass(eq=False)
class StreamTables:
    """Per-matmul operand tables for the stream-direct kernel.

    ``w_tab[kk, nn]`` / ``s_tab[gg, nn]`` hold the *global bit offset*
    (u32-word view, :meth:`ExecProgram.stream_bit_offsets`) of weight
    code ``(kk, nn)`` and scale ``(gg, nn)`` inside the packed stream.
    The kernel derives word index (``tab >> 5``) and shift (``tab & 31``)
    in registers; element width is static per operand (``bits`` / 16).
    """

    bits: int
    group_size: int
    w_tab: np.ndarray            # (K, N) uint32
    s_tab: np.ndarray            # (K // group_size, N) uint32
    #: the program the offsets index; kernels memoize their forms of
    #: these tables in its ``jit_cache`` under ``("stream", key, ...)``
    program: ExecProgram = dataclasses.field(repr=False)
    #: (weight array, scale array, K, N): names these tables in the program
    key: tuple[int, int, int, int] = dataclasses.field(repr=False)


def stream_matmul_tables(layout: Layout, weights: int | str,
                         shape: tuple[int, int], *,
                         scales: int | str, group_size: int,
                         elem_widths: tuple[int, ...] | None = None,
                         program: ExecProgram | None = None,
                         ) -> StreamTables:
    """Build :class:`StreamTables` for one ``(K, N)`` weight matrix.

    ``weights`` / ``scales`` name (or index) the layout arrays holding
    the row-major flattened weight codes and bf16 scale bit patterns —
    the flattening convention of ``repro.tree``.  Works for any piece
    width <= 32 (no lane-packing divisibility constraint), which is what
    lifts ``packed_matmul``'s ``SUPPORTED_BITS`` restriction.
    """
    prog = program if program is not None \
        else lower_exec(layout, elem_widths)
    names = [a.name for a in layout.problem.arrays]

    def _resolve(ref) -> int:
        if isinstance(ref, str):
            if ref not in names:
                raise KeyError(f"no array named {ref!r}")
            return names.index(ref)
        return int(ref)

    wi, si = _resolve(weights), _resolve(scales)
    k, n = shape
    bits = prog.elem_widths[wi]
    if bits > KERNEL_MAX_WIDTH:
        raise ValueError(
            f"weight piece width {bits} > {KERNEL_MAX_WIDTH}; "
            "stream-direct extraction is u32-register based"
        )
    if prog.elem_widths[si] != 16:
        raise ValueError(
            f"scale piece width {prog.elem_widths[si]} != 16 (bf16)"
        )
    if k % group_size:
        raise ValueError(f"K={k} not divisible by group_size={group_size}")
    if k * n > prog.piece_depths[wi]:
        raise ValueError(
            f"shape {shape} needs {k * n} weight pieces, array has "
            f"{prog.piece_depths[wi]}"
        )
    g = k // group_size
    if g * n > prog.piece_depths[si]:
        raise ValueError(
            f"shape {shape} needs {g * n} scale pieces, array has "
            f"{prog.piece_depths[si]}"
        )
    w_tab = prog.stream_bit_offsets(wi)[:k * n].reshape(k, n)
    s_tab = prog.stream_bit_offsets(si)[:g * n].reshape(g, n)
    return StreamTables(bits=bits, group_size=group_size,
                        w_tab=w_tab, s_tab=s_tab, program=prog,
                        key=(wi, si, k, n))


# ----------------------------------------------------------------------
# lowering
# ----------------------------------------------------------------------
def lower_exec(layout: Layout,
               elem_widths: tuple[int, ...] | None = None) -> ExecProgram:
    """Lower ``layout`` into an :class:`ExecProgram` (memoized per layout).

    ``elem_widths[i]`` is the piece width for array ``i`` — the
    granularity at which data enters ``pack`` and leaves ``unpack``.  It
    must divide the array's scheduled width and be <= 64.  ``None``
    lowers at whole-element granularity.

    The program is cached on the layout (``layout._exec_cache``) keyed by
    the resolved widths; :meth:`Layout.rebind` shares the cache dict, so
    every rebound copy handed out by a :class:`LayoutCache` hit sees the
    already-lowered program.
    """
    prob = layout.problem
    if elem_widths is None:
        key = tuple(a.width for a in prob.arrays)
    else:
        key = tuple(int(w) for w in elem_widths)
        if len(key) != len(prob.arrays):
            raise ValueError(
                f"elem_widths has {len(key)} entries for "
                f"{len(prob.arrays)} arrays"
            )
    cache = layout._exec_cache
    prog = cache.get(key)
    if prog is None:
        prog = _lower(layout, key)
        cache[key] = prog
    return prog


def _lower(layout: Layout, elem_widths: tuple[int, ...]) -> ExecProgram:
    prob = layout.problem
    if prob.m % 8 != 0:
        raise ValueError(f"bus width {prob.m} is not byte-aligned")
    for a, ew in zip(prob.arrays, elem_widths):
        if ew <= 0 or a.width % ew:
            raise ValueError(
                f"{a.name}: piece width {ew} does not divide width {a.width}"
            )
        if ew > 64:
            raise ValueError(
                f"{a.name}: piece width {ew} > 64; lower at a finer "
                "granularity (e.g. the bundle's element width)"
            )
    row_bytes = prob.m // 8
    wpr = -(-row_bytes // 8)
    c_max = layout.c_max
    subs = [a.width // ew for a, ew in zip(prob.arrays, elem_widths)]
    piece_depths = tuple(a.depth * s for a, s in zip(prob.arrays, subs))
    piece_base = (0, *np.cumsum(piece_depths).tolist())
    n_pieces = piece_base[-1]

    word = np.empty(n_pieces, dtype=np.int64)
    shift = np.empty(n_pieces, dtype=np.uint8)
    for iv in layout.intervals():
        rows = np.arange(iv.start_cycle, iv.start_cycle + iv.n_cycles)
        for (a, off, n), base in zip(iv.slots, iv.elem_base):
            w_elem, ew, s = prob.arrays[a].width, elem_widths[a], subs[a]
            # piece (c, k, j): cycle c, lane k, sub-element j
            c = np.arange(iv.n_cycles)[:, None, None]
            k = np.arange(n)[None, :, None]
            j = np.arange(s)[None, None, :]
            pid = piece_base[a] + (base + c * n + k) * s + j
            bits = off + k * w_elem + j * ew          # (1, n, s)
            word[pid] = rows[:, None, None] * wpr + (bits >> 6)
            shift[pid] = (bits & 63).astype(np.uint8)

    ewv = np.empty(n_pieces, dtype=np.int64)
    for i, ew in enumerate(elem_widths):
        ewv[piece_base[i]:piece_base[i + 1]] = ew
    hi_sel = np.flatnonzero(shift.astype(np.int64) + ewv > 64)

    # contribution order: [lo (piece order), hi (piece order)]; sort by
    # destination word and group by rank within each word
    cw = np.concatenate([word, word[hi_sel] + 1])
    n_contribs = cw.shape[0]
    perm = np.argsort(cw, kind="stable")
    sw = cw[perm]
    new_seg = np.concatenate([[True], sw[1:] != sw[:-1]])
    seg_starts = np.flatnonzero(new_seg)
    # rank of each sorted contribution within its destination word
    rank = np.arange(n_contribs) - seg_starts[np.cumsum(new_seg) - 1]
    # int32 indices where the program fits (halves index memory traffic)
    n_words = c_max * wpr
    idx_t = np.int32 \
        if max(n_words, n_contribs) < (1 << 31) else np.int64
    layers = []
    for r in range(int(rank.max()) + 1 if rank.size else 0):
        sel = rank == r
        layers.append((perm[sel].astype(idx_t), sw[sel].astype(idx_t)))
    hi_tabs = []
    hi_base = [0]
    for i in range(len(prob.arrays)):
        mask = (hi_sel >= piece_base[i]) & (hi_sel < piece_base[i + 1])
        loc = (hi_sel[mask] - piece_base[i]).astype(idx_t)
        shr = (64 - shift[hi_sel[mask]].astype(np.int64)).astype(np.uint8)
        hi_tabs.append((loc, shr))
        hi_base.append(hi_base[-1] + loc.shape[0])

    kernel, host = _lower_kernel_table(
        prob, elem_widths, piece_base, word, shift, wpr, c_max, row_bytes)
    return ExecProgram(
        m=prob.m, c_max=c_max, row_bytes=row_bytes, wpr=wpr,
        elem_widths=elem_widths, piece_depths=piece_depths,
        piece_base=piece_base, word=word.astype(idx_t),
        shift=shift, hi_tabs=tuple(hi_tabs), hi_base=tuple(hi_base),
        pack_layers=tuple(layers), n_contribs=n_contribs,
        kernel=kernel, host_arrays=host,
    )


def _lower_kernel_table(prob, elem_widths, piece_base, word, shift,
                        wpr, c_max, row_bytes,
                        ) -> tuple[KernelTable, tuple[int, ...]]:
    """Row-major slot encoding for the fused kernel.

    Kernel-eligible pieces (width <= 32) are sorted by (row, bit offset)
    and assigned dense per-row lane columns; ``tab[row, col]`` encodes
    ``bit_offset | width << 20`` (0 = empty).  The per-array gather
    indices invert the assignment: ``grid.ravel()[gathers[i]]`` is array
    ``i``'s piece stream.
    """
    if prob.m > (1 << _TAB_WIDTH_SHIFT):
        raise ValueError(
            f"bus width {prob.m} exceeds the kernel slot-table encoding"
        )
    kernel_arrays = tuple(
        i for i, ew in enumerate(elem_widths) if ew <= KERNEL_MAX_WIDTH)
    host_arrays = tuple(
        i for i, ew in enumerate(elem_widths) if ew > KERNEL_MAX_WIDTH)
    words32 = -(-row_bytes // 4)
    if not kernel_arrays:
        empty = KernelTable(words32=words32, lanes=0,
                            tab=np.zeros((c_max, 0), dtype=np.uint32),
                            gathers=())
        return empty, host_arrays

    ids = np.concatenate([
        np.arange(piece_base[i], piece_base[i + 1]) for i in kernel_arrays])
    rows = word[ids] // wpr
    bit_in_row = (word[ids] - rows * wpr) * 64 + shift[ids].astype(np.int64)
    order = np.lexsort((bit_in_row, rows))
    ids_s, rows_s, bits_s = ids[order], rows[order], bit_in_row[order]
    counts = np.bincount(rows_s, minlength=c_max)
    lanes = _round_up(max(int(counts.max()), 1), 128)
    starts = np.concatenate([[0], np.cumsum(counts)[:-1]])
    cols = np.arange(ids_s.shape[0]) - starts[rows_s]

    widths = np.empty(ids_s.shape[0], dtype=np.uint32)
    garr = np.full(piece_base[-1], -1, dtype=np.int64)
    garr[ids_s] = rows_s * lanes + cols
    for i in kernel_arrays:
        sel = (ids_s >= piece_base[i]) & (ids_s < piece_base[i + 1])
        widths[sel] = elem_widths[i]
    tab = np.zeros((c_max, lanes), dtype=np.uint32)
    tab[rows_s, cols] = bits_s.astype(np.uint32) \
        | (widths << _TAB_WIDTH_SHIFT)
    gathers = tuple(
        (i, garr[piece_base[i]:piece_base[i + 1]].astype(np.int32))
        for i in kernel_arrays)
    return KernelTable(words32=words32, lanes=lanes, tab=tab,
                       gathers=gathers), host_arrays


# ----------------------------------------------------------------------
# named host entry points
# ----------------------------------------------------------------------
def pack_compiled(layout: Layout, arrays: dict[str, np.ndarray], *,
                  elem_widths: tuple[int, ...] | None = None,
                  program: ExecProgram | None = None) -> np.ndarray:
    """Vectorized :func:`~repro.core.codegen.pack_arrays` (bit-identical).

    ``arrays[name]`` holds each array's piece codes at the program's
    granularity (= element codes when ``elem_widths`` is None).  Lowering
    happens once per layout; repeated packs reuse the cached program.
    """
    prog = program if program is not None \
        else lower_exec(layout, elem_widths)
    data: list[np.ndarray] = []
    for i, spec in enumerate(layout.problem.arrays):
        if spec.name not in arrays:
            raise KeyError(f"missing array {spec.name!r}")
        a = np.asarray(arrays[spec.name]).reshape(-1)
        if a.dtype != np.uint64:
            a = a.astype(np.uint64)
        if a.shape[0] != prog.piece_depths[i]:
            raise ValueError(
                f"{spec.name}: expected {prog.piece_depths[i]} elements, "
                f"got {a.shape[0]}"
            )
        ew = prog.elem_widths[i]
        if ew < 64 and (a >> np.uint64(ew)).any():
            raise ValueError(f"{spec.name}: codes overflow {ew} bits")
        data.append(a)
    return prog.pack_indexed(data)


def unpack_compiled(layout: Layout, buf: np.ndarray, *,
                    elem_widths: tuple[int, ...] | None = None,
                    program: ExecProgram | None = None,
                    ) -> dict[str, np.ndarray]:
    """Vectorized :func:`~repro.core.codegen.unpack_arrays` (bit-identical)."""
    prog = program if program is not None \
        else lower_exec(layout, elem_widths)
    out = prog.unpack_indexed(np.asarray(buf))
    names = [a.name for a in layout.problem.arrays]
    return {names[i]: v for i, v in out.items()}


# ----------------------------------------------------------------------
# device pack tables (the inverse of the KernelTable direction)
# ----------------------------------------------------------------------
def pack_kernel_tables(prog: ExecProgram,
                       ) -> tuple[np.ndarray, np.ndarray, int]:
    """Gather-only contribution tables for the fused device pack kernel.

    The host pack (:meth:`ExecProgram.pack_indexed`) scatters piece
    contributions into destination words; scatters are pathological on
    the XLA CPU backend, so the device kernel inverts the mapping at
    lowering time: for every destination u32 word (``words32`` per row,
    :meth:`ExecProgram.buffer_words32` view) we precompute the <= K
    source pieces that contribute to it and the shift each needs.

    Returns ``(src, scode, K)`` where ``src``/``scode`` are
    ``(c_max, words32 * K)`` int32 tables.  ``src`` indexes a flat
    piece-order stream vector with a zero sentinel at index 0 (entry 0 =
    empty contribution slot, piece ``p`` stored as ``p + 1``);
    ``scode >= 0`` means shift left, ``< 0`` shift right (the hi part of
    a u32-straddling piece).  The kernel computes, per word,
    ``OR_k shift(flat[src_k], scode_k)`` — pure gathers, rank layers
    vectorized across the whole tile.  Memoized on the program
    (``jit_cache``), so the one-time numpy build is paid once per layout
    signature and shared across :class:`LayoutCache` rebinds.
    """
    key = ("pack_tables",)
    cached = prog.jit_cache.get(key)
    if cached is not None:
        return cached
    kt = prog.kernel
    w32 = kt.words32
    if not kt.gathers:
        empty = (np.zeros((prog.c_max, 0), dtype=np.int32),
                 np.zeros((prog.c_max, 0), dtype=np.int32), 1)
        prog.jit_cache[key] = empty
        return empty
    ids = np.concatenate([
        np.arange(prog.piece_base[i], prog.piece_base[i + 1])
        for i, _g in kt.gathers])
    word = prog.word[ids].astype(np.int64)
    rows = word // prog.wpr
    bit = (word - rows * prog.wpr) * 64 + prog.shift[ids].astype(np.int64)
    widths = np.empty(ids.shape[0], dtype=np.int64)
    for i, _g in kt.gathers:
        sel = (ids >= prog.piece_base[i]) & (ids < prog.piece_base[i + 1])
        widths[sel] = prog.elem_widths[i]
    w0 = bit >> 5
    sh = bit & 31
    strad = sh + widths > 32
    # contribution list: (destination u32 word, source piece, shift code);
    # a straddling piece contributes twice, its hi part right-shifted
    gw = np.concatenate([rows * w32 + w0, (rows * w32 + w0 + 1)[strad]])
    src = np.concatenate([ids, ids[strad]])
    sc = np.concatenate([sh, sh[strad] - 32])
    order = np.argsort(gw, kind="stable")
    gw, src, sc = gw[order], src[order], sc[order]
    new_seg = np.concatenate([[True], gw[1:] != gw[:-1]])
    seg_starts = np.flatnonzero(new_seg)
    rank = np.arange(gw.shape[0]) - seg_starts[np.cumsum(new_seg) - 1]
    k = int(rank.max()) + 1 if rank.size else 1
    src_t = np.zeros(prog.c_max * w32 * k, dtype=np.int32)
    sc_t = np.zeros(prog.c_max * w32 * k, dtype=np.int32)
    src_t[gw * k + rank] = src + 1          # 0 = empty slot sentinel
    sc_t[gw * k + rank] = sc
    tables = (src_t.reshape(prog.c_max, w32 * k),
              sc_t.reshape(prog.c_max, w32 * k), k)
    prog.jit_cache[key] = tables
    return tables
