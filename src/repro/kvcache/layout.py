"""Iris-planned KV-cache stream layouts: per-page bundles and tables.

The KV-cache is the first *mutable* Iris-planned stream in the repo.
The growth model is paged: a slot's cache is a sequence of fixed
``page_tokens``-sized token pages, each packed with the same per-page
layout.  The layout problem depends only on
``(page_tokens, n_kv_heads, head_dim, bits, m)`` — never on sequence
length — so the scheduling instance is planned once, appends never
re-plan, and every layer / slot / page rebinds the one cached layout
exactly like the uniform weight stacks in :func:`repro.api.plan_layer_stack`
(which is the planning entry this module routes through).

Three table families are derived from the lowered
:class:`~repro.core.exec_plan.ExecProgram` and memoized on its
``jit_cache`` (shared across :class:`~repro.core.iris.LayoutCache`
rebinds):

* :func:`append_tables` — the write path.  Inverts
  :func:`~repro.core.exec_plan.pack_kernel_tables` per *token*: each
  destination u32 word knows its <= K contributing pieces, their shift
  codes, the precomputed bit mask each contribution covers, and which
  in-page token owns it.  Appending token ``t`` is then a masked
  read-modify-write ``new = (old & ~mask_t) | value_t`` over the page
  words — the ``pack_layout_fused`` gather/shift/OR structure, restricted
  to one token's bits.
* :func:`page_stream_tables` — per-page global bit offsets of every
  K/V code and scale (the :class:`~repro.core.exec_plan.StreamTables`
  convention: word index ``tab >> 5``, shift ``tab & 31``).
* :func:`full_stream_tables` — the per-page tables broadcast across
  ``n_pages`` by adding each page's bit stride: one flat (smax, ...)
  table over a slot's concatenated pages (the host oracles' input).
* :func:`page_window_tables` — the attention kernel's form: page-local
  window entries (:mod:`repro.kernels.window`) that every page shares.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from repro.core.exec_plan import ExecProgram, pack_kernel_tables
from repro.core.packing import BundleTensor
from repro.kernels.window import LANES, encode_entries, split_offsets

#: bundle array order (index into the lowered program's arrays)
KV_ARRAYS = ("kv/k", "kv/k_scales", "kv/v", "kv/v_scales")


def kv_bundle(cfg, bits: int, page_tokens: int) -> list[BundleTensor]:
    """The Iris bundle for one KV-cache token page.

    ``cfg`` is any object with ``n_kv_heads`` / ``head_dim``.  Codes are
    quantized per head-vector (one bf16 scale per (token, head) — the
    group always divides, so non-power-of-two head dims and any
    ``2 <= bits <= 8`` pack).  K feeds the score matmul before V feeds
    the output matmul, hence the two dataflow stages.
    """
    if not 2 <= bits <= 8:
        raise ValueError(f"kv bits must be in [2, 8], got {bits}")
    if page_tokens <= 0:
        raise ValueError(f"page_tokens must be positive, got {page_tokens}")
    hkv, hd = int(cfg.n_kv_heads), int(cfg.head_dim)
    n_codes = page_tokens * hkv * hd
    n_scales = page_tokens * hkv
    return [
        BundleTensor("kv/k", bits, n_codes, 0),
        BundleTensor("kv/k_scales", 16, n_scales, 0),
        BundleTensor("kv/v", bits, n_codes, 1),
        BundleTensor("kv/v_scales", 16, n_scales, 1),
    ]


def plan_kv_stack(cfg, *, bits: int, page_tokens: int,
                  n_layers: int | None = None, m: int = 512,
                  mode: str = "auto", cache=None):
    """Plan the per-page KV layout for every layer of a model.

    Routed through :func:`repro.api.plan_layer_stack` with the KV bundle
    substituted for the weight bundle, so the per-head layouts share the
    process-wide :class:`~repro.core.iris.LayoutCache`: one scheduler run
    (zero on a warm cache) plus ``n_layers - 1`` rebinds, with the
    ``scheduler_runs`` / ``cache_hits`` accounting callers assert on to
    prove appends never re-plan.
    """
    from repro.api import DEFAULT_CACHE, plan_layer_stack  # lazy

    if cache is None:
        cache = DEFAULT_CACHE
    return plan_layer_stack(
        cfg, None, m=m, n_layers=n_layers, mode=mode, cache=cache,
        bundle=kv_bundle(cfg, bits, page_tokens))


# ----------------------------------------------------------------------
# write-path tables
# ----------------------------------------------------------------------
@dataclasses.dataclass(eq=False)
class AppendTables:
    """Per-word contribution tables for the token-masked page pack.

    All tables are ``(c_max, words32, K)``; ``src`` indexes a flat
    piece-order vector with a zero sentinel at index 0 (piece ``p``
    stored as ``p + 1``), ``scode >= 0`` shifts left / ``< 0`` right
    (:func:`~repro.core.exec_plan.pack_kernel_tables` conventions),
    ``tok`` is the in-page token owning the contribution (-1 = empty or
    residual padding piece, never written), and ``maskbits`` is the
    precomputed u32 bit mask the shifted contribution covers.
    """

    K: int
    src: np.ndarray          # int32
    scode: np.ndarray        # int32
    tok: np.ndarray          # int32
    maskbits: np.ndarray     # uint32
    piece_base: tuple[int, ...]
    per_token: tuple[int, ...]   # pieces per token, per array
    logical: tuple[int, ...]     # logical pieces per array (pre-padding)


def append_tables(prog: ExecProgram, *, page_tokens: int,
                  logical: tuple[int, ...]) -> AppendTables:
    """Derive (and memoize) the append pack tables for one page layout.

    ``logical`` gives each array's *bundle* element count — the planner
    pads depths up with residual fill, so token ownership must be
    computed against the pre-padding counts (padding pieces get token -1
    and are never written; their bits stay zero for the page's life).
    """
    key = ("kv_append", page_tokens, tuple(logical))
    cached = prog.jit_cache.get(key)
    if cached is not None:
        return cached
    n_arr = len(prog.piece_depths)
    if len(logical) != n_arr:
        raise ValueError(
            f"logical has {len(logical)} entries for {n_arr} arrays")
    for i, n in enumerate(logical):
        if n % page_tokens:
            raise ValueError(
                f"array {i}: {n} elements not divisible by "
                f"page_tokens={page_tokens}")
        if n > prog.piece_depths[i]:
            raise ValueError(
                f"array {i}: {n} logical elements exceed the program's "
                f"{prog.piece_depths[i]} pieces")
    src_t, sc_t, k = pack_kernel_tables(prog)
    w32 = prog.kernel.words32
    src = src_t.reshape(prog.c_max, w32, k).astype(np.int32)
    scode = sc_t.reshape(prog.c_max, w32, k).astype(np.int32)

    base = np.asarray(prog.piece_base, dtype=np.int64)
    per_token = tuple(n // page_tokens for n in logical)
    piece = src.astype(np.int64) - 1                       # -1 = empty
    arr_of = np.clip(np.searchsorted(base[1:], piece, side="right"),
                     0, n_arr - 1)
    local = piece - base[arr_of]
    widths = np.asarray(prog.elem_widths, dtype=np.int64)[arr_of]
    ones = (np.uint64(1) << widths.astype(np.uint64)) - np.uint64(1)
    sc64 = scode.astype(np.int64)
    shifted = np.where(sc64 >= 0,
                       ones << np.maximum(sc64, 0).astype(np.uint64),
                       ones >> np.maximum(-sc64, 0).astype(np.uint64))
    maskbits = np.where(src > 0, shifted & np.uint64(0xFFFFFFFF),
                        np.uint64(0)).astype(np.uint32)

    pt = np.asarray(per_token, dtype=np.int64)[arr_of]
    in_range = (src > 0) & (local < np.asarray(logical)[arr_of])
    tok = np.where(in_range & (pt > 0), local // np.maximum(pt, 1), -1)
    # a residual-padding contribution is never written: mask it out too
    maskbits = np.where(tok >= 0, maskbits, np.uint32(0))
    tables = AppendTables(
        K=k, src=src, scode=scode, tok=tok.astype(np.int32),
        maskbits=maskbits,
        piece_base=tuple(int(b) for b in base),
        per_token=per_token,
        logical=tuple(int(x) for x in logical),
    )
    prog.jit_cache[key] = tables
    return tables


# ----------------------------------------------------------------------
# read-path tables
# ----------------------------------------------------------------------
def page_bit_stride(prog: ExecProgram) -> int:
    """Bits one packed page occupies in the flattened u32 word view."""
    return prog.c_max * prog.kernel.words32 * 32


def page_stream_tables(prog: ExecProgram, *, page_tokens: int,
                       n_kv_heads: int, head_dim: int
                       ) -> dict[str, np.ndarray]:
    """Per-page bit-offset tables of every logical KV element.

    ``k`` / ``v``: ``(page_tokens, n_kv_heads, head_dim)`` uint32;
    ``k_scales`` / ``v_scales``: ``(page_tokens, n_kv_heads)`` uint32.
    """
    key = ("kv_page_tabs", page_tokens, n_kv_heads, head_dim)
    cached = prog.jit_cache.get(key)
    if cached is not None:
        return cached
    n_codes = page_tokens * n_kv_heads * head_dim
    n_scales = page_tokens * n_kv_heads
    tabs = {
        "k": prog.stream_bit_offsets(0)[:n_codes].reshape(
            page_tokens, n_kv_heads, head_dim),
        "k_scales": prog.stream_bit_offsets(1)[:n_scales].reshape(
            page_tokens, n_kv_heads),
        "v": prog.stream_bit_offsets(2)[:n_codes].reshape(
            page_tokens, n_kv_heads, head_dim),
        "v_scales": prog.stream_bit_offsets(3)[:n_scales].reshape(
            page_tokens, n_kv_heads),
    }
    prog.jit_cache[key] = tabs
    return tabs


def full_stream_tables(prog: ExecProgram, *, page_tokens: int,
                       n_kv_heads: int, head_dim: int, n_pages: int
                       ) -> dict[str, np.ndarray]:
    """Page tables broadcast over ``n_pages`` along the token axis.

    Token ``s`` of a slot lives in page ``s // page_tokens`` at in-page
    index ``s % page_tokens``; its global bit offset is the per-page
    offset plus the page's bit stride.  Validated against the uint32
    addressing range of the stream tables.
    """
    key = ("kv_full_tabs", page_tokens, n_kv_heads, head_dim, n_pages)
    cached = prog.jit_cache.get(key)
    if cached is not None:
        return cached
    page = page_stream_tables(prog, page_tokens=page_tokens,
                              n_kv_heads=n_kv_heads, head_dim=head_dim)
    stride = page_bit_stride(prog)
    if n_pages * stride > (1 << 32):
        raise ValueError(
            f"{n_pages} pages x {stride} bits exceed the 2^32-bit "
            "addressing range of the uint32 stream tables")
    offs = (np.arange(n_pages, dtype=np.int64) * stride)
    full = {}
    for name, tab in page.items():
        t = tab.astype(np.int64)[None] + offs.reshape(
            (n_pages,) + (1,) * tab.ndim)
        full[name] = t.reshape((n_pages * page_tokens,) + tab.shape[1:]) \
            .astype(np.uint32)
    prog.jit_cache[key] = full
    return full


def page_window_tables(prog: ExecProgram, *, page_tokens: int,
                       n_kv_heads: int, head_dim: int, bits: int
                       ) -> dict[str, np.ndarray]:
    """Page-local window entries for the stream-attention kernel.

    A page's words are staged as ``n_chunks`` rows of 128 words; an
    element's source row is its chunk (``word >> 7``) and every page
    shares the same entries.  Each table is ``(n_kv_heads, page_tokens,
    128)`` uint32 (:func:`repro.kernels.window.encode_entries`): ``k`` /
    ``v`` hold head dim ``d`` in lane ``d`` (lanes past ``head_dim``
    repeat lane 0), ``k_scales`` / ``v_scales`` repeat the (token, head)
    scale across all lanes, so the extracted scales broadcast over the
    head dim for free.
    """
    if head_dim > LANES:
        raise ValueError(
            f"head_dim {head_dim} > {LANES}: stream attention holds one "
            "head vector per 128-lane row")
    key = ("kv_window", page_tokens, n_kv_heads, head_dim, bits)
    cached = prog.jit_cache.get(key)
    if cached is not None:
        return cached
    page = page_stream_tables(prog, page_tokens=page_tokens,
                              n_kv_heads=n_kv_heads, head_dim=head_dim)
    shape = (n_kv_heads, page_tokens, LANES)
    out = {}
    for name, tab in page.items():
        if tab.ndim == 3:                       # (pt, hkv, hd) codes
            t = np.empty(shape, dtype=np.int64)
            t[..., :head_dim] = tab.transpose(1, 0, 2)
            t[..., head_dim:] = t[..., :1]
            width = bits
        else:                                   # (pt, hkv) scales
            t = np.broadcast_to(tab.T[..., None].astype(np.int64), shape)
            width = 16
        lo, hi, sh = split_offsets(t, width)
        out[name] = encode_entries(lo, sh, lo >> 7, hi >> 7)
    prog.jit_cache[key] = out
    return out
