"""`PackedTree`: the pytree-level front door for Iris-packed models.

The paper automates the *layout workflow*; this module automates it at
**parameter-tree granularity**.  One call —

    import repro.api as iris

    pt = iris.pack_tree(cfg, params, QuantSpec(bits=4))

— quantizes every large weight matrix, plans the per-layer Iris stream
layout through :func:`repro.api.plan_layer_stack` (one scheduler run for
the whole uniform stack, N-1 cache rebinds), packs the per-layer unified
HBM stream buffers, and returns a :class:`PackedTree` that the rest of
the toolchain composes with *as a pytree*:

* **jit / sharding** — ``PackedTree`` is registered with
  ``jax.tree_util`` (buffers as leaves, the static
  :class:`LayoutManifest` as aux_data), so it flows through ``jax.jit``,
  ``jax.device_put`` and ``NamedSharding`` unchanged.
* **serving** — ``models.quantized.packed_decode_step`` consumes the
  lane-packed kernel views (``.packed`` / ``.scales``) directly; no
  consumer re-wires quantize→plan→pack by hand.
* **checkpointing** — the per-layer stream buffers *are* the checkpoint
  (``checkpoint.save_packed``); the manifest records the layout
  signature and count-intervals, so :func:`unpack_streams` rebuilds the
  kernel views bit-identically on restore — rebinding the layout from
  the cache (or the manifest itself) without ever re-running the
  scheduler, and never materializing dense weights.

Two array-level representations coexist in the tree:

* ``streams`` — ``(n_layers, c_max, m/8)`` uint8: the unified Iris
  stream per layer, i.e. the storage/DMA byte order the paper generates
  (codes + scale bit-patterns + 16-bit norm slots, interleaved by the
  scheduler).  Canonical for checkpoint/transport.
* ``packed`` / ``scales`` — per-tensor lane-packed uint32 codes and
  group scales: the operand format of the dequant-on-load Pallas matmul
  (``kernels.packed_matmul``).  Canonical for the decode hot path.

Both are derived from the same element codes; ``unpack_streams`` proves
they stay interconvertible bit-for-bit.
"""
from __future__ import annotations

import dataclasses
import json
import warnings
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.exec_plan import (
    ExecProgram,
    StreamTables,
    lower_exec,
    pack_compiled,
    stream_matmul_tables,
)
from repro.core.iris import DEFAULT_CACHE, LayoutCache
from repro.core.layout import Layout
from repro.core.packing import (
    BundleTensor,
    bundle_problem,
    pad_bundle_elements,
)
from repro.core.task import LayoutProblem
from repro.kernels.packed_matmul import SUPPORTED_BITS
from repro.quant.qtypes import QuantSpec, pack_codes_u32, quantize

__all__ = [
    "LayoutManifest", "PackedTree", "pack_tree", "unpack_streams",
]

#: weight names quantized in a dense decoder sublayer (bundle order)
_QUANT_NAMES = ("wq", "wk", "wv", "wo", "w_gate", "w_up", "w_down")

#: bundle tensor name -> quantized param key
_BUNDLE_TO_PARAM = {
    "wq": "attn/wq", "wk": "attn/wk", "wv": "attn/wv", "wo": "attn/wo",
    "w_gate": "mlp/w_gate", "w_up": "mlp/w_up", "w_down": "mlp/w_down",
}

#: bundle norm slot -> (other key, leaf key)
_BUNDLE_NORMS = {"attn_norm": "norm1", "mlp_norm": "norm2"}


def _to_tuple(x: Any) -> Any:
    """Recursively freeze lists (JSON round-trip) into hashable tuples."""
    if isinstance(x, (list, tuple)):
        return tuple(_to_tuple(v) for v in x)
    return x


# ----------------------------------------------------------------------
# the manifest: content-addressed static layout metadata
# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class LayoutManifest:
    """Static description of how a :class:`PackedTree` is laid out.

    Everything a consumer needs to *rebind* — not re-derive — the layout:
    the bundle spec, the problem's content signature (the
    :class:`~repro.core.iris.LayoutCache` key) and the layout's
    count-intervals.  Frozen and hashable, so it rides through
    ``jax.jit`` as pytree aux_data; JSON-serializable, so it rides
    through checkpoints.  Restoring from a manifest never runs the
    scheduler: a warm cache answers by signature, a cold one is seeded
    from ``intervals``.
    """

    arch: str
    spec: QuantSpec
    shapes: tuple[tuple[str, tuple[int, int]], ...]  # quantized name -> (K, N)
    n_layers: int
    m: int
    c_max: int
    row_bytes: int
    bundle: tuple[BundleTensor, ...]
    signature: tuple                     # LayoutProblem.canonical_signature()
    intervals: tuple                     # Layout.count_intervals
    strategy: str = "iris"

    # -- layout resolution ---------------------------------------------
    def problem(self) -> LayoutProblem:
        return bundle_problem(list(self.bundle), m=self.m)

    def elem_widths(self) -> tuple[int, ...]:
        return tuple(b.width_bits for b in self.bundle)

    def resolve_layout(self, cache: LayoutCache | None = DEFAULT_CACHE,
                       ) -> tuple[Layout, str]:
        """The layout this manifest describes, **without scheduling**.

        Returns ``(layout, provenance)`` where provenance is
        ``"cache-hit"`` (the shared cache already held this scheduling
        instance — O(intervals) rebind) or ``"manifest"`` (layout rebuilt
        from the recorded count-intervals and seeded into the cache).

        Only ``"iris"`` manifests consult the cache: the
        :class:`~repro.core.iris.LayoutCache` is keyed on the problem's
        content signature alone, which for a baseline-strategy manifest
        would both return the *iris* layout for the same problem (wrong
        bit offsets for the recorded stream) and, on insert, poison the
        cache with a baseline layout under the signature iris plans
        resolve by.  Baseline layouts are O(intervals) to rebuild anyway.
        """
        prob = self.problem()
        if prob.canonical_signature() != self.signature:
            raise ValueError(
                "manifest signature does not match its bundle problem — "
                "manifest is corrupt or from an incompatible version"
            )
        use_cache = cache is not None and self.strategy == "iris"
        if use_cache:
            hit = cache.lookup(prob)
            if hit is not None:
                return hit, "cache-hit"
        lay = Layout.from_count_intervals(prob, self.intervals)
        lay.validate()
        if use_cache:
            cache.insert(prob, False, lay)
        return lay, "manifest"

    # -- (de)serialization: manifests ride inside checkpoint JSON ------
    def to_json_dict(self) -> dict:
        return {
            "arch": self.arch,
            "spec": dataclasses.asdict(self.spec),
            "shapes": [[n, list(s)] for n, s in self.shapes],
            "n_layers": self.n_layers,
            "m": self.m,
            "c_max": self.c_max,
            "row_bytes": self.row_bytes,
            "bundle": [dataclasses.asdict(b) for b in self.bundle],
            "signature": self.signature,
            "intervals": self.intervals,
            "strategy": self.strategy,
        }

    @staticmethod
    def from_json_dict(d: dict) -> "LayoutManifest":
        return LayoutManifest(
            arch=d["arch"],
            spec=QuantSpec(**d["spec"]),
            shapes=tuple((n, tuple(s)) for n, s in d["shapes"]),
            n_layers=int(d["n_layers"]),
            m=int(d["m"]),
            c_max=int(d["c_max"]),
            row_bytes=int(d["row_bytes"]),
            bundle=tuple(BundleTensor(**b) for b in d["bundle"]),
            signature=_to_tuple(d["signature"]),
            intervals=_to_tuple(d["intervals"]),
            strategy=d["strategy"],
        )

    def to_json(self) -> str:
        return json.dumps(self.to_json_dict())

    @staticmethod
    def from_json(text: str) -> "LayoutManifest":
        return LayoutManifest.from_json_dict(json.loads(text))


# ----------------------------------------------------------------------
# the tree
# ----------------------------------------------------------------------
@jax.tree_util.register_pytree_with_keys_class
class PackedTree:
    """A parameter tree in Iris-packed form, registered as a JAX pytree.

    Children (dynamic leaves): ``packed`` (lane-packed uint32 kernel
    views), ``scales`` (group scales), ``other`` (embed / norms / biases
    — unquantized), ``streams`` (the per-layer unified Iris stream
    buffers, ``(n_layers, c_max, m/8)`` uint8, or ``None`` when built
    with ``with_streams=False``).  Aux_data (static): the
    :class:`LayoutManifest`.

    Because the manifest is hashable aux_data, a ``PackedTree`` passes
    through ``jax.jit`` boundaries, ``jax.device_put`` and
    ``NamedSharding`` placement like any parameter pytree.
    Layout/exec-program handles are *not* part of the tree: they resolve
    lazily through the content-addressed layout cache, so a tree that
    crossed a jit/transport boundary re-acquires them with zero
    scheduler runs.
    """

    def __init__(self, packed: dict, scales: dict, other: dict,
                 streams: Any, manifest: LayoutManifest, *,
                 provenance: str = "scheduled") -> None:
        self.packed = packed
        self.scales = scales
        self.other = other
        self.streams = streams
        self.manifest = manifest
        #: where this tree's layout came from: "scheduled", "cache-hit",
        #: "manifest" (checkpoint restore) or "pytree" (rebuilt by
        #: tree_unflatten, e.g. on the far side of a jit boundary)
        self.provenance = provenance
        self._layout: Layout | None = None
        self._program: ExecProgram | None = None
        # stream-direct matmul caches (static derivations, not leaves):
        # bit-offset tables per weight key, uint32 word view per layer
        self._stream_tabs: dict = {}
        self._stream_words: dict = {}

    # -- pytree protocol -----------------------------------------------
    def tree_flatten_with_keys(self):
        k = jax.tree_util.GetAttrKey
        children = (
            (k("packed"), self.packed),
            (k("scales"), self.scales),
            (k("other"), self.other),
            (k("streams"), self.streams),
        )
        return children, self.manifest

    @classmethod
    def tree_unflatten(cls, manifest, children):
        packed, scales, other, streams = children
        return cls(packed, scales, other, streams, manifest,
                   provenance="pytree")

    # -- compat surface (PackedParams fields) --------------------------
    @property
    def spec(self) -> QuantSpec:
        return self.manifest.spec

    @property
    def shapes(self) -> dict[str, tuple[int, int]]:
        return dict(self.manifest.shapes)

    @property
    def n_layers(self) -> int:
        return self.manifest.n_layers

    def hbm_bytes(self) -> int:
        """Serving-view footprint: lane-packed codes + scales + other."""
        b = sum(int(np.asarray(x).size) * 4 for x in self.packed.values())
        b += sum(int(np.asarray(x).size) * np.asarray(x).dtype.itemsize
                 for x in self.scales.values())
        b += sum(int(np.asarray(x).size) * np.asarray(x).dtype.itemsize
                 for x in jax.tree.leaves(self.other))
        return b

    @property
    def stream_bytes(self) -> int:
        """Total bytes of the unified per-layer Iris stream buffers."""
        return self.manifest.n_layers * self.manifest.c_max \
            * self.manifest.row_bytes

    # -- layout / program handles (lazy, cache-routed) ------------------
    def layout(self, cache: LayoutCache | None = DEFAULT_CACHE) -> Layout:
        """The per-layer stream :class:`Layout` (never re-scheduled)."""
        if self._layout is None:
            self._layout, prov = self.manifest.resolve_layout(cache)
            if self.provenance == "pytree":
                self.provenance = prov
        return self._layout

    def exec_program(self, cache: LayoutCache | None = DEFAULT_CACHE,
                     ) -> ExecProgram:
        """Compiled pack/decode program at bundle-element granularity."""
        if self._program is None:
            self._program = lower_exec(self.layout(cache),
                                       elem_widths=self.manifest.elem_widths())
        return self._program

    # -- stream-direct matmul (no dense intermediate) -------------------
    def stream_tables(self, key: str) -> StreamTables:
        """Bit-offset tables for quantized param ``key`` (e.g. "attn/wq").

        Memoized; all layers share one layout signature, hence one table
        per weight matrix for the whole stack.
        """
        tabs = self._stream_tabs.get(key)
        if tabs is None:
            shapes = dict(self.manifest.shapes)
            if key not in shapes:
                raise KeyError(
                    f"{key!r} is not a quantized tensor; have "
                    f"{sorted(shapes)}"
                )
            bname = key.split("/", 1)[1]
            tabs = stream_matmul_tables(
                self.layout(), bname, shapes[key],
                scales=f"{bname}_scales",
                group_size=self.manifest.spec.group_size,
                program=self.exec_program())
            self._stream_tabs[key] = tabs
        return tabs

    def host_stream_words(self, layer: int) -> np.ndarray:
        """Layer ``layer``'s stream as host uint32 words (no device copy).

        The upload-side twin of :meth:`layer_stream_words`: the engine's
        :class:`~repro.engine.streams.StreamUploader` reads these and
        owns the ``device_put`` itself, so the transfer can overlap
        decode on a side thread.
        """
        if self.streams is None:
            raise ValueError(
                "tree was built with with_streams=False; stream-"
                "direct execution needs the stream buffers"
            )
        prog = self.exec_program()
        return prog.buffer_words32(
            np.asarray(self.streams[layer])).reshape(-1)

    def layer_stream_words(self, layer: int):
        """Layer ``layer``'s stream as the flat uint32 kernel view."""
        import jax.numpy as jnp

        words = self._stream_words.get(layer)
        if words is None:
            words = jnp.asarray(self.host_stream_words(layer))
            self._stream_words[layer] = words
        return words

    def matmul_direct(self, x, key: str, layer: int, *, words=None,
                      **block_kw):
        """``x @ dequant(key)`` gathered straight from layer ``layer``'s
        packed stream — the serving path that never materializes a dense
        weight intermediate, for any element width <= 32 (including the
        widths the lane-packed kernel views cannot represent).

        ``words`` overrides the stream word source: pass the layer's
        uint32 word view (e.g. from a
        :class:`~repro.engine.streams.StreamUploader`) to matmul against
        an externally staged buffer instead of the tree's resident copy.
        """
        from repro.kernels.stream_matmul import stream_matmul

        if words is None:
            words = self.layer_stream_words(layer)
        return stream_matmul(x, words, self.stream_tables(key), **block_kw)

    # -- verification ---------------------------------------------------
    def verify(self, *, raise_on_error: bool = True, passes=None):
        """Statically verify this tree before serving or checkpointing.

        Runs the :mod:`repro.analysis` pass set over the manifest, the
        layout it rebinds, the lowered tables and the resident stream
        buffers.  Returns the :class:`~repro.analysis.Report`; with
        ``raise_on_error=True`` (default) any error-severity finding
        raises :class:`~repro.analysis.AnalysisError`.
        """
        from repro.analysis import verify_tree  # lazy: avoid cycle

        report = verify_tree(self, passes=passes)
        return report.raise_if_errors() if raise_on_error else report

    # -- reporting ------------------------------------------------------
    def summary(self) -> str:
        """One-line report: strategy, B_eff, buffer bytes, provenance."""
        man = self.manifest
        prob = man.problem()
        b_eff = prob.p_tot / (man.c_max * man.m)
        stream = "none" if self.streams is None \
            else f"{self.stream_bytes / 2**20:.2f} MiB"
        return (
            f"PackedTree[{man.arch}] int{man.spec.bits}/g{man.spec.group_size}"
            f" layers={man.n_layers} strategy={man.strategy}"
            f" B_eff={b_eff:.4f} stream={stream}"
            f" hbm={self.hbm_bytes() / 2**20:.2f} MiB"
            f" cache={self.provenance}"
        )

    def __repr__(self) -> str:
        return f"<{self.summary()}>"


# ----------------------------------------------------------------------
# forward: params -> PackedTree
# ----------------------------------------------------------------------
def _bits16(x: jax.Array) -> np.ndarray:
    """Bit pattern of a 16-bit float array as host uint64 elements."""
    if x.dtype.itemsize != 2:
        x = x.astype(jnp.bfloat16)
    u16 = jax.lax.bitcast_convert_type(x, jnp.uint16)
    return np.asarray(u16).reshape(x.shape[0], -1).astype(np.uint64)


def _layer_element_data(bundle, codes, scales16, norms16, layer: int,
                        ) -> dict[str, np.ndarray]:
    """Element streams for one layer, keyed by bundle tensor name."""
    data: dict[str, np.ndarray] = {}
    for b in bundle:
        if b.name in _BUNDLE_NORMS:
            data[b.name] = norms16[b.name][layer]
        elif b.name.endswith("_scales"):
            data[b.name] = scales16[b.name[:-len("_scales")]][layer]
        else:
            data[b.name] = codes[_BUNDLE_TO_PARAM[b.name]][layer] \
                .reshape(-1).astype(np.uint64)
    return data


def pack_tree(cfg, params: dict, spec: QuantSpec, *, m: int = 4096,
              strategy: str = "iris",
              cache: LayoutCache | None = DEFAULT_CACHE,
              with_streams: bool = True,
              with_kernel_views: bool | None = None,
              pack_backend: str = "numpy") -> PackedTree:
    """Quantize + plan + pack a parameter tree in one call.

    The front door the ISSUE's consumers share: serving
    (``launch.serve --packed``), checkpointing
    (``checkpoint.save_packed``) and the examples all call this instead
    of wiring quantize→plan→pack by hand.  Planning goes through
    :func:`repro.api.plan_layer_stack`, so a uniform stack costs one
    scheduler run (or zero on a warm cache) and N-1 rebinds.

    ``with_streams=False`` skips building the unified stream buffers
    (serving-only use; such a tree cannot be checkpointed packed, and
    cannot serve stream-direct).

    ``with_kernel_views`` controls the lane-packed uint32 views
    (``.packed``) consumed by the legacy two-pass ``packed_matmul``
    path.  ``None`` (default) builds them exactly when the bit width
    lane-packs (``32 % bits == 0``); other widths — int3, int5, ... —
    serve through :meth:`PackedTree.matmul_direct`, which reads the
    stream buffers directly, so the whole 2..8-bit range is end-to-end
    servable.  Forcing ``True`` for a non-lane width raises.

    ``pack_backend`` selects how the per-layer stream rows are packed:
    ``"numpy"`` (default) is the vectorized host
    :func:`~repro.core.exec_plan.pack_compiled`; ``"pallas"`` the fused
    device kernel (:func:`~repro.kernels.layout_pack.pack_layout_fused`)
    — bit-identical, so ``save_packed`` checkpoints are byte-equal
    either way.
    """
    from repro import api  # deferred: repro.api lazy-loads this module
    from repro.models.quantized import quantizable  # deferred: no cycle

    lane_packable = spec.bits in SUPPORTED_BITS
    if with_kernel_views is None:
        with_kernel_views = lane_packable
    if with_kernel_views and not lane_packable:
        raise ValueError(
            f"lane-packed kernel views need bits in "
            f"{sorted(SUPPORTED_BITS)}; got {spec.bits} — serve it "
            "stream-direct (with_kernel_views=False)"
        )
    if not with_kernel_views and not with_streams:
        raise ValueError(
            "with_kernel_views=False and with_streams=False leaves "
            "nothing servable"
        )
    if not quantizable(cfg):
        raise NotImplementedError(
            f"pack_tree covers dense-family archs; {cfg.name} is not"
        )

    # -- quantize every large matrix of the (uniform) decoder stack ----
    blocks = params["blocks"][0]
    codes: dict[str, np.ndarray] = {}     # param key -> (L, K, N) uint8
    packed: dict[str, Any] = {}
    scales: dict[str, Any] = {}
    shapes: dict[str, tuple[int, int]] = {}
    other: dict[str, Any] = {
        "embed": params["embed"],
        "final_norm": params["final_norm"],
        "norm1": blocks["norm1"],
        "norm2": blocks["norm2"],
    }
    if "unembed" in params:
        other["unembed"] = params["unembed"]
    for sub in ("attn", "mlp"):
        for name, w in blocks[sub].items():
            if name not in _QUANT_NAMES:
                other[f"{sub}/{name}"] = w      # biases stay dense
                continue
            k = f"{sub}/{name}"
            qt = jax.vmap(lambda wl: quantize(wl, spec))(w)
            if with_kernel_views:
                packed[k] = jax.vmap(
                    lambda c: pack_codes_u32(c, spec.bits))(qt.codes)
            scales[k] = qt.scales
            shapes[k] = tuple(int(d) for d in w.shape[1:])
            if with_streams:
                codes[k] = np.asarray(qt.codes)

    # -- plan the per-layer stream layout through the façade -----------
    stack = api.plan_layer_stack(cfg, spec, m=m, strategy=strategy,
                                 cache=cache)
    lay = stack.plans[0].layout
    manifest = LayoutManifest(
        arch=cfg.name,
        spec=spec,
        shapes=tuple(sorted(shapes.items())),
        n_layers=stack.n_layers,
        m=m,
        c_max=lay.c_max,
        row_bytes=m // 8,
        bundle=stack.bundle,
        signature=lay.problem.canonical_signature(),
        intervals=lay.count_intervals,
        strategy=strategy,
    )
    # "scheduled" / "cache-hit" for iris, "closed-form" for baselines
    provenance = stack.plans[0].provenance

    # -- pack the unified per-layer HBM streams ------------------------
    streams = None
    if with_streams:
        if spec.scale_dtype not in ("bfloat16", "float16"):
            raise ValueError(
                f"stream packing stores 16-bit scale slots; scale_dtype "
                f"{spec.scale_dtype!r} is not 16-bit"
            )
        prog = stack.exec_program()
        if pack_backend == "pallas":
            from repro.kernels.layout_pack import pack_layout_fused

            def _pack_row(data):
                return pack_layout_fused(lay, data, program=prog)
        elif pack_backend == "numpy":
            def _pack_row(data):
                return pack_compiled(lay, data, program=prog)
        else:
            raise NotImplementedError(
                f"pack_backend {pack_backend!r}; use 'numpy' or 'pallas'"
            )
        scales16 = {k[len("attn/"):] if k.startswith("attn/")
                    else k[len("mlp/"):]: _bits16(v)
                    for k, v in scales.items()}
        norms16 = {name: _bits16(other[key]["scale"])
                   for name, key in _BUNDLE_NORMS.items()}
        rows = []
        for layer in range(stack.n_layers):
            data = _layer_element_data(stack.bundle, codes, scales16,
                                       norms16, layer)
            padded = pad_bundle_elements(stack.problem, prog, data)
            rows.append(_pack_row(padded))
        streams = jnp.asarray(np.stack(rows))

    pt = PackedTree(packed=packed, scales=scales, other=other,
                    streams=streams, manifest=manifest,
                    provenance=provenance)
    pt._layout = lay
    return pt


# ----------------------------------------------------------------------
# inverse: streams -> kernel views (checkpoint restore)
# ----------------------------------------------------------------------
def unpack_streams(manifest: LayoutManifest, streams: Any, other: dict, *,
                   cache: LayoutCache | None = DEFAULT_CACHE) -> PackedTree:
    """Rebuild a :class:`PackedTree` from its stream buffers.

    The checkpoint-restore path: the layout is *rebound* from the cache
    (or rebuilt from the manifest's count-intervals) — the scheduler
    never runs — and the lane-packed kernel views are regenerated from
    the stream bytes **bit-identically** (codes and scale bit patterns
    round-trip exactly; dense weights are never materialized).
    """
    lay, provenance = manifest.resolve_layout(cache)
    prog = lower_exec(lay, elem_widths=manifest.elem_widths())
    streams = np.asarray(streams)
    n_layers = manifest.n_layers
    if streams.shape[0] != n_layers:
        raise ValueError(
            f"streams has {streams.shape[0]} layers, manifest says {n_layers}"
        )
    names = [a.name for a in lay.problem.arrays]
    idx = {n: i for i, n in enumerate(names)}
    shapes = dict(manifest.shapes)
    spec = manifest.spec
    g = spec.group_size

    # one vectorized unpack per layer, then slice per tensor
    per_layer = [prog.unpack_indexed(streams[layer])
                 for layer in range(n_layers)]

    # lane-packed kernel views only exist for widths pack_codes_u32 can
    # represent; other widths serve stream-direct off the buffers
    lane_packable = spec.bits in SUPPORTED_BITS
    packed: dict[str, Any] = {}
    scales: dict[str, Any] = {}
    for key, (kk, nn) in shapes.items():
        bname = key.split("/", 1)[1]
        ci, si = idx[bname], idx[f"{bname}_scales"]
        layer_scales = np.stack([
            per_layer[la][si][:(kk // g) * nn]
            .astype(np.uint16).reshape(kk // g, nn)
            for la in range(n_layers)])
        if lane_packable:
            layer_codes = np.stack([
                per_layer[la][ci][:kk * nn].reshape(kk, nn).astype(np.uint8)
                for la in range(n_layers)])
            packed[key] = jax.vmap(
                lambda c: pack_codes_u32(c, spec.bits))(
                    jnp.asarray(layer_codes))
        scales[key] = jax.lax.bitcast_convert_type(
            jnp.asarray(layer_scales), jnp.dtype(spec.scale_dtype))
    pt = PackedTree(packed=packed, scales=scales, other=other,
                    streams=jnp.asarray(streams), manifest=manifest,
                    provenance=provenance)
    pt._layout = lay
    pt._program = prog
    return pt


# ----------------------------------------------------------------------
# deprecated alias support (models.quantized re-exports this)
# ----------------------------------------------------------------------
def _warn_packed_params() -> type[PackedTree]:
    warnings.warn(
        "PackedParams is deprecated; it is now an alias of "
        "repro.api.PackedTree — build one with repro.api.pack_tree()",
        DeprecationWarning, stacklevel=3,
    )
    return PackedTree
