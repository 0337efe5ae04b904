"""Shared test fixtures and problem builders.

Dedupes the deterministic problem lists and hypothesis composites that
used to be copy-pasted across test_exec_plan*, test_kernels and
test_packing_properties, and hosts the stream-matmul case builder the
equivalence/property suites share.

Hypothesis is optional (the container may not ship it): everything
hypothesis-flavoured is guarded, and the property-test modules keep
their ``pytest.importorskip`` gates.  When hypothesis *is* present, two
profiles are registered — ``ci`` (derandomized, fixed seed database:
reproducible CI runs) and ``dev`` — selected by ``HYPOTHESIS_PROFILE``.
"""
import os

import numpy as np

# the deterministic problem sets live in repro.analysis.suite — one
# source of truth shared by these tests and the analysis-gate CI job
from repro.analysis.suite import (  # noqa: F401  (test-suite re-exports)
    DECODE_PROBLEMS,
    EXEC_PROBLEMS,
    GATE_PROBLEMS,
    GOLDEN_PROBLEM,
)
from repro.core.task import make_problem


# ----------------------------------------------------------------------
# stream-matmul case builder (equivalence + property suites)
# ----------------------------------------------------------------------
def build_stream_case(bits: int, group_size: int, k: int, n: int, *,
                      m: int = 512, layout_fn=None, max_lanes=None,
                      seed: int = 0):
    """Quantize a random (K, N) matrix, pack it (with its scales) into an
    Iris stream, and return everything a stream-direct matmul needs.

    Returns ``(codes, qt, layout, prog, buf, tabs)`` where ``codes`` is
    the (K, N) uint8 code matrix, ``qt`` the QuantizedTensor (for float
    references), ``buf`` the packed ``(c_max, m/8)`` buffer and ``tabs``
    the :class:`~repro.core.exec_plan.StreamTables`.

    ``layout_fn`` defaults to the Iris scheduler; pass a baseline to
    exercise strategy invariance.  ``max_lanes`` schedules the weight
    array lane-capped (paper §3.3 constraint) — that path bypasses
    ``bundle_problem`` and builds the problem directly.
    """
    import jax
    import jax.numpy as jnp

    from repro.core.exec_plan import (
        lower_exec,
        pack_compiled,
        stream_matmul_tables,
    )
    from repro.core.iris import schedule
    from repro.core.packing import (
        BundleTensor,
        bundle_problem,
        pad_bundle_elements,
    )
    from repro.quant import QuantSpec, quantize

    g = group_size
    spec = QuantSpec(bits=bits, group_size=g)
    w = jax.random.normal(jax.random.PRNGKey(seed + bits * 1000 + k + n),
                          (k, n), jnp.float32)
    qt = quantize(w, spec)
    codes = np.asarray(qt.codes)
    u16 = np.asarray(jax.lax.bitcast_convert_type(
        qt.scales, jnp.uint16)).astype(np.uint64)
    data = {"w": codes.reshape(-1).astype(np.uint64),
            "w_scales": u16.reshape(-1)}
    if max_lanes is not None:
        prob = make_problem(
            m, [("w", bits, k * n, 1), ("w_scales", 16, (k // g) * n, 1)],
            max_lanes=max_lanes)
        ew = None
    else:
        bundle = [BundleTensor("w", bits, k * n, 1),
                  BundleTensor("w_scales", 16, (k // g) * n, 1)]
        prob = bundle_problem(bundle, m=m)
        ew = (bits, 16)
    lay = (layout_fn or schedule)(prob)
    prog = lower_exec(lay, elem_widths=ew)
    padded = pad_bundle_elements(prob, prog, data) if ew is not None else data
    buf = pack_compiled(lay, padded, program=prog)
    tabs = stream_matmul_tables(lay, "w", (k, n), scales="w_scales",
                                group_size=g, program=prog)
    return codes, qt, lay, prog, buf, tabs


def two_pass_oracle(x, lay, prog, buf, bits: int, group_size: int,
                    k: int, n: int, *, block_m: int = 128,
                    block_n: int = 128, block_k: int = 512):
    """The legacy two-pass path: fused Pallas decode materializes dense
    codes/scales, then the lane-packed Pallas matmul consumes them.

    For widths ``packed_matmul`` cannot lane-pack, the codes are
    re-biased into 8-bit containers (``c + 128 - 2^(bits-1)``), which
    leaves every dequantized float value identical — so the oracle
    remains *bit-exact* for any ``bits <= 8``.
    """
    import jax
    import jax.numpy as jnp

    from repro.kernels.layout_decode import decode_layout_fused
    from repro.kernels.packed_matmul import SUPPORTED_BITS, packed_matmul

    g = group_size
    dec = decode_layout_fused(lay, buf, program=prog)
    codes = np.asarray(dec["w"])[:k * n].reshape(k, n)
    scales = jax.lax.bitcast_convert_type(
        jnp.asarray(np.asarray(dec["w_scales"])[:(k // g) * n]
                    .astype(np.uint16).reshape(k // g, n)), jnp.bfloat16)
    if bits in SUPPORTED_BITS:
        mm_bits = bits
    else:
        codes = codes + (128 - (1 << (bits - 1)))
        mm_bits = 8
    from repro.quant import pack_codes_u32
    pw = pack_codes_u32(jnp.asarray(codes.astype(np.uint8)), mm_bits)
    return packed_matmul(x, pw, scales, bits=mm_bits, group_size=g,
                         block_m=block_m, block_n=block_n, block_k=block_k)


# ----------------------------------------------------------------------
# packed KV-cache random-walk oracle (deterministic + property suites)
# ----------------------------------------------------------------------
def run_kv_walk(bits, hd, ops, seed, *, page_tokens=4, n_slots=3,
                max_seq=8):
    """Replay append/reset ``ops`` against a PackedKVCache and a dense
    numpy mirror of the quantize -> dequantize values, then assert the
    packed pages decode bit-exactly to the mirror.

    ``ops``: sequence of ``("reset", slot)`` or ``("append", [slots])``.
    Each slot keeps its own clock (continuous batching); appends past
    capacity are dropped.  Shared by the always-on seeded subset in
    test_kvcache.py and the hypothesis walk in test_kvcache_property.py.
    """
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.kvcache import PackedKVCache, dequantize_kv, quantize_kv

    cfg = get_config("smollm-135m").reduced(
        n_layers=1, n_heads=4, n_kv_heads=2, head_dim=hd, d_model=4 * hd,
        d_ff=64, vocab_size=64)
    rng = np.random.default_rng(seed)
    kvc = PackedKVCache.create(cfg, bits=bits, page_tokens=page_tokens,
                               n_slots=n_slots, max_seq=max_seq)
    smax = kvc.smax
    want_k = np.zeros((n_slots, smax, 2, hd), np.float32)
    want_v = np.zeros_like(want_k)
    clock = [0] * n_slots
    for op, arg in ops:
        if op == "reset":
            kvc = kvc.reset(arg)
            want_k[arg] = want_v[arg] = 0.0
            clock[arg] = 0
            continue
        slots = [s for s in arg if clock[s] < smax]
        if not slots:
            continue
        k = jnp.asarray(rng.normal(size=(len(slots), 2, hd)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(len(slots), 2, hd)), jnp.float32)
        pos = jnp.asarray([clock[s] for s in slots], jnp.int32)
        kvc = kvc.append(k, v, pos, jnp.asarray(slots, jnp.int32), layer=0)
        kq = np.asarray(dequantize_kv(*quantize_kv(k, bits), bits))
        vq = np.asarray(dequantize_kv(*quantize_kv(v, bits), bits))
        for i, s in enumerate(slots):
            want_k[s, clock[s]] = kq[i]
            want_v[s, clock[s]] = vq[i]
            clock[s] += 1
    kf, vf = kvc.dense_kv(0)
    np.testing.assert_array_equal(np.asarray(kf), want_k)
    np.testing.assert_array_equal(np.asarray(vf), want_v)
    return kvc


# ----------------------------------------------------------------------
# golden-file serialization
# ----------------------------------------------------------------------
def serialize_exec_program(prog) -> dict:
    """JSON-stable dump of an ExecProgram's lowered tables.

    Covers everything the kernels consume: destination words/shifts,
    piece bookkeeping, the fused-decode slot table (nonzero entries
    only, as (row, col, tab) triplets), the per-array gathers and the
    stream-direct global bit offsets.
    """
    kt = prog.kernel
    nz = np.argwhere(kt.tab != 0)
    return {
        "m": prog.m,
        "c_max": prog.c_max,
        "row_bytes": prog.row_bytes,
        "wpr": prog.wpr,
        "elem_widths": list(prog.elem_widths),
        "piece_depths": list(prog.piece_depths),
        "piece_base": list(prog.piece_base),
        "word": prog.word.tolist(),
        "shift": prog.shift.tolist(),
        "host_arrays": list(prog.host_arrays),
        "kernel": {
            "words32": kt.words32,
            "lanes": kt.lanes,
            "tab_nonzero": [[int(r), int(c), int(kt.tab[r, c])]
                            for r, c in nz],
            "gathers": [[int(i), g.tolist()] for i, g in kt.gathers],
        },
        "stream_bit_offsets": [
            prog.stream_bit_offsets(i).tolist()
            for i in range(len(prog.piece_depths))
            if prog.elem_widths[i] <= 32
        ],
    }


# ----------------------------------------------------------------------
# hypothesis: profiles + shared composites (all guarded)
# ----------------------------------------------------------------------
try:
    import hypothesis
    import hypothesis.strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    hypothesis.settings.register_profile(
        "ci", derandomize=True, deadline=None, print_blob=True)
    hypothesis.settings.register_profile("dev", deadline=None)
    hypothesis.settings.load_profile(
        os.environ.get("HYPOTHESIS_PROFILE", "dev"))

    @st.composite
    def problems(draw):
        """Random LayoutProblems: §4-style, non-power-of-two widths and
        bus, lane-capped, multi-interval (shared by the exec-plan and
        stream-matmul property suites)."""
        m = draw(st.sampled_from([24, 40, 64, 128, 256]))
        n = draw(st.integers(2, 5))
        max_lanes = draw(st.sampled_from([None, 1, 2, 4]))
        specs = []
        for i in range(n):
            width = draw(st.integers(1, min(64, m)))
            depth = draw(st.integers(1, 400))
            due = draw(st.integers(0, 40))       # spread -> multi-interval
            specs.append((f"a{i}", width, depth, due))
        return make_problem(m, specs, max_lanes=max_lanes)

    @st.composite
    def bundles(draw):
        """Random layer bundles (model-integration packing layer)."""
        from repro.core.packing import BundleTensor

        n = draw(st.integers(2, 6))
        out = []
        for i in range(n):
            out.append(BundleTensor(
                name=f"t{i}",
                width_bits=draw(st.integers(2, 32)),
                n_elems=draw(st.integers(100, 50_000)),
                stage=draw(st.integers(0, 5)),
            ))
        return out

    @st.composite
    def stream_matmul_cases(draw):
        """Shrinking-friendly stream-matmul problems: (bits, group_size,
        K, N, M, m, strategy).  Shrinks toward small shapes and the
        plain Iris strategy."""
        bits = draw(st.integers(2, 8))
        g = draw(st.sampled_from([32, 64]))
        k = g * draw(st.integers(1, 5))
        n = draw(st.integers(1, 150))
        mm = draw(st.integers(1, 33))
        bus = draw(st.sampled_from([64, 512]))
        strategy = draw(st.sampled_from(["iris", "homogeneous"]))
        return bits, g, k, n, mm, bus, strategy
