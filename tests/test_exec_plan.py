"""Compiled execution plans: bit-equivalence to the per-slot legacy
paths, single-pallas_call fused decode, cache-hit program reuse, and
mixed-width end-to-end decode (deterministic suite; the hypothesis
sweep lives in test_exec_plan_properties.py)."""
import numpy as np
import pytest

from repro import api
from repro.core.baselines import homogeneous_layout, naive_layout
from repro.core.codegen import pack_arrays, random_codes, unpack_arrays
from repro.core.exec_plan import lower_exec, pack_compiled, unpack_compiled
from repro.core.iris import LayoutCache, schedule
from repro.core.task import PAPER_EXAMPLE, make_problem

# §4 worked example, non-power-of-two widths/bus, lane-capped, and a
# multi-interval many-release problem — the ISSUE-4 property-test axes
# (shared with the golden-file and stream-matmul suites via conftest)
from conftest import EXEC_PROBLEMS as PROBLEMS
LAYOUT_FNS = [schedule, homogeneous_layout, naive_layout]


@pytest.mark.parametrize("prob_idx", range(len(PROBLEMS)))
@pytest.mark.parametrize("layout_fn", LAYOUT_FNS)
class TestHostEquivalence:
    def test_pack_bit_identical(self, prob_idx, layout_fn):
        p = PROBLEMS[prob_idx]
        lay = layout_fn(p)
        codes = random_codes(p, seed=prob_idx)
        legacy = pack_arrays(lay, codes)
        compiled = pack_compiled(lay, codes)
        assert legacy.shape == compiled.shape
        assert np.array_equal(legacy, compiled)

    def test_unpack_roundtrip(self, prob_idx, layout_fn):
        p = PROBLEMS[prob_idx]
        lay = layout_fn(p)
        codes = random_codes(p, seed=prob_idx)
        buf = pack_compiled(lay, codes)
        got = unpack_compiled(lay, buf)
        legacy = unpack_arrays(lay, buf)
        for name, want in codes.items():
            np.testing.assert_array_equal(got[name], want)
            np.testing.assert_array_equal(got[name], legacy[name])


class TestFusedDecode:
    @pytest.mark.parametrize("prob_idx", range(len(PROBLEMS)))
    def test_fused_equals_legacy_and_codes(self, prob_idx):
        from repro.kernels.ops import decode_layout

        p = PROBLEMS[prob_idx]
        lay = schedule(p)
        codes = random_codes(p, seed=prob_idx)
        buf = pack_compiled(lay, codes)
        fused = decode_layout(lay, buf, fused=True)
        legacy = decode_layout(lay, buf, fused=False)
        for name, want in codes.items():
            np.testing.assert_array_equal(
                np.asarray(fused[name]).astype(np.uint64), want)
            np.testing.assert_array_equal(
                np.asarray(legacy[name]).astype(np.uint64), want)

    def test_single_pallas_call(self, monkeypatch):
        """The fused path launches exactly one Pallas kernel per decode."""
        import repro.kernels.layout_decode as ld

        p = make_problem(64, [("a", 5, 64, 4), ("b", 11, 30, 8),
                              ("c", 16, 12, 8)])
        lay = schedule(p)
        codes = random_codes(p, seed=0)
        buf = pack_compiled(lay, codes)
        prog = lower_exec(lay)
        prog.jit_cache.clear()          # force a fresh trace we can count
        calls = []
        real = ld.pl.pallas_call

        def counting(*a, **kw):
            calls.append(1)
            return real(*a, **kw)

        monkeypatch.setattr(ld.pl, "pallas_call", counting)
        out = ld.decode_layout_fused(lay, buf)
        assert len(calls) == prog.n_pallas_calls == 1
        for name, want in codes.items():
            np.testing.assert_array_equal(
                np.asarray(out[name]).astype(np.uint64), want)

    def test_mixed_width_end_to_end(self):
        """Slots wider than 32 bits route to the host path (both modes)."""
        from repro.kernels.ops import decode_layout

        p = make_problem(128, [("a", 8, 100, 10), ("w", 40, 21, 3),
                               ("z", 64, 9, 20)])
        lay = schedule(p)
        codes = random_codes(p, seed=3)
        buf = pack_arrays(lay, codes)
        prog = lower_exec(lay)
        assert prog.host_arrays == (1, 2)
        for fused in (True, False):
            got = decode_layout(lay, buf, fused=fused)
            for name, want in codes.items():
                np.testing.assert_array_equal(
                    np.asarray(got[name]).astype(np.uint64), want)


class TestProgramCaching:
    def test_cache_hit_returns_prebuilt_program(self):
        """A LayoutCache hit yields a plan whose exec program is already
        built — including across rebinds to renamed problems."""
        cache = LayoutCache()
        p1 = make_problem(64, [("x", 5, 60, 4), ("y", 9, 31, 9)])
        pl1 = api.plan(p1, cache=cache)
        prog1 = pl1.exec_program
        # same scheduling instance, different array names -> rebind path
        p2 = make_problem(64, [("u", 5, 60, 4), ("v", 9, 31, 9)])
        pl2 = api.plan(p2, cache=cache)
        assert pl2.layout._exec_cache is pl1.layout._exec_cache
        assert cache.hits >= 1
        assert pl2.exec_program is prog1

    def test_lowering_runs_once_per_signature(self, monkeypatch):
        import repro.core.exec_plan as ep

        cache = LayoutCache()
        p = make_problem(32, [("x", 3, 50, 5), ("y", 7, 30, 9)])
        calls = []
        real = ep._lower

        def counting(layout, ew):
            calls.append(1)
            return real(layout, ew)

        monkeypatch.setattr(ep, "_lower", counting)
        api.plan(p, cache=cache).exec_program
        api.plan(p, cache=cache).exec_program
        assert len(calls) == 1

    def test_fused_trace_memoized_on_program(self):
        from repro.kernels.ops import decode_layout

        p = make_problem(64, [("a", 4, 64, 4), ("b", 8, 16, 8)])
        lay = schedule(p)
        buf = pack_compiled(lay, random_codes(p, seed=0))
        prog = lower_exec(lay)
        decode_layout(lay, buf, fused=True, program=prog)
        assert len(prog.jit_cache) == 1
        decode_layout(lay, buf, fused=True, program=prog)
        assert len(prog.jit_cache) == 1


class TestFacade:
    def test_plan_pack_compiled_matches_legacy(self):
        pl = api.plan(PAPER_EXAMPLE)
        codes = random_codes(PAPER_EXAMPLE)
        assert np.array_equal(pl.pack(codes),
                              pl.pack(codes, compiled=False))

    def test_decode_backends_agree(self):
        p = make_problem(64, [("a", 5, 64, 4), ("b", 12, 30, 8)])
        pl = api.plan(p)
        codes = random_codes(p, seed=1)
        buf = pl.pack(codes)
        outs = [
            pl.decode(buf, backend="numpy"),
            pl.decode(buf, backend="numpy", compiled=False),
            pl.decode(buf, backend="pallas"),
            pl.decode(buf, backend="pallas", fused=False),
        ]
        for out in outs:
            for name, want in codes.items():
                np.testing.assert_array_equal(out[name], want)

    def test_layer_stack_exec_program_element_granularity(self):
        """Bundle-granular programs pack >64-bit units at element width."""
        from repro.quant import QuantSpec

        class Cfg:
            name = "toy"
            d_model, d_ff = 64, 128
            n_heads, n_kv_heads, head_dim = 4, 2, 16
            n_layers = 2

        stack = api.plan_layer_stack(Cfg, QuantSpec(bits=4, group_size=32),
                                     m=4096)
        assert any(a.width > 64 for a in stack.problem.arrays)
        prog = stack.exec_program()
        assert prog.n_pieces == sum(prog.piece_depths)
        assert stack.exec_program() is prog      # cached on the layout


class TestBundlePacking:
    def test_pack_bundle_matches_legacy_merge_path(self):
        """Element-granular compiled pack == unit merge + pack_arrays."""
        from repro.core.packing import BundleTensor, pack_bundle

        rng = np.random.default_rng(0)
        bundle = [BundleTensor("w", 4, 3000, 1),
                  BundleTensor("s", 16, 200, 1),
                  BundleTensor("n", 16, 64, 0)]
        data = {b.name: rng.integers(0, 1 << b.width_bits, b.n_elems,
                                     dtype=np.uint64) for b in bundle}
        pb = pack_bundle(bundle, m=512, data=data, cache=None)
        assert all(a.width <= 64 for a in pb.problem.arrays)
        # legacy: merge elements into scheduling units, then pack_arrays
        unit_data = {}
        for spec, b in zip(pb.problem.arrays, bundle):
            unit = spec.width // b.width_bits
            vals = np.asarray(data[b.name], dtype=np.uint64)
            vals = np.pad(vals, (0, spec.depth * unit - vals.shape[0]))
            merged = np.zeros(spec.depth, dtype=np.uint64)
            for k in range(unit):
                merged |= vals[k::unit] << np.uint64(k * b.width_bits)
            unit_data[spec.name] = merged
        legacy = pack_arrays(pb.layout, unit_data)
        assert np.array_equal(pb.buffer, legacy)

    def test_wide_unit_bundle_packs_and_unpacks(self):
        """>64-bit scheduling units (m=4096) pack now — was plan-only."""
        from repro.core.packing import BundleTensor, pack_bundle

        rng = np.random.default_rng(1)
        bundle = [BundleTensor("w", 4, 5000, 1),
                  BundleTensor("s", 16, 400, 1)]
        data = {b.name: rng.integers(0, 1 << b.width_bits, b.n_elems,
                                     dtype=np.uint64) for b in bundle}
        pb = pack_bundle(bundle, m=4096, data=data, cache=None)
        assert any(a.width > 64 for a in pb.problem.arrays)
        assert pb.buffer is not None
        back = pb.unpack()
        for b in bundle:
            np.testing.assert_array_equal(back[b.name][:b.n_elems],
                                          data[b.name])
            assert (back[b.name][b.n_elems:] == 0).all()


class TestValidation:
    def test_pack_rejects_bad_inputs(self):
        lay = schedule(PAPER_EXAMPLE)
        codes = random_codes(PAPER_EXAMPLE)
        with pytest.raises(KeyError):
            pack_compiled(lay, {k: v for k, v in codes.items() if k != "A"})
        bad = dict(codes)
        bad["A"] = bad["A"][:-1]
        with pytest.raises(ValueError, match="expected"):
            pack_compiled(lay, bad)
        bad = dict(codes)
        bad["A"] = bad["A"] | np.uint64(1 << 10)     # overflows 2 bits
        with pytest.raises(ValueError, match="overflow"):
            pack_compiled(lay, bad)

    def test_bad_elem_widths_rejected(self):
        lay = schedule(PAPER_EXAMPLE)
        with pytest.raises(ValueError, match="does not divide"):
            lower_exec(lay, elem_widths=(2, 3, 4, 5, 4))
        with pytest.raises(ValueError, match="entries"):
            lower_exec(lay, elem_widths=(2, 3))

    def test_unpack_rejects_bad_buffer_shape(self):
        lay = schedule(PAPER_EXAMPLE)
        with pytest.raises(ValueError, match="buffer shape"):
            unpack_compiled(lay, np.zeros((3, 1), dtype=np.uint8))
