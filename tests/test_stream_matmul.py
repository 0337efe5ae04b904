"""Stream-direct packed matmul: layout-equivalence lockdown.

The contract under test: ``kernels.stream_matmul`` — which gathers
quantized weights *straight from the packed Iris stream* inside the
matmul prologue — must be **bit-identical** to the legacy two-pass
oracle (fused Pallas layout-decode -> lane-packed Pallas matmul), for
every quantization width, every layout strategy, ragged shapes,
lane-capped schedules and §4-style small buses.  Both kernels share the
inline dequant-prologue + ``jnp.dot`` structure, so XLA lowers their
reductions identically and exact equality is the right assertion (a
plain ``jnp.dot`` reference is *not* bit-stable at M=1, where XLA's
small-M dot lowering is fusion-sensitive — those cells get the host
reference with float tolerance instead).

For widths packed_matmul cannot lane-pack (3/5/6/7), the oracle
re-biases codes into 8-bit containers, which preserves every
dequantized float exactly — see ``conftest.two_pass_oracle``.

On a CPU backend every kernel runs in Pallas interpret mode
(``repro.kernels.backend``); the TPU is the lowering target.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from conftest import build_stream_case, two_pass_oracle
from repro.core.baselines import homogeneous_layout, naive_layout
from repro.core.exec_plan import lower_exec, pack_compiled, stream_matmul_tables
from repro.core.iris import schedule
from repro.core.packing import pad_bundle_elements
from repro.core.task import make_problem
from repro.kernels.ops import HostFallbackWarning, decode_layout_fused
from repro.kernels.ref import stream_matmul_ref
from repro.kernels.stream_matmul import stream_matmul, stream_words


def _x(m, k, seed=0):
    return jax.random.normal(jax.random.PRNGKey(seed), (m, k), jnp.float32)


def _run(case, x, **kw):
    _, _, _, prog, buf, tabs = case
    sw = stream_words(prog, buf)
    return stream_matmul(x, sw, tabs, **kw)


# ----------------------------------------------------------------------
# bit-identity vs the two-pass oracle
# ----------------------------------------------------------------------
class TestBitIdentity:
    # ragged M (incl. the fusion-sensitive M=1), non-power-of-two N,
    # K that is a non-power-of-two multiple of the group
    SHAPES = [(16, 256, 128), (7, 192, 96), (1, 384, 33)]

    @pytest.mark.parametrize("bits", [2, 3, 4, 8])
    @pytest.mark.parametrize("shape", SHAPES)
    def test_matches_two_pass_oracle(self, bits, shape):
        m, k, n = shape
        case = build_stream_case(bits, 64, k, n)
        _, _, lay, prog, buf, _ = case
        x = _x(m, k, seed=bits)
        got = np.asarray(_run(case, x))
        want = np.asarray(two_pass_oracle(x, lay, prog, buf, bits, 64, k, n))
        assert got.shape == (m, n)
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("bits", [2, 3, 8])
    def test_matches_host_reference(self, bits):
        """Float agreement with the pure-host reference (covers the
        oracle itself; tolerance because XLA may fuse differently)."""
        m, k, n = 5, 128, 40
        case = build_stream_case(bits, 32, k, n)
        _, _, _, prog, buf, tabs = case
        x = _x(m, k, seed=bits + 7)
        got = np.asarray(_run(case, x))
        sw = np.asarray(stream_words(prog, buf))
        want = np.asarray(stream_matmul_ref(
            np.asarray(x), sw, tabs.w_tab, tabs.s_tab, bits=bits,
            group_size=32))
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)

    def test_dequant_value_agreement(self):
        """Stream-direct == x @ dequantize(w): the gathered weights are
        the true quantized values, not merely self-consistent bits."""
        from repro.quant import dequantize

        k, n = 128, 24
        case = build_stream_case(4, 32, k, n)
        _, qt, _, _, _, _ = case
        x = _x(9, k, seed=3)
        got = np.asarray(_run(case, x))
        want = np.asarray(x @ dequantize(qt).astype(jnp.float32))
        np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


# ----------------------------------------------------------------------
# layout-strategy invariance
# ----------------------------------------------------------------------
class TestLayoutInvariance:
    def test_strategies_bit_identical(self):
        """Iris, homogeneous and naive layouts scatter the same elements
        to different stream addresses; the slot tables must make the
        matmul output *bit-identical* across all three — N=130 also
        exercises the padded-N lane path."""
        m, k, n, bits, g = 5, 320, 130, 3, 64
        outs = []
        x = _x(m, k, seed=11)
        for fn in (schedule, homogeneous_layout, naive_layout):
            case = build_stream_case(bits, g, k, n, layout_fn=fn)
            outs.append(np.asarray(_run(case, x)))
        np.testing.assert_array_equal(outs[0], outs[1])
        np.testing.assert_array_equal(outs[0], outs[2])
        # and the shared value is the two-pass result
        case = build_stream_case(bits, g, k, n)
        _, _, lay, prog, buf, _ = case
        want = np.asarray(two_pass_oracle(x, lay, prog, buf, bits, g, k, n,
                                          block_n=130))
        np.testing.assert_array_equal(outs[0], want)


# ----------------------------------------------------------------------
# scheduling-constraint corners: lane caps and §4-style buses
# ----------------------------------------------------------------------
class TestSchedulingCorners:
    def test_lane_capped_schedule(self):
        """max_lanes=2 (§3.3) forces deep multi-row pieces; the global
        bit offsets must still address every element exactly."""
        m, k, n, bits, g = 4, 128, 16, 4, 32
        case = build_stream_case(bits, g, k, n, m=256, max_lanes=2)
        _, _, lay, prog, buf, _ = case
        x = _x(m, k, seed=5)
        got = np.asarray(_run(case, x))
        want = np.asarray(two_pass_oracle(x, lay, prog, buf, bits, g, k, n))
        np.testing.assert_array_equal(got, want)

    @pytest.mark.parametrize("bus", [24, 40])
    def test_small_nonpow2_bus(self, bus):
        """§4-scale buses (m=24 like the worked example's 8-bit rows,
        m=40 non-power-of-two): many elements straddle u32 words."""
        m, k, n, bits, g = 3, 64, 5, 3, 32
        case = build_stream_case(bits, g, k, n, m=bus)
        _, _, lay, prog, buf, _ = case
        x = _x(m, k, seed=bus)
        got = np.asarray(_run(case, x))
        want = np.asarray(two_pass_oracle(x, lay, prog, buf, bits, g, k, n))
        np.testing.assert_array_equal(got, want)


# ----------------------------------------------------------------------
# host fallback: unit widths > 32 (satellite: HostFallbackWarning)
# ----------------------------------------------------------------------
class TestHostFallback:
    # 64 units of 40 bits = 128 elements of 20 bits, plus bf16 scales
    K, N, G = 16, 8, 8

    def _problem(self):
        return make_problem(128, [("w", 40, self.K * self.N // 2, 1),
                                  ("s", 16, (self.K // self.G) * self.N, 1)])

    def test_fused_decode_warns(self):
        """Unit widths > 32 silently fell back to host unpack before;
        now the fused decode raises HostFallbackWarning naming them."""
        from repro.core.codegen import random_codes
        from repro.kernels.ops import reset_host_fallback_warnings

        reset_host_fallback_warnings()
        p = self._problem()
        lay = schedule(p)
        buf = pack_compiled(lay, random_codes(p, seed=0))
        with pytest.warns(HostFallbackWarning) as rec:
            decode_layout_fused(lay, buf)
        w = rec[0].message
        assert ("w", 40) in w.arrays
        assert "40" in str(w) and "w" in str(w.arrays[0])

    def test_fallback_warning_rebuilds_from_args(self):
        """pytest-xdist ships a worker's warning as ``cls(*args)``; the
        rebuilt warning must carry the same (name, width) pairs."""
        w = HostFallbackWarning.for_arrays((("w", 40), ("kv/k_scales", 64)))
        rebuilt = type(w)(*w.args)
        assert rebuilt.arrays == (("w", 40), ("kv/k_scales", 64))
        assert str(rebuilt) == str(w)

    def test_fallback_warns_once_per_layout_and_array(self):
        """Serving loops decode the same layout thousands of times; the
        fallback warning fires once per (layout signature, array), not
        per call — and the reset helper re-arms it."""
        import warnings

        from repro.core.codegen import random_codes
        from repro.kernels.ops import reset_host_fallback_warnings

        reset_host_fallback_warnings()
        p = self._problem()
        lay = schedule(p)
        buf = pack_compiled(lay, random_codes(p, seed=0))
        with pytest.warns(HostFallbackWarning):
            decode_layout_fused(lay, buf)
        # further decodes of the same layout: silent
        with warnings.catch_warnings():
            warnings.simplefilter("error", HostFallbackWarning)
            decode_layout_fused(lay, buf)
            decode_layout_fused(lay, buf)
        # reset re-arms the warning for the same layout
        reset_host_fallback_warnings()
        with pytest.warns(HostFallbackWarning) as rec:
            decode_layout_fused(lay, buf)
        assert ("w", 40) in rec[0].message.arrays

    def test_stream_direct_serves_wide_units_natively(self):
        """The same layout lowered at *element* granularity (20-bit
        elements inside the 40-bit units) needs no host path at all —
        stream-direct matmul consumes it exactly."""
        rng = np.random.default_rng(1)
        k, n, g = self.K, self.N, self.G
        codes = rng.integers(0, 1 << 20, size=(k, n), dtype=np.uint64)
        scales = np.asarray(
            jax.lax.bitcast_convert_type(
                jnp.asarray(rng.normal(size=(k // g, n)), jnp.bfloat16),
                jnp.uint16)).astype(np.uint64)
        p = self._problem()
        lay = schedule(p)
        prog = lower_exec(lay, elem_widths=(20, 16))
        assert prog.host_arrays == ()          # nothing left for the host
        data = pad_bundle_elements(
            p, prog, {"w": codes.reshape(-1), "s": scales.reshape(-1)})
        buf = pack_compiled(lay, data, program=prog)
        tabs = stream_matmul_tables(lay, "w", (k, n), scales="s",
                                    group_size=g, program=prog)
        x = _x(4, k, seed=9)
        got = np.asarray(stream_matmul(x, stream_words(prog, buf), tabs))
        want = stream_matmul_ref(
            np.asarray(x), np.asarray(stream_words(prog, buf)),
            tabs.w_tab, tabs.s_tab, bits=20, group_size=g)
        np.testing.assert_allclose(got, np.asarray(want),
                                   rtol=1e-5, atol=1e-3)


# ----------------------------------------------------------------------
# validation surface
# ----------------------------------------------------------------------
class TestValidation:
    def _layout(self):
        case = build_stream_case(4, 32, 64, 8)
        return case[2], case[3]

    def test_unknown_array_name(self):
        lay, prog = self._layout()
        with pytest.raises(KeyError, match="nope"):
            stream_matmul_tables(lay, "nope", (64, 8), scales="w_scales",
                                 group_size=32, program=prog)

    def test_bad_group_size(self):
        lay, prog = self._layout()
        with pytest.raises(ValueError, match="group_size"):
            stream_matmul_tables(lay, "w", (64, 8), scales="w_scales",
                                 group_size=48, program=prog)

    def test_scale_width_must_be_bf16(self):
        lay, prog = self._layout()
        with pytest.raises(ValueError, match="16"):
            stream_matmul_tables(lay, "w", (64, 8), scales="w",
                                 group_size=32, program=prog)

    def test_shape_exceeds_capacity(self):
        lay, prog = self._layout()
        with pytest.raises(ValueError, match="pieces"):
            stream_matmul_tables(lay, "w", (64, 512), scales="w_scales",
                                 group_size=32, program=prog)

    def test_wide_weights_rejected(self):
        p = make_problem(128, [("w", 40, 64, 1), ("s", 16, 16, 1)])
        lay = schedule(p)
        with pytest.raises(ValueError, match="32"):
            stream_matmul_tables(lay, "w", (16, 8), scales="s",
                                 group_size=8)

    def test_kernel_rejects_bad_dtypes(self):
        case = build_stream_case(4, 32, 64, 8)
        _, _, _, prog, buf, tabs = case
        sw = stream_words(prog, buf)
        with pytest.raises(ValueError, match="uint32"):
            stream_matmul(_x(2, 64), sw.astype(jnp.int32), tabs)
