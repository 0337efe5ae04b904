"""Per-kernel tests: shape/dtype sweeps asserting allclose vs ref.py oracles.

All Pallas kernels run in interpret mode (CPU backend; TPU is the
lowering target)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.baselines import homogeneous_layout, naive_layout
from repro.core.codegen import pack_arrays, random_codes
from repro.core.iris import schedule
from repro.kernels.layout_decode import decode_slot
from repro.kernels.ops import buffer_to_u32, decode_layout
from repro.kernels.packed_matmul import packed_matmul
from repro.kernels.ref import decode_layout_ref, decode_slot_ref, packed_matmul_ref
from repro.quant import QuantSpec, dequantize, pack_codes_u32, quantize, unpack_codes_u32


# ----------------------------------------------------------------------
# layout_decode
# ----------------------------------------------------------------------
class TestDecodeSlot:
    @pytest.mark.parametrize("width", [1, 3, 4, 7, 8, 12, 16, 17, 31, 32])
    @pytest.mark.parametrize("n_rows", [1, 7, 256, 300])
    def test_width_row_sweep(self, width, n_rows):
        rng = np.random.default_rng(width * 1000 + n_rows)
        words = 6
        rows = rng.integers(0, 1 << 32, size=(n_rows, words), dtype=np.uint64)
        rows = rows.astype(np.uint32)
        # a handful of in-bounds lane offsets (must fit within words-1 words
        # so the funnel shift's second word exists)
        max_off = (words - 1) * 32 - width
        offsets = tuple(sorted(rng.integers(0, max_off, size=3).tolist()))
        got = decode_slot(jnp.asarray(rows), offsets=offsets, width=width,
                          n_rows=n_rows)
        want = decode_slot_ref(rows, offsets, width, n_rows)
        np.testing.assert_array_equal(np.asarray(got), want)

    def test_word_straddling_offsets(self):
        """Elements crossing u32 word boundaries must funnel-shift exactly."""
        rng = np.random.default_rng(0)
        rows = rng.integers(0, 1 << 32, size=(64, 4), dtype=np.uint64)
        rows = rows.astype(np.uint32)
        for width in (17, 24, 31):
            off = 32 - (width // 2)          # deliberately straddles
            got = decode_slot(jnp.asarray(rows), offsets=(off,), width=width,
                              n_rows=64)
            want = decode_slot_ref(rows, (off,), width, 64)
            np.testing.assert_array_equal(np.asarray(got), want)


class TestDecodeLayout:
    # shared with the golden-file suite via conftest
    from conftest import DECODE_PROBLEMS as PROBLEMS

    @pytest.mark.parametrize("prob_idx", range(len(PROBLEMS)))
    @pytest.mark.parametrize("layout_fn", [schedule, homogeneous_layout,
                                           naive_layout])
    def test_roundtrip_through_kernel(self, prob_idx, layout_fn):
        p = self.PROBLEMS[prob_idx]
        lay = layout_fn(p)
        lay.validate()
        codes = random_codes(p, seed=prob_idx)
        buf = pack_arrays(lay, codes)
        ref = decode_layout_ref(lay, buf)
        got = decode_layout(lay, buf)
        for name, want in codes.items():
            np.testing.assert_array_equal(
                np.asarray(got[name], dtype=np.uint64), ref[name])
            np.testing.assert_array_equal(ref[name], want)

    def test_buffer_to_u32_layout(self):
        buf = np.arange(32, dtype=np.uint8).reshape(2, 16)
        w = np.asarray(buffer_to_u32(buf))
        assert w.shape == (2, 6)          # 4 data words + 2 spare
        assert w[0, 0] == 0x03020100      # little-endian
        assert w[1, 0] == 0x13121110
        assert (w[:, 4:] == 0).all()


# ----------------------------------------------------------------------
# packed_matmul
# ----------------------------------------------------------------------
class TestPackedMatmul:
    @pytest.mark.parametrize("bits", [2, 4, 8])
    @pytest.mark.parametrize("shape", [(16, 256, 128), (128, 512, 256),
                                       (8, 1024, 128)])
    def test_bits_shape_sweep(self, bits, shape):
        m, k, n = shape
        spec = QuantSpec(bits=bits, group_size=128)
        key = jax.random.PRNGKey(bits)
        w = jax.random.normal(key, (k, n), dtype=jnp.float32)
        qt = quantize(w, spec)
        pw = pack_codes_u32(qt.codes, bits)
        x = jax.random.normal(jax.random.PRNGKey(1), (m, k), jnp.float32)
        got = packed_matmul(x, pw, qt.scales, bits=bits, group_size=128,
                            block_m=min(128, m), block_k=256)
        want = packed_matmul_ref(x, pw, qt.scales, bits=bits, group_size=128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)

    @pytest.mark.parametrize("x_dtype", [jnp.float32, jnp.bfloat16])
    def test_input_dtypes(self, x_dtype):
        spec = QuantSpec(bits=4, group_size=64)
        w = jax.random.normal(jax.random.PRNGKey(2), (256, 128), jnp.float32)
        qt = quantize(w, spec)
        pw = pack_codes_u32(qt.codes, 4)
        x = jax.random.normal(jax.random.PRNGKey(3), (32, 256)).astype(x_dtype)
        got = packed_matmul(x, pw, qt.scales, bits=4, group_size=64,
                            block_m=32, block_k=128)
        want = packed_matmul_ref(x, pw, qt.scales, bits=4, group_size=64)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=2e-2, atol=2e-2)

    def test_matches_dense_dequant_matmul(self):
        """End to end: packed path == x @ dequantize(quantize(w))."""
        spec = QuantSpec(bits=4, group_size=128)
        w = jax.random.normal(jax.random.PRNGKey(4), (512, 256), jnp.float32)
        qt = quantize(w, spec)
        x = jax.random.normal(jax.random.PRNGKey(5), (64, 512), jnp.float32)
        got = packed_matmul(x, pack_codes_u32(qt.codes, 4), qt.scales,
                            bits=4, group_size=128)
        want = x @ dequantize(qt)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-4, atol=1e-3)

    @pytest.mark.parametrize("m", [1, 5, 37, 100])
    def test_ragged_m_padded_internally(self, m):
        """Serving batch sizes are ragged: M need not tile by block_m."""
        spec = QuantSpec(bits=4, group_size=64)
        w = jax.random.normal(jax.random.PRNGKey(6), (256, 128), jnp.float32)
        qt = quantize(w, spec)
        pw = pack_codes_u32(qt.codes, 4)
        x = jax.random.normal(jax.random.PRNGKey(7), (m, 256), jnp.float32)
        got = packed_matmul(x, pw, qt.scales, bits=4, group_size=64,
                            block_m=64, block_k=128)
        want = packed_matmul_ref(x, pw, qt.scales, bits=4, group_size=64)
        assert got.shape == (m, 128)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-4)

    def test_bad_shapes_rejected(self):
        x = jnp.zeros((32, 256))
        pw = jnp.zeros((256 * 4 // 32, 128), jnp.uint32)
        s = jnp.ones((2, 128))
        with pytest.raises(ValueError):
            packed_matmul(x, pw, s, bits=4, group_size=100)
        with pytest.raises(ValueError):
            packed_matmul(x, jnp.zeros((3, 128), jnp.uint32), s, bits=4,
                          group_size=128)
        # genuinely invalid N tiling still errors
        with pytest.raises(ValueError):
            packed_matmul(x, pw, jnp.ones((2, 128)), bits=4, group_size=128,
                          block_n=96)


# ----------------------------------------------------------------------
# quantization substrate
# ----------------------------------------------------------------------
class TestQuant:
    @pytest.mark.parametrize("bits", [2, 3, 4, 5, 8])
    def test_roundtrip_error_bound(self, bits):
        spec = QuantSpec(bits=bits, group_size=64)
        w = jax.random.normal(jax.random.PRNGKey(bits), (256, 64), jnp.float32)
        qt = quantize(w, spec)
        wd = dequantize(qt)
        # symmetric grid: |err| <= scale/2, plus bf16 scale rounding which
        # perturbs every dequantized value by up to |q| * scale * 2^-8
        g = 256 // 64
        amax = np.abs(np.asarray(w).reshape(g, 64, 64)).max(axis=1)
        bound = (amax / spec.qmax) * 0.5 + amax * 2.0 ** -7 + 1e-6
        err = np.abs(np.asarray(wd - w)).reshape(g, 64, 64).max(axis=1)
        assert (err <= bound).all()

    @pytest.mark.parametrize("bits", [2, 4, 8])
    def test_lane_pack_roundtrip(self, bits):
        rng = np.random.default_rng(bits)
        k, n = 128, 32
        codes = rng.integers(0, 1 << bits, size=(k, n)).astype(np.uint8)
        packed = pack_codes_u32(jnp.asarray(codes), bits)
        assert packed.shape == (k * bits // 32, n)
        back = unpack_codes_u32(packed, bits, k)
        np.testing.assert_array_equal(np.asarray(back), codes)

    def test_bad_specs(self):
        with pytest.raises(ValueError):
            QuantSpec(bits=1)
        with pytest.raises(ValueError):
            QuantSpec(bits=9)
        with pytest.raises(ValueError):
            pack_codes_u32(jnp.zeros((128, 8), jnp.uint8), 3)
