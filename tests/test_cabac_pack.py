"""The CABAC record packer's TPU kernels (``ops/cabac_pack``: slot ->
macroblock by a compress network, macroblock -> row -> frame by a scalar
walk) against the XLA packer they replace on the chip, which stays the CPU
path and the oracle.

Tier-1 on purpose (``test_cabac_device`` is a slow module): the kernels run
here in ``pltpu.force_tpu_interpret_mode()``, reached through
``_pack_stream``'s own ``jax.default_backend()`` test, as the loop filter's
kernel is in ``test_cabac_device.TestDeblockKernel``.  What Mosaic makes of
them at 1920x1080 is ``tests/test_chip_compile.py``'s part.
"""

import numpy as np
import pytest

import conftest
from test_cabac_device import _p_levels, _yuv

# (the 240-column programs are 40 s of trace and XLA:CPU compile each: the
# slow tier's; tier-1 holds ``pack_rows`` itself to 240 columns below)
_PACK_CASES = ("desktop", "fulldamage", "all_skip", "empty_rows",
               "extreme_levels", "overflow", "shard_1x5",
               pytest.param("rows_of_240", marks=pytest.mark.slow))


@pytest.fixture(scope="module")
def kernel_jits():
    """kind -> the jit these tests trace on the TPU's branch, and reuse: a
    case is 6 s of interpreter, a trace 8 to 25 s more."""
    return {}


def _pack_case(kind, case, grid=None):
    """Level tensors for one packer case (the arguments of ``binarize_p`` /
    ``binarize_intra``): real stage output for the desktop, crafted (3, 5)
    grids (a column count that is no power of two) for the rest, one MB
    row of 5 for a spatial shard, and two rows of 240, a 3840-wide picture's
    (full damage: kernel B's row buffer at its longest); ``grid``: another
    (rows, columns)."""
    import jax.numpy as jnp

    from docker_nvidia_glx_desktop_tpu.ops import h264_device

    rng = np.random.default_rng(sum(map(ord, kind + case)))
    if case == "desktop":
        if kind == "p":
            out = _p_levels(qp=26)
            return tuple(np.asarray(out[k]) for k in (
                "mv", "luma", "cb_dc", "cb_ac", "cr_dc", "cr_ac"))
        f0 = _yuv(conftest.make_test_frame(96, 128, seed=5), 128, 96)
        lv = h264_device.encode_intra_frame_yuv(
            *[jnp.asarray(p) for p in f0], 26)
        return tuple(np.asarray(lv[k]) for k in (
            "luma_dc", "luma_ac", "cb_dc", "cb_ac", "cr_dc", "cr_ac",
            "pred_mode", "mb_i4", "i4_modes", "luma_i4"))
    nr, nc = grid or {"shard_1x5": (1, 5),
                      "rows_of_240": (2, 240)}.get(case, (3, 5))
    rows = {"all_skip": [], "empty_rows": [0, 2]}.get(case, range(nr))
    z = lambda *shape: np.zeros((nr, nc) + shape, np.int32)

    def fill(a, lo, hi, one_in=1):
        for r in rows:
            keep = rng.integers(0, one_in, a[r].shape) == 0
            a[r] = rng.integers(lo, hi + 1, a[r].shape) * keep
        return a

    sparse = 1 if case in ("fulldamage", "rows_of_240") else 3
    cb_dc, cr_dc = fill(z(4), -9, 9), fill(z(4), -9, 9, sparse)
    cb_ac, cr_ac = fill(z(4, 15), -2, 2, sparse), fill(z(4, 15), -2, 2, 4)
    if kind == "p":
        mv, luma = fill(z(2), -39, 39), fill(z(16, 16), -3, 3, sparse)
        if case == "extreme_levels":
            luma[0, 0, 0, 0], luma[0, 0, 0, 5] = 141, -141
            luma[2, 1, 3, :] = rng.integers(-20, 21, 16)
            cb_dc[2, 2] = (16398, -16398, 700, 0)    # two-slot DC suffixes
        if case == "overflow":
            luma[0, 0, 0, 0] = 500      # test_p_overflow_flag_on_giant_level
        return mv, luma, cb_dc, cb_ac, cr_dc, cr_ac
    mb_i4 = fill(z(), 0, 1)
    i16 = (1 - mb_i4)[..., None]
    luma_dc = fill(z(16), -30, 30, sparse) * i16
    luma_ac = fill(z(16, 15), -3, 3, sparse) * i16[..., None]
    luma_i4 = fill(z(16, 16), -4, 4, sparse) * mb_i4[..., None, None]
    if case == "extreme_levels":
        mb_i4[0, 0] = 0
        luma_dc[0, 0, :4] = (16398, -16398, 15, -9000)
        luma_ac[0, 0, 2, 0] = -141
        cr_dc[1, 1] = (-16398, 1, 0, 2000)
    if case == "overflow":
        mb_i4[0, 0] = 0
        luma_ac[0, 0, 0, 0] = 500
    return (luma_dc, luma_ac, cb_dc, cb_ac, cr_dc, cr_ac, fill(z(), 0, 3),
            mb_i4, fill(z(16), 0, 8), luma_i4)


def _bit_string_slots(widest, cols):
    """(values, lengths, words a macroblock, words in all) of ``pack_rows``'
    own cases: two rows of ``cols`` macroblocks of 37 slots of up to
    ``widest`` bits, some of them empty and one macroblock empty."""
    rng = np.random.default_rng(widest)
    r, c, s = 2, cols, 37
    lns = rng.integers(widest - 5, widest + 1, (r, c, s))
    lns *= rng.integers(0, 8, (r, c, s)) > 0            # some empty
    lns[1, 1] = 0                                       # an empty MB
    vals = rng.integers(0, 1 << 26, (r, c, s)) & ((1 << lns) - 1)
    # a macroblock's piece: a line of a chunk, or whole chunks
    cap, out_words = (64 if widest == 32 else 200), 512 * -(-cols // 3)
    return vals, lns, cap, out_words


class TestPackKernels:
    @pytest.mark.parametrize("case", _PACK_CASES)
    @pytest.mark.parametrize("kind", ["p", "intra"])
    def test_kernels_buffer_equals_xla_packer(self, kind, case,
                                              monkeypatch, kernel_jits):
        """The TPU packer (``ops/cabac_pack``: two Pallas kernels, here in
        interpret mode and reached through ``_pack_stream``'s own backend
        test) must give the XLA packer's transport buffer word for word:
        header, row bit table, every payload word up to ``head[2]``, and
        the zeros behind them; on the overflow input, the same flag."""
        import jax
        from jax.experimental.pallas import tpu as pltpu

        from docker_nvidia_glx_desktop_tpu.ops import cabac_binarize as cb

        args = _pack_case(kind, case)
        fn = cb.binarize_p if kind == "p" else cb.binarize_intra
        want = np.asarray(fn(*args))
        # a jit of a function of its own: JAX keeps traces by the function,
        # whichever jit asks, and ``fn`` holds the CPU's by now
        body = kernel_jits.setdefault(
            kind, jax.jit(lambda *a: fn.__wrapped__(*a)))
        with monkeypatch.context() as mp, pltpu.force_tpu_interpret_mode():
            mp.setattr(jax, "default_backend", lambda: "tpu")
            got = np.asarray(body(*args))
        assert got.shape == want.shape
        assert int(got[1]) == int(want[1]) == (case == "overflow")
        if case == "overflow":
            return
        rows = args[0].shape[0]
        assert int(want[3]) == rows and int(want[2]) > 0
        if case in ("all_skip", "empty_rows") and kind == "p":
            # an empty row is 120 skip flags and the end of slice: short
            assert int(want[cb.META_WORDS + rows - 2]) < 32 * args[0].shape[1]
        n = cb.META_WORDS + rows + int(want[2])
        np.testing.assert_array_equal(got[:n], want[:n])
        np.testing.assert_array_equal(got[n:], want[n:])

    @pytest.mark.parametrize("cols", [3, 240])
    @pytest.mark.parametrize("widest", [26, 32])
    def test_pack_rows_against_a_bit_string(self, widest, cols):
        """``pack_rows`` itself on slots up to its stated widths (a value of
        at most 26 bits under a length of at most 32: CAVLC's level escapes
        are 28, 30 and 32), against the bits written out one after the
        other.  Eight 32-bit slots behind a phase reach into a ninth word,
        which is the next group's first: the case must hold such groups.
        240 columns are a 3840-wide picture's rows: kernel B's walk and
        row buffer at the length the 4K deployment gives them."""
        import jax
        from jax.experimental.pallas import tpu as pltpu

        from docker_nvidia_glx_desktop_tpu.ops import cabac_pack

        vals, lns, cap, out_words = _bit_string_slots(widest, cols)
        r, c, _ = lns.shape
        want = np.zeros(out_words, np.uint32)
        word, spills = 0, 0
        for i in range(r):
            bits = "".join(format(int(v), "b").zfill(int(n))
                           for v, n in zip(vals[i].ravel(), lns[i].ravel())
                           if n)
            bits += "0" * (-len(bits) % 32)
            row = [int(bits[k:k + 32], 2) for k in range(0, len(bits), 32)]
            want[word:word + len(row)] = row
            word += len(row)
            ends = np.cumsum(np.pad(lns[i], ((0, 0), (0, 3))).reshape(c, -1, 8)
                             .sum(-1).ravel())
            spills += int(((ends - np.diff(ends, prepend=0)) % 32
                           + np.diff(ends, prepend=0) > 256).sum())
        assert (spills > 0) == (widest == 32)
        with pltpu.force_tpu_interpret_mode():
            overflow, row_bits, payload = jax.jit(
                cabac_pack.pack_rows, static_argnums=(3, 4))(
                    vals.astype(np.uint32), lns.astype(np.int32),
                    np.zeros((r, c), bool), cap, out_words)
        assert not bool(overflow)
        np.testing.assert_array_equal(np.asarray(row_bits),
                                      lns.sum((1, 2)))
        np.testing.assert_array_equal(np.asarray(payload), want)

    @pytest.mark.parametrize("cols", [3, 240])
    @pytest.mark.parametrize("widest", [26, 32])
    def test_slot_major_entry_equals_the_front(self, widest, cols):
        """``pack_rows_slot_major`` (PR 45: the CAVLC slot builder hands
        its slots over as kernel A reads them, ``slot_words`` of shape
        (S, R * C)) against the (R, C, S) front on the same slots: the
        flag, every row's bit count and the payload word for word, on the
        cases above and on one that passes a cap (the flag must be the
        same flag)."""
        import jax
        from jax.experimental.pallas import tpu as pltpu

        from docker_nvidia_glx_desktop_tpu.ops import cabac_pack

        vals, lns, cap, out_words = _bit_string_slots(widest, cols)
        r, c, s = lns.shape
        vals, lns = vals.astype(np.uint32), lns.astype(np.int32)
        slots = np.moveaxis(np.asarray(cabac_pack.slot_words(vals, lns)),
                            2, 0).reshape(s, r * c)
        front = jax.jit(cabac_pack.pack_rows, static_argnums=(3, 4))
        entry = jax.jit(cabac_pack.pack_rows_slot_major,
                        static_argnums=(2, 3))
        ovf = np.zeros((r, c), bool)
        with pltpu.force_tpu_interpret_mode():
            for words in (out_words, 64):
                want = front(vals, lns, ovf, cap, words)
                got = entry(slots, ovf, cap, words)
                assert bool(want[0]) == (words == 64)
                for g, w in zip(got, want):
                    np.testing.assert_array_equal(np.asarray(g),
                                                  np.asarray(w))
