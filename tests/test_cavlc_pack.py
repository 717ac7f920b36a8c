"""The CAVLC frame pack's TPU form (``cavlc_device.pack_frame`` through
``ops/cabac_pack``'s two kernels, PR 31) against the bitmerge hierarchy it
replaces on the chip, which stays the CPU path and the oracle.

Tier-1, as ``test_cabac_pack`` is and for its reason: the kernels run here
in ``pltpu.force_tpu_interpret_mode()``, reached through ``pack_frame``'s own
``jax.default_backend()`` test from the served programs' ``_finish_p`` /
``_finish_cavlc``.  What Mosaic makes of them at 1920x1080 is
``tests/test_chip_compile.py``'s part, what the chip makes of them
``chip_binarize_check.py --cavlc``'s.
"""

import numpy as np
import pytest

from test_cabac_pack import _pack_case

P_KEYS = ("mv", "luma", "cb_dc", "cb_ac", "cr_dc", "cr_ac")
I_KEYS = ("luma_dc", "luma_ac", "cb_dc", "cb_ac", "cr_dc", "cr_ac",
          "pred_mode", "mb_i4", "i4_modes", "luma_i4")
_FLAT_CASES = ("desktop", "fulldamage", "all_skip", "empty_rows",
               "level_escapes", "block_overflow", "mb_overflow",
               "flat_cap_overflow", "shard_1x5", "row_of_163_pieces",
               "two_sessions")
_SMALL_CAP = 512            # words: under a (3, 5) grid's full damage


def _levels(kind, case):
    """The level tensors of one case, as ``_pack_case`` crafts them, and on
    top of its full damage: levels whose codes are 28, 30 and 32 bits (the
    escape tiers the qp = 1 checkerboard reaches), one block over its 256
    bits, one macroblock over its 2,048 with no block over, and one MB row
    of 160: 163 pieces, the count that made the row tree 256 wide."""
    if case in ("desktop", "fulldamage", "all_skip", "empty_rows",
                "shard_1x5"):
        return _pack_case(kind, case)
    if case == "row_of_163_pieces":
        return _pack_case(kind, "fulldamage", grid=(1, 160))
    args = [np.array(a) for a in _pack_case(kind, "fulldamage")]
    luma = args[1]                      # P: (R, C, 16, 16); I: (R, C, 16, 15)
    if kind == "intra":
        args[7][0, 0] = args[7][1, 2] = 0           # I_16x16 there
        args[9][0, 0] = args[9][1, 2] = 0
    if case == "level_escapes":
        luma[0, 0, 0, :3] = (8000, -4000, 2000)
        luma[1, 2, 5, :8] = (40, -2100, 2100, -2100, 2100, -2100, 2100, 9)
        args[2][2, 2] = (-9000, 5000, 2500, 0)      # chroma DC
    elif case == "block_overflow":
        luma[0, 0, 0, :] = 3000
    elif case == "mb_overflow":
        luma[0, 0] = np.where(np.arange(luma.shape[-1]) % 2, 37, -41)
    return tuple(args)


def _flat(kind, hv, hl, *levels):
    """``flat`` as the served programs finish it, from the level tensors."""
    import jax.numpy as jnp

    from docker_nvidia_glx_desktop_tpu.ops import cavlc_device, cavlc_p_device

    none = jnp.zeros((1, 1), jnp.uint8)
    lv = dict(zip(P_KEYS if kind == "p" else I_KEYS, levels),
              recon_y=none, recon_cb=none, recon_cr=none)
    if kind == "p":
        return cavlc_p_device._finish_p(lv, hv, hl, slice_qp=26)[0]
    return cavlc_device._finish_cavlc(lv, hv, hl, False, 26)


def _vmapped_grids_in_the_interpreter(mp):
    """``jax.vmap`` puts a grid dimension of its own in front of a kernel's;
    the Mosaic lowering gives it "parallel" semantics beside the kernel's
    own (``MosaicGridMapping``), the interpreter of this JAX zips the
    kernel's own against the longer grid and stops.  Do as the lowering."""
    from jax._src.pallas.mosaic.interpret import interpret_pallas_call as ip

    own = ip._get_parallel_dim_semantics
    mp.setattr(ip, "_get_parallel_dim_semantics", lambda params, n: (
        lambda sem: (True,) * (n - len(sem)) + sem)(own(params, n)))


@pytest.fixture(scope="module")
def flat_jits():
    """(form, kind, what) -> jit: the cases of one grid share a trace."""
    return {}


class TestFlatPackKernels:
    @pytest.mark.parametrize("case", _FLAT_CASES)
    @pytest.mark.parametrize("kind", ["p", "intra"])
    def test_kernels_flat_equals_bitmerge_flat(self, kind, case,
                                               monkeypatch, flat_jits):
        """The TPU's form must give the bitmerge form's ``flat`` byte for
        byte: flags, total words, every row's byte count and word offset,
        every row's bytes and the zeros behind them; where a cap is
        passed, the same flag (the host then codes the levels itself)."""
        import jax
        from jax.experimental.pallas import tpu as pltpu

        from docker_nvidia_glx_desktop_tpu.ops import cavlc_device

        if case == "flat_cap_overflow":
            monkeypatch.setattr(cavlc_device, "FLAT_CAP_WORDS", _SMALL_CAP)
        args = (_levels(kind, "fulldamage") if case.startswith(("flat_cap",
                                                                "two_"))
                else _levels(kind, case))
        nr, nc = args[0].shape[:2]
        hv, hl = cavlc_device.slice_header_slots(
            nr, nc, frame_num=3, deblocking_idc=2,
            **({"slice_type": 5, "idr": False} if kind == "p" else {}))
        body = lambda *a: _flat(kind, *a)
        if case == "two_sessions":
            # parallel/batch's steps: jax.vmap over the sessions of a chip
            others = _levels(kind, "empty_rows")
            args = tuple(np.stack(pair) for pair in zip(args, others))
            body = jax.vmap(body, in_axes=(None, None) + (0,) * len(args))
        args = (hv, hl) + args
        # jits of functions of their own: JAX keeps a trace by the function
        what = case if case.startswith(("flat_cap", "two_")) else ""
        forms = [flat_jits.setdefault(
            (form, kind, what), jax.jit(lambda *a: body(*a)))
            for form in ("bitmerge", "kernels")]
        want = np.asarray(forms[0](*args))
        with monkeypatch.context() as mp, pltpu.force_tpu_interpret_mode():
            mp.setattr(jax, "default_backend", lambda: "tpu")
            _vmapped_grids_in_the_interpreter(mp)
            got = np.asarray(forms[1](*args))
        assert got.shape == want.shape and got.dtype == np.uint8
        for got1, want1 in zip(got.reshape(-1, want.shape[-1]),
                               want.reshape(-1, want.shape[-1])):
            meta = cavlc_device.FlatMeta(want1, nr)
            assert meta.overflow == case.endswith("overflow")
            assert cavlc_device.FlatMeta(got1, nr).overflow == meta.overflow
            if meta.overflow:
                continue
            assert meta.total_words > 0
            np.testing.assert_array_equal(got1, want1)
