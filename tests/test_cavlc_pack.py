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

# sha256 of ``flat`` (its first 16 hex digits) from these level tensors, as
# ``flat_digests()`` made them on the tree of commit 8d589af (PR 44), with
# that tree's package on the path: the slot builders numbered their blocks
# macroblock-major there (PR 45 turned the order round, in the builders and
# in both forms of the pack at once)
_DIGEST_CASES = ("desktop", "fulldamage", "level_escapes",
                 "row_of_163_pieces")
_DIGEST_KEYS = [(kind, case) for kind in ("p", "intra")
                for case in _DIGEST_CASES]
_PARENT_FLAT = dict(zip(_DIGEST_KEYS, """
    1b50bab6d87b1884 5134c2f9e639e30d 7423715ef5a7d5fd f64984f9c60f0355
    bd2c2750fb53948e e4ffbee66158f610 1768757311963b60 b55f8e39d65ad88d
    """.split()))


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


def _header_slots(kind, nr, nc):
    from docker_nvidia_glx_desktop_tpu.ops import cavlc_device

    return cavlc_device.slice_header_slots(
        nr, nc, frame_num=3, deblocking_idc=2,
        **({"slice_type": 5, "idr": False} if kind == "p" else {}))


def flat_digests(jits=None, keys=_DIGEST_KEYS):
    """(kind, case) -> digest of the bitmerge form's ``flat``."""
    import hashlib

    import jax

    jits = {} if jits is None else jits
    out = {}
    for kind, case in keys:
        args = _levels(kind, case)
        fn = jits.setdefault(("bitmerge", kind, ""), jax.jit(
            lambda *a, kind=kind: _flat(kind, *a)))
        flat = np.asarray(fn(*_header_slots(kind, *args[0].shape[:2]),
                             *args))
        out[kind, case] = hashlib.sha256(flat.tobytes()).hexdigest()[:16]
    return out


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
    @pytest.mark.parametrize("kind,case", _DIGEST_KEYS)
    def test_flat_is_what_the_macroblock_major_builders_gave(
            self, kind, case, flat_jits):
        """The builders and both forms of the pack changed their order of
        blocks in one PR, and the test below holds the two forms to each
        other only: they could drift TOGETHER.  So ``flat`` is held to the
        bytes the tree before gave for the same level tensors."""
        assert flat_digests(flat_jits, [(kind, case)]) == {
            (kind, case): _PARENT_FLAT[kind, case]}

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
        hv, hl = _header_slots(kind, nr, nc)
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


@pytest.mark.parametrize("order", ["reversed", "shuffled", "block_major"])
def test_code_blocks_knows_no_order_of_its_blocks(order):
    """``code_blocks`` codes each of its N blocks by itself: a permutation
    of the blocks (with their nC, kind and maxNumCoeff) permutes the rows
    of its (N, 34) values and lengths and changes nothing else, which is
    why the builders may number their blocks as the packer wants them
    (``block_major``: 26 blocks of 15 macroblocks turned from
    macroblock-major, the permutation PR 45 made)."""
    from docker_nvidia_glx_desktop_tpu.ops import cavlc_device

    nblk, nmb = 26, 15
    n = nblk * nmb
    rng = np.random.default_rng(45)
    max_coeff = rng.choice([4, 15, 16], n)
    levels = rng.integers(-3, 4, (n, 16)) * (rng.integers(0, 3, (n, 16)) == 0)
    levels[::7, :3] = (2100, -40, 9)                    # escapes among them
    levels[::11] = 0                                    # and empty blocks
    levels *= np.arange(16) < max_coeff[:, None]
    nc = rng.integers(0, 17, n)
    perm = {"reversed": np.arange(n)[::-1],
            "shuffled": rng.permutation(n),
            "block_major": np.arange(n).reshape(nmb, nblk).T.ravel()}[order]
    code = lambda idx: [np.asarray(a) for a in cavlc_device.code_blocks(
        levels[idx], nc[idx], max_coeff[idx] == 4, max_coeff[idx])]
    values, lengths = code(np.arange(n))
    assert lengths.shape == (n, cavlc_device.BLOCK_SLOTS) and lengths.any(1).all()
    got_values, got_lengths = code(perm)
    np.testing.assert_array_equal(got_values, values[perm])
    np.testing.assert_array_equal(got_lengths, lengths[perm])
