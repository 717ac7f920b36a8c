"""The served path's programs, compiled at 1920x1080 for a DESCRIBED v5e
chip (``jax.experimental.topologies``; no chip attached, nothing runs).

What the TPU compiler would refuse on the chip machine — a program that
does not fit 16 GB, a donation it cannot alias, a sharding it cannot
partition — it refuses here, at no chip time.  The shapes are the real
call shapes: the intra step as ``H264Encoder._submit_device`` issues it
(recon kept for the GOP, qp a traced scalar), the P step as
``_submit_p_device`` issues it (reference ring donated, as it resolves
under ``JAX_PLATFORMS=tpu``),
the in-loop deblock of a P frame on the TPU's schedule, the Pallas
kernel compiled by Mosaic (a ``jax.default_backend()`` branch would pick
the CPU's form here, so the test answers "tpu" while the programs are
lowered), the two CABAC binarize programs with the record packer's two
kernels (``ops/cabac_pack``, chosen the same way; since PR 31 the CAVLC
frame pack of the intra and the P step is the same two), the P picture's
binarize program and the loop filter again at 3840x2176 (the 4K
deployment's 240 macroblocks a row: a new shape is a new compile), a damage
mask's row program at a bucket of 8 of the 68 rows (``ops/damage_mask``:
the P step's stages under ``jax.vmap`` over row bands, the packer and the
loop filter over the worklist's rows, the recon scattered into the donated
ring; qp traced), the same worklist through the CABAC stream's row
program and the binarizer over that band of 8 rows (PR 43), the (4,1)
session-mesh step of ``TPU_SESSIONS``/``TPU_MESH`` on a ``Mesh`` of the
four described devices, and a P step of two sessions a chip (``jax.vmap``
over the kernels), the content statistics' program at 2560x1600 and
3840x2176, whose temporaries are read (PR 44), and the dense P step again
at 2560x1600, whose buffers under the slot builder and the pack are read
one by one (PR 45).

Tier-1 on purpose (not in conftest's ``_SLOW_MODULES``).  Only one
process may hold the TPU library, so everything that touches the
topology lives in module-scoped fixtures of THIS file — nothing at
import, in a ``skipif``, a ``parametrize`` argument or ``conftest.py`` —
and the compiles run in this process, one after the other, with the
persistent cache off around them (a described-topology entry can be
written but never read back).
"""

import math
import os
import re

import pytest

H, W, QP = 1088, 1920, 26          # 1080p padded to MB rows; the base qp
HBM_BYTES = 16e9                   # one v5e chip


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_persistent_cache():
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", prev)
    cc.reset_cache()


@pytest.fixture(scope="module")
def programs(topo, no_persistent_cache):
    """name -> compiled program (or the exception its compile raised)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import (NamedSharding, PartitionSpec as P,
                              SingleDeviceSharding)

    from docker_nvidia_glx_desktop_tpu.ops import (cabac_binarize,
                                                   cavlc_device,
                                                   cavlc_p_device,
                                                   content_stats,
                                                   damage_mask,
                                                   h264_deblock)
    from docker_nvidia_glx_desktop_tpu.parallel import batch

    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one),
            tree)

    y = jax.ShapeDtypeStruct((H, W), jnp.uint8, sharding=one)
    c = jax.ShapeDtypeStruct((H // 2, W // 2), jnp.uint8, sharding=one)
    hv_np, hl_np = cavlc_device.slice_header_slots(
        H // 16, W // 16, frame_num=0, idr_pic_id=0, qp_delta=0,
        deblocking_idc=2)
    hv, hl = on_chip((jax.ShapeDtypeStruct(hv_np.shape, hv_np.dtype),
                      jax.ShapeDtypeStruct(hl_np.shape, hl_np.dtype)))

    qp = jax.ShapeDtypeStruct((), jnp.int32, sharding=one)   # traced
    lowered = {}
    p_body = cavlc_p_device.encode_p_cavlc_frame.__wrapped__
    p_args = (y, c, c, y, c, c, hv, hl, qp)
    # every program on the branch a TPU backend picks, through functions
    # of their own: JAX keeps a trace by the function, and another test
    # may have left the CPU's there
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(jax, "default_backend", lambda: "tpu")
        # H264Encoder._submit_device: host-converted planes, recon kept
        intra_body = cavlc_device.encode_intra_cavlc_frame_yuv.__wrapped__
        lowered["intra"] = jax.jit(lambda *a: intra_body(
            *a, with_recon=True, i16_modes="auto", tune="off")).lower(
                y, c, c, hv, hl, qp)
        # H264Encoder._submit_p_device with the ring donated (RING_DONATE
        # is resolved from JAX_PLATFORMS at import and is () in this
        # CPU-held process, so the donated jit is rebuilt here from the
        # same body: ref_y, ref_cb, ref_cr)
        lowered["p"] = jax.jit(
            lambda *a: p_body(*a, "off", None, False),
            donate_argnums=(3, 4, 5)).lower(*p_args)
        _flat, ry, rcb, rcr, mv, nnz, _lv = on_chip(
            jax.eval_shape(lambda *a: p_body(*a, "off", None, False),
                           *p_args))
        # H264Encoder._submit_p_masked: a worklist of 8 rows, the loop
        # filter inside the program, the ring donated as above
        work = on_chip((jax.ShapeDtypeStruct((8,), jnp.int32),
                        jax.ShapeDtypeStruct((8,) + hv_np.shape[1:],
                                             hv_np.dtype),
                        jax.ShapeDtypeStruct((8,) + hl_np.shape[1:],
                                             hl_np.dtype)))
        rows_body = damage_mask.row_step(8).__wrapped__
        lowered["rows_b8"] = jax.jit(
            lambda *a: rows_body(*a, tune="off", next_y=None,
                                 p_intra=False, deblock=True),
            donate_argnums=(3, 4, 5)).lower(y, c, c, y, c, c, *work, qp)
        # H264Encoder._submit_cabac_p_masked (PR 43): the same worklist
        # through the CABAC stream's row step (no slots, no pack; the loop
        # filter inside, the ring donated), and the binarizer over the
        # band of 8 rows it hands on
        rows_cabac_body = damage_mask.row_step_cabac(8).__wrapped__
        cabac_work = (y, c, c, y, c, c, work[0], qp)
        lowered["rows_cabac_b8"] = jax.jit(
            lambda *a: rows_cabac_body(*a, deblock=True),
            donate_argnums=(3, 4, 5)).lower(*cabac_work)
        band = on_chip(jax.eval_shape(
            lambda *a: rows_cabac_body(*a, deblock=True), *cabac_work))
        lowered["binarize_band8"] = jax.jit(
            lambda *a: cabac_binarize.binarize_p.__wrapped__(*a)).lower(
                band[3], *(band[4][k] for k in (
                    "luma", "cb_dc", "cb_ac", "cr_dc", "cr_ac")))
        # H264Encoder._deblock as the served path calls it (traced qp)
        lowered["deblock_p"] = jax.jit(
            h264_deblock.deblock_frame.__wrapped__).lower(
                ry, rcb, rcr, qp, nnz_blk=nnz, mv=mv)
        # H264Encoder._submit_cabac_p / _submit_cabac_intra: the record
        # stream of a P picture and of an IDR, packed by the two kernels
        lv = lambda *shape: jax.ShapeDtypeStruct(
            (H // 16, W // 16) + shape, jnp.int32, sharding=one)
        chroma = (lv(4), lv(4, 15), lv(4), lv(4, 15))
        lowered["binarize_p"] = jax.jit(
            lambda *a: cabac_binarize.binarize_p.__wrapped__(*a)).lower(
                lv(2), lv(16, 16), *chroma)
        lowered["binarize_intra"] = jax.jit(
            lambda *a: cabac_binarize.binarize_intra.__wrapped__(*a)).lower(
                lv(16), lv(16, 15), *chroma, lv(), lv(), lv(16), lv(16, 16))
        # desk2160-cabac (PR 32): the two places whose kernels read a row
        # of 240 macroblocks from their shapes: the record packer (kernel
        # A's tiles, kernel B's row buffer) and the loop filter
        h4, w4 = 2176, 3840
        at4 = lambda dt, *shape: jax.ShapeDtypeStruct(
            (h4 // 16, w4 // 16) + shape, dt, sharding=one)
        lowered["binarize_p_2160"] = jax.jit(
            lambda *a: cabac_binarize.binarize_p.__wrapped__(*a)).lower(
                at4(jnp.int8, 2), at4(jnp.int32, 16, 16),
                *[at4(jnp.int32, *sh) for sh in ((4,), (4, 15)) * 2])
        y4, c4 = (jax.ShapeDtypeStruct((h4 // d, w4 // d), jnp.uint8,
                                       sharding=one) for d in (1, 2))
        lowered["deblock_p_2160"] = jax.jit(
            lambda *a, **kw: h264_deblock.deblock_frame.__wrapped__(
                *a, **kw)).lower(y4, c4, c4, qp,
                                 nnz_blk=at4(jnp.bool_, 4, 4),
                                 mv=at4(jnp.int32, 2))
        # H264Encoder._content_submit beside a dense P frame: the luma,
        # the one before, the recon, the vectors and the five level
        # tensors as the P program hands them on, no intra map
        for hs, ws in ((1600, 2560), (h4, w4)):
            mb = lambda dt, *shape: jax.ShapeDtypeStruct(
                (hs // 16, ws // 16) + shape, dt, sharding=one)
            ys = jax.ShapeDtypeStruct((hs, ws), jnp.uint8, sharding=one)
            lowered[f"frame_stats_{ws}x{hs}"] = jax.jit(
                lambda *a: content_stats.frame_stats.__wrapped__(
                    *a, None, 512)).lower(
                        ys, ys, ys, mb(jnp.int8, 2),
                        tuple(mb(jnp.int16, *sh) for sh in (
                            (16, 16), (4,), (4, 15), (4,), (4, 15))))
        # the dense P step of the two device-paced 1600p cells (PR 45):
        # the slot builder's hand-over to the pack kernels is read there
        hv16, hl16 = (jax.ShapeDtypeStruct((100,) + a.shape[1:], a.dtype,
                                           sharding=one)
                      for a in (hv_np, hl_np))
        y16, c16 = (jax.ShapeDtypeStruct((1600 // d, 2560 // d), jnp.uint8,
                                         sharding=one) for d in (1, 2))
        lowered["p_2560x1600"] = jax.jit(
            lambda *a: p_body(*a, "off", None, False),
            donate_argnums=(3, 4, 5)).lower(
                y16, c16, c16, y16, c16, c16, hv16, hl16, qp)
        # web/multisession: four 1080p sessions, one per chip
        mesh = batch.make_mesh((4, 1), topo.devices)
        planes = lambda m, n: tuple(
            jax.ShapeDtypeStruct((n, H // d, W // d), jnp.uint8,
                                 sharding=NamedSharding(
                                     m, P("session", "spatial", None)))
            for d in (1, 2, 2))
        step, _rows = batch.h264_batch_encode_step(mesh, H, W, qp=QP)
        lowered["mesh41"] = jax.jit(step).lower(*planes(mesh, 4))
        # two sessions a chip: the P step's jax.vmap is then a grid
        # dimension of kernel A and a loop round kernel B
        mesh2 = batch.make_mesh((1, 1), topo.devices[:1])
        rows = NamedSharding(mesh2, P("spatial", None))
        step, _rows = batch.h264_p_batch_step(mesh2, H, W, qp=QP)
        lowered["p_two_sessions"] = jax.jit(step).lower(
            *planes(mesh2, 2), *planes(mesh2, 2),
            jax.ShapeDtypeStruct(hv_np.shape, hv_np.dtype, sharding=rows),
            jax.ShapeDtypeStruct(hl_np.shape, hl_np.dtype, sharding=rows))

    def compile_one(item):
        name, low = item
        try:
            return name, low.compile()
        except Exception as e:          # re-raised by the test that owns it
            return name, e

    # One at a time, on this thread: six TPU compiles side by side
    # overflow the stack of the installed libtpu's compiler (SIGSEGV in
    # TpuBroadcastRewriter; PERF.md Findings, PR 22), and two side by
    # side still died in 2 of 5 whole runs under six workers (PR 30).  A
    # worker that dies takes the whole file with it, and the run hangs.
    return dict(map(compile_one, lowered.items()))


def _compiled(programs, name):
    prog = programs[name]
    if isinstance(prog, Exception):
        raise prog
    return prog


def _device_bytes(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.generated_code_size_in_bytes + m.temp_size_in_bytes
            + m.argument_size_in_bytes + m.output_size_in_bytes
            - m.alias_size_in_bytes)


_ELEMENT_BYTES = {"pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2,
                  "f16": 2, "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8}
_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%[\w.\-]+) = (\w+)\[([\d,]*)\]\{([\d,]*)(?::([^}]*))?\} ")


def _padded_buffers(text, least_bytes, widest_minor):
    """The buffers of a compiled program that the TPU's tiling pads: every
    instruction outside a fusion's body (those inside are no buffers)
    whose result, with its ``minor_to_major`` and its tile applied, takes
    ``least_bytes`` or more while the dimension on the lanes is
    ``widest_minor`` or less, as (bytes as tiled, bytes of data, name,
    shape, op_name), largest first.  ``s32[416000,34]{1,0:T(8,128)}`` is
    34 of 128 lanes: 203.1 MiB for 54.0 (PERF.md section 7; the parse of
    PR 44's satellite)."""
    fused = set(re.findall(r"calls=(%[\w.\-]+)", text))
    out, inside = [], False
    for line in text.splitlines():
        if line.endswith("{") and "=" not in line.split("(")[0]:
            inside = line.split()[0] in fused    # a computation's first line
            continue
        m = _INSTRUCTION.match(line)
        if inside or not m or not m.group(3):
            continue
        name, dtype, dims, order, tiling = m.groups()
        dims = [int(d) for d in dims.split(",")]
        order = [int(d) for d in order.split(",")]
        tiles = re.findall(r"T\(([\d,]+)\)", tiling or "")
        padded = list(dims)
        if tiles:
            tile = [int(t) for t in tiles[0].split(",")]
            if len(tiles) > 1 and len(tile) > 1:  # (k, 1): k rows a word
                tile[-2] *= int(tiles[1].split(",")[0])
            for t, ax in zip(reversed(tile), order):
                padded[ax] = -(-dims[ax] // t) * t
        size = _ELEMENT_BYTES.get(dtype, 4)
        as_tiled = size * math.prod(padded)
        if as_tiled >= least_bytes and dims[order[0]] <= widest_minor:
            op = re.search(r'op_name="([^"]*)"', line)
            out.append((as_tiled, size * math.prod(dims), name,
                        line.split(" = ")[1].split(" ")[0],
                        op.group(1) if op else ""))
    return sorted(out, reverse=True)


def _has_the_pack_kernels(text, calls=2):
    """Both merge levels of a packer are Mosaic kernels, and kernel A asks
    for more VMEM than the compiler's default scoped limit (16 MiB on a
    v5e) and says so; the chip has 128 MiB."""
    from docker_nvidia_glx_desktop_tpu.ops import cabac_pack

    assert text.count("tpu_custom_call") == calls
    assert "cabac_compact" in text and "cabac_rows" in text
    assert f'"size":"{cabac_pack.VMEM_LIMIT_BYTES}"' in text
    assert cabac_pack.VMEM_LIMIT_BYTES <= 64 * 1024 * 1024


def test_intra_step_compiles_for_v5e(programs):
    c = _compiled(programs, "intra")
    assert 0 < _device_bytes(c) < HBM_BYTES
    _has_the_pack_kernels(c.as_text())


def test_p_step_compiles_and_donates_the_ring(programs):
    c = _compiled(programs, "p")
    assert 0 < _device_bytes(c) < HBM_BYTES
    # the recon is written in place of the donated reference planes
    assert c.memory_analysis().alias_size_in_bytes >= H * W * 3 // 2
    _has_the_pack_kernels(c.as_text())


def test_slot_hand_over_at_1600p_pads_no_buffer(programs):
    """The slot builder codes its blocks block-major and hands the pack
    kernels slot words with the macroblocks on the lanes (PR 45).
    Macroblock-major, the 26 blocks of a macroblock had to leave the lane
    axis on the way, through ``s32[416000,34]{1,0}``,
    ``s32[100,160,26,16]{2,1,0,3}`` and ``s32[100,160,26,15]{2,1,0,3}``:
    445 MiB as tiled for 103 of data, and 367.1 MiB of temporaries in all
    (PERF.md section 7).  This test FAILS on the tree of before PR 45."""
    c = _compiled(programs, "p_2560x1600")
    text = c.as_text()
    _has_the_pack_kernels(text)
    padded = [b for b in _padded_buffers(text, 32 * 2 ** 20, 36)
              if "dngd.slots" in b[4] or "dngd.pack" in b[4]]
    assert not padded, padded
    assert 0 < c.memory_analysis().temp_size_in_bytes < 300 * 2 ** 20


def test_row_program_compiles_scatters_in_place_and_keeps_the_kernels(
        programs):
    c = _compiled(programs, "rows_b8")
    assert 0 < _device_bytes(c) < HBM_BYTES
    # the worklist's recon rows are written into the donated planes
    assert c.memory_analysis().alias_size_in_bytes >= H * W * 3 // 2
    # the packer's two kernels and the loop filter's one, over 8 rows
    text = c.as_text()
    _has_the_pack_kernels(text, calls=3)
    assert "dngd_deblock_edges" in text
    # what is held beside the planes is bands, not frames
    assert c.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


def test_cabac_row_program_compiles_with_the_loop_filter_and_no_packer(
        programs):
    c = _compiled(programs, "rows_cabac_b8")
    assert 0 < _device_bytes(c) < HBM_BYTES
    assert c.memory_analysis().alias_size_in_bytes >= H * W * 3 // 2
    # the loop filter's kernel and nothing of an entropy stage
    text = c.as_text()
    assert text.count("tpu_custom_call") == 1
    assert "dngd_deblock_edges" in text and "cabac_compact" not in text
    assert c.memory_analysis().temp_size_in_bytes < 64 * 2 ** 20


@pytest.mark.parametrize("name", ["deblock_p", "deblock_p_2160"])
def test_p_deblock_compiles_with_the_edge_kernel(programs, name):
    c = _compiled(programs, name)
    assert 0 < _device_bytes(c) < HBM_BYTES
    # the whole edge chain is one Mosaic kernel, and no scan is left
    text = c.as_text()
    assert "dngd_deblock_edges" in text and "tpu_custom_call" in text
    assert " while(" not in text


@pytest.mark.parametrize("name", ["binarize_p", "binarize_intra",
                                  "binarize_p_2160", "binarize_band8"])
def test_binarize_compiles_with_the_pack_kernels(programs, name):
    c = _compiled(programs, name)
    assert 0 < _device_bytes(c) < HBM_BYTES
    # no barrel-shifter tree and no row-by-row ``dynamic_update_slice``
    # loop is left in the program
    text = c.as_text()
    _has_the_pack_kernels(text)
    assert " while(" not in text


@pytest.mark.parametrize("size", ["2560x1600", "3840x2176"])
def test_frame_stats_holds_no_padded_picture(programs, size):
    """The statistics read three planes and a few small tensors, and hold
    little beside them.  Reduced to macroblocks over (R, 16, C, 16) in one
    step, each of the four ``int32`` pictures was laid out with 16 of 128
    lanes in use, eight times its size, and from this size up those copies
    no longer fit the fast memory: 126.5 and 288.7 MiB of temporaries,
    1.5 and 3.0 ms a frame on the chip.  This test FAILS on the tree of
    before PR 44, which is its point."""
    c = _compiled(programs, f"frame_stats_{size}")
    assert 0 < c.memory_analysis().temp_size_in_bytes < 8 * 2 ** 20


def test_session_mesh_step_fits_each_chip(programs):
    c = _compiled(programs, "mesh41")
    # memory_analysis() of a partitioned program is per device
    assert 0 < _device_bytes(c) < HBM_BYTES
    assert len(c.input_shardings[0][0].device_set) == 4
    _has_the_pack_kernels(c.as_text())


def test_two_sessions_a_chip_fit_with_the_kernels_vmapped(programs):
    c = _compiled(programs, "p_two_sessions")
    assert 0 < _device_bytes(c) < HBM_BYTES
    # kernel A once over a grid with the sessions in it, kernel B in the
    # loop JAX puts round a kernel whose prefetched scalars are batched
    text = c.as_text()
    assert "cabac_compact" in text and "cabac_rows" in text
