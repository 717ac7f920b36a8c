"""What the damage mask does to a frame of the CABAC stream
(``desk1600-cabac-mask``: ``ENCODER_ENTROPY=cabac``,
``ENCODER_CABAC_BINARIZE=device``, ``DNGD_DAMAGE_MASK=true``; models/h264.py
``_submit_cabac_p_masked`` / ``_collect_cabac_p_masked``, ops/damage_mask.py
``row_step_cabac``, bitstream/h264_cabac.py ``encode_p_rows_from_binstream``).

The plan's counters (``dngd_mask_*``) and the stage ``damage_grid`` are the
CAVLC mask's, on this path as on that one, and are read through ``_mask``'s
helpers.  The host's part of a masked frame is the stage ``engine`` (the
native engine over the planned rows' record streams) and the stage
``skip_slices`` (the slice data the unplanned rows leave as: fetched from the
cache by qp, or coded), both inside ``assemble``.  Device times are the chip's
SELF time a frame under a scope in EVERY program of the traced span (the
binarizer is a program of its own, ``jit_binarize_p``, and no frame is counted
by it), whatever share of the operations' time is scoped: no nine-tenths rule,
as ``_mask.py`` and ``_mesh.py``.  A bucket's row program carries the bucket
in its name (``jit_encode_p_rows_cabac_b8``), so the rows the traced frames
gathered are read off the trace.

Loading this module holds the program to what the configuration's file
states, before the chip (``run.py`` loads a cell's readers first, and these
are listed by the masked CABAC cell alone): ``_mask``'s import refuses a row
step that is specialized on ``qp``, and ``require_masked_cabac`` refuses a
program that DROPS the mask under the CABAC stream and would serve the cell
dense (``models/h264.py:_damage_plan`` before PR 43: ``None`` unless the
entropy coder is the device's CAVLC).  Either ends the run with exit code 1
and no result line."""
import re

from benchmark.layer_metrics import _cabac, _counters, _mask

ROW_PROGRAM = re.compile(r"^jit_encode_p_rows_cabac_b(\d+)$")
MOVE = {_mask.GATHER, _mask.SCATTER}


class MaskDropped(RuntimeError):
    """The program serves ``ENCODER_ENTROPY=cabac`` + ``DNGD_DAMAGE_MASK``
    with the mask off: every frame dense, no row program, nothing for this
    cell's readers to read."""


def require_masked_cabac() -> None:
    """The program's own word on which entropy placements its mask compacts
    (``ops/damage_mask.MASKED_ENTROPY``) must name ``cabac``, and the row
    step of that stream must be there (``row_step_cabac``).  A program that
    says nothing is the parent.  A copy of the benchmark's files with no
    program beside it (the manifest's tests make one) has nothing to hold,
    and resolves."""
    try:
        from docker_nvidia_glx_desktop_tpu.ops import damage_mask
    except ModuleNotFoundError as e:
        if e.name != "docker_nvidia_glx_desktop_tpu":
            raise
        return
    masked = getattr(damage_mask, "MASKED_ENTROPY", None)
    if (masked is None or "cabac" not in masked
            or not hasattr(damage_mask, "row_step_cabac")):
        raise MaskDropped(
            "ops/damage_mask masks "
            f"{'the device CAVLC path alone' if masked is None else masked}"
            ": under ENCODER_ENTROPY=cabac this program drops "
            "DNGD_DAMAGE_MASK without a word and serves every frame dense; "
            "it cannot run a masked CABAC cell")


def scopes_ms(run, pick):
    """ms a frame of the chip under the scopes ``pick`` accepts, over every
    program of the traced span; nothing where none is found."""
    st = run.get("stages")
    if not st or not st["frames"]:
        return None
    total = sum(s for p in st["programs"].values()
                for scope, s in p["scopes"].items() if pick(scope))
    return 1e3 * total / st["frames"] if total else None


def traced_rows(run):
    """Rows the CABAC row program gathered in the traced span: bucket times
    runs, by the programs' names."""
    st = run.get("stages")
    if not st:
        return None
    found = [(ROW_PROGRAM.match(name), p["runs"])
             for name, p in st["programs"].items()]
    return sum(int(m.group(1)) * runs for m, runs in found if m) or None


def move_hbm_share(run):
    """``_mask.move_hbm_share`` by this stream's row programs: the least
    time the chip's memory could take for the bytes the traced frames'
    worklists must move, over the time the chip spent under
    ``dngd.mask_gather`` + ``dngd.mask_scatter`` in those frames."""
    st, rows = run.get("stages"), traced_rows(run)
    spent = _mask.scopes_ms(run, MOVE.__contains__)
    if not rows or not spent:
        return None
    peak = _mask.hbm_bytes_per_s(run)
    if not peak:
        return None
    seconds = spent * st["frames"] / 1e3
    return (rows * _mask.row_move_bytes(_mask.stated_width(run))
            / peak / seconds)


def per_frame(run, family: str):
    """A counter's growth over the window a frame the encoder coded."""
    grown = _counters.delta(run, family)
    frames = _counters.delta(run, "dngd_encoder_frames_total")
    return grown / frames if grown is not None and frames else None


BINARIZE, SEARCH = _cabac.BINARIZE, _cabac.SEARCH

require_masked_cabac()
