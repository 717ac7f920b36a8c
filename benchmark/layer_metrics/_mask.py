"""What the damage mask does to a frame (``desk1600-mask``:
``DNGD_DAMAGE_MASK``, ``ops/damage_mask.py``: a P frame's device work is the
macroblock rows in which something changed).

Counters are the program's own over the window: ``dngd_mask_rows_total`` (the
frame's rows, every planned P frame), ``..._rows_damaged_total`` (the plan's
worklist), ``..._rows_coded_total`` (what the device was handed: the padded
bucket on a frame of the row program, every row on a dense one) and
``..._rows_gathered_total`` (the row program's part of that).  ``run.py``'s
``parse_metrics`` adds a family's series up, so
``dngd_mask_frames_total{program=}`` reads here as the planned P frames and
no more; the row program's share of them comes from the rows, exactly as long
as the picture's size does not change: a dense frame adds the frame's rows to
coded-less-gathered and to the total, a frame of the row program to the total
alone (``row_program_share``).

Device times are the chip's SELF time a frame under ``dngd.mask_gather`` (the
references' pad and the bands cut from them and from the frame) and
``dngd.mask_scatter`` (the recon rows back into the reference planes), read
as ``_mesh.py`` reads its scopes: whatever share of the operations' time is
scoped (no nine-tenths rule).  The row program of a bucket carries the bucket in its name
(``jit_encode_p_rows_b8``), so the rows the TRACED frames gathered are read off
the trace itself (``traced_rows``) and ``move_hbm_share`` divides the bytes of
those very frames by the time of those very frames.

Loading this module holds the program to what the mask configuration's file
states (``nothing compiles while frames are served, at any damage``):
``run.py`` loads a cell's readers before it touches the chip, and these
readers are listed by mask cells alone, so a program whose row step is
specialized on ``qp`` (one compile a rung of the rate ladder and a bucket, on
the serving thread) ends the run there, with exit code 1 and no result line,
instead of compiling its way through the window."""
import json
import pathlib
import re

from benchmark.layer_metrics import _counters
from benchmark.stage_reduce import FRAME_PROGRAM_PREFIX, SCOPE_PREFIX

GATHER = SCOPE_PREFIX + "mask_gather"
SCATTER = SCOPE_PREFIX + "mask_scatter"
ROW_PROGRAM = re.compile(r"^jit_encode_p_rows_b(\d+)$")
ROWS = "dngd_mask_rows_total"
ROWS_DAMAGED = "dngd_mask_rows_damaged_total"
ROWS_CODED = "dngd_mask_rows_coded_total"
ROWS_GATHERED = "dngd_mask_rows_gathered_total"
SEARCH_PAD = 13        # ops/h264_inter._PAD: reference samples a row's search
                       # reads beyond the row's own, on every side

ROOT = pathlib.Path(__file__).resolve().parents[2]


class StaticRowStep(RuntimeError):
    """The program's row step compiles once a qp: it cannot serve a mask
    session under CBR without compiling while frames are served."""


def require_traced_row_step() -> None:
    """The program's own word on what its served row step is specialized
    on (``ops/damage_mask.ROW_STEP_DYNQP_STATIC``, the ``static_argnames`` its
    ``row_step`` is jitted with): ``qp`` must not be among them.  Before
    PR 40 the one row step is ``encode_p_rows`` with ``qp`` static and the
    program says nothing.  A copy of the benchmark's files with no program
    beside it (the manifest's tests make one) has nothing to hold, and
    resolves."""
    try:
        from docker_nvidia_glx_desktop_tpu.ops import damage_mask
    except ModuleNotFoundError as e:
        if e.name != "docker_nvidia_glx_desktop_tpu":
            raise
        return
    static = getattr(damage_mask, "ROW_STEP_DYNQP_STATIC", None)
    if static is None or "qp" in static or not hasattr(damage_mask,
                                                       "row_step"):
        raise StaticRowStep(
            "the row step of ops/damage_mask is specialized on "
            f"{'qp' if static is None else ', '.join(static)}: every rung "
            "of the rate ladder times every row bucket would compile on "
            "the serving thread, inside the window; this program cannot "
            "run a damage-mask cell")


def share_pct(run, part: str, whole: str):
    a, b = _counters.delta(run, part), _counters.delta(run, whole)
    return 100.0 * a / b if a is not None and b else None


def row_program_share(run):
    """Share of the window's planned P frames that the row program coded;
    nothing from a program without the counters or a window without a
    planned frame."""
    rows, coded, gathered = (_counters.delta(run, ROWS),
                             _counters.delta(run, ROWS_CODED),
                             _counters.delta(run, ROWS_GATHERED))
    if not rows or coded is None or gathered is None:
        return None
    return 1.0 - (coded - gathered) / rows


def scopes_ms(run, pick):
    """ms a frame of the chip under the scopes ``pick`` accepts, in the
    programs a frame is counted by (``_mesh.scopes_ms``; not imported: that
    module holds the program to the MESH configurations' plans when it
    loads); nothing where none is found."""
    st = run.get("stages")
    if not st or not st["frames"]:
        return None
    total = sum(s for name, p in st["programs"].items()
                if name.startswith(FRAME_PROGRAM_PREFIX)
                for scope, s in p["scopes"].items() if pick(scope))
    return 1e3 * total / st["frames"] if total else None


def scope_ms(run, scope: str):
    """ms a frame under ``scope``; 0 where the row program ran in the traced
    span and spent nothing there, nothing where it did not run (or carries
    no scope of the mask's)."""
    spent = scopes_ms(run, scope.__eq__)
    if spent is None and scopes_ms(
            run, {GATHER, SCATTER}.__contains__) is not None:
        return 0.0
    return spent


def traced_rows(run):
    """Rows the row program gathered in the traced span: bucket times runs,
    by the programs' names."""
    st = run.get("stages")
    if not st:
        return None
    found = [(ROW_PROGRAM.match(name), p["runs"])
             for name, p in st["programs"].items()]
    return sum(int(m.group(1)) * runs for m, runs in found if m) or None


def row_move_bytes(width: int) -> int:
    """Bytes one coded row must move AT THE LEAST, a byte a sample, whatever
    implements the move: in, the bands of the three reference planes the
    row's search and compensation read (16 luma lines and 8 of each chroma
    plane with ``SEARCH_PAD`` more on every side); out, the row's recon in
    the three planes.  The frame's own row, the headers and the bitstream
    are not the gather's and not counted."""
    pad = 2 * SEARCH_PAD
    luma_in = (16 + pad) * (width + pad)
    chroma_in = 2 * (8 + pad) * (width // 2 + pad)
    out = 16 * width + 2 * 8 * (width // 2)
    return luma_in + chroma_in + out


def stated_width(run) -> int:
    """The picture's width: the run's where a test says it, else the one
    mask configuration's of BENCHMARK.json."""
    if "width" in run:
        return run["width"]
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    widths = set()
    for entry in manifest["configs"]:
        config = json.loads((ROOT / entry["file"]).read_text())
        if config["env"].get("DNGD_DAMAGE_MASK") == "true":
            widths.add(config["geometry"]["width"])
    (width,) = widths
    return width


def hbm_bytes_per_s(run):
    kind = run.get("device_kind")
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
    return peaks["devices"].get(kind, {}).get("hbm_bytes_per_s")


def move_hbm_share(run):
    """The least time the chip's memory could take for the bytes the traced
    frames' worklists must move, over the time the chip spent under the two
    scopes in those frames."""
    st, rows = run.get("stages"), traced_rows(run)
    spent = scopes_ms(run, {GATHER, SCATTER}.__contains__)
    if not rows or not spent:
        return None
    peak = hbm_bytes_per_s(run)
    if not peak:
        return None
    seconds = spent * st["frames"] / 1e3
    return rows * row_move_bytes(stated_width(run)) / peak / seconds


require_traced_row_step()
