"""What ``ENCODER_TUNE=hq`` does to a frame (``desk1080-hq``: a qp a
macroblock from luma activity, every decision by ``SSD + lambda * bits``, a P
macroblock whose motion candidate loses coded I_16x16, all of it under the
loop filter and CBR on the per-frame device-CAVLC path).

Device times are the chip's self time a frame by named scope, read as
``_stages.py`` reads its own (nothing under nine tenths scoped): ``dngd.aq``
(activity, plane and the ``mb_qp_delta`` chain), ``dngd.mode_decision`` (the
forced skip and the I_16x16 escape, which had no reader), the search whole
(``me_int`` + ``me_subpel`` + ``mc``), and the program ``jit_deblock_frame``
whole, which under hq builds a threshold word a line of every edge
(``dngd.deblock_thr``) beside the bS.

``edges_hbm_share`` is the changed kernel's share of its roofline: the bytes
the loop filter NEEDS for a picture (``filter_bytes``: the three planes in and
out at a byte a sample and what decides its edges, a macroblock, as
``deblock_frame`` is handed it; whatever implements the filter and whatever
fills its tiles) times the traced executions of the program, over
``peaks.json``'s ``hbm_bytes_per_s``, against the chip's time under
``dngd.deblock_edges``.  (What the kernel's block specs move, 67.8 MB a
1080p picture as 32-bit words on 128 lanes for 68 rows, read 129% of the
peak in the time the kernel took: XLA keeps operands of that size in the
chip's fast memory, so the specs' bytes are not the memory's; my chip run,
PR 48.)

Counters are the program's own over the window, off the P frames' meta words
(no pull of their own): ``dngd_encoder_p_mbs_total``,
``dngd_encoder_p_intra_mbs_total``, ``dngd_encoder_coded_qp_sum_total`` and
``dngd_encoder_slice_qp_sum_total``.

Loading this module holds the program to what the configuration's file
states (``nothing compiles while frames are served, at any qp``): ``run.py``
loads a cell's readers before it touches the chip, and these readers are
listed by hq cells alone, so a program whose served hq step is specialized on
``qp`` (a compile of the intra and of the P program a rung of the rate
ladder, on the serving thread: every tree before PR 48) ends the run there,
with exit code 1 and no result line."""
import json
import pathlib

from benchmark.layer_metrics import _counters, _stages
from benchmark.stage_reduce import SCOPE_PREFIX

EDGES = SCOPE_PREFIX + "deblock_edges"
SEARCH = {SCOPE_PREFIX + s for s in ("me_int", "me_subpel", "mc")}
P_MBS = "dngd_encoder_p_mbs_total"
P_INTRA_MBS = "dngd_encoder_p_intra_mbs_total"
CODED_QP_SUM = "dngd_encoder_coded_qp_sum_total"
SLICE_QP_SUM = "dngd_encoder_slice_qp_sum_total"
# a macroblock: 256 luma and 2 x 64 chroma samples; its effective qp (int32),
# intra flag, sixteen coded-block flags and vector (two int8)
MB_SAMPLES, MB_EDGE_INPUTS = 384, 4 + 1 + 16 + 2

ROOT = pathlib.Path(__file__).resolve().parents[2]


class StaticHqStep(RuntimeError):
    """The program's per-frame step compiles once a qp under ``hq``: it
    cannot serve the tier under CBR without compiling while frames are
    served."""


def require_traced_hq_step() -> None:
    """The program's own word on which tunes its per-frame step serves with
    ``qp`` traced (``ops/cavlc_p_device.DYNQP_STEP_TUNES``): ``hq`` must be
    among them.  Before PR 48 the step refuses a traced qp under either hq
    tier and the program says nothing.  A copy of the benchmark's files
    with no program beside it (the manifest's tests make one) has nothing
    to hold, and resolves."""
    try:
        from docker_nvidia_glx_desktop_tpu.ops import cavlc_p_device
    except ModuleNotFoundError as e:
        if e.name != "docker_nvidia_glx_desktop_tpu":
            raise
        return
    tunes = getattr(cavlc_p_device, "DYNQP_STEP_TUNES", ("off",))
    if "hq" not in tunes:
        raise StaticHqStep(
            "the per-frame step of ops/cavlc_p_device takes a traced qp "
            f"under {', '.join(tunes)} alone: under ENCODER_TUNE=hq every "
            "rung of the rate ladder would compile the intra and the P "
            "program on the serving thread, inside the window; this "
            "program cannot run an hq cell")


def search_ms(run):
    return _stages.scopes_ms(run, SEARCH.__contains__)


def filter_bytes(width: int, height: int) -> int:
    """Bytes the loop filter must move for one picture AT THE LEAST: every
    sample in and out once, a byte each, and a macroblock's edge inputs as
    ``deblock_frame`` takes them."""
    mbs = -(-height // 16) * -(-width // 16)
    return mbs * (2 * MB_SAMPLES + MB_EDGE_INPUTS)


def stated_geometry(run):
    """(width, height): the run's where a test says it, else the one hq
    configuration's of BENCHMARK.json."""
    if "width" in run:
        return run["width"], run["height"]
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    found = set()
    for entry in manifest["configs"]:
        config = json.loads((ROOT / entry["file"]).read_text())
        if config["env"].get("ENCODER_TUNE") == "hq":
            found.add((config["geometry"]["width"],
                       config["geometry"]["height"]))
    (geometry,) = found
    return geometry


def hbm_bytes_per_s(run):
    kind = run.get("device_kind")
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    peaks = json.loads((ROOT / "benchmark" / "peaks.json").read_text())
    return peaks["devices"].get(kind, {}).get("hbm_bytes_per_s")


def edges_hbm_share(run):
    """The least time the chip's memory could take for what the loop filter
    needs in its traced executions, over the time the chip spent under
    ``dngd.deblock_edges`` in them."""
    st = _stages.sound(run)
    prog = st and st["programs"].get(_stages.DEBLOCK_PROGRAM)
    if not prog or not prog["scopes"].get(EDGES):
        return None
    peak = hbm_bytes_per_s(run)
    if not peak:
        return None
    moved = prog["runs"] * filter_bytes(*stated_geometry(run))
    return moved / peak / prog["scopes"][EDGES]


def per_p_mb(run, family: str):
    part, mbs = _counters.delta(run, family), _counters.delta(run, P_MBS)
    return part / mbs if part is not None and mbs else None


require_traced_hq_step()
