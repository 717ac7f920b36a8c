"""What a frame costs where it crosses chips (``desk2160-cabac-mesh4``: one
session's macroblock rows over the chips of a host, ``parallel/batch.py``'s
``jit_encode_p_mesh`` / ``jit_encode_intra_mesh``).

Device times are chip 0's SELF time a frame under a ``dngd.`` scope of the
shard programs (``run["stages"]``, ``stage_reduce``'s view of one chip),
whatever share of the operations' time lies under a scope: the 4K programs
are 88% scoped and ``_stages.py``'s nine-tenths rule would give nothing.
Bytes are the program's own counters over the window; the arithmetic the
halo's must equal is here (``halo_bytes``), so that a change of what the
compiled collectives move shows as a test that fails.  A program without
the scope, the span or the counter gives nothing; the per-frame steps gather
nothing since PR 36 (each shard's buffer is pulled from its own chip), which
reads 0 and not nothing: ``gather_ms``.

Loading this module holds the program to the shard plan each mesh
configuration STATES (``require_stated_plans``): ``run.py`` loads a cell's
readers before it touches the chip, and these readers are listed by mesh
cells alone, so a program that plans another picture for the configuration's
environment ends the run there, with exit code 1 and no result line, and not
with the numbers of another deployment under this one's name."""
import json
import pathlib

from benchmark.layer_metrics import _counters
from benchmark.stage_reduce import (FRAME_PROGRAM_PREFIX, NO_SCOPE,
                                    SCOPE_PREFIX)

HALO = SCOPE_PREFIX + "halo"
GATHER = SCOPE_PREFIX + "gather"
SEARCH = {SCOPE_PREFIX + "me_int", SCOPE_PREFIX + "me_subpel"}
BINARIZE = SCOPE_PREFIX + "binarize"
HALO_BYTES = "dngd_mesh_halo_bytes_total"
GATHER_BYTES = "dngd_mesh_gather_bytes_total"
FRAMES = "dngd_encoder_frames_total"
SEARCH_PAD = 13        # ops/h264_inter._PAD: reference rows a search reads
                       # beyond its own, in luma and in each chroma plane


ROOT = pathlib.Path(__file__).resolve().parents[2]


class PlanMismatch(RuntimeError):
    """The program does not plan the picture a mesh configuration states."""


def stated_plans():
    """``(name, height, shards asked for, chips, geometry)`` of every
    configuration of BENCHMARK.json whose file states a shard plan
    (``geometry.shards``)."""
    manifest = json.loads((ROOT / "BENCHMARK.json").read_text())
    for entry in manifest["configs"]:
        config = json.loads((ROOT / entry["file"]).read_text())
        geometry = config.get("geometry", {})
        if "shards" in geometry:
            yield (entry["name"], geometry["height"],
                   int(config["env"]["ENCODER_SPATIAL_SHARDS"]),
                   config["chips"], geometry)


def require_stated_plans(plans=None) -> None:
    """Each stated plan against the program's own planner
    (``parallel/batch.feasible_spatial_shards`` and ``coded_height``, the
    functions ``H264Encoder`` asks): the shard count and the coded height the
    configuration's file gives.  ``PlanMismatch`` where they differ: before
    PR 36 the planner gives native 4K THREE shards of 45 rows of a 2160-line
    picture on four chips, which is not ``desk2160-cabac-mesh4`` (four of 34
    rows, 2176 lines coded and 16 cropped).  A copy of the benchmark's files
    with no program beside it (the manifest's tests make one) has nothing to
    hold to a plan, and resolves."""
    try:
        from docker_nvidia_glx_desktop_tpu.parallel import batch
    except ModuleNotFoundError as e:
        if e.name != "docker_nvidia_glx_desktop_tpu":
            raise
        return
    for name, height, want, chips, geometry in (
            stated_plans() if plans is None else plans):
        shards = batch.feasible_spatial_shards(height, want, chips)
        coded_of = getattr(batch, "coded_height", None)
        coded = (coded_of(height, shards) if coded_of
                 else -(-height // 16) * 16)
        if (shards, coded) != (geometry["shards"], geometry["coded_height"]):
            raise PlanMismatch(
                f"configuration {name!r} states {geometry['shards']} shards "
                f"of a {geometry['coded_height']}-line coded picture for "
                f"{height} lines on {chips} chips; this program plans "
                f"{shards} of {coded} lines, so it cannot run it")


def halo_bytes(width: int, shards: int, itemsize: int = 1) -> int:
    """Bytes of reference halo the ``ppermute``s bring the chip that
    receives most, a P frame: ``SEARCH_PAD`` rows of luma and of both chroma
    planes from each neighbour (two for an interior shard, one on a two-chip
    mesh)."""
    neighbours = min(max(shards - 1, 0), 2)
    return neighbours * SEARCH_PAD * (width + 2 * (width // 2)) * itemsize


def scopes_ms(run, pick):
    """ms a frame of chip 0 under the scopes ``pick`` accepts, in the
    programs a frame is counted by; nothing where none is found."""
    st = run.get("stages")
    if not st or not st["frames"]:
        return None
    total = sum(s for name, p in st["programs"].items()
                if name.startswith(FRAME_PROGRAM_PREFIX)
                for scope, s in p["scopes"].items() if pick(scope))
    return 1e3 * total / st["frames"] if total else None


def unscoped_ms(run):
    return scopes_ms(run, NO_SCOPE.__eq__)


def gather_ms(run):
    """ms a frame under ``dngd.gather``; 0 where the shard programs carry
    their scopes (the halo's is there) and gather nothing."""
    spent = scopes_ms(run, GATHER.__eq__)
    if spent is None and scopes_ms(run, HALO.__eq__) is not None:
        return 0.0
    return spent


def collective_bytes_per_frame(run):
    """What one chip receives a frame in the two collectives; nothing from
    a program without both counters, or a window without a frame."""
    halo, gather = (_counters.delta(run, HALO_BYTES),
                    _counters.delta(run, GATHER_BYTES))
    frames = _counters.delta(run, FRAMES)
    if halo is None or gather is None or not frames:
        return None
    return (halo + gather) / frames


def ici_bytes_per_s(run):
    """The chip-to-chip peak of ``peaks.json`` for the device that ran
    (``run["device_kind"]`` where a test says it; else what JAX shows)."""
    kind = run.get("device_kind")
    if kind is None:
        import jax
        kind = jax.devices()[0].device_kind
    peaks = json.loads(
        (pathlib.Path(__file__).resolve().parents[1] / "peaks.json")
        .read_text())["devices"]
    return peaks[kind]["ici_bits_per_s"] / 8 if kind in peaks else None


require_stated_plans()
