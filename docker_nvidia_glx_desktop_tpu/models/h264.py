"""H.264 encoder family — the flagship codec (the ``nvh264enc``
replacement; reference Dockerfile:210, SURVEY.md §3.2 hot loop):
I_16x16 / I_4x4 intra and P frames over the integer 4x4 transform, behind
a Baseline CAVLC or a Main-profile CABAC stream.  With ``gop == 1`` every
frame is an IDR, so ``request_keyframe`` is trivially satisfied.
"""

from __future__ import annotations

import functools
import logging
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..bitstream import h264 as syn
from ..obs import metrics as obsm
from ..obs import trace as obst
from ..obs.profile import PROFILER
from ..ops import color
from ..utils.mathutil import round_up
from .base import EncodedFrame, Encoder
from .prefix_pull import M_D2H_BYTES as _M_D2H_BYTES
from .prefix_pull import FlatPull, PrefixPull, prefetch_host as _prefetch_host

log = logging.getLogger(__name__)

_M_ENTROPY_OVERFLOW = obsm.counter(
    "dngd_encoder_entropy_overflow_total",
    "Frames whose device CAVLC buffer overflowed and were entropy-coded "
    "on the host instead (pathological content; a device coder that "
    "gives way on every frame is not serving from the device)")
_M_CABAC_FALLBACK = obsm.counter(
    "dngd_encoder_cabac_fallback_total",
    "CABAC frames the native engine did not code from the transport "
    "buffer: dense = the packed stream or the engine's cap overflowed and "
    "the level tensors were pulled whole; python = no native engine was "
    "built and the Python coder (about 100x slower) coded the frame",
    ("kind",))
_M_H2D_BYTES = obsm.counter(
    "dngd_encoder_h2d_bytes_total",
    "Bytes of host arrays the per-frame CABAC path handed to its device "
    "programs in dispatch: the picture's planes (the RGB frame where the "
    "colour conversion is the device's)")
_M_MESH_HALO_BYTES = obsm.counter(
    "dngd_mesh_halo_bytes_total",
    "Bytes of reference halo one chip of a spatial mesh receives from "
    "its neighbours (parallel/batch.spatial_halo_bytes: an interior "
    "shard's two, from the operands' shapes at dispatch)")
_M_MESH_GATHER_BYTES = obsm.counter(
    "dngd_mesh_gather_bytes_total",
    "Bytes of the other shards' entropy buffers an all_gather brings one "
    "chip of a spatial mesh; 0 on the per-frame steps, whose shards are "
    "pulled each from its own chip")
_M_MESH_SHARDS = obsm.gauge(
    "dngd_mesh_shards",
    "Chips one session's macroblock rows are spread over "
    "(ENCODER_SPATIAL_SHARDS as resolved; 1 = one chip)")
_M_MASK_ROWS = obsm.counter(
    "dngd_mask_rows_total",
    "Macroblock rows of the P frames a damage-mask session planned "
    "(DNGD_DAMAGE_MASK; the frame's rows, every planned P frame; an IDR "
    "is planned by nothing and counted by none of the dngd_mask_ families)")
_M_MASK_ROWS_DAMAGED = obsm.counter(
    "dngd_mask_rows_damaged_total",
    "Rows of those in which the damage grid found a changed macroblock "
    "(the plan's worklist before padding; a wholly calm frame still "
    "names row 0, a frame past the ladder's top names every row)")
_M_MASK_ROWS_CODED = obsm.counter(
    "dngd_mask_rows_coded_total",
    "Rows of those the device was handed: the worklist padded to its "
    "power-of-two bucket on a frame of the row program, every row on a "
    "frame of the full-frame program")
_M_MASK_ROWS_GATHERED = obsm.counter(
    "dngd_mask_rows_gathered_total",
    "Rows the ROW program gathered and scattered back (its bucket, a "
    "frame; nothing on a frame of the full-frame program): "
    "dngd_mask_rows_coded_total less the dense frames' rows")
_M_MASK_FRAMES = obsm.counter(
    "dngd_mask_frames_total",
    "Planned P frames by the program that coded them: rows = "
    "jit_encode_p_rows_b<bucket> over the worklist, dense = the "
    "full-frame P program (the plan reached the ladder's top)",
    ("program",))
_M_MASK_FRAMES_ROWS = _M_MASK_FRAMES.labels("rows")
_M_MASK_FRAMES_DENSE = _M_MASK_FRAMES.labels("dense")
_M_P_MBS = obsm.counter(
    "dngd_encoder_p_mbs_total",
    "Macroblocks of the P frames coded under ENCODER_TUNE=hq on the served "
    "per-frame path (a qp a macroblock, the intra escape open): what the "
    "three families below are shares and means of")
_M_P_INTRA_MBS = obsm.counter(
    "dngd_encoder_p_intra_mbs_total",
    "Macroblocks of those whose motion candidate lost to intra by "
    "SSD + lambda * bits and were coded I_16x16 in the P slice (the "
    "frame's meta word, no pull of its own)")
_M_CODED_QP_SUM = obsm.counter(
    "dngd_encoder_coded_qp_sum_total",
    "Sum over those macroblocks of the EFFECTIVE qp (what a decoder holds "
    "as QPY there: the mb_qp_delta chain; the frame's meta word)")
_M_SLICE_QP_SUM = obsm.counter(
    "dngd_encoder_slice_qp_sum_total",
    "Sum over those macroblocks of their slice's qp (the rate ladder's "
    "rung): coded less slice, over dngd_encoder_p_mbs_total, is what the "
    "adaptive quantization moved the mean macroblock by")
_M_CABAC_DENSE = _M_CABAC_FALLBACK.labels("dense")
_M_CABAC_PYTHON = _M_CABAC_FALLBACK.labels("python")


def _note_cabac_dense() -> None:
    """A CABAC frame's transport or engine cap overflowed and its level
    tensors were pulled whole: counted, and said."""
    _M_CABAC_DENSE.inc()
    n = int(_M_CABAC_DENSE.value)
    if n == 1 or n % 100 == 0:
        log.warning("CABAC transport overflow: level tensors pulled "
                    "dense for this frame (%d so far)", n)


def _note_h2d(*arrays) -> None:
    """Host arrays on their way into a device program: counted (what is
    on the device already crosses nothing)."""
    _M_H2D_BYTES.inc(sum(a.nbytes for a in arrays
                         if isinstance(a, np.ndarray)))


def _note_mask_plan(plan) -> None:
    """A P frame's damage plan on its way to the device: its rows, the
    damaged and the coded ones, and the program that codes it."""
    _M_MASK_ROWS.inc(plan.total)
    _M_MASK_ROWS_DAMAGED.inc(len(plan.rows))
    _M_MASK_ROWS_CODED.inc(plan.bucket)
    if plan.full:
        _M_MASK_FRAMES_DENSE.inc()
    else:
        _M_MASK_FRAMES_ROWS.inc()
        _M_MASK_ROWS_GATHERED.inc(plan.bucket)


def _note_entropy_overflow(what: str) -> None:
    """A device-entropy frame fell back to the host coder: count it and
    say so — the stream stays valid, but the fallback must be visible."""
    _M_ENTROPY_OVERFLOW.inc()
    n = int(_M_ENTROPY_OVERFLOW.value)
    if n == 1 or n % 100 == 0:       # the counter has every one of them
        log.warning("device CAVLC buffer overflow (%s): frame "
                    "entropy-coded on the host (%d so far)", what, n)


class RateController:
    """Leaky-bucket (VBV-style) qp control toward ENCODER_BITRATE_KBPS.

    The virtual buffer drains at the target rate and fills with each
    coded frame; qp is chosen BEFORE encoding from the buffer level plus
    a per-frame-type size prediction (intra frames run ~3-6x a P frame:
    exactly the burst a pure average-tracking controller lets through,
    flooding the client at every GOP boundary or scene cut).

    qp still moves on a quantized ladder within [base-6, base+18] so the
    jit cache sees a small bounded set of distinct qp values (each
    distinct qp is one compile of the static-qp device stage).  Size
    prediction uses per-type EMAs normalized to base qp via the standard
    +6-qp-halves-bits model, so a scene cut's oversized frame raises the
    NEXT frames' qp immediately, and the pre-encode VBV check raises qp
    for a frame the prediction says would overflow the buffer.
    """

    STEPS = (-6, -4, -2, 0, 2, 4, 6, 8, 10, 12, 14, 16, 18)
    TARGET_FILL = 0.5           # steer the bucket toward half full
    DRAIN_FRAMES = 30           # spread fill-error correction over ~0.5-1 s
    MAX_INFLIGHT = 8            # > any pipeline depth; deeper = orphans

    def __init__(self, base_qp: int, bitrate_kbps: int, fps: float,
                 vbv_s: float = 0.75):
        import collections

        self.base_qp = base_qp
        self.target_bits = bitrate_kbps * 1000.0 / max(fps, 1.0)
        self.vbv_cap = bitrate_kbps * 1000.0 * vbv_s
        self.level = 0.0                        # bucket fill (bits)
        self._ema = {True: None, False: None}   # per-type, base-qp units
        self._step_idx = self.STEPS.index(0)
        self._avg = None                        # long-term bits/frame EMA
        # (keyframe, step_idx) per in-flight frame: the pipelined serving
        # loop calls qp_for(N+1) before update(N) arrives from collect
        self._pending = collections.deque()
        # damage-driven encode (ops/damage_mask): rolling damage
        # fraction fed by the gating plan so a calm->spike transition
        # can pre-empt the burst (see note_damage)
        self._damage_ema = None

    def note_damage(self, frac: float, spike: float = 0.85) -> None:
        """Damage-plane consumer: after a long-calm stretch (the masked
        encoder has been emitting near-empty frames, so the per-type
        size EMAs and the VBV level have drifted toward 'P frames are
        free'), a full-frame damage spike lands an intra-sized P burst
        BEFORE update() can react.  Seeing the spike at SUBMIT time —
        the damage grid is computed host-side before qp_for — lets the
        controller take one ladder step from the NEXT frame on (a
        pipeline-depth's worth of frames earlier than the collect-side
        update loop would).  Rises jump the EMA
        immediately (spike detection must not lag); decays are slow
        (spike-recovery headroom, mirroring the capacity charge)."""
        frac = min(max(float(frac), 0.0), 1.0)
        prev = self._damage_ema
        calm = prev is not None and prev < spike / 4.0
        self._damage_ema = (frac if prev is None or frac >= prev
                            else 0.9 * prev + 0.1 * frac)
        if calm and frac >= spike \
                and self._step_idx < len(self.STEPS) - 1:
            self._step_idx += 1

    def _eff_step(self, step_idx: int) -> int:
        """The qp offset ACTUALLY applied at this ladder step after the
        [0, 51] clamp — size scaling must use the coded qp, not the
        nominal ladder value (base qp near either end otherwise skews the
        EMAs by up to the full clamp distance)."""
        return min(51, max(0, self.base_qp + self.STEPS[step_idx])) \
            - self.base_qp

    def _norm(self, bits: float, qp: float) -> float:
        """Measured bits -> equivalent at base_qp (+6 qp halves bits)."""
        return bits * 2.0 ** ((qp - self.base_qp) / 6.0)

    def _predict(self, keyframe: bool, step_idx: int) -> float:
        ema = self._ema[keyframe]
        if ema is None:
            # no sample yet: assume intra ~4x the per-frame budget
            ema = self.target_bits * (4.0 if keyframe else 1.0)
        return ema * 2.0 ** (-self._eff_step(step_idx) / 6.0)

    def qp_for(self, keyframe: bool) -> int:
        """qp for the NEXT frame; remembers the type for update()."""
        idx = self._step_idx
        # pre-encode VBV guard: this frame's allowance is the per-frame
        # budget plus a share of the bucket's distance from its target
        # fill — an over-full bucket (a scene cut just landed) DEMANDS
        # under-budget frames until it drains, not merely on-budget ones.
        allowed = max(
            self.target_bits
            + (self.TARGET_FILL * self.vbv_cap - self.level)
            / self.DRAIN_FRAMES,
            0.1 * self.target_bits)
        while (idx < len(self.STEPS) - 1
               and self._predict(keyframe, idx) > allowed):
            idx += 1
        self._pending.append((keyframe, idx))
        # a failed encode never reaches update(), which is what pops; an
        # entry deeper than any possible pipeline is an orphan — resync so
        # one swallowed exception can't shift keyframe/P attribution of
        # the size EMAs for the rest of the session
        while len(self._pending) > self.MAX_INFLIGHT:
            self._pending.popleft()
        return min(51, max(0, self.base_qp + self.STEPS[idx]))

    @property
    def pending_count(self) -> int:
        return len(self._pending)

    def mark(self) -> int:
        """Snapshot the in-flight reservation depth before an encode
        attempt; pass to :meth:`rollback_to` if the attempt raises."""
        return len(self._pending)

    def rollback_to(self, n: int) -> None:
        """Forget reservations made since :meth:`mark` returned ``n`` —
        the failed attempt never reaches update(), and an orphaned entry
        would shift keyframe/P attribution of the size EMAs for the rest
        of the session."""
        while len(self._pending) > n:
            self._pending.pop()

    def repeat_last_reservation(self) -> None:
        """Duplicate the newest in-flight reservation — the super-step
        ring stages a whole GOP-chunk at ONE qp (qp is a static jit arg,
        so per-frame qp movement inside a chunk would recompile), and
        each staged frame still needs its own reservation so the
        per-frame update() pops stay aligned with keyframe/P
        attribution."""
        if self._pending:
            self._pending.append(self._pending[-1])
            while len(self._pending) > self.MAX_INFLIGHT:
                self._pending.popleft()

    def drop_oldest_pending(self) -> None:
        """Forget the OLDEST in-flight reservation after a collect-side
        failure — collects complete in FIFO order, so the frame that just
        failed is the deque head.  (Submit-side failures roll back via
        mark()/rollback_to instead: they must not pop when qp_for was
        never reached.)"""
        if self._pending:
            self._pending.popleft()

    @property
    def qp(self) -> int:
        return min(51, max(0, self.base_qp + self.STEPS[self._step_idx]))

    def update(self, frame_bits: int, mean_qp: float = None) -> None:
        """Fold a coded frame into the model.  ``mean_qp`` (tune=hq):
        the frame's MEAN CODED qp — adaptive quantization moves the
        coded plane away from the nominal ladder value, and the
        +6-qp-halves-bits normalization must use what was actually
        coded or the per-type EMAs skew by the AQ offset."""
        import math

        kf, used_idx = (self._pending.popleft() if self._pending
                        else (True, self._step_idx))
        used_qp = (float(mean_qp) if mean_qp is not None
                   else self.base_qp + self._eff_step(used_idx))
        norm = self._norm(frame_bits, used_qp)
        prev = self._ema[kf]
        self._ema[kf] = norm if prev is None else 0.7 * prev + 0.3 * norm
        self.level = max(0.0, self.level + frame_bits - self.target_bits)

        # long-term trend: hold the MIX (GOP-weighted average) on budget
        self._avg = (frame_bits if self._avg is None
                     else 0.85 * self._avg + 0.15 * frame_bits)
        err = math.log2(max(self._avg, 1.0) / max(self.target_bits, 1.0))
        if err > 0.25 and self._step_idx < len(self.STEPS) - 1:
            self._step_idx += 1                 # over budget -> coarser
        elif err < -0.25 and self._step_idx > 0:
            self._step_idx -= 1                 # under budget -> finer


@functools.partial(jax.jit, static_argnames=("pad_h", "pad_w"))
@jax.named_scope("dngd.colour")
def _yuv_stage(rgb, pad_h: int, pad_w: int):
    """RGB -> studio-range YUV 4:2:0 uint8 planes, padded to MB multiples."""
    h, w = rgb.shape[0], rgb.shape[1]
    rgb_p = jnp.pad(rgb, ((0, pad_h - h), (0, pad_w - w), (0, 0)), mode="edge")
    y, cb, cr = color.rgb_to_yuv420(rgb_p, matrix="video")

    def q(p):
        return jnp.clip(jnp.round(p), 0, 255).astype(jnp.uint8)

    return q(y), q(cb), q(cr)


@functools.partial(jax.jit, static_argnames=("pad_h", "pad_w"))
def _stack_luma(rgbs, pad_h: int, pad_w: int):
    """Staged RGB chunk (K, H, W, 3) -> padded luma stack (K, ph, pw):
    the content-stats twin of the chunk scan's in-graph ingest (same
    color program, luma only — stats never touch chroma)."""
    return jax.vmap(lambda f: _yuv_stage(f, pad_h, pad_w)[0])(rgbs)


@jax.jit
@jax.named_scope("dngd.deblock_bs")
def _cabac_bs_inputs(luma, mv):
    """The loop filter's bS inputs from the P stage's levels, as ONE
    device program (the CAVLC program computes them inside itself)."""
    from ..ops.h264_device import nnz_blocks_raster
    return nnz_blocks_raster(luma), mv.astype(jnp.int32)


def spatial_auto_shards(width: int, height: int, fps: float = 60.0,
                        n_devices: int = None, model=None) -> int:
    """Chips ONE session of this geometry should spread across
    (ENCODER_SPATIAL_SHARDS=auto): the fleet capacity model's modeled
    per-chip cost against the ACTIVE SLO rung's budget (obs/budget
    ladder; frame interval for off-ladder geometry).  1 = the geometry
    fits one chip — spatial sharding stays off.  The caller still
    clamps to what the geometry divides into
    (``parallel.batch.feasible_spatial_shards``)."""
    if n_devices is None:
        import jax
        n_devices = len(jax.devices())
    if model is None:
        from ..fleet.capacity import CapacityModel
        model = CapacityModel()
    from ..obs.budget import SLO_LADDER
    rung = next((r for r in SLO_LADDER
                 if r.matches(width, height, fps)), None)
    budget = (rung.budget_ms if rung is not None
              else 1000.0 / max(float(fps), 1.0))
    return model.chips_for_session(width, height, fps,
                                   max_chips=max(int(n_devices), 1),
                                   budget_ms=budget)


class H264Encoder(Encoder):
    codec = "h264"

    def __init__(self, width: int, height: int, qp: int = 26,
                 mode: str = "cavlc", entropy: str = "device",
                 keep_recon: bool = False, host_color: bool = False,
                 gop: int = 1, bitrate_kbps: int = 0, fps: float = 60.0,
                 deblock: bool = False, intra_modes: str = None,
                 superstep_chunk: int = None, spatial_shards=None,
                 tune: str = None, damage_mask: bool = None,
                 row_align: int = None):
        """``mode``: "cavlc", the one mode there is; any other value is
        refused (the argument is kept for a caller that still names it).
        ``entropy``: where/how entropy coding runs —
        "device" (TPU CAVLC, via ops/cavlc_device: only the packed
        bitstream crosses the host link), "python" (the host CAVLC
        reference coder: the tests' reference and the device path's
        overflow fallback, synchronous), or "cabac" (Main-profile
        entropy_coding_mode_flag=1 streams, ~10-15% smaller at equal PSNR
        — the reference's nvh264enc default, ref Dockerfile:210: the
        device binarizes and the host runs the arithmetic engine,
        :attr:`cabac_device_binarize`).
        ``keep_recon``: pull reconstruction planes to the host each frame
        (tests/PSNR only — it costs a multi-MB transfer per frame).
        ``host_color``: convert RGB->YUV420 on the host with cv2 before
        upload (halves host->device bytes; negligibly different rounding
        from the device conversion, so off by default for the byte-identity
        tests and on for the serving/bench flagship).
        ``gop``: keyframe interval (ENCODER_GOP); 1 = all-intra.  With
        gop > 1, non-key frames use the inter stage (ops/h264_inter) with
        the reference picture held on device.
        ``bitrate_kbps``: > 0 enables the rate controller (ENCODER_BITRATE_
        KBPS): per-frame qp adaptation in quantized steps (each distinct qp
        compiles once).
        ``deblock``: normative in-loop deblocking (ops/h264_deblock):
        slice headers signal disable_deblocking_filter_idc=2 and the
        reference planes P frames predict from are loop-filtered exactly
        as a conformant decoder filters them.
        ``row_align``: the coded picture's macroblock rows are a multiple
        of this, the added lines repeating the last one and cropped back
        by the SPS (as a height that is no multiple of 16 is padded).
        Left None it is the shard count ``spatial_shards`` resolves to
        (the coded height follows the mesh: 2160 lines on four chips are
        coded as 136 rows, 34 a shard) and 1 without shards; given, a
        one-chip encoder codes the very picture a mesh of that many
        shards does (the byte-identity reference of the sharded path)."""
        super().__init__(width, height)
        if mode != "cavlc":
            raise ValueError(f"unknown h264 mode {mode!r}")
        if entropy not in ("device", "python", "cabac"):
            raise ValueError(f"unknown entropy {entropy!r}")
        self.qp = qp
        self.entropy = entropy
        self.keep_recon = keep_recon
        self.host_color = host_color
        self.gop = max(int(gop), 1)
        self.deblock = bool(deblock)
        self._deblock_idc = 2 if self.deblock else 1
        # -- perceptual-efficiency tuning tier (ENCODER_TUNE) ----------
        # "off" = byte-identical to the pre-tune encoder; "hq" = per-MB
        # adaptive quantization + Lagrangian mode decisions + optional
        # 1-frame lookahead (ops/aq).  Under the loop filter the tier is
        # served whole on the per-frame, one-chip, device-CAVLC path
        # (:attr:`_hq_loop`, decided at the end of this constructor: the
        # filter takes the plane of effective qps and the intra flags);
        # on every other path with the filter on the kernel tune is
        # "hq_noaq" (the lambda decisions at one qp a slice).
        if tune is None:
            import os
            tune = os.environ.get("ENCODER_TUNE", "off") or "off"
        # "hq_noaq" (lambda mode decisions at uniform slice qp) is the
        # kernel tier hq degrades to under deblock; the BD-rate bench
        # constructs it directly to attribute gains between the lambda
        # decisions and the qp plane.  The config surface stays off|hq.
        if tune not in ("off", "hq", "hq_noaq"):
            # warn-and-serve, like ENCODER_SPATIAL_SHARDS: a typo'd env
            # value must not kill every session at construction
            log.warning(
                "unknown ENCODER_TUNE %r: serving tune=off", tune)
            tune = "off"
        self.tune = tune
        self._hq_loop = False
        self._ktune = "hq_noaq" if tune == "hq" and self.deblock else tune
        # where a CABAC stream is binarized (:attr:`cabac_device_binarize`):
        # on the device, except that the record stream carries no per-MB
        # qp, so the tier that codes one keeps the level transport
        self._cabac_dev_bin = self._ktune != "hq"
        # I_16x16-in-P lambda mode decision (the intra escape for
        # content ME cannot track).  v1 plumbing: the device + python
        # CAVLC coders; gated off under deblock outside the served path
        # (:attr:`_hq_loop`: the ring's, the mesh's and the row programs'
        # filter calls hand over no intra flags) and CABAC (no I16-in-P
        # binarize records).
        self._p_intra = (self._ktune != "off" and not self.deblock
                         and entropy in ("device", "python"))
        self._mean_qp_pending = None     # per-frame mean coded qp (hq)
        # Intra mode-set selection ("auto" fast sets / "full" nine-mode
        # I4x4, ENCODER_INTRA_MODES).
        if intra_modes not in (None, "auto", "full", "i16", "dc"):
            raise ValueError(f"unknown intra_modes {intra_modes!r}")
        self.i16_modes = intra_modes or "auto"
        self.last_recon = None
        self.fps = float(fps)
        self._spatial_req = spatial_shards
        self._spatial_nx_cached = None
        self.row_align = max(int(
            self._spatial_plan() if row_align is None else row_align), 1)
        self.pad_w = round_up(width, 16)
        self.pad_h = round_up(height, 16 * self.row_align)
        self.mb_w = self.pad_w // 16
        self.mb_h = self.pad_h // 16
        cabac = entropy == "cabac"
        if cabac:
            # Fail fast: table recovery needs libx264/libavcodec on the
            # host.  Checked here rather than lazily at the first frame so
            # a misconfigured deployment dies at startup instead of going
            # unhealthy frame-by-frame inside the serving loop.
            from ..bitstream import cabac_tables
            from ..native import lib as native_lib
            from ..ops import level_pack
            cabac_tables.engine_tables()
            cabac_tables.context_init_tables()
            # one pull helper a kind of frame, for either transport
            hdrw = level_pack.header_words(self.mb_h)
            self._cabac_pull = {"intra": PrefixPull(hdrw, 8),
                                "p": PrefixPull(hdrw, 4)}
            # ... and one a row bucket of a damage-masked P frame, made
            # when the bucket is first met (_cabac_mask_pull)
            self._cabac_mask_pulls = {}
            # never silently: without the compiled engine every frame is
            # coded by the Python one, and counted as such
            self._cabac_native = (native_lib.has_cabac_engine()
                                  if self.cabac_device_binarize
                                  else native_lib.has_cabac())
            if not self._cabac_native:
                log.error(
                    "ENCODER_ENTROPY=cabac without the native engine (no "
                    "g++, or native/cabac.cpp did not build): every frame "
                    "is coded by the Python engine, about 100x slower; "
                    "counted in dngd_encoder_cabac_fallback_total"
                    "{kind=\"python\"}")
        self._sps = syn.sps_rbsp(width, height, fps,
                                 profile="main" if cabac else "baseline",
                                 coded_height=self.pad_h)
        self._pps = syn.pps_rbsp(init_qp=qp, cabac=cabac)
        self._hdr_slots_cache = {}
        # GOP / reference state (device-resident planes)
        self._ref = None
        self._frame_num = 0
        self._gop_pos = 0
        self._force_idr = False
        self._idr_count = 0
        self._rate = (RateController(qp, bitrate_kbps, fps)
                      if bitrate_kbps > 0 else None)
        self._forced_qp = None          # prewarm(): pin the ladder step
        self.degrade_qp_offset = 0      # resilience/degrade ladder bias
        # The flat buffer's pull, one helper a kind of frame as the CABAC
        # transport's above: the prefix must cover the LARGEST recent
        # frame, not the previous one — content whose size alternates
        # across frames would otherwise mispredict half the time, and
        # every mispredict costs a serial second device pull (a full
        # host<->device round trip).
        self._flat_pull = {"intra": FlatPull(4), "p": FlatPull(2)}
        # -- super-step ring (ops/devloop.build_p_chunk_step) ----------
        # P frames are staged host-side into a GOP-chunk ring and the
        # whole chunk is dispatched as ONE donated-buffer XLA program
        # (ENCODER_SUPERSTEP_CHUNK; 0 = per-frame dispatch).  Ring
        # eligibility is resolved lazily (_ring_chunk).
        if superstep_chunk is None:
            import os
            superstep_chunk = int(
                os.environ.get("ENCODER_SUPERSTEP_CHUNK", "0") or 0)
        self.superstep_chunk = int(superstep_chunk)
        self._ring = None               # the chunk currently staging
        self._ring_chunk_cached = None
        self._chunk_hdr_cache = {}
        # -- spatial mesh sharding (ENCODER_SPATIAL_SHARDS) ------------
        # ONE session's frame split over several chips' MB rows
        # (parallel/batch spatial steps): the resolution-ladder lever
        # for geometry whose modeled per-chip cost exceeds its SLO
        # rung.  The count is planned at construction (the coded height
        # follows it: _spatial_plan) and resolved at the first frame
        # (_spatial_nx).
        self._sp_steps = {}
        self._sp_pull = None
        self._sp_mesh_cache = None
        self._sp_hdr_cache = {}
        # dispatch accounting (obs/budget 'dispatch' stage): Python ->
        # device crossings + submit-to-launch gap, popped per frame by
        # the session via pop_dispatch_sample()
        self._disp_count = 0
        self._disp_gap_ms = 0.0
        self._disp_seen = 0
        self._disp_gap_seen = 0.0
        # frame-journey attribution (obs/journey): per-collect chunk
        # identity so per-frame device spans amortize honestly over the
        # super-step ring; chunk ids are per-encoder monotonic
        self._chunk_seq = 0
        self._journey_meta = None
        # content & quality telemetry (obs/content, ISSUE 17): the
        # previous INGEST luma (never donated — safe to hold across
        # frames), per-frame stats handles keyed by frame index, and
        # the last collected frame's decoded stats dict
        self._content_prev_y = None
        self._content_last = None
        self._content_pending = {}
        self._content_meta = None
        self._content_n = 0
        # -- damage-driven encode (ops/damage_mask) --------------------
        # Per-frame device cost proportional to CHANGED rows: the host
        # twin of the content plane's damage grid (same kernel, same
        # threshold — one substrate) compacts each P frame to a padded
        # damaged-row worklist; untouched rows ship as host-cached
        # all-skip slices and cost the device nothing.  Requires the
        # host-color ingest (the gating grid diffs host luma — no
        # device round-trip) and the device CAVLC path; keep_recon
        # (tests/PSNR debug) stays on the unmasked program.  Default
        # OFF (DNGD_DAMAGE_MASK): mask off is byte-identical to the
        # pre-mask encoder.
        if damage_mask is None:
            from ..ops import damage_mask as _dmg
            damage_mask = _dmg.enabled()
        self.damage_mask = bool(damage_mask)
        self._damage_prev_y = None       # previous frame's ingest luma
        self._damage_cur_y = None        # current frame's ingest luma
        self._damage_frac = None         # latest gated damage fraction
        # -- ENCODER_TUNE=hq under the loop filter ---------------------
        # Served whole where a frame is ONE device-CAVLC program on one
        # chip and the filter is a program of its own behind it: qp is a
        # traced scalar there as at tune=off (:attr:`_dyn_qp`), the plane
        # of effective qps and the I_16x16 flags go to the filter
        # (:meth:`_deblock`).  The ring, the mesh, the damage mask and
        # CABAC keep the parent's tier.
        if tune == "hq" and self.deblock:
            if (entropy == "device" and not self.damage_mask
                    and not self._ring_chunk and self._spatial_nx == 1):
                self._hq_loop = True
                self._ktune = "hq"
                self._p_intra = True
            else:
                log.warning(
                    "ENCODER_TUNE=hq with deblock on, outside the "
                    "per-frame one-chip device-CAVLC path: per-MB "
                    "adaptive quantization and I_16x16 in P are disabled "
                    "(lambda mode decisions stay active): this path's "
                    "loop filter is handed one qp a slice")

    def headers(self) -> bytes:
        return (syn.nal_unit(syn.NAL_SPS, self._sps)
                + syn.nal_unit(syn.NAL_PPS, self._pps))

    # -- dispatch accounting (obs/budget 'dispatch' stage) -------------

    def _count_dispatch(self, t0: float = None, ms: float = 0.0) -> None:
        """One Python -> device crossing; ``t0`` = the submit path's
        entry, so the accumulated gap is the submit-to-launch cost.  The
        served per-frame paths pass ``ms``, their ``dispatch`` stage
        span's own duration, instead."""
        self._disp_count += 1
        self._disp_gap_ms += (ms if t0 is None
                              else (time.perf_counter() - t0) * 1e3)

    def pop_dispatch_sample(self):
        """(crossings, gap_ms) accrued since the last pop — the
        session calls this once per submitted frame and feeds the
        budget ledger, so crossings-per-frame is a scraped gauge.  A
        ring-staged frame costs 0 crossings; the chunk-dispatch frame
        carries the whole chunk's single crossing."""
        delta = self._disp_count - self._disp_seen
        gap = self._disp_gap_ms - self._disp_gap_seen
        self._disp_seen = self._disp_count
        self._disp_gap_seen = self._disp_gap_ms
        return delta, gap

    def pop_journey_meta(self):
        """Chunk/shard identity of the LAST collected frame (set by
        encode_collect, cleared by this pop): chunk_id is None for
        per-frame dispatches (including a flushed partial ring — those
        frames really did pay their own dispatch), chunk_len > 1 marks
        a super-step frame whose device span should be amortized, and
        shards carries the spatial-mesh extent."""
        meta = self._journey_meta
        self._journey_meta = None
        return meta

    # -- content & quality telemetry (obs/content, ISSUE 17) -----------
    #
    # Every submit path dispatches the small ops/content_stats program
    # INSIDE its existing submit event, right after _count_dispatch —
    # so the stats jit rides the already-counted crossing and
    # dispatch_crossings_per_frame is byte-for-byte unchanged.  Stats
    # never feed back into the encode graph (bitstreams are identical
    # on/off, tested), and every hook is try/except-guarded: telemetry
    # must never kill a frame.

    def _content_enabled(self) -> bool:
        try:
            from ..obs import content as obsc
            return obsc.enabled()
        except Exception:
            return False

    def _content_submit(self, y, recon_y=None, mv=None, resid=None,
                        mb_intra=None, frame_type="p"):
        """Dispatch the in-graph stats kernel for one frame; sets
        ``self._content_last`` to a device-handle dict (or None when
        disabled / cadence-skipped / first frame / resize)."""
        self._content_last = None
        try:
            if not self._content_enabled():
                self._content_prev_y = None
                return
            from ..obs import content as obsc
            from ..ops import content_stats as cs
            self._content_n += 1
            prev = self._content_prev_y
            # the prev-ingest luma advances even on skipped frames so
            # damage stays strictly frame-to-frame (ingest planes are
            # never donated — holding them across frames is safe)
            self._content_prev_y = y
            if (self._content_n - 1) % obsc.sample_every():
                return
            # the first ingest (or a post-resize one) has no reference:
            # run the kernel self-diff so PSNR/mode/activity still land,
            # and null the damage fields at finish — self-diff is not
            # damage
            first = prev is None or tuple(getattr(prev, "shape", ())) \
                != tuple(getattr(y, "shape", ()))
            vec, grid = cs.frame_stats(
                y, y if first else prev, recon_y, mv,
                tuple(resid) if resid else None, mb_intra,
                obsc.damage_thr_sad())
            self._content_last = {"vec": vec, "grid": grid,
                                  "frame_type": frame_type,
                                  "first": first}
        except Exception:
            self._content_last = None

    def _content_stash(self, idx: int) -> None:
        """Move the submit-path handle under the frame index (popped by
        the matching collect; bounded against never-collected tokens)."""
        h = self._content_last
        self._content_last = None
        if h is not None:
            if len(self._content_pending) > 32:
                self._content_pending.clear()
            self._content_pending[idx] = h

    def _content_ring_dispatch(self, ring, args, ry, mvs, lvs) -> None:
        """Chunk-ring twin of :meth:`_content_submit`: one vmapped
        stats program per dispatched chunk.  yuv rings carry the full
        stat set; an rgb ring first runs its staged stack through a
        jitted luma twin of the chunk's in-graph ingest (same color
        program, so damage is computed on exactly the luma the scan
        encodes); spatial chunks keep their staged full-frame planes
        but the step's recon/mv tensors are shard-local, so PSNR and
        mode-mix are excluded for them — damage and activity still
        land (documented exclusion, obs/content)."""
        try:
            if not self._content_enabled():
                self._content_prev_y = None
                return
            from ..obs import content as obsc
            from ..ops import content_stats as cs
            if ring["ingest"] == "rgb":
                ys = _stack_luma(jnp.asarray(args[0]), self.pad_h,
                                 self.pad_w)
            else:
                ys = args[0]
            if self._spatial_nx > 1:
                ry = mvs = lvs = None    # shard-local layouts
            prev = self._content_prev_y
            self._content_prev_y = ys[-1]
            self._content_n += len(ring["fns"])
            if prev is None or tuple(getattr(prev, "shape", ())) != \
                    tuple(ys.shape[1:]):
                return
            resid = None
            if isinstance(lvs, dict):
                keys = ("luma", "cb_dc", "cb_ac", "cr_dc", "cr_ac")
                if all(k in lvs for k in keys):
                    resid = tuple(lvs[k] for k in keys)
            vecs, grids = cs.chunk_stats(
                jnp.asarray(ys), prev, ry, mvs, resid,
                obsc.damage_thr_sad())
            ring["content"] = {"vecs": vecs, "grids": grids}
        except Exception:
            ring.pop("content", None)

    def _content_finish(self, token, data: bytes) -> None:
        """Decode the collected frame's stats handle into the dict the
        session pops via :meth:`pop_content_stats`."""
        self._content_meta = None
        try:
            kind, idx, t0, key, payload = token
            if not self._content_enabled():
                return
            from ..ops import content_stats as cs
            h = None
            if kind == "ring":
                ring, slot = payload
                if ring.get("pf") is not None:
                    pf = ring.get("content_pf") or []
                    h = pf[slot] if slot < len(pf) else None
                elif ring.get("content") is not None:
                    cnp = ring.get("content_np")
                    if cnp is None:
                        c = ring["content"]
                        cnp = ring["content_np"] = (
                            np.asarray(c["vecs"]),
                            np.asarray(c["grids"]))
                    h = {"vec": cnp[0][slot], "grid": cnp[1][slot],
                         "frame_type": "p"}
            else:
                h = self._content_pending.pop(idx, None)
            if h is None:
                return
            vec, grid = np.asarray(h["vec"]), np.asarray(h["grid"])
            if kind in ("cabac_p", "cabac_intra", "cabac_p_mask"):
                _M_D2H_BYTES.inc(vec.nbytes + grid.nbytes)
            stats = cs.vec_to_stats(vec, grid, self.pad_h * self.pad_w)
            if h.get("first"):
                stats["damage_fraction"] = None
                stats["damage_grid"] = None
            ft = h.get("frame_type", "p")
            if ft == "intra" and stats.get("mode") is None \
                    and stats.get("mbs"):
                # intra frames carry no mode tensors: every MB is intra
                stats["mode"] = {"skip": 0.0, "inter": 0.0,
                                 "intra": 1.0}
            stats["frame_type"] = ft
            stats["au_bytes"] = len(data)
            stats["tier"] = self._ktune
            self._content_meta = stats
        except Exception:
            self._content_meta = None

    def pop_content_stats(self):
        """Content stats of the LAST collected frame (set by
        encode_collect, cleared by this pop), or None — same contract
        as :meth:`pop_journey_meta`."""
        m = self._content_meta
        self._content_meta = None
        return m

    # -- super-step ring eligibility -----------------------------------

    @property
    def _ring_chunk(self) -> int:
        """Frames per super-step chunk (0 = ring off).  The ring needs
        a GOP (P frames to chain), a device-resident entropy path
        (:attr:`_device_entropy`), and no per-frame recon pulls
        (``keep_recon`` is the tests' PSNR hook — the chunk step keeps
        recon on device by design)."""
        c = self._ring_chunk_cached
        if c is None:
            c = 0
            if (self.superstep_chunk >= 2 and self.gop > 1
                    and not self.keep_recon and self._device_entropy):
                # <= 6 so ring depth + pipeline never outruns the rate
                # controller's MAX_INFLIGHT reservation window
                c = max(2, min(self.superstep_chunk, 6))
            self._ring_chunk_cached = c
        return c

    @property
    def pipeline_depth(self) -> int:
        """Frames the serving loop should keep in flight: chunk + 1 in
        ring mode (the +1 lets chunk N's collect overlap chunk N+1's
        staging), the classic 2 otherwise."""
        c = self._ring_chunk
        return c + 1 if c else 2

    # ------------------------------------------------------------------
    # Spatial mesh sharding: ONE session's frame across N chips
    #
    # The batch managers shard populations of sessions; this shards a
    # single session's MB rows over a (1, N) mesh when one chip cannot
    # close the geometry's budget: 4K30 CABAC on ONE v5e chip delivers
    # 21.5 of 30 frames/s at 43-44 ms of device a frame (the benchmark's
    # cell desk2160-cabac.fulldamage); over the four chips of a host it
    # is the cell desk2160-cabac-mesh4.fulldamage (PERF.md section 4).
    # The coded height follows the mesh (row_align: 2160 lines are coded
    # as 136 rows over four chips, the SPS crops the padding row).
    # The sharded steps live in parallel/batch (h264_spatial_*); the
    # assembled AU is byte-identical to the single-device path — CAVLC
    # shards concatenate NAL-by-NAL (slice-per-MB-row), CABAC binarize
    # record streams stitch row-wise (ops/cabac_binarize.stitch_rows)
    # before the unchanged host arithmetic engine.  The reference ring
    # lives SHARDED on device between frames/chunks under one fixed
    # P("spatial", None) spec.
    # ------------------------------------------------------------------

    def _spatial_plan(self) -> int:
        """Shards the request asks for and the host can give (1 = off).
        Eligibility mirrors the super-step ring's: device-resident
        entropy (:attr:`_device_entropy`) and no per-frame recon pulls
        (``keep_recon`` is the tests' PSNR hook;
        the sharded recon stays distributed by design).  Asked at
        construction, where the coded height follows the answer
        (``row_align``), and again at the first frame."""
        req = self._spatial_req
        if req is None:
            import os
            req = os.environ.get("ENCODER_SPATIAL_SHARDS", "0")
        req = str(req).strip() or "0"
        eligible = not self.keep_recon and self._device_entropy
        if not eligible or req in ("0", "1", "off"):
            return 1
        import jax
        ndev = len(jax.devices())
        if req == "auto":
            want = spatial_auto_shards(self.width, self.height, self.fps,
                                       n_devices=ndev)
        else:
            try:
                want = int(req)
            except ValueError:
                # a typo'd knob must not kill every frame of the
                # session — warn, serve unsharded
                log.warning("ENCODER_SPATIAL_SHARDS=%r not understood; "
                            "spatial sharding off", req)
                want = 1
        if want <= 1 or ndev <= 1:
            return 1
        from ..parallel import batch
        return batch.feasible_spatial_shards(self.height, want, ndev)

    @property
    def _spatial_nx(self) -> int:
        """Resolved spatial shard count (1 = off): the plan, where the
        coded picture's rows divide over it (they do when the plan set
        ``row_align``; an encoder built with another alignment serves
        on one chip)."""
        n = self._spatial_nx_cached
        if n is None:
            from ..parallel import batch
            n = self._spatial_plan()
            if n > 1 and (self.mb_h % n
                          or not batch.p_halo_feasible(self.pad_h, n)):
                log.warning("%d MB rows do not divide over %d shards "
                            "(row_align=%d): spatial sharding off",
                            self.mb_h, n, self.row_align)
                n = 1
            if n > 1:
                _M_MESH_SHARDS.set(n)
            self._spatial_nx_cached = n
        return n

    def _sp_rows_local(self) -> int:
        return self.mb_h // self._spatial_nx

    def _sp_mesh(self):
        if self._sp_mesh_cache is None:
            from ..parallel import batch
            self._sp_mesh_cache = batch.make_spatial_mesh(
                self._spatial_nx)
        return self._sp_mesh_cache

    def _sp_step(self, kind: str, qp: int):
        """Cached sharded step builders.  Where qp is traced
        (:attr:`_dyn_qp`, the served default) one program a kind serves
        every qp and the step is handed it as its last operand
        (:meth:`_sp_qp_operand`); the hq tiers compile one a (kind,
        qp)."""
        key = (kind, None if self._dyn_qp else qp)
        got = self._sp_steps.get(key)
        if got is None:
            from ..parallel import batch
            ent = "cabac" if self.entropy == "cabac" else "cavlc"
            mesh = self._sp_mesh()
            if kind == "intra":
                got, _ = batch.h264_spatial_intra_step(
                    mesh, self.pad_h, self.pad_w, key[1], entropy=ent,
                    i16_modes=self.i16_modes, deblock=self.deblock,
                    with_recon=self.gop > 1, tune=self._ktune)
            else:
                got, _ = batch.h264_spatial_step(
                    mesh, self.pad_h, self.pad_w, key[1],
                    deblock=self.deblock, entropy=ent,
                    tune=self._ktune, p_intra=self._p_intra,
                    masked=(kind == "p_masked"))
            self._sp_steps[key] = got
        return got

    def _sp_qp_operand(self, qp: int) -> tuple:
        """The traced qp as the step's last operand, or nothing where
        the step closed over it."""
        return (np.int32(qp),) if self._dyn_qp else ()

    def _sp_cabac_pull(self, kind: str) -> PrefixPull:
        """The pull helper of one kind of frame's per-shard record
        buffers (header words of a SHARD's rows; the guess covers the
        longest shard)."""
        if self._sp_pull is None:
            from ..ops import cabac_binarize
            hdrw = cabac_binarize.header_words(self._sp_rows_local())
            self._sp_pull = {"intra": PrefixPull(hdrw, 8),
                             "p": PrefixPull(hdrw, 4)}
        return self._sp_pull[kind]

    def _sp_hdr_slots(self, idr: bool, frame_num: int,
                      idr_pic_id: int, qp_delta: int):
        """Slice-header slots kept as HOST arrays: shard_map shards
        them per its in_spec; a cached device-committed copy would be
        resharded every dispatch."""
        key = (idr, frame_num, idr_pic_id, qp_delta)
        got = self._sp_hdr_cache.get(key)
        if got is None:
            from ..ops import cavlc_device
            if idr:
                hv, hl = cavlc_device.slice_header_slots(
                    self.mb_h, self.mb_w, frame_num=0,
                    idr_pic_id=idr_pic_id, qp_delta=qp_delta,
                    deblocking_idc=self._deblock_idc)
            else:
                hv, hl = cavlc_device.slice_header_slots(
                    self.mb_h, self.mb_w, frame_num=frame_num,
                    qp_delta=qp_delta, slice_type=5, idr=False,
                    deblocking_idc=self._deblock_idc)
            got = (np.asarray(hv), np.asarray(hl))
            self._sp_hdr_cache[key] = got
        return got

    def _sp_record_stitch(self, ms: float) -> None:
        """The host-side shard assembly/stitch cost, to the budget
        ledger too (obs/budget ``bitstream-stitch`` row, dngd_stitch_ms
        gauge); the stage span ``stitch`` is what measured it."""
        try:
            from ..obs.budget import LEDGER
            LEDGER.record_spatial(stitch_ms=ms)
        except Exception:
            pass

    def _sp_submit_intra(self, idr_pic_id: int, begun):
        qp, (y, cb, cr) = begun                   # of _intra_begin
        with obst.stage("dispatch") as span:
            step = self._sp_step("intra", qp)
            _note_h2d(y, cb, cr)
            if self.entropy == "cabac":
                out = step(y, cb, cr, *self._sp_qp_operand(qp))
                if self.gop > 1:
                    buf, ry, rcb, rcr, lv = out
                    # reference advances at submit time (sharded device
                    # futures; deblock fused in the sharded program)
                    self._ref = (ry, rcb, rcr)
                else:
                    buf, lv = out
                marker = "sp_bin"
                prefix = self._sp_cabac_pull("intra").prefix(buf)
            else:
                hv, hl = self._sp_hdr_slots(True, 0, idr_pic_id,
                                            qp - self.qp)
                out = step(y, cb, cr, hv, hl, *self._sp_qp_operand(qp))
                if self.gop > 1:
                    buf, ry, rcb, rcr = out
                    self._ref = (ry, rcb, rcr)
                else:
                    buf = out
                marker, lv = "sp", None
                prefix = self._flat_pull["intra"].prefix(buf)
            # sharded stats: damage + activity only (recon/MV layouts
            # are per-shard; the global-reduce stats stay exact)
            self._content_submit(y, frame_type="intra")
        self._count_dispatch(ms=span.ms)
        return (marker, "intra", qp, idr_pic_id, 0, buf, prefix, lv)

    def _sp_submit_p(self, y, cb, cr, qp: int, frame_num: int = None):
        from ..parallel import batch

        with obst.stage("dispatch") as span:
            frame_num = self._frame_num if frame_num is None else frame_num
            _note_h2d(y, cb, cr, *self._ref)
            qp_t = self._sp_qp_operand(qp)
            if self.entropy == "cabac":
                step = self._sp_step("p", qp)
                buf, ry, rcb, rcr, mv, lv = step(y, cb, cr, *self._ref,
                                                 *qp_t)
                marker = "sp_bin"
                prefix = self._sp_cabac_pull("p").prefix(buf)
            else:
                hv, hl = self._sp_hdr_slots(False, frame_num, 0,
                                            qp - self.qp)
                keep = self._sp_damage_keep()
                if keep is not None:
                    step = self._sp_step("p_masked", qp)
                    buf, ry, rcb, rcr, mv, lv = step(
                        y, cb, cr, *self._ref, hv, hl, keep, *qp_t)
                else:
                    step = self._sp_step("p", qp)
                    buf, ry, rcb, rcr, mv, lv = step(
                        y, cb, cr, *self._ref, hv, hl, *qp_t)
                marker = "sp"
                prefix = self._flat_pull["p"].prefix(buf)
            # what one chip receives in this frame's one collective, from
            # the operands' shapes (the per-frame steps gather nothing:
            # dngd_mesh_gather_bytes_total stays 0)
            _M_MESH_HALO_BYTES.inc(batch.spatial_halo_bytes(
                self.pad_w, self._spatial_nx, self._ref[0].dtype.itemsize))
            self._ref = (ry, rcb, rcr)
            self._content_submit(y)
        self._count_dispatch(ms=span.ms)
        return (marker, "p", qp, 0, frame_num, buf, prefix, (lv, mv))

    def _sp_collect(self, submitted) -> bytes:
        marker, kind, qp, idr_pic_id, frame_num, buf, prefix, lv_mv = \
            submitted
        if marker == "sp":
            return self._sp_collect_flat(kind, qp, idr_pic_id,
                                         frame_num, buf, prefix, lv_mv)
        return self._sp_collect_bin(kind, qp, idr_pic_id, frame_num,
                                    buf, prefix, lv_mv)

    def _sp_collect_flat(self, kind: str, qp: int, idr_pic_id: int,
                         frame_num: int, flat, prefix, lv_mv) -> bytes:
        """Assemble a spatially-sharded CAVLC AU: per-shard FlatMeta +
        NAL concatenation (slice-per-MB-row makes shards self-contained
        — the 'stitch' is pure byte concatenation).  The one-chip path's
        pull (:class:`FlatPull`), every shard at the longest's length."""
        from ..bitstream import h264 as syn, h264_entropy
        from ..ops import cavlc_device

        got = self._flat_pull[kind].pull(flat, prefix,
                                         self._sp_rows_local())
        t0 = time.perf_counter()                  # post-pull: stitch only
        if got is None:
            _note_entropy_overflow("spatial " + kind)
            if kind == "p" and lv_mv is not None:
                # host-entropy the sharded stage's OWN level tensors
                # (gathered lazily only on this rare path) — identical
                # bytes, no access to the consumed reference ring
                lv, mv = lv_mv
                pulled = {k: np.asarray(v) for k, v in lv.items()}
                pulled["mv"] = np.asarray(mv)
                qp_map = pulled.pop("qp_map", None)
                self._note_qp_map(qp_map, levels=pulled, slice_qp=qp)
                return h264_entropy.encode_p_picture(
                    pulled, frame_num=frame_num,
                    qp_delta=qp - self.qp,
                    deblocking_idc=self._deblock_idc,
                    qp_map=qp_map, slice_qp=qp)
            # intra overflow is pathological-qp only; the session's
            # resilience path turns this into an IDR resync
            raise RuntimeError("spatial intra shard overflow")
        bufs, metas = got
        self._note_qp_sum(sum(m.qp_sum for m in metas))
        parts = [self.headers()] if kind == "intra" else []
        parts += [cavlc_device.assemble_annexb(
            buf_i, m, nal_type=None if kind == "intra" else syn.NAL_SLICE,
            ref_idc=3 if kind == "intra" else 2)
            for buf_i, m in zip(bufs, metas)]
        au = b"".join(parts)
        self._sp_record_stitch((time.perf_counter() - t0) * 1e3)
        return au

    def _sp_collect_bin(self, kind: str, qp: int, idr_pic_id: int,
                        frame_num: int, buf, prefix, lv_mv) -> bytes:
        """Assemble a spatially-sharded CABAC AU: one pull of every
        shard's binarize record stream (the one-chip path's ladder of
        lengths, :class:`PrefixPull`), row-wise stitch into one
        whole-frame transport buffer (ops/cabac_binarize.stitch_rows),
        then the UNCHANGED host arithmetic engine — byte-identical to
        the single-device path.  Stages as on one chip (``pull``,
        ``pull_extra``, ``assemble`` with ``engine`` inside), and
        ``stitch`` inside ``assemble``."""
        from ..bitstream import h264_cabac
        from ..ops import cabac_binarize, level_pack

        rows_l = self._sp_rows_local()
        if not self._cabac_native:
            _M_CABAC_PYTHON.inc()
        heads = self._sp_cabac_pull(kind).pull(buf, prefix)
        with obst.stage("assemble", more=True):
            hdr = dict(qp=qp, qp_delta=qp - self.qp,
                       deblocking_idc=self._deblock_idc)
            if kind == "intra":
                hdr.update(frame_num=0, idr_pic_id=idr_pic_id,
                           sps=self._sps, pps=self._pps,
                           with_headers=True)
            else:
                hdr.update(frame_num=frame_num)
            au = None
            if heads is not None:
                with obst.stage("stitch") as span:
                    stitched = cabac_binarize.stitch_rows(list(heads),
                                                          rows_l)
                self._sp_record_stitch(span.ms)
                code = (h264_cabac.encode_intra_from_binstream
                        if kind == "intra"
                        else h264_cabac.encode_p_from_binstream)
                au = code(stitched, nr=self.mb_h, nc_mb=self.mb_w, **hdr)
            if au is not None:
                return au
            # overflow (packed stream or engine cap): dense fallback from
            # the sharded stage's own level tensors, gathered lazily
            _note_cabac_dense()
            if kind == "intra":
                lv = lv_mv
                dense = {k: np.asarray(lv[k])
                         for k, _, _ in level_pack.INTRA_KEYS}
                _M_D2H_BYTES.inc(sum(v.nbytes for v in dense.values()))
                dense.update({k: np.asarray(lv[k])
                              for k in ("pred_mode", "mb_i4", "i4_modes")})
                return h264_cabac.encode_intra_picture(dense, **hdr)
            lv, mv = lv_mv
            dense = {k: np.asarray(v) for k, v in lv.items()}
            _M_D2H_BYTES.inc(sum(v.nbytes for v in dense.values()))
            dense["mv"] = np.asarray(mv, np.int32)
            return h264_cabac.encode_p_picture(dense, **hdr)

    # ------------------------------------------------------------------
    # Intra path: one IDR through the stream's entropy coder
    # ------------------------------------------------------------------

    def _encode_cavlc(self, rgb) -> bytes:
        # Consecutive IDRs must carry different idr_pic_id; in GOP mode the
        # IDR cadence is the counter, in all-intra mode every frame is one.
        idr_pic_id = (self._idr_count if self.gop > 1
                      else self.frame_index) % 2
        if self.entropy == "device":
            return self._encode_cavlc_device(rgb, idr_pic_id)
        if self.entropy == "cabac":
            return self._collect_cabac_intra(
                self._submit_cabac_intra(rgb, idr_pic_id))

        return self._encode_host_entropy(rgb, idr_pic_id)

    _host_yuv_ok = None                            # class-level cv2 probe

    def _host_yuv420(self, rgb):
        """(y, cb, cr) uint8 planes padded to MB multiples, host-converted
        by the shared :mod:`..utils.hostcolor` path (cv2's bytes; in row
        bands where the host has cores to spare).  Returns None when cv2
        is unavailable (the device conversion takes over) or the geometry
        resists 4:2:0."""
        cls = type(self)
        if cls._host_yuv_ok is False:
            return None
        h, w = rgb.shape[:2]
        if h % 2 or w % 2:
            return None
        from ..utils.hostcolor import rgb_to_yuv420_host

        planes = rgb_to_yuv420_host(rgb, self.pad_h, self.pad_w,
                                    float_fallback=False)
        cls._host_yuv_ok = planes is not None
        if planes is not None and self.damage_mask:
            # damage-gating twin: the ingest luma chain advances on
            # EVERY host-converted frame (IDR, ring-staged, per-frame
            # alike) so the gating grid always diffs strictly
            # frame-to-frame — exactly the content plane's semantics.
            # (The chain keeps the planes themselves: each conversion
            # makes new ones and nothing writes to them afterwards.)
            self._damage_prev_y = self._damage_cur_y
            self._damage_cur_y = planes[0]
        return planes

    def _encode_cavlc_device(self, rgb, idr_pic_id: int) -> bytes:
        """Device-entropy path: one fused jit, one bucketed host pull."""
        return self._collect_device(self._submit_device(rgb, idr_pic_id))

    # tune=hq GOP-aware I/P split (the x264 ipratio / NVENC-HQ analog,
    # and the same principle as the ring lookahead: bias qp by how long
    # the bits LIVE).  The IDR is every P frame's transitive reference —
    # on skip-heavy desktop content the whole GOP's quality IS the IDR's
    # — so hq spends ~2^(3/6)=1.41x the bits on that one frame and earns
    # the dB back across every frame that references it.
    I_QP_BIAS = 3

    @property
    def _dyn_qp(self) -> bool:
        """The per-frame device-CAVLC and CABAC paths at tune=off run ONE
        compiled program set for every qp (qp is a traced scalar there):
        nothing is qp-specialized, so the rate ladder and the degrade
        bias move freely on a cold cache and there is no ladder to
        prewarm.  So does ``hq`` under the loop filter where it is served
        whole (:attr:`_hq_loop`: everything downstream of the slice qp
        is a plane there); the other hq paths keep qp static
        (``hq_noaq``'s lambda decisions are compile-time floats)."""
        return self._hq_loop or (self._ktune == "off"
                                 and self.entropy in ("device", "cabac"))

    def _deblock(self, y, cb, cr, qp: int, **bs_inputs):
        """In-loop filter of the per-frame device path (qp traced where
        the encode stage's is); ``bs_inputs``: a P frame's ``nnz_blk``
        and ``mv`` and, where ``hq`` is served whole, the frame's
        ``qp_eff`` plane and ``mb_intra`` flags."""
        from ..ops import h264_deblock
        if self._dyn_qp:
            return h264_deblock.deblock_frame_dynqp(y, cb, cr, np.int32(qp),
                                                    **bs_inputs)
        return h264_deblock.deblock_frame(y, cb, cr, qp, **bs_inputs)

    def _eff_qp(self, keyframe: bool = True) -> int:
        if self._forced_qp is not None:
            return self._forced_qp       # prewarm pins exact qps: no bias
        qp = self.qp if self._rate is None else self._rate.qp_for(keyframe)
        # gate on the KERNEL tier: the hq_noaq degrade (deblock) emits
        # no qp_sum meta, so a biased IDR there would be normalized at
        # the nominal qp and skew the keyframe EMA ~2^(3/6)
        if keyframe and self._ktune == "hq" and self.gop > 1:
            qp = max(qp - self.I_QP_BIAS, 1)
        # degradation-ladder bias (resilience/degrade via the session):
        # one coarse step, because each distinct qp is a jit specialization
        off = getattr(self, "degrade_qp_offset", 0)
        return min(51, max(0, qp + off)) if off else qp

    # -- qp-ladder prewarm -------------------------------------------------
    # Each distinct qp is one XLA compile of the static-qp device encode
    # (design note at RateController's docstring).  Without prewarm, the
    # first scene cut that moves the ladder stalls serving for a full
    # compile (tens of seconds on a cold cache).  prewarm_async() walks
    # the bounded ladder on a SCRATCH encoder in a background thread —
    # the process-wide jit cache is shared, so serving hits warm
    # executables; with the persistent compile cache (utils/jaxcache)
    # later processes skip even the first-ever compile.

    # The resilience ladder's qp_up rung biases the coded qp by this
    # much (resilience/degrade.SessionExecutor.QP_STEP mirrors it);
    # prewarm covers the biased variants so engaging degradation under
    # load does not stall serving on a fresh compile.
    DEGRADE_QP_OFFSETS = (4,)

    def ladder_qps(self) -> list:
        """Every qp the rate controller (or the degradation ladder) can
        request, nearest-first (the ladder moves in small steps, so
        near qps are needed soonest)."""
        if self._rate is None:
            base = {self.qp}
        else:
            base = {min(51, max(0, self.qp + s))
                    for s in RateController.STEPS}
        qps = set(base)
        for off in self.DEGRADE_QP_OFFSETS:
            qps |= {min(51, q + off) for q in base}
        if self._ktune == "hq" and self.gop > 1 and not self._dyn_qp:
            # IDRs code at qp - I_QP_BIAS (_eff_qp) — prewarm those
            # specializations too or the first hq scene cut compiles
            # (the ring's; where qp is traced the bias is arithmetic)
            qps |= {max(q - self.I_QP_BIAS, 1) for q in set(qps)}
        return sorted(qps, key=lambda q: (abs(q - self.qp), q))

    def prewarm(self, qps=None, stop=None) -> int:
        """Compile intra+P executables for each qp by driving the REAL
        encode path on a scratch encoder (exact jit-cache keys, robust to
        signature changes).  ``stop``: optional threading.Event to abort
        between steps.  Returns the number of qps warmed.

        Where qp is a traced scalar (:attr:`_dyn_qp`, the served
        default) no program is qp-specialized: there is nothing to warm,
        and compiling the one program set here BESIDE the serving thread
        doing the same is exactly the side-by-side TPU compile the
        installed libtpu does not survive (PERF.md Findings, PR 22)."""
        if self._dyn_qp and qps is None:
            log.info("qp-ladder prewarm: nothing to compile (qp is a "
                     "traced scalar on this path)")
            return 0
        qps = self.ladder_qps() if qps is None else list(qps)
        scratch = H264Encoder(
            self.width, self.height, qp=self.qp,
            entropy=self.entropy, host_color=self.host_color,
            gop=max(self.gop, 2), deblock=self.deblock,
            intra_modes=self.i16_modes,
            spatial_shards=self._spatial_nx, tune=self.tune,
            row_align=self.row_align)
        rgb = np.zeros((self.height, self.width, 3), np.uint8)
        t0 = time.perf_counter()
        done = 0
        for qp in qps:
            if stop is not None and stop.is_set():
                break
            scratch._forced_qp = qp
            scratch._force_idr = True
            scratch.encode(rgb)          # IDR at this qp
            scratch.encode(rgb)          # P at this qp (+deblock)
            done += 1
        log.info("qp-ladder prewarm: %d/%d qps in %.1f s", done, len(qps),
                 time.perf_counter() - t0)
        return done

    def warm_pulls(self) -> int:
        """Compile every prefix slice the per-frame CABAC path's two
        pulls can meet, up to the transport buffer's whole length (past
        it the stream's overflow flag is up and the levels go dense): one
        IDR and one P frame through a scratch encoder, whose programs
        and slices land in the process-wide jit cache this encoder
        shares.  For codec set-up (web/session.py under ENCODER_PREWARM),
        BEFORE frames are served: it compiles the path's programs too,
        and compiling beside the serving thread is what the installed
        libtpu does not survive (:meth:`prewarm`).  On a spatial mesh
        the scratch encoder runs this one's mesh and step programs, and
        the slices are those of the stacked per-shard buffers.  Returns
        the slices compiled.  A damage-mask session compiles its row
        programs here: instead, on the device CAVLC path, and behind the
        dense ladder under the CABAC stream (:meth:`_warm_row_buckets`,
        which does nothing where the mask is off); 0 on every other path
        (the CAVLC pull ladder is content's to walk: 64 KiB steps of a
        46 KB frame)."""
        if self.entropy == "device":
            return self._warm_row_buckets()
        if self.entropy != "cabac":
            return 0
        nx = self._spatial_nx
        if nx > 1 and self._ring_chunk:
            return 0
        t0 = time.perf_counter()
        scratch = H264Encoder(
            self.width, self.height, qp=self.qp,
            entropy=self.entropy, host_color=self.host_color, gop=2,
            deblock=self.deblock, intra_modes=self.i16_modes,
            superstep_chunk=0, spatial_shards=nx, tune=self.tune,
            damage_mask=False, row_align=self.row_align)
        scratch._cabac_dev_bin = self.cabac_device_binarize
        rgb = np.zeros((self.height, self.width, 3), np.uint8)
        if nx > 1:
            scratch._sp_mesh_cache = self._sp_mesh()
            scratch._sp_steps = self._sp_steps
            pulls = (scratch._sp_cabac_pull("intra"),
                     scratch._sp_cabac_pull("p"))
            buf = scratch._submit_cabac_intra(rgb, 0)[5]
            n = pulls[0].warm(buf)
            buf = scratch._sp_submit_p(
                *scratch._planes_device(rgb), self.qp)[5]
        else:
            pulls = (scratch._cabac_pull["intra"], scratch._cabac_pull["p"])
            buf = scratch._submit_cabac_intra(rgb, 0)[1]
            n = pulls[0].warm(buf)
            buf = scratch._submit_cabac_p(
                *scratch._planes_device(rgb), self.qp)[3]
        n += pulls[1].warm(buf)
        log.info("CABAC pull ladder: %d slices of %d-word buffers in "
                 "%.1f s", n, buf.shape[-1], time.perf_counter() - t0)
        return n + self._warm_row_buckets()

    def _warm_row_buckets(self) -> int:
        """Compile everything a damage-mask session's frames can ask for,
        before frames are served: the IDR program, the full-frame P
        program with its loop filter (the ladder's top), the row program
        of every bucket under it (``ops/damage_mask.bucket_ladder``: 1, 2,
        4 ... 64 at 100 rows; qp is traced, so one a bucket serves the
        whole rate ladder) and every prefix slice the pull ladder can cut
        off the flat buffer, which has one length for every bucket and
        for the dense frame.  One scratch encoder, a frame a program, one
        after the other (never side by side: :meth:`prewarm`).  Returns
        programs and slices compiled; 0 where the mask is off, and where
        qp is static (the hq tiers: a program a bucket AND a rung, which
        :meth:`prewarm` walks at the calm frame's bucket alone).  Under
        the CABAC stream the dense programs are :meth:`warm_pulls`' and
        the rest is :meth:`_warm_row_buckets_cabac`."""
        if not (self.damage_mask and self._dyn_qp and self.host_color
                and self.gop > 1) or self.keep_recon \
                or self._spatial_nx > 1 or self._ring_chunk:
            return 0
        if self.entropy == "cabac":
            return self._warm_row_buckets_cabac()
        from ..ops import damage_mask as dmg

        t0 = time.perf_counter()
        scratch = H264Encoder(
            self.width, self.height, qp=self.qp,
            entropy=self.entropy, host_color=True, gop=self.gop,
            deblock=self.deblock, intra_modes=self.i16_modes,
            superstep_chunk=0, spatial_shards=1, tune=self.tune,
            damage_mask=True, row_align=self.row_align)
        rgb = np.zeros((self.height, self.width, 3), np.uint8)
        scratch.encode(rgb)                       # the IDR
        planes = scratch._planes_device(rgb)
        if not isinstance(planes[0], np.ndarray):
            return 0                              # no host colour: no plan
        total = self.mb_h
        ladder = dmg.bucket_ladder(total)
        for bucket in [total] + ladder:           # the dense program first
            rows = np.arange(bucket, dtype=np.int32)
            sub = scratch._submit_p_device(
                *planes, self.qp,
                damage_plan=dmg.RowPlan(rows, rows, bucket, total, 1.0))
            scratch._collect_p_device(sub)
        flat = sub[4]
        slices = scratch._flat_pull["p"].warm(flat)
        log.info("damage mask: %d row programs (buckets %s of %d rows) "
                 "beside the IDR and the full-frame P program, %d prefix "
                 "slices of the %d-byte flat buffer, in %.1f s",
                 len(ladder), ladder, total, slices, flat.shape[0],
                 time.perf_counter() - t0)
        return len(ladder) + 2 + slices

    def _warm_row_buckets_cabac(self) -> int:
        """:meth:`_warm_row_buckets` for the CABAC stream, behind
        :meth:`warm_pulls`' dense ladder (which has compiled the IDR's
        and the dense P frame's programs and their pull slices): a frame
        through the row program of every bucket and the binarizer over
        its band (qp traced in the first, absent from the second: one
        compile a bucket each), every prefix slice of every bucket's
        record buffer, and the all-skip slice's data at every qp the
        stream can take (``h264_cabac.skip_row_payload``: 52 entries of
        a few bytes, through the Python engine).  Returns programs and
        slices compiled."""
        from ..bitstream import h264_cabac
        from ..ops import damage_mask as dmg

        t0 = time.perf_counter()
        scratch = H264Encoder(
            self.width, self.height, qp=self.qp,
            entropy=self.entropy, host_color=True, gop=self.gop,
            deblock=self.deblock, intra_modes=self.i16_modes,
            superstep_chunk=0, spatial_shards=1, tune=self.tune,
            damage_mask=True, row_align=self.row_align)
        rgb = np.zeros((self.height, self.width, 3), np.uint8)
        scratch.encode(rgb)                       # the IDR: a reference
        planes = scratch._planes_device(rgb)
        if not isinstance(planes[0], np.ndarray):
            return 0                              # no host colour: no plan
        total, slices = self.mb_h, 0
        ladder = dmg.bucket_ladder(total)
        for bucket in ladder:
            rows = np.arange(bucket, dtype=np.int32)
            sub = scratch._submit_cabac_p_masked(
                *planes, self.qp,
                dmg.RowPlan(rows, rows, bucket, total, 1.0))
            slices += scratch._cabac_mask_pull(bucket).warm(sub[5])
            scratch._collect_cabac_p_masked(sub)
        for qp in range(52):
            h264_cabac.skip_row_payload(self.mb_w, qp)
        log.info("damage mask (CABAC): %d row programs and their "
                 "binarize (buckets %s of %d rows), %d prefix slices of "
                 "their record buffers, in %.1f s", len(ladder), ladder,
                 total, slices, time.perf_counter() - t0)
        return 2 * len(ladder) + slices

    def prewarm_async(self, qps=None):
        """Run :meth:`prewarm` in a daemon thread; returns (thread,
        stop_event).  Safe alongside live serving: the scratch encoder
        shares only the process-wide jit cache."""
        import threading
        stop = threading.Event()
        t = threading.Thread(target=self.prewarm, kwargs={
            "qps": qps, "stop": stop}, daemon=True,
            name="h264-qp-prewarm")
        t.start()
        return t, stop

    def _hdr_slots(self, idr_pic_id: int, qp_delta: int = 0):
        key = (0, idr_pic_id, qp_delta)  # (frame_num, idr_pic_id, qp_delta)
        slots = self._hdr_slots_cache.get(key)
        if slots is None:
            from ..ops import cavlc_device
            hv, hl = cavlc_device.slice_header_slots(
                self.mb_h, self.mb_w, frame_num=key[0], idr_pic_id=key[1],
                qp_delta=qp_delta, deblocking_idc=self._deblock_idc)
            slots = (jnp.asarray(hv), jnp.asarray(hl))
            self._hdr_slots_cache[key] = slots
        return slots

    def _intra_begin(self, rgb):
        """An intra frame's qp and planes, ``(qp, planes)``: the half of
        its submit that hands the device nothing while the host converts
        (planes None: the device converts, in the second half)."""
        qp = self._eff_qp()
        if self._spatial_nx > 1:
            return qp, self._planes_device(rgb)
        with obst.stage("colour"):
            return qp, (self._host_yuv420(rgb) if self.host_color else None)

    def _submit_device(self, rgb, idr_pic_id: int, begun=None):
        """Dispatch the device stage asynchronously (no host sync);
        ``begun``: what :meth:`_intra_begin` made of ``rgb`` already.

        When cv2 is available the RGB->YUV420 conversion runs on the host
        (the stage ``colour``: :mod:`..utils.hostcolor`, one native pass
        over row bands on up to 8 cores, 0.8 ms at 1080p on a chip's host;
        the three cv2 calls it equals byte for byte took 4.1 there, 2.2 of
        them in ``cv2.transform``, which runs on ONE thread) so only
        1.5 B/px cross the host->device link instead of 3 — that link is
        the measured hot-path bottleneck (SURVEY.md §3.2); cv2's BT.601
        studio-range matches ops/color "video" (tested in
        tests/test_h264_cavlc.py)."""
        from ..ops import cavlc_device

        if begun is None:
            begun = self._intra_begin(rgb)
        if self._spatial_nx > 1:
            return self._sp_submit_intra(idr_pic_id, begun)
        qp, planes = begun
        with_recon = self.keep_recon or self.gop > 1
        with obst.stage("dispatch") as span:
            hv, hl = self._hdr_slots(idr_pic_id, qp_delta=qp - self.qp)
            if planes is None and self._hq_loop:
                # the traced-qp program takes planes: the device converts
                planes = _yuv_stage(jnp.asarray(rgb), self.pad_h,
                                    self.pad_w)
            qp_eff = {}
            if planes is not None and self._dyn_qp:
                out = cavlc_device.encode_intra_cavlc_frame_yuv_dynqp(
                    *planes, hv, hl, np.int32(qp), with_recon=with_recon,
                    i16_modes=self.i16_modes, tune=self._ktune,
                    **({"with_qp_eff": True}
                       if self._hq_loop and with_recon else {}))
                if self._hq_loop and with_recon:
                    qp_eff = {"qp_eff": out[1][3]}
                    out = out[0], out[1][:3]
            elif planes is not None:
                out = cavlc_device.encode_intra_cavlc_frame_yuv(
                    *planes, hv, hl, qp, with_recon=with_recon,
                    i16_modes=self.i16_modes, tune=self._ktune)
            else:
                out = cavlc_device.encode_intra_cavlc_frame(
                    jnp.asarray(rgb), hv, hl,
                    self.pad_h, self.pad_w, qp, with_recon=with_recon,
                    i16_modes=self.i16_modes, tune=self._ktune)
            if with_recon:
                flat, recon = out
            else:
                flat, recon = out, None
            if recon is not None and self.gop > 1:
                # advance the reference at SUBMIT time (device futures): a
                # pipelined P frame submitted before this IDR is collected
                # must see it.  With deblocking on, the reference is the
                # loop-filtered picture — exactly what the decoder predicts
                # from.
                if self.deblock:
                    self._ref = self._deblock(*recon, qp, **qp_eff)
                else:
                    self._ref = tuple(recon)
            # content stats ride this submit's crossing (extra jit calls in
            # the same event are free — _count_dispatch counts events)
            self._content_submit(
                planes[0] if planes is not None
                else _yuv_stage(jnp.asarray(rgb), self.pad_h, self.pad_w)[0],
                recon_y=recon[0] if recon is not None else None,
                frame_type="intra")
            if recon is not None and self.keep_recon:
                # pull NOW: with deblock off these arrays become the next P
                # submit's DONATED refs — dead by collect time in a pipeline
                recon = tuple(np.asarray(p) for p in recon)
            prefix = self._flat_pull["intra"].prefix(flat)
        self._count_dispatch(ms=span.ms)
        return (rgb, idr_pic_id, qp, planes, flat, prefix, recon)

    def _collect_device(self, submitted, in_pipeline: bool = False) -> bytes:
        """Block on the device stage and assemble the Annex-B access unit."""
        from ..ops import cavlc_device

        if isinstance(submitted[0], str) and \
                submitted[0] in ("sp", "sp_bin"):
            return self._sp_collect(submitted)
        rgb, idr_pic_id, qp, planes, flat, prefix, recon = submitted
        if recon is not None and self.keep_recon:
            self.last_recon = tuple(np.asarray(p) for p in recon)
        got = self._flat_pull["intra"].pull(flat, prefix, self.mb_h)
        if got is None:
            _note_entropy_overflow("intra")
            # Reuse the exact device inputs (planes + rate-controlled qp)
            # so the fallback's recon matches what later pipelined frames
            # already referenced; never clobber an advanced ref chain.
            with obst.stage("assemble", more=True):
                return self._encode_host_entropy(
                    rgb, idr_pic_id, planes=planes, qp=qp,
                    update_ref=not in_pipeline)
        buf, meta = got
        self._note_qp_sum(meta.qp_sum)
        with obst.stage("assemble", more=True):
            return cavlc_device.assemble_annexb(buf, meta,
                                                headers=self.headers())

    # ------------------------------------------------------------------
    # CABAC serving path: device transform+quant with device-side
    # nonzero compaction (ops/level_pack) so only ~2*nnz words + int8
    # mode planes cross the link, then the native C++ CABAC coder
    # (native/cabac.cpp, ~8 ms at 1080p) on the host.  Fixes the round-4
    # transport regression (VERDICT weak #4: the dense ~multi-MB/frame
    # level pull).  Submit/collect split so the session loop pipelines
    # the device stage under the host entropy stage.
    # ------------------------------------------------------------------

    @property
    def cabac_device_binarize(self) -> bool:
        """Whether the DEVICE binarizes a CABAC stream and derives its
        ctxIdx: it emits the packed (bin, ctxIdx, bypass) record stream
        (ops/cabac_binarize) and the host runs only the arithmetic
        engine.  Chosen from the tune: the record stream has no
        ``mb_qp_delta``, so ``hq`` (a qp a macroblock) keeps the level
        transport (ops/level_pack: packed levels to the host's whole
        coder) and every other tier takes the records (``hq`` under the
        loop filter is ``hq_noaq``, one qp a slice).  On a v5e at 1080p
        the binarize program is ``cabac_binarize_ms`` 2.1 of the chip a
        frame and both cells deliver 58.7-59.2 frames/s (ledger, PR 29),
        where the level transport read 34.25 / 40.85 (builder's chip
        runs, PR 28).  Either transport emits byte-identical streams
        (tested: a test chooses one through ``_cabac_dev_bin``); an
        overflow in the packed stream falls back dense per-frame, and is
        counted."""
        return self._cabac_dev_bin

    @property
    def _device_entropy(self) -> bool:
        """Whether the bitstream (or its record stream) is made on the
        device, which the super-step ring and the spatial mesh need:
        device CAVLC, or CABAC binarized there."""
        return self.entropy == "device" or (
            self.entropy == "cabac" and self.cabac_device_binarize)

    # -- mean coded qp (tune=hq): RateController normalization ---------

    def _note_qp_sum(self, qp_sum: int) -> None:
        """Record a frame's summed per-MB effective qp (device CAVLC
        meta word); 0 = uniform slice qp (tune=off programs)."""
        if qp_sum:
            self._mean_qp_pending = qp_sum / float(self.mb_w * self.mb_h)

    def _note_qp_map(self, qp_map, levels=None, slice_qp=None,
                     intra: bool = False) -> None:
        """Host-path twin of :meth:`_note_qp_sum`.  With ``levels`` it
        reports the mean EFFECTIVE qp of the emitted mb_qp_delta chain
        (the statistic the device meta word sums) so the rate model
        cannot jitter between the device path and a host fallback; the
        bare-plane mean is the (close) approximation for callers with
        no level tensors in reach."""
        if qp_map is None:
            return
        if levels is None:
            self._mean_qp_pending = float(np.mean(qp_map))
            return
        from ..bitstream import h264_entropy as _he
        f = _he.intra_mean_coded_qp if intra else _he.p_mean_coded_qp
        self._mean_qp_pending = f(levels, qp_map, slice_qp)

    def _take_mean_qp(self):
        m = self._mean_qp_pending
        self._mean_qp_pending = None
        return m

    def _submit_cabac_intra(self, rgb, idr_pic_id: int, begun=None):
        from ..ops import cabac_binarize, h264_device, level_pack

        if begun is None:
            begun = self._intra_begin(rgb)
        if self._spatial_nx > 1:
            return self._sp_submit_intra(idr_pic_id, begun)
        qp, planes = begun
        with obst.stage("dispatch") as span:
            _note_h2d(*(planes if planes is not None else (rgb,)))
            if planes is not None and self._dyn_qp:
                levels = h264_device.encode_intra_frame_yuv_dynqp(
                    *planes, np.int32(qp), i16_modes=self.i16_modes,
                    tune="off")
            elif planes is not None:
                levels = h264_device.encode_intra_frame_yuv(
                    *planes, qp, i16_modes=self.i16_modes,
                    tune=self._ktune)
            else:
                levels = h264_device.encode_intra_frame(
                    jnp.asarray(rgb), self.pad_h, self.pad_w, qp,
                    i16_modes=self.i16_modes, tune=self._ktune)
            if self.gop > 1:
                # advance the reference at submit time (device futures),
                # same contract as the device-CAVLC path
                recon3 = (levels["recon_y"], levels["recon_cb"],
                          levels["recon_cr"])
                self._ref = (self._deblock(*recon3, qp) if self.deblock
                             else recon3)
            self._content_submit(
                planes[0] if planes is not None
                else _yuv_stage(jnp.asarray(rgb), self.pad_h, self.pad_w)[0],
                recon_y=levels.get("recon_y"), frame_type="intra")
            if self.keep_recon and self.gop > 1:
                # pull NOW: with deblock off these recon planes become the
                # next P submit's DONATED refs — dead by collect time
                levels = dict(levels)
                for k in ("recon_y", "recon_cb", "recon_cr"):
                    levels[k] = np.asarray(levels[k])
            # the int8 mode planes ride beside the packed levels; the
            # record stream carries them itself
            small = None
            if self.cabac_device_binarize:
                buf = cabac_binarize.binarize_intra(
                    levels["luma_dc"], levels["luma_ac"], levels["cb_dc"],
                    levels["cb_ac"], levels["cr_dc"], levels["cr_ac"],
                    levels["pred_mode"], levels["mb_i4"],
                    levels["i4_modes"], levels["luma_i4"])
            else:
                buf = level_pack.pack_levels(levels, level_pack.INTRA_KEYS)
                small = {k: levels[k].astype(jnp.int8)
                         for k in ("pred_mode", "mb_i4", "i4_modes")}
                if "qp_map" in levels:       # tune=hq: per-MB qp (<= 51)
                    small["qp_map"] = levels["qp_map"].astype(jnp.int8)
                for v in small.values():
                    _prefetch_host(v)
            prefix = self._cabac_pull["intra"].prefix(buf)
        self._count_dispatch(ms=span.ms)
        return (levels, buf, prefix, small, qp, idr_pic_id)

    def _collect_cabac_intra(self, submitted) -> bytes:
        from ..bitstream import h264_cabac
        from ..ops import level_pack

        if submitted[0] in ("sp", "sp_bin"):
            return self._sp_collect(submitted)
        levels, buf, prefix, small, qp, idr_pic_id = submitted
        if self.keep_recon:
            self.last_recon = tuple(
                np.asarray(levels[k])
                for k in ("recon_y", "recon_cb", "recon_cr"))
        if not self._cabac_native:
            _M_CABAC_PYTHON.inc()
        head = self._cabac_pull["intra"].pull(buf, prefix)
        with obst.stage("assemble", more=True):
            hdr = dict(qp=qp, frame_num=0, idr_pic_id=idr_pic_id,
                       sps=self._sps, pps=self._pps, with_headers=True,
                       qp_delta=qp - self.qp,
                       deblocking_idc=self._deblock_idc)
            dense = None
            if head is not None and small is None:
                au = h264_cabac.encode_intra_from_binstream(
                    head, nr=self.mb_h, nc_mb=self.mb_w, **hdr)
                if au is not None:
                    return au
            elif head is not None:
                dense = level_pack.unpack_levels(
                    head, self.mb_h, self.mb_w, level_pack.INTRA_KEYS)
            if dense is None:    # the stream's flag or the engine's cap
                _note_cabac_dense()
                dense = {k: np.asarray(levels[k])
                         for k, _, _ in level_pack.INTRA_KEYS}
                _M_D2H_BYTES.inc(sum(v.nbytes for v in dense.values()))
            modes = small if small is not None else {
                k: levels[k] for k in ("pred_mode", "mb_i4", "i4_modes")}
            dense.update({k: np.asarray(v) for k, v in modes.items()})
            qp_map = dense.pop("qp_map", None)
            if qp_map is not None:
                qp_map = qp_map.astype(np.int32)
                self._note_qp_map(qp_map, levels=dense, slice_qp=qp,
                                  intra=True)
            return h264_cabac.encode_intra_picture(dense, **hdr,
                                                   qp_map=qp_map)

    def _submit_cabac_p(self, y, cb, cr, qp: int, frame_num: int = None,
                        next_y=None):
        from ..ops import cabac_binarize, h264_inter, level_pack

        if self._spatial_nx > 1:
            return self._sp_submit_p(y, cb, cr, qp, frame_num)
        with obst.stage("dispatch") as span:
            frame_num = self._frame_num if frame_num is None else frame_num
            _note_h2d(y, cb, cr)
            # self._ref is DONATED to the inter stage (recon aliases its
            # buffers — ops/h264_inter ring contract): dead past this call
            if self._dyn_qp:   # tune=off: no lookahead luma, no I16-in-P
                out = h264_inter.encode_p_frame_dynqp(
                    jnp.asarray(y), jnp.asarray(cb), jnp.asarray(cr),
                    *self._ref, np.int32(qp), tune="off")
            else:
                out = h264_inter.encode_p_frame(
                    jnp.asarray(y), jnp.asarray(cb), jnp.asarray(cr),
                    *self._ref, qp=qp, tune=self._ktune, next_y=next_y)
            recon = (out["recon_y"], out["recon_cb"], out["recon_cr"])
            mv = out["mv"]                       # already int8
            if self.deblock:
                nnz, mv32 = _cabac_bs_inputs(out["luma"], mv)
                self._ref = self._deblock(*recon, qp, nnz_blk=nnz, mv=mv32)
            else:
                self._ref = recon
            self._content_submit(
                jnp.asarray(y), recon_y=out["recon_y"], mv=mv,
                resid=(out["luma"], out["cb_dc"], out["cb_ac"],
                       out["cr_dc"], out["cr_ac"]),
                mb_intra=out.get("mb_intra"))
            if self.keep_recon:
                # pull NOW: with deblock off these arrays are the next
                # submit's donated refs — dead by collect time in a
                # pipeline
                recon = tuple(np.asarray(p) for p in recon)
            binarized = self.cabac_device_binarize
            if binarized:
                buf = cabac_binarize.binarize_p(
                    mv, out["luma"], out["cb_dc"], out["cb_ac"],
                    out["cr_dc"], out["cr_ac"])
            else:
                buf = level_pack.pack_levels(out, level_pack.P_KEYS)
            prefix = self._cabac_pull["p"].prefix(buf)
            if self.keep_recon or not binarized:
                _prefetch_host(mv)
        self._count_dispatch(ms=span.ms)
        return (binarized, out, recon, buf, prefix, mv, qp, frame_num)

    def _collect_cabac_p(self, submitted) -> bytes:
        from ..bitstream import h264_cabac
        from ..ops import level_pack

        if submitted[0] in ("sp", "sp_bin"):
            return self._sp_collect(submitted)
        binarized, out, recon, buf, prefix, mv, qp, frame_num = submitted
        if self.keep_recon:
            self.last_recon = tuple(np.asarray(p) for p in recon)
            self.last_mv = np.asarray(mv, np.int32)
        if not self._cabac_native:
            _M_CABAC_PYTHON.inc()
        head = self._cabac_pull["p"].pull(buf, prefix)
        with obst.stage("assemble", more=True):
            hdr = dict(qp=qp, frame_num=frame_num, qp_delta=qp - self.qp,
                       deblocking_idc=self._deblock_idc)
            dense = None
            if head is not None and binarized:
                au = h264_cabac.encode_p_from_binstream(
                    head, nr=self.mb_h, nc_mb=self.mb_w, **hdr)
                if au is not None:
                    return au
            elif head is not None:
                dense = level_pack.unpack_levels(
                    head, self.mb_h, self.mb_w, level_pack.P_KEYS)
            if dense is None:    # the stream's flag or the engine's cap
                _note_cabac_dense()
                dense = {k: np.asarray(out[k])
                         for k, _, _ in level_pack.P_KEYS}
                _M_D2H_BYTES.inc(sum(v.nbytes for v in dense.values()))
            dense["mv"] = np.asarray(mv, np.int32)
            qp_map = (np.asarray(out["qp_map"]) if "qp_map" in out
                      else None)
            self._note_qp_map(qp_map, levels=dense, slice_qp=qp)
            return h264_cabac.encode_p_picture(dense, **hdr, qp_map=qp_map)

    # -- the damage mask under the CABAC stream (ops/damage_mask) --------
    # NEW functions beside the dense pair above, which they leave as it
    # is: a planned P frame of at most the ladder's top goes through the
    # row program of its bucket and the binarizer over that band, under a
    # token kind of its own (``cabac_p_mask``); a plan past the ladder's
    # top is the dense pair's frame.  Stages as on the CAVLC mask:
    # ``damage_grid`` | ``dispatch``, ``pull``, ``pull_extra``,
    # ``assemble`` (in it ``skip_slices`` and ``engine``).

    def _cabac_mask_pull(self, bucket: int) -> PrefixPull:
        """The pull helper of one row bucket's record buffer: its header
        and its whole length follow the bucket, and its history is its
        own, so that a run of four-row frames does not shrink the guess
        the next dense frame's pull starts from."""
        pull = self._cabac_mask_pulls.get(bucket)
        if pull is None:
            from ..ops import cabac_binarize
            pull = self._cabac_mask_pulls[bucket] = PrefixPull(
                cabac_binarize.header_words(bucket), 1)
        return pull

    def _submit_cabac_p_planned(self, y, cb, cr, qp: int, plan):
        """``(token kind, payload)`` of a CABAC P frame that has a row
        plan: the row program's where the plan is under the ladder's top,
        else the dense program's."""
        _note_mask_plan(plan)
        if plan.full:
            return "cabac_p", self._submit_cabac_p(y, cb, cr, qp)
        return "cabac_p_mask", self._submit_cabac_p_masked(
            y, cb, cr, qp, plan)

    def _submit_cabac_p_masked(self, y, cb, cr, qp: int, plan):
        """Masked counterpart of :meth:`_submit_cabac_p` (device binarize,
        tune=off): the bucket's row program over the worklist (the refs
        donated, the loop filter inside, the scattered planes the next
        reference), then the binarizer over the band's vectors and
        levels, then the guessed prefix of ITS record buffer on its way
        to the host."""
        from ..ops import cabac_binarize
        from ..ops import damage_mask as dmg

        with obst.stage("dispatch") as span:
            _note_h2d(y, cb, cr, plan.padded)
            planes = (jnp.asarray(y), jnp.asarray(cb), jnp.asarray(cr))
            ry, rcb, rcr, mv, levels = dmg.row_step_cabac(plan.bucket)(
                *planes, *self._ref, jnp.asarray(plan.padded),
                np.int32(qp), deblock=self.deblock)
            self._ref = (ry, rcb, rcr)
            self._content_submit(planes[0], recon_y=ry)
            buf = cabac_binarize.binarize_p(
                mv, levels["luma"], levels["cb_dc"], levels["cb_ac"],
                levels["cr_dc"], levels["cr_ac"])
            prefix = self._cabac_mask_pull(plan.bucket).prefix(buf)
        self._count_dispatch(ms=span.ms)
        return (qp, self._frame_num, plan, levels, mv, buf, prefix)

    def _collect_cabac_p_masked(self, submitted) -> bytes:
        from ..bitstream import h264_cabac
        from ..ops import damage_mask as dmg

        qp, frame_num, plan, levels, mv, buf, prefix = submitted
        if not self._cabac_native:
            _M_CABAC_PYTHON.inc()
        head = self._cabac_mask_pull(plan.bucket).pull(buf, prefix)
        with obst.stage("assemble", more=True):
            hdr = dict(qp=qp, frame_num=frame_num, qp_delta=qp - self.qp,
                       deblocking_idc=self._deblock_idc)
            if head is not None:
                au = h264_cabac.encode_p_rows_from_binstream(
                    head, plan.rows, nr=self.mb_h, nc_mb=self.mb_w, **hdr)
                if au is not None:
                    return au
            # the stream's flag or the engine's cap: the worklist's
            # levels scattered to full-frame shapes (untouched rows zero
            # = skip) and the WHOLE frame through the host coder; the
            # reference chain needs no rewind
            _note_cabac_dense()
            pulled = {k: np.asarray(v) for k, v in levels.items()}
            mv_np = np.asarray(mv)
            _M_D2H_BYTES.inc(mv_np.nbytes
                             + sum(v.nbytes for v in pulled.values()))
            dense, full_mv = dmg.scatter_levels_np(
                pulled, mv_np, plan.padded, self.mb_h)
            dense["mv"] = full_mv.astype(np.int32)
            return h264_cabac.encode_p_picture(dense, **hdr)

    def _encode_host_entropy(self, rgb, idr_pic_id: int,
                             planes=None, qp: int = None,
                             update_ref: bool = True) -> bytes:
        """Host-entropy access unit: device transform+quant, the Python
        CAVLC coder (bitstream/h264_entropy).

        Shared by ``entropy="python"`` and the device path's
        static-cap overflow fallback (pathological low-qp content), so the
        two can never diverge.  ``planes``/``qp`` let the fallback reuse
        the exact device inputs of the overflowed submit (host-color
        conversion and rate-controlled qp included); ``update_ref=False``
        protects a pipeline's in-flight reference chain.  Reconstruction
        planes cross the host link only when ``keep_recon`` asked for them.
        """
        from ..bitstream import h264_entropy
        from ..ops import h264_device

        if qp is None:
            # direct host-entropy call (entropy="python"): consult the
            # rate controller like the device path's submit does — IDR
            # bursts must hit the VBV keyframe guard on every path
            qp = self._eff_qp()
        if planes is not None:
            levels = h264_device.encode_intra_frame_yuv(
                jnp.asarray(planes[0]), jnp.asarray(planes[1]),
                jnp.asarray(planes[2]), qp, i16_modes=self.i16_modes,
                tune=self._ktune)
        else:
            levels = h264_device.encode_intra_frame(
                jnp.asarray(rgb), self.pad_h, self.pad_w, qp,
                i16_modes=self.i16_modes, tune=self._ktune)
        if self.gop > 1 and update_ref:
            recon3 = (levels["recon_y"], levels["recon_cb"],
                      levels["recon_cr"])
            if self.deblock:
                from ..ops import h264_deblock
                kw = {}
                if "qp_map" in levels:   # hq served whole: the chain's qps
                    kw["qp_eff"] = h264_entropy.intra_qp_chain(
                        {k: np.asarray(v) for k, v in levels.items()
                         if not k.startswith("recon")},
                        np.asarray(levels["qp_map"]), qp)
                recon3 = h264_deblock.deblock_frame(*recon3, qp, **kw)
            self._ref = recon3
        if self.keep_recon:
            self.last_recon = tuple(
                np.asarray(levels[k])
                for k in ("recon_y", "recon_cb", "recon_cr"))
        levels = {k: np.asarray(v) for k, v in levels.items()
                  if not k.startswith("recon")}
        qp_map = levels.pop("qp_map", None)
        self._note_qp_map(qp_map, levels=levels, slice_qp=qp,
                          intra=True)
        # entropy == "cabac" never reaches here: _encode_cavlc routes it
        # to the packed-transport path (_submit/_collect_cabac_intra),
        # and the device-overflow fallback only runs with entropy=="device"
        return h264_entropy.encode_intra_picture(
            levels, frame_num=0, idr_pic_id=idr_pic_id,
            sps=self._sps, pps=self._pps, with_headers=True,
            qp_delta=qp - self.qp, deblocking_idc=self._deblock_idc,
            qp_map=qp_map, slice_qp=qp)

    # ------------------------------------------------------------------
    # Inter (P-frame) path: GOP state machine + device inter stage
    # ------------------------------------------------------------------

    def request_keyframe(self) -> None:
        """Resume semantics (SURVEY.md §5): the next frame becomes an IDR."""
        self._force_idr = True

    # -- checkpoint/restore (resilience/continuity) --------------------

    def export_state(self) -> dict:
        """Everything a replacement encoder needs to continue this
        stream's lineage, pulled to HOST memory (the checkpoint must
        survive the device): GOP phase + frame_num (slice-header
        continuity), idr_pic_id parity (H.264 7.4.3 — consecutive IDRs
        must differ, and the recovery IDR is consecutive with the last
        delivered one), rate-controller bucket/EMAs (in-flight
        reservations are dropped: those frames died with the device),
        pull-size predictors, the degradation bias, and the reconstructed
        reference planes (so a same-chip reset can in principle resume
        the P chain — the recovery IDR makes them optional on a
        replacement chip)."""
        st = super().export_state()
        st.update({
            "gop_pos": self._gop_pos,
            "frame_num": self._frame_num,
            "idr_count": self._idr_count,
            "qp_offset": self.degrade_qp_offset,
            "pull_guess": self._flat_pull["intra"].checkpoint(),
            "p_pull_guess": self._flat_pull["p"].checkpoint(),
        })
        if self._rate is not None:
            st["rate"] = {
                "level": self._rate.level,
                "ema_key": self._rate._ema[True],
                "ema_p": self._rate._ema[False],
                "step_idx": self._rate._step_idx,
                "avg": self._rate._avg,
            }
        if self._ref is not None and self.gop > 1:
            try:
                st["ref"] = tuple(np.asarray(p) for p in self._ref)
            except Exception:
                # device already gone mid-snapshot: the lineage state
                # above still checkpoints; recovery leans on the IDR
                st["ref"] = None
        return st

    def import_state(self, state: dict) -> None:
        super().import_state(state)        # geometry check + force IDR
        self._gop_pos = int(state.get("gop_pos", 0))
        self._frame_num = int(state.get("frame_num", 0))
        self._idr_count = int(state.get("idr_count", 0))
        self.degrade_qp_offset = int(state.get("qp_offset", 0))
        self._flat_pull["intra"].restore(state.get("pull_guess"))
        self._flat_pull["p"].restore(state.get("p_pull_guess"))
        rate = state.get("rate")
        if rate is not None and self._rate is not None:
            self._rate.level = float(rate["level"])
            self._rate._ema[True] = rate["ema_key"]
            self._rate._ema[False] = rate["ema_p"]
            self._rate._step_idx = int(rate["step_idx"])
            self._rate._avg = rate["avg"]
            self._rate._pending.clear()    # in-flight frames are gone
        ref = state.get("ref")
        if ref is not None and self.gop > 1:
            if self._spatial_nx > 1:
                # host copies: the sharded step's in_specs place them
                # across the mesh on the next dispatch (re-uploading to
                # ONE committed device here would fight the sharding)
                self._ref = tuple(np.asarray(p) for p in ref)
            else:
                # re-upload to the CURRENT device; exercises the device
                # too, so a restore onto a still-dead chip fails here,
                # not mid-GOP
                self._ref = tuple(jnp.asarray(p) for p in ref)

    def _planes_device(self, rgb):
        """Current frame as padded YUV planes (host cv2 or device jit)."""
        with obst.stage("colour"):
            planes = self._host_yuv420(rgb) if self.host_color else None
            if planes is not None:
                return planes
            return _yuv_stage(jnp.asarray(rgb), self.pad_h, self.pad_w)

    def _encode_p(self, rgb) -> bytes:
        qp = self._eff_qp(keyframe=False)
        y, cb, cr = self._planes_device(rgb)
        if self.entropy == "device":
            return self._encode_p_device(y, cb, cr, qp)
        if self.entropy == "cabac":
            return self._collect_cabac_p(self._submit_cabac_p(y, cb, cr, qp))
        return self._encode_p_host(y, cb, cr, qp)

    def _p_hdr_slots(self, frame_num: int, qp_delta: int):
        key = ("p", frame_num, qp_delta)
        slots = self._hdr_slots_cache.get(key)
        if slots is None:
            from ..ops import cavlc_device
            hv, hl = cavlc_device.slice_header_slots(
                self.mb_h, self.mb_w, frame_num=frame_num,
                qp_delta=qp_delta, slice_type=5, idr=False,
                deblocking_idc=self._deblock_idc)
            slots = (jnp.asarray(hv), jnp.asarray(hl))
            self._hdr_slots_cache[key] = slots
        return slots

    def _encode_p_device(self, y, cb, cr, qp: int) -> bytes:
        """Device CAVLC P path: one flat-buffer pull per frame; recon (the
        next reference) never leaves the device."""
        return self._collect_p_device(self._submit_p_device(y, cb, cr, qp))

    def _submit_p_device(self, y, cb, cr, qp: int, frame_num: int = None,
                         next_y=None, damage_plan=None):
        """Dispatch the P device stage asynchronously; self._ref advances
        immediately (device futures), so the next frame can submit before
        this one is collected.  The reference planes are DONATED to the
        fused device stage (the recon is written into their buffers —
        the ring contract of ops/cavlc_p_device), so the old refs are
        dead past this call; the overflow fallback entropy-codes the
        stage's own level tensors instead of re-encoding against them.
        ``next_y`` (tune=hq ring flush): the 1-frame-lookahead luma."""
        from ..ops import cavlc_p_device

        if self._spatial_nx > 1:
            return self._sp_submit_p(y, cb, cr, qp, frame_num)
        # an explicit plan carries the damage baseline of when it was
        # made: the first half of encode_submit's (made there, so that the
        # session's collect between the halves has only the dispatch
        # behind it), or a ring flush's STAGE-time one — the twin chain
        # has moved past those frames
        plan = (damage_plan if damage_plan is not None
                else self._damage_plan(y))
        if plan is not None:
            _note_mask_plan(plan)
            if not plan.full:
                return self._submit_p_masked(y, cb, cr, qp, frame_num,
                                             next_y, plan)
        with obst.stage("dispatch") as span:
            frame_num = self._frame_num if frame_num is None else frame_num
            hv, hl = self._p_hdr_slots(frame_num, qp - self.qp)
            if self._dyn_qp:      # no lookahead luma on the per-frame path
                flat, ry, rcb, rcr, mv, nnz, levels = \
                    cavlc_p_device.encode_p_cavlc_frame_dynqp(
                        jnp.asarray(y), jnp.asarray(cb), jnp.asarray(cr),
                        *self._ref, hv, hl, np.int32(qp), self._ktune, None,
                        self._p_intra, *((True,) if self._hq_loop else ()))
            else:
                flat, ry, rcb, rcr, mv, nnz, levels = \
                    cavlc_p_device.encode_p_cavlc_frame(
                        jnp.asarray(y), jnp.asarray(cb), jnp.asarray(cr),
                        *self._ref, hv, hl, qp, self._ktune, next_y,
                        self._p_intra)
            recon = (ry, rcb, rcr)
            self._content_submit(
                jnp.asarray(y), recon_y=ry, mv=mv,
                resid=(levels["luma"], levels["cb_dc"], levels["cb_ac"],
                       levels["cr_dc"], levels["cr_ac"]),
                mb_intra=levels.get("mb_intra"))
            if self.deblock:
                bs_inputs = {"nnz_blk": nnz, "mv": mv}
                if self._hq_loop:    # the filter's thresholds and intra bS
                    bs_inputs.update(qp_eff=levels["qp_eff"],
                                     mb_intra=levels["mb_intra"])
                self._ref = self._deblock(ry, rcb, rcr, qp, **bs_inputs)
            else:
                self._ref = recon
            if self.keep_recon:
                # pull NOW: with deblock off these arrays ARE the next
                # submit's (donated) refs — by collect time they may be dead
                recon = tuple(np.asarray(p) for p in recon)
                mv = np.asarray(mv)
            prefix = self._flat_pull["p"].prefix(flat)
        self._count_dispatch(ms=span.ms)
        return (qp, frame_num, levels, recon, flat, prefix, mv)

    def _collect_p_device(self, submitted) -> bytes:
        from ..bitstream import h264 as syn, h264_entropy
        from ..ops import cavlc_device

        if isinstance(submitted[0], str) and \
                submitted[0] in ("sp", "sp_bin"):
            return self._sp_collect(submitted)
        if isinstance(submitted[0], str) and submitted[0] == "dmg":
            return self._collect_p_masked(submitted)
        qp, frame_num, levels, recon, flat, prefix, mv = submitted
        got = self._flat_pull["p"].pull(flat, prefix, self.mb_h)
        if self.keep_recon:
            # THIS frame's recon (pulled at submit) — self._ref may
            # already belong to a newer pipelined submit.
            self.last_recon = tuple(np.asarray(p) for p in recon)
            self.last_mv = np.asarray(mv)
        if got is None:
            _note_entropy_overflow("p")
            # pathological content: host-entropy the SAME levels the
            # device stage produced (byte-identical to re-running the
            # inter stage — it is literally the same tensors), so the
            # stream stays bit-consistent and the already-advanced
            # reference chain needs no rewind.
            with obst.stage("assemble", more=True):
                pulled = {k: np.asarray(v) for k, v in levels.items()
                          if k != "qp_eff"}          # (the loop filter's)
                pulled["mv"] = np.asarray(mv)
                self.last_mv = pulled["mv"]
                qp_map = pulled.pop("qp_map", None)
                self._note_qp_map(qp_map, levels=pulled, slice_qp=qp)
                return h264_entropy.encode_p_picture(
                    pulled, frame_num=frame_num, qp_delta=qp - self.qp,
                    deblocking_idc=self._deblock_idc,
                    qp_map=qp_map, slice_qp=qp)
        buf, meta = got
        self._note_qp_sum(meta.qp_sum)
        if self._hq_loop:
            mbs = self.mb_w * self.mb_h
            _M_P_MBS.inc(mbs)
            _M_P_INTRA_MBS.inc(meta.p_intra_mbs)
            _M_CODED_QP_SUM.inc(meta.qp_sum)
            _M_SLICE_QP_SUM.inc(qp * mbs)
        with obst.stage("assemble", more=True):
            return cavlc_device.assemble_annexb(
                buf, meta, nal_type=syn.NAL_SLICE, ref_idc=2)

    # ------------------------------------------------------------------
    # Damage-driven encode (ops/damage_mask): the masked P path.  The
    # host twin of the content plane's damage grid compacts each P
    # frame to its damaged MB rows; untouched rows ship
    # as host-cached all-skip slices whose decoder reconstruction is
    # the reference rows bit-exactly.  One submit event per frame
    # either way — dispatch-crossings-per-frame is unchanged — and the
    # same stages and the same token as the dense P frame: ``damage_grid``
    # (the plan, in the first half of encode_submit) in front of
    # ``dispatch``, then ``pull``, ``pull_extra``, ``assemble``.

    def _damage_plan(self, y):
        """RowPlan for the CURRENT host-ingested frame, or None when
        the masked path cannot serve it: mask off, device-side ingest,
        keep_recon debug pulls, a spatial mesh, or an entropy placement
        outside ``ops/damage_mask.MASKED_ENTROPY`` — the device CAVLC
        path, and the CABAC path where qp is traced and the device
        binarizes (tune=off: the hq tiers' CABAC frames have no row
        program, and stay dense).  Feeds the rate controller's damage
        consumer as a side effect."""
        from ..ops import damage_mask as dmg
        if (not self.damage_mask
                or self.entropy not in dmg.MASKED_ENTROPY
                or (self.entropy == "cabac"
                    and not (self._dyn_qp and self.cabac_device_binarize))
                or self.keep_recon
                or self._spatial_nx > 1      # a mesh gates rows instead
                or not isinstance(y, np.ndarray)
                or self._damage_cur_y is None):
            return None
        prev = self._damage_prev_y
        if prev is not None and prev.shape != y.shape:
            prev = None                   # post-resize: everything dirty
        with obst.stage("damage_grid"):
            plan = dmg.plan_rows(dmg.damage_grid_np(y, prev))
        self._damage_frac = plan.frac
        if self._rate is not None:
            try:
                self._rate.note_damage(plan.frac)
            except Exception:
                pass
        return plan

    def _sp_damage_keep(self):
        """Per-MB-row keep mask for the SPATIAL masked step, or None to
        serve the unmasked program (mask off, device-side ingest, or a
        fully-damaged frame — the unmasked program is byte-identical
        there and skips the gating ops).  Shards can't compact a
        worklist without repartitioning the mesh, so spatial masking is
        a forced-skip row gate, not a gather (ops/damage_mask).  Feeds
        the rate controller's damage consumer like :meth:`_damage_plan`."""
        if (not self.damage_mask or self.entropy == "cabac"
                or self._damage_cur_y is None
                or self._damage_cur_y.shape != (self.pad_h, self.pad_w)):
            return None
        from ..ops import damage_mask as dmg
        grid = dmg.damage_grid_np(self._damage_cur_y,
                                  self._damage_prev_y)
        self._damage_frac = float(grid.mean())
        if self._rate is not None:
            try:
                self._rate.note_damage(self._damage_frac)
            except Exception:
                pass
        rowmask = grid.any(axis=1)
        return None if rowmask.all() else rowmask

    def _p_hdr_slots_np(self, frame_num: int, qp_delta: int):
        """Host-side twin of :meth:`_p_hdr_slots`: the full-frame header
        slot arrays stay numpy so the masked path can gather the
        worklist's rows before upload."""
        key = ("p_np", frame_num & 0xF, qp_delta)
        slots = self._hdr_slots_cache.get(key)
        if slots is None:
            from ..ops import cavlc_device
            hv, hl = cavlc_device.slice_header_slots(
                self.mb_h, self.mb_w, frame_num=frame_num,
                qp_delta=qp_delta, slice_type=5, idr=False,
                deblocking_idc=self._deblock_idc)
            slots = (np.asarray(hv), np.asarray(hl))
            self._hdr_slots_cache[key] = slots
        return slots

    def _submit_p_masked(self, y, cb, cr, qp: int, frame_num, next_y,
                         plan):
        """Masked counterpart of :meth:`_submit_p_device`: dispatch the
        row-compacted program over the damaged-row worklist.  The refs
        are donated exactly like the unmasked step; the scattered-recon
        planes (deblocked inside the program when the loop filter is
        on) become the next reference.  Content telemetry rides the
        same submit event with the full ingest luma, so damage/PSNR/
        activity land; mode-mix stats are excluded on this path (the
        untouched rows ARE skip by construction — same documented
        exclusion class as the spatial shards)."""
        from ..ops import damage_mask as dmg

        with obst.stage("dispatch") as span:
            frame_num = self._frame_num if frame_num is None else frame_num
            hv, hl = self._p_hdr_slots_np(frame_num, qp - self.qp)
            planes = (jnp.asarray(y), jnp.asarray(cb), jnp.asarray(cr))
            work = (jnp.asarray(plan.padded), jnp.asarray(hv[plan.padded]),
                    jnp.asarray(hl[plan.padded]))
            if self._dyn_qp:      # tune=off: one program a row bucket
                flat, ry, rcb, rcr, mv, nnz, levels = \
                    dmg.row_step(plan.bucket)(
                        *planes, *self._ref, *work, np.int32(qp),
                        tune="off", next_y=None, p_intra=False,
                        deblock=self.deblock)
            else:
                flat, ry, rcb, rcr, mv, nnz, levels = dmg.encode_p_rows(
                    *planes, *self._ref, *work, qp, tune=self._ktune,
                    next_y=None if next_y is None else jnp.asarray(next_y),
                    p_intra=self._p_intra, deblock=self.deblock)
            self._ref = (ry, rcb, rcr)
            self._content_submit(planes[0], recon_y=ry)
            prefix = self._flat_pull["p"].prefix(flat)
        self._count_dispatch(ms=span.ms)
        return ("dmg", qp, frame_num, levels, flat, prefix, mv, plan)

    def _collect_p_masked(self, submitted) -> bytes:
        from ..bitstream import h264_entropy
        from ..ops import damage_mask as dmg

        _, qp, frame_num, levels, flat, prefix, mv, plan = submitted
        got = self._flat_pull["p"].pull(flat, prefix, plan.bucket)
        if got is None:
            _note_entropy_overflow("masked p")
            # flat-cap overflow on a compacted frame: scatter the
            # worklist's level tensors back to full-frame shapes
            # (untouched rows zero = skip) and host-entropy the WHOLE
            # frame — same bytes the device would have packed, ref
            # chain needs no rewind
            with obst.stage("assemble", more=True):
                pulled = {k: np.asarray(v) for k, v in levels.items()}
                qp_map = pulled.pop("qp_map", None)
                full_lv, full_mv = dmg.scatter_levels_np(
                    pulled, np.asarray(mv), plan.padded, self.mb_h)
                full_lv["mv"] = full_mv
                if qp_map is not None:
                    # untouched (skip) rows never code mb_qp_delta; slice
                    # qp keeps the host coder's chain arithmetic aligned
                    fq = np.full(
                        (self.mb_h,) + np.asarray(qp_map).shape[1:],
                        qp, np.asarray(qp_map).dtype)
                    fq[plan.padded] = np.asarray(qp_map)
                    qp_map = fq
                self.last_mv = full_mv
                self._note_qp_map(qp_map, levels=full_lv, slice_qp=qp)
                return h264_entropy.encode_p_picture(
                    full_lv, frame_num=frame_num, qp_delta=qp - self.qp,
                    deblocking_idc=self._deblock_idc,
                    qp_map=qp_map, slice_qp=qp)
        buf, meta = got
        if meta.qp_sum:
            # meta sums the WORKLIST's effective qps; untouched rows
            # decode at slice qp.  (Padded duplicate rows bias the sum
            # by < one row of qp — noise for the rate normalizer.)
            self._note_qp_sum(int(meta.qp_sum)
                              + qp * self.mb_w
                              * (self.mb_h - plan.bucket))
        with obst.stage("assemble", more=True):
            return dmg.assemble_masked_au(
                buf, meta, plan.rows, self.mb_h, self.mb_w,
                frame_num=frame_num, qp_delta=qp - self.qp,
                deblocking_idc=self._deblock_idc)

    # ------------------------------------------------------------------
    # Super-step ring: P frames stage HOST-side (no device dispatch at
    # all), and a full GOP-chunk launches as ONE donated-buffer XLA
    # program (ops/devloop.build_p_chunk_step) — capture-ingest, DCT,
    # ME, deblock and entropy binarization fused, the reference ring
    # aliased in place, ~1 Python crossing per chunk instead of per
    # frame.  Byte-exactness vs the per-frame path is a tested
    # invariant (the scan body IS the per-frame program), which is what
    # lets a partial chunk (IDR due, idle drain, resize) flush through
    # the per-frame path mid-stream with an identical bitstream.
    # ------------------------------------------------------------------

    def _ring_stage(self, rgb, idx: int, t0: float):
        """Stage one P frame into the chunk ring; dispatches the
        super-step when the ring fills.  Returns the frame's token."""
        ring = self._ring
        if ring is None:
            qp = self._eff_qp(keyframe=False)
            planes = self._host_yuv420(rgb) if self.host_color else None
            if self._spatial_nx > 1 and planes is None:
                # the spatial chunk step stages pre-split YUV planes
                # (rgb ingest would move the 4:2:0 subsample rounding
                # at shard seams); without a host converter this
                # session serves per-frame spatial instead — still
                # sharded, just dispatched per frame
                self._ring_chunk_cached = 0
                y, cb, cr = self._planes_device(rgb)
                kind = "cabac_p" if self.entropy == "cabac" else "p"
                return (kind, idx, t0, False,
                        self._sp_submit_p(y, cb, cr, qp))
            ring = self._ring = {
                "kind": "cabac" if self.entropy == "cabac" else "cavlc",
                "ingest": "yuv" if planes is not None else "rgb",
                "qp": qp, "frames": [], "fns": [],
                "res": None, "pf": None, "error": False,
            }
            # masked chunks stage the damaged-row plan PER FRAME (the
            # host twin chain only holds the latest pair, so the grid
            # must be taken while this frame IS the latest)
            ring["plans"] = ([] if self.damage_mask
                             and ring["kind"] == "cavlc"
                             and ring["ingest"] == "yuv"
                             and not self.keep_recon else None)
        else:
            qp = ring["qp"]
            planes = (self._host_yuv420(rgb)
                      if ring["ingest"] == "yuv" else None)
            if self._rate is not None and self._forced_qp is None:
                # chunk frames share one (static-arg) qp; keep the rate
                # controller's per-frame reservation ledger aligned
                self._rate.repeat_last_reservation()
        ring["frames"].append(planes if planes is not None
                              else np.asarray(rgb))
        ring["fns"].append(self._frame_num)
        if ring.get("plans") is not None:
            from ..ops import damage_mask as dmg
            if self._damage_cur_y is None:    # twin chain unavailable
                ring["plans"] = None
            else:
                plan = dmg.plan_rows(dmg.damage_grid_np(
                    self._damage_cur_y, self._damage_prev_y))
                self._damage_frac = plan.frac
                if self._rate is not None:
                    try:
                        self._rate.note_damage(plan.frac)
                    except Exception:
                        pass
                ring["plans"].append(plan)
        token = ("ring", idx, t0, False, (ring, len(ring["frames"]) - 1))
        if len(ring["frames"]) >= self._ring_chunk:
            try:
                self._ring_dispatch(ring)
            except Exception:
                ring["error"] = True
                raise
            finally:
                self._ring = None
        return token

    def _chunk_hdr_slots(self, fns: tuple, qp_delta: int):
        """Per-frame slice-header slots for a chunk, stacked on axis 0
        (the scan axis).  frame_num cycles mod 16, so the distinct
        chunk-start sequences are bounded and the stacked device arrays
        cache like the per-frame slots do."""
        key = (fns, qp_delta)
        got = self._chunk_hdr_cache.get(key)
        if got is None:
            from ..ops import cavlc_device
            hvs, hls = [], []
            for fn in fns:
                hv, hl = cavlc_device.slice_header_slots(
                    self.mb_h, self.mb_w, frame_num=fn,
                    qp_delta=qp_delta, slice_type=5, idr=False,
                    deblocking_idc=self._deblock_idc)
                hvs.append(np.asarray(hv))
                hls.append(np.asarray(hl))
            got = (np.stack(hvs), np.stack(hls))
            if self._spatial_nx == 1:
                # single-device: cache ON device (a host copy would
                # re-upload per dispatch); the spatial chunk step
                # shards rows per its in_spec, so it keeps host arrays
                got = (jnp.asarray(got[0]), jnp.asarray(got[1]))
            self._chunk_hdr_cache[key] = got
        return got

    def _ring_dispatch(self, ring: dict) -> None:
        """Launch the chunk: ONE jitted call; the ref ring is donated
        and the bitstream prefix comes back as an output of the same
        program (no separate slice dispatch)."""
        from ..ops import devloop

        t0 = time.perf_counter()
        self._chunk_seq += 1
        ring["chunk_id"] = self._chunk_seq
        qp = ring["qp"]
        if ring["kind"] == "cavlc":
            pull = self._flat_pull["p"]
            plen = pull.hdrw + pull.guess
            hdrs = self._chunk_hdr_slots(tuple(ring["fns"]),
                                         qp - self.qp)
        else:
            from ..ops import cabac_binarize
            rows = (self._sp_rows_local() if self._spatial_nx > 1
                    else self.mb_h)
            hdrw = cabac_binarize.header_words(rows)
            plen = hdrw + self._cabac_pull["p"].guess
            hdrs = ()
        # damage-masked chunk: shared row bucket = the worst frame's
        # rung (a shared static bucket keeps ONE compile per rung; the
        # calmer frames just pad with duplicate rows).  A chunk whose
        # worst frame is fully damaged dispatches the ordinary
        # full-frame scan — bit-exact by the same argument as the
        # per-frame fallback.
        dmg_bucket = 0
        plans = ring.get("plans")
        if plans and len(plans) == len(ring["frames"]):
            from ..ops import damage_mask as dmg
            b = dmg._bucket_for(max(p.rows.size for p in plans),
                                self.mb_h)
            if b < self.mb_h:
                dmg_bucket = b
        step = devloop.build_p_chunk_step(
            qp, deblock=self.deblock, entropy=ring["kind"],
            ingest=ring["ingest"], prefix_len=plen,
            spatial_shards=self._spatial_nx, tune=self._ktune,
            p_intra=self._p_intra, damage_bucket=dmg_bucket)
        if ring["ingest"] == "rgb":
            args = (np.stack(ring["frames"]),)
        else:
            args = tuple(np.stack([f[i] for f in ring["frames"]])
                         for i in range(3))
        extra = ()
        if dmg_bucket:
            padded, hvs, hls = [], [], []
            for p, fn in zip(plans, ring["fns"]):
                pr = np.concatenate(
                    [p.rows, np.full(dmg_bucket - p.rows.size,
                                     p.rows[-1], np.int32)]) \
                    if p.rows.size < dmg_bucket else \
                    p.rows[:dmg_bucket]
                hv, hl = self._p_hdr_slots_np(fn, qp - self.qp)
                padded.append(pr)
                hvs.append(hv[pr])
                hls.append(hl[pr])
            hdrs = (jnp.asarray(np.stack(hvs)), jnp.asarray(np.stack(hls)))
            extra = (jnp.asarray(np.stack(padded)),)
            ring["dmg"] = (dmg_bucket, padded)
        # self._ref is DONATED: the chunk writes the new reference into
        # the old ring's buffers (ops/devloop ring contract)
        flats, prefix, ry, rcb, rcr, mvs, lvs = step(
            *args, *self._ref, *hdrs, *extra)
        self._ref = (ry, rcb, rcr)
        self._count_dispatch(t0)
        # content stats for the whole chunk: ONE vmapped program riding
        # the chunk's single counted crossing (PSNR on the last slot —
        # the ring keeps only the final reference on device).  A masked
        # chunk's mv/level tensors are row-compacted, so mode-mix/|MV|
        # are excluded for it (same documented class as the spatial
        # shards); damage, activity and last-slot PSNR still land.
        self._content_ring_dispatch(
            ring, args, ry, None if dmg_bucket else mvs,
            None if dmg_bucket else lvs)
        _prefetch_host(prefix)
        ring["frames"] = None              # host staging freed
        ring["res"] = (flats, prefix, mvs, lvs)

    def _ring_flush(self) -> None:
        """Push a PARTIAL ring through the per-frame path (IDR due, an
        idle drain, or a collect arriving before the chunk filled).
        Byte-exactness between the two paths makes this a pure latency
        decision — the stream cannot tell which path coded a frame."""
        ring = self._ring
        self._ring = None
        if ring is None or ring["res"] is not None:
            return
        toks = []
        cstats = []
        planes = []
        for fr in ring["frames"]:
            if ring["ingest"] == "rgb":
                planes.append(_yuv_stage(jnp.asarray(fr), self.pad_h,
                                         self.pad_w))
            else:
                planes.append(fr)
        for i, (y, cb, cr) in enumerate(planes):
            next_y = None
            if self._ktune == "hq":
                # mirror the chunk scan's lookahead shift: frame k sees
                # frame k+1, the last staged frame sees itself.  The
                # SPATIAL per-frame step has no next_y input yet, so a
                # sharded hq flush codes without the lookahead bias —
                # conformant, rate-model safe (the qp_sum meta still
                # rides), but not byte-equal to the chunk the frames
                # would have ridden (the spatial step has no lookahead
                # operand).
                next_y = planes[min(i + 1, len(planes) - 1)][0]
            if ring["kind"] == "cavlc":
                plans = ring.get("plans")
                toks.append(("p", self._submit_p_device(
                    y, cb, cr, ring["qp"], frame_num=ring["fns"][i],
                    next_y=next_y,
                    damage_plan=(plans[i] if plans
                                 and len(plans) > i else None))))
            else:
                toks.append(("cabac_p", self._submit_cabac_p(
                    y, cb, cr, ring["qp"], frame_num=ring["fns"][i],
                    next_y=next_y)))
            # each per-frame submit set _content_last; keep them
            # slot-aligned for the ring collect
            cstats.append(self._content_last)
            self._content_last = None
        ring["pf"] = toks
        ring["content_pf"] = cstats

    def _ring_collect(self, payload) -> bytes:
        ring, slot = payload
        if ring["error"]:
            raise RuntimeError("super-step chunk dispatch failed; "
                               "frame lost (IDR resync follows)")
        if ring["res"] is None and ring["pf"] is None:
            # collect reached a frame whose chunk never filled (source
            # went idle / pipeline drain): flush the partial ring
            self._ring_flush()
        if ring["pf"] is not None:
            kind, tok = ring["pf"][slot]
            if kind == "p":
                return self._collect_p_device(tok)
            return self._collect_cabac_p(tok)
        flats, prefix, mvs, lvs = ring["res"]
        buf = ring.get("prefix_np")
        if buf is None:
            buf = ring["prefix_np"] = np.asarray(prefix)
        fn = ring["fns"][slot]
        if ring["kind"] == "cavlc":
            return self._ring_collect_cavlc(ring, buf[slot], slot, fn)
        return self._ring_collect_cabac(ring, buf[slot], slot, fn)

    def _ring_collect_cavlc(self, ring, head, slot: int,
                            frame_num: int) -> bytes:
        from ..bitstream import h264 as syn, h264_entropy
        from ..ops import cavlc_device

        qp = ring["qp"]
        flats, _, mvs, lvs = ring["res"]
        if head.ndim == 2:
            # spatial chunk: (nx, plen) per frame — per-shard metas +
            # NAL concat through the shared spatial collect
            lv = {k: v[slot] for k, v in lvs.items()}
            return self._sp_collect_flat("p", qp, 0, frame_num,
                                         flats[slot], head,
                                         (lv, mvs[slot]))
        if ring.get("dmg") is not None:
            return self._ring_collect_masked(ring, head, slot,
                                             frame_num)
        # the chunk's prefix is on the host already: the pull's first
        # step copies nothing, its second is the per-frame path's
        got = self._flat_pull["p"].pull(flats[slot], head, self.mb_h)
        if got is None:
            _note_entropy_overflow("ring p")
            # same fallback as the per-frame path: host-entropy the
            # chunk's own level tensors for this frame
            pulled = {k: np.asarray(v[slot]) for k, v in lvs.items()}
            pulled["mv"] = np.asarray(mvs[slot])
            qp_map = pulled.pop("qp_map", None)
            self._note_qp_map(qp_map, levels=pulled, slice_qp=qp)
            return h264_entropy.encode_p_picture(
                pulled, frame_num=frame_num, qp_delta=qp - self.qp,
                deblocking_idc=self._deblock_idc, qp_map=qp_map,
                slice_qp=qp)
        buf, meta = got
        self._note_qp_sum(meta.qp_sum)
        return cavlc_device.assemble_annexb(
            buf, meta, nal_type=syn.NAL_SLICE, ref_idc=2)

    def _ring_collect_masked(self, ring, head, slot: int,
                             frame_num: int) -> bytes:
        """Masked-chunk collect: :meth:`_collect_p_masked`'s protocol
        against the chunk's stacked outputs — FlatMeta over the shared
        row bucket, skip-slice interleave from the staged worklist."""
        from ..bitstream import h264_entropy
        from ..ops import damage_mask as dmg

        qp = ring["qp"]
        flats, _, mvs, lvs = ring["res"]
        bucket, padded = ring["dmg"]
        rows_p = padded[slot]
        got = self._flat_pull["p"].pull(flats[slot], head, bucket)
        if got is None:
            _note_entropy_overflow("masked ring p")
            pulled = {k: np.asarray(v[slot]) for k, v in lvs.items()}
            qp_map = pulled.pop("qp_map", None)
            mv = np.asarray(mvs[slot])
            full_lv, full_mv = dmg.scatter_levels_np(
                pulled, mv, rows_p, self.mb_h)
            full_lv["mv"] = full_mv
            if qp_map is not None:
                fq = np.full(
                    (self.mb_h,) + np.asarray(qp_map).shape[1:],
                    qp, np.asarray(qp_map).dtype)
                fq[rows_p] = np.asarray(qp_map)
                qp_map = fq
            self._note_qp_map(qp_map, levels=full_lv, slice_qp=qp)
            return h264_entropy.encode_p_picture(
                full_lv, frame_num=frame_num, qp_delta=qp - self.qp,
                deblocking_idc=self._deblock_idc,
                qp_map=qp_map, slice_qp=qp)
        buf, meta = got
        if meta.qp_sum:
            self._note_qp_sum(int(meta.qp_sum)
                              + qp * self.mb_w
                              * (self.mb_h - bucket))
        return dmg.assemble_masked_au(
            buf, meta, rows_p, self.mb_h, self.mb_w,
            frame_num=frame_num, qp_delta=qp - self.qp,
            deblocking_idc=self._deblock_idc)

    def _ring_collect_cabac(self, ring, head, slot: int,
                            frame_num: int) -> bytes:
        from ..bitstream import h264_cabac

        qp = ring["qp"]
        flats, _, mvs, lvs = ring["res"]
        if head.ndim == 2:
            # spatial chunk: per-shard record streams, row-stitched
            # through the shared spatial collect
            lv = {k: v[slot] for k, v in lvs.items()}
            return self._sp_collect_bin("p", qp, 0, frame_num,
                                        flats[slot], head,
                                        (lv, mvs[slot]))
        # same pull-guess/short-read/overflow protocol as the per-frame
        # path — ONE implementation, the P frames' pull helper
        if not self._cabac_native:
            _M_CABAC_PYTHON.inc()
        head = self._cabac_pull["p"].pull(flats[slot], head)
        if head is not None:
            au = h264_cabac.encode_p_from_binstream(
                head, nr=self.mb_h, nc_mb=self.mb_w, qp=qp,
                frame_num=frame_num, qp_delta=qp - self.qp,
                deblocking_idc=self._deblock_idc)
            if au is not None:
                return au
        # packed-stream or engine overflow: dense fallback from the
        # chunk's level tensors (same contract as _collect_cabac_p)
        _note_cabac_dense()
        dense = {k: np.asarray(v[slot]) for k, v in lvs.items()}
        dense["mv"] = np.asarray(mvs[slot], np.int32)
        return h264_cabac.encode_p_picture(
            dense, qp=qp, frame_num=frame_num, qp_delta=qp - self.qp,
            deblocking_idc=self._deblock_idc)

    def _encode_p_host(self, y, cb, cr, qp: int, ref=None,
                       update_ref: bool = True,
                       frame_num: int = None) -> bytes:
        from ..bitstream import h264_entropy
        from ..ops import h264_inter

        ref = self._ref if ref is None else ref
        frame_num = self._frame_num if frame_num is None else frame_num
        out = h264_inter.encode_p_frame(
            jnp.asarray(y), jnp.asarray(cb), jnp.asarray(cr), *ref, qp=qp,
            tune=self._ktune, p_intra=self._p_intra)
        recon = (out["recon_y"], out["recon_cb"], out["recon_cr"])
        if update_ref:
            if self.deblock:
                from ..ops import h264_deblock
                from ..ops.h264_device import LUMA_BLOCK_ORDER
                # nnz stays on device (analysis finding jax-host-roundtrip
                # h264.py/_encode_p_host): pulling the full level array
                # just to scatter 16 booleans cost a blocking D2H + H2D
                # pair per P frame — a full round trip each —
                # and the same array is pulled AGAIN below for entropy.
                nnz_idx = out["luma"].any(axis=-1)        # (R, C, 16)
                nr_, nc_ = nnz_idx.shape[:2]
                nnz = jnp.zeros((nr_, nc_, 4, 4), bool).at[
                    :, :, LUMA_BLOCK_ORDER[:, 1],
                    LUMA_BLOCK_ORDER[:, 0]].set(nnz_idx)
                self._ref = h264_deblock.deblock_frame(
                    *recon, qp, nnz_blk=nnz,
                    mv=jnp.asarray(out["mv"], jnp.int32))
            else:
                self._ref = recon
        if self.keep_recon:
            self.last_recon = tuple(np.asarray(p) for p in recon)
        pulled = {k: np.asarray(out[k])
                  for k in ("mv", "luma", "cb_dc", "cb_ac", "cr_dc", "cr_ac")}
        for k in ("mb_intra", "i16_dc", "i16_ac"):
            if k in out:                     # I16-in-P (tune=hq)
                pulled[k] = np.asarray(out[k])
        self.last_mv = pulled["mv"]          # (R, C, 2) quarter-pel; debug
        qp_map = np.asarray(out["qp_map"]) if "qp_map" in out else None
        self._note_qp_map(qp_map, levels=pulled, slice_qp=qp)
        # entropy == "cabac" never reaches here (_encode_p routes it to
        # the packed-transport path; the P overflow fallback is
        # entropy=="device" only)
        return h264_entropy.encode_p_picture(
            pulled, frame_num=frame_num, qp_delta=qp - self.qp,
            deblocking_idc=self._deblock_idc,
            qp_map=qp_map, slice_qp=qp)

    def _gop_step(self, rgb):
        """One GOP state-machine step -> (data, keyframe)."""
        idr = (self._gop_pos == 0 or self._force_idr or self._ref is None)
        n0 = self._rate.mark() if self._rate is not None else 0
        try:
            if idr:
                self._force_idr = False
                self._gop_pos = 0
                self._frame_num = 0
                self._idr_count += 1
                data = self._encode_cavlc(rgb)
            else:
                self._frame_num = (self._frame_num + 1) % 16
                data = self._encode_p(rgb)
        except Exception:
            if self._rate is not None:
                self._rate.rollback_to(n0)
            self._force_idr = True   # ref chain may be ahead of the client
            raise
        self._gop_pos = (self._gop_pos + 1) % self.gop
        if self._rate is not None:
            self._rate.update(len(data) * 8,
                              mean_qp=self._take_mean_qp())
        return data, idr

    # ------------------------------------------------------------------

    def encode(self, rgb) -> EncodedFrame:
        t0 = time.perf_counter()
        if self.gop > 1:
            data, key = self._gop_step(rgb)
        else:
            n0 = self._rate.mark() if self._rate is not None else 0
            try:
                data = self._encode_cavlc(rgb)
            except Exception:
                if self._rate is not None:
                    self._rate.rollback_to(n0)
                raise
            key = True
            if self._rate is not None:
                self._rate.update(len(data) * 8,
                                  mean_qp=self._take_mean_qp())
        ms = (time.perf_counter() - t0) * 1e3
        PROFILER.record_encoder(
            self, ("intra" if key else "p") + "-encode", ms)
        ef = EncodedFrame(data=data, keyframe=key, frame_index=self.frame_index,
                          codec=self.codec, width=self.width,
                          height=self.height, encode_ms=ms)
        self.frame_index += 1
        return ef

    # ------------------------------------------------------------------
    # Pipelined API (SURVEY.md §3.2 double-buffering requirement): submit
    # dispatches asynchronously so the next frame's host->device transfer
    # and the current frame's compute overlap; collect blocks on the pull.
    # ------------------------------------------------------------------

    # What the session loop puts between the halves of a submit, for the
    # length of its own call: the collect of the frame before, where the
    # device has finished it (web/session.py:_collect_between).
    between_halves = None

    def encode_submit(self, rgb):
        """Start encoding a frame; returns an opaque token.  Device-entropy
        CAVLC and packed-transport CABAC pipeline fully — including GOP
        mode, where the reference dependency between consecutive P frames
        lives on device, so frame N+1 can be submitted while frame N's
        bitstream is still in flight.

        On the per-frame paths the submit is two halves.  The FIRST is
        the frame's index, the GOP's decision, the rate controller's qp
        reservation, the colour conversion and, on a damage-mask
        session, the P frame's row plan (it needs the luma alone, and
        tells the controller its damage there): everything up to the
        planes, and nothing for the device while the host converts.  The
        SECOND is the dispatch: header slots, H2D, the frame's programs,
        the prefix slice and its prefetch.  ``between_halves``, where the
        caller has set one, is called with nothing between the two;
        whatever it does, the stream is the same bytes (an
        ``encode_collect`` there folds its frame into the rate controller
        AFTER this frame's qp was reserved and its damage noted, as it
        does behind the whole submit).  The super-step ring submits in
        one piece and calls nothing."""
        if self.entropy not in ("device", "cabac"):
            ef = self.encode(rgb)
            self._content_last = None    # sync path: no stats contract
            return ("sync", None, None, True, ef)
        cabac = self.entropy == "cabac"
        idx = self.frame_index
        self.frame_index += 1
        t0 = time.perf_counter()
        n0 = self._rate.mark() if self._rate is not None else 0
        try:
            intra = (self.gop == 1 or self._gop_pos == 0 or self._force_idr
                     or self._ref is None)
            if not intra:
                self._frame_num = (self._frame_num + 1) % 16
            elif self.gop == 1:
                pic_id = idx % 2
            else:
                if self._ring is not None:
                    # partial chunk ahead of an IDR: per-frame flush
                    # (byte-identical path) so the ring never straddles
                    # a reference-chain reset
                    self._ring_flush()
                self._force_idr = False
                self._gop_pos = 0
                self._frame_num = 0
                self._idr_count += 1
                pic_id = self._idr_count % 2
            ring = not intra and self._ring_chunk
            if intra:
                begun = self._intra_begin(rgb)
            elif not ring:
                qp = self._eff_qp(keyframe=False)
                y, cb, cr = self._planes_device(rgb)
                plan = self._damage_plan(y)
            between = self.between_halves
            if between is not None and not self._ring_chunk:
                reserved = self._rate.mark() - n0 \
                    if self._rate is not None else 0
                t_b = time.perf_counter()
                try:
                    between()
                finally:
                    # (a collect in there took ITS reservation off the
                    # queue's other end: this frame's count from the top)
                    t0 += time.perf_counter() - t_b
                    if self._rate is not None:
                        n0 = self._rate.mark() - reserved
            if intra:
                kind = "cabac_intra" if cabac else "intra"
                sub = (self._submit_cabac_intra(rgb, pic_id, begun) if cabac
                       else self._submit_device(rgb, pic_id, begun))
                tok = (kind, idx, t0, True, sub)
            elif ring:
                tok = self._ring_stage(rgb, idx, t0)
            else:
                kind = "cabac_p" if cabac else "p"
                if cabac and plan is not None:
                    kind, sub = self._submit_cabac_p_planned(
                        y, cb, cr, qp, plan)
                else:
                    sub = (self._submit_cabac_p(y, cb, cr, qp) if cabac
                           else self._submit_p_device(y, cb, cr, qp,
                                                      damage_plan=plan))
                tok = (kind, idx, t0, False, sub)
        except Exception:
            # this submit's qp reservation (if it got that far) will never
            # see an update(); drop it so EMA attribution stays aligned
            if self._rate is not None:
                self._rate.rollback_to(n0)
            # _submit_p_device may have advanced self._ref before raising;
            # the decoder never gets this frame — IDR-resync the chain
            self._force_idr = True
            raise
        self._gop_pos = (self._gop_pos + 1) % self.gop
        # submit-span profile: host color convert + async dispatch (a
        # ring stage is just the host splice until the chunk boundary)
        PROFILER.record_encoder(self, f"{tok[0]}-submit",
                                (time.perf_counter() - t0) * 1e3)
        self._content_stash(idx)
        return tok

    # where each per-frame path's token keeps the device array its collect
    # pulls FIRST (the guessed prefix; models/prefix_pull.py)
    _PREFIX_AT = {"intra": 5, "p": 5, "cabac_intra": 2, "cabac_p": 4,
                  "cabac_p_mask": 6}
    # ... and where a P frame's marked payload does: a mesh's, a damage
    # mask's row program's
    _MARKED_PREFIX_AT = {"sp": 6, "sp_bin": 6, "dmg": 5}

    def token_ready(self, token) -> Optional[bool]:
        """Whether the device has FINISHED what ``encode_collect(token)``
        pulls first: ``is_ready()`` of the guessed prefix (of every
        shard's on a mesh, which is one array).  It asks and nothing
        else: no transfer starts, nothing blocks, nothing compiles, and
        it never raises.  ``None`` where there is nothing to ask: the
        ring path, a synchronous token, an array without
        ``is_ready``, one deleted or donated since."""
        try:
            at = self._PREFIX_AT.get(token[0])
            if at is None:
                return None
            payload = token[4]
            if isinstance(payload[0], str):      # a marked token:
                at = self._MARKED_PREFIX_AT.get(payload[0])
                if at is None:
                    return None
            prefix = payload[at]
            # (asked of a deleted array, jaxlib 0.9's is_ready() takes the
            # process down instead of raising)
            return None if prefix.is_deleted() else bool(prefix.is_ready())
        except Exception:
            return None

    def encode_collect(self, token) -> EncodedFrame:
        kind, idx, t0, key, payload = token
        if kind == "sync":
            return payload
        t_c0 = time.perf_counter()
        try:
            if kind == "ring":
                data = self._ring_collect(payload)
            elif kind == "p":
                data = self._collect_p_device(payload)
            elif kind == "cabac_p":
                data = self._collect_cabac_p(payload)
            elif kind == "cabac_p_mask":
                data = self._collect_cabac_p_masked(payload)
            elif kind == "cabac_intra":
                data = self._collect_cabac_intra(payload)
            else:
                data = self._collect_device(payload,
                                            in_pipeline=self.gop > 1)
        except Exception:
            if self._rate is not None:
                self._rate.drop_oldest_pending()
            # the dropped frame's recon may already be self._ref (submit
            # advances the reference chain) — the decoder never saw it, so
            # every later P in this GOP would predict from a reference the
            # client doesn't have.  Resync with an IDR on the next submit.
            self._force_idr = True
            raise
        if self._rate is not None:
            self._rate.update(len(data) * 8,
                              mean_qp=self._take_mean_qp())
        with obst.stage("stats"):
            self._content_finish(token, data)
        # journey attribution: a ring frame that rode a dispatched chunk
        # carries its chunk identity; a flushed partial ring went
        # per-frame and is unchunked (it paid its own dispatch)
        if kind == "ring":
            ring, slot = payload
            chunked = ring.get("pf") is None and "chunk_id" in ring
            self._journey_meta = {
                "chunk_id": ring["chunk_id"] if chunked else None,
                "slot": slot,
                "chunk_len": len(ring["fns"]) if chunked else 1,
                "shards": self._spatial_nx,
            }
        else:
            self._journey_meta = {"chunk_id": None, "slot": 0,
                                  "chunk_len": 1,
                                  "shards": self._spatial_nx}
        # collect-span profile: device wait + bitstream pull + assembly,
        # amortized over the chunk like the journey accounting (a ring
        # collect that rode a dispatched chunk pays 1/chunk_len of the
        # whole pull per frame)
        PROFILER.record_encoder(
            self, f"{kind}-collect", (time.perf_counter() - t_c0) * 1e3,
            chunk_len=self._journey_meta["chunk_len"])
        ms = (time.perf_counter() - t0) * 1e3
        return EncodedFrame(data=data, keyframe=key, frame_index=idx,
                            codec=self.codec, width=self.width,
                            height=self.height, encode_ms=ms)
