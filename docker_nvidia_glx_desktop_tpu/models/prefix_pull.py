"""The host's pull of a device buffer whose length only the device knows:
a guessed prefix, copied while the next frame is dispatched, and a second
pull where the guess was short (models/h264.py)."""

from __future__ import annotations

import collections

import numpy as np

from ..obs import metrics as obsm
from ..obs import trace as obst

M_PULL_EXTRA = obsm.counter(
    "dngd_encoder_pull_extra_total",
    "Frames whose bitstream outgrew the guessed pull prefix and paid a "
    "second device->host round trip (the first time a length is seen "
    "it is also a compile of the slice)")
M_CABAC_RECORD_BYTES = obsm.counter(
    "dngd_encoder_cabac_record_bytes_total",
    "Bytes of CABAC transport the host pulled for its engine: header and "
    "payload of the binarize record stream (of the packed levels on the "
    "level transport), without the slack of the guessed prefix")
M_D2H_BYTES = obsm.counter(
    "dngd_encoder_d2h_bytes_total",
    "Bytes the per-frame CABAC path copied from the device: the guessed "
    "prefix of the transport buffer, slack included, a second pull where "
    "the guess was short, the content statistics' vector and grid, and "
    "the level tensors of a dense fallback")


def prefetch_host(arr) -> None:
    """Start the device->host copy of a pull-prefix at SUBMIT time.

    The pipelined serving loop collects frames with a synchronous
    ``np.asarray`` — one host<->device round-trip per frame.
    ``copy_to_host_async`` lets the pulls of in-flight frames overlap
    each other and the next frame's dispatch."""
    arr.copy_to_host_async()


class PrefixPull:
    """The host's pull of one kind of CABAC transport buffer
    (ops/cabac_binarize and ops/level_pack share the layout: ``hdrw``
    header words, [1] the overflow flag and [2] the payload's words,
    then the payload).

    What is pulled is the header and a GUESS of the payload: the
    decaying max of the last 64 frames' needs (a second or two: content
    whose size hovers round a rung would otherwise mispredict every few
    frames, and a mispredict is a second device round trip, behind the
    next frame's programs where the device sets the pace), rounded up to
    a rung.
    Rungs are multiples of 64 KiB with two significant bits (1, 2, 3,
    4, 6, 8, 12, 16, 24 ...): every rung is one compiled slice, a
    quarter of a second in the serving thread when first met (PERF.md
    PR 24 finding 3), and a 1080p record buffer is 33 MiB, 19 rungs
    where a linear ladder has 521.  :meth:`warm` compiles them all.

    A spatial mesh's frame is one such buffer a shard, stacked
    (``(shards, words)``): the same ladder along the last axis, every
    shard pulled at the length the longest needs.

    :class:`FlatPull` is the same pull of the CAVLC flat buffer, under
    its own ladder and header."""

    BUCKET = 1 << 14                       # words: 64 KiB
    HISTORY = 64                           # frames the guess looks back on

    def __init__(self, hdrw: int, buckets: int):
        self.hdrw = hdrw
        self.guess = buckets * self.BUCKET
        self.hist = collections.deque(maxlen=self.HISTORY)

    def rung(self, words: int) -> int:
        b = max(-(-words // self.BUCKET), 1)
        step = 1 << max(b.bit_length() - 2, 0)
        return -(-b // step) * step * self.BUCKET

    def note(self, words: int) -> None:
        """One frame's payload: the next guess covers it."""
        self.hist.append(words)
        self.guess = self.rung(max(self.hist))

    def prefix(self, buf):
        """Slice the guessed prefix off ``buf`` and start its copy to
        the host (at submit time)."""
        head = self._cut(buf, self.guess)
        prefetch_host(head)
        return head

    def _cut(self, buf, words: int):
        n = self.hdrw + words
        return buf[:n] if buf.ndim == 1 else buf[:, :n]

    def warm(self, buf) -> int:
        """Compile the slice of every rung ``buf`` can meet (up to its
        whole length); returns how many."""
        words = n = 0
        while self.hdrw + words < buf.shape[-1]:
            words = self.rung(words + 1)
            self._cut(buf, words).block_until_ready()
            n += 1
        return n

    def _pull(self, buf, prefix, read):
        """The one pull, ``(host buffer, bytes copied)``: the host copy
        of the guessed prefix (stage ``pull``), ``read(header)`` for what
        the payload needs in the ladder's unit (None where an overflow
        flag is up, which ends the pull: no buffer), the need noted for
        the next guess, and the whole of it pulled again at its rung
        where the guess was short (stage ``pull_extra``, counted)."""
        with obst.stage("pull"):
            head = np.asarray(prefix)
        copied = head.nbytes
        need = read(head)
        if need is None:
            return None, copied
        self.note(need)
        if self.hdrw + need > head.shape[-1]:
            M_PULL_EXTRA.inc()
            with obst.stage("pull_extra"):
                head = np.asarray(self._cut(buf, self.rung(need)))
            copied += head.nbytes
        return head, copied

    def pull(self, buf, prefix):
        """The host copy of header and payload (of every shard's, on a
        mesh's ``(shards, words)`` buffer), pulled again where the guess
        was short (all shards at the longest need's rung); None where an
        overflow flag is up."""
        head, copied = self._pull(
            buf, prefix,
            lambda h: None if h[..., 1].any() else int(h[..., 2].max()))
        M_D2H_BYTES.inc(copied)
        if head is not None:
            words = head[..., 2].astype(np.int64)
            M_CABAC_RECORD_BYTES.inc(4 * int((self.hdrw + words).sum()))
        return head


class FlatPull(PrefixPull):
    """The host's pull of the CAVLC flat buffer (ops/cavlc_device: a
    header of ``META_WORDS`` words, then the frame's bytes), one a kind
    of frame as the CABAC helpers are.  Its ladder is the linear one the
    path has always walked: multiples of 64 KiB, the max of the last 8
    frames' needs, counted in BYTES (the buffer is uint8).  Content
    walks it (64 KiB steps of a 46 KB frame), the damage mask's set-up
    warms it whole (:meth:`warm`)."""

    BUCKET = 1 << 16                       # bytes: 64 KiB
    HISTORY = 8

    def __init__(self, buckets: int):
        from ..ops import cavlc_device
        super().__init__(cavlc_device.META_WORDS * 4, buckets)
        self._learnt = False

    def rung(self, nbytes: int) -> int:
        return -(-nbytes // self.BUCKET) * self.BUCKET

    def note(self, nbytes: int) -> None:
        super().note(nbytes)
        self._learnt = True

    def checkpoint(self):
        """The guess as ``export_state`` carries it: None until a frame
        or a checkpoint has set it, so that putting a fresh encoder's
        state back leaves a warmed guess alone."""
        return self.guess if self._learnt else None

    def restore(self, guess) -> None:
        """A checkpoint's guess (``import_state``); None or 0 = none."""
        if guess:
            self.guess = int(guess)
            self._learnt = True

    def pull(self, flat, prefix, rows: int):
        """``(host buffer, FlatMeta)`` of a frame of ``rows`` slices,
        pulled again where the guess was short; on a mesh's ``(shards,
        bytes)`` buffer, every shard's rows and a list of metas, all
        pulled again at the longest need.  None where an overflow flag
        is up (the caller's host coder takes the frame)."""
        from ..ops import cavlc_device
        metas = []

        def read(head):
            metas[:] = [cavlc_device.FlatMeta(h, rows)
                        for h in (head if head.ndim == 2 else head[None])]
            if any(m.overflow for m in metas):
                return None
            return max(4 * m.total_words for m in metas)

        buf, _ = self._pull(flat, prefix, read)
        if buf is None:
            return None
        return buf, (metas if buf.ndim == 2 else metas[0])
