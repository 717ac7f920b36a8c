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
    "payload of the binarize record stream (of the packed levels under "
    "ENCODER_CABAC_BINARIZE=host), without the slack of the guessed prefix")
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
    shard pulled at the length the longest needs
    (:meth:`pull_shards`)."""

    BUCKET = 1 << 14                       # words: 64 KiB

    def __init__(self, hdrw: int, buckets: int):
        self.hdrw = hdrw
        self.guess = buckets * self.BUCKET
        self.hist = collections.deque(maxlen=64)

    @classmethod
    def rung(cls, words: int) -> int:
        b = max(-(-words // cls.BUCKET), 1)
        step = 1 << max(b.bit_length() - 2, 0)
        return -(-b // step) * step * cls.BUCKET

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

    def pull(self, buf, prefix):
        """The host copy of header and payload, pulled again where the
        guess was short; None on the overflow flag."""
        with obst.stage("pull"):
            head = np.asarray(prefix)
        M_D2H_BYTES.inc(head.nbytes)
        if head[1]:
            return None
        words = int(head[2])
        self.note(words)
        if self.hdrw + words > len(head):
            M_PULL_EXTRA.inc()
            with obst.stage("pull_extra"):
                head = np.asarray(buf[:self.hdrw + self.rung(words)])
            M_D2H_BYTES.inc(head.nbytes)
        M_CABAC_RECORD_BYTES.inc(4 * (self.hdrw + words))
        return head

    def pull_shards(self, buf, prefix):
        """:meth:`pull` for a mesh's ``(shards, words)`` buffer: the
        host copy of every shard's header and payload, all pulled again
        at the longest need's rung where the guess was short of any;
        None where any shard's overflow flag is up."""
        with obst.stage("pull"):
            heads = np.asarray(prefix)
        M_D2H_BYTES.inc(heads.nbytes)
        if heads[:, 1].any():
            return None
        words = heads[:, 2].astype(np.int64)
        need = int(words.max())
        self.note(need)
        if self.hdrw + need > heads.shape[1]:
            M_PULL_EXTRA.inc()
            with obst.stage("pull_extra"):
                heads = np.asarray(self._cut(buf, self.rung(need)))
            M_D2H_BYTES.inc(heads.nbytes)
        M_CABAC_RECORD_BYTES.inc(4 * int((self.hdrw + words).sum()))
        return heads
