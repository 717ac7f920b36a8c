"""Seeded building blocks of the traffic generators: band-limited texture,
bitmap glyphs, text pages.  Plain numpy and cv2; nothing of the program."""

from __future__ import annotations

import cv2
import numpy as np

GLYPH_W, GLYPH_H = 8, 16


def rng_for(seed: int, *stream: int) -> np.random.Generator:
    """One generator per (seed, purpose): ``--seed`` may be any whole number
    up to a little over 2**31, so it goes in as an entropy word, not a C int."""
    return np.random.default_rng([int(seed) & 0xFFFFFFFFFFFF, *stream])


def texture(rng: np.random.Generator, height: int, width: int,
            sigma: float = 2.0) -> np.ndarray:
    """(height, width, 3) uint8 Gaussian-filtered noise that tiles: blurred
    with wrapped borders, so a pan that wraps around shows no seam."""
    pad = int(4 * sigma) + 1
    noise = rng.integers(0, 256, (height, width, 3), dtype=np.uint8)
    wrapped = np.pad(noise, ((pad, pad), (pad, pad), (0, 0)), mode="wrap")
    blur = cv2.GaussianBlur(wrapped.astype(np.float32), (0, 0), sigma)
    blur = blur[pad:-pad, pad:-pad]
    # blurring uniform noise leaves a standard deviation near 74/(2*sqrt(pi)*sigma):
    # stretch it back to a picture's contrast
    out = (blur - 127.5) * (1.4 * sigma) + 127.5
    return np.clip(out, 0, 255).astype(np.uint8)


def wrapped_window(tex: np.ndarray, y0: int, x0: int, out: np.ndarray) -> None:
    """Copy the (h, w) window of the tiling ``tex`` at (y0, x0) into ``out``."""
    th, tw = tex.shape[:2]
    h, w = out.shape[:2]
    y0 %= th
    x0 %= tw
    hy = min(h, th - y0)
    wx = min(w, tw - x0)
    out[:hy, :wx] = tex[y0:y0 + hy, x0:x0 + wx]
    if wx < w:
        out[:hy, wx:] = tex[y0:y0 + hy, :w - wx]
    if hy < h:
        out[hy:, :wx] = tex[:h - hy, x0:x0 + wx]
        if wx < w:
            out[hy:, wx:] = tex[:h - hy, :w - wx]


def glyph_bank(rng: np.random.Generator, n: int = 96) -> np.ndarray:
    """(n, 16, 8) uint8 bitmaps, 0 = ink: two to four one-pixel strokes
    inside a 6x11 box, as the letters of a terminal font are made (connected
    runs of ink over about a fifth of the box, not per-pixel noise, which
    costs a codec several times the bits of real text).  No font file."""
    bank = np.full((n, GLYPH_H, GLYPH_W), 255, np.uint8)
    for g in range(n):
        for _ in range(int(rng.integers(2, 5))):
            x0, x1 = (int(v) for v in rng.integers(1, 7, 2))
            y0, y1 = (int(v) for v in rng.integers(3, 14, 2))
            if rng.random() < 0.6:          # most strokes are upright or level
                if rng.random() < 0.5:
                    x1 = x0
                else:
                    y1 = y0
            cv2.line(bank[g], (x0, y0), (x1, y1), 0, 1)
    return bank


def text_page(rng: np.random.Generator, bank: np.ndarray, height: int,
              width: int, ink=(24, 24, 28), paper=(250, 250, 246)) -> np.ndarray:
    """(height, width, 3) page of lines of seeded glyphs with spaces and
    ragged line ends, as a text window shows."""
    rows, cols = -(-height // GLYPH_H), -(-width // GLYPH_W)
    ids = rng.integers(0, len(bank), (rows, cols))
    cells = bank[ids]                                   # (rows, cols, 16, 8)
    space = rng.random((rows, cols)) < 0.16
    ends = rng.integers(cols // 3, cols, rows)
    space |= np.arange(cols)[None, :] >= ends[:, None]
    cells[space] = 255
    mono = cells.transpose(0, 2, 1, 3).reshape(rows * GLYPH_H, cols * GLYPH_W)
    mono = mono[:height, :width]
    page = np.empty((height, width, 3), np.uint8)
    page[:] = paper
    page[mono == 0] = ink
    return page


def triangle(t: int, span: int) -> int:
    """0..span..0 bounce of period 2*span, in whole pixels."""
    if span <= 0:
        return 0
    t %= 2 * span
    return t if t <= span else 2 * span - t
