"""The frame index, drawn into the picture.

Which display frame a fragment carries cannot be taken from the program (it
drops frames, and pts is its own clock), so every frame carries its index
``k`` as 32 black or white 16x16 macroblocks from the top-left corner, row by
row: 24 bits of ``k`` and an 8-bit check of them.  A whole macroblock of flat
black or white survives the coarse end of the rate ladder (qp 44), and it is a
few changed macroblocks a frame, like a clock applet.
"""

from __future__ import annotations

import numpy as np

MB = 16
BITS = 32
K_BITS = 24
_WHITE_ABOVE = 125          # studio-range luma: black 16, white 235


def check_byte(k: int) -> int:
    return ((k & 0xFFFFFF) * 2654435761 >> 13) & 0xFF


def word(k: int) -> int:
    return (k & 0xFFFFFF) | (check_byte(k) << K_BITS)


def layout(width: int) -> tuple:
    """(rows, cols) of the macroblock grid that holds the 32 bits."""
    cols = min(BITS, width // MB)
    if cols < 1:
        raise ValueError(f"frame of width {width} holds no macroblock")
    return -(-BITS // cols), cols


def draw(frame: np.ndarray, k: int) -> None:
    """Write ``k`` into ``frame`` (H, W, 3) uint8, in place."""
    rows, cols = layout(frame.shape[1])
    w = word(k)
    bits = np.zeros(rows * cols, np.uint8)
    bits[:BITS] = [(w >> i) & 1 for i in range(BITS)]
    cells = (bits.reshape(rows, cols) * 255).astype(np.uint8)
    block = np.repeat(np.repeat(cells, MB, axis=0), MB, axis=1)
    frame[:rows * MB, :cols * MB] = block[:, :, None]


def read(luma: np.ndarray):
    """``k`` from a decoded luma plane, or None if the check byte disagrees."""
    rows, cols = layout(luma.shape[1])
    area = luma[:rows * MB, :cols * MB].astype(np.float32)
    means = area.reshape(rows, MB, cols, MB).mean(axis=(1, 3)).reshape(-1)
    w = 0
    for i in range(BITS):
        if means[i] > _WHITE_ABOVE:
            w |= 1 << i
    k = w & 0xFFFFFF
    return k if (w >> K_BITS) == check_byte(k) else None
