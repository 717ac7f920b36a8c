"""An office desktop: a pointer that moves every refresh, and episodes of
typing (calm), scrolling, window dragging and a small video.

The script is a fixed list of episodes (the rate controller remembers, so
their order is part of the work and is the same for every seed); the seed sets
the text, the places and the textures.  It repeats every ``sum(frames)``
frames.  Every episode starts from the same desktop, and a
frame inside an episode is a closed form of its offset ``j`` (glyphs typed =
j div 6, scroll offset = 48 j), so the check re-renders any frame without
replaying the run.  Window geometry is in pixels, clamped to small frames.
"""

from __future__ import annotations

import numpy as np

from benchmark import scene

_ARROW = np.array([[1 if x <= y and x + y // 2 < 12 else 0
                    for x in range(12)] for y in range(19)], bool)


def _fit(size: int, room: int) -> int:
    return max(16, min(size, room))


class Scene:
    def __init__(self, params: dict, width: int, height: int, fps: int,
                 seed: int):
        self.seed, self.w, self.h = seed, width, height
        p = params
        self.episodes = list(p["episodes"])
        self.starts = np.cumsum([0] + [e["frames"] for e in self.episodes])
        self.cycle = int(self.starts[-1])
        self.type_every = int(p["type_every_frames"])
        self.scroll_px = int(p["scroll_px_per_frame"])
        self.drag_px = int(p["drag_px_per_frame"])
        self.video_pan = p["video_pan"]

        rng = scene.rng_for(seed, 2)
        self.bank = scene.glyph_bank(rng)
        yy, xx = np.mgrid[0:height, 0:width]
        desk = np.stack([40 + xx * 60 // width, 70 + yy * 70 // height,
                         120 + (xx + yy) * 60 // (width + height)],
                        axis=-1).astype(np.uint8)
        desk[height - 40:] = (32, 34, 40)                     # task bar
        # the text window (typing and scrolling happen in it)
        tw, th = p["text_window"]
        self.tw, self.th = _fit(tw, width - 96), _fit(th, height - 120)
        self.tx, self.ty = min(64, width - self.tw), min(72, height - self.th)
        self.doc = scene.text_page(rng, self.bank, max(4 * self.th, 2048),
                                   self.tw)
        desk[self.ty - 24:self.ty, self.tx:self.tx + self.tw] = (60, 90, 150)
        desk[self.ty:self.ty + self.th,
             self.tx:self.tx + self.tw] = self.doc[:self.th]
        self.desk = desk
        # the window that is dragged
        dw, dh = p["drag_window"]
        self.dw, self.dh = _fit(dw, width // 2), _fit(dh, height // 2)
        self.dwin = scene.text_page(rng, self.bank, self.dh, self.dw,
                                    ink=(230, 230, 220), paper=(28, 30, 36))
        self.dwin[:24] = (150, 70, 60)
        # the video region
        vw, vh = p["video_region"]
        self.vw, self.vh = _fit(vw, width // 2), _fit(vh, height // 2)
        self.vx = max(0, width - self.vw - 80)
        self.vy = max(0, height - self.vh - 120)
        self.vtex = scene.texture(scene.rng_for(seed, 3), self.vh + 256,
                                  self.vw + 256, float(p["video_sigma"]))
        # where typing starts, per episode, and what is typed
        self.cols = self.tw // scene.GLYPH_W
        self.lines = self.th // scene.GLYPH_H

    def _episode(self, c: int):
        j = c % self.cycle
        i = int(np.searchsorted(self.starts, j, side="right")) - 1
        return i, self.episodes[i], j - int(self.starts[i])

    def render(self, c: int, out: np.ndarray) -> None:
        np.copyto(out, self.desk)
        i, ep, j = self._episode(c)
        kind = ep["kind"]
        if kind == "calm":
            rng = scene.rng_for(self.seed, 10, i)
            n = j // self.type_every + 1
            line = int(rng.integers(0, self.lines))
            col0 = int(rng.integers(0, max(1, self.cols // 2)))
            ids = rng.integers(0, len(self.bank), 1024)
            for g in range(n):
                col = col0 + g
                y = self.ty + ((line + col // self.cols) % self.lines) \
                    * scene.GLYPH_H
                x = self.tx + (col % self.cols) * scene.GLYPH_W
                out[y:y + scene.GLYPH_H, x:x + scene.GLYPH_W] = \
                    self.bank[ids[g]][:, :, None]
        elif kind == "scroll":
            scene.wrapped_window(
                self.doc, self.scroll_px * j, 0,
                out[self.ty:self.ty + self.th, self.tx:self.tx + self.tw])
        elif kind == "drag":
            rng = scene.rng_for(self.seed, 11, i)
            x0 = int(rng.integers(0, self.w - self.dw + 1))
            y0 = int(rng.integers(0, self.h - self.dh + 1))
            x = scene.triangle(x0 + self.drag_px * j, self.w - self.dw)
            y = scene.triangle(y0 + self.drag_px * j // 2, self.h - self.dh)
            out[y:y + self.dh, x:x + self.dw] = self.dwin
        elif kind == "video":
            scene.wrapped_window(
                self.vtex, self.video_pan[1] * j, self.video_pan[0] * j,
                out[self.vy:self.vy + self.vh, self.vx:self.vx + self.vw])
        else:
            raise ValueError(f"unknown episode kind {kind!r}")
        # the pointer moves every refresh
        px = scene.triangle(37 + 5 * c, self.w - 12)
        py = scene.triangle(91 + 3 * c, self.h - 19)
        out[py:py + 19, px:px + 12][_ARROW] = (255, 255, 255)


def build(params: dict, width: int, height: int, fps: int, seed: int) -> Scene:
    return Scene(params, width, height, fps, seed)
