"""Full-screen game or video: every macroblock changes every frame.

A seeded band-limited texture larger than the frame pans by ``pan`` pixels a
frame (motion search finds most of it), and one macroblock in
``fresh_one_in`` is replaced each frame by texture the reference picture never
held.  Frame ``c`` is a closed form of ``(seed, c)``.
"""

from __future__ import annotations

import numpy as np

from benchmark import scene

MB = 16


class Scene:
    def __init__(self, params: dict, width: int, height: int, fps: int,
                 seed: int):
        self.seed = seed
        self.pan_x, self.pan_y = params["pan"]
        self.sigma = float(params["sigma"])
        self.one_in = int(params["fresh_one_in"])
        margin = int(params["texture_margin"])
        rng = scene.rng_for(seed, 1)
        self.tex = scene.texture(rng, height + margin, width + margin,
                                 self.sigma)
        self.mb_h, self.mb_w = height // MB, width // MB
        n_bank = int(params["fresh_bank"])
        other = scene.texture(scene.rng_for(seed, 2), 256, 256, self.sigma)
        ys = rng.integers(0, 256 - MB, n_bank)
        xs = rng.integers(0, 256 - MB, n_bank)
        self.bank = np.stack([other[y:y + MB, x:x + MB]
                              for y, x in zip(ys, xs)])
        self.n_fresh = max(1, self.mb_h * self.mb_w // self.one_in)

    def render(self, c: int, out: np.ndarray) -> None:
        scene.wrapped_window(self.tex, self.pan_y * c, self.pan_x * c, out)
        rng = scene.rng_for(self.seed, 3, c)
        where = rng.integers(0, self.mb_h * self.mb_w, self.n_fresh)
        which = rng.integers(0, len(self.bank), self.n_fresh)
        grid = out[:self.mb_h * MB, :self.mb_w * MB].reshape(
            self.mb_h, MB, self.mb_w, MB, 3)
        grid[where // self.mb_w, :, where % self.mb_w] = self.bank[which]


def build(params: dict, width: int, height: int, fps: int, seed: int) -> Scene:
    return Scene(params, width, height, fps, seed)
