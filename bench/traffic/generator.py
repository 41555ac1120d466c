"""The one traffic generator: a mix file of parameters -> submissions.

A mix (``bench/traffic/<mix>.json``) names its loop ``kind`` (the module
``bench/traffic/<kind>.py`` that drives it) and the parameters read here:

* ``shapes``: ``[{"name", "frames", "objects"}, ...]``, the sequence
  shapes (length in frames, simultaneous-object cap);
* ``variants``: scenes generated per shape; the pool is shapes x variants;
* ``shift_px``: each submission is a pool scene with every box moved by a
  seeded offset in ``[-shift_px, shift_px]`` on each axis, so no two
  submissions are identical;
* ``scene``: optional overrides of :class:`bench.scenes.SceneConfig`;
* closed loop only: ``first_fill``: ``"residual"`` makes the first
  lane-width of submissions the unserved tails of sequences, as the lanes
  of a service that has run for a while hold them (:meth:`Traffic.
  _first_fill`), so the window measures the steady state and not a start
  in which every lane begins and ends together;
* open loop only: ``rate_per_s`` (arrivals per second) and ``cameras``
  (clients, assigned round-robin).

The order of the pool is a fresh seeded permutation on every pass, and the
gaps between arrivals are the quantiles of a unit exponential in a seeded
order: every seed gets the same set of sizes and gaps, in another order.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import numpy as np

from bench.scenes import SceneConfig, generate_multiclass_scene, \
    generate_scene

BLOCK = 4096        # submissions per block of seeded shifts and gaps


@dataclasses.dataclass
class Submission:
    index: int
    name: str
    client: str
    det_boxes: np.ndarray            # [F, D_i, 4] float32
    det_mask: np.ndarray             # [F, D_i] bool
    det_class: Optional[np.ndarray]  # [F, D_i] int32 or None
    det_embed: Optional[np.ndarray]  # [F, D_i, E] float32 or None

    @property
    def frames(self) -> int:
        return self.det_boxes.shape[0]


class Traffic:
    """Seeded submissions of one mix for one configuration."""

    def __init__(self, mix: dict, config: dict, seed: int, lanes: int = 0):
        self.mix = mix
        self.seed = int(seed)
        self.kind = mix["kind"]
        engine = config["engine"]
        self.num_classes = int(engine.get("num_classes", 1))
        self.embed_dim = int(engine.get("cost", {}).get("embed_dim", 0))
        self.shift_px = float(mix.get("shift_px", 0.0))
        self.cameras = int(mix.get("cameras", 1))
        scene_kw = mix.get("scene", {})
        shapes = mix["shapes"]
        variants = int(mix.get("variants", 1))
        rng = np.random.default_rng([self.seed, 0])
        self.pool = []
        for v in range(variants):
            for shape in shapes:
                cfg = SceneConfig(num_frames=int(shape["frames"]),
                                  max_objects=int(shape["objects"]),
                                  seed=int(rng.integers(2**31)), **scene_kw)
                self.pool.append((shape["name"], self._scene(cfg)))
        self._order: dict[int, np.ndarray] = {}
        self._shifts: dict[int, np.ndarray] = {}
        self.fill = (self._first_fill(lanes, variants)
                     if mix.get("first_fill") == "residual" else [])
        self._arrivals = None
        if self.kind == "open_loop":
            self._arrivals = Arrivals(mix["rate_per_s"], self.seed)

    def _first_fill(self, lanes: int, variants: int) -> list:
        """``(pool index, first frame)`` of the first ``lanes`` submissions.

        In a service that has run for a while a lane holds a shape for a
        share of its time proportional to the shape's length, and is at any
        point of it alike.  So each shape gets lanes in that proportion
        (largest remainder), each at an even quantile of the frames already
        served, and they are ordered oldest admission first, as the service
        would have admitted them.  The sizes depend on the mix alone; the
        seed picks each one's scene and orders equal ages."""
        lengths = [int(s["frames"]) for s in self.mix["shapes"]]
        share = np.array(lengths, np.float64) * lanes / sum(lengths)
        count = np.floor(share).astype(int)
        for k in np.argsort(-(share - count), kind="stable")[
                :lanes - count.sum()]:
            count[k] += 1
        rng = np.random.default_rng([self.seed, 4])
        fill = []
        for k, (length, n) in enumerate(zip(lengths, count)):
            for q in range(n):
                served = int((q + 0.5) / n * length)
                variant = int(rng.integers(variants))
                fill.append((-served, rng.random(),
                             variant * len(lengths) + k, served))
        fill.sort()
        return [(p, served) for _, _, p, served in fill]

    def _scene(self, cfg: SceneConfig):
        if self.num_classes > 1 or self.embed_dim > 0:
            (_, _, _, db, dm, dc, de) = generate_multiclass_scene(
                cfg, num_classes=max(self.num_classes, 1),
                embed_dim=max(self.embed_dim, 1))
            return (db, dm, dc if self.num_classes > 1 else None,
                    de if self.embed_dim > 0 else None)
        _, _, db, dm = generate_scene(cfg)
        return db, dm, None, None

    def _pool_index(self, i: int) -> int:
        p = len(self.pool)
        cycle = i // p
        if cycle not in self._order:
            self._order = {cycle: np.random.default_rng(
                [self.seed, 1, cycle]).permutation(p)}
        return int(self._order[cycle][i % p])

    def _shift(self, i: int) -> np.ndarray:
        b = i // BLOCK
        if b not in self._shifts:
            self._shifts = {b: np.random.default_rng([self.seed, 2, b])
                            .uniform(-self.shift_px, self.shift_px,
                                     (BLOCK, 2)).astype(np.float32)}
        dx, dy = self._shifts[b][i % BLOCK]
        return np.array([dx, dy, dx, dy], np.float32)

    def _source(self, i: int) -> tuple[int, int]:
        """``(pool index, first frame)`` of the ``i``-th submission."""
        if i < len(self.fill):
            return self.fill[i]
        return self._pool_index(i - len(self.fill)), 0

    def frames(self, i: int) -> int:
        """Length of the ``i``-th submission, without building it."""
        p, start = self._source(i)
        return self.pool[p][1][0].shape[0] - start

    def submission(self, i: int) -> Submission:
        """The ``i``-th submission; the same ``(seed, i)`` gives the same
        arrays every time, so the reference can rebuild any of them."""
        p, start = self._source(i)
        name, arrays = self.pool[p]
        db, dm, dc, de = (None if a is None else a[start:] for a in arrays)
        return Submission(i, f"{name}-{i:07d}", f"cam{i % self.cameras}",
                          db + self._shift(i), dm, dc, de)

    def due(self, i: int) -> float:
        """Seconds from the generator's start to arrival ``i`` (open
        loop)."""
        return self._arrivals.time(i)


class Arrivals:
    """Arrival times at a fixed rate: the sums of unit-mean gaps (the
    exponential's quantiles in a seeded order) over the rate."""

    def __init__(self, rate: float, seed: int):
        self.rate = float(rate)
        if self.rate <= 0:
            raise ValueError(f"the rate must be > 0: {rate}")
        self.seed = seed
        self._sums = np.zeros(1)           # S_0 .. S_n, grown by blocks
        q = (np.arange(BLOCK) + 0.5) / BLOCK
        self._gaps = -np.log1p(-q)

    def time(self, i: int) -> float:
        while i + 1 >= self._sums.size:
            b = self._sums.size // BLOCK
            gaps = np.random.default_rng([self.seed, 3, b]).permutation(
                self._gaps)
            self._sums = np.concatenate(
                [self._sums, self._sums[-1] + np.cumsum(gaps)])
        return float(self._sums[i + 1]) / self.rate
