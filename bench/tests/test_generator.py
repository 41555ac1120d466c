"""The traffic generator: seeded, and of the shapes its mixes state."""
from __future__ import annotations

import json
from pathlib import Path

import numpy as np
import pytest

from bench.traffic.generator import Arrivals, Traffic

BENCH = Path(__file__).resolve().parents[1]


def mix(name: str) -> dict:
    return json.loads((BENCH / "traffic" / f"{name}.json").read_text())


def config(name: str) -> dict:
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


def small(m: dict, keep: int = 3) -> dict:
    """The mix with its shortest shapes only, one variant: fast to build."""
    shapes = sorted(m["shapes"], key=lambda s: s["frames"])[:keep]
    return dict(m, shapes=shapes, variants=1)


@pytest.mark.parametrize("mix_name,config_name", [
    ("mot15-archive", "mot15-sort"),
    ("kitti-archive", "kitti-mc"),
    ("mot15-segments", "mot15-sort"),
])
def test_same_seed_same_submissions(mix_name, config_name):
    m, c = small(mix(mix_name)), config(config_name)
    a, b = Traffic(m, c, 2**31 + 5), Traffic(m, c, 2**31 + 5)
    for i in (0, 1, 7, 4100):
        sa, sb = a.submission(i), b.submission(i)
        assert sa.name == sb.name and sa.client == sb.client
        np.testing.assert_array_equal(sa.det_boxes, sb.det_boxes)
        np.testing.assert_array_equal(sa.det_mask, sb.det_mask)
    other = Traffic(m, c, 2**31 + 6).submission(0)
    assert not np.array_equal(other.det_boxes, a.submission(0).det_boxes)


@pytest.mark.parametrize("mix_name,config_name", [
    ("mot15-archive", "mot15-sort"),
    ("kitti-archive", "kitti-mc"),
    ("mot15-segments", "mot15-sort"),
])
def test_stated_shapes(mix_name, config_name):
    m, c = small(mix(mix_name)), config(config_name)
    t = Traffic(m, c, 11)
    lengths = sorted(s["frames"] for s in m["shapes"])
    d = c["engine"]["max_detections"]
    e = c["engine"]["cost"]["embed_dim"]
    seen = []
    for i in range(len(t.pool)):
        s = t.submission(i)
        seen.append(s.frames)
        assert s.det_boxes.shape[1:] == (s.det_mask.shape[1], 4)
        assert s.det_mask.shape[1] <= d
        assert s.det_boxes.dtype == np.float32 and s.det_mask.dtype == bool
        if c["engine"]["num_classes"] > 1:
            assert s.det_class.shape == s.det_mask.shape
            assert set(np.unique(s.det_class[s.det_mask])) <= {0, 1, 2}
            assert s.det_embed.shape == s.det_mask.shape + (e,)
        else:
            assert s.det_class is None and s.det_embed is None
    # one pass over the pool submits every shape once, in a seeded order
    assert sorted(seen) == lengths


def test_every_seed_same_sizes_other_order():
    m, c = small(mix("mot15-segments"), keep=6), config("mot15-sort")
    a, b = Traffic(m, c, 1), Traffic(m, c, 2)
    la = [a.submission(i).frames for i in range(6)]
    lb = [b.submission(i).frames for i in range(6)]
    assert sorted(la) == sorted(lb)


def test_shift_moves_every_box_alike():
    m = dict(small(mix("mot15-archive")), shift_px=32.0)
    t = Traffic(m, config("mot15-sort"), 3)
    s = t.submission(5)
    name, (db, dm, _, _) = t.pool[t._pool_index(5)]
    d = (s.det_boxes - db)[dm]
    assert np.allclose(d, d[0]) and np.abs(d[0]).max() <= 32.0
    assert d[0][0] == d[0][2] and d[0][1] == d[0][3]


def test_arrivals_rate_and_seeded_order():
    a = Arrivals(500.0, 9)
    times = np.array([a.time(i) for i in range(4096)])
    assert np.all(np.diff(times) > 0)
    # the 4096 gaps are the exponential's quantiles: mean exactly 1/rate
    assert times[-1] == pytest.approx(4096 / 500.0, rel=1e-3)
    b = Arrivals(500.0, 10)
    gaps_a = np.diff(np.concatenate([[0.0], times]))
    gaps_b = np.diff(np.concatenate([[0.0], [b.time(i) for i in range(4096)]]))
    assert not np.allclose(gaps_a, gaps_b)
    np.testing.assert_allclose(np.sort(gaps_a), np.sort(gaps_b))


def test_residual_first_fill():
    """The first lane-width holds tails of the mix's shapes: each shape in
    lanes in proportion to its length, oldest admission first; the same
    sizes for every seed; the whole sequences follow."""
    m = dict(small(mix("mot15-archive"), keep=3), first_fill="residual")
    lengths = sorted(s["frames"] for s in m["shapes"])
    lanes = 64
    a = Traffic(m, config("mot15-sort"), 2**31 + 17, lanes=lanes)
    b = Traffic(m, config("mot15-sort"), 5, lanes=lanes)
    served = [start for _, start in a.fill]
    assert len(a.fill) == lanes and served == sorted(served, reverse=True)
    fa = sorted(a.frames(i) for i in range(lanes))
    assert fa == sorted(b.frames(i) for i in range(lanes))
    assert min(fa) >= 1 and max(fa) <= max(lengths)
    full = {}
    for p, start in a.fill:
        full.setdefault(a.pool[p][1][0].shape[0], []).append(start)
    counts = [len(full[n]) for n in lengths]
    want = np.array(lengths) * lanes / sum(lengths)
    assert np.all(np.abs(np.array(counts) - want) < 1)
    s = a.submission(3)
    p, start = a.fill[3]
    assert s.frames == a.frames(3) == a.pool[p][1][0].shape[0] - start
    np.testing.assert_array_equal(s.det_mask, a.pool[p][1][1][start:])
    assert sorted(a.frames(i) for i in range(lanes, lanes + 3)) == lengths
