"""The comparison that decides ``correct``: its control fails, and a run
with the timed path broken underneath comes out not correct."""
from __future__ import annotations

import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from bench import check, kernel_bytes
from bench.tests.tiny import BENCH, CpuChip, tiny_root
from bench.traffic.generator import Traffic


def load(kind: str, name: str) -> dict:
    return json.loads((BENCH / kind / f"{name}.json").read_text())


@pytest.mark.parametrize("config_name,mix_name", [
    ("mot15-sort", "mot15-archive"),
    ("kitti-mc", "kitti-archive"),
])
def test_bf16_control_fails_the_limits(config_name, mix_name):
    """The reference with its state in bfloat16, put in the program's
    place, reads over the configuration's limits; the reference against
    itself reads 0."""
    config = load("configs", config_name)
    mix = load("traffic", mix_name)
    mix = dict(mix, variants=1,
               shapes=sorted(mix["shapes"], key=lambda s: s["frames"])[:3])
    traffic = Traffic(mix, config, 2**31 + 77)
    limits = config["limits"]
    worst, bad = 0.0, 0
    for i in range(3):
        sub = traffic.submission(i)
        want = check.reference_frames(config, sub)
        assert check.compare(want, want)[1:] == (0, 0.0)
        _, b, w = check.compare(check.reference_frames(config, sub, "bf16"),
                                want)
        worst, bad = max(worst, w), bad + b
    assert worst > limits["box_err_px"] or bad > limits["id_mismatch_frames"]


def test_reference_capacity():
    """With ``max_trackers`` places, a detection left unmatched when all
    are taken is not born and takes no id; a place freed by a track that
    dies is taken by the next birth, in the same frame."""
    from bench.reference import Sort
    box = lambda x: [x, 0.0, x + 10.0, 10.0]
    three = np.array([box(0), box(100), box(200)])
    ref = Sort(max_age=1, min_hits=0, max_trackers=2)
    assert sorted(r[4] for r in ref.update(three)) == [1, 2]
    assert sorted(r[4] for r in ref.update(three)) == [1, 2]
    assert ref.update(np.array([box(200)])) == []   # both miss, 1 place
    assert sorted(r[4] for r in ref.update(np.array([box(300)]))) == [3]
    free = Sort(max_age=1, min_hits=0)
    assert sorted(r[4] for r in free.update(three)) == [1, 2, 3]


def test_round_bf16():
    from bench.reference import round_bf16
    x = np.array([1.0, 1.00390625, 1.0078125, 1000.3, -3.14159])
    got = round_bf16(x)
    assert got[0] == 1.0 and got[2] == 1.0078125
    assert got[1] in (1.0, 1.0078125)          # a tie, to even
    assert abs(got[3] - 1000.0) <= 2.0 and abs(got[4] + 3.140625) < 1e-9


SEED = 3000000019


def _run_tiny(tmp_path, capsys, loop="closed_loop", classes=1):
    from bench import run
    root = tiny_root(tmp_path, loop=loop, classes=classes)
    rc = run.main(["--workload", "tiny", "--seed", str(SEED),
                   "--seconds", "2", "--trace", "0"], root=root,
                  chip=CpuChip())
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1])


def _fault(name):
    """A chunk program broken underneath the scheduler."""
    from repro.core.sort import SortEngine
    real = SortEngine.run_chunk_ragged

    def broken(self, state, det, dm, active, reset, **kw):
        if name == "half_the_lanes_left_out":
            half = active.shape[1] // 2
            active = active.at[:, half:].set(False)
        new_state, out = real(self, state, det, dm, active, reset, **kw)
        if name == "state_returned_unchanged":
            return state, out
        if name == "id_altered":
            return new_state, out._replace(uid=out.uid + 1)
        if name == "box_altered":
            return new_state, out._replace(boxes=out.boxes + 1.0)
        return new_state, out
    return broken


@pytest.mark.parametrize("fault", [None, "state_returned_unchanged",
                                   "half_the_lanes_left_out", "id_altered",
                                   "box_altered"])
def test_broken_timed_path_is_not_correct(fault, tmp_path, capsys,
                                          monkeypatch):
    if fault is not None:
        from repro.core.sort import SortEngine
        monkeypatch.setattr(SortEngine, "run_chunk_ragged", _fault(fault))
    result = _run_tiny(tmp_path, capsys)
    assert result["correct"] is (fault is None), result["checks"]
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("loop", ["closed_loop", "open_loop"])
def test_control_in_place_is_not_correct(loop, tmp_path, capsys):
    """The bfloat16 reference, put where the program's results are
    produced, comes out not correct through the whole run."""
    from bench import control
    config = load("configs", "mot15-sort")
    with control.in_place(config, SEED):
        result = _run_tiny(tmp_path, capsys, loop=loop)
    assert result["correct"] is False, result["checks"]
    assert result["checks"]["checked_seqs"][0] >= 1
    box, _, limit = result["checks"]["box_err_px"]
    assert box > limit


def test_open_loop_multiclass_run(tmp_path, capsys):
    result = _run_tiny(tmp_path, capsys, loop="open_loop", classes=3)
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["checks"]["id_mismatch_frames"][0] == 0
    box, _, limit = result["checks"]["box_err_px"]
    assert box <= limit
    assert set(result["metrics"]) == {"latency_p50_s", "latency_p95_s",
                                      "setup_s"}


def _no_result(cwd: Path) -> None:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "mot15-backlog", "--seed", "2147483659",
                        "--seconds", "1", "--trace", "0"], cwd=cwd, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert not any(line.startswith("{") for line in p.stdout.splitlines())


def test_no_result_off_the_chip():
    _no_result(BENCH.parent)


def test_no_result_with_only_the_benchmark(tmp_path):
    import shutil
    shutil.copy(BENCH.parent / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    _no_result(tmp_path)


def test_fused_chunk_bytes():
    # 2,048 lanes, 32 frames, T = D = 16, Hungarian assignment operand
    s, t, f = 2048, 16, 32
    state = (7 * t + 49 * t + 7 * t + 2) * s
    per_frame = (16 * 4 + 16 + 2 + t + (t * 4 + 4 * t + 16)) * s
    want = 4 * (2 * state + f * per_frame)
    assert kernel_bytes.fused_chunk_per_chip(load("configs", "mot15-sort")) \
        == want
    kitti = kernel_bytes.fused_chunk_per_chip(load("configs", "kitti-mc"))
    extra = 4 * (2 * 8 * t * s + f * (16 + 16 * 8) * s)
    assert kitti == want + extra
