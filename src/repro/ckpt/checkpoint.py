"""Sharded checkpointing with atomic commit, async writes, elastic restore.

Layout (one directory per step)::

    <dir>/step_000123/
        manifest.json      tree structure, shapes, dtypes, logical specs
        shard_<host>.npz   this host's param/opt leaves (flattened paths)
    <dir>/LATEST           committed step pointer (written last — atomicity)

Fault-tolerance contract (DESIGN.md §11):

* a checkpoint is visible only after ``LATEST`` is atomically renamed in —
  a host dying mid-write never corrupts the restore point;
* a stale ``LATEST`` (its step directory deleted or incomplete) never
  strands a restore: ``restore``/``restore_flat`` fall back to the newest
  *committed* step — a directory whose ``manifest.json`` exists;
* ``restore`` takes an *optional* mesh: leaves are re-sharded from the
  logical specs recorded at save time, so a job restarted on a different
  topology (e.g. one pod lost, 2x16x16 -> 16x16) resumes without
  conversion — elastic restart; requested leaf paths are validated
  against the manifest first, so a topology mismatch raises a
  ``ValueError`` naming the missing/extra paths instead of a bare
  ``KeyError``;
* ``CheckpointManager`` writes in a background thread (training never
  blocks on disk) and keeps the newest ``keep`` checkpoints.  A failed
  background write is **never silent**: the exception is recorded and
  re-raised on the next ``wait()``/``save_async()`` call.  Temp dirs
  leaked by a writer killed between ``mkdtemp`` and ``os.replace`` are
  swept once they go stale.
"""
from __future__ import annotations

import json
import os
import shutil
import tempfile
import threading
import time

import jax
import numpy as np


def flatten_with_paths(tree):
    """``(keys, leaves, treedef)`` with the exact "/"-joined path strings
    ``save``/``restore`` name leaves by — public so callers serializing
    data-dependent trees (the serving checkpoint, DESIGN.md §11) can
    address leaves consistently."""
    flat, treedef = jax.tree.flatten_with_path(tree)
    keys = ["/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path) for path, _ in flat]
    return keys, [leaf for _, leaf in flat], treedef


_flatten = flatten_with_paths


def save(ckpt_dir: str, step: int, tree, logical_specs=None,
         host_id: int = 0):
    """Write one checkpoint synchronously. Safe against partial writes."""
    step_dir = os.path.join(ckpt_dir, f"step_{step:09d}")
    tmp = tempfile.mkdtemp(prefix=".tmp_ckpt_", dir=_ensure(ckpt_dir))
    try:
        keys, leaves, _ = _flatten(tree)
        arrays = {k: np.asarray(l) for k, l in zip(keys, leaves)}
        np.savez(os.path.join(tmp, f"shard_{host_id:05d}.npz"), **arrays)
        manifest = {
            "step": step,
            "keys": keys,
            "shapes": [list(np.shape(a)) for a in arrays.values()],
            "dtypes": [str(a.dtype) for a in arrays.values()],
            "specs": _specs_json(logical_specs, tree),
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as fh:
            json.dump(manifest, fh)
        if os.path.exists(step_dir):
            shutil.rmtree(step_dir)
        os.replace(tmp, step_dir)
        _commit_latest(ckpt_dir, step)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    return step_dir


def latest_step(ckpt_dir: str) -> int | None:
    p = os.path.join(ckpt_dir, "LATEST")
    if not os.path.exists(p):
        return None
    with open(p) as fh:
        return int(fh.read().strip())


def _step_dir(ckpt_dir: str, step: int) -> str:
    return os.path.join(ckpt_dir, f"step_{step:09d}")


def committed_steps(ckpt_dir: str) -> list[int]:
    """Every step with a *complete* directory (``manifest.json`` present),
    ascending.  ``LATEST`` is the commit pointer, but a crash can leave it
    stale (its target GC'd or never finished) — this is ground truth."""
    try:
        names = os.listdir(ckpt_dir)
    except FileNotFoundError:
        return []
    steps = []
    for d in names:
        if not d.startswith("step_"):
            continue
        try:
            s = int(d.split("_", 1)[1])
        except ValueError:
            continue
        if os.path.exists(os.path.join(ckpt_dir, d, "manifest.json")):
            steps.append(s)
    return sorted(steps)


def _resolve_step(ckpt_dir: str, step: int | None) -> int:
    """An explicit ``step`` is trusted; ``None`` resolves to ``LATEST`` if
    its directory is complete, else to the newest committed step (a killed
    writer must always land the restore on the last *committed* step)."""
    if step is not None:
        return step
    step = latest_step(ckpt_dir)
    if step is not None and os.path.exists(
            os.path.join(_step_dir(ckpt_dir, step), "manifest.json")):
        return step
    committed = committed_steps(ckpt_dir)
    if not committed:
        raise FileNotFoundError(f"no committed checkpoint in {ckpt_dir}")
    return committed[-1]


def _load_manifest(step_dir: str) -> dict:
    with open(os.path.join(step_dir, "manifest.json")) as fh:
        return json.load(fh)


def _validate_keys(step_dir: str, requested: list[str]) -> None:
    """Requested leaf paths must all exist in the shard — checked against
    ``manifest.json`` up front so an elastic-restart topology mismatch
    raises a diagnosable ``ValueError`` naming the offending paths, not a
    bare ``KeyError`` from the npz lookup."""
    stored = set(_load_manifest(step_dir)["keys"])
    missing = [k for k in requested if k not in stored]
    if missing:
        extra = sorted(stored - set(requested))
        raise ValueError(
            f"checkpoint {step_dir} does not match the requested tree: "
            f"missing leaf path(s) {missing}; checkpoint-only path(s) "
            f"{extra}.  (restoring onto a different tree topology than "
            f"was saved?)")


def restore_flat(ckpt_dir: str, step: int | None = None,
                 host_id: int = 0) -> tuple[dict, int]:
    """Every stored leaf of one committed checkpoint as a flat
    ``{path: np.ndarray}`` dict, plus the resolved step.

    For callers whose tree *structure* is data-dependent and therefore
    unknowable before the load (the serving checkpoint's per-sequence
    buffers, DESIGN.md §11) — the manifest, not a ``tree_like``, defines
    what comes back.  Leaves are materialized host copies; ml_dtypes
    stored as raw void are NOT re-viewed (callers with such leaves should
    use :func:`restore`).
    """
    step = _resolve_step(ckpt_dir, step)
    path = os.path.join(_step_dir(ckpt_dir, step), f"shard_{host_id:05d}.npz")
    with np.load(path) as data:
        return {k: np.array(data[k]) for k in data.files}, step


def restore(ckpt_dir: str, tree_like, step: int | None = None,
            mesh=None, pspecs=None, host_id: int = 0):
    """Load a checkpoint into the structure of ``tree_like``.

    With ``mesh``+``pspecs``, leaves are placed as NamedSharding arrays for
    the *current* topology (elastic restart); otherwise plain host arrays.
    """
    step = _resolve_step(ckpt_dir, step)
    step_dir = _step_dir(ckpt_dir, step)
    keys, leaves, treedef = _flatten(tree_like)
    _validate_keys(step_dir, keys)
    data = np.load(os.path.join(step_dir, f"shard_{host_id:05d}.npz"))
    out = []
    flat_specs = None
    if pspecs is not None:
        flat_specs = treedef.flatten_up_to(pspecs)
    for i, (k, like) in enumerate(zip(keys, leaves)):
        arr = data[k]
        assert tuple(arr.shape) == tuple(np.shape(like)), \
            f"shape mismatch for {k}: {arr.shape} vs {np.shape(like)}"
        want = np.dtype(getattr(like, "dtype", arr.dtype))
        if arr.dtype != want and arr.dtype.itemsize == want.itemsize:
            # npz stores ml_dtypes (bfloat16, fp8) as raw void — re-view
            arr = arr.view(want)
        if mesh is not None and flat_specs is not None:
            from jax.sharding import NamedSharding
            arr = jax.device_put(arr, NamedSharding(mesh, flat_specs[i]))
        out.append(arr)
    return jax.tree.unflatten(treedef, out), step


class CheckpointManager:
    """Async background writer + retention policy.

    Failure contract: the background thread records any exception from
    ``save()`` and the next ``wait()``/``save_async()`` **re-raises it** —
    a failed write (disk full, permissions, ...) is never mistaken for a
    committed checkpoint.  ``wait()`` must therefore be called before
    trusting that a ``save_async`` landed (e.g. before shutdown).
    """

    def __init__(self, ckpt_dir: str, keep: int = 3, logical_specs=None,
                 stale_tmp_age: float = 3600.0):
        if keep < 1:
            raise ValueError(f"keep must be >= 1, got {keep} (keep=0 would "
                             f"GC every checkpoint the moment it commits)")
        self.ckpt_dir = _ensure(ckpt_dir)
        self.keep = keep
        self.logical_specs = logical_specs
        self.stale_tmp_age = stale_tmp_age
        self._thread: threading.Thread | None = None
        self._error: BaseException | None = None
        # a writer killed between mkdtemp and os.replace leaks its temp
        # dir forever (atomic commit never renames it in, and step-dir GC
        # only matches step_*); sweep leftovers from previous incarnations
        # now, and stale ones on every _gc.
        _sweep_stale_tmp(self.ckpt_dir, self.stale_tmp_age)

    def save_async(self, step: int, tree):
        self.wait()  # one in-flight write at a time; raises a prior failure
        host_tree = jax.tree.map(np.asarray, jax.device_get(tree))
        self._thread = threading.Thread(
            target=self._write, args=(step, host_tree), daemon=True)
        self._thread.start()

    def _write(self, step, host_tree):
        try:
            save(self.ckpt_dir, step, host_tree, self.logical_specs)
            self._gc()
        except BaseException as e:  # surfaced by the next wait()/save_async()
            self._error = e

    def wait(self):
        """Join the in-flight write; re-raise its failure, if any."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def _gc(self):
        steps = []
        for d in os.listdir(self.ckpt_dir):
            if not d.startswith("step_"):
                continue
            try:
                steps.append(int(d.split("_", 1)[1]))
            except ValueError:
                continue  # foreign step_* name: not ours to delete or crash on
        for s in sorted(steps)[:-self.keep]:
            shutil.rmtree(os.path.join(self.ckpt_dir, f"step_{s:09d}"),
                          ignore_errors=True)
        _sweep_stale_tmp(self.ckpt_dir, self.stale_tmp_age)


def _sweep_stale_tmp(ckpt_dir: str, max_age: float) -> None:
    """Remove ``.tmp_ckpt_*`` dirs older than ``max_age`` seconds — debris
    of writers killed mid-write.  The age guard keeps a *live* concurrent
    writer's temp dir (same or another process) safe from the sweep."""
    now = time.time()
    try:
        names = os.listdir(ckpt_dir)
    except FileNotFoundError:
        return
    for d in names:
        if not d.startswith(".tmp_ckpt_"):
            continue
        p = os.path.join(ckpt_dir, d)
        try:
            age = now - os.path.getmtime(p)
        except OSError:
            continue  # raced with its own writer's os.replace — it's live
        if age >= max_age:
            shutil.rmtree(p, ignore_errors=True)


def _commit_latest(ckpt_dir, step):
    tmp = os.path.join(ckpt_dir, ".LATEST.tmp")
    with open(tmp, "w") as fh:
        fh.write(str(step))
    os.replace(tmp, os.path.join(ckpt_dir, "LATEST"))


def _ensure(d):
    os.makedirs(d, exist_ok=True)
    return d


def _specs_json(logical_specs, tree):
    """Recursively JSON-encode the logical-spec tree (tuples of axis names)."""
    if logical_specs is None:
        return None

    def enc(node):
        if isinstance(node, tuple):
            return [str(a) if a is not None else None for a in node]
        if isinstance(node, dict):
            return {k: enc(v) for k, v in node.items()}
        if isinstance(node, (list,)):
            return [enc(v) for v in node]
        return None

    return enc(logical_specs)
