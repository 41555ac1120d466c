"""Hungarian algorithm (linear sum assignment) in pure JAX ``lax`` control flow.

The paper uses the Hungarian method in matrix form to match Kalman
predictions to detections.  The cost matrices are tiny (<= ~13x13, paper
Table I), so the right TPU strategy is the one the paper argues for threads:
never split one matrix — batch *many* matrices and solve them in parallel
lanes.  This module is written so the full solver ``vmap``s over a leading
batch axis with static shapes.

Algorithm: shortest-augmenting-path / Jonker-Volgenant variant, O(n^3), the
same scheme scipy's ``linear_sum_assignment`` uses, expressed with
``lax.fori_loop`` (rows) + ``lax.while_loop`` (Dijkstra + augmentation).

Masked / rectangular problems are handled by padding to a fixed ``n x n``
matrix with a large constant ``PAD``: because every pad entry has the *same*
cost, the optimum on the valid ``D x T`` submatrix is preserved and the
number of real-real matches is maximized (PAD dominates any real cost range).
Validated against scipy in ``tests/test_hungarian.py``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

_INF = 1.0e18


def auto_pad_value(cost: jnp.ndarray, valid: jnp.ndarray, n: int) -> jnp.ndarray:
    """Pad cost that (a) always loses to any real match and (b) stays inside
    float32 precision of the real cost range.

    A fixed huge constant (1e6) breaks in float32: reduced costs mix the pad
    scale with the real scale and the real costs quantize away.  Instead use
    ``cmax + n * (cmax - cmin) + 1`` per problem: swapping one real match for
    a pad match then always increases the total, so the solver still
    maximizes the number of real-real matches.
    """
    big = jnp.where(valid, cost, -_INF)
    small = jnp.where(valid, cost, _INF)
    cmax = jnp.maximum(big.max(axis=(-2, -1)), 0.0)
    cmin = jnp.minimum(small.min(axis=(-2, -1)), 0.0)
    return cmax + n * (cmax - cmin) + 1.0


def pad_cost_matrix(cost: jnp.ndarray, row_mask: jnp.ndarray, col_mask: jnp.ndarray,
                    n: int, pad_value=None, pair_mask=None) -> jnp.ndarray:
    """Embed a masked ``[..., R, C]`` cost into an ``[..., n, n]`` padded square
    matrix.  ``pad_value=None`` selects the precision-safe adaptive pad.

    ``pair_mask [..., R, C]`` (optional) marks individual pairs infeasible
    on top of the row/col masks — the hook for class partitioning and the
    Mahalanobis gate (DESIGN.md §10).  Infeasible pairs take the same pad
    value as masked rows/cols, so the solver maximizes the number of
    *feasible* matches; with a class-equality mask the feasible pairs
    decompose into disjoint per-class blocks, making one padded solve
    exactly equivalent to solving each class's sub-problem separately
    (block-diagonal matching in a single lane-batched call).
    """
    r, c = cost.shape[-2], cost.shape[-1]
    assert n >= r and n >= c, (n, r, c)
    valid = row_mask[..., :, None] & col_mask[..., None, :]
    if pair_mask is not None:
        valid = valid & pair_mask
    if pad_value is None:
        pad_value = auto_pad_value(cost, valid, n)
    pad_value = jnp.asarray(pad_value, cost.dtype)[..., None, None]
    out = jnp.broadcast_to(pad_value, cost.shape[:-2] + (n, n)).copy()
    block = jnp.where(valid, cost, pad_value)
    return out.at[..., :r, :c].set(block)


def _pick(a: jnp.ndarray, hot: jnp.ndarray) -> jnp.ndarray:
    """``a[k]`` along axis 0, for ``hot = arange(n) == k``: a select and a
    max-reduction, exact since one entry survives.  The solver indexes by
    data only through this and ``where`` on ``hot``: under ``vmap`` a
    dynamic index becomes a gather or scatter over the whole lane batch,
    which the TPU executes one index at a time."""
    hot = hot.reshape(hot.shape + (1,) * (a.ndim - hot.ndim))
    low = (-jnp.inf if jnp.issubdtype(a.dtype, jnp.floating)
           else jnp.iinfo(a.dtype).min)
    return jnp.max(jnp.where(hot, a, low), axis=0)


def solve(cost: jnp.ndarray) -> jnp.ndarray:
    """Solve one ``[n, n]`` assignment problem.

    Returns ``col4row [n] int32``: column assigned to each row.  Total cost
    ``cost[arange(n), col4row].sum()`` is minimal.
    """
    n = cost.shape[-1]
    assert cost.shape == (n, n), cost.shape
    cost = cost.astype(jnp.float32)
    idx = jnp.arange(n, dtype=jnp.int32)

    def solve_row(cur_row, carry):
        u, v, col4row, row4col = carry
        # --- Dijkstra over columns to find an augmenting path from cur_row ---
        spc = jnp.full((n,), _INF)       # shortest path cost to each column
        path = jnp.full((n,), -1, jnp.int32)  # predecessor row per column
        sr = jnp.zeros((n,), bool)       # scanned rows
        sc = jnp.zeros((n,), bool)       # scanned cols

        def cond(st):
            _i, _min_val, sink, *_ = st
            return sink < 0

        def body(st):
            i, min_val, sink, spc, path, sr, sc = st
            row = idx == i
            sr = sr | row
            red = min_val + _pick(cost, row) - _pick(u, row) - v
            upd = (~sc) & (red < spc)
            spc = jnp.where(upd, red, spc)
            path = jnp.where(upd, i, path)
            # pick the cheapest unscanned column (ties broken arbitrarily --
            # any minimum keeps Dijkstra invariants and the optimal cost)
            masked = jnp.where(sc, _INF, spc)
            j = jnp.argmin(masked).astype(jnp.int32)
            col = idx == j
            min_val = _pick(spc, col)
            sc = sc | col
            owner = _pick(row4col, col)
            free = owner < 0
            sink = jnp.where(free, j, jnp.int32(-1))
            i = jnp.where(free, i, owner)
            return i, min_val, sink, spc, path, sr, sc

        init = (jnp.int32(cur_row), jnp.float32(0.0), jnp.int32(-1), spc, path, sr, sc)
        _, min_val, sink, spc, path, sr, sc = lax.while_loop(cond, body, init)

        # --- dual updates (scipy rectangular_lsap convention) ---
        u = jnp.where(idx == cur_row, u + min_val, u)
        others = sr & (idx != cur_row)
        col_of_row = idx[:, None] == jnp.clip(col4row, 0, n - 1)  # [col, row]
        u = jnp.where(others, u + min_val - _pick(spc[:, None], col_of_row),
                      u)
        v = jnp.where(sc, v + spc - min_val, v)

        # --- augment along the alternating path back from sink ---
        def aug_cond(st):
            _c4r, _r4c, _j, done = st
            return ~done

        def aug_body(st):
            col4row, row4col, j, _done = st
            i = _pick(path, idx == j)
            row4col = jnp.where(idx == j, i, row4col)
            nxt = _pick(col4row, idx == i)
            col4row = jnp.where(idx == i, j, col4row)
            return col4row, row4col, nxt, i == cur_row

        col4row, row4col, _, _ = lax.while_loop(
            aug_cond, aug_body, (col4row, row4col, sink, jnp.bool_(False)))
        return u, v, col4row, row4col

    u0 = jnp.zeros((n,), jnp.float32)
    v0 = jnp.zeros((n,), jnp.float32)
    c4r0 = jnp.full((n,), -1, jnp.int32)
    r4c0 = jnp.full((n,), -1, jnp.int32)
    _, _, col4row, _ = lax.fori_loop(0, n, solve_row, (u0, v0, c4r0, r4c0))
    return col4row


def solve_batched(cost: jnp.ndarray) -> jnp.ndarray:
    """``[..., n, n] -> [..., n]`` — vmapped over all leading axes."""
    batch = cost.shape[:-2]
    n = cost.shape[-1]
    flat = cost.reshape((-1, n, n))
    out = jax.vmap(solve)(flat)
    return out.reshape(batch + (n,))


def solve_masked(cost: jnp.ndarray, row_mask: jnp.ndarray, col_mask: jnp.ndarray,
                 n: int, pair_mask=None) -> jnp.ndarray:
    """Masked rectangular assignment.

    Returns ``col4row [..., n]`` where entry ``i`` is the assigned column for
    row ``i``, or an arbitrary pad column when row ``i`` is invalid or was
    matched to padding.  Callers must re-validate matches (e.g. by IoU gate);
    SORT does this anyway.  ``pair_mask [..., R, C]`` marks individual
    pairs infeasible (see :func:`pad_cost_matrix`) — an infeasible
    assignment can survive only as a padding match, which the caller's
    gate discards.
    """
    padded = pad_cost_matrix(cost, row_mask, col_mask, n, pair_mask=pair_mask)
    return solve_batched(padded)


def solve_masked_lane(cost: jnp.ndarray, row_mask: jnp.ndarray,
                      col_mask: jnp.ndarray, n: int,
                      pair_mask=None) -> jnp.ndarray:
    """:func:`solve_masked` for the kernels' *lane layout* (DESIGN.md §2):
    the batch lives on the trailing lane axes, the tiny matrix on the
    leading ones — ``cost [R, C, *lanes]``, ``row_mask [R, *lanes]``,
    ``col_mask [C, *lanes]`` -> ``col4row [n, *lanes] int32``.

    This is the standalone lane-level solver API for the fused frame
    step's layout: the ``[D, T, S]`` IoU cost built from the resident
    ``[7, B]`` state solves one tiny problem per lane, never splitting a
    matrix — the paper's batching argument.  Per-lane results are
    bit-identical to :func:`solve_masked` on the transposed batch (the
    same per-problem op sequence, only the batch axis moves;
    ``tests/test_hungarian.py`` locks this down), which is what lets the
    fused-Hungarian engine path (``core.association.associate_lane``, the
    same transpose + the same batch core) match the unfused one exactly.
    """
    r, c = cost.shape[0], cost.shape[1]
    lanes = cost.shape[2:]
    cost_b = jnp.moveaxis(cost.reshape(r, c, -1), -1, 0)       # [L, R, C]
    rm_b = jnp.moveaxis((row_mask > 0).reshape(r, -1), -1, 0)  # [L, R]
    cm_b = jnp.moveaxis((col_mask > 0).reshape(c, -1), -1, 0)  # [L, C]
    pm_b = (None if pair_mask is None
            else jnp.moveaxis(pair_mask.reshape(r, c, -1), -1, 0))
    out = solve_masked(cost_b, rm_b, cm_b, n, pair_mask=pm_b)  # [L, n]
    return jnp.moveaxis(out, 0, -1).reshape((n,) + lanes)
