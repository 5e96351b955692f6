"""The comparison that decides ``correct``: readings of the program's first
three training steps against the plain reference, each gap beside its limit.

The numbers, of which a cell's limits file names those it compares:

- ``loss_gap``: the largest relative gap of the three steps' mean losses.
- ``grad_gap``: the worst leaf's gap between the norms of the program's and
  the reference's first gradient (the program's is read back from its velocity
  after one step: v1 = -lr * g1, since v0 = 0), over the larger of the
  reference norm of that leaf and of the median leaf.
- ``update_gap``: the same for the parameters' change over the three steps,
  leaving out leaves whose reference gradient is under a thousandth of the
  median leaf's (they move by round-off alone).
- ``grad_gap_median``, ``update_gap_median``: the median leaf's gap of the
  two, steady from seed to seed where one leaf's gap swings.

A leaf is one array of the parameter tree; a stacked segment of layers gives
one leaf per layer. Every gap is taken per worker, and the worst worker counts.
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp

NUMBERS = ("loss_gap", "grad_gap", "update_gap", "grad_gap_median",
           "update_gap_median")
FLAT_LEAF = 1e-3     # leaves with a reference gradient under this share of
#                      the median leaf's are left out of update_gap


def leaf_names(tree) -> list:
    """Names of the leaves :func:`leaf_norms` reports, in its order."""
    names = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        name = jax.tree_util.keystr(path)
        if name.startswith("['segments']"):
            names += [f"{name}[{j}]" for j in range(x.shape[0])]
        else:
            names.append(name)
    return names


def leaf_norms(tree, lead: int = 0):
    """L2 norm of every leaf (one per layer in a stacked segment), in float32:
    ``[n_leaves]``, or ``[n_leaves, W]`` for trees with a leading worker axis
    (``lead=1``)."""
    rows = []
    for path, x in jax.tree_util.tree_flatten_with_path(tree)[0]:
        x = x.astype(jnp.float32)
        split = jax.tree_util.keystr(path).startswith("['segments']")
        axes = tuple(range(lead + (1 if split else 0), x.ndim))
        sq = jnp.sqrt(jnp.sum(x * x, axis=axes))
        if split:   # [W, L] or [L] -> one row per layer
            sq = jnp.moveaxis(sq, -1, 0)
            rows += [sq[j] for j in range(sq.shape[0])]
        else:
            rows.append(sq)
    return jnp.stack(rows)


def norm_gap(prog, ref):
    """Per worker: |prog - ref| over max(ref, median leaf of ref); ``prog`` and
    ``ref`` are ``[n_leaves, W]``. Returns the ``[n_leaves, W]`` gaps."""
    prog, ref = np.asarray(prog, np.float64), np.asarray(ref, np.float64)
    floor = np.median(ref, axis=0, keepdims=True)
    return np.abs(prog - ref) / np.maximum(ref, floor)


def gaps(prog: dict, ref: dict) -> dict:
    """``prog`` and ``ref`` hold ``losses`` [steps], ``grad`` and ``change``
    ([n_leaves, W] norms). Returns {number: value}; NaN reads as a failure."""
    lp, lr = np.asarray(prog["losses"], np.float64), np.asarray(ref["losses"], np.float64)
    g_ref = np.asarray(ref["grad"], np.float64)
    moving = g_ref >= FLAT_LEAF * np.median(g_ref, axis=0, keepdims=True)
    grad = norm_gap(prog["grad"], g_ref)
    upd = norm_gap(prog["change"], ref["change"])
    upd_median = [np.median(upd[moving[:, w], w]) for w in range(upd.shape[1])]
    out = {
        "loss_gap": float(np.max(np.abs(lp - lr) / np.abs(lr))),
        "grad_gap": float(np.max(grad)),
        "update_gap": float(np.max(np.where(moving, upd, 0.0))),
        "grad_gap_median": float(np.max(np.median(grad, axis=0))),
        "update_gap_median": float(np.max(upd_median)),
    }
    return {k: (v if np.isfinite(v) else float("nan")) for k, v in out.items()}


def worst_leaves(prog: dict, ref: dict, names: list, n: int = 3) -> dict:
    """The ``n`` leaves with the largest gradient and change gaps, as
    [name, worker, gap], for the look behind a number."""
    out = {}
    for key, gap in (("grad", norm_gap(prog["grad"], ref["grad"])),
                     ("change", norm_gap(prog["change"], ref["change"]))):
        flat = np.argsort(gap, axis=None)[::-1][:n]
        out[key] = [[names[i], int(w), float(gap[i, w])]
                    for i, w in zip(*np.unravel_index(flat, gap.shape))]
    return out


def verdict(values: dict, limits: dict) -> tuple:
    """(correct, {number: {"value", "limit"}}): correct when every number the
    cell's limits name is finite and within its limit. A number without a
    limit is not compared (nothing the calibration planted separated it)."""
    checks = {k: {"value": values[k], "limit": limits[k]}
              for k in NUMBERS if k in limits}
    ok = all(np.isfinite(c["value"]) and c["value"] <= c["limit"]
             for c in checks.values())
    return bool(ok), checks
