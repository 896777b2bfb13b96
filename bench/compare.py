"""The comparison that decides `correct` for a training cell.

The program's first steps, as the window's own loop ran them, against
the plain reference's (`bench/reference.py`) on the same seed:

- batch_mismatch: token and label positions of the first steps' batches
  that differ from the reference's (exact: limit 0);
- loss_gap: the largest |loss - reference loss| over the first steps;
- grad_norm_gap: the largest relative gap of the gradient's global norm
  (before clipping) over the first steps;
- first_grad_gap: over the leaves whose gradient is exact (all but the
  sketched linears'), the largest gap between the norm of the first
  gradient as AdamW got it (before its clipping: the first moment after
  one step, over (1 - b1) and over the clipping factor of the step's
  own global norm) and the reference's, relative to the reference's
  norm of that leaf or of the median leaf, whichever is larger;
- first_grad_diff: over the same exact leaves, the largest norm of the
  difference between the program's and the reference's first gradient
  (before clipping), taken over the elements at `sample_index`,
  relative to the reference's norm of that leaf's sample or of the
  median leaf's, whichever is larger. The gaps of norms above average
  a change in precision out; this number does not;
- sketched_grad_gap (sketch cells): the median of the first gradient's
  gap of norms over the sketched linears, whose gradient comes from the
  reconstructed activation;
- change_gap: the worst leaf's gap, as above, of each leaf's change over
  the first steps, leaving out leaves whose reference gradient is below
  a thousandth of the median leaf's (they move by round-off alone under
  Adam).

Each number a cell compares has its limit in `bench/limits/<cell>.json`.
"""
from __future__ import annotations

import math

import numpy as np

QUIET_LEAF = 1e-3
SAMPLE = 1 << 16          # elements per leaf and layer of first_grad_diff
SAMPLE_STRIDE = 7919


def sample_index(size: int) -> np.ndarray:
    """The flat positions of a leaf (one layer of it) that
    first_grad_diff compares: all of a small leaf, else SAMPLE positions
    spread over it by a prime stride."""
    if size <= SAMPLE:
        return np.arange(size, dtype=np.int32)
    if math.gcd(size, SAMPLE_STRIDE) != 1:
        raise ValueError(f"a leaf of {size} elements shares a factor with "
                         f"the sampling stride {SAMPLE_STRIDE}")
    return (np.arange(SAMPLE, dtype=np.int64) * SAMPLE_STRIDE
            % size).astype(np.int32)


def leaf_gaps(got: dict, want: dict, names) -> dict:
    """{leaf: |got - want| / max(want, median of want over `names`)}."""
    med = float(np.median([want[n] for n in names]))
    out = {}
    for n in names:
        g = got.get(n, math.nan)
        out[n] = abs(g - want[n]) / max(want[n], med, 1e-30) \
            if math.isfinite(g) else math.inf
    return out


def _worst(gaps: dict) -> float:
    return max(gaps.values()) if gaps else 0.0


def unclipped(first: dict, grad_norm: float, grad_clip: float) -> dict:
    """Leaf norms or elements of the first gradient before global-norm
    clipping, from those after it and the gradient's global norm."""
    if grad_clip <= 0:
        return dict(first)
    scale = min(1.0, grad_clip / max(grad_norm, 1e-12))
    return {n: v / scale for n, v in first.items()}


def sample_diffs(got: dict, want: dict, names) -> dict:
    """{leaf: ||got - want|| / max(||want||, median leaf's ||want||)}
    over each leaf's sampled elements."""
    norms = {n: float(np.linalg.norm(want[n])) for n in names}
    med = float(np.median(list(norms.values())))
    out = {}
    for n in names:
        g = got.get(n)
        if g is None or g.shape != want[n].shape or \
                not np.all(np.isfinite(g)):
            out[n] = math.inf
            continue
        out[n] = float(np.linalg.norm(g - want[n])) / \
            max(norms[n], med, 1e-30)
    return out


def readings(prog: dict, ref: dict, sketched=(),
             grad_clip: float = 0.0) -> dict:
    """The compared numbers, and every leaf's gap under "where".
    `sketched` names the leaves whose gradient the sketch reconstructs;
    `grad_clip` is AdamW's global-norm clip, undone on both sides."""
    steps = len(ref["loss"])
    mism = sum(int(np.sum(a != b)) for pb, rb in zip(prog["batches"],
                                                     ref["batches"])
               for a, b in zip(pb, rb))
    if len(prog["batches"]) != len(ref["batches"]):
        mism = math.inf
    fin = lambda x: x if math.isfinite(x) else math.inf
    lg = fin(max(abs(prog["loss"][s] - ref["loss"][s])
                 for s in range(steps)))
    gg = fin(max(abs(prog["grad_norm"][s] - ref["grad_norm"][s])
                 / ref["grad_norm"][s] for s in range(steps)))
    first_p = unclipped(prog["first_grad"], prog["grad_norm"][0], grad_clip)
    first_r = unclipped(ref["first_grad"], ref["grad_norm"][0], grad_clip)
    names = list(first_r)
    med = float(np.median(list(first_r.values())))
    quiet = {n for n in names if first_r[n] < QUIET_LEAF * med}
    fg = leaf_gaps(first_p, first_r, names)
    exact = [n for n in names if n not in sketched]
    fd = sample_diffs(
        unclipped(prog["first_sample"], prog["grad_norm"][0], grad_clip),
        unclipped(ref["first_sample"], ref["grad_norm"][0], grad_clip),
        exact)
    cg = leaf_gaps(prog["change"], ref["change"],
                   [n for n in names if n not in quiet])
    missing = set(names) ^ set(prog["first_grad"]) or \
        set(names) ^ set(prog["change"])
    out = {"batch_mismatch": mism, "loss_gap": lg, "grad_norm_gap": gg,
           "first_grad_gap": _worst({n: v for n, v in fg.items()
                                     if n not in sketched}),
           "first_grad_diff": _worst(fd),
           "change_gap": _worst(cg),
           "where": {"first_grad": fg, "first_diff": fd, "change": cg,
                     "quiet_leaves": sorted(quiet)}}
    if sketched:
        out["sketched_grad_gap"] = float(np.median(
            [fg[n] for n in sketched]))
    if missing:
        out["first_grad_gap"] = out["change_gap"] = math.inf
        out["first_grad_diff"] = math.inf
    return out


def cell_readings(traffic: dict, prog: dict, ref: dict) -> dict:
    """`readings` with what the cell's traffic says: which leaves the
    sketch reconstructs (the MLP linears, where it sketches) and the
    clip of the global gradient norm."""
    sketched = {n for n in ref["first_grad"] if "/mlp/" in n} \
        if traffic["sketch"] else set()
    return readings(prog, ref, sketched,
                    grad_clip=traffic["optimizer"]["grad_clip"])


def judge(read: dict, limits: dict) -> tuple[bool, dict]:
    """(correct, {number: {"value", "limit"}}) over the numbers the
    limits name: correct when every one is at or under its limit."""
    checks = {n: {"value": read[n], "limit": lim}
              for n, lim in limits.items()}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
