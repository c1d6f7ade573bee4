"""Plain reference of a SortingLSH + Stars build, and the comparison that
decides ``correct``.

The reference imports nothing of the program.  It follows the build's
published semantics (arXiv:2212.02635, Stars 2) and the random protocol
that the configuration's seed fixes, one repetition ``r`` at a time:

* sketch: M SimHash bits ``x . z > 0``, with ``z`` ~ N(0, I) drawn from
  ``fold_in(key(0), r ^ seed)``, at the configuration's sketch precision
  (the chip's default matmul precision: bfloat16 inputs);
* sort: points in lexicographic order of their bits, then a random 20-bit
  tiebreak, then id; cut into windows of W after a random first block of
  r' ~ U[W/2, W] (``k_tie``, ``k_shift`` of ``split(fold_in(key(seed), r))``);
* leaders: the s slots of largest uniform priority in each window
  (``k_lead``), ties to the lower slot; a leader is compared with every
  other point of its window;
* fold: each node keeps the k heaviest distinct neighbours over all
  repetitions, by (cosine desc, id asc).

Only the rows of a sample are rebuilt: for each sampled node, the union of
its candidates over the repetitions, weighed in float64 on the host, and
cut to its top k.  ``compare`` sets the program's slab rows beside them.
``control_rows`` is the control: the same rows weighed at the next
precision below the configuration's float32-at-HIGHEST scoring, three
bfloat16 passes (``Precision.HIGH``), emulated in float32.
"""

from __future__ import annotations

import functools
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from bench.roofline import n_windows

TIE_MASK = 0xFFFFF000          # the sort tiebreak keeps its top 20 bits


@functools.partial(jax.jit, static_argnames=("seed", "m", "window", "nw"))
def _rep_draws(x, rep, *, seed: int, m: int, window: int, nw: int):
    n, d = x.shape
    rep_seed = jnp.asarray(rep, jnp.uint32) ^ jnp.uint32(seed)
    kz = jax.random.fold_in(jax.random.key(0), rep_seed.astype(jnp.int32))
    z = jax.random.normal(kz, (d, m), x.dtype)
    bits = jnp.dot(x, z) > 0
    shifts = jnp.arange(m - 1, -1, -1, dtype=jnp.uint32)
    code = jnp.sum(bits.astype(jnp.uint32) << shifts, axis=1,
                   dtype=jnp.uint32)
    k_tie, k_shift, k_lead = jax.random.split(
        jax.random.fold_in(jax.random.key(seed), rep), 3)
    tie = jax.random.bits(k_tie, (n,), jnp.uint32) & jnp.uint32(TIE_MASK)
    first = jax.random.randint(k_shift, (), window // 2, window + 1)
    pri = jax.random.uniform(k_lead, (nw, window))
    return code, tie, first, pri


def candidates(x: jax.Array, config: dict, seed: int, reps: int,
               rows: np.ndarray) -> List[np.ndarray]:
    """Sorted distinct candidate ids of each of ``rows`` over repetitions
    0 .. reps-1 (the node itself excluded)."""
    n = x.shape[0]
    w, s = config["window"], config["leaders"]
    nw = n_windows(n, w)
    lanes = np.arange(w)
    found: List[List[np.ndarray]] = [[] for _ in rows]
    for rep in range(reps):
        code, tie, first, pri = jax.device_get(_rep_draws(
            x, jnp.int32(rep), seed=seed, m=config["m"], window=w, nw=nw))
        order = np.lexsort((np.arange(n), tie, code))   # last key sorts first
        pos = np.empty(n, np.int64)
        pos[order] = np.arange(n)
        offset = w - int(first)
        slot = offset + pos[rows]
        win = slot // w
        at = (win * w - offset)[:, None] + lanes[None, :]   # sorted positions
        valid = (at >= 0) & (at < n)
        member = np.where(valid, order[np.clip(at, 0, n - 1)], -1)
        prio = np.where(valid, pri[win], -1.0)
        lead = np.argsort(-prio, axis=1, kind="stable")[:, :s]
        lead_ok = np.take_along_axis(prio, lead, axis=1) >= 0
        is_leader = ((lead == (slot - win * w)[:, None]) & lead_ok).any(1)
        for i in range(len(rows)):
            found[i].append(member[i, lead[i, lead_ok[i]]])
            if is_leader[i]:
                found[i].append(member[i, valid[i]])
    return [np.setdiff1d(np.concatenate(f), [r]) for r, f in zip(rows, found)]


def _bf16(a: np.ndarray) -> np.ndarray:
    """float32 rounded to the nearest bfloat16 (ties to even), as float32."""
    b = np.ascontiguousarray(a, np.float32).view(np.uint32)
    b = (b + np.uint32(0x7FFF) + ((b >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return b.view(np.float32)


class Weigher:
    """Cosines of the rows of ``xh`` named in ``ids``: in float64, and as
    a TPU computes a float32 dot at ``Precision.HIGH`` (unit rows in
    float32, then three bfloat16 products hi.hi + hi.lo + lo.hi summed in
    float32)."""

    def __init__(self, xh: np.ndarray, ids: np.ndarray):
        self.ids = ids
        x = xh[ids]
        x64 = x.astype(np.float64)
        self.u64 = x64 / np.linalg.norm(x64, axis=1, keepdims=True)
        x32 = x.astype(np.float32)
        u32 = x32 / np.sqrt((x32 * x32).sum(-1, keepdims=True)
                            + np.float32(1e-12))
        self.hi = _bf16(u32)
        self.lo = _bf16(u32 - self.hi)

    def __call__(self, i: int, ids: np.ndarray):
        a, b = np.searchsorted(self.ids, i), np.searchsorted(self.ids, ids)
        exact = self.u64[b] @ self.u64[a]
        hi, lo = self.hi[b], self.lo[b]
        high = hi @ self.hi[a] + (hi @ self.lo[a] + lo @ self.hi[a])
        return exact, high.astype(np.float64)


def top_k(ids: np.ndarray, w: np.ndarray, k: int) -> Tuple[np.ndarray,
                                                           np.ndarray]:
    """The k heaviest of ``ids`` by (weight desc, id asc)."""
    keep = np.lexsort((ids, -w))[:k]
    return ids[keep], w[keep]


def compare(prog_nbr: np.ndarray, prog_w: np.ndarray,
            cands: Sequence[np.ndarray], ref_w: Sequence[np.ndarray],
            high_w: Sequence[np.ndarray], k: int,
            tie_tol: float) -> Dict[str, float]:
    """The program's slab rows against the reference's.

    ``rows_wrong`` counts rows whose neighbour set differs from the
    reference's top k, a duplicate or a non-candidate included, except
    where every id in the difference is a candidate whose reference weight
    lies within ``tie_tol`` of the reference's k-th weight (a near-tie at
    the cap, which rounding may order either way).  ``weight_gap`` is the
    largest |program weight - reference weight| over the program's
    entries that are candidates.  ``high_share`` is
    the share of the three-pass rounding error (``high_w`` - ``ref_w``)
    that the program's weights carry: the least-squares slope of
    (program - reference) on it, about 0 for float32 at HIGHEST and 1 at
    ``Precision.HIGH`` or below."""
    wrong, gap, entries, cov, var = 0, 0.0, 0, 0.0, 0.0
    for nbr, pw, ids, w, hw in zip(prog_nbr, prog_w, cands, ref_w, high_w):
        live = nbr >= 0
        nbr, pw = nbr[live], pw[live]
        entries += nbr.size
        at = np.minimum(np.searchsorted(ids, nbr), max(ids.size - 1, 0))
        known = ids[at] == nbr if ids.size else np.zeros(nbr.shape, bool)
        if known.any():
            err = pw[known] - w[at[known]]
            gap = max(gap, float(np.max(np.abs(err))))
            high = hw[at[known]] - w[at[known]]
            cov += float(np.sum(err * high))
            var += float(np.sum(high ** 2))
        top, top_w = top_k(ids, w, k)
        diff = np.setxor1d(nbr, top)
        if np.unique(nbr).size != nbr.size or not known.all():
            wrong += 1
        elif diff.size:
            cut = top_w[-1] if ids.size > k else None
            near = cut is not None and bool(np.all(
                np.abs(w[np.searchsorted(ids, diff)] - cut) <= tie_tol))
            wrong += not near
    return {"rows_wrong": wrong, "weight_gap": gap,
            "high_share": cov / var if var > 0 else 0.0,
            "entries": entries}


def reference_rows(x: jax.Array, config: dict, seed: int, reps: int,
                   rows: np.ndarray):
    """Candidate ids of each sampled row, and their cosines in float64 and
    at ``Precision.HIGH``."""
    cands = candidates(x, config, seed, reps, rows)
    xh = np.asarray(jax.device_get(x))
    weigh = Weigher(xh, np.unique(np.concatenate([rows, *cands])))
    exact, high = zip(*(weigh(r, c) for r, c in zip(rows, cands)))
    return cands, list(exact), list(high)


def control_rows(cands: Sequence[np.ndarray], high_w: Sequence[np.ndarray],
                 k: int):
    """The control's slab rows: each row's candidates weighed at
    ``Precision.HIGH`` and cut to the top k, as (n_rows, k) arrays padded
    with -1 / -inf like the program's slabs."""
    nbr = np.full((len(cands), k), -1, np.int64)
    w = np.full((len(cands), k), -np.inf, np.float64)
    for j, (c, hw) in enumerate(zip(cands, high_w)):
        ids, cw = top_k(c, hw, k)
        nbr[j, :ids.size], w[j, :ids.size] = ids, cw
    return nbr, w
