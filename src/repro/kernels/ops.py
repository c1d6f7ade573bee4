"""Public jit'd wrappers for the Pallas kernels.

Dispatch policy: on TPU backends the Pallas kernels lower natively and are
the default; on CPU the default is the pure-jnp reference (``ref.py``), and
``use_pallas=True`` runs a kernel under ``interpret=True`` (the kernel
tests).  ``use_pallas`` overrides the choice; on TPU both choices run
natively, which is how the chip compares each kernel with its oracle
(``chip_smoke.py``).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels import flash_attention as _fa
from repro.kernels import leader_score as _ls
from repro.kernels import ref as _ref
from repro.kernels import simhash as _sh
from repro.kernels import topk_merge as _tm
from repro.kernels import window_score as _ws


def pallas_by_default() -> bool:
    """True when the kernels lower natively (the Pallas TPU path).

    Callers preparing kernel-specific side inputs key off this rather than
    re-deriving the backend themselves: e.g. the edge accumulator only
    builds the presorted companion view (``topk_merge``'s
    ``inc_presorted``) for the jnp reference path — the Pallas kernel
    dedups in VMEM and never reads it.  Also valid inside ``shard_map``
    bodies (the mesh emit path): the default backend is a process-level
    property, not a per-shard one.
    """
    return jax.default_backend() == "tpu"


def _pick(use_pallas: Optional[bool]) -> tuple[bool, bool]:
    """Returns (use_pallas, interpret)."""
    native = pallas_by_default()
    if use_pallas is None:
        use_pallas = native
    return use_pallas, not native


@functools.partial(jax.jit, static_argnames=("use_pallas",))
def simhash_packed(x: jax.Array, proj: jax.Array, *,
                   use_pallas: Optional[bool] = None) -> jax.Array:
    use, interp = _pick(use_pallas)
    if use:
        return _sh.simhash_packed(x, proj, interpret=interp)
    return _ref.simhash_packed_ref(x, proj)


@functools.partial(jax.jit, static_argnames=("normalized", "use_pallas"))
def leader_score(leaders, members, leader_ok, member_ok, *,
                 normalized: bool = True,
                 use_pallas: Optional[bool] = None) -> jax.Array:
    use, interp = _pick(use_pallas)
    if use:
        return _ls.leader_score(leaders, members, leader_ok, member_ok,
                                normalized=normalized, interpret=interp)
    return _ref.leader_score_ref(leaders, members, leader_ok, member_ok,
                                 normalized=normalized)


@functools.partial(jax.jit, static_argnames=(
    "normalized", "allpairs", "match_bucket", "new_from", "refresh_below",
    "r1", "use_pallas"))
def window_score(leaders, members, leader_slot, lead_gid, gid, leader_ok,
                 member_ok, lead_bucket, bucket, keep, *,
                 normalized: bool = True, allpairs: bool = False,
                 match_bucket: bool = False, new_from: int = 0,
                 refresh_below: int = 0, r1: Optional[float] = None,
                 use_pallas: Optional[bool] = None):
    """Fused Stars window scoring (similarities + emit mask + counters).

    The whole per-window pipeline of ``core/stars._score_windows`` in one
    op — see ``ref.window_score_ref`` for the shape/mask contract.  The
    Pallas kernel (``kernels/window_score.py``) shares the reference's
    normalization and HIGHEST-precision contraction: discrete outputs are
    equal and similarities agree to ~1 ulp.
    """
    use, interp = _pick(use_pallas)
    if use:
        return _ws.window_score(
            leaders, members, leader_slot, lead_gid, gid, leader_ok,
            member_ok, lead_bucket, bucket, keep, normalized=normalized,
            allpairs=allpairs, match_bucket=match_bucket, new_from=new_from,
            refresh_below=refresh_below, r1=r1, interpret=interp)
    return _ref.window_score_ref(
        leaders, members, leader_slot, lead_gid, gid, leader_ok, member_ok,
        lead_bucket, bucket, keep, normalized=normalized, allpairs=allpairs,
        match_bucket=match_bucket, new_from=new_from,
        refresh_below=refresh_below, r1=r1)


@functools.partial(jax.jit, static_argnames=("use_pallas", "sorted_inputs"))
def topk_merge(slab_nbr, slab_w, inc_nbr, inc_w, *,
               use_pallas: Optional[bool] = None,
               sorted_inputs: bool = False,
               inc_presorted=None):
    """Per-node top-k degree-slab merge (the edge-accumulator update).

    The Pallas kernel sorts each staged row of K = k + kin lanes (padded
    to a power of two) with two bitonic sorting networks in VMEM, by
    neighbour to drop duplicates and then by weight to rank: O(K log^2 K)
    compare-exchanges on static lane shifts (kernels/topk_merge.py).
    ``sorted_inputs=True`` asserts the accumulator-traffic preconditions
    (rows weight-sorted descending, per-row deduped, -1/-inf tails) and
    routes the CPU path to the merge-path formulation instead of the full
    re-sort — see ``ref.topk_merge_sorted_ref``; ``inc_presorted`` (the
    batch's nbr-ascending companion view produced by the accumulator's
    bucketing stage) additionally removes the merge's dedup sort.  The
    Pallas kernel is order-insensitive, so the TPU path is unchanged.
    """
    use, interp = _pick(use_pallas)
    if use:
        return _tm.topk_merge(slab_nbr, slab_w, inc_nbr, inc_w,
                              interpret=interp)
    if sorted_inputs:
        return _ref.topk_merge_sorted_ref(slab_nbr, slab_w, inc_nbr, inc_w,
                                          inc_presorted)
    return _ref.topk_merge_ref(slab_nbr, slab_w, inc_nbr, inc_w)


@functools.partial(jax.jit, static_argnames=("causal", "window", "scale",
                                             "use_pallas"))
def attention(q, k, v, *, causal: bool = True,
              window: Optional[int] = None, scale: Optional[float] = None,
              use_pallas: Optional[bool] = None) -> jax.Array:
    use, interp = _pick(use_pallas)
    if use:
        return _fa.flash_attention(q, k, v, causal=causal, window=window,
                                   scale=scale, interpret=interp)
    return _ref.mha_ref(q, k, v, causal=causal, window=window, scale=scale)
