"""Pure-jnp oracles for every Pallas kernel in this package.

These are the semantics of record: each kernel's test sweeps shapes/dtypes
and asserts allclose against the functions here.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.distributed.activation_sharding import constrain


def simhash_packed_ref(x: jax.Array, proj: jax.Array) -> jax.Array:
    """sign(x @ proj) bits packed little-endian into uint32 words.

    x: (n, d) float; proj: (d, m) float, m % 32 == 0 -> (n, m//32) uint32.
    """
    bits = (x @ proj) > 0
    n, m = bits.shape
    b = bits.reshape(n, m // 32, 32).astype(jnp.uint32)
    shifts = jnp.arange(32, dtype=jnp.uint32)
    return jnp.sum(b << shifts, axis=-1).astype(jnp.uint32)


def leader_score_ref(leaders: jax.Array, members: jax.Array,
                     leader_ok: jax.Array, member_ok: jax.Array, *,
                     normalized: bool = True) -> jax.Array:
    """Masked leader x member similarity tiles.

    leaders: (nw, s, d); members: (nw, w, d); masks (nw, s) / (nw, w).
    Returns (nw, s, w) float32; masked entries are -inf.
    Cosine when normalized=True (inputs l2-normalized inside), else dot.
    The contraction runs at HIGHEST precision: the TPU default would round
    float32 operands to bfloat16 (see kernels/window_score.py).
    """
    if normalized:
        nrm = lambda t: t / jnp.sqrt(
            jnp.sum(t.astype(jnp.float32) ** 2, -1, keepdims=True) + 1e-12)
        la, mb = nrm(leaders), nrm(members)
    else:
        la, mb = leaders.astype(jnp.float32), members.astype(jnp.float32)
    sims = jnp.einsum("nsd,nwd->nsw", la, mb,
                      precision=jax.lax.Precision.HIGHEST)
    mask = leader_ok[:, :, None] & member_ok[:, None, :]
    return jnp.where(mask, sims, -jnp.inf).astype(jnp.float32)


def window_score_ref(leaders: jax.Array, members: jax.Array,
                     leader_slot: jax.Array, lead_gid: jax.Array,
                     gid: jax.Array, leader_ok: jax.Array,
                     member_ok: jax.Array, lead_bucket: jax.Array,
                     bucket: jax.Array, keep: jax.Array, *,
                     normalized: bool = True, allpairs: bool = False,
                     match_bucket: bool = False, new_from: int = 0,
                     refresh_below: int = 0, r1=None):
    """Fused Stars window scoring: similarity tiles + the full emit mask.

    The oracle for ``kernels/window_score.py`` — one call scores a batch of
    windows end to end: masked leader x member similarities
    (:func:`leader_score_ref` — same normalization, same contraction) plus
    the candidate-emit mask chain of ``core/stars._score_windows`` (self /
    upper-triangle / same-bucket / extension / refresh masks) and the
    per-window comparison counters, so the (nw, s, w) grid needs no second
    pass over features.

    leaders: (nw, s, d); members: (nw, w, d); leader_slot / lead_gid /
    leader_ok / lead_bucket: (nw, s); gid / member_ok / bucket: (nw, w);
    keep: (nw,) bool (the refresh window sample; ignored unless
    ``refresh_below`` > 0).

    Returns ``(sims, emit, comparisons, emitted)``: (nw, s, w) float32
    similarities (-inf outside the validity mask; every emitted entry is
    finite), (nw, s, w) bool emit mask, and per-window int32 counts.
    """
    sims = leader_score_ref(leaders, members, leader_ok, member_ok,
                            normalized=normalized)
    w = members.shape[1]
    slot = jnp.arange(w, dtype=jnp.int32)[None, None, :]
    mask = leader_ok[:, :, None] & member_ok[:, None, :]
    # exclude self-comparison (slot identity, robust to duplicate gids)
    mask &= leader_slot[:, :, None] != slot
    if allpairs:
        # count each unordered pair once: upper triangle
        mask &= leader_slot[:, :, None] < slot
    if match_bucket:
        mask &= lead_bucket[:, :, None] == bucket[:, None, :]
    if new_from > 0:
        nf = jnp.int32(new_from)
        mask &= (lead_gid[:, :, None] >= nf) | (gid[:, None, :] >= nf)
    if refresh_below > 0:
        rb = jnp.int32(refresh_below)
        mask &= keep[:, None, None]
        mask &= (lead_gid[:, :, None] < rb) & (gid[:, None, :] < rb)
    comparisons = jnp.sum(mask, axis=(1, 2), dtype=jnp.int32)
    emit = mask
    if r1 is not None:
        emit &= sims > r1
    emitted = jnp.sum(emit, axis=(1, 2), dtype=jnp.int32)
    return sims, emit, comparisons, emitted


def topk_merge_ref(slab_nbr: jax.Array, slab_w: jax.Array,
                   inc_nbr: jax.Array, inc_w: jax.Array
                   ) -> tuple[jax.Array, jax.Array]:
    """Per-node top-k degree-slab merge (see kernels/topk_merge.py).

    slab_nbr/slab_w: (n, k); inc_nbr/inc_w: (n, kin); -1 / -inf mark empty
    slots.  Per row: dedup by neighbour keeping max weight, then keep the k
    heaviest survivors sorted by (weight desc, nbr asc).

    Two stable row sorts, O(K log K) per row, K = k + kin.  The Pallas
    kernel runs the same two orderings as bitonic sorting networks in VMEM
    (O(K log^2 K) compare-exchanges on static lane shifts, kernels/
    topk_merge.py) and matches this function bit for bit.
    """
    big = jnp.int32(2**31 - 1)
    k = slab_nbr.shape[1]
    nbr = jnp.concatenate([slab_nbr, inc_nbr], axis=1)       # (n, K)
    w = jnp.concatenate([slab_w, inc_w], axis=1).astype(jnp.float32)
    valid = nbr >= 0
    negw = jnp.where(valid, -w, jnp.inf)
    nbr_key = jnp.where(valid, nbr, big)
    # group instances of a neighbour together, heaviest first
    nbr_s, negw_s = jax.lax.sort((nbr_key, negw), num_keys=2, dimension=1)
    first = jnp.concatenate(
        [jnp.ones_like(nbr_s[:, :1], bool), nbr_s[:, 1:] != nbr_s[:, :-1]],
        axis=1)
    keep = first & (nbr_s != big)
    # rank survivors by (w desc, nbr asc); duplicates sort to the tail
    negw2 = jnp.where(keep, negw_s, jnp.inf)
    nbr2 = jnp.where(keep, nbr_s, big)
    negw_f, nbr_f = jax.lax.sort((negw2, nbr2), num_keys=2, dimension=1)
    out_valid = negw_f[:, :k] != jnp.inf
    out_nbr = jnp.where(out_valid, nbr_f[:, :k], -1)
    out_w = jnp.where(out_valid, -negw_f[:, :k], -jnp.inf)
    return out_nbr.astype(jnp.int32), out_w


def topk_merge_sorted_ref(slab_nbr: jax.Array, slab_w: jax.Array,
                          inc_nbr: jax.Array, inc_w: jax.Array,
                          inc_presorted=None) -> tuple[jax.Array, jax.Array]:
    """Merge-path top-k slab merge for accumulator-shaped inputs.

    Preconditions (hold for all accumulator traffic, by construction):
      * every row of both inputs is sorted by weight descending with empty
        slots (nbr < 0, w = -inf) at the tail, finite weights on valid slots,
      * no neighbour appears twice within one row of one input (cross-input
        duplicates are fine — resolved here, max weight wins).

    ``topk_merge_ref`` re-sorts the (n, k+kin) concatenation twice — XLA CPU
    comparator sorts make that the k=250 build bottleneck (ROADMAP).  Here
    each element's output slot is computed directly as

        pos = rank-in-own-row + #other-row-entries-that-beat-it,

    the second term found by binary search in the other row (merge-path),
    so the heavy (n, k+kin) comparator sorts disappear.  Cross-input
    duplicates are found with one narrow (n, kin) sort of the batch by
    neighbour id plus a binary search per slab entry; the lighter instance
    is masked out and positions are corrected by prefix counts of masked
    entries.  Cost: one (n, kin) sort + O((k+kin) log) searches/gathers vs
    two (n, k+kin) multi-key sorts.

    Tie policy: cross-input equal weights between *different* neighbours
    resolve slab-before-batch (the full re-sort resolves them nbr-ascending);
    exact ties are measure-zero for real-valued similarities and either
    order satisfies the top-k contract (see graph/accumulator.py).  Equal
    weight AND equal neighbour is a duplicate: the slab instance survives,
    matching the stable re-sort.

    ``inc_presorted``, when given, is ``(nbr_bn, negw_bn, idx_bn)`` — the
    batch's nbr-ascending companion view (neighbour ids with int32-max on
    empty slots, negated weights with +inf on empty slots, and each slot's
    weight-order index with ``kin`` on empty slots).  The accumulator's
    bucketing stage already visits the batch in neighbour order, so it
    produces this view with a few stream-length scatters (accumulate step
    2b) and even the narrow dedup sort disappears from the merge.
    """
    n, k = slab_nbr.shape
    kin = inc_nbr.shape[1]
    big = jnp.int32(2**31 - 1)
    rows = jnp.arange(n, dtype=jnp.int32)[:, None]

    a_valid = slab_nbr >= 0
    b_valid = inc_nbr >= 0
    a_nbr = jnp.where(a_valid, slab_nbr, -1)
    b_nbr = jnp.where(b_valid, inc_nbr, -1)
    nega = jnp.where(a_valid, -slab_w.astype(jnp.float32), jnp.inf)
    negb = jnp.where(b_valid, -inc_w.astype(jnp.float32), jnp.inf)

    # -- cross-input dedup against the batch's nbr-ascending view (supplied
    #    by the accumulator, else one narrow sort of the batch) --
    if inc_presorted is not None:
        nbr_bn, negw_bn, idx_bn = inc_presorted
    else:
        b_key = jnp.where(b_valid, b_nbr, big)
        iota = jnp.broadcast_to(jnp.arange(kin, dtype=jnp.int32), (n, kin))
        nbr_bn, negw_bn, idx_bn = jax.lax.sort((b_key, negb, iota),
                                               num_keys=2, dimension=1)
    pos = jax.vmap(jnp.searchsorted)(nbr_bn, a_nbr)
    pos_c = jnp.minimum(pos, kin - 1)
    hit = (jnp.take_along_axis(nbr_bn, pos_c, axis=1) == a_nbr) & a_valid
    negw_hit = jnp.take_along_axis(negw_bn, pos_c, axis=1)
    drop_a = hit & (negw_hit < nega)           # batch strictly heavier wins
    loser_b = hit & (negw_hit >= nega)          # ties keep the slab instance
    # mark the losing batch instance at its nbr-order slot, then permute the
    # flags back to the batch's weight order via the sort's carried indices
    drop_b_nbrorder = jnp.zeros((n, kin), bool).at[
        rows, jnp.where(loser_b, pos_c, kin)].set(True, mode="drop")
    drop_b = jnp.zeros((n, kin), bool).at[rows, idx_bn].set(
        drop_b_nbrorder, mode="drop")

    # -- merge-path: output slot = own-row rank + beaten-by count, both
    #    corrected by the prefix count of dedup-dropped entries --
    beats_b = jax.vmap(
        lambda b, a: jnp.searchsorted(b, a, side="left"))(negb, nega)
    beats_a = jax.vmap(
        lambda a, b: jnp.searchsorted(a, b, side="right"))(nega, negb)
    cda = jnp.concatenate(
        [jnp.zeros((n, 1), jnp.int32),
         jnp.cumsum(drop_a, axis=1, dtype=jnp.int32)], axis=1)
    cdb = jnp.concatenate(
        [jnp.zeros((n, 1), jnp.int32),
         jnp.cumsum(drop_b, axis=1, dtype=jnp.int32)], axis=1)
    pos_a = (jnp.arange(k, dtype=jnp.int32)[None, :] - cda[:, :k]
             + beats_b - jnp.take_along_axis(cdb, beats_b, axis=1))
    pos_a = jnp.where(drop_a, k, pos_a)        # k == dropped (scatter-drop)
    pos_b = (jnp.arange(kin, dtype=jnp.int32)[None, :] - cdb[:, :kin]
             + beats_a - jnp.take_along_axis(cda, beats_a, axis=1))
    pos_b = jnp.where(drop_b, k, pos_b)

    out_nbr = jnp.full((n, k), -1, jnp.int32)
    out_nbr = out_nbr.at[rows, pos_a].set(a_nbr, mode="drop")
    out_nbr = out_nbr.at[rows, pos_b].set(b_nbr, mode="drop")
    out_w = jnp.full((n, k), -jnp.inf, jnp.float32)
    out_w = out_w.at[rows, pos_a].set(
        jnp.where(a_valid, slab_w.astype(jnp.float32), -jnp.inf), mode="drop")
    out_w = out_w.at[rows, pos_b].set(
        jnp.where(b_valid, inc_w.astype(jnp.float32), -jnp.inf), mode="drop")
    return out_nbr, out_w


def mha_ref(q: jax.Array, k: jax.Array, v: jax.Array, *,
            causal: bool = True, window: int | None = None,
            scale: float | None = None) -> jax.Array:
    """Grouped-query attention oracle.

    q: (b, hq, sq, d); k, v: (b, hkv, sk, d); hq % hkv == 0.
    window=w keeps key j for query i iff i - w < j (sliding window).
    """
    b, hq, sq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    scale = scale if scale is not None else 1.0 / (d ** 0.5)
    # Expand KV heads to the full query-head count.  GQA-shaped einsums force
    # GSPMD to split the head axis into (hkv, g) sub-dims that rarely divide
    # the TP axis (kv=4, g=8 vs 16): the measured result is head-replicated
    # S^2 score tensors.  Repeating KV keeps one 16-way-shardable head axis;
    # the O(hq*S*d) activation copy is noise next to the O(S^2) scores it
    # de-replicates.  (The Pallas kernel on TPU needs no repeat — its index
    # map reuses KV tiles per group.)
    qf = q.astype(jnp.float32)
    kf = jnp.repeat(k.astype(jnp.float32), g, axis=1)
    vf = jnp.repeat(v.astype(jnp.float32), g, axis=1)
    s = jnp.einsum("bhqd,bhkd->bhqk", qf, kf) * scale
    s = constrain(s, "dp", "tp", None, None)
    sk = kf.shape[2]
    qpos = jnp.arange(sq)[:, None] + (sk - sq)   # right-aligned positions
    kpos = jnp.arange(sk)[None, :]
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= kpos <= qpos
    if window is not None:
        mask &= kpos > qpos - window
    s = jnp.where(mask[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p, vf)
    o = constrain(o, "dp", "tp", None, None)
    return o.astype(q.dtype)
