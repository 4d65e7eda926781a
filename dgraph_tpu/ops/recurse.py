"""One visit-once @recurse hop for the whole-query fused program.

Reference parity: `query/recurse.go` (expandRecurse) — the north-star
workload. The reference's outer loop (re-seed SubGraph, re-run ProcessGraph
per depth) becomes a `lax.scan` of this hop inside one compiled program
(engine/fused.py), so an entire depth-k traversal has zero host
round-trips: each hop is gather → filter → seen-set subtraction →
sort-unique, all fused.

TPU design note: the seen set is a dense int8 bitmap over rank space, not a
sorted list — membership is one vectorised gather instead of the
log2(n)-round binary search a sorted-set difference
(`uidalgebra.difference_sorted`) costs on TPU (measured ~50× slower).

The multi-device form of the hop (shard_map + collectives) is
`parallel/dhop.py chain_hop`.
"""

from __future__ import annotations

import jax.numpy as jnp

from dgraph_tpu.ops.hop import gather_edges
from dgraph_tpu.ops.uidalgebra import _member, sentinel, sort_unique_count


def masked_hop(indptr, indices, frontier, allowed, seen_mask,
               edge_cap: int, out_cap: int, use_allowed: bool):
    """One visit-once @recurse hop with the filter fused into the gather
    mask — the per-hop body of the whole-query fused program
    (engine/fused.py). It keeps the per-hop edge matrix (parents render)
    and the filter's allowed-set membership test, so a filtered
    `@recurse` block compiles into one program instead of per-hop
    expand → filter → subtract host passes.

    `frontier` is sorted sentinel-padded; `seen_mask` is the dense int8
    visited bitmap over rank space (ops/recurse.py design note).
    Returns `(nbrs[edge_cap], seg[edge_cap], n_kept, nxt[out_cap],
    n_unique, seen_mask, total)`: kept edges compacted to the front in
    CSR row order (the host loop's `nbrs[keep]` order), the deduped
    fresh frontier, the updated bitmap, and the raw gathered edge count
    (`total > edge_cap` ⇒ re-run bigger; `n_unique > out_cap` ⇒ same)."""
    n_nodes = indptr.shape[0] - 1
    nbrs, seg, _pos, valid, total = gather_edges(
        indptr, indices, frontier, edge_cap)
    keep = valid
    if use_allowed:
        keep = keep & _member(nbrs, allowed)
    visited = jnp.take(seen_mask, jnp.clip(nbrs, 0, n_nodes - 1),
                       mode="clip") > 0
    keep = keep & ~visited
    snt = sentinel(nbrs.dtype)
    m_nbrs = jnp.where(keep, nbrs, snt)
    m_seg = jnp.where(keep, seg, jnp.int32(2**31 - 1))
    # compact kept edges to the front preserving CSR row order (kept
    # slot keys are unique, so the argsort is deterministic)
    slot_key = jnp.where(keep, jnp.arange(edge_cap, dtype=jnp.int32),
                         jnp.int32(edge_cap))
    order = jnp.argsort(slot_key)
    n_kept = jnp.sum(keep.astype(jnp.int32))
    nxt, n_unique = sort_unique_count(m_nbrs, out_cap)
    # sentinel padding >= n_nodes: mode="drop" discards it
    seen_mask = seen_mask.at[nxt].set(jnp.int8(1), mode="drop")
    return (m_nbrs[order], m_seg[order], n_kept, nxt, n_unique,
            seen_mask, total)
