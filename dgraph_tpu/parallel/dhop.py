"""Distributed hop kernels: one query level as one SPMD program on the mesh.

Reference parity: `worker/task.go ProcessTaskOverNetwork` — scatter the
frontier to the groups owning each tablet over gRPC, each Alpha walks its
posting lists, gather `pb.Result`s and k-way merge (`algo.MergeSorted`).
Here the scatter/gather is XLA collectives over ICI inside a single jitted
`shard_map` program. Every hop returns the edge matrix the engine renders
from (engine/execute.py, engine/recurse.py):

  matrix hop / level  — frontier replicated; each device expands the rows
      it owns (the level form also filters and paginates them); outputs
      stay sharded.

  ring matrix hop     — frontier *sharded* (too big to replicate, the
      long-context case of SURVEY §5); chunks rotate around the mesh via
      `ppermute` while every device expands the resident chunk against its
      local rows. D steps, each overlapping compute with a neighbour
      exchange — the structural cousin of ring attention.

  chain hop           — one visit-once @recurse hop whose replicated
      (frontier, seen) outputs are the next launch's inputs; `all_gather`
      + fused sort-unique merge the next frontier on every device.

Edge totals are `psum`/`pmax`-reduced — the north-star edges-traversed/sec
counter falls out of the kernel itself.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from dgraph_tpu.ops.hop import gather_edges
from dgraph_tpu.ops.uidalgebra import (
    _member, sentinel, sort_unique_count, valid_mask)
from dgraph_tpu.utils.jaxcompat import shard_map
from dgraph_tpu.parallel.mesh import SHARD_AXIS, hop_input
from dgraph_tpu.parallel.pshard import ShardedRel


def _local_expand_full(indptr, indices, row_lo, frontier, edge_cap):
    """Expand the slice of a (global-rank) frontier this shard owns.
    Returns the full gather_edges tuple; `seg` indexes the GLOBAL
    frontier (rows not owned by this shard simply contribute no edges)."""
    n_rows = indptr.shape[0] - 1
    mine = (valid_mask(frontier) & (frontier >= row_lo)
            & (frontier < row_lo + n_rows))
    local_f = jnp.where(mine, frontier - row_lo, sentinel(frontier.dtype))
    return gather_edges(indptr, indices, local_f, edge_cap)


@functools.lru_cache(maxsize=64)
def _build_matrix_hop(mesh: Mesh, edge_cap: int):
    def per_device(indptr_b, indices_b, row_lo_b, frontier):
        nbrs, seg, edge_pos, valid, total = _local_expand_full(
            indptr_b[0], indices_b[0], row_lo_b[0], frontier, edge_cap)
        max_shard = lax.pmax(total, SHARD_AXIS)
        return (nbrs[None], seg[None], edge_pos[None], total[None],
                max_shard)

    fn = shard_map(
        per_device, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                   P(SHARD_AXIS), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def matrix_hop(mesh: Mesh, rel: ShardedRel, frontier: jax.Array,
               edge_cap: int):
    """One hop that RETURNS the edge matrix, not just the merged frontier —
    the seam the query engine needs (reference: pb.Result.UidMatrix from
    ProcessTaskOverNetwork). Frontier is replicated; each device expands
    the rows it owns; outputs stay sharded:

      (nbrs[D, edge_cap], seg[D, edge_cap], edge_pos[D, edge_cap],
       totals[D], max_shard_edges)

    Per shard d, the first totals[d] slots are that shard's edges in CSR
    row order; `seg` indexes the GLOBAL frontier (each row is owned by
    exactly one shard, so a host stable-sort by seg rebuilds global row
    order); `edge_pos` is local — add rel.pos_lo[d] for the absolute
    position facet columns key on. Valid only if max_shard_edges ≤
    edge_cap; otherwise re-run at a bigger bucket."""
    return _build_matrix_hop(mesh, edge_cap)(
        rel.indptr_s, rel.indices_s, rel.row_lo,
        hop_input(frontier, mesh))


@functools.lru_cache(maxsize=64)
def _build_matrix_level(mesh: Mesh, edge_cap: int, use_allowed: bool):
    from dgraph_tpu.ops.level import filter_paginate

    def per_device(indptr_b, indices_b, row_lo_b, frontier, allowed,
                   offset, first):
        nbrs, seg, edge_pos, valid, total = _local_expand_full(
            indptr_b[0], indices_b[0], row_lo_b[0], frontier, edge_cap)
        # rows partition over shards, so per-row filter+pagination is
        # shard-local; `allowed` is replicated (it is an index lookup set,
        # small next to the edge set)
        c_nbrs, c_seg, c_pos, n_kept, _ = filter_paginate(
            nbrs, seg, edge_pos, valid, allowed, offset, first,
            frontier.shape[0], use_allowed)
        max_shard = lax.pmax(total, SHARD_AXIS)
        return (c_nbrs[None], c_seg[None], c_pos[None], n_kept[None],
                total[None], max_shard)

    fn = shard_map(
        per_device, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P(), P(),
                  P(), P()),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                   P(SHARD_AXIS), P(SHARD_AXIS), P()),
        check_vma=False,
    )
    return jax.jit(fn, static_argnames=())


def matrix_level(mesh: Mesh, rel: ShardedRel, frontier: jax.Array,
                 allowed: jax.Array, offset, first, edge_cap: int,
                 use_allowed: bool):
    """The fused level (expand → filter → paginate → compact) as ONE SPMD
    program — matrix_hop and ops.level.expand_level combined, so the served
    mesh engine gets the same fused fast path as the single-device one
    (reference: ProcessTaskOverNetwork with the filter/pagination pushed
    into each group's processTask rather than applied at the coordinator).

    Returns (nbrs[D, edge_cap], seg[D, edge_cap], pos[D, edge_cap],
    kept[D], totals[D], max_shard_edges): per shard d the first kept[d]
    slots are its surviving edges in CSR row order; seg indexes the GLOBAL
    frontier; pos is local (add rel.pos_lo[d]). Valid only if
    max_shard_edges ≤ edge_cap."""
    return _build_matrix_level(mesh, edge_cap, use_allowed)(
        rel.indptr_s, rel.indices_s, rel.row_lo, frontier, allowed,
        jnp.int32(offset), jnp.int32(first))


@functools.lru_cache(maxsize=64)
def _build_ring_matrix(mesh: Mesh, edge_cap: int, f_cap: int):
    n_dev = mesh.devices.size
    perm = [(i, (i + 1) % n_dev) for i in range(n_dev)]

    def per_device(indptr_b, indices_b, row_lo_b, chunk_b):
        indptr, indices, row_lo = indptr_b[0], indices_b[0], row_lo_b[0]
        chunk = chunk_b[0]

        def step(i, carry):
            chunk, nbrs_a, seg_a, pos_a, tot_a, max_e = carry
            nbrs, seg, pos, valid, t = _local_expand_full(
                indptr, indices, row_lo, chunk, edge_cap)
            nbrs_a = lax.dynamic_update_index_in_dim(nbrs_a, nbrs, i, 0)
            seg_a = lax.dynamic_update_index_in_dim(seg_a, seg, i, 0)
            pos_a = lax.dynamic_update_index_in_dim(pos_a, pos, i, 0)
            tot_a = lax.dynamic_update_index_in_dim(tot_a, t, i, 0)
            chunk = lax.ppermute(chunk, SHARD_AXIS, perm)
            return (chunk, nbrs_a, seg_a, pos_a, tot_a,
                    jnp.maximum(max_e, t))

        z = jnp.zeros
        _, nbrs_a, seg_a, pos_a, tot_a, max_e = lax.fori_loop(
            0, n_dev, step,
            (chunk, z((n_dev, edge_cap), jnp.int32),
             z((n_dev, edge_cap), jnp.int32),
             z((n_dev, edge_cap), jnp.int32),
             z((n_dev,), jnp.int32), jnp.int32(0)))
        max_all = lax.pmax(max_e, SHARD_AXIS)
        return (nbrs_a[None], seg_a[None], pos_a[None], tot_a[None],
                max_all)

    fn = shard_map(
        per_device, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                  P(SHARD_AXIS)),
        out_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS),
                   P(SHARD_AXIS), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def ring_matrix_hop(mesh: Mesh, rel: ShardedRel, frontier_chunks,
                    edge_cap: int):
    """One hop with a SHARDED frontier that RETURNS the edge matrix — the
    long-context analog wired for the query engine (SURVEY §5): the
    frontier is too big to replicate, so chunks rotate ring-wise over ICI
    (ppermute) while every device expands the resident chunk against its
    local rows.

    Returns (nbrs[D, D, edge_cap], seg[D, D, edge_cap],
    pos[D, D, edge_cap], totals[D, D], max_step_edges). For shard d at
    ring step i the expanded chunk ORIGINATED on shard (d - i) mod D;
    `seg` indexes within that chunk; valid only if max_step_edges ≤
    edge_cap."""
    f_cap = frontier_chunks.shape[1]
    return _build_ring_matrix(mesh, edge_cap, f_cap)(
        rel.indptr_s, rel.indices_s, rel.row_lo,
        jax.device_put(frontier_chunks))


@functools.lru_cache(maxsize=64)
def _build_chain_hop(mesh: Mesh, edge_cap: int, out_cap: int,
                     seen_cap: int):
    """ONE visit-once hop with edge-matrix capture, compiled so its
    replicated (frontier, seen) outputs are EXACTLY the next launch's
    replicated inputs — the reshard-free multi-hop building block. One
    compiled program serves every depth, and between launches the
    frontier/seen arrays stay device-resident: the host reads their
    VALUES for rendering but feeds the same jax.Arrays back in, so no
    bytes re-cross the mesh (mesh.hop_input counts any violation)."""

    def per_device(indptr_b, indices_b, row_lo_b, frontier, seen):
        indptr, indices, row_lo = indptr_b[0], indices_b[0], row_lo_b[0]
        n_rows = indptr.shape[0] - 1
        snt = sentinel(frontier.dtype)
        mine = (valid_mask(frontier) & (frontier >= row_lo)
                & (frontier < row_lo + n_rows))
        local_f = jnp.where(mine, frontier - row_lo, snt)
        nbrs, seg, _pos, valid, t = gather_edges(
            indptr, indices, local_f, edge_cap)
        # visit-once: drop edges to nodes seen BEFORE this hop (edges
        # between two same-hop discoveries are kept — the host loop's
        # first-visit-tree semantics)
        keep = valid & ~_member(nbrs, seen)
        m_nbrs = jnp.where(keep, nbrs, snt)
        m_seg = jnp.where(keep, seg, jnp.int32(-1))
        local, local_cnt = sort_unique_count(m_nbrs, out_cap)
        gathered = lax.all_gather(local, SHARD_AXIS)
        fresh, mcnt = sort_unique_count(gathered.reshape(-1), out_cap)
        seen2, scnt = sort_unique_count(
            jnp.concatenate([seen, fresh]), seen_cap)
        needs = jnp.stack([
            jnp.maximum(mcnt, lax.pmax(local_cnt, SHARD_AXIS)),
            scnt, lax.pmax(t, SHARD_AXIS)])
        totals = lax.psum(
            jnp.where(keep, 1, 0).sum().astype(jnp.int32), SHARD_AXIS)
        return (fresh, seen2, lax.psum(t, SHARD_AXIS), needs,
                m_nbrs[None], m_seg[None], t[None], totals)

    fn = shard_map(
        per_device, mesh=mesh,
        in_specs=(P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P(), P()),
        out_specs=(P(), P(), P(), P(),
                   P(SHARD_AXIS), P(SHARD_AXIS), P(SHARD_AXIS), P()),
        check_vma=False,
    )
    return jax.jit(fn)


def chain_hop(mesh: Mesh, rel: ShardedRel, frontier, seen,
              edge_cap: int, out_cap: int, seen_cap: int):
    """One launch of the chained visit-once hop (see _build_chain_hop).

    `frontier`/`seen` are sorted sentinel-padded buffers of exactly
    `out_cap`/`seen_cap` slots — host numpy on the first hop (the seed
    upload), then the previous launch's DEVICE outputs unmoved. Returns
    `(fresh[out_cap], seen2[seen_cap], edges, needs[3],
    nbrs[D, edge_cap], seg[D, edge_cap], shard_edges[D], kept)`:
    `fresh`/`seen2` are the next launch's inputs; `seg` indexes this
    hop's input frontier; per shard d the slots with nbrs != sentinel
    are its surviving (visit-once filtered) edges in CSR row order;
    `shard_edges[d]` is the raw edges shard d expanded (the balance /
    per-shard cost signal). `needs` = [max frontier slots, seen slots,
    max per-shard edge slots] the hop required: results are valid only
    if needs <= [out_cap, seen_cap, edge_cap] elementwise; otherwise
    re-run with the caps `needs` asks for."""
    return _build_chain_hop(mesh, edge_cap, out_cap, seen_cap)(
        rel.indptr_s, rel.indices_s, rel.row_lo,
        hop_input(frontier, mesh), hop_input(seen, mesh))
