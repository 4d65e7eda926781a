"""Pallas TPU kernel: blocked ELL pull-hop with an explicit DMA prefetch ring.

Reference parity: this is the hot loop of every traversal — the role
`posting.List.Uids` + `codec` block decoding play per-uid in the
reference (SURVEY §3.1 🔥 marks), batched over lane-packed queries.

Why a hand-written kernel (BASELINE.md headroom note): the XLA form of
the hop (`ops/bfs.py _ell_hop`) is a gather + OR-reduce whose
throughput was builder-reported at ~12% of HBM peak (2026-07-30, not
reproduced on today's code) — random 512-byte row reads are
LATENCY-bound, not bandwidth-bound. XLA's gather bounds its outstanding
reads; this kernel controls the pipeline explicitly: an N_BUF-deep ring
of async row DMAs (HBM → VMEM) stays in flight while the VPU ORs the
rows that already landed, so row latency amortizes across the ring
depth instead of serializing.

Structure per grid step (one block of output rows):
  nbr block  [BR*K] int32    flattened, streamed to SMEM by the pallas
                             pipeline — a DMA's source row is a SCALAR,
                             and Mosaic reads scalars from SMEM, not from
                             a VMEM vector block
  frontier   [n+1, W] uint32 stays in HBM; rows DMA'd on demand
  out block  [BR, W] uint32  accumulated in VMEM, written back once
The flat edge loop issues the DMA for edge t+N_BUF before waiting on
edge t — the "prefetch pipelining" BASELINE.md names as the remaining
headroom. K is static per bucket (EllGraph's degree buckets), so each
bucket compiles its own specialization.

What Mosaic holds the shapes to (established by compiling for a v5e):
  * W must be a multiple of 128 words (one 4096-lane serving row): a
    row DMA moves whole (1, 128) tiles. `ops/bfs.prepare_parts` routes
    narrower masks to the XLA hop.
  * a 1-D int32 SMEM window is tiled by 1024 elements, so BR*K must be
    a multiple of 1024 for every K: BLOCK_ROWS = 1024. The window is
    double-buffered: 2 * 1024 * K * 4 bytes, 256 KiB of the 1 MiB SMEM
    at the widest dense class (K = 32).

Interpret mode is something only a test asks for (`interpret=True`); it
is never picked from the backend's name. Without it a non-TPU backend is an
error — pallas_call's own. chip_smoke.py compiles the kernel for real
at the serving widths and compares it with the XLA hop on every run;
timing it against the XLA hop is a later PR's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bucket_hop_pallas", "pallas_enabled", "BLOCK_ROWS",
           "LANE_WORDS"]

BLOCK_ROWS = 1024    # output rows per grid step (see the SMEM tiling note)
N_BUF = 16           # DMA ring depth (rows in flight)
LANE_WORDS = 128     # mask words per row the compiled kernel moves


def pallas_enabled() -> bool:
    """Opt-in flag: the Pallas hop replaces the XLA gather hop when
    DGRAPH_TPU_PALLAS=1 (kept opt-in until an on-chip A/B says it wins
    by default)."""
    import os
    return os.environ.get("DGRAPH_TPU_PALLAS", "") == "1"


def _make_kernel(K: int, W: int, n_buf: int, block_rows: int):
    # runs once per pallas_call CONSTRUCTION (i.e. per trace of
    # bucket_hop_pallas): counts Mosaic kernel builds per bucket width —
    # the observable that separates "compiling" from "hung" when a long
    # first request goes quiet
    from dgraph_tpu.utils.metrics import METRICS
    METRICS.inc("pallas_kernel_builds_total", k=str(K), w=str(W))
    total = block_rows * K

    def kernel(nbr_ref, frontier_ref, out_ref, rows, sems):
        def dma(t, slot):
            return pltpu.make_async_copy(
                frontier_ref.at[pl.ds(nbr_ref[t], 1), :],
                rows.at[slot], sems.at[slot])

        out_ref[:] = jnp.zeros_like(out_ref)
        # warm the ring (total = BR*K is static, python-level guard)
        for s in range(min(n_buf, total)):
            dma(s, s).start()

        def body(t, _):
            slot = t % n_buf
            dma(t, slot).wait()
            i = t // K
            out_ref[pl.ds(i, 1), :] = out_ref[pl.ds(i, 1), :] | rows[slot]

            @pl.when(t + n_buf < total)
            def _():
                # reuse the slot just freed: the ring stays n_buf deep
                dma(t + n_buf, slot).start()
            return 0

        lax.fori_loop(0, total, body, 0)

    return kernel


@functools.partial(jax.jit,
                   static_argnames=("block_rows", "n_buf", "interpret"))
def bucket_hop_pallas(nbr: jax.Array, frontier: jax.Array,
                      block_rows: int = BLOCK_ROWS,
                      n_buf: int = N_BUF,
                      interpret: bool = False) -> jax.Array:
    """One ELL bucket's pull-hop: out[i] = OR_k frontier[nbr[i, k]].

    `nbr` is [n_b, K] int32 (rows padded with the sentinel row index —
    frontier's last, all-zero row); n_b must be a multiple of
    `block_rows` (ops/bfs.py pads buckets at prepare time). `frontier`
    is [n+1, W] uint32 and never leaves HBM — only the referenced rows
    move, through the DMA ring. `interpret=True` runs the kernel under
    the pallas interpreter (tests); the compiled kernel needs a TPU,
    W % 128 == 0 and block_rows * K % 1024 == 0."""
    n_b, K = nbr.shape
    W = frontier.shape[1]
    assert n_b % block_rows == 0, (n_b, block_rows)
    return pl.pallas_call(
        _make_kernel(K, W, n_buf, block_rows),
        out_shape=jax.ShapeDtypeStruct((n_b, W), jnp.uint32),
        grid=(n_b // block_rows,),
        in_specs=[
            pl.BlockSpec((block_rows * K,), lambda i: (i,),
                         memory_space=pltpu.SMEM),    # DMA source rows
            pl.BlockSpec(memory_space=pl.ANY),        # frontier: HBM
        ],
        out_specs=pl.BlockSpec((block_rows, W), lambda i: (i, 0),
                               memory_space=pltpu.VMEM),
        scratch_shapes=[
            pltpu.VMEM((n_buf, 1, W), jnp.uint32),    # landed rows
            pltpu.SemaphoreType.DMA((n_buf,)),
        ],
        interpret=interpret,
    )(nbr.reshape(-1), frontier)
