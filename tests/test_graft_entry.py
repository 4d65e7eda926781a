"""The driver's single-chip compile check, pinned on the CPU.

`__graft_entry__.entry()` is what the driver jits to see that the
flagship program still compiles; PR 7 rewrote ops/bfs.py under it and no
test noticed, because nothing imported the module.
"""

import numpy as np

import jax

import __graft_entry__ as graft


def test_entry_jits_and_counts_exactly():
    fn, args = graft.entry()
    last, seen, edges = jax.jit(fn)(*args)
    jax.block_until_ready(edges)
    assert last.shape == seen.shape == args[0].shape
    # lane 0's counter equals the numpy walk from the same seeds
    rel = graft._demo_graph()
    n = rel.indptr.shape[0] - 1
    seeds = np.random.default_rng(0).integers(0, n, 4)
    _seen, want = graft._bfs_oracle(rel, seeds, 3)
    assert int(edges[0]) == want
