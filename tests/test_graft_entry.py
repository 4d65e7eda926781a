"""The driver's single-chip compile check and its multi-chip dry run,
pinned on the CPU.

`__graft_entry__.entry()` is what the driver jits to see that the
flagship program still compiles; PR 7 rewrote ops/bfs.py under it and no
test noticed, because nothing imported the module.
"""

import numpy as np
import pytest

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


def test_dryrun_multichip_on_four_host_devices():
    """The driver's multi-chip check: the chained recurse hop and the ring
    matrix hop against the numpy walk, then the mesh engine against the
    host engine, on four of the suite's virtual CPU devices."""
    if jax.device_count() < 4:
        pytest.skip("fewer than four devices here")
    graft.dryrun_multichip(4)
