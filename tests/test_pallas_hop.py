"""Pallas DMA-ring ELL hop == XLA gather hop == numpy, exactly.

Reference parity: the hop is the reference's hottest loop (posting-list
walk per uid, SURVEY §3.1); the Pallas kernel must be bit-identical to
the XLA form it can replace (DGRAPH_TPU_PALLAS=1). These tests ASK for
the pallas interpreter (`interpret=True`, handed to the serving path by
patching the kernel it calls) — the kernel never picks it from the
backend's name. The compiled kernel is checked
against the XLA hop on the chip by chip_smoke.py's kernels phase.
"""

import numpy as np
import pytest

import functools

import jax
import jax.numpy as jnp

import dgraph_tpu.ops.pallas_hop as ph
from dgraph_tpu.models.synthetic import powerlaw_rel
from dgraph_tpu.ops.bfs import (build_ell, device_ell, ell_recurse,
                                make_ell_recurse, pack_seed_masks,
                                unpack_masks)
from dgraph_tpu.ops.pallas_hop import bucket_hop_pallas


@pytest.mark.parametrize("n_b,K,W", [(1024, 1, 128), (1024, 5, 128),
                                     (2048, 8, 128), (1024, 3, 4)])
def test_bucket_hop_matches_numpy(n_b, K, W):
    rng = np.random.default_rng(7)
    n = 1000
    nbr = rng.integers(0, n + 1, (n_b, K)).astype(np.int32)
    frontier = rng.integers(0, 2**32, (n + 1, W), dtype=np.uint32)
    frontier[n] = 0  # sentinel row
    got = np.asarray(bucket_hop_pallas(jnp.asarray(nbr),
                                       jnp.asarray(frontier),
                                       interpret=True))
    want = np.bitwise_or.reduce(frontier[nbr], axis=1)
    assert np.array_equal(got, want)


def test_compiled_kernel_is_an_error_off_the_chip():
    """Interpret mode is never selected from the backend's name: without
    the explicit argument, a non-TPU backend refuses the kernel."""
    nbr = jnp.zeros((1024, 1), jnp.int32)
    frontier = jnp.zeros((2, 128), jnp.uint32)
    with pytest.raises(ValueError, match="[Ii]nterpret"):
        bucket_hop_pallas(nbr, frontier)


def test_ell_recurse_pallas_equals_xla(monkeypatch):
    """The full depth-N recurse kernel with pallas hops enabled produces
    the same masks and frontier sets as the XLA gather form — at the
    4096-lane width the compiled kernel moves (W = 128 words)."""
    rng = np.random.default_rng(3)
    rel = powerlaw_rel(1 << 9, 2.0, seed=11)
    g = build_ell(rel.indptr, rel.indices)
    seeds = [rng.integers(0, 1 << 9, 4) for _ in range(4096)]
    mask0 = pack_seed_masks(g, seeds)
    assert mask0.shape[1] == 128

    last_x, seen_x, edges_x = ell_recurse(g, mask0, 2)

    from dgraph_tpu.utils.metrics import METRICS
    fallbacks0 = METRICS.get("pallas_fallback_total")
    monkeypatch.setenv("DGRAPH_TPU_PALLAS", "1")
    monkeypatch.setattr(ph, "bucket_hop_pallas", functools.partial(
        bucket_hop_pallas, interpret=True))
    fn = make_ell_recurse(device_ell(g), g.outdeg, g.n, mask0.shape[1])
    last_p, seen_p, edges_p = fn(jax.device_put(mask0), 2)
    assert METRICS.get("pallas_fallback_total") == fallbacks0

    assert np.array_equal(np.asarray(seen_x), np.asarray(seen_p))
    assert np.array_equal(np.asarray(last_x), np.asarray(last_p))
    assert np.array_equal(np.asarray(edges_x), np.asarray(edges_p))
    # and the decoded per-query reachable sets agree
    sx = unpack_masks(g, np.asarray(seen_x))[:64]
    sp = unpack_masks(g, np.asarray(seen_p))[:64]
    for a, b in zip(sx, sp):
        assert np.array_equal(a, b)


def test_pallas_flag_off_by_default(monkeypatch):
    monkeypatch.delenv("DGRAPH_TPU_PALLAS", raising=False)
    from dgraph_tpu.ops.bfs import prepare_parts
    rel = powerlaw_rel(1 << 8, 4.0, seed=2)
    g = build_ell(rel.indptr, rel.indices)
    dev = device_ell(g)

    def kinds_of(prep):
        ks = {k for k, _e, _n in prep["parts"]}
        if prep["tiles"] is not None:
            ks.add(prep["tiles"][0])
        return ks

    kinds = kinds_of(prepare_parts(dev, 128))
    assert "pallas" not in kinds
    monkeypatch.setenv("DGRAPH_TPU_PALLAS", "1")
    kinds = kinds_of(prepare_parts(dev, 128))
    assert kinds <= {"pallas", "zero"} and "pallas" in kinds
    # the compiled kernel moves whole 128-word rows: a narrower mask
    # keeps the XLA hop even under the flag
    assert "pallas" not in kinds_of(prepare_parts(dev, 2))


def test_pallas_trace_failure_falls_back_to_xla(monkeypatch):
    """A failed Mosaic compile must never take the hop down: with the
    kernel raising at trace time, the hop falls back to the XLA gather
    form, still answers correctly — and is counted, so it cannot hide."""
    import dgraph_tpu.ops.bfs as bfs

    rng = np.random.default_rng(5)
    rel = powerlaw_rel(1 << 9, 5.0, seed=9)
    g = build_ell(rel.indptr, rel.indices)
    seeds = [rng.integers(0, 1 << 9, 3) for _ in range(4096)]
    mask0 = pack_seed_masks(g, seeds)
    want_last, want_seen, want_edges = ell_recurse(g, mask0, 3)

    def boom(*a, **kw):
        raise RuntimeError("injected Mosaic trace failure")

    monkeypatch.setenv("DGRAPH_TPU_PALLAS", "1")
    monkeypatch.setattr(ph, "bucket_hop_pallas", boom)
    monkeypatch.setattr(bfs, "_pallas_failed", False)  # restored after
    fn = bfs.make_ell_recurse(bfs.device_ell(g), g.outdeg, g.n,
                              mask0.shape[1])
    last, seen, edges = fn(jnp.asarray(mask0), 3)
    assert bfs._pallas_failed, "fallback flag must stick after failure"
    assert np.array_equal(np.asarray(seen), np.asarray(want_seen))
    assert np.array_equal(np.asarray(last), np.asarray(want_last))
    assert np.array_equal(np.asarray(edges), np.asarray(want_edges))
