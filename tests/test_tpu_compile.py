"""The lane step's pushed hop, compiled for a v5e that is described and not
attached (libtpu's compiler runs on this CPU host), at the follower cell's
shapes. Nothing runs: these guard what only the chip's compiler refuses or
makes dear. XLA:TPU sorts the updates of a scatter once they number over
2^16 to 2^17, and compiling that sort alone takes longer than the whole pull
(PERF.md, PR 30), so the pushed hop must come out with no sort in it.

The topology is described inside a fixture, never at import: one process at
a time may load libtpu, and every xdist worker imports this file."""

import jax
import jax.numpy as jnp
import pytest

N, E, W = 1303125, 44919214, 2      # follower-tw2010-32nd, 64 lanes

# The three cells' relations as a pull sees them (PERF.md §4; the slots to
# the nearest 0.1 M): rows, list slots, the hub block. push_caps of each
# is the caps its programs are compiled at on the chip.
CELLS = {"follower": (N, 33_800_000, (768256, 2048)),
         "graph500": (2395982, 51_800_000, (32640, 64896)),
         "knows": (633432, 70_190_000, None)}


def _cell_caps(cell):
    """(rows, push_caps) of a cell's relation, from a DeviceEll of shapes
    alone: push_caps reads sizes, not data."""
    from dgraph_tpu.ops import bfs
    n, slots, block = CELLS[cell]
    shape = jax.ShapeDtypeStruct
    dev = bfs.DeviceEll(
        n=n, parts=[("ell", shape((slots // 8, 8), jnp.int32), slots // 8)],
        tiles=None, lvl2=[], seg_rows=0,
        dense=block and (shape(block, jnp.int8),
                         shape(block[1:], jnp.int32)))
    return n, bfs.push_caps(dev)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


def _compiled(one_chip, fn, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    return jax.jit(fn).lower(*args).compile()


@pytest.mark.parametrize("cell,edges,slot_cap", [
    ("follower", E, 1_700_000), ("knows", 68371494, 3_500_000)])
def test_pushed_hop_compiles_for_the_v5e_without_a_sort(one_chip, cell,
                                                        edges, slot_cap):
    """At the caps of the follower cell's relation and of the `knows`
    one's, the break-even of each one's pull (PR 44)."""
    from dgraph_tpu.ops import bfs
    n, (f_cap, e_cap, chunk) = _cell_caps(cell)
    assert abs(e_cap - slot_cap) < 100_000 and chunk == bfs.PUSH_CHUNK
    assert f_cap == e_cap // bfs.PUSH_FANOUT

    def push(indptr, indices, deg, frontier, act):
        return bfs._push_hop((indptr, indices, deg), frontier, act, n, W,
                             jnp.uint32, 32, f_cap, chunk)

    c = _compiled(one_chip, push, ((n + 1,), jnp.int32),
                  ((edges,), jnp.int32), ((n,), jnp.int32),
                  ((n + 1, W), jnp.uint32), ((n,), jnp.bool_))
    hlo = c.as_text()
    assert " sort(" not in hlo
    assert " scatter(" in hlo                    # the text is the HLO's
    # the byte mask, its repack and a turn's slots: well under a GB
    assert c.memory_analysis().temp_size_in_bytes < 1 << 30


@pytest.mark.parametrize("cap", [64, 8192, 65536, "graph500", "knows"])
def test_set_rows_compiles_for_the_v5e_without_a_sort(one_chip, cap):
    """At a few caps over the follower graph's rows, and at the row caps
    of the two cells whose caps are larger (83 K and 111 K rows)."""
    from dgraph_tpu.ops import bfs
    n = N
    if not isinstance(cap, int):
        n, (cap, _slots, _turn) = _cell_caps(cap)
    c = _compiled(one_chip, lambda act: bfs._set_rows(act, n, cap, 4096),
                  ((n,), jnp.bool_))
    assert " sort(" not in c.as_text()
    # a turn's rows' flags as float32 and their prefix sums, not the cap's
    assert c.memory_analysis().temp_size_in_bytes < 16 << 20


@pytest.mark.parametrize("weighted", [False, True])
def test_lane_sums_compile_for_the_v5e_at_graph500_22(one_chip, weighted):
    """The tree program's per-lane integer sums (a count of `seen`, the
    out-degree mass of the rows expanded) at graph500-22's 2.4 M rows and
    64 lanes: a block of rows a turn, so nothing of n x lanes is held."""
    from dgraph_tpu.ops import bfs
    n = 2395982
    shapes = [((n + 1, W), jnp.uint32)] + [((n,), jnp.int32)] * weighted
    c = _compiled(one_chip,
                  lambda m, w=None: bfs._lane_sums(m, w, n, W, 32), *shapes)
    assert c.memory_analysis().temp_size_in_bytes < 64 << 20
    assert c.memory_analysis().output_size_in_bytes <= 4 * 32 * W + 1024


def test_tree_program_with_the_push_compiles_for_the_v5e(one_chip):
    """The k-hop count's program (one recurse stage of depth 3, 64 lanes)
    at graph500-22's rows, out-edges and caps, so that a pushed hop takes
    the real turn of 32,768 slots over the real 2.4 M rows (what XLA:TPU
    does with a scatter depends on both). The pull's blocks are cut to one
    degree class: the pull is not what this guards. The program keeps its
    name and its hops a loop with the choice inside, sorts nothing, and
    every index block and the out-CSR are parameters (a device array the
    program closed over would be a constant of it)."""
    import re

    import numpy as np

    from dgraph_tpu.ops import bfs
    edges, dense = 65242600, 1 << 20
    described = {}

    def standin(*shape):
        """An array of the stage, for make_ell_tree to hold: the shape it
        is compiled at is given at the lowering."""
        a = jnp.zeros((1,) * len(shape), jnp.int32)
        described[id(a)] = jax.ShapeDtypeStruct(shape, jnp.int32,
                                                sharding=one_chip)
        return a

    n, caps = _cell_caps("graph500")
    assert caps[2] == bfs.PUSH_CHUNK
    stage = {"kind": "recurse",
             "prepared": {"parts": [("chain", standin(dense, 4), dense),
                                    ("zero", None, n - dense)],
                          "tiles": None, "lvl2": [], "seg_rows": 0, "n": n},
             "perm_in": standin(n + 1), "out_idx": standin(n + 1),
             "out": (standin(n + 1), standin(edges), standin(n)),
             "caps": caps, "parent": ("seed", 0), "filt": None, "depth": 3,
             "keep_hops": False}
    tree = bfs.make_ell_tree([stage], n, W)
    held, = tree.args
    c = tree.func.lower(
        [described[id(a)] for a in held],
        (jax.ShapeDtypeStruct((n + 1, W), jnp.uint32, sharding=one_chip),),
        ()).compile()
    hlo = c.as_text()
    assert hlo.startswith("HloModule jit_tree")
    assert " sort(" not in hlo
    assert " scatter(" in hlo and " conditional(" in hlo
    assert " while(" in hlo
    width = {"pred": 1, "s8": 1, "u8": 1, "bf16": 2, "s32": 4, "u32": 4,
             "f32": 4}
    constants = [
        width.get(dtype, 8) * int(np.prod([int(d) for d in dims.split(",")
                                           if d] or [1]))
        for dtype, dims in re.findall(r"= (\w+)\[([\d,]*)\]\S* constant\(",
                                      hlo)]
    assert constants and max(constants) <= 4096
    # the out-CSR's edges are among the parameters
    assert c.memory_analysis().argument_size_in_bytes > 4 * edges


@pytest.mark.parametrize("rows,cols", [(32640, 64896), (768256, 2048)])
def test_pull_with_the_hub_block_compiles_for_the_v5e(one_chip, rows, cols):
    """A pull whose hub block is the Graph500 cell's (32,640 x 64,896) or
    the follower cell's (768,256 x 2,048) int8: the block's share is ONE
    matrix product of int8 operands summed in int32 (the MXU's own, no
    widened copy of the block), the block a parameter, and what the hop
    holds besides its arguments stays small beside them."""
    import re

    from dgraph_tpu.ops import bfs
    n, tiles, body = 2395982, 1 << 20, 1 << 20

    def pull(block, cols_, e, te, t2, frontier):
        prepared = {"parts": [("chain", e, body), ("zero", None, n - body
                                                   - (1 << 16))],
                    "tiles": ("chain", te, tiles), "lvl2": [t2],
                    "dense": (block, cols_), "seg_rows": 1 << 16, "n": n}
        return bfs._ell_hop(prepared, frontier, W)

    c = _compiled(one_chip, pull, ((rows, cols), jnp.int8),
                  ((cols,), jnp.int32), ((body, 4), jnp.int32),
                  ((tiles, 8), jnp.int32), ((1 << 16, 16), jnp.int32),
                  ((n + 1, W), jnp.uint32))
    hlo = c.as_text()
    products = re.findall(r"= (\w+)\[[\d,]*\]\S* convolution\(", hlo)
    assert products == ["s32"]
    assert f"s8[{rows},{cols}]" in hlo and f"bf16[{rows},{cols}]" not in hlo
    mem = c.memory_analysis()
    assert mem.argument_size_in_bytes > rows * cols
    assert mem.temp_size_in_bytes < 1 << 30
