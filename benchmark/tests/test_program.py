"""What the benchmark takes from the program: the array-built checkpoint
equals the mutation path's, and the references agree with the engine."""

import json

import numpy as np
import pytest

import build_checkpoint
from generators import follower, ldbc_snb
from references import follower as fref
from references import ldbc_snb as lref
from traffic_kinds import ic_mix, shortest_pairs
from test_references import FOLLOWER, IC, PAIRS, SNB


def _open(tmp_path, generator, data):
    from dgraph_tpu.server.api import Alpha
    build_checkpoint.save_arrays(data, str(tmp_path / "arrays"))
    build_checkpoint.build(generator, str(tmp_path / "arrays"),
                           str(tmp_path / "p"))
    return Alpha.open(str(tmp_path / "p"))


def test_array_built_checkpoint_equals_load_into(tmp_path):
    from dgraph_tpu.models import ldbc
    from dgraph_tpu.server.api import Alpha
    # the generator's structure is the copy of `models/ldbc.py generate`
    data = ldbc_snb._structure(0.01, 9)
    g = ldbc.generate(0.01, 9)
    assert np.array_equal(g.knows, data["knows"])
    ours = _open(tmp_path, "ldbc_snb", data)
    theirs = Alpha()
    ldbc.load_into(theirs, g)
    theirs.checkpoint_to(str(tmp_path / "theirs"))
    theirs = Alpha.open(str(tmp_path / "theirs"))
    templates = ldbc.ic_templates(g)
    assert len(templates) == 14
    for name, q in templates.items():
        assert ours.query_raw(q) == theirs.query_raw(q), name


def test_snb_reference_agrees_with_the_engine(tmp_path):
    data = ldbc_snb.generate(SNB, 7)
    alpha = _open(tmp_path, "ldbc_snb", data)
    ref = lref.make(data, {})
    mix = ic_mix.make(data, IC, 7)
    reqs = mix.warm_requests(20)[:60] + mix.requests(140)
    for r in reqs:
        got = json.loads(alpha.query_raw(r["body"].decode()))
        ok, why = ref.check(r["meta"], got)
        assert ok, (r["meta"], why)


def test_follower_reference_agrees_with_the_engine(tmp_path):
    data = follower.generate(FOLLOWER, 7)
    alpha = _open(tmp_path, "follower", data)
    ref = fref.make(data, {})
    mix = shortest_pairs.make(data, PAIRS, 7)
    req = mix.requests(1)[0]
    outs = alpha.query_batch(json.loads(req["body"])["queries"])
    for meta, got in mix.split(req, outs):
        ok, why = ref.check(meta, got)
        assert ok, (meta, why)
