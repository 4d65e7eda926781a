"""BASELINE.md config measurements — real engine runs, CPU baseline.

Reference parity: BASELINE.json configs 1-5. The reference's datasets
(21million movies, LDBC SNB, Twitter-2010) are not fetchable here (zero
egress), so each config runs on a deterministic synthetic stand-in with
the same shape, scale noted in the output:

  1. 1-hop expand(starring)      movie-shaped bipartite graph
  2. 2-hop actor->film->actor    same graph, co-star traversal
  3. 3-hop @recurse + @filter    LDBC SNB-shaped graph (models/ldbc.py)
  4. shortest(from, to)          powerlaw follower graph (Twitter-shaped,
                                 scaled down; scale noted)
  5. LDBC IC mix p50             SNB-shaped graph, all 14
                                 interactive-complex template shapes

Every number is a real `Engine.query_bytes` (parse -> execute -> JSON
response bytes, i.e. the full serving path through the native emitter)
wall time, post-warmup, best-of-N. Run: python bench_baseline.py
[--platform cpu|tpu] (`cpu` = the engine's host path with jax held to
the CPU; otherwise the device path on the environment's device). Every
row names the device jax actually reports. Prints one JSON line per
config plus a markdown table ready for BASELINE.md.
"""

from __future__ import annotations

import json
import sys
import time

import numpy as np


def _engine(store, threshold):
    from dgraph_tpu.engine import Engine
    return Engine(store, device_threshold=threshold)


def timed(fn, reps=3):
    fn()  # warmup (jit compile / caches)
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        ts.append(time.perf_counter() - t0)
    return min(ts), out


def build_movie_alpha(n_films=40_000, n_actors=160_000, avg_cast=8,
                      seed=13):
    """Movie-shaped store: film -[starring]-> actor, film names/genres
    (the 21million dataset's shape at ~1/6 scale)."""
    from dgraph_tpu.server.api import Alpha
    rng = np.random.default_rng(seed)
    a = Alpha(device_threshold=512)
    a.alter("""
        name: string @index(term, exact) .
        genre: string @index(exact) .
        starring: [uid] @reverse .
    """)
    film0 = 1
    actor0 = film0 + n_films
    txn = a.new_txn()
    cast_n = rng.poisson(avg_cast, n_films).clip(1, 64)
    # popular actors get cast more (zipf), like real filmographies
    pop = rng.zipf(1.7, n_actors).astype(np.float64)
    pop /= pop.sum()
    genres = ["drama", "comedy", "action", "doc", "noir"]
    for f in range(n_films):
        fu = film0 + f
        txn.mutation.val_sets.append((fu, "name", f"film_{f}", "", ()))
        txn.mutation.val_sets.append(
            (fu, "genre", genres[f % len(genres)], "", ()))
        if len(txn.mutation.val_sets) > 200_000:
            txn.commit()
            txn = a.new_txn()
    txn.commit()
    txn = a.new_txn()
    cast = rng.choice(n_actors, size=int(cast_n.sum()), p=pop)
    offs = np.concatenate([[0], np.cumsum(cast_n)])
    for f in range(n_films):
        fu = film0 + f
        for ac in cast[offs[f]:offs[f + 1]]:
            txn.mutation.edge_sets.append(
                (fu, "starring", actor0 + int(ac), ()))
        if len(txn.mutation.edge_sets) > 200_000:
            txn.commit()
            txn = a.new_txn()
    txn.commit()
    return a, int(cast_n.sum())


def config1_2(threshold):
    a, n_edges = build_movie_alpha()
    store = a.mvcc.read_view(a.oracle.read_only_ts())

    # config 1: 1-hop expand(starring) over every drama film
    q1 = '{ q(func: eq(genre, "drama")) { name starring { uid } } }'
    t1, raw1 = timed(lambda: _engine(store, threshold).query_bytes(q1))
    out1 = json.loads(raw1)
    edges1 = sum(len(r.get("starring", [])) for r in out1["q"])

    # config 2: 2-hop co-star (actor -> ~starring -> film -> starring)
    # from the best-cast actor (max reverse degree)
    rev = store.rel("starring", True)
    busiest = int(np.argmax(np.diff(rev.indptr)))
    busiest_uid = int(store.uid_of(np.array([busiest]))[0])
    q2 = ('{ q(func: uid(%s)) { ~starring { starring { uid } } } }'
          % hex(busiest_uid))
    t2, raw2 = timed(lambda: _engine(store, threshold).query_bytes(q2))
    out2 = json.loads(raw2)
    films = out2["q"][0]["~starring"]
    edges2 = len(films) + sum(len(f["starring"]) for f in films)
    return [
        {"config": 1, "desc": "1-hop expand(starring), movie-shaped "
         f"{n_edges} casting edges", "p50_ms": round(t1 * 1e3, 1),
         "edges_per_sec": round(edges1 / t1), "edges": edges1},
        {"config": 2, "desc": "2-hop co-star from busiest actor",
         "p50_ms": round(t2 * 1e3, 1),
         "edges_per_sec": round(edges2 / t2), "edges": edges2},
    ]


def config3_5(threshold, sf=1.0):
    from dgraph_tpu.models import ldbc
    from dgraph_tpu.server.api import Alpha
    g = ldbc.generate(sf=sf)
    a = Alpha(device_threshold=512)
    ldbc.load_into(a, g)
    store = a.mvcc.read_view(a.oracle.read_only_ts())
    city = g.city[0]

    q3 = ('{ q(func: eq(city, "%s")) @recurse(depth: 3, loop: false) '
          '{ uid knows @filter(ge(birthday_year, 1980)) } }' % city)
    t3, raw3 = timed(lambda: _engine(store, threshold).query_bytes(q3))
    out3 = json.loads(raw3)

    def count(node):
        kids = node.get("knows", [])
        return len(kids) + sum(count(k) for k in kids)
    edges3 = sum(count(r) for r in out3["q"])

    # config 5: the FULL LDBC SNB Interactive Complex mix — all 14
    # template shapes on the synthetic model (models/ldbc.py):
    #   IC1  3-hop friend search by first name (ordered, paginated)
    #   IC2  recent messages by friends (orderdesc ts, top 20)
    #   IC3  friends-of-friends in given cities
    #   IC4  topics of friends' recent posts
    #   IC5  forums my friends belong to
    #   IC6  co-occurring tags on posts tagged X
    #   IC7  recent likers of my messages
    #   IC8  recent replies to my content (with commenter)
    #   IC9  messages by the 2-hop circle before a date
    #   IC10 friend-of-friend recommendation (birthday window)
    #   IC11 friends working at a given organisation
    #   IC12 expert search: friends' replies, by replied-post topic
    #   IC13 shortest knows-path between two persons
    #   IC14 weighted knows-paths (interaction-weight facets, numpaths)
    mix = list(ldbc.ic_templates(g).items())
    lats = []
    for _name, q in mix:
        t, _ = timed(lambda q=q: _engine(store, threshold).query_bytes(q))
        lats.append(t)

    # config 5b: BATCHED serving of the same mix — the lane-kernel path
    # (engine/treebatch.py): 12/14 templates share tree-kernel launches,
    # IC13/14 fall back per-query. Throughput over R repetitions of the
    # whole mix, vs the per-query loop at identical work AND identical
    # engine configuration (query_batch reads alpha.device_threshold,
    # which must match the per-query side's threshold or the comparison
    # measures two different engines).
    R = 8
    qs = [q for _n, q in mix] * R
    saved_threshold = a.device_threshold
    a.device_threshold = threshold
    try:
        t_batch, outs = timed(lambda: a.query_batch(qs), reps=2)
    finally:
        a.device_threshold = saved_threshold
    eng = _engine(store, threshold)
    t_seq, want = timed(lambda: [eng.query(q) for q in qs], reps=2)
    assert outs == want, "batched serving diverged from per-query"
    return [
        {"config": 3, "desc": f"3-hop @recurse+@filter, SNB-shaped sf={sf} "
         f"({g.n_nodes} nodes, {g.n_edges} edges)",
         "p50_ms": round(t3 * 1e3, 1),
         "edges_per_sec": round(edges3 / t3) if edges3 else 0,
         "edges": edges3},
        {"config": 5,
         "desc": f"LDBC IC mix (all {len(mix)} interactive-complex "
         f"template shapes), SNB-shaped sf={sf}",
         "p50_ms": round(sorted(lats)[len(lats) // 2] * 1e3, 1),
         "per_query_ms": {name: round(t * 1e3, 1)
                          for (name, _q), t in zip(mix, lats)}},
        {"config": "5b",
         "desc": f"BATCHED IC mix ({len(qs)} queries = {len(mix)} "
         f"templates x {R}, lane tree-kernel groups vs per-query loop)",
         "batch_wall_ms": round(t_batch * 1e3, 1),
         "batch_qps": round(len(qs) / t_batch),
         "per_query_qps": round(len(qs) / t_seq),
         "batch_speedup": round(t_seq / t_batch, 2)},
    ]


def config4(threshold, n=1 << 18, avg=24.0):
    """shortest(from,to) on a follower-shaped powerlaw graph.
    Twitter-2010 is 41.6M nodes / 1.47B edges; this is the same shape at
    1/159 node scale (noted in the output)."""
    from dgraph_tpu.models.synthetic import powerlaw_rel
    from dgraph_tpu.server.api import Alpha
    from dgraph_tpu.store.store import StoreBuilder

    rel = powerlaw_rel(n, avg, seed=21)
    b = StoreBuilder()
    uids = np.arange(1, n + 1, dtype=np.int64)
    src = np.repeat(np.arange(n, dtype=np.int64),
                    np.diff(rel.indptr).astype(np.int64))
    b.add_edges("follows", uids[src], uids[rel.indices.astype(np.int64)])
    store = b.finalize()
    # target a hub (low ranks are the preferential-attachment targets);
    # high-rank nodes have ~no in-edges and would make the path vacuous
    src_uid, dst_uid = hex(int(uids[n - 3])), hex(int(uids[100]))
    q = ('{ path as shortest(from: %s, to: %s) { follows } '
         '  path(func: uid(path)) { uid } }' % (src_uid, dst_uid))
    t, raw = timed(lambda: _engine(store, threshold).query_bytes(q))
    out = json.loads(raw)
    return [{"config": 4,
             "desc": f"shortest(from,to), follower-shaped {n} nodes "
             f"{rel.nnz} edges (Twitter-2010 1/159 node scale)",
             "p50_ms": round(t * 1e3, 1),
             "hops": len(out.get("path", []))}]


def main():
    # `--platform cpu` asks for the engine's HOST path (and holds jax to
    # the CPU); anything else asks for the device path on whatever device
    # the environment gives. Rows are labelled with the device jax
    # actually reports, never with what was asked for.
    asked = "cpu"
    if "--platform" in sys.argv:
        asked = sys.argv[sys.argv.index("--platform") + 1]
    import jax
    if asked == "cpu":
        jax.config.update("jax_platforms", "cpu")
        threshold = 1 << 62          # engine host path
    else:
        threshold = 512              # large frontiers on device
    devs = jax.devices()
    label = {"platform": devs[0].platform,
             "device_kind": devs[0].device_kind, "n_devices": len(devs),
             "engine_path": "host" if asked == "cpu" else "device"}

    rows = []
    rows += config1_2(threshold)
    rows += config4(threshold)
    rows += config3_5(threshold)
    rows.sort(key=lambda r: str(r["config"]))
    for r in rows:
        r.update(label)
        print(json.dumps(r), flush=True)
    where = (f"{label['platform']} ({label['device_kind']} x"
             f"{label['n_devices']}), {label['engine_path']} path")
    print("\n| # | Config | p50 | edges/sec | Device |")
    print("|---|---|---|---|---|")
    for r in rows:
        eps = f"{r['edges_per_sec']:,}" if r.get("edges_per_sec") else "—"
        lat = (f"{r['p50_ms']} ms" if "p50_ms" in r
               else f"{r['batch_wall_ms']} ms wall")
        print(f"| {r['config']} | {r['desc']} | {lat} | "
              f"{eps} | {where} |")


if __name__ == "__main__":
    main()
