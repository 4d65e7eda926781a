"""LDBC SNB-shaped data, as arrays (numpy only; imports nothing of the program).

A copy of `dgraph_tpu/models/ldbc.py generate` as it stood at PR 21 (the
yardstick may not move when the program does): SF-scaled entity counts, a
community-clustered heavy-tailed `knows` graph, posts/comments with
creator/reply/tag edges, forums, likes, employment and typed properties.
`sf=3` gives 3,094,529 nodes / about 13.7 M edges: the node count of the
official SF1 data set (3,181,724 nodes, 17,256,038 edges, 9,892 persons)
on three times its persons and four fifths of its edges.
The same `(params, seed)` gives the same arrays, bit for bit.
"""

from __future__ import annotations

import numpy as np

FIRST_NAMES = ["Jan", "Yang", "Arjun", "Maria", "Chen", "Otto", "Abebe",
               "Sofia", "Kenji", "Amara", "Ivan", "Lucia", "Wei", "Noor",
               "Pavel", "Aiko"]
LAST_NAMES = ["Kov", "Li", "Sharma", "Garcia", "Wang", "Muller", "Bekele",
              "Rossi", "Sato", "Okafor", "Petrov", "Silva", "Zhang",
              "Hassan", "Novak", "Tanaka"]
CITIES = ["Beijing", "Mumbai", "Lagos", "Moscow", "Sao_Paulo", "Tokyo",
          "Berlin", "Nairobi", "Lima", "Hanoi", "Tbilisi", "Porto"]
N_TAG_NAMES = 128

EDGE_PREDS = ("knows", "has_creator", "reply_of", "has_tag", "has_member",
              "container_of", "likes", "works_at")

SCHEMA = """
first_name: string @index(exact, term) .
last_name: string @index(exact) .
city: string @index(exact) .
birthday_year: int @index(int) .
creation_ts: int @index(int) .
tag_name: string @index(exact) .
forum_title: string @index(exact) .
org_name: string @index(exact) .
knows: [uid] @reverse .
has_creator: [uid] @reverse .
reply_of: [uid] @reverse .
has_tag: [uid] @reverse .
has_member: [uid] @reverse .
container_of: [uid] @reverse .
likes: [uid] @reverse .
works_at: [uid] @reverse .
"""


def generate(params: dict, seed: int) -> dict:
    """`params` and the seed -> a dict of numpy arrays: uid ranges
    `<entity>_uids`, edges `<pred>` as (src uid, dst uid) int64 pairs,
    `knows_weight`, and person/message properties as index arrays.

    `params["structure_seed"]` fixes the shape of the graph (how many
    edges each predicate has and every node's degree); `seed` draws which
    uid is which node (a permutation inside each entity class), every
    property and every weight. The program compiles a device program for
    each exact array length it meets (PERF.md), so a graph whose edge
    counts moved with the seed would make every run a cold compile."""
    base = _structure(float(params["sf"]), int(params["structure_seed"]))
    return _relabel(base, np.random.default_rng([seed, 1]))


def _relabel(base: dict, rng) -> dict:
    classes = ("person", "post", "comment", "tag", "forum", "org")
    n = sum(len(base[f"{c}_uids"]) for c in classes)
    new_of = np.zeros(n + 1, np.int64)
    for c in classes:
        uids = base[f"{c}_uids"]
        new_of[uids] = uids[rng.permutation(len(uids))]
    out = {f"{c}_uids": base[f"{c}_uids"] for c in classes}
    # the uid of the i-th person of the structure: traffic that picks its
    # persons by this index asks for the same neighbourhood sizes in
    # every run, whatever the uids are called
    out["person_of_structure"] = new_of[base["person_uids"]]
    for pred in EDGE_PREDS:
        pairs = new_of[base[pred]]
        out[pred] = pairs[np.lexsort((pairs[:, 1], pairs[:, 0]))]
    knows = out["knows"]
    pair_lo = np.minimum(knows[:, 0], knows[:, 1])
    pair_hi = np.maximum(knows[:, 0], knows[:, 1])
    uniq, inverse = np.unique(pair_lo * (n + 1) + pair_hi,
                              return_inverse=True)
    out["knows_weight"] = np.round(rng.uniform(0.5, 10.0, len(uniq)),
                                   2)[inverse]
    n_persons = len(base["person_uids"])
    n_msgs = len(base["creation_ts"])
    out["first_name"] = rng.integers(0, len(FIRST_NAMES), n_persons)
    out["last_name"] = rng.integers(0, len(LAST_NAMES), n_persons)
    out["city"] = rng.integers(0, len(CITIES), n_persons)
    out["birthday_year"] = rng.integers(1950, 2005, n_persons)
    out["creation_ts"] = np.sort(
        rng.integers(1_262_304_000, 1_356_998_400, n_msgs))
    return out


def _structure(sf: float, seed: int) -> dict:
    """The copy of `models/ldbc.py generate`, bit for bit."""
    rng = np.random.default_rng(seed)
    n_persons = max(int(9892 * sf), 64)
    n_posts = max(int(400_000 * sf), 256)
    n_comments = max(int(600_000 * sf), 256)
    n_tags = min(N_TAG_NAMES, max(int(16_080 * sf), 16))
    n_forums = max(int(20_000 * sf), 32)
    n_orgs = max(int(1_575 * sf), 8)

    uid = 1
    ranges = {}
    for name, n in (("person", n_persons), ("post", n_posts),
                    ("comment", n_comments), ("tag", n_tags),
                    ("forum", n_forums), ("org", n_orgs)):
        ranges[name] = np.arange(uid, uid + n, dtype=np.int64)
        uid += n
    person_uids, post_uids, comment_uids = (
        ranges["person"], ranges["post"], ranges["comment"])
    tag_uids, forum_uids, org_uids = (
        ranges["tag"], ranges["forum"], ranges["org"])

    # knows: sqrt(n)-sized communities, ~80% intra-community, the rest
    # global with hub skew
    n_comm = max(int(np.sqrt(n_persons)), 4)
    comm = rng.integers(0, n_comm, n_persons)
    deg = np.minimum(rng.zipf(2.2, n_persons), 512)
    deg = np.maximum((deg * (18.0 / max(deg.mean(), 1e-9))).astype(np.int64),
                     1)
    src = np.repeat(np.arange(n_persons), deg)
    local = rng.random(len(src)) < 0.8
    dst = np.empty(len(src), np.int64)
    order = np.argsort(comm, kind="stable")
    bounds = np.searchsorted(comm[order], np.arange(n_comm + 1))
    csrc = comm[src[local]]
    lo, hi = bounds[csrc], bounds[csrc + 1]
    dst[local] = order[lo + (rng.random(local.sum())
                             * np.maximum(hi - lo, 1)).astype(np.int64)]
    n_far = int((~local).sum())
    dst[~local] = (n_persons * rng.beta(0.7, 2.0, n_far)).astype(np.int64)
    keep = src != dst
    s, d = src[keep], dst[keep]
    knows = np.stack([np.concatenate([s, d]), np.concatenate([d, s])],
                     axis=1)
    knows = np.unique(knows, axis=0)
    knows = np.stack([person_uids[knows[:, 0]], person_uids[knows[:, 1]]],
                     axis=1)

    # activity: authorship follows the same heavy tail as friendships
    author_w = deg.astype(np.float64) / deg.sum()
    post_author = rng.choice(n_persons, n_posts, p=author_w)
    comment_author = rng.choice(n_persons, n_comments, p=author_w)
    has_creator = np.stack([
        np.concatenate([post_uids, comment_uids]),
        person_uids[np.concatenate([post_author, comment_author])]], axis=1)

    to_post = rng.random(n_comments) < 0.7
    parent = np.empty(n_comments, np.int64)
    parent[to_post] = post_uids[rng.integers(0, n_posts, to_post.sum())]
    idx = np.arange(n_comments)[~to_post]
    earlier = np.maximum(idx, 1)
    parent[~to_post] = comment_uids[(rng.random(len(idx))
                                     * earlier).astype(np.int64)]
    reply_of = np.stack([comment_uids, parent], axis=1)

    n_msgs = n_posts + n_comments
    tag_cnt = rng.integers(0, 4, n_msgs)
    msg_uids = np.concatenate([post_uids, comment_uids])
    tsrc = np.repeat(msg_uids, tag_cnt)
    tpick = np.minimum(rng.zipf(1.8, len(tsrc)) - 1, n_tags - 1)
    has_tag = np.stack([tsrc, tag_uids[tpick]], axis=1)

    m_cnt = np.minimum(rng.zipf(1.9, n_forums) + 4, 256)
    fsrc = np.repeat(np.arange(n_forums), m_cnt)
    fmem = rng.choice(n_persons, len(fsrc), p=author_w)
    has_member = np.unique(np.stack(
        [forum_uids[fsrc], person_uids[fmem]], axis=1), axis=0)
    container_of = np.stack(
        [forum_uids[rng.integers(0, n_forums, n_posts)], post_uids],
        axis=1)
    n_likes = max(int(600_000 * sf), 512)
    lik_p = rng.choice(n_persons, n_likes, p=author_w)
    lik_m = rng.integers(0, n_msgs, n_likes)
    likes = np.unique(np.stack(
        [person_uids[lik_p], msg_uids[lik_m]], axis=1), axis=0)
    org_of = np.minimum(rng.zipf(1.6, n_persons) - 1, n_orgs - 1)
    works_at = np.stack([person_uids, org_uids[org_of]], axis=1)
    # one weight per person pair: both directed rows carry the same
    pair_lo = np.minimum(knows[:, 0], knows[:, 1])
    pair_hi = np.maximum(knows[:, 0], knows[:, 1])
    pair_key = pair_lo * (knows.max() + 1) + pair_hi
    uniq_pairs, inverse = np.unique(pair_key, return_inverse=True)
    pair_w = np.round(rng.uniform(0.5, 10.0, len(uniq_pairs)), 2)
    knows_weight = pair_w[inverse]

    first = rng.integers(0, len(FIRST_NAMES), n_persons)
    last = rng.integers(0, len(LAST_NAMES), n_persons)
    city = rng.integers(0, len(CITIES), n_persons)
    birthday = rng.integers(1950, 2005, n_persons)
    creation = np.sort(rng.integers(1_262_304_000, 1_356_998_400, n_msgs))

    out = {f"{k}_uids": v for k, v in ranges.items()}
    out.update(
        knows=knows, knows_weight=knows_weight, has_creator=has_creator,
        reply_of=reply_of, has_tag=has_tag, has_member=has_member,
        container_of=container_of, likes=likes, works_at=works_at,
        first_name=first.astype(np.int64), last_name=last.astype(np.int64),
        city=city.astype(np.int64), birthday_year=birthday.astype(np.int64),
        creation_ts=creation.astype(np.int64))
    return out


def sizes(data: dict) -> dict:
    n_nodes = sum(len(data[f"{k}_uids"]) for k in
                  ("person", "post", "comment", "tag", "forum", "org"))
    n_edges = sum(len(data[p]) for p in EDGE_PREDS)
    return {"nodes": int(n_nodes), "edges": int(n_edges),
            "persons": int(len(data["person_uids"]))}
