"""LDBC datagen's person-knows-person graph alone, as arrays (numpy only).

The shape of the Graphalytics `datagen-*-fb` graphs: persons and the
undirected `knows` between them, with the four person properties that LDBC
SNB Interactive's complex read IC1 touches. Datagen itself is not at hand,
so the laws are this file's (`configs/ldbc-knows-7_5-fb.json` lists them
under `assumed`):

  - degrees: a log-normal target, clipped (`degree_sigma`, `degree_cap`),
    scaled to the mean that `knows` and `persons` give: a Facebook-like
    body with no hubs;
  - clustering: `generators/ldbc_snb.py`'s recipe, sqrt(n)-sized
    communities and `local_share` of a person's friendships inside its
    own; a friend is drawn in proportion to the friends it wants;
  - names: dictionaries of `first_names`, `last_names` and `cities`
    distinct strings, popularity Zipf(`name_zipf`) over an order that the
    seed draws.

Exactly `knows` distinct unordered pairs, no self-loop, stored as the
database stores an undirected relation: an edge each way, sorted by
(source, target). `structure_seed` fixes every pair up to the persons'
names, and with them every degree (the shapes of the device's ELL blocks,
one compiled program each: PERF.md), and how common each person's names
are; `seed` draws which node is which person of the structure, which
string is which name, and the birthdays. Node `i` has uid `i + 1`.
The same `(params, seed)` gives the same arrays, bit for bit.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

SCHEMA = """
first_name: string @index(exact, term) .
last_name: string @index(exact) .
city: string @index(exact) .
birthday_year: int @index(int) .
knows: [uid] @reverse .
"""

CHUNKS = 16          # fixed: part of what a seed means
TICKETS = 32         # places a person holds, on average, in the list that
#                      friends are drawn from
ROUNDS = 8           # of proposals, at most
OVERDRAW = 1.2       # friendships proposed, over those still missing; what
#                      is over `knows` at the end is dropped, evenly

_ONSETS = ("b", "d", "f", "g", "h", "j", "k", "l", "m", "n", "p", "r", "s",
           "t", "v", "z")
_VOWELS = ("a", "e", "i", "o", "u", "ai", "ei", "ou")


def names(count: int, tail: str) -> list:
    """`count` distinct capitalised names, syllables counted out in mixed
    radix and ended by `tail`: the same list wherever it is asked for."""
    out = []
    for i in range(count):
        parts, k = [], i
        while True:
            k, r = divmod(k, len(_ONSETS) * len(_VOWELS))
            parts.append(_ONSETS[r % len(_ONSETS)]
                         + _VOWELS[r // len(_ONSETS)])
            if not k:
                break
        out.append(("".join(parts) + tail).capitalize())
    return out


def dictionaries(sizes: dict) -> dict:
    """property -> its list of strings, the generator's arrays holding
    indices into it. `sizes` has `first_names`, `last_names`, `cities`:
    the parameters, or what `generate` returned."""
    return {"first_name": names(int(sizes["first_names"]), ""),
            "last_name": names(int(sizes["last_names"]), "son"),
            "city": names(int(sizes["cities"]), "ville")}


def generate(params: dict, seed: int) -> dict:
    """`params`: persons, knows, degree_sigma, degree_cap, local_share,
    first_names, last_names, cities, name_zipf, structure_seed. Returns
    the directed edges `src`/`dst` (int32 node indices, both directions of
    every pair), the edge list grouped by source for the reference (node
    i's friends are `dst[row_start[i] : row_start[i] + row_len[i]]`),
    `node_of_structure[k]` (the node that the structure's k-th person
    became), `n_nodes`, `max_degree`, per node `first_name`, `last_name`,
    `city` (indices into `dictionaries`, whose sizes ride along as
    `first_names`, `last_names`, `cities`) and `birthday_year`."""
    n = int(params["persons"])
    src, dst = _structure(params, int(params["structure_seed"]))
    rng = np.random.default_rng([seed, 1])
    perm = rng.permutation(n).astype(np.int32)
    counts = np.bincount(src, minlength=n)
    starts = np.zeros(n, np.int64)
    np.cumsum(counts[:-1], out=starts[1:])
    row_start = np.empty(n, np.int64)
    row_len = np.empty(n, np.int64)
    row_start[perm] = starts
    row_len[perm] = counts
    out = {"src": perm[src], "dst": perm[dst], "row_start": row_start,
           "row_len": row_len, "node_of_structure": perm,
           "n_nodes": np.array(n, np.int64),
           "max_degree": np.array(counts.max(), np.int64)}
    # how common a person's name is belongs to the structure, as its
    # degree does: every run's name of a given popularity has as many
    # bearers, and does a query as much work; which string that name is,
    # the seed draws
    a = float(params["name_zipf"])
    law = np.random.default_rng([int(params["structure_seed"]), 2])
    for prop, sized in (("first_name", "first_names"),
                        ("last_name", "last_names"), ("city", "cities")):
        size = int(params[sized])
        out[sized] = np.array(size, np.int64)
        w = 1.0 / np.power(np.arange(1, size + 1, dtype=np.float64), a)
        out[prop] = np.empty(n, np.int32)
        out[prop][perm] = rng.permutation(size)[
            law.choice(size, size=n, p=w / w.sum())]
    out["birthday_year"] = rng.integers(1950, 2005, n)
    return out


def _structure(params: dict, seed: int):
    """(src, dst) int32: both directions of exactly `knows` distinct
    pairs over persons 0..n-1, sorted by (src, dst)."""
    n, pairs = int(params["persons"]), int(params["knows"])
    bits = max(n - 1, 1).bit_length()      # a pair's key: lo << bits | hi
    root = np.random.default_rng(seed)
    n_comm = max(int(np.sqrt(n)), 4)
    comm = root.integers(0, n_comm, n)
    # the friends a person should end with: log-normal about the mean
    # that `knows` gives, cut at the cap
    want = root.lognormal(0.0, float(params["degree_sigma"]), n)
    want = np.minimum(want * (2.0 * pairs / n / want.mean()),
                      float(params["degree_cap"]))
    want /= want.sum()
    # a friend is drawn in proportion to what it wants, inside the
    # proposer's community or anywhere: persons laid out community by
    # community, each as many times as it wants friends (TICKETS a
    # person, on average), and a draw is a place in that list
    order = np.argsort(comm, kind="stable").astype(np.int32)
    times = np.floor(want[order] * (TICKETS * n)
                     + root.random(n)).astype(np.int64)
    tickets = np.repeat(order, times)
    ends = np.cumsum(times)[np.searchsorted(
        comm[order], np.arange(n_comm), side="right") - 1]
    begin_of = np.concatenate([[0], ends[:-1]])[comm]
    width_of = (ends[comm] - begin_of).astype(np.float64)
    local_share = float(params["local_share"])
    cuts = np.linspace(0, n, CHUNKS + 1).astype(np.int64)

    def propose(job):
        """The sorted keys of the friendships that persons cuts[i] to
        cuts[i+1] propose, `mine` each; a step works into an array that
        is there where it can (a fresh one is most of its time where a
        page fault is dear)."""
        i, rng, mine = job
        who = slice(int(cuts[i]), int(cuts[i + 1]))
        src = np.repeat(np.arange(who.start, who.stop), mine)
        lo = np.repeat(begin_of[who], mine)
        width = np.repeat(width_of[who], mine)
        far = rng.random(len(src), dtype=np.float32) >= local_share
        lo[far], width[far] = 0, len(tickets)
        np.multiply(width, rng.random(len(src)), out=width)
        np.add(lo, width, out=lo, casting="unsafe")      # rounds down
        dst = tickets[lo]
        np.minimum(src, dst, out=lo)
        np.maximum(src, dst, out=src)
        keep = lo != src
        np.left_shift(lo, bits, out=lo)
        np.bitwise_or(lo, src, out=lo)
        key = lo[keep]
        key.sort()
        return key

    key = np.zeros(0, np.int64)
    with ThreadPoolExecutor(max_workers=8) as pool:
        # rounds, each proposing over what is still missing: a pair
        # proposed twice counts once (a dense community repeats itself)
        for _ in range(ROUNDS):
            # a person proposes half its friendships, is proposed the rest
            mine = np.floor(want * (OVERDRAW * (pairs - len(key)) + n)
                            + root.random(n)).astype(np.int64)
            jobs = [(i, rng, mine[cuts[i]:cuts[i + 1]])
                    for i, rng in enumerate(root.spawn(CHUNKS))]
            key = np.unique(np.concatenate(
                [key, *pool.map(propose, jobs)]))
            if len(key) >= pairs:
                break
        else:
            raise SystemExit(
                f"ldbc_knows: {len(key)} distinct pairs after {ROUNDS} "
                f"rounds where knows asks for {pairs}: too dense for {n} "
                f"persons in communities of {n // n_comm}")
    keep = np.ones(len(key), bool)
    keep[root.choice(len(key), len(key) - pairs, replace=False)] = False
    key = key[keep]
    # the pair a < b is the edges a -> b, among a's friends after those
    # under a, and b -> a, among b's before those over b: every row sorted
    a, b = key >> bits, key & ((1 << bits) - 1)
    back = (b << bits) | a
    back.sort()
    over = np.bincount(a, minlength=n)          # friends over a person
    under = np.bincount(b, minlength=n)
    dst = np.empty(2 * pairs, np.int32)
    place = np.arange(pairs, dtype=np.int64)
    dst[place + np.cumsum(under)[a]] = b
    dst[place + (np.cumsum(over) - over)[back >> bits]] = \
        back & ((1 << bits) - 1)
    return np.repeat(np.arange(n, dtype=np.int32), over + under), dst


def sizes(data: dict) -> dict:
    return {"nodes": int(data["n_nodes"]), "knows": int(len(data["src"])),
            "max_degree": int(data["max_degree"])}
