"""Traffic kind `ic_mix`: LDBC SNB complex reads, one template a request.

A request is one of the 14 IC template shapes (the DQL of
`dgraph_tpu/models/ldbc.py ic_templates` at PR 21): the template by the
mix's share table, the person by Zipf over an order of the persons, the
rest uniform. The set of requests (templates in the shares' proportions,
persons as places of the structure, parameters) is drawn from the mix's
`schedule_seed`; the run's seed names the persons (their uids and
properties) and puts the requests in an order of its own.
"""

from __future__ import annotations

import numpy as np

from generators import ldbc_snb as gen

TEMPLATES = {
    "IC1": '{ v as var(func: uid(%(p)s)) @recurse(depth: 3, '
           'loop: false) { knows } '
           'q(func: uid(v), orderasc: last_name, first: 20) '
           '@filter(eq(first_name, "%(fn)s")) '
           '{ first_name last_name city } }',
    "IC2": '{ q(func: uid(%(p)s)) { knows { ~has_creator '
           '(orderdesc: creation_ts, first: 20) '
           '{ creation_ts } } } }',
    "IC3": '{ q(func: uid(%(p)s)) { knows { knows '
           '@filter(eq(city, "%(city)s") OR eq(city, "%(city2)s")) '
           '{ first_name last_name city } } } }',
    "IC4": '{ q(func: uid(%(p)s)) { knows { ~has_creator (first: 20) '
           '@filter(ge(creation_ts, %(ts)d)) '
           '{ has_tag { tag_name } } } } }',
    "IC5": '{ q(func: uid(%(p)s)) { knows { ~has_member '
           '(orderasc: forum_title, first: 20) '
           '{ forum_title } } } }',
    "IC6": '{ t(func: eq(tag_name, "%(tag)s")) { ~has_tag (first: 50)'
           ' { has_tag { tag_name } } } }',
    "IC7": '{ q(func: uid(%(p)s)) { ~has_creator { ~likes (first: 20) '
           '{ first_name } } } }',
    "IC8": '{ q(func: uid(%(p)s)) { ~has_creator { ~reply_of '
           '(orderdesc: creation_ts, first: 20) { creation_ts '
           'has_creator { first_name } } } } }',
    "IC9": '{ var(func: uid(%(p)s)) { knows { f as knows } } '
           'q(func: uid(f)) { ~has_creator (first: 20) '
           '@filter(le(creation_ts, %(ts)d)) '
           '{ creation_ts } } }',
    "IC10": '{ q(func: uid(%(p)s)) { knows { knows (first: 10) '
            '@filter(ge(birthday_year, %(year)d)) '
            '{ first_name city } } } }',
    "IC11": '{ q(func: uid(%(p)s)) { knows { works_at '
            '@filter(eq(org_name, "%(org)s")) { org_name } } } }',
    "IC12": '{ q(func: uid(%(p)s)) { knows { ~has_creator (first: 20) '
            '@filter(has(reply_of)) { reply_of '
            '{ has_tag { tag_name } } } } } }',
    "IC13": '{ path as shortest(from: %(p)s, to: %(p2)s) { knows } '
            'p(func: uid(path)) { first_name } }',
    "IC14": '{ path as shortest(from: %(p)s, to: %(p2)s, numpaths: 2) '
            '{ knows @facets(weight) } }',
}


def _zipf_ranks(rng, n: int, count: int, a: float) -> np.ndarray:
    """`count` ranks in [0, n) with P(rank r) proportional to 1/(r+1)^a."""
    w = 1.0 / np.power(np.arange(1, n + 1, dtype=np.float64), a)
    return rng.choice(n, size=count, p=w / w.sum())


class Mix:
    def __init__(self, data: dict, params: dict, seed: int):
        self.data, self.params = data, params
        self.seed = seed
        freq = params["frequency"]
        names = sorted(freq, key=lambda k: int(k[2:]))
        share = np.array([1.0 / freq[k] for k in names])
        self.names, self.share = names, share / share.sum()

    def _template_multiset(self, count: int) -> list:
        """`count` template names in the shares' proportions, largest
        remainders first: the same multiset for every seed."""
        exact = self.share * count
        base = np.floor(exact).astype(int)
        rest = count - int(base.sum())
        for i in np.argsort(-(exact - base), kind="stable")[:rest]:
            base[i] += 1
        return [n for n, c in zip(self.names, base) for _ in range(c)]

    def requests(self, count: int, stream: int = 0, names=None,
                 persons=None) -> list:
        """`count` requests; `stream` separates warm-up from the window;
        `names`/`persons` fix the templates and persons (warm-up).

        Which templates, which persons of the structure and every other
        parameter belong to the mix (its `schedule_seed`): every run
        offers the same work. The run's seed made the data (what those
        persons' uids are, what they are called, when their messages were
        written) and picks the order the requests come in."""
        sched = np.random.default_rng(
            [int(self.params["schedule_seed"]), stream])
        d = self.data
        everyone = np.asarray(d["person_of_structure"])
        order = sched.permutation(len(everyone))   # which persons are hot
        drawn = names is None
        if drawn:
            names = self._template_multiset(count)
            names = [names[i] for i in sched.permutation(count)]
        a = float(self.params.get("person_zipf_a", 1.0))
        p = everyone[order[_zipf_ranks(sched, len(everyone), count, a)]]
        if persons is not None:
            p = np.asarray(persons)
        p2 = everyone[sched.integers(0, len(everyone), count)]
        ts_all = d["creation_ts"]
        out = []
        for i, name in enumerate(names):
            city, city2 = sched.choice(len(gen.CITIES), 2, replace=False)
            pr = {"p": int(p[i]), "p2": int(p2[i]),
                  "fn": gen.FIRST_NAMES[sched.integers(len(gen.FIRST_NAMES))],
                  "city": gen.CITIES[city], "city2": gen.CITIES[city2],
                  # a quantile of the messages' times: the same share of
                  # them passes the filter in every run
                  "ts": int(ts_all[sched.integers(len(ts_all))]),
                  "tag": f"tag_{sched.integers(len(d['tag_uids']))}",
                  "org": f"org_{sched.integers(len(d['org_uids']))}",
                  "year": int(sched.integers(1950, 2005))}
            if pr["p2"] == pr["p"]:
                pr["p2"] = int(everyone[(i + 1) % len(everyone)])
                if pr["p2"] == pr["p"]:
                    pr["p2"] = int(everyone[(i + 2) % len(everyone)])
            q = TEMPLATES[name] % {**pr, "p": hex(pr["p"]),
                                   "p2": hex(pr["p2"])}
            out.append({"path": "/query", "ctype": "application/dql",
                        "body": q.encode(), "queries": 1,
                        "meta": {"template": name, "params": pr}})
        if drawn:
            order = np.random.default_rng(
                [self.seed, 3, stream]).permutation(count)
            out = [out[i] for i in order]
        return out

    def warm_requests(self, window_count: int = 0) -> list:
        """Device buffers are sized by the largest neighbourhood seen and
        only grow, so the person with the most friends, the one with the
        most friends of friends and the one with the most messages go
        through every template first. Then one pass of another draw of
        the same mix, as long as the window: the window's own requests
        stay unseen, as independent users' requests are."""
        d = self.data
        n = len(d["person_uids"])
        deg = np.bincount(d["knows"][:, 0] - 1, minlength=n)
        fof = np.bincount(d["knows"][:, 0] - 1, minlength=n,
                          weights=deg[d["knows"][:, 1] - 1])
        msgs = np.bincount(d["has_creator"][:, 1] - 1, minlength=n)
        big = []
        for rank in (deg, fof, msgs):
            top = int(np.argmax(rank)) + 1
            if top not in big:
                big.append(top)
        # IC13/IC14 walk on the host: no device buffer to grow for them
        fused = [t for t in self.names if t not in ("IC13", "IC14")]
        names = [t for t in fused for _ in big]
        heavy = self.requests(len(names), stream=2, names=names,
                              persons=big * len(fused))
        return heavy + self.requests(window_count, stream=1)

    def split(self, request: dict, data) -> list:
        """(meta, answer) pairs of one finished request."""
        return [(request["meta"], data)]


def make(data: dict, params: dict, seed: int) -> Mix:
    return Mix(data, params, seed)
