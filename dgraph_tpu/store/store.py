"""The posting store: uid vocabulary + predicate-sharded CSR blocks.

Reference parity: `posting/` (posting lists keyed `(predicate, uid)`,
`posting/list.go List.Uids/Value`, `posting/index.go` secondary indexes) and
`codec/` (compact uid blocks). Where the reference stores one Badger entry
per `(pred, uid)` holding a varint-packed posting list, this store keeps one
**CSR block per predicate per direction** over a dense int32 *rank* space:

    uids[int64, N]            sorted global uid vocabulary (rank = position)
    indptr[int32, N+1]        per-predicate row offsets
    indices[int32, nnz]       object ranks, sorted within each row

Rank space is what lives in HBM; 64-bit uids exist only at the host
boundary (JSON in/out). Compactness comes from int32 ranks + sharding, not
varint blocks — the decode step the reference burns CPU on simply doesn't
exist here.

Scalar values ride columnar `(subj_ranks, values)` pairs sorted by subject;
string-ish indexes are host-side inverted dicts (token → sorted rank
array), numeric/datetime comparisons use the sorted columns directly.

This object is an immutable snapshot at a commit timestamp; the MVCC layer
(store/mvcc.py) layers transactional deltas above it and rebuilds blocks on
rollup, mirroring the reference's immutable-layer + mutable-delta design.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import jax
import numpy as np

from dgraph_tpu.store.schema import PredicateSchema, Schema
from dgraph_tpu.store.tok import tokens_for
from dgraph_tpu.store.types import NUMPY_DTYPE, Kind, convert

TYPE_PRED = "dgraph.type"


@dataclass
class EdgeRel:
    """One direction of a uid predicate as CSR over rank space."""

    indptr: np.ndarray  # int32 [N+1]
    indices: np.ndarray  # int32 [nnz], sorted within each row

    @property
    def nnz(self) -> int:
        return int(self.indices.shape[0])

    def degree(self, ranks: np.ndarray) -> np.ndarray:
        return self.indptr[ranks + 1] - self.indptr[ranks]

    def row(self, rank: int) -> np.ndarray:
        return self.indices[self.indptr[rank]:self.indptr[rank + 1]]


@dataclass
class ValueColumn:
    """Scalar predicate values, columnar, sorted by subject rank.

    `subj` may repeat for list-valued predicates. (Reference: value
    postings in posting/list.go, `ValueFor`.)
    """

    subj: np.ndarray  # int32 [k] sorted
    vals: np.ndarray  # typed per schema kind

    def get(self, rank: int) -> list:
        lo = np.searchsorted(self.subj, rank, side="left")
        hi = np.searchsorted(self.subj, rank, side="right")
        return list(self.vals[lo:hi])

    def get_many(self, ranks: np.ndarray) -> dict[int, list]:
        """Values for a whole batch of ranks in two searchsorted calls
        (the render path's replacement for per-node get()); ranks with
        no value are absent from the result."""
        ranks = np.asarray(ranks)
        lo = np.searchsorted(self.subj, ranks, side="left")
        hi = np.searchsorted(self.subj, ranks, side="right")
        out: dict[int, list] = {}
        single = (hi - lo) == 1  # the common, fully-vectorizable case
        if single.any():
            # iterate the numpy array, NOT .tolist(): tolist() would
            # down-convert np scalars (datetime64 → datetime) and change
            # downstream JSON rendering
            out.update((int(r), [v]) for r, v in
                       zip(ranks[single].tolist(), self.vals[lo[single]]))
        multi = (hi - lo) > 1
        for r, l, h in zip(ranks[multi].tolist(), lo[multi].tolist(),
                           hi[multi].tolist()):
            out[int(r)] = list(self.vals[l:h])
        return out

    def has(self) -> np.ndarray:
        """Sorted unique ranks that have a value."""
        return np.unique(self.subj)

    def order_codes(self) -> np.ndarray | None:
        """int32[k]: each value's place among the column's distinct
        values, ascending: a key that orders rows as the values do (equal
        values, equal codes) without a string compared per query. For a
        column of `str` alone, else None. Computed once a column."""
        if not hasattr(self, "_codes"):
            self._codes = None
            vals = self.vals.tolist() if self.vals.dtype.kind == "O" else ()
            if vals and set(map(type, vals)) == {str}:
                self._codes = np.unique(
                    np.array(vals), return_inverse=True)[1].astype(np.int32)
        return self._codes


@dataclass
class FacetCol:
    """Edge facets for one key, columnar by edge position.

    Reference: facets stored per posting (pb.Posting.Facets); here a
    sparse column aligned to `EdgeRel.indices` positions — the layout the
    hop kernel's `edge_pos` output gathers from (ops/hop.py)."""

    pos: np.ndarray   # sorted int64 positions into fwd.indices
    # facet values: an object array (what a mutation folds to), or one
    # typed column (int/float/bool dtype) where a bulk loader hands the
    # values over as an array: 68 M Python objects are never made
    vals: np.ndarray

    def _locate(self, positions: np.ndarray):
        """(clamped indexes, hit mask) for edge positions — the one
        sorted-position lookup both accessors share."""
        idx = np.searchsorted(self.pos, positions)
        idx_c = np.minimum(idx, max(len(self.pos) - 1, 0))
        hit = (len(self.pos) > 0) & (self.pos[idx_c] == positions)
        return np.atleast_1d(idx_c), np.atleast_1d(hit)

    def get(self, positions: np.ndarray) -> list:
        """Facet values at edge positions; None where absent."""
        idx_c, hit = self._locate(positions)
        if not len(self.pos):
            return [None] * len(hit)
        vals = self.vals[idx_c]
        if vals.dtype != object:       # typed column: Python scalars out
            vals = vals.tolist()
        return [v if h else None for v, h in zip(vals, hit.tolist())]

    def numeric_at(self, positions: np.ndarray):
        """(values float64, hit mask) at edge positions — the vectorized
        form weighted shortest-path relaxation batches over (reference:
        the weight facet read per relaxed edge). None unless EVERY value
        is genuinely numeric (bool/int/float — numeric STRINGS must not
        parse here: the per-value path treats them as weight 1, and the
        two paths must agree). The float cast computes once."""
        if not hasattr(self, "_num"):
            if self.vals.dtype.kind in "biuf" or all(
                    isinstance(v, (bool, int, float, np.integer,
                                   np.floating, np.bool_))
                    for v in self.vals):
                self._num = self.vals.astype(np.float64)
            else:
                self._num = None
        if self._num is None or not len(self.pos):
            return None
        idx_c, hit = self._locate(positions)
        return self._num[idx_c], hit

    def int_values(self) -> np.ndarray | None:
        """The column as integers, aligned with `pos`, where EVERY value
        is an integer (a bool is not: it is a flag, and the host's
        relaxation reads it as a float); else None. A typed integer
        column is handed over as it is, whatever its width; an object
        column is cast to int64, once. What the weighted lane program's
        slot-aligned weights are built from (engine/batch.py)."""
        if not hasattr(self, "_int"):
            vals, self._int = self.vals, None
            if vals.dtype.kind in "iu":
                self._int = vals
            elif vals.dtype == object and all(
                    isinstance(v, (int, np.integer))
                    and not isinstance(v, (bool, np.bool_)) for v in vals):
                try:
                    self._int = np.array(vals.tolist(), np.int64)
                except OverflowError:
                    pass
        return self._int

    def int_range(self) -> tuple | None:
        """(least, largest) of int_values(); None where it has none, or
        the column is empty. Computed once: a planner asks a query."""
        if not hasattr(self, "_int_range"):
            vals = self.int_values()
            self._int_range = (
                (int(vals.min()), int(vals.max()))
                if vals is not None and len(vals) else None)
        return self._int_range


@dataclass
class PredicateData:
    schema: PredicateSchema
    fwd: EdgeRel | None = None
    rev: EdgeRel | None = None
    # lang tag → column; "" is the untagged default column
    vals: dict[str, ValueColumn] = field(default_factory=dict)
    # tokenizer → token → sorted int32 rank array
    index: dict[str, dict[str, np.ndarray]] = field(default_factory=dict)
    # facet key → edge-position column (forward direction)
    efacets: dict[str, FacetCol] = field(default_factory=dict)
    # facet key → {subject rank: value} for value postings
    vfacets: dict[str, dict[int, object]] = field(default_factory=dict)
    # reverse-CSR position → forward-CSR position: facets live on the
    # forward posting, but the reference serves them on ~pred expansions
    # too; this map makes reverse edge_pos facet-addressable
    rev_pos: np.ndarray | None = None

    def build_rev_pos(self, n: int) -> None:
        if self.rev is None or self.fwd is None or not self.rev.nnz:
            return
        o_arr = np.repeat(np.arange(n, dtype=np.int64),
                          np.diff(self.rev.indptr).astype(np.int64))
        s_arr = self.rev.indices.astype(np.int64)
        # both CSRs are sorted by (subject, object), so the flattened
        # (s * n + o) keys of the forward edges are ascending
        fwd_src = np.repeat(np.arange(n, dtype=np.int64),
                            np.diff(self.fwd.indptr).astype(np.int64))
        fwd_keys = fwd_src * n + self.fwd.indices.astype(np.int64)
        self.rev_pos = np.searchsorted(fwd_keys, s_arr * n + o_arr)


def _register_device_caches(store) -> None:
    """Join the snapshot's HBM caches (`_device` CSR blocks,
    `_sharded` mesh stacks) to the process memory governor. Callbacks
    close over a weakref — a dropped snapshot's registrations die with
    it. Eviction pops oldest-inserted (first-use order ≈ coldest);
    `device_rel`/`sharded_rel` simply re-place an evicted tablet."""
    import weakref

    from dgraph_tpu.utils import memgov

    ref = weakref.ref(store)

    def _dict_of(attr):
        s = ref()
        return getattr(s, attr, None) if s is not None else None

    def make_cbs(attr):
        def nbytes():
            d = _dict_of(attr)
            if not d:
                return 0
            return sum(memgov.estimate_nbytes(v)
                       for v in list(d.values()))

        def evict_one():
            d = _dict_of(attr)
            if not d:
                return 0
            try:
                v = d.pop(next(iter(d)))
            except (KeyError, StopIteration):
                return 0
            return memgov.estimate_nbytes(v)

        return nbytes, evict_one

    def vec_detail():
        """Resident vector stacks with their dims — the /debug/memory
        rows that make eviction thrash on `store.vec` visible."""
        s = ref()
        if s is None:
            return []
        out = []
        for (pred, kind), v in sorted(getattr(s, "_vec_dev", {}).items()):
            if kind == "mesh":
                _subj, vecs, rows = v
                out.append({"pred": pred, "placement": "mesh",
                            "shards": int(vecs.shape[0]),
                            "rows": int(rows),
                            "dim": int(vecs.shape[-1])})
            else:
                _subj, vecs = v
                out.append({"pred": pred, "placement": "device",
                            "rows": int(vecs.shape[0]),
                            "dim": int(vecs.shape[1])})
        return out

    for attr, name in (("_device", "store.device"),
                       ("_sharded", "store.sharded"),
                       ("_vec_dev", "store.vec")):
        nbytes, evict_one = make_cbs(attr)
        memgov.GOVERNOR.register(
            name, "device", nbytes, evict_one, owner=store,
            detail_cb=vec_detail if name == "store.vec" else None)


class Store:
    """Immutable posting-store snapshot (host arrays + device cache)."""

    def __init__(self, uids: np.ndarray, schema: Schema,
                 preds: dict[str, PredicateData]):
        assert uids.dtype == np.int64 and np.all(np.diff(uids) > 0)
        self.uids = uids
        self.schema = schema
        self.preds = preds
        self._device: dict[tuple[str, str], tuple[jax.Array, jax.Array]] = {}
        self._sharded: dict = {}
        self._sharded_mesh = None
        # float32vector tablets: host stacks (cheap, rebuilt from the
        # value column) and device/mesh placements (governed: store.vec)
        self._vec_tab: dict = {}
        self._vec_dev: dict = {}
        self._vec_mesh = None
        # keys ever placed: a rebuild of one of these is a RE-placement
        # (memgov evicted it, or the mesh changed) — metered so
        # eviction thrash on the vector stacks is visible
        self._vec_placed: set = set()
        self._empty_rel = EdgeRel(np.zeros(self.n_nodes + 1, np.int32),
                                  np.zeros(0, np.int32))
        _register_device_caches(self)

    def rev_to_fwd_pos(self, pred: str, pos: np.ndarray) -> np.ndarray:
        """Map reverse-CSR edge positions to their forward positions (the
        space facet columns key on). Built lazily per predicate."""
        pd = self.preds.get(pred)
        if pd is None or not len(pos):
            return pos
        if pd.rev_pos is None:
            pd.build_rev_pos(self.n_nodes)
        return pd.rev_pos[pos] if pd.rev_pos is not None else pos

    # -- uid ↔ rank ---------------------------------------------------------
    @property
    def n_nodes(self) -> int:
        return int(self.uids.shape[0])

    def rank_of(self, uid_arr) -> np.ndarray:
        """Global uids → ranks; -1 for unknown uids."""
        uid_arr = np.asarray(uid_arr, np.int64)
        pos = np.searchsorted(self.uids, uid_arr)
        pos_c = np.minimum(pos, self.n_nodes - 1) if self.n_nodes else pos * 0
        ok = self.n_nodes > 0
        hit = ok & (self.uids[pos_c] == uid_arr) if ok else np.zeros_like(uid_arr, bool)
        return np.where(hit, pos_c, -1).astype(np.int32)

    def uid_of(self, ranks) -> np.ndarray:
        return self.uids[np.asarray(ranks)]

    # -- relations ----------------------------------------------------------
    def rel(self, pred: str, reverse: bool = False) -> EdgeRel:
        p = self.preds.get(pred)
        r = (p.rev if reverse else p.fwd) if p else None
        return r if r is not None else self._empty_rel

    def device_rel(self, pred: str, reverse: bool = False):
        """CSR block on the default device, cached (HBM residency —
        reference analog: posting-list cache, posting/lists.go)."""
        key = (pred, "rev" if reverse else "fwd")
        out = self._device.get(key)
        if out is None:
            r = self.rel(pred, reverse)
            out = self._device[key] = (jax.device_put(r.indptr),
                                       jax.device_put(r.indices))
            from dgraph_tpu.utils import memgov
            # `out` is returned even if the pass evicts it: the caller's
            # launch still holds the arrays; next lookup re-places
            memgov.GOVERNOR.maybe_evict("device")
        return out

    def sharded_rel(self, pred: str, reverse: bool, mesh):
        """Row-sharded CSR placed on a mesh, cached per (pred, direction)
        — the tablet residency of the distributed path (reference analog:
        worker/groups.go tablet ownership; here every device owns a row
        slab of every predicate, SURVEY §2.3 S1)."""
        from dgraph_tpu.parallel.pshard import device_put_rel, shard_rel
        key = (pred, "rev" if reverse else "fwd")
        cache = getattr(self, "_sharded", None)
        if cache is None or self._sharded_mesh is not mesh:
            cache = {}
            self._sharded = cache
            self._sharded_mesh = mesh
        out = cache.get(key)
        if out is None:
            srel = shard_rel(self.rel(pred, reverse), mesh.devices.size)
            out = cache[key] = device_put_rel(srel, mesh)
            self._note_mesh_residency(srel)
            from dgraph_tpu.utils import memgov
            memgov.GOVERNOR.maybe_evict("device")
        return out

    def _note_mesh_residency(self, srel) -> None:
        """Residency gauges for a newly placed sharded tablet:
        `mesh_shard_bytes{shard=}` accumulates each shard's resident
        bytes across this snapshot's cached tablets (padded widths —
        what actually occupies device memory), `mesh_shard_balance`
        tracks max/mean TRUE edges per shard (1.0 = perfectly
        balanced; the padding hides imbalance from the bytes gauge)."""
        from dgraph_tpu.utils.metrics import METRICS
        ptr = np.asarray(srel.indptr_s)
        d = ptr.shape[0]
        per_bytes = (ptr[0].nbytes
                     + np.asarray(srel.indices_s[0]).nbytes + 4)
        nnz = ptr[:, -1].astype(np.int64)
        tot_b = getattr(self, "_mesh_shard_bytes", None)
        if tot_b is None or len(tot_b) != d:
            tot_b = self._mesh_shard_bytes = np.zeros(d, np.int64)
            self._mesh_shard_nnz = np.zeros(d, np.int64)
        tot_b += per_bytes
        self._mesh_shard_nnz += nnz
        for s in range(d):
            METRICS.set_gauge("mesh_shard_bytes", float(tot_b[s]),
                              shard=s)
        mean = float(self._mesh_shard_nnz.mean())
        if mean > 0:
            METRICS.set_gauge("mesh_shard_balance",
                              float(self._mesh_shard_nnz.max()) / mean)

    # -- vector tablets ------------------------------------------------------
    def vec_tablet(self, pred: str):
        """Host `[n, d]` embedding stack of a float32vector predicate,
        built lazily from the value column and cached on this snapshot.
        None for non-vector predicates."""
        t = self._vec_tab.get(pred)
        if t is None:
            ps = self.schema.peek(pred)
            if ps is None or ps.kind != Kind.VECTOR:
                return None
            from dgraph_tpu.store import vec as _vec
            t = self._vec_tab[pred] = _vec.build_tablet(
                self.value_col(pred), ps.vector_dim)
        return t

    def vec_device(self, pred: str):
        """Embedding stack on the default device, cached + governed
        under `store.vec` (the device_rel residency discipline)."""
        key = (pred, "dev")
        out = self._vec_dev.get(key)
        if out is None:
            t = self.vec_tablet(pred)
            out = self._vec_dev[key] = (jax.device_put(t.subj),
                                        jax.device_put(t.vecs))
            if key in self._vec_placed:
                from dgraph_tpu.utils.metrics import METRICS
                METRICS.inc("vec_replacements_total", kind="device")
            self._vec_placed.add(key)
            from dgraph_tpu.utils import memgov
            memgov.GOVERNOR.maybe_evict("device")
        return out

    def vec_sharded(self, pred: str, mesh):
        """Row-sharded embedding stack placed on a mesh, cached per
        predicate (the sharded_rel tablet discipline — residency
        carried across folds while the mesh object is unchanged).
        Shard-stacked layout: subj `[d, rows]` padded with sentinel
        ranks, vecs `[d, rows, dim]` padded with zero rows. Returns
        (subj_s, vecs_s, rows_per_shard)."""
        from dgraph_tpu.ops.uidalgebra import SENTINEL32
        from dgraph_tpu.parallel.mesh import shard_leading
        key = (pred, "mesh")
        if self._vec_mesh is not mesh:
            for k in [k for k in self._vec_dev if k[1] == "mesh"]:
                self._vec_dev.pop(k, None)
            self._vec_mesh = mesh
        out = self._vec_dev.get(key)
        if out is None:
            t = self.vec_tablet(pred)
            d = int(mesh.devices.size)
            rows = -(-max(t.rows, 1) // d)
            pad = rows * d - t.rows
            subj = np.concatenate(
                [t.subj, np.full(pad, SENTINEL32, np.int32)])
            vecs = np.concatenate(
                [t.vecs, np.zeros((pad, t.dim), np.float32)])
            sh = shard_leading(mesh)
            out = self._vec_dev[key] = (
                jax.device_put(subj.reshape(d, rows), sh),
                jax.device_put(vecs.reshape(d, rows, t.dim), sh),
                rows)
            if key in self._vec_placed:
                from dgraph_tpu.utils.metrics import METRICS
                METRICS.inc("vec_replacements_total", kind="mesh")
            self._vec_placed.add(key)
            from dgraph_tpu.utils import memgov
            memgov.GOVERNOR.maybe_evict("device")
        return out

    # -- values -------------------------------------------------------------
    def value_col(self, pred: str, lang: str = "") -> ValueColumn | None:
        p = self.preds.get(pred)
        if not p:
            return None
        return p.vals.get(lang)

    def values_for(self, pred: str, rank: int, lang: str = "") -> list:
        """Values of `pred` on `rank`. `lang` may be a fallback chain like
        "en:fr:." (reference: language preference lists; "." = ANY
        language, untagged preferred — gql lang fallback semantics)."""
        if not lang:
            col = self.value_col(pred, "")
            return col.get(rank) if col is not None else []
        pd = self.preds.get(pred)
        for l in lang.split(":"):
            if l == ".":
                langs = [""] + sorted(k for k in (pd.vals if pd else {})
                                      if k)
            else:
                langs = [l]
            for lk in langs:
                col = self.value_col(pred, lk)
                if col is not None:
                    vs = col.get(rank)
                    if vs:
                        return vs
        return []

    def values_for_many(self, pred: str, ranks: np.ndarray,
                        lang: str = "") -> dict[int, list]:
        """Batched values_for over a rank set — the JSON render path
        fetches each (level, predicate) column ONCE instead of a
        searchsorted pair per node. Same per-rank lang-chain fallback
        semantics as values_for."""
        ranks = np.asarray(ranks)
        if not lang:
            col = self.value_col(pred, "")
            return col.get_many(ranks) if col is not None else {}
        pd = self.preds.get(pred)
        out: dict[int, list] = {}
        remaining = ranks
        for l in lang.split(":"):
            if not len(remaining):
                break
            if l == ".":
                langs = [""] + sorted(k for k in (pd.vals if pd else {})
                                      if k)
            else:
                langs = [l]
            for lk in langs:
                if not len(remaining):
                    break
                col = self.value_col(pred, lk)
                if col is None:
                    continue
                got = col.get_many(remaining)
                if got:
                    out.update(got)
                    keep = np.array([r not in got
                                     for r in remaining.tolist()])
                    remaining = remaining[keep]
        return out

    def has_ranks(self, pred: str) -> np.ndarray:
        """Sorted ranks of subjects that have `pred` (edges or values);
        `~pred` counts incoming edges. Reference: `has(pred)` root function."""
        reverse = pred.startswith("~")
        p = self.preds.get(pred.lstrip("~"))
        if not p:
            return np.zeros(0, np.int32)
        if reverse:
            rel = p.rev
            if rel is None:
                return np.zeros(0, np.int32)
            deg = rel.indptr[1:] - rel.indptr[:-1]
            return np.nonzero(deg > 0)[0].astype(np.int32)
        parts = []
        if p.fwd is not None:
            deg = p.fwd.indptr[1:] - p.fwd.indptr[:-1]
            parts.append(np.nonzero(deg > 0)[0].astype(np.int32))
        for col in p.vals.values():
            parts.append(col.has().astype(np.int32))
        if not parts:
            return np.zeros(0, np.int32)
        return np.unique(np.concatenate(parts))

    # -- facets -------------------------------------------------------------
    def edge_facets(self, pred: str, positions: np.ndarray,
                    keys=None) -> dict[str, list]:
        """Facet values per requested key at forward edge positions.
        `keys=None` → every key present (reference: @facets with no args)."""
        p = self.preds.get(pred)
        if not p or not p.efacets:
            return {}
        use = p.efacets.keys() if keys is None else \
            [k for k in keys if k in p.efacets]
        return {k: p.efacets[k].get(np.asarray(positions, np.int64))
                for k in use}

    def value_facets(self, pred: str, rank: int, keys=None) -> dict:
        """Facets on a value posting (reference: facets on scalar edges)."""
        p = self.preds.get(pred)
        if not p or not p.vfacets:
            return {}
        use = p.vfacets.keys() if keys is None else \
            [k for k in keys if k in p.vfacets]
        out = {}
        for k in use:
            if rank in p.vfacets[k]:
                out[k] = p.vfacets[k][rank]
        return out

    def index_lookup(self, pred: str, tokenizer: str, token: str) -> np.ndarray:
        """token → sorted rank posting list (reference: index key get)."""
        p = self.preds.get(pred)
        if not p:
            return np.zeros(0, np.int32)
        return p.index.get(tokenizer, {}).get(token, np.zeros(0, np.int32))

    def predicates_of_types(self, type_names) -> list[str]:
        fields: list[str] = []
        for t in type_names:
            td = self.schema.types.get(t)
            if td:
                fields.extend(td.fields)
        seen = set()
        return [f for f in fields if not (f in seen or seen.add(f))]


class StoreBuilder:
    """Accumulates triples, then finalizes into an immutable Store.

    Plays the role of the reference's bulk-load reduce phase
    (dgraph/cmd/bulk/reduce.go): group edges by predicate, sort, emit
    packed blocks — here CSR + columnar values + inverted indexes.
    """

    def __init__(self, schema: Schema | None = None):
        self.schema = schema or Schema()
        self.schema.get(TYPE_PRED).kind = Kind.STRING
        self.schema.get(TYPE_PRED).is_list = True
        if not self.schema.get(TYPE_PRED).index_tokenizers:
            self.schema.get(TYPE_PRED).index_tokenizers = ("exact",)
        self._edges: dict[str, list[tuple[int, int]]] = {}
        self._values: dict[tuple[str, str], list[tuple[int, object]]] = {}
        self._known_uids: set[int] = set()
        # facets keyed by the (subject, object) uid pair / subject uid
        self._efacets: dict[str, dict[tuple[int, int], dict]] = {}
        self._vfacets: dict[str, dict[int, dict]] = {}

    def add_edge(self, subj: int, pred: str, obj: int,
                 facets: dict | None = None) -> None:
        ps = self.schema.get(pred)
        if ps.kind == Kind.DEFAULT and not any(
                p == pred for p, _ in self._values):
            ps.kind = Kind.UID
        elif ps.kind != Kind.UID:
            raise ValueError(f"predicate {pred!r} holds {ps.kind} values, not uids")
        self._edges.setdefault(pred, []).append((subj, obj))
        if facets:
            self._efacets.setdefault(pred, {})[(subj, obj)] = dict(facets)
        self._known_uids.add(subj)
        self._known_uids.add(obj)

    def add_edges(self, pred: str, subjs, objs) -> None:
        """Vectorised bulk form of add_edge (no facets): the bulk-load
        mapper hands whole columns over instead of 10^7 Python calls."""
        ps = self.schema.get(pred)
        if ps.kind == Kind.DEFAULT and not any(
                p == pred for p, _ in self._values):
            ps.kind = Kind.UID
        elif ps.kind != Kind.UID:
            raise ValueError(
                f"predicate {pred!r} holds {ps.kind} values, not uids")
        subjs = np.asarray(subjs, np.int64)
        objs = np.asarray(objs, np.int64)
        self._edges.setdefault(pred, []).extend(
            zip(subjs.tolist(), objs.tolist()))
        self._known_uids.update(subjs.tolist())
        self._known_uids.update(objs.tolist())

    def touch(self, uid: int) -> None:
        """Register a uid in the vocabulary without any posting (cluster
        vocab sync: nodes whose data lives on other groups still occupy a
        rank so the dense rank space is identical everywhere)."""
        self._known_uids.add(int(uid))

    def touch_many(self, uids) -> None:
        self._known_uids.update(int(u) for u in uids)

    def add_value(self, subj: int, pred: str, value, lang: str = "",
                  facets: dict | None = None) -> None:
        ps = self.schema.get(pred)
        if ps.kind == Kind.UID or pred in self._edges:
            raise ValueError(f"predicate {pred!r} is a uid predicate")
        if ps.kind == Kind.DEFAULT and not isinstance(value, str):
            # auto-type from first value (reference: first-mutation typing)
            if isinstance(value, bool):
                ps.kind = Kind.BOOL
            elif isinstance(value, int):
                ps.kind = Kind.INT
            elif isinstance(value, float):
                ps.kind = Kind.FLOAT
        if ps.kind == Kind.VECTOR:
            # convert NOW so a width mismatch is refused at schema time
            # (load time), not discovered mid-query; first vector fixes
            # the width when the schema didn't declare @dim
            value = convert(value, Kind.VECTOR)
            if ps.vector_dim == 0:
                ps.vector_dim = int(len(value))
            elif len(value) != ps.vector_dim:
                raise ValueError(
                    f"predicate {pred!r}: vector of dim {len(value)} "
                    f"does not match schema dim {ps.vector_dim}")
        self._values.setdefault((pred, lang), []).append((subj, value))
        if facets:
            self._vfacets.setdefault(pred, {})[subj] = dict(facets)
        self._known_uids.add(subj)

    def add_type(self, subj: int, type_name: str) -> None:
        self.add_value(subj, TYPE_PRED, type_name)

    def finalize(self) -> Store:
        uids = np.array(sorted(self._known_uids), np.int64)
        n = len(uids)
        rank = {int(u): i for i, u in enumerate(uids)}

        preds: dict[str, PredicateData] = {}
        for pred, pairs in self._edges.items():
            ps = self.schema.get(pred)
            pd = preds.setdefault(pred, PredicateData(schema=ps))
            sr = np.array([(rank[s], rank[o]) for s, o in pairs], np.int32)
            pd.fwd = _csr_from_pairs(sr[:, 0], sr[:, 1], n)
            if ps.reverse:
                pd.rev = _csr_from_pairs(sr[:, 1], sr[:, 0], n)
            # align edge facets to final CSR positions
            fmap = self._efacets.get(pred)
            if fmap:
                by_key: dict[str, list[tuple[int, object]]] = {}
                for (s, o), fd in fmap.items():
                    sr_, or_ = rank[s], rank[o]
                    row = pd.fwd.row(sr_)
                    j = int(np.searchsorted(row, or_))
                    if j >= len(row) or row[j] != or_:
                        continue  # edge was not retained
                    pos = int(pd.fwd.indptr[sr_]) + j
                    for k, v in fd.items():
                        by_key.setdefault(k, []).append((pos, v))
                for k, pv in by_key.items():
                    pv.sort()
                    pd.efacets[k] = FacetCol(
                        pos=np.array([p for p, _ in pv], np.int64),
                        vals=np.array([v for _, v in pv], object))

        for (pred, lang), pairs in self._values.items():
            ps = self.schema.get(pred)
            pd = preds.setdefault(pred, PredicateData(schema=ps))
            kind = ps.kind if ps.kind != Kind.DEFAULT else Kind.STRING
            # dedupe exact (subj, value) repeats (set semantics, as the
            # reference's posting lists are sets); keep list multiplicity
            # for distinct values only
            seen: set = set()
            dpairs = []
            for s, v in pairs:
                cv = convert(v, kind)
                if isinstance(cv, np.datetime64):
                    key = (rank[s], cv.astype("int64").item())
                elif isinstance(cv, np.ndarray):  # vectors: hash bytes
                    key = (rank[s], cv.tobytes())
                else:
                    key = (rank[s], cv)
                if key in seen:
                    continue
                seen.add(key)
                dpairs.append((rank[s], cv))
            subj = np.array([s for s, _ in dpairs], np.int32)
            order = np.argsort(subj, kind="stable")
            subj = subj[order]
            vals = np.empty(len(dpairs), dtype=NUMPY_DTYPE[kind])
            for i, j in enumerate(order):
                vals[i] = dpairs[j][1]
            pd.vals[lang] = ValueColumn(subj=subj, vals=vals)

        for pred, vmap in self._vfacets.items():
            pd = preds.get(pred)
            if pd is None:
                continue
            for s, fd in vmap.items():
                for k, v in fd.items():
                    pd.vfacets.setdefault(k, {})[int(rank[s])] = v

        build_indexes(preds)
        return Store(uids=uids, schema=self.schema, preds=preds)


def build_indexes(preds: dict[str, PredicateData]) -> None:
    """Build inverted token indexes from value columns (reference:
    posting/index.go BuildTokens / RebuildIndex). Shared by StoreBuilder
    and checkpoint load."""
    for pred, pd in preds.items():
        ps = pd.schema
        if not ps.index_tokenizers:
            continue
        for tk in ps.index_tokenizers:
            if tk not in ("exact", "hash", "term", "fulltext", "trigram",
                          "geo"):
                continue  # numeric/datetime ranges use sorted columns
            inv: dict[str, list[int]] = {}
            for lang, col in pd.vals.items():
                for s, v in zip(col.subj, col.vals):
                    for t in tokens_for(tk, v):
                        inv.setdefault(t, []).append(int(s))
            pd.index[tk] = {t: np.unique(np.array(s_list, np.int32))
                            for t, s_list in inv.items()}


def _csr_from_pairs(src: np.ndarray, dst: np.ndarray, n: int) -> EdgeRel:
    """Sorted-by-(src, dst), deduped CSR from edge pairs. Uses the native
    C++ builder when built (native/csr.cpp — the bulk-reduce hot loop);
    numpy otherwise. Outputs are bit-identical either way."""
    if len(src) and n < 2**31:
        from dgraph_tpu import native
        if native.HAVE_NATIVE:
            indptr, indices = native.build_csr(src, dst, n)
            return EdgeRel(indptr=indptr, indices=indices)
    return _csr_from_pairs_np(src, dst, n)


def _csr_from_pairs_np(src: np.ndarray, dst: np.ndarray, n: int) -> EdgeRel:
    order = np.lexsort((dst, src))
    src, dst = src[order], dst[order]
    if len(src):
        keep = np.concatenate([[True], (src[1:] != src[:-1]) | (dst[1:] != dst[:-1])])
        src, dst = src[keep], dst[keep]
    counts = np.bincount(src, minlength=n).astype(np.int32)
    indptr = np.zeros(n + 1, np.int32)
    np.cumsum(counts, out=indptr[1:])
    return EdgeRel(indptr=indptr, indices=dst.astype(np.int32))
