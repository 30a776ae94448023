"""QueryClient — the unified user-side facade over the secret-shared clouds.

One object replaces the nine free query functions: it owns the root PRNG key
(per-query keys derive via ``jax.random.fold_in``, no manual threading), the
backend choice (``repro.api.backends`` registry), the optional MapReduce
executor, and the cost-based selection planner (``repro.api.planner``).
Every query family returns the same :class:`~.plans.QueryResult`.

The client fronts a *registry* of attached relations, matching the paper's
deployment model (§2: the owner outsources secret-shares of a database —
plural relations — once; users then query any of them without the owner in
the loop). ``QueryClient(db, key)`` registers ``db`` under the default
name; ``attach(other_db, name="orders", shards=S)`` registers more, each
with its own sharded dataplane, its own planner statistics and — crucially
— its own root key and query counter, so the per-query key stream of one
relation never depends on traffic to another: a plan sequence submitted to
relation "orders" opens bit-identical rows and ledgers whether or not
"users" traffic interleaves with it (the multi-tenant serving acceptance).

Every plan family executes through the round-structured batch engine
(``repro.core.queries.rounds``): :meth:`QueryClient.run_batch` cost-plans
each query, groups compatible strategies — Count/Select by selection
algorithm, ranges by (bit-width, ``reduce_every``), joins by kind — stacks
their shared predicates and executes each protocol round *once for the
whole group*: one fused device dispatch + one interpolation per match or
Q&A round, one ``ripple_carry`` dispatch per SS-SUB bit-round, and ONE
cross-group ``ss_matmul`` for every oblivious fetch (one_round, tree and
range one-hot matrices *and* PK/FK match matrices stack row-wise).
:meth:`QueryClient.run` is the B = 1 case of the same machinery, so
per-query rows and ``CostLedger`` totals are bit-identical between a batch
and the equivalent sequential calls (asserted by ``tests/test_batch.py``).
"""
from __future__ import annotations

import dataclasses
import itertools
import zlib
from typing import Dict, Iterator, List, Optional, Sequence, Tuple, Union

import jax

from ..core import encoding
from ..core.costs import CostLedger
from ..core.dataplane import (Dispatcher, RelationLike, ShardedRelation,
                              span)
from ..core.engine import SecretSharedDB
from ..core.queries import CardinalityError, aggregate, rounds
from ..core.queries import embed as embed_q
from . import planner as _planner
from .backends import BackendLike, get_backend
from .executor import MapReduceExecutor
from .plans import (AUTO, Aggregate, Between, ColumnRef, Contains, Count,
                    EmbedLookup, Eq, Join, Like, Padding, Plan, Prefix,
                    QueryResult, RangeCount, RangeSelect, Select, Suffix,
                    resolve_column)

#: registry name a bare ``QueryClient(db, key)`` attaches its relation
#: under; single-relation callers never need to spell it.
DEFAULT_RELATION = "default"

#: explanation-cache entries kept per client (FIFO eviction) — a serving
#: frontend explains a bounded set of recurring plan shapes; anything
#: beyond this just recomputes.
EXPLAIN_CACHE_MAX = 128


@dataclasses.dataclass
class AttachedRelation:
    """One registered relation: its shares, dataplane and key stream."""
    name: str
    db: SecretSharedDB
    dataplane: Optional[ShardedRelation]
    root_key: jax.Array
    counter: Iterator[int]

    @property
    def rel(self) -> Union[SecretSharedDB, ShardedRelation]:
        """What the round engine executes against (plane if attached)."""
        return self.dataplane if self.dataplane is not None else self.db

    @property
    def n_shards(self) -> int:
        return self.dataplane.n_shards if self.dataplane is not None else 1


def _as_key(key) -> jax.Array:
    return jax.random.PRNGKey(key) if isinstance(key, int) else key


#: surface shapes of the literal-tile predicates (for error display).
_TILE_SOURCES = {Prefix: "{0}%", Suffix: "%{0}", Contains: "%{0}%"}


def _lower_match(db: SecretSharedDB, where, context: str
                 ) -> Tuple[int, str, Optional[encoding.PatternSpec]]:
    """Lower a Count/Select predicate -> (column, body, spec).

    ``Eq`` — and any wildcard-free ``Like``, provably — lower to the exact
    path (``spec=None``); the other shapes build their
    :class:`~repro.core.encoding.PatternSpec` and validate it against the
    relation's codec here, at plan time, so malformed patterns (interior
    ``%``, ``_`` under a shifted window, tiles longer than the word, empty
    bodies, out-of-alphabet characters) surface as a typed
    :class:`~.planner.PlanNotSupported` before any share moves. Unknown
    predicate classes raise the same — never an ``AttributeError`` from
    duck-typed field access.
    """
    if isinstance(where, Eq):
        return resolve_column(db, where.column), where.pattern, None
    if isinstance(where, Like):
        try:
            kind, body, wild = encoding.parse_like(where.pattern)
            if kind == "exact":
                return resolve_column(db, where.column), body, None
            spec = encoding.PatternSpec(kind, body, wild, where.pattern)
            encoding.encode_pattern_tile(db.codec, spec)
        except (KeyError, ValueError) as e:
            raise _planner.PlanNotSupported(
                where, f"{context} ({e})") from None
        return resolve_column(db, where.column), body, spec
    if isinstance(where, (Prefix, Suffix, Contains)):
        source = _TILE_SOURCES[type(where)].format(where.literal)
        try:
            spec = encoding.PatternSpec(type(where).__name__.lower(),
                                        where.literal, (), source)
            encoding.encode_pattern_tile(db.codec, spec)
        except (KeyError, ValueError) as e:
            raise _planner.PlanNotSupported(
                where, f"{context} ({e})") from None
        return resolve_column(db, where.column), where.literal, spec
    raise _planner.PlanNotSupported(where, context)


def _ripple_every(db: SecretSharedDB, plan: Plan, t_bits: int) -> int:
    """The ``reduce_every`` a range or MIN/MAX plan runs with: the
    planner's choice from (c, base degree, t) where the plan leaves it at
    0, else the plan's own value once the planner has checked it
    (:func:`~.planner.choose_reduce_every`). A column not outsourced in
    binary form keeps the plan's value; the round engine refuses it."""
    if t_bits <= 0:
        return plan.reduce_every
    minmax = isinstance(plan, Aggregate)
    match_degree = ((db.relation.degree + db.base_degree)
                    * db.codec.word_length
                    if minmax and plan.where is not None else None)
    return _planner.choose_reduce_every(
        db.n_shares, db.base_degree, t_bits, plan.reduce_every,
        tournament=minmax, match_degree=match_degree)


def _plan_signature(plan: Plan) -> tuple:
    """Structural cache key for one plan (Join rights key by identity —
    two different share sets are different plans even if equal-valued)."""
    if isinstance(plan, Join):
        return ("Join", id(plan.right), tuple(plan.on), plan.kind,
                plan.padding.rows, plan.padding.values)
    if not dataclasses.is_dataclass(plan):
        # unknown plan classes fail HERE with the clear error, not with
        # dataclasses.fields' opaque TypeError
        raise _planner.PlanNotSupported(plan)
    return (type(plan).__name__,) + tuple(
        getattr(plan, f.name) for f in dataclasses.fields(plan))


@dataclasses.dataclass
class _Slot:
    """One plan's execution state inside a batch."""
    idx: int
    plan: Plan
    key: jax.Array
    ledger: CostLedger = dataclasses.field(default_factory=CostLedger)
    strategy: str = ""
    known_count: Optional[int] = None
    column: int = -1
    pattern: str = ""
    spec: Optional[encoding.PatternSpec] = None
    pred_column: Optional[int] = None
    fetch_key: Optional[jax.Array] = None


@dataclasses.dataclass
class _BatchWork:
    """One relation's in-flight ``run_batch`` state, split at the fetch.

    ``_prepare_batch`` runs every pre-fetch round and parks the deferred
    cross-group fetch jobs here; ``_finish_batch`` consumes the fused
    fetch output and the post-fetch rounds. The split lets
    :meth:`QueryClient.run_batch_multi` drive several relations' batches
    to the fetch boundary and fuse their cloud-side matmuls into one
    dispatch wave.
    """
    plans: Sequence[Plan]
    db: SecretSharedDB
    rel: "RelationLike"
    results: Dict[int, QueryResult]
    fetch_jobs: List[rounds.FetchJob]
    fetch_meta: List[Tuple[_Slot, str, List[int]]]
    join_jobs: List[rounds.JoinJob]
    join_entries: List[rounds.FetchEntry]
    pkfk_grp: List[_Slot]
    equi_grp: List[_Slot]


class QueryClient:
    """Authorized-user facade over the outsourced relation registry.

    db:              the user's secret-shared relation (``core.outsource``)
                     — registered under :data:`DEFAULT_RELATION`; pass
                     ``None`` to start with an empty registry and
                     ``attach(..., name=...)`` relations explicitly.
    key:             root PRNG key (or int seed); per-query keys derive via
                     ``fold_in`` so identical plans replay identically.
                     Each attached relation gets its own independent key
                     stream (seeded from this root unless ``attach`` is
                     given an explicit ``key=``).
    backend:         registered backend name or Backend instance.
    executor:        optional :class:`MapReduceExecutor` — fans every
                     cloud-side map phase out over fault-tolerant splits.
    round_cost_bits: planner latency weight — how many communication bits
                     one extra protocol round is worth to this user.
    """

    def __init__(self, db: Union[SecretSharedDB, ShardedRelation,
                                 None] = None, key=0, *,
                 backend: BackendLike = "jnp",
                 executor: Optional[MapReduceExecutor] = None,
                 round_cost_bits: int = 0):
        if isinstance(key, int):
            key = jax.random.PRNGKey(key)
        self._root_key = key
        self._relations: Dict[str, AttachedRelation] = {}
        # sig -> (BatchExplanation, pinned Join right relations)
        self._explanations: Dict[tuple, tuple] = {}
        if db is not None:
            plane = db if isinstance(db, ShardedRelation) else None
            self._relations[DEFAULT_RELATION] = AttachedRelation(
                DEFAULT_RELATION, plane.db if plane is not None else db,
                plane, key, itertools.count())
        self.backend = get_backend(backend)
        if executor is not None:
            self.backend = executor.wrap(self.backend)
        self.executor = executor
        self.round_cost_bits = round_cost_bits

    # -- registry -----------------------------------------------------------
    @property
    def relations(self) -> Tuple[str, ...]:
        """Attached relation names, in registration order."""
        return tuple(self._relations)

    def _entry(self, relation: Optional[str] = None) -> AttachedRelation:
        if relation is None:
            ent = self._relations.get(DEFAULT_RELATION)
            if ent is not None:
                return ent
            if len(self._relations) == 1:
                return next(iter(self._relations.values()))
            if not self._relations:
                raise ValueError("no relation attached — pass a db to "
                                 "QueryClient(...) or call attach(db, "
                                 "name=...)")
            raise ValueError(f"several relations attached "
                             f"({list(self._relations)}) and none is "
                             f"{DEFAULT_RELATION!r} — pass relation=")
        try:
            return self._relations[relation]
        except KeyError:
            raise KeyError(f"unknown relation {relation!r}; attached: "
                           f"{list(self._relations)}") from None

    @property
    def db(self) -> Optional[SecretSharedDB]:
        """The default relation's shares (None with an empty registry)."""
        ent = (self._relations.get(DEFAULT_RELATION)
               or next(iter(self._relations.values()), None))
        return ent.db if ent is not None else None

    @property
    def dataplane(self) -> Optional[ShardedRelation]:
        """The default relation's dataplane (None until sharded/attached)."""
        ent = (self._relations.get(DEFAULT_RELATION)
               or next(iter(self._relations.values()), None))
        return ent.dataplane if ent is not None else None

    def dataplane_of(self, relation: str) -> Optional[ShardedRelation]:
        return self._entry(relation).dataplane

    # -- keys ---------------------------------------------------------------
    def _next_key(self,
                  ent: Optional[AttachedRelation] = None) -> jax.Array:
        ent = ent if ent is not None else self._entry()
        return jax.random.fold_in(ent.root_key, next(ent.counter))

    # -- dataplane ----------------------------------------------------------
    def attach(self, relation: Union[SecretSharedDB, ShardedRelation,
                                     None] = None, *,
               name: Optional[str] = None,
               shards: int = 1,
               dispatcher: Optional[Dispatcher] = None,
               key=None) -> ShardedRelation:
        """Attach (or re-shard) a serving relation as a sharded dataplane.

        ``name`` addresses the registry slot (default:
        :data:`DEFAULT_RELATION`, the single-relation surface). A new name
        registers ``relation`` as an additional tenant with its own key
        stream — ``key`` seeds it explicitly (so a multi-tenant server can
        replay a solo client bit-for-bit); otherwise the stream derives
        from the client root key and the name, order-independently.

        Every cloud step of every subsequent query against this relation
        fans out as one dispatch per tuple-axis shard, executed by
        ``dispatcher`` (serial by default; pass a ``ThreadedDispatcher`` —
        or a shared pool's ``handle()`` — for concurrent shards, or
        ``MapReduceExecutor.dispatcher()`` for fault-tolerant placement).
        Sharding is pure execution policy: rows, opened values and ledgers
        stay bit-identical to the unsharded relation, and the planner
        prices the per-shard dispatch counts through ``stats().shards``.

        Re-attaching invalidates cached :class:`~.planner.BatchExplanation`
        estimates — their ``dispatches`` are priced per target relation at
        its shard count, so they go stale the moment the dataplane moves.
        """
        name = DEFAULT_RELATION if name is None else name
        ent = self._relations.get(name)
        if relation is None:
            if ent is None:
                raise ValueError(f"no relation registered under {name!r} — "
                                 f"pass the db to attach")
            rel = ent.dataplane if ent.dataplane is not None else ent.db
        else:
            rel = relation
        if isinstance(rel, ShardedRelation):
            if shards <= 1 and dispatcher is None:
                plane = rel                      # adopt as-is
            else:
                # re-shard only on an explicit shards>1; a new dispatcher
                # alone must not collapse the existing partitioning
                plane = ShardedRelation(
                    rel.db, shards=(shards if shards > 1 else rel.n_shards),
                    dispatcher=dispatcher or rel.dispatcher)
        else:
            plane = ShardedRelation(rel, shards=shards,
                                    dispatcher=dispatcher)
        # a device-resident dispatcher (MeshDispatcher) pre-places the
        # share arrays on its mesh at attach time — before the entry below
        # captures plane.db — so every subsequent round runs zero-copy
        bind = getattr(plane.dispatcher, "bind_plane", None)
        if bind is not None:
            bind(plane)
        if ent is None:
            if key is not None:
                root = _as_key(key)
            else:
                # derive the relation's key stream from the client root and
                # the NAME ALONE (two independent 31-bit folds), so the
                # stream is order-independent — attaching the same names in
                # any order replays identically. Distinct tenants MUST get
                # distinct streams (the protocol's masking randomness must
                # be independent), so the astronomically unlikely double
                # collision is checked and refused, never absorbed.
                raw = name.encode()
                root = jax.random.fold_in(
                    jax.random.fold_in(self._root_key,
                                       zlib.crc32(raw) & 0x7fffffff),
                    zlib.crc32(raw[::-1] + b"\x00") & 0x7fffffff)
                for other in self._relations.values():
                    if bool((other.root_key == root).all()):
                        raise ValueError(
                            f"derived key stream for {name!r} collides "
                            f"with relation {other.name!r} — pass an "
                            f"explicit key= for one of them")
            ent = AttachedRelation(name, plane.db, plane, root,
                                   itertools.count())
            self._relations[name] = ent
        else:
            ent.db, ent.dataplane = plane.db, plane
            if key is not None:                  # explicit re-key: restart
                ent.root_key = _as_key(key)
                ent.counter = itertools.count()
        # stale-estimate bugfix: cached explanations price dispatches at
        # the OLD shard count — drop them all (cheap; they re-compute).
        self._explanations.clear()
        return plane

    # -- planning -----------------------------------------------------------
    def stats(self, relation: Optional[str] = None) -> _planner.DBStats:
        ent = self._entry(relation)
        return _planner.DBStats.of(ent.db, shards=ent.n_shards,
                                   relation=ent.name)

    def explain(self, plan: Union[Select, Sequence[Plan]], *,
                relation: Optional[str] = None):
        """Planner predictions without touching shares.

        One ``Select`` -> its eligible strategy estimates, cheapest first
        (each carries bits, rounds and per-shard dispatches). Any other
        single plan -> its batch-of-one :class:`~.planner.BatchExplanation`
        (those families have one strategy each — nothing to rank).

        A *sequence of plans* -> a :class:`~.planner.BatchExplanation`: the
        plans are grouped exactly as :meth:`run_batch` would group them and
        each group is priced with ``estimate_batch_group_cost`` (bits sum,
        rounds/dispatches fuse, the cross-group fetch priced once) — a
        predicted ``run_batch`` ledger for the target relation.
        Explanations are cached per (relation, plan signature) and
        invalidated by :meth:`attach` — a re-shard re-prices dispatches.
        """
        ent = self._entry(relation)
        if isinstance(plan, Select):
            spec = _lower_match(ent.db, plan.where, "Select predicate")[2]
            if spec is not None and plan.strategy == "one_tuple":
                raise _planner.PlanNotSupported(
                    plan.where, "one_tuple select (pattern predicates "
                    "run one_round or tree)")
            if spec is not None:
                cands = _planner.candidate_pattern_estimates(
                    self.stats(ent.name), spec, ell=plan.expected_matches,
                    padded_rows=plan.padding.rows)
            else:
                cands = _planner.candidate_estimates(
                    self.stats(ent.name), ell=plan.expected_matches,
                    padded_rows=plan.padding.rows)
            return sorted(cands,
                          key=lambda e: (e.score(self.round_cost_bits),
                                         e.rounds))
        if isinstance(plan, Plan):
            # single-strategy families: the batch-of-one prediction
            return self.explain([plan], relation=ent.name)
        try:
            plans = list(plan)
        except TypeError:
            raise _planner.PlanNotSupported(
                plan, "explain() argument") from None
        sig = (ent.name, tuple(_plan_signature(p) for p in plans))
        hit = self._explanations.get(sig)
        if hit is not None:
            return hit[0]
        exp = self._explain_batch(plans, ent)
        if len(self._explanations) >= EXPLAIN_CACHE_MAX:
            self._explanations.pop(next(iter(self._explanations)))
        # the entry pins every Join right relation: its id() is part of
        # the signature, so the object must stay alive (un-reusable) for
        # as long as the cached explanation can be served.
        self._explanations[sig] = (exp, tuple(
            p.right for p in plans if isinstance(p, Join)))
        return exp

    def explain_multi(self, batches: Sequence[
            Tuple[Optional[str], Sequence[Plan]]]
            ) -> _planner.MultiBatchExplanation:
        """Predicted ledgers for a prospective :meth:`run_batch_multi`.

        Each ``(relation, plans)`` batch is priced exactly as
        :meth:`explain` would price it solo (fusion never moves a
        relation's bits, rounds or dispatch fan-out); the assembly adds
        the shared-dispatch view — ``fetch_parts`` relations closing with
        fetch work share ``fetch_waves`` (== 1 when at least two fuse)
        cloud-side dispatch waves instead of one wave each.
        """
        return _planner.explain_multi_batches(
            [self.explain(list(plans), relation=relation)
             for relation, plans in batches])

    def _explain_batch(self, plans: List[Plan],
                       ent: AttachedRelation) -> _planner.BatchExplanation:
        """Group ``plans`` exactly as :meth:`run_batch` would (AUTO plans
        see the same live group sizes/depths) and price each group."""
        db = ent.db
        stats = self.stats(ent.name)
        sel_ells: Dict[str, List[Optional[int]]] = {"one_tuple": [],
                                                    "one_round": [],
                                                    "tree": []}
        sel_specs: Dict[str, List[Optional[encoding.PatternSpec]]] = {
            s: [] for s in sel_ells}
        sel_pad: Dict[str, Optional[int]] = {s: None for s in sel_ells}
        group_sizes: Dict[str, int] = {s: 0 for s in sel_ells}
        group_rounds: Dict[str, int] = {}
        count_ests: List[_planner.CostEstimate] = []
        range_grps: Dict[Tuple[int, int], List[Tuple[bool, Optional[int],
                                                     Optional[int]]]] = {}
        joins: Dict[str, List[Plan]] = {"pkfk": [], "equi": []}
        agg_grps: Dict[tuple, List[_planner.CostEstimate]] = {}
        embed_ests: List[_planner.CostEstimate] = []
        auto_plans: List[Tuple[Select, Optional[encoding.PatternSpec]]] = []

        def add_select(plan: Select, strategy: str,
                       spec: Optional[encoding.PatternSpec]) -> None:
            ell = 1 if strategy == "one_tuple" else plan.expected_matches
            sel_ells[strategy].append(ell)
            sel_specs[strategy].append(spec)
            sel_pad[strategy] = sel_pad[strategy] or plan.padding.rows
            group_sizes[strategy] += 1
            ell_eff = (1 if strategy == "one_tuple" else
                       _planner.DEFAULT_ELL if ell is None else max(ell, 1))
            if spec is not None:
                est = _planner.estimate_pattern_cost(
                    stats, spec, select=strategy, ell=ell_eff,
                    padded_rows=plan.padding.rows)
            else:
                est = _planner.estimate_select_cost(
                    strategy, stats, ell=ell_eff,
                    padded_rows=plan.padding.rows)
            group_rounds[strategy] = max(group_rounds.get(strategy, 0),
                                         est.rounds)

        for plan in plans:
            if isinstance(plan, Count):
                spec = _lower_match(db, plan.where, "Count predicate")[2]
                count_ests.append(
                    _planner.estimate_pattern_cost(stats, spec))
            elif isinstance(plan, Select):
                spec = _lower_match(db, plan.where, "Select predicate")[2]
                if spec is not None and plan.strategy == "one_tuple":
                    raise _planner.PlanNotSupported(
                        plan.where, "one_tuple select (pattern predicates "
                        "run one_round or tree)")
                if plan.strategy == AUTO:
                    auto_plans.append((plan, spec))
                else:
                    add_select(plan, plan.strategy, spec)
            elif isinstance(plan, (RangeCount, RangeSelect)):
                col = resolve_column(db, plan.where.column)
                if col not in db.numeric_bits:   # as range_phase would
                    raise ValueError(f"column {col} was not outsourced in "
                                     f"binary form")
                t_bits = db.numeric_bits[col]
                gk = (t_bits, _ripple_every(db, plan, t_bits))
                want = isinstance(plan, RangeSelect)
                range_grps.setdefault(gk, []).append(
                    (want, None, plan.padding.rows if want else None))
            elif isinstance(plan, Aggregate):
                col = resolve_column(db, plan.column)
                if col not in db.numeric_bits:   # as the agg phases would
                    raise ValueError(f"column {col} was not outsourced in "
                                     f"binary form")
                t_bits = db.numeric_bits[col]
                every = (_ripple_every(db, plan, t_bits)
                         if plan.op in ("min", "max") else 0)
                est = _planner.estimate_aggregate_cost(
                    stats, plan.op, t_bits=t_bits,
                    conditional=plan.where is not None,
                    verify=plan.verify, reduce_every=every)
                # mirror run_batch grouping: SUM/AVG fuse per bit-width,
                # MIN/MAX per (bit-width, reduce_every)
                gk = (("agg_sum", t_bits) if plan.op in ("sum", "avg")
                      else ("agg_minmax", t_bits, every))
                agg_grps.setdefault(gk, []).append(est)
            elif isinstance(plan, EmbedLookup):
                embed_ests.append(_planner.estimate_embed_cost(
                    stats, n_tokens=len(plan.tokens), verify=plan.verify))
            elif isinstance(plan, Join):
                self._validate_join(plan)
                joins[plan.kind].append(plan)
            else:
                raise _planner.PlanNotSupported(plan)
        for plan, spec in auto_plans:
            chooser = (_planner.choose_pattern_strategy if spec is not None
                       else _planner.choose_select_strategy)
            args = (stats, spec) if spec is not None else (stats,)
            chosen = chooser(
                *args, ell=plan.expected_matches,
                padded_rows=plan.padding.rows,
                round_cost_bits=self.round_cost_bits,
                group_sizes=group_sizes, group_rounds=group_rounds).strategy
            add_select(plan, chosen, spec)

        groups: List[_planner.GroupEstimate] = []
        if count_ests:
            groups.append(_planner.GroupEstimate(
                "count", len(count_ests), _planner.CostEstimate(
                    "count", bits=sum(e.bits for e in count_ests),
                    rounds=max(e.rounds for e in count_ests),
                    dispatches=max(e.dispatches for e in count_ests))))
        for strategy, ells in sel_ells.items():
            if ells:
                groups.append(_planner.GroupEstimate(
                    strategy, len(ells),
                    _planner.estimate_batch_group_cost(
                        stats, strategy, ells=ells,
                        padded_rows=sel_pad[strategy],
                        specs=sel_specs[strategy])))
        for (t_bits, reduce_every), members in range_grps.items():
            ests = [_planner.estimate_range_cost(
                stats, t_bits=t_bits, reduce_every=reduce_every,
                want_addresses=want,
                ell=_planner.DEFAULT_ELL if ell is None else max(ell, 1),
                padded_rows=pad) for (want, ell, pad) in members]
            family = ("range_select" if any(m[0] for m in members)
                      else "range_count")
            groups.append(_planner.GroupEstimate(
                family, len(members), _planner.CostEstimate(
                    family, bits=sum(e.bits for e in ests),
                    rounds=max(e.rounds for e in ests),
                    dispatches=max(e.dispatches for e in ests))))
        for ests in agg_grps.values():
            groups.append(_planner.GroupEstimate(
                "aggregate", len(ests), _planner.CostEstimate(
                    "aggregate", bits=sum(e.bits for e in ests),
                    rounds=max(e.rounds for e in ests),
                    dispatches=max(e.dispatches for e in ests))))
        if embed_ests:      # one fused contraction: dispatches don't stack
            groups.append(_planner.GroupEstimate(
                "embed", len(embed_ests), _planner.CostEstimate(
                    "embed", bits=sum(e.bits for e in embed_ests),
                    rounds=max(e.rounds for e in embed_ests),
                    dispatches=max(e.dispatches for e in embed_ests))))
        if joins["pkfk"]:       # one fused group: batched match matrices
            ests = [_planner.estimate_pkfk_cost(
                stats, _planner.DBStats.of(p.right))
                for p in joins["pkfk"]]
            groups.append(_planner.GroupEstimate(
                "pkfk", len(ests), _planner.CostEstimate(
                    "pkfk", bits=sum(e.bits for e in ests),
                    rounds=max(e.rounds for e in ests),
                    dispatches=max(e.dispatches for e in ests))))
        if joins["equi"]:       # phases fuse; per-value rounds stay per job
            ests = [_planner.estimate_equijoin_cost(
                stats, _planner.DBStats.of(p.right),
                fake_values=p.padding.values) for p in joins["equi"]]
            groups.append(_planner.GroupEstimate(
                "equi", len(ests), _planner.CostEstimate(
                    "equi", bits=sum(e.bits for e in ests),
                    rounds=max(e.rounds for e in ests),
                    dispatches=max(e.dispatches for e in ests))))
        return _planner.explain_batch_groups(stats, groups)

    # -- execution ----------------------------------------------------------
    def run(self, plan: Plan, *,
            relation: Optional[str] = None) -> QueryResult:
        """Execute one logical plan (the B = 1 case of :meth:`run_batch`)."""
        return self.run_batch([plan], relation=relation)[0]

    def run_batch(self, plans: Sequence[Plan], *,
                  relation: Optional[str] = None) -> List[QueryResult]:
        """Execute B logical plans, fusing each protocol round per group.

        ``relation`` picks the registry entry the batch runs against (the
        default relation when omitted). Per-plan keys derive from THAT
        relation's root key in list order — key streams are per relation,
        so batches against different relations never perturb each other's
        transcripts. Every plan is cost-planned exactly as :meth:`run`
        would (AUTO selections see the batch's live group sizes, so with
        ``round_cost_bits > 0`` a borderline query is steered onto a group
        whose fused rounds it can ride for free), then compatible plans
        are grouped and executed through the batched round engine:

        * Count/Select groups stack their shared predicates — each match,
          Q&A and address round is one fused dispatch + one interpolation.
        * Range plans group by (bit-width, ``reduce_every`` as the planner
          resolves it: :func:`~.planner.choose_reduce_every`); the whole
          group's SS-SUB bit-vectors ripple in ONE ``(c, 2B, n, t)`` carry
          chain — one ``ripple_carry`` dispatch per bit-round, one
          degree-reduction re-share per boundary for the batch.
        * Every oblivious fetch in the batch — one_round, tree and range
          one-hot matrices plus PK/FK join match matrices (a zero-match
          one_round/range query contributes a 0-row block; a tree query
          that counted ℓ=0 skips the fetch, as sequentially) — stacks
          into a single cross-group ``ss_matmul``.
        * Equijoins fuse per phase: one column-open interpolation, one
          X-side layer-1 matmul for the group, Y-side per distinct right
          relation.
        * Aggregates fuse per family: SUM/AVG numerators share ONE masked
          contraction per bit-width (conditional AVG denominators ride the
          batch's count phase), MIN/MAX tournaments march in lockstep per
          (bit-width, ``reduce_every``) group.

        Results come back in plan order; each query's rows and
        ``CostLedger`` are bit-identical to running it sequentially (ledgers
        record the query's own protocol cost, never a groupmate's padding).

        A forced ``one_tuple`` whose predicate turns out to hit ℓ ≠ 1 tuples
        raises :class:`CardinalityError` (as sequentially); with
        ``strategy="auto"`` the query replans onto one_round/tree inside the
        batch, reusing the learned count.
        """
        (out,) = self.run_batch_multi([(relation, plans)])
        return out

    def run_batch_multi(self, batches: Sequence[
            Tuple[Optional[str], Sequence[Plan]]]) -> List[List[QueryResult]]:
        """Execute several relations' batches with ONE fused fetch wave.

        ``batches`` is a sequence of ``(relation, plans)`` pairs — the
        scheduler's simultaneously-closing batch groups. Each batch runs
        exactly as :meth:`run_batch` would (its own relation's key stream,
        its own grouping, its own ledgers — batches are never mixed), but
        all batches advance to the cross-group fetch boundary first and
        their cloud-side fetch ``ss_matmul``s execute as ONE dispatch wave
        when the relations' dataplanes share a dispatch pool
        (:func:`repro.core.queries.rounds.fetch_fusion_multi`). Results and
        ledgers are bit-identical to running the batches back-to-back;
        returns one result list per batch, in ``batches`` order.
        """
        works = [self._prepare_batch(list(plans), self._entry(relation))
                 for relation, plans in batches]
        fetched = rounds.fetch_fusion_multi(
            self.backend,
            [(w.rel, w.fetch_jobs, w.join_entries) for w in works])
        return [self._finish_batch(w, f) for w, f in zip(works, fetched)]

    def _prepare_batch(self, plans: Sequence[Plan],
                       ent: AttachedRelation) -> _BatchWork:
        """Group, plan and run every pre-fetch round of one batch."""
        db, rel = ent.db, ent.rel
        # planning: stats, strategy choice and grouping, up to the first
        # round (the span ``client.plan``)
        plane = ent.dataplane
        with span(plane.stats if plane is not None else None,
                  "client.plan"):
            stats = self.stats(ent.name)
            results: Dict[int, QueryResult] = {}
            count_grp: List[_Slot] = []
            sel_grp: Dict[str, List[_Slot]] = {
                "one_tuple": [], "one_round": [], "tree": []}
            range_grps: Dict[Tuple[int, int], List[_Slot]] = {}
            agg_sum_grps: Dict[int, List[_Slot]] = {}
            agg_mm_grps: Dict[Tuple[int, int], List[_Slot]] = {}
            embed_grp: List[_Slot] = []
            pkfk_grp: List[_Slot] = []
            equi_grp: List[_Slot] = []
            auto_slots: List[_Slot] = []
            group_sizes: Dict[str, int] = {s: 0 for s in sel_grp}
            group_rounds: Dict[str, int] = {}

            def join_group(slot: _Slot, strategy: str,
                           ell: Optional[int]) -> None:
                """Track a group's size and deepest member's estimated
                rounds so later AUTO riders are priced at their true
                marginal depth."""
                slot.strategy = strategy
                group_sizes[strategy] += 1
                ell_eff = (1 if strategy == "one_tuple" else
                           _planner.DEFAULT_ELL if ell is None
                           else max(ell, 1))
                if slot.spec is not None:
                    est = _planner.estimate_pattern_cost(
                        stats, slot.spec, select=strategy, ell=ell_eff,
                        padded_rows=slot.plan.padding.rows)
                else:
                    est = _planner.estimate_select_cost(
                        strategy, stats, ell=ell_eff,
                        padded_rows=slot.plan.padding.rows)
                group_rounds[strategy] = max(group_rounds.get(strategy, 0),
                                             est.rounds)
                sel_grp[strategy].append(slot)

            for idx, plan in enumerate(plans):
                slot = _Slot(idx, plan, self._next_key(ent))
                if isinstance(plan, Count):
                    slot.column, slot.pattern, slot.spec = _lower_match(
                        db, plan.where, "Count predicate")
                    count_grp.append(slot)
                elif isinstance(plan, Select):
                    slot.column, slot.pattern, slot.spec = _lower_match(
                        db, plan.where, "Select predicate")
                    if slot.spec is not None and plan.strategy == "one_tuple":
                        raise _planner.PlanNotSupported(
                            plan.where, "one_tuple select (the §3.2.1 single-"
                            "tuple map is the exact-equality special case — "
                            "pattern predicates run one_round or tree)")
                    if plan.strategy == AUTO:
                        auto_slots.append(slot)   # assigned once groups known
                        continue
                    if plan.strategy == "one_tuple" and plan.padding.rows:
                        raise ValueError(
                            "one_tuple returns the single tuple directly and "
                            "cannot pad its output size — use "
                            "one_round/tree (or auto, which excludes "
                            "one_tuple when padding is requested)")
                    join_group(slot, plan.strategy, plan.expected_matches)
                elif isinstance(plan, (RangeCount, RangeSelect)):
                    slot.column = resolve_column(db, plan.where.column)
                    t_bits = db.numeric_bits.get(slot.column, -1)
                    gk = (t_bits, _ripple_every(db, plan, t_bits))
                    range_grps.setdefault(gk, []).append(slot)
                elif isinstance(plan, Aggregate):
                    slot.column = resolve_column(db, plan.column)
                    if plan.where is not None:
                        slot.pred_column = resolve_column(
                            db, plan.where.column)
                    t_bits = db.numeric_bits.get(slot.column, -1)
                    if plan.op in ("sum", "avg"):
                        agg_sum_grps.setdefault(t_bits, []).append(slot)
                    else:
                        agg_mm_grps.setdefault(
                            (t_bits, _ripple_every(db, plan, t_bits)),
                            []).append(slot)
                elif isinstance(plan, EmbedLookup):
                    embed_grp.append(slot)
                elif isinstance(plan, Join):
                    self._validate_join(plan)
                    (pkfk_grp if plan.kind == "pkfk"
                     else equi_grp).append(slot)
                else:
                    raise _planner.PlanNotSupported(plan)

            # AUTO selections plan against the batch's live group sizes and
            # depths (riding a non-empty group costs only the rounds the rider
            # adds beyond its deepest member — marginal round pricing; with
            # round_cost_bits=0 this reduces to sequential planning). Pattern
            # predicates choose among their eligible strategies only.
            for slot in auto_slots:
                if slot.spec is not None:
                    chosen = _planner.choose_pattern_strategy(
                        stats, slot.spec, ell=slot.plan.expected_matches,
                        padded_rows=slot.plan.padding.rows,
                        round_cost_bits=self.round_cost_bits,
                        group_sizes=group_sizes,
                        group_rounds=group_rounds).strategy
                else:
                    chosen = _planner.choose_select_strategy(
                        stats, ell=slot.plan.expected_matches,
                        padded_rows=slot.plan.padding.rows,
                        round_cost_bits=self.round_cost_bits,
                        group_sizes=group_sizes,
                        group_rounds=group_rounds).strategy
                join_group(slot, chosen, slot.plan.expected_matches)

            be = self.backend
            # deferred cross-group fetch: (slot, strategy, addresses) per job
            fetch_jobs: List[rounds.FetchJob] = []
            fetch_meta: List[Tuple[_Slot, str, List[int]]] = []

            # conditional AVG denominators ride the batch's §3.1 count phase:
            # their MatchJobs fuse into the same dispatch as explicit Counts.
            avg_cnt_slots: List[_Slot] = []
            for group in agg_sum_grps.values():
                for s in group:
                    if s.plan.op == "avg" and s.plan.where is not None:
                        s.key, s.fetch_key = jax.random.split(s.key)
                        avg_cnt_slots.append(s)

        if count_grp or avg_cnt_slots:
            counts = rounds.count_phase(be, rel, [
                rounds.MatchJob(s.column, s.pattern, s.key,
                                s.ledger, s.spec) for s in count_grp] + [
                rounds.MatchJob(s.pred_column, s.plan.where.pattern,
                                s.fetch_key, s.ledger)
                for s in avg_cnt_slots])
            for s, cnt in zip(count_grp, counts):
                results[s.idx] = QueryResult(plan=s.plan, ledger=s.ledger,
                                             strategy="count", count=cnt)
            for s, cnt in zip(avg_cnt_slots, counts[len(count_grp):]):
                s.known_count = cnt

        # -- embedding lookups: every job's one-hots share in one program
        # and the whole group contracts in ONE ss_matmul per shard ---------
        if embed_grp:
            embs = embed_q.embed_phase(be, rel, [
                embed_q.EmbedJob(tokens=s.plan.tokens, key=s.key,
                                 ledger=s.ledger, verify=s.plan.verify)
                for s in embed_grp])
            for s, emb in zip(embed_grp, embs):
                results[s.idx] = QueryResult(plan=s.plan, ledger=s.ledger,
                                             strategy="embed",
                                             embeddings=emb)

        # -- aggregation: SUM/AVG numerators fuse per bit-width, MIN/MAX
        # tournaments per (bit-width, reduce_every) ------------------------
        for group in agg_sum_grps.values():
            sums = aggregate.agg_sum_phase(be, rel, [
                aggregate.SumJob(
                    value_column=s.column, key=s.key, ledger=s.ledger,
                    pred_column=s.pred_column,
                    pattern=(s.plan.where.pattern if s.plan.where is not None
                             else None),
                    verify=s.plan.verify) for s in group])
            for s, total in zip(group, sums):
                if s.plan.op == "sum":
                    results[s.idx] = QueryResult(
                        plan=s.plan, ledger=s.ledger, strategy="agg_sum",
                        value=total)
                elif s.plan.where is not None:
                    results[s.idx] = QueryResult(
                        plan=s.plan, ledger=s.ledger, strategy="agg_avg",
                        value=(total / s.known_count
                               if s.known_count else None),
                        count=s.known_count)
                else:                   # denominator is the public n
                    results[s.idx] = QueryResult(
                        plan=s.plan, ledger=s.ledger, strategy="agg_avg",
                        value=(total / db.n_tuples if db.n_tuples
                               else None))
        for (_, reduce_every), group in agg_mm_grps.items():
            outs = aggregate.agg_minmax_rounds(be, rel, [
                aggregate.MinMaxJob(
                    value_column=s.column, key=s.key, ledger=s.ledger,
                    pred_column=s.pred_column,
                    pattern=(s.plan.where.pattern if s.plan.where is not None
                             else None),
                    verify=s.plan.verify, op=s.plan.op,
                    reduce_every=reduce_every) for s in group])
            for s, (val, cnt) in zip(group, outs):
                results[s.idx] = QueryResult(
                    plan=s.plan, ledger=s.ledger,
                    strategy=f"agg_{s.plan.op}", value=val, count=cnt)

        # -- one_tuple: batched count phase, then the Alg 3 map round -------
        if sel_grp["one_tuple"]:
            group = sel_grp["one_tuple"]
            keys = [jax.random.split(s.key) for s in group]
            ells = rounds.count_phase(be, rel, [
                rounds.MatchJob(s.column, s.pattern, kc, s.ledger)
                for s, (kc, _) in zip(group, keys)])
            verified: List[Tuple[_Slot, jax.Array]] = []
            for s, (_, k_sel), ell in zip(group, keys, ells):
                if ell == 1:
                    verified.append((s, k_sel))
                    continue
                if s.plan.strategy != AUTO:
                    raise CardinalityError(
                        f"select_one_tuple needs ℓ=1, predicate has {ell}"
                        " — use select_one_round/select_tree", count=ell)
                # hint was wrong: replan with the learned ℓ on a fresh key;
                # the slot's ledger keeps the aborted count-phase cost.
                chosen = _planner.choose_select_strategy(
                    stats, ell=ell, padded_rows=s.plan.padding.rows,
                    round_cost_bits=self.round_cost_bits,
                    group_sizes=group_sizes,
                    group_rounds=group_rounds).strategy
                s.key, s.known_count = self._next_key(ent), ell
                join_group(s, chosen, ell)
            if verified:
                rows = rounds.one_tuple_round(be, rel, [
                    rounds.MatchJob(s.column, s.pattern, k_sel,
                                    s.ledger) for s, k_sel in verified])
                for (s, _), row in zip(verified, rows):
                    results[s.idx] = QueryResult(
                        plan=s.plan, ledger=s.ledger, strategy="one_tuple",
                        rows=[row])

        # -- one_round: fused Phase 1; fetch joins the cross-group matmul ---
        if sel_grp["one_round"]:
            group = sel_grp["one_round"]
            keys = [jax.random.split(s.key) for s in group]
            addrs = rounds.match_all_round(be, rel, [
                rounds.MatchJob(s.column, s.pattern, kp, s.ledger, s.spec)
                for s, (kp, _) in zip(group, keys)])
            for s, (_, kf), a in zip(group, keys, addrs):
                fetch_jobs.append(rounds.FetchJob(kf, a, s.ledger,
                                                  s.plan.padding.rows))
                fetch_meta.append((s, "one_round", a))

        # -- tree: batched count phase, lockstep Q&A rounds -----------------
        if sel_grp["tree"]:
            group = sel_grp["tree"]
            keys = [jax.random.split(s.key, 3) for s in group]
            need = [(s, kc) for s, (kc, _, _) in zip(group, keys)
                    if s.known_count is None]
            ells = rounds.count_phase(be, rel, [
                rounds.MatchJob(s.column, s.pattern, kc, s.ledger, s.spec)
                for s, kc in need])
            for (s, _), ell in zip(need, ells):
                s.known_count = ell
            live: List[Tuple[_Slot, jax.Array, jax.Array]] = []
            for s, (_, kp, kf) in zip(group, keys):
                if s.known_count == 0:
                    results[s.idx] = QueryResult(
                        plan=s.plan, ledger=s.ledger, strategy="tree",
                        rows=[], addresses=[])
                else:
                    live.append((s, kp, kf))
            if live:
                addrs = rounds.tree_rounds(be, rel, [
                    rounds.TreeJob(s.column, s.pattern, kp,
                                   s.ledger, s.spec, ell=s.known_count,
                                   branching=s.plan.branching)
                    for s, kp, _ in live])
                for (s, _, kf), a in zip(live, addrs):
                    fetch_jobs.append(rounds.FetchJob(kf, a, s.ledger,
                                                      s.plan.padding.rows))
                    fetch_meta.append((s, "tree", a))

        # -- ranges: one fused ripple per (bit-width, reduce_every) group ---
        for (_, reduce_every), group in range_grps.items():
            jobs = []
            for s in group:
                if isinstance(s.plan, RangeSelect):
                    k_ind, s.fetch_key = jax.random.split(s.key)
                else:
                    k_ind = s.key
                jobs.append(rounds.RangeJob(
                    s.column, s.plan.where.lo, s.plan.where.hi, k_ind,
                    s.ledger, reduce_every=reduce_every,
                    want_addresses=isinstance(s.plan, RangeSelect)))
            for s, out in zip(group, rounds.range_rounds(be, rel, jobs)):
                if isinstance(s.plan, RangeCount):
                    results[s.idx] = QueryResult(
                        plan=s.plan, ledger=s.ledger,
                        strategy="range_count", count=out)
                else:
                    fetch_jobs.append(rounds.FetchJob(
                        s.fetch_key, out, s.ledger, s.plan.padding.rows))
                    fetch_meta.append((s, "range_select", out))

        # -- pkfk joins: match matrices become rows of the shared fetch -----
        join_jobs: List[rounds.JoinJob] = []
        join_entries: List[rounds.FetchEntry] = []
        if pkfk_grp:
            join_jobs = [rounds.JoinJob(
                s.plan.right, resolve_column(db, s.plan.on[0]),
                resolve_column(s.plan.right, s.plan.on[1]), s.key, s.ledger,
                match_method=_planner.choose_match_method(
                    stats, s.plan.match_method))
                for s in pkfk_grp]
            join_entries = rounds.join_match_round(be, rel, join_jobs)

        return _BatchWork(plans=plans, db=db, rel=rel, results=results,
                          fetch_jobs=fetch_jobs, fetch_meta=fetch_meta,
                          join_jobs=join_jobs, join_entries=join_entries,
                          pkfk_grp=pkfk_grp, equi_grp=equi_grp)

    def _finish_batch(self, work: _BatchWork,
                      fetched: Tuple[List[List[List[str]]], List["rounds.Shares"]]
                      ) -> List[QueryResult]:
        """Consume the fused fetch output and run the post-fetch rounds."""
        be = self.backend
        db, results = work.db, work.results
        rows_list, extra_sh = fetched
        for (s, strat, a), r in zip(work.fetch_meta, rows_list):
            results[s.idx] = QueryResult(plan=s.plan, ledger=s.ledger,
                                         strategy=strat, rows=r,
                                         addresses=a)
        if work.pkfk_grp:
            join_rows = rounds.join_emit_round(db, work.join_jobs,
                                               extra_sh)
            for s, r in zip(work.pkfk_grp, join_rows):
                results[s.idx] = QueryResult(plan=s.plan,
                                             ledger=s.ledger,
                                             strategy="pkfk", rows=r)

        # -- equijoins: phases fused across the group -----------------------
        if work.equi_grp:
            equi_rows = rounds.equijoin_rounds(be, work.rel, [
                rounds.EquiJob(
                    s.plan.right, resolve_column(db, s.plan.on[0]),
                    resolve_column(s.plan.right, s.plan.on[1]), s.key,
                    s.ledger, padded_values=s.plan.padding.values)
                for s in work.equi_grp])
            for s, r in zip(work.equi_grp, equi_rows):
                results[s.idx] = QueryResult(plan=s.plan, ledger=s.ledger,
                                             strategy="equi", rows=r)
        return [results[i] for i in range(len(work.plans))]

    @staticmethod
    def _validate_join(plan: Join) -> None:
        if plan.padding.rows:
            raise ValueError("joins take Padding.fake_values (fake join "
                             "jobs), not Padding.rows")
        if plan.kind == "pkfk" and plan.padding.values:
            raise ValueError(
                "pkfk_join's output size is always n_y (one reducer per "
                "child tuple) — nothing to hide; Padding.fake_values "
                "applies to kind='equi' only")

    # -- conveniences (build the plan, run it) ------------------------------
    def count(self, column: ColumnRef, pattern: str, *,
              relation: Optional[str] = None) -> QueryResult:
        return self.run(Count(Eq(column, pattern)), relation=relation)

    def select(self, column: ColumnRef, pattern: str, *,
               strategy: str = AUTO, expected_matches: Optional[int] = None,
               padding: Padding = Padding.NONE,
               branching: Optional[int] = None,
               relation: Optional[str] = None) -> QueryResult:
        return self.run(Select(Eq(column, pattern), strategy=strategy,
                               expected_matches=expected_matches,
                               padding=padding, branching=branching),
                        relation=relation)

    def like(self, column: ColumnRef, pattern: str, *,
             count_only: bool = False, strategy: str = AUTO,
             expected_matches: Optional[int] = None,
             padding: Padding = Padding.NONE,
             relation: Optional[str] = None) -> QueryResult:
        """``column LIKE pattern`` — a pattern-engine Select (or Count
        with ``count_only=True``). Wildcard-free patterns lower to the
        exact Eq path; ``lit%``/``%lit``/``%lit%``/``l_t`` run the
        prefix / suffix / substring / masked matchers."""
        where = Like(column, pattern)
        if count_only:
            return self.run(Count(where), relation=relation)
        return self.run(Select(where, strategy=strategy,
                               expected_matches=expected_matches,
                               padding=padding), relation=relation)

    def range_count(self, column: ColumnRef, lo: int, hi: int, *,
                    reduce_every: int = 0,
                    relation: Optional[str] = None) -> QueryResult:
        return self.run(RangeCount(Between(column, lo, hi),
                                   reduce_every=reduce_every),
                        relation=relation)

    def range_select(self, column: ColumnRef, lo: int, hi: int, *,
                     reduce_every: int = 0,
                     padding: Padding = Padding.NONE,
                     relation: Optional[str] = None) -> QueryResult:
        return self.run(RangeSelect(Between(column, lo, hi),
                                    reduce_every=reduce_every,
                                    padding=padding), relation=relation)

    def aggregate(self, op: str, column: ColumnRef, *,
                  where: Optional[Eq] = None, verify: bool = False,
                  reduce_every: int = 0,
                  relation: Optional[str] = None) -> QueryResult:
        return self.run(Aggregate(op, column, where=where, verify=verify,
                                  reduce_every=reduce_every),
                        relation=relation)

    def join(self, right: SecretSharedDB,
             on: Tuple[ColumnRef, ColumnRef], *, kind: str = "pkfk",
             padding: Padding = Padding.NONE,
             relation: Optional[str] = None) -> QueryResult:
        return self.run(Join(right=right, on=on, kind=kind, padding=padding),
                        relation=relation)
