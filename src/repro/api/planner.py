"""Cost-based selection-strategy planner (paper §3.2 / Table 1 formulas).

The paper gives per-algorithm communication/round costs; this module turns
them into static ``(bits, rounds)`` estimates in exactly the units
``CostLedger`` records (field elements × 31 bits), so the planner's numbers
are directly comparable with measured ledgers:

  one_tuple  (§3.2.1, Alg 3): count + pattern + one m·w·A tuple; 2 rounds.
               Only valid when the predicate hits exactly ℓ = 1 tuple.
  one_round  (§3.2.2):        pattern + n match bits + ℓ'×n fetch; 2 rounds.
  tree       (§3.2.2, Alg 4): count + pattern + per-round block counts +
               ℓ address-fetches + ℓ'×n fetch;
               rounds ≤ ⌊log_ℓ n⌋ + ⌊log₂ ℓ⌋ + 1 (+ count + fetch).

The crossover the planner captures is the paper's own: ``one_round`` ships
(and the user interpolates) all n match bits — unbeatable for small n, linear
pain for large n — while ``tree`` replaces that n-vector with O(ℓ·log n)
block counts at the price of extra rounds. Estimates are pure functions of
the public relation statistics (n, m, w, A, c′) plus the cardinality hint ℓ,
so the planner runs without touching shares.

Estimates also price the *execution* axis: ``DBStats.shards`` carries the
attached dataplane's shard count and every :class:`CostEstimate` reports
``dispatches`` — the number of per-shard device dispatches the sharded
round engine will emit (each sharded cloud step fans out S ways; tree Q&A
rounds gather blocks from the full relation and stay at one dispatch).
Dispatches are an execution cost, never a protocol cost: bits and rounds
are independent of S by construction.

:func:`explain_batch_groups` assembles per-group estimates into the
:class:`BatchExplanation` that ``QueryClient.explain(plans)`` returns — a
predicted ``run_batch`` ledger (bits sum per query, rounds fuse to the
deepest member, the cross-group fetch is priced ONCE) without running
anything.
"""
from __future__ import annotations

import dataclasses
import math
from typing import List, Mapping, Optional, Sequence, Tuple

from ..core.costs import WORD_BITS
from ..core.dataplane import ShardedRelation
from ..core.encoding import PatternSpec
from ..core.engine import SecretSharedDB
from ..core.queries.aggregate import sum_limbs
from ..core.queries.rounds import match_phase_cost, ripple_schedule

#: ℓ assumed when the plan carries no ``expected_matches`` hint. Two is the
#: smallest multi-match cardinality: it keeps ``one_tuple`` out of the
#: running (which would raise on ℓ≠1) without inflating tree-round counts.
DEFAULT_ELL = 2


class PlanNotSupported(TypeError):
    """A plan object no estimator/executor knows how to price or run.

    Subclasses TypeError so existing ``except TypeError`` callers keep
    working, but carries the offending plan's type name instead of the
    opaque ``KeyError``/``AttributeError`` an unknown class used to hit.
    """

    def __init__(self, plan, context: str = "plan"):
        self.plan = plan
        super().__init__(
            f"unsupported {context}: {type(plan).__name__!r} "
            f"({plan!r}) is not a known logical plan class")


@dataclasses.dataclass(frozen=True)
class CostEstimate:
    """Planner-side (bits, rounds, per-shard dispatches) prediction."""
    strategy: str
    bits: int
    rounds: int
    dispatches: int = 0

    def score(self, round_cost_bits: int = 0) -> int:
        """Total cost with rounds priced at ``round_cost_bits`` each."""
        return self.bits + round_cost_bits * self.rounds


@dataclasses.dataclass(frozen=True)
class DBStats:
    """The public statistics the planner works from (§2.3: the adversary —
    and hence the planner — may know n, m and the schema). ``shards`` is
    the attached dataplane's tuple-axis shard count (execution, not
    protocol: it scales dispatch estimates, never bits or rounds).
    ``relation`` names the registry entry these statistics describe — with
    several relations attached, every estimate (and in particular its
    ``dispatches``) is priced per *target* relation at that relation's own
    n and shard count, never at a neighbour's."""
    n: int          # tuples
    m: int          # attributes
    c: int          # clouds / shares
    w: int          # word length
    a: int          # alphabet size
    shards: int = 1
    relation: str = ""

    @classmethod
    def of(cls, db, shards: Optional[int] = None,
           relation: str = "") -> "DBStats":
        if isinstance(db, ShardedRelation):
            shards = db.n_shards if shards is None else shards
            db = db.db
        return cls(n=db.n_tuples, m=db.n_attrs, c=db.n_shares,
                   w=db.codec.word_length, a=db.codec.alphabet_size,
                   shards=shards or 1, relation=relation)


def _pattern_elems(s: DBStats) -> int:
    return s.c * s.w * s.a


def _count_elems(s: DBStats) -> int:
    # Alg 2: pattern up, one word per cloud down.
    return _pattern_elems(s) + s.c


def _fetch_elems(s: DBStats, ell: int, padded_rows: Optional[int]) -> int:
    # ℓ'×n one-hot matrix up, ℓ' tuples down (fetch_by_addresses).
    ellp = max(padded_rows or ell, ell)
    return s.c * ellp * s.n + s.c * ellp * s.m * s.w * s.a


def estimate_select_cost(strategy: str, stats: DBStats, *,
                         ell: int = DEFAULT_ELL,
                         padded_rows: Optional[int] = None) -> CostEstimate:
    """(bits, rounds, dispatches) for one §3.2 strategy at cardinality ℓ.

    Dispatches count the sharded round engine's per-shard device fan-out:
    count / match / fetch steps slice the tuple axis (S dispatches each),
    and tree Q&A / address rounds are shard-aligned — each round's block
    gathers clip to shard bounds and fan out per shard too (S dispatches
    per Q&A round; the public block partition itself never moves with S).
    """
    s = stats
    S = max(1, min(s.shards, max(s.n, 1)))
    if strategy == "one_tuple":
        if ell != 1:
            raise ValueError("one_tuple requires ℓ = 1")
        elems = _count_elems(s) + _pattern_elems(s) + s.c * s.m * s.w * s.a
        return CostEstimate("one_tuple", elems * WORD_BITS, rounds=2,
                            dispatches=2 * S)    # count step + map step
    if strategy == "one_round":
        elems = _pattern_elems(s) + s.c * s.n + _fetch_elems(s, ell,
                                                             padded_rows)
        return CostEstimate("one_round", elems * WORD_BITS, rounds=2,
                            dispatches=2 * S)    # match step + fetch step
    if strategy == "tree":
        if ell <= 1:
            # Alg 4 line 2: count, one whole-table Address_fetch, fetch.
            elems = (_count_elems(s) + _pattern_elems(s) + s.c
                     + _fetch_elems(s, max(ell, 1), padded_rows))
            return CostEstimate("tree", elems * WORD_BITS, rounds=3,
                                dispatches=3 * S)
        qa_rounds = (math.floor(math.log(max(s.n, 2), ell))
                     + math.floor(math.log2(ell)) + 1)       # Theorem 4
        elems = (_count_elems(s) + _pattern_elems(s)
                 + qa_rounds * ell * s.c                     # block counts
                 + ell * s.c                                 # address fetches
                 + _fetch_elems(s, ell, padded_rows))
        return CostEstimate("tree", elems * WORD_BITS,
                            rounds=1 + qa_rounds + 1,
                            dispatches=(2 + qa_rounds + 1) * S)
    raise ValueError(f"unknown selection strategy {strategy!r}")


def estimate_count_cost(stats: DBStats) -> CostEstimate:
    """§3.1 Algorithm 2: one round, O(1) comm, one count step per shard."""
    S = max(1, min(stats.shards, max(stats.n, 1)))
    return CostEstimate("count", _count_elems(stats) * WORD_BITS, rounds=1,
                        dispatches=S)


def estimate_pattern_cost(stats: DBStats, spec: Optional[PatternSpec], *,
                          select: Optional[str] = None,
                          ell: int = DEFAULT_ELL,
                          padded_rows: Optional[int] = None) -> CostEstimate:
    """Price a pattern-predicate COUNT (``select=None``) or SELECT
    (``select="one_round" | "tree"``) from the very same Table-1-style
    atoms the round engine charges (:func:`~repro.core.queries.rounds.
    match_phase_cost` is the single source for both), so the prediction is
    *exact* against the measured ledger for pattern counts and one-round
    selects, and a Theorem-4-style bound for the tree.

    ``spec=None`` is the wildcard-free case — the predicate lowered onto
    the exact-equality path — and the estimate degenerates, field for
    field, to :func:`estimate_count_cost` / :func:`estimate_select_cost`
    (the planner-level statement that a wildcard-free LIKE costs exactly
    what an Eq costs; asserted by the planner tests).

    The CONTAINS family's degree-reduction re-share adds its round and its
    c² + n·M elements wherever the match phase runs (count, one_round
    Phase 1, tree Phase 0 *and* tree prelude — hence twice for a CONTAINS
    tree). ``one_tuple`` is the §3.2.1 exact-equality special case and is
    deliberately absent here.
    """
    s = stats
    S = max(1, min(s.shards, max(s.n, 1)))
    cost = match_phase_cost(spec, n=s.n, c=s.c, w=s.w, a=s.a)
    match_elems = cost["send"] + cost["reduce_send"]
    rr = cost["reduce_rounds"]
    if select is None:
        return CostEstimate("count", (match_elems + s.c) * WORD_BITS,
                            rounds=1 + rr, dispatches=S)
    ell = max(ell, 1)
    if select == "one_round":
        elems = match_elems + s.c * s.n + _fetch_elems(s, ell, padded_rows)
        return CostEstimate("one_round", elems * WORD_BITS,
                            rounds=2 + rr, dispatches=2 * S)
    if select == "tree":
        count_elems = match_elems + s.c          # Phase 0 runs the pattern
        if ell <= 1:
            elems = (count_elems + match_elems + s.c
                     + _fetch_elems(s, 1, padded_rows))
            return CostEstimate("tree", elems * WORD_BITS,
                                rounds=3 + 2 * rr, dispatches=3 * S)
        qa_rounds = (math.floor(math.log(max(s.n, 2), ell))
                     + math.floor(math.log2(ell)) + 1)       # Theorem 4
        elems = (count_elems + match_elems
                 + qa_rounds * ell * s.c                     # block counts
                 + ell * s.c                                 # address fetches
                 + _fetch_elems(s, ell, padded_rows))
        return CostEstimate("tree", elems * WORD_BITS,
                            rounds=1 + qa_rounds + 1 + 2 * rr,
                            dispatches=(2 + qa_rounds + 1) * S)
    raise ValueError(f"pattern selects support one_round/tree, "
                     f"not {select!r}")


def candidate_pattern_estimates(stats: DBStats,
                                spec: Optional[PatternSpec], *,
                                ell: Optional[int] = None,
                                padded_rows: Optional[int] = None
                                ) -> List[CostEstimate]:
    """Eligible strategies for a pattern select — ``one_round`` and
    ``tree`` only: ``one_tuple`` is the exact-equality special case, even
    at an ℓ = 1 hint."""
    ell_eff = DEFAULT_ELL if ell is None else max(ell, 1)
    return [estimate_pattern_cost(stats, spec, select=strat, ell=ell_eff,
                                  padded_rows=padded_rows)
            for strat in ("one_round", "tree")]


#: backend launches one PK/FK match-matrix evaluation needs per method:
#: the §3.1.2 chain walks the word one dot-set per position; the aggregate
#: form flattens all W·A products into ONE contraction plus the Lagrange
#: equality indicator (2 launches, any W).
MATCH_METHOD_LAUNCHES = {"chain": lambda w: w, "aggregate": lambda w: 2}


def estimate_match_method_launches(stats: DBStats, method: str) -> int:
    """Device launches for one match-matrix evaluation under ``method``."""
    try:
        return MATCH_METHOD_LAUNCHES[method](stats.w)
    except KeyError:
        raise ValueError(f"unknown match_method {method!r}; choose from "
                         f"('chain', 'aggregate')") from None


def choose_match_method(stats: DBStats, method: str = "auto") -> str:
    """Resolve a ``Join.match_method`` — the §3.1.2 chain-vs-aggregate
    execution knob. Both methods open the same match matrix at the same
    degree (2tW) with identical ledgers, so bits and rounds never
    discriminate; the planner prices the remaining axis — backend launch
    count — and AUTO takes the cheaper evaluation (``aggregate`` whenever
    the word is longer than its two fixed launches, i.e. any real W)."""
    if method != "auto":
        estimate_match_method_launches(stats, method)   # validate
        return method
    return min(("chain", "aggregate"),
               key=lambda m: estimate_match_method_launches(stats, m))


def choose_reduce_every(c: int, degree: int, t_bits: int,
                        requested: int = 0, *, tournament: bool = False,
                        match_degree: Optional[int] = None) -> int:
    """``reduce_every`` for a range (or, with ``tournament``, a MIN/MAX)
    ripple over a t-bit column shared at ``degree`` to ``c`` clouds.

    Every sharing re-shared or opened must stay at degree ≤ c − 1 (no
    fewer than degree + 1 points determine it): each segment's carry of
    the round engine's :func:`~repro.core.queries.rounds.ripple_schedule`,
    and the result bit, which the tournament's select x₁ + s·(x₂ − x₁)
    lifts by ``degree`` before it is re-shared or opened.
    ``requested = 0`` gets the widest segment that keeps them there: 0, no
    reduction, wherever the whole width fits, so such a plan runs, and
    costs, exactly as without the choice. An explicit ``requested`` is
    kept if it fits and refused otherwise. ``match_degree`` is a
    conditional tournament's match: its count opens at that degree and
    the masked candidates re-share at that plus ``degree``, whatever the
    segment. Raises ``ValueError`` naming the degree and c.
    """
    top = c - 1
    mux = degree if tournament else 0
    if match_degree is not None and match_degree + degree > top:
        raise ValueError(
            f"the MIN/MAX sentinel mask re-shares at degree "
            f"{match_degree + degree}; {c} clouds hold at most degree {top}")

    def worst(k: int) -> int:
        degrees = [g for _, _, g in ripple_schedule(t_bits, k, degree)]
        return max(degrees[:-1] + [degrees[-1] + mux])
    if requested:
        if worst(requested) > top:
            raise ValueError(
                f"reduce_every={requested} lets a {t_bits}-bit ripple reach "
                f"degree {worst(requested)}; {c} clouds re-share or open at "
                f"most degree {top}")
        return requested
    for k in range(t_bits, 0, -1):
        if worst(k) <= top:
            return 0 if k == t_bits else k
    raise ValueError(
        f"no reduce_every keeps a {t_bits}-bit ripple within {c} clouds: "
        f"one bit a segment reaches degree {worst(1)}, above {top}")


def estimate_range_cost(stats: DBStats, *, t_bits: int,
                        reduce_every: int = 0, want_addresses: bool = False,
                        ell: int = DEFAULT_ELL,
                        padded_rows: Optional[int] = None) -> CostEstimate:
    """§3.4 Algorithms 5/6: the SS-SUB ripple over a t-bit column.

    Bits mirror the measured ledger: both endpoints up (2·c·t elements),
    one 2c² re-share per degree-reduction boundary (each boundary is two
    logical rounds, one per subtraction direction), the count (c) or the n
    indicator bits plus the oblivious fetch down. Dispatches: one fused
    ripple *segment* per boundary interval per shard, plus the fetch step.
    """
    s = stats
    S = max(1, min(s.shards, max(s.n, 1)))
    n_red = (t_bits - 1) // reduce_every if reduce_every > 0 else 0
    segments = n_red + 1
    elems = s.c * 2 * t_bits + n_red * 2 * s.c * s.c
    rounds = 1 + 2 * n_red
    dispatches = segments * S
    if want_addresses:
        elems += s.c * s.n + _fetch_elems(s, ell, padded_rows)
        rounds += 1
        dispatches += S                              # the oblivious fetch
        name = "range_select"
    else:
        elems += s.c
        name = "range_count"
    return CostEstimate(name, elems * WORD_BITS, rounds=rounds,
                        dispatches=dispatches)


def estimate_aggregate_cost(stats: DBStats, op: str, *, t_bits: int,
                            conditional: bool = False, verify: bool = False,
                            reduce_every: int = 0) -> CostEstimate:
    """OBSCURE-style aggregation over a t-bit numeric column.

    sum:     one contraction round — pattern up (conditional only), a
             share of each limb's sum back from each cloud (one limb where
             the field sum cannot wrap: ``aggregate.sum_limbs``).
    avg:     the sum plus (conditional only) the §3.1 count round for the
             denominator; an unconditional AVG divides by the public n.
    min/max: knockout tournament of ⌈log₂ n⌉ SS-SUB comparator levels —
             each level pays its ``reduce_every`` carry reductions (one c²
             re-share round each) and every level but the last one
             inter-level re-share; conditional jobs add the sentinel-mask
             re-share round and open the match count alongside the value.
    verify:  +1 round and c checksum elements per opened tensor
             (value, and the count for a conditional min/max).

    Bits mirror the measured ledger exactly in ``CostLedger`` units.
    """
    s = stats
    S = max(1, min(s.shards, max(s.n, 1)))
    if op in ("sum", "avg"):
        elems = (s.c * len(sum_limbs(s.n, t_bits))
                 + (s.c * s.w * s.a if conditional else 0))
        rounds, dispatches = 1, S
        if op == "avg" and conditional:
            elems += _count_elems(s)
            rounds += 1
            dispatches += S
        if verify:
            rounds += 1
            elems += s.c
        return CostEstimate(f"agg_{op}", elems * WORD_BITS, rounds=rounds,
                            dispatches=dispatches)
    if op in ("min", "max"):
        levels = math.ceil(math.log2(s.n)) if s.n > 1 else 0
        n_red = (t_bits - 1) // reduce_every if reduce_every > 0 else 0
        elems = (levels * n_red * s.c * s.c          # carry reductions
                 + max(levels - 1, 0) * s.c * s.c    # inter-level re-shares
                 + s.c * t_bits)                     # final value opening
        rounds = 1 + levels * n_red + max(levels - 1, 0)
        dispatches = levels * (n_red + 1)
        if conditional:
            elems += s.c * s.w * s.a + s.c * s.c + s.c
            rounds += 1
            dispatches += S
        if verify:
            rounds += 1
            elems += s.c * (2 if conditional else 1)
        return CostEstimate(f"agg_{op}", elems * WORD_BITS, rounds=rounds,
                            dispatches=dispatches)
    raise ValueError(f"unknown aggregate op {op!r}")


def estimate_embed_cost(stats: DBStats, *, n_tokens: int,
                        verify: bool = False) -> CostEstimate:
    """§3.2.1 as the LM embedding layer: one fused lookup round.

    The relation is the shared ``(c, V, D)`` table (``n`` = V vocab rows,
    ``m`` = D model dims). The step's ``n_tokens`` shared one-hots go up
    (c·n_tok·V), the picked embedding share rows come down (c·n_tok·D),
    all in ONE contraction — dispatches = S (one ``ss_matmul`` per shard).
    ``verify=`` adds the OBSCURE consistency round and c checksum elements.

    Bits mirror the measured ledger exactly in ``CostLedger`` units.
    """
    s = stats
    S = max(1, min(s.shards, max(s.n, 1)))
    elems = s.c * n_tokens * s.n + s.c * n_tokens * s.m
    rounds = 1
    if verify:
        rounds += 1
        elems += s.c
    return CostEstimate("embed", elems * WORD_BITS, rounds=rounds,
                        dispatches=S)


def estimate_pkfk_cost(stats: DBStats, right: DBStats) -> CostEstimate:
    """§3.3.1: match-matrix step (per shard) + the shared fetch + one round
    shipping every reducer's (parent ⊕ child) concatenation."""
    s = stats
    S = max(1, min(s.shards, max(s.n, 1)))
    elems = s.c * right.n * (s.m + right.m) * s.w * s.a
    return CostEstimate("pkfk", elems * WORD_BITS, rounds=1,
                        dispatches=2 * S)            # match + fetch steps


def estimate_equijoin_cost(stats: DBStats, right: DBStats, *,
                           values: int = 1,
                           fake_values: int = 0) -> CostEstimate:
    """§3.3.2 (Thm 6): column-open round + 2 rounds per (fake) common
    value. ``values`` is the caller's guess at k (the true count is data
    the planner cannot see); value groups are assumed singletons, the
    asymptotically common PK-ish case. Dispatches: the X-side layer-1
    matmul fans per shard, the Y-side runs against the (unsharded) right."""
    s = stats
    S = max(1, min(s.shards, max(s.n, 1)))
    k = max(0, values) + max(0, fake_values)
    elems = (s.c * s.n * s.w * s.a + right.c * right.n * s.w * s.a  # open
             + k * (s.c * s.n + right.c * right.n)       # layer-1 one-hots
             + k * s.c * (s.m + right.m) * s.w * s.a)    # layer-2 pairs
    return CostEstimate("equi", elems * WORD_BITS, rounds=1 + 2 * k,
                        dispatches=S + 1)



def candidate_estimates(stats: DBStats, *, ell: Optional[int] = None,
                        padded_rows: Optional[int] = None
                        ) -> List[CostEstimate]:
    """All eligible strategies for cardinality hint ℓ (None = unknown)."""
    known_one = ell == 1
    ell_eff = DEFAULT_ELL if ell is None else max(ell, 1)
    out = []
    if known_one and not padded_rows:
        out.append(estimate_select_cost("one_tuple", stats, ell=1))
    for strat in ("one_round", "tree"):
        out.append(estimate_select_cost(strat, stats, ell=ell_eff,
                                        padded_rows=padded_rows))
    return out


def choose_select_strategy(stats: DBStats, *, ell: Optional[int] = None,
                           padded_rows: Optional[int] = None,
                           round_cost_bits: int = 0,
                           group_sizes: Optional[Mapping[str, int]] = None,
                           group_rounds: Optional[Mapping[str, int]] = None
                           ) -> CostEstimate:
    """Pick the paper-optimal strategy: min bits, rounds as tie-break
    (price a round via ``round_cost_bits`` to trade bandwidth for latency).

    ``group_sizes`` makes the choice *batching-aware*: it maps strategy name
    to the number of batch-mates already executing that strategy in the
    current ``run_batch``. The batched round engine fuses a group's protocol
    rounds into one dispatch/interpolation each, so a query that joins a
    non-empty group pays its bits but rides the group's existing rounds for
    free — its **marginal** round cost is only the depth it adds beyond the
    group's deepest member (``group_rounds``: strategy -> estimated rounds
    of that deepest member; without it a non-empty group is assumed at
    least as deep as the rider). With ``round_cost_bits > 0`` that steers a
    borderline query onto the strategy of an already-running (typically the
    larger) group whenever riding its fused rounds is cheaper than opening
    a new round chain. With the default pricing (``round_cost_bits = 0``)
    rounds never enter the score, so the choice — and therefore every
    row/ledger — is identical to sequential planning.
    """
    cands = candidate_estimates(stats, ell=ell, padded_rows=padded_rows)
    return min(cands, key=_riding_key(round_cost_bits, group_sizes,
                                      group_rounds))


def _riding_key(round_cost_bits: int,
                group_sizes: Optional[Mapping[str, int]],
                group_rounds: Optional[Mapping[str, int]]):
    """Batching-aware scoring: a strategy whose group is already running
    pays only its *marginal* rounds beyond the group's deepest member."""
    def key(e: CostEstimate):
        riding = bool(group_sizes) and group_sizes.get(e.strategy, 0) > 0
        if riding:
            depth = (group_rounds or {}).get(e.strategy)
            marginal_rounds = (0 if depth is None
                               else max(0, e.rounds - depth))
        else:
            marginal_rounds = e.rounds
        return (e.bits + round_cost_bits * marginal_rounds, e.rounds)
    return key


def choose_pattern_strategy(stats: DBStats, spec: Optional[PatternSpec], *,
                            ell: Optional[int] = None,
                            padded_rows: Optional[int] = None,
                            round_cost_bits: int = 0,
                            group_sizes: Optional[Mapping[str, int]] = None,
                            group_rounds: Optional[Mapping[str, int]] = None
                            ) -> CostEstimate:
    """:func:`choose_select_strategy` for a pattern predicate: the same
    min-bits / marginal-rounds scoring over the pattern-eligible
    candidates (``one_round``/``tree`` — never ``one_tuple``)."""
    cands = candidate_pattern_estimates(stats, spec, ell=ell,
                                        padded_rows=padded_rows)
    return min(cands, key=_riding_key(round_cost_bits, group_sizes,
                                      group_rounds))


def estimate_batch_group_cost(stats: DBStats, strategy: str, *,
                              ells: Sequence[Optional[int]],
                              padded_rows: Optional[int] = None,
                              specs: Optional[Sequence[
                                  Optional[PatternSpec]]] = None
                              ) -> CostEstimate:
    """Price a whole ``run_batch`` group: bits add up query by query, but
    the lockstep engine pays each protocol round — and each per-shard
    dispatch — once for the group, so the group's round and dispatch counts
    are its deepest member's (not the sum). This is the per-group ledger
    shape ``tests/test_batch.py`` asserts, exposed as a planner-side
    estimate. ``specs`` aligns with ``ells`` and prices pattern-predicate
    members through :func:`estimate_pattern_cost` (a ``None`` entry is an
    exact-equality member; both estimators agree there, field for field)."""
    specs = specs if specs is not None else [None] * len(ells)
    ests = [estimate_pattern_cost(
        stats, spec, select=strategy,
        ell=DEFAULT_ELL if e is None else max(e, 1),
        padded_rows=padded_rows)
        if (spec is not None and strategy != "one_tuple")
        else estimate_select_cost(
            strategy, stats, ell=DEFAULT_ELL if e is None else max(e, 1),
            padded_rows=padded_rows)
        for e, spec in zip(ells, specs)]
    return CostEstimate(strategy,
                        bits=sum(e.bits for e in ests),
                        rounds=max((e.rounds for e in ests), default=0),
                        dispatches=max((e.dispatches for e in ests),
                                       default=0))


#: group families whose oblivious fetch rides the single cross-group
#: ``ss_matmul`` of ``run_batch`` (their solo estimates each include one
#: fetch step; the batch pays it once).
FETCH_RIDERS = ("one_round", "tree", "range_select", "pkfk")


@dataclasses.dataclass(frozen=True)
class GroupEstimate:
    """One ``run_batch`` group's predicted ledger."""
    family: str                 # count/one_tuple/one_round/tree/range_*/…
    size: int                   # member queries
    estimate: CostEstimate      # bits summed, rounds/dispatches fused


@dataclasses.dataclass(frozen=True)
class BatchExplanation:
    """Predicted ``run_batch`` ledger for a prospective batch.

    bits sum over every member query (protocol bits are per query, fusion
    never changes them); rounds are the deepest group's (groups share the
    batch's fused round structure); dispatches total the per-shard device
    fan-out with the cross-group fetch counted ONCE (each rider group's
    solo estimate prices its own fetch step — the assembly removes the
    duplicates).
    """
    groups: Tuple[GroupEstimate, ...]
    bits: int
    rounds: int
    dispatches: int
    shards: int
    relation: str = ""


def explain_batch_groups(stats: DBStats,
                         groups: Sequence[GroupEstimate]
                         ) -> BatchExplanation:
    """Assemble per-group estimates into the batch-level prediction."""
    S = max(1, min(stats.shards, max(stats.n, 1)))
    riders = sum(1 for g in groups
                 if g.family in FETCH_RIDERS and g.size > 0)
    dispatches = sum(g.estimate.dispatches for g in groups)
    if riders > 1:
        dispatches -= (riders - 1) * S      # ONE shared fetch dispatch set
    return BatchExplanation(
        groups=tuple(groups),
        bits=sum(g.estimate.bits for g in groups),
        rounds=max((g.estimate.rounds for g in groups), default=0),
        dispatches=dispatches,
        shards=S,
        relation=stats.relation)


def _has_fetch(part: BatchExplanation) -> bool:
    return any(g.family in FETCH_RIDERS and g.size > 0 for g in part.groups)


@dataclasses.dataclass(frozen=True)
class MultiBatchExplanation:
    """Predicted ledgers for a fused multi-relation ``run_batch_multi``.

    Per-relation predictions are the untouched solo
    :class:`BatchExplanation`\\ s — cross-relation fusion co-schedules the
    already-independent shard dispatches, so no relation's bits, rounds or
    dispatch fan-out moves. What fusion buys is waves: the
    ``fetch_parts`` relations that would each close with their own fetch
    dispatch wave share ONE (``fetch_waves``); the wave's total dispatch
    fan-out stays Σ of the per-relation shard counts.
    """
    parts: Tuple[BatchExplanation, ...]
    bits: int                   # Σ parts — protocol bits are per relation
    rounds: int                 # deepest part (waves run side by side)
    dispatches: int             # Σ parts — fan-out is per relation's shards
    fetch_parts: int            # relations riding the shared fetch wave
    fetch_waves: int            # 1 when >= 2 parts fuse, else fetch_parts


def explain_multi_batches(parts: Sequence[BatchExplanation]
                          ) -> MultiBatchExplanation:
    """Price a prospective ``run_batch_multi`` from its solo predictions."""
    fetch_parts = sum(1 for p in parts if _has_fetch(p))
    return MultiBatchExplanation(
        parts=tuple(parts),
        bits=sum(p.bits for p in parts),
        rounds=max((p.rounds for p in parts), default=0),
        dispatches=sum(p.dispatches for p in parts),
        fetch_parts=fetch_parts,
        fetch_waves=1 if fetch_parts > 1 else fetch_parts)
