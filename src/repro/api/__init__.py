"""repro.api — the stable, composable client surface for the query suite.

OBSCURE-style framing: the user holds a :class:`QueryClient` over the
secret-shared clouds; queries are *logical plans* (``Count``, ``Select``,
``RangeCount``, ``RangeSelect``, ``Join``) with columns by name, predicate
objects and an explicit padding policy; a cost-based planner picks the
paper-optimal selection strategy; backends are looked up in a registry
(``jnp``, ``pallas``, or anything registered); and every query returns one
:class:`QueryResult`.

    from repro.api import QueryClient, Eq, Select
    client = QueryClient(db, key=7, backend="jnp")
    res = client.select("FirstName", "John")      # planner picks strategy
    res.rows, res.count, res.ledger, res.strategy

The legacy free functions in ``repro.core.queries`` remain as thin
deprecated wrappers; new code should go through this package.
"""
from ..core.dataplane import (Dispatcher, MapReduceDispatcher, PoolHandle,
                              ShardedRelation, ThreadedDispatcher)
from ..core.encoding import PatternSpec, parse_like
from ..core.mesh_dispatch import MeshDispatcher
from ..core.queries import VerificationError
from .backends import (Backend, available_backends, batched_match_matrix,
                       batched_matcher, get_backend, register_backend,
                       ripple_segmenter, ripple_stepper, slide_matcher)
from .client import DEFAULT_RELATION, AttachedRelation, QueryClient
from .planner import (DEFAULT_ELL, BatchExplanation, CostEstimate, DBStats,
                      GroupEstimate, PlanNotSupported, candidate_estimates,
                      candidate_pattern_estimates, choose_match_method,
                      choose_pattern_strategy, choose_select_strategy,
                      estimate_aggregate_cost, estimate_batch_group_cost,
                      estimate_count_cost, estimate_embed_cost,
                      estimate_equijoin_cost, estimate_match_method_launches,
                      estimate_pattern_cost, estimate_pkfk_cost,
                      estimate_range_cost, estimate_select_cost,
                      explain_batch_groups)
from .plans import (AUTO, Aggregate, Between, ColumnRef, Contains, Count,
                    EmbedLookup, Eq, Join, Like, Padding, Plan, Prefix,
                    QueryResult, RangeCount, RangeSelect, Select, Suffix,
                    resolve_column)

__all__ = [
    "Backend", "available_backends", "batched_matcher",
    "batched_match_matrix", "get_backend", "register_backend",
    "ripple_segmenter", "ripple_stepper", "slide_matcher", "QueryClient",
    "DEFAULT_RELATION", "AttachedRelation",
    "MapReduceDispatcher", "MeshDispatcher",
    "Dispatcher", "PoolHandle", "ShardedRelation", "ThreadedDispatcher",
    "DEFAULT_ELL", "BatchExplanation", "CostEstimate", "DBStats",
    "GroupEstimate", "PlanNotSupported", "candidate_estimates",
    "candidate_pattern_estimates", "choose_match_method",
    "choose_pattern_strategy", "choose_select_strategy",
    "estimate_aggregate_cost", "estimate_batch_group_cost",
    "estimate_count_cost", "estimate_embed_cost", "estimate_equijoin_cost",
    "estimate_match_method_launches", "estimate_pattern_cost",
    "estimate_pkfk_cost", "estimate_range_cost", "estimate_select_cost",
    "explain_batch_groups",
    "AUTO", "Aggregate", "Between", "ColumnRef", "Contains", "Count",
    "EmbedLookup", "Eq", "Join", "Like", "Padding", "PatternSpec", "Plan",
    "Prefix", "QueryResult", "RangeCount", "RangeSelect", "Select",
    "Suffix", "VerificationError", "parse_like", "resolve_column",
]
