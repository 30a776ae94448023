"""Privacy-preserving query suite on secret-shares (paper §3).

Every query function simulates both protocol sides faithfully:
user-side encode/share/interpolate, cloud-side oblivious share-space
computation, with a CostLedger recording bits/rounds/ops (Table 1 units).

DEPRECATED as a public surface: these free functions are kept as the
protocol implementations (and for backward compatibility), but new code
should use ``repro.api.QueryClient`` — one facade with logical plans,
name-based columns, automatic key derivation, a cost-based selection
planner, and the backend registry.
"""
from . import aggregate, embed, rounds
from .aggregate import VerificationError
from .embed import EmbedJob, embed_phase
from .count import count_query
from .pattern import like_spec, match_phase_cost, pattern_count, pattern_select
from .select import (CardinalityError, select_one_tuple, select_one_round,
                     select_tree)
from .join import pkfk_join, equijoin
from .range_query import ss_sub, range_count, range_select

__all__ = [
    "CardinalityError", "VerificationError", "aggregate", "embed", "rounds",
    "EmbedJob", "embed_phase", "count_query", "select_one_tuple",
    "select_one_round", "select_tree", "pkfk_join", "equijoin", "ss_sub",
    "range_count", "range_select", "like_spec", "match_phase_cost",
    "pattern_count", "pattern_select",
]
