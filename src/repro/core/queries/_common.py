"""Shared backend resolution for the query suite.

Queries accept ``backend=`` (a registered name or ``repro.api.backends
.Backend`` instance). The import of the registry is deferred: ``repro.api``
sits *above* the core layer, and resolving at call time keeps the layering
acyclic.
"""
from __future__ import annotations

from ..shamir import Shares


def get_backend(backend):
    """-> Backend: ``repro.api.backends.get_backend``, imported late."""
    from ...api import backends as _registry
    return _registry.get_backend(backend)


def match_matrix_shares(be, col_x: Shares, col_y: Shares) -> Shares:
    """Backend all-pairs match with the same degree bookkeeping."""
    w = col_x.values.shape[-2]
    return Shares(be.match_matrix(col_x.values, col_y.values),
                  (col_x.degree + col_y.degree) * w)
