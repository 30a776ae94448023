"""Operations, bytes and the least time of the benchmark's kernels.

Counted from the shapes of a call, never from the compiled program: the
same call costs the same whatever implements it (the jnp ``field.matmul``,
the ``ss_matmul`` kernel, or a later scheme).

A field element is 4 bytes. A field multiply-add of two 31-bit residues
mod p = 2^31 - 1 needs both operands split into at least four int8 digits
(three hold only 24 bits), and a product of two 4-digit numbers has
bilinear rank 2*4 - 1 = 7: no exact method built on int8 products uses
fewer than ``INT8_PRODUCTS_PER_MAC`` = 7 of them (the program uses 16).
Each int8 product-accumulate is two operations of the peak's count.
The least time of a call is the larger of its bytes over the HBM
bandwidth and its int8 operations over the int8 peak.
"""
from __future__ import annotations

import dataclasses
import json
import os

ELEMENT_BYTES = 4
INT8_PRODUCTS_PER_MAC = 7
OPS_PER_PRODUCT = 2
PEAKS_FILE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "peaks.json")


@dataclasses.dataclass(frozen=True)
class Work:
    """Field multiply-adds and bytes moved by one or more calls."""
    macs: int = 0
    bytes: int = 0

    def __add__(self, other: "Work") -> "Work":
        return Work(self.macs + other.macs, self.bytes + other.bytes)

    @property
    def int8_ops(self) -> int:
        return self.macs * INT8_PRODUCTS_PER_MAC * OPS_PER_PRODUCT


def peaks_for(device_kind: str) -> dict:
    """The peak table's row for ``device_kind``; a device not in the table
    is an error, never a default."""
    with open(PEAKS_FILE) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def contraction(c: int, m: int, k: int, n: int) -> Work:
    """(c, m, k) @ (c, k, n) mod p: operands and result read or written
    once."""
    return Work(macs=c * m * k * n,
                bytes=ELEMENT_BYTES * c * (m * k + k * n + m * n))


def match(c: int, b: int, n: int, width: int, alphabet: int,
          windows: int = 1) -> Work:
    """AA match of ``b`` predicates against one (c, n, width, alphabet)
    column: every tuple's one-hot share dotted with every pattern position
    in each of ``windows`` placements (a window chain of ``width`` rows).
    The column is read once for the whole stack; the chain's own products
    are left out, so the count is a floor for any exact method."""
    return Work(macs=c * b * n * windows * width * alphabet,
                bytes=ELEMENT_BYTES * c * (n * (width + windows - 1)
                                           * alphabet
                                           + b * width * alphabet
                                           + b * n * windows))


def least_seconds(work: Work, peaks: dict) -> float:
    """The least time any exact implementation can take for ``work``."""
    return max(work.bytes / peaks["hbm_bytes_per_s"],
               work.int8_ops / peaks["int8_ops_per_s"])
