#!/usr/bin/env python3
"""The ripple control for ``correct``: the SS-SUB ripple with its mod-p
products formed in float32.

    python3 bench/control_range.py --workload lineitem.range --seconds 5 \
        --seeds 11 12 13 [--control]

Runs the cell once per seed in ONE process, as ``control.py`` does, and
prints each number the run compares with the reference. With
``--control`` the served backend's ``ripple_segment`` (every range count
and every MAX comparison) multiplies its shares in float32, the precision
below the exact F_p arithmetic the configuration states; its runs must
come out not correct. The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def float32_ripple_segment():
    """The jnp ripple segment (Algorithm 6 lines 1-4 per bit) with each
    product of two shares formed in float32 and reduced mod p: inexact
    once a product passes 2^24."""
    import jax
    import jax.numpy as jnp
    from repro.core import field
    p = float(field.P)

    def mul(x, y):
        prod = x.astype(jnp.float32) * y.astype(jnp.float32)
        return jnp.mod(prod, p).astype(jnp.uint32)

    @jax.jit
    def ripple_segment(a, b, carry=None):
        rb = None
        for i in range(a.shape[-1]):
            ai = field.sub(jnp.ones_like(a[..., i]), a[..., i])
            bi = b[..., i]
            ab = mul(ai, bi)
            if carry is None:
                s = field.add(ai, bi)
                carry = field.sub(s, ab)
                rb = field.sub(s, field.add(carry, carry))
                continue
            x = field.sub(field.add(ai, bi), field.add(ab, ab))
            cx = mul(carry, x)
            rb = field.sub(field.add(x, carry), field.add(cx, cx))
            carry = field.add(ab, cx)
        return rb, carry
    return ripple_segment


def install_control() -> None:
    """Swap the served backend's ripple segment for the float32 one."""
    from repro.api import backends
    served = backends.get_backend("jnp")
    backends.register_backend(
        dataclasses.replace(served, ripple_segment=float32_ripple_segment()),
        overwrite=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]
    import harness
    import repro  # noqa: F401
    if args.control:
        install_control()
    cell = harness.load_cell(args.workload)
    for seed in args.seeds:
        t0 = time.perf_counter()
        result = harness.run_cell(cell, seed, args.seconds, False,
                                  t_start=t0)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control,
                          "correct": result["correct"],
                          "attempted": result["attempted"],
                          "failed": result["failed"],
                          "errors": result["errors"],
                          "checks": result["checks"],
                          "memory_peak_bytes":
                              result["device"]["memory_peak_bytes"],
                          "seconds": time.perf_counter() - t0}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
