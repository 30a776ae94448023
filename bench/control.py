#!/usr/bin/env python3
"""Readings for the limits of ``correct``: sound runs and the control.

    python3 bench/control.py --workload lineitem.match --seconds 5 \
        --seeds 11 12 13 [--control]

Runs the cell once per seed in ONE process (set-up and compiles are paid
once) and prints, per seed, each number the run compares with the
reference. With ``--control`` the program's share-space contraction (the
backend's ``ss_matmul``: every select's fetch and every embedding lookup)
is replaced by the same contraction in float32, the precision below the
exact F_p arithmetic the configurations state; its runs must come out not
correct. The benchmark's own runs never run this.
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


def float32_contraction(a, b):
    """``a @ b`` over share values in float32, reduced mod p: inexact once
    the sums pass 2^24."""
    import jax.numpy as jnp
    p = float(2**31 - 1)
    prod = jnp.matmul(a.astype(jnp.float32), b.astype(jnp.float32),
                      precision="highest")
    return jnp.mod(prod, p).astype(jnp.uint32)


def install_control() -> None:
    """Swap the served backend's contraction for the float32 one."""
    from repro.api import backends
    served = backends.get_backend("jnp")
    backends.register_backend(
        dataclasses.replace(served, ss_matmul=float32_contraction),
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
                          "checks": result["checks"],
                          "memory_peak_bytes":
                              result["device"]["memory_peak_bytes"],
                          "seconds": time.perf_counter() - t0}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
