"""A private embedding table served by ``QueryServer``.

The table (``vocab_size`` x ``hidden_size``, float32, N(0, 1) clipped to
``clip``) is made from the seed and shared by the program's own owner step
(``setup_private_embed``) in ONE jitted program, so it never leaves the
device. Requests are ``EmbedLookup``s of token ids.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np

import datagen
import reference
import roofline
import traffic as traffic_mod

RELATION = "embed"
WRONG_SHAPE_GAP = 2 ** 31   # the gap reported for an answer of wrong shape


class Deployment:
    relation = RELATION

    def __init__(self, config: dict, traffic: dict, seed: int):
        import jax
        from repro import api
        from repro.launch.serve import QueryServer
        from repro.models import private_embed as pe

        self.api = api
        self.config = config
        self.seed = seed
        vocab, width = config["vocab_size"], config["hidden_size"]
        k_table, k_share, k_query = (jax.random.PRNGKey(s) for s in
                                     datagen.sub_seeds(seed, 3))
        clouds, degree, clip = (config["clouds"], config["degree"],
                                config["clip"])

        @jax.jit
        def shared_table(k_table, k_share):
            table = datagen.embed_table(k_table, vocab, width, clip)
            return pe.setup_private_embed(k_share, table, n_shares=clouds,
                                          degree=degree)
        shares = shared_table(k_table, k_share)
        server = traffic["server"]
        self.server = QueryServer(key=k_query,
                                  max_batch=server["max_batch"],
                                  max_wait_ms=server["max_wait_ms"])
        self.server.attach(RELATION, pe.as_embed_relation(shares),
                           dispatcher=api.MeshDispatcher())

    def column_values(self, name: str) -> list:
        raise KeyError(f"an embedding table has no column {name!r}")

    def plan(self, request: dict):
        if request["plan"] != "embed_lookup":
            raise ValueError(f"unknown plan {request['plan']!r}")
        return self.api.EmbedLookup(tokens=tuple(request["tokens"]))

    def plane_stats(self):
        return self.server.dataplane_of(RELATION).stats

    def batch_work(self, requests: List[dict]) -> Dict[str, roofline.Work]:
        cfg = self.config
        m = sum(len(r["tokens"]) for r in requests)
        return {"contraction": roofline.contraction(
            cfg["clouds"], m, cfg["vocab_size"], cfg["hidden_size"])}

    def release(self) -> None:
        self.server = None

    def compare(self, records) -> Dict[str, int]:
        """Requests whose opened rows differ from the quantized table rows,
        and the widest gap in steps of the fixed point."""
        import jax
        cfg = self.config
        done = [r for r in records if r.result is not None]
        if not done:
            return {"wrong_answers": 0, "max_gap_steps": 0}
        tokens = np.concatenate([np.asarray(r.request["tokens"])
                                 for r in done])
        k_table = jax.random.PRNGKey(datagen.sub_seeds(self.seed, 3)[0])
        rows = jax.jit(lambda k, t: datagen.embed_table(
            k, cfg["vocab_size"], cfg["hidden_size"], cfg["clip"])[t])(
                k_table, tokens)
        want = reference.quantize_rows(np.asarray(rows), cfg["quant_scale"])
        wrong, gap, off = 0, 0.0, 0
        for r in done:
            got = np.asarray(r.result.embeddings, np.float32)
            ref = want[off:off + len(r.request["tokens"])]
            off += len(r.request["tokens"])
            if got.shape != ref.shape:
                wrong += 1
                gap = max(gap, float(WRONG_SHAPE_GAP))
                continue
            d = float(np.max(np.abs(got.astype(np.float64) - ref)))
            wrong += d > 0
            gap = max(gap, d * cfg["quant_scale"])
        return {"wrong_answers": wrong, "max_gap_steps": gap}


def warm_requests(traffic: dict, deployment: Deployment) -> List[List[dict]]:
    """One batch of each size up to the client count."""
    rng = np.random.default_rng(0)
    (template,) = [m["request"] for m in traffic["mix"]]
    return [[traffic_mod.realize(template, rng, deployment.column_values,
                                 deployment.config) for _ in range(b)]
            for b in range(1, traffic["clients"] + 1)]
