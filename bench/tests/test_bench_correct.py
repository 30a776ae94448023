"""``correct`` at sizes a test run can hold, on the CPU: a sound run of
each cell passes the reference; the control (the contraction in float32)
and each fault a serving cell can have (an answer altered where it is
produced; half of a batch left out, the rest's answers reused) come out
not correct. The harness's look for a chip is skipped; the rest of the run
is the benchmark's own."""
import time

import numpy as np
import pytest

import control
import harness

SEED = 2**31 + 77


def _cell(name):
    cell = harness.load_cell(name)
    if cell.config["system"] == "relation":
        cell.config["rows"] = 48
    else:
        cell.config["vocab_size"], cell.config["hidden_size"] = 256, 32
    return cell


def _run(name, seconds=1.5):
    return harness.run_cell(_cell(name), SEED, seconds, False,
                            t_start=time.perf_counter(), require_chip=False)


@pytest.fixture
def served_backend():
    """Put the served backend back after a test that swaps it."""
    from repro.api import backends
    saved = backends.get_backend("jnp")
    yield
    backends.register_backend(saved, overwrite=True)


@pytest.mark.parametrize("cell", ["lineitem.match", "embed.decode"])
def test_sound_run_is_correct(cell):
    r = _run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert all(c["value"] == 0 for c in r["checks"].values())
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("cell", ["lineitem.match", "embed.decode"])
def test_control_is_not_correct(cell, served_backend):
    control.install_control()
    r = _run(cell)
    assert r["correct"] is False, r["checks"]
    assert (r["checks"]["wrong_answers"]["value"] > 0
            or r["checks"]["unanswered"]["value"] > 0), r["checks"]


def _alter_answers(monkeypatch):
    from repro.core.queries import embed, rounds
    count_phase, embed_phase = rounds.count_phase, embed.embed_phase

    def altered_counts(be, db, jobs):
        out = count_phase(be, db, jobs)
        return [out[0] + 1] + out[1:] if out else out

    def altered_rows(be, rel, jobs):
        out = embed_phase(be, rel, jobs)
        if out:
            out[0] = np.array(out[0], copy=True)
            out[0][0, 0] += 1.0 / 4096
        return out
    monkeypatch.setattr(rounds, "count_phase", altered_counts)
    monkeypatch.setattr(embed, "embed_phase", altered_rows)


def _drop_half_of_each_batch(monkeypatch):
    from repro.api import QueryClient
    run_batch = QueryClient.run_batch

    def half(self, plans, *, relation=None):
        kept = list(plans)[:(len(plans) + 1) // 2]
        out = run_batch(self, kept, relation=relation)
        return [out[i % len(out)] for i in range(len(plans))]
    monkeypatch.setattr(QueryClient, "run_batch", half)


@pytest.mark.parametrize("cell", ["lineitem.match", "embed.decode"])
@pytest.mark.parametrize("fault", [_alter_answers, _drop_half_of_each_batch])
def test_faults_are_not_correct(cell, fault, monkeypatch):
    fault(monkeypatch)
    r = _run(cell)
    assert not r["correct"], (fault.__name__, r["checks"])
