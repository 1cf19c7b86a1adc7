"""The comparison that decides `correct`: the reference agrees with the
store, and a run whose timed path is broken underneath comes out not
correct — for each fault a lookup or scan cell can have, and for the
control (the store's own degraded path: one shard served as a hole)."""
import numpy as np
import pytest

from bench import check, run, spec
from bench.reference import TripleReference
from bench.tests.tiny import REPO, SEED, on_cpu, tiny_root
from bench.traffic import Record

SHAPES = ("spo", "sp?", "s?o", "s??", "?po", "?p?", "??o", "???")
CELLS = [w["name"] for w in spec.load_benchmark(REPO)["workloads"]]


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tiny_root(tmp_path_factory.mktemp("tiny"))


@pytest.fixture(scope="module")
def stores(tmp_path_factory):
    return tmp_path_factory.mktemp("stores")


@pytest.fixture(scope="module")
def store():
    from repro.serve.sharded import ShardedTripleService

    triples, n_nodes, n_preds = spec.generator(REPO, "rdf_like")(SEED, 600, 2000, 7)
    svc = ShardedTripleService.build(triples, n_nodes, n_preds, n_shards=4,
                                     strategy="predicate_hash", serve_threads=1)
    yield svc, triples
    svc.close()


def _patterns(triples, rng, n=40):
    rows = triples[rng.integers(0, len(triples), n)]
    out = [tuple(None if ch == "?" else int(v) for ch, v in zip(shape, row))
           for shape in SHAPES for row in rows]
    out.append((10**6, None, None))  # an unknown subject: empty on both sides
    return out


def test_reference_agrees_with_the_store_on_every_shape(store):
    svc, triples = store
    patterns = _patterns(triples, np.random.default_rng(5))
    answers = svc.query_many(patterns)
    ref = TripleReference(triples)
    for pattern, got in zip(patterns, answers):
        want = ref.answer(pattern)
        assert np.array_equal(check.answer_rows(got), want), pattern
    assert sum(len(a) for a in answers) > len(patterns)


def _record(patterns, answers):
    rec = Record(0, "mixed", patterns, 0.0, 0.0, 0.1)
    rec.counts = [len(a) for a in answers]
    rec.answer = answers
    return rec


def test_a_wrong_answer_fails_the_check(store):
    svc, triples = store
    patterns = _patterns(triples, np.random.default_rng(6))
    answers = svc.query_many(patterns)
    ok = check.compare([_record(patterns, answers)], triples)["checks"]
    assert all(c["value"] == 0 for c in ok.values())
    i = next(j for j, a in enumerate(answers) if len(a) > 1)
    p, (s, o) = answers[i][0]
    altered = list(answers)
    altered[i] = ((p, (s, o + 1)),) + tuple(answers[i][1:])  # same count, one triple off
    bad = check.compare([_record(patterns, altered)], triples)["checks"]
    assert bad["wrong_answers"]["value"] == 1
    assert bad["wrong_counts"]["value"] == 0
    dropped = list(answers)
    dropped[i] = answers[i][1:]
    bad = check.compare([_record(patterns, dropped)], triples)["checks"]
    assert bad["wrong_counts"]["value"] == 1 and bad["wrong_answers"]["value"] == 1


def test_a_request_that_never_returns_is_lost(store):
    _, triples = store
    rec = Record(0, "spo", [(1, 2, 3)], 0.0)  # never started, never ended
    checks = check.compare([rec], triples)["checks"]
    assert checks["lost_requests"]["value"] == 1


# -- the timed path broken underneath: each run must come out not correct --

def _off_by_one_rank(monkeypatch):
    """An answer altered where it is produced: the k²-tree descent's rank
    returns one too many, on the host and device paths alike."""
    from repro.core.succinct.k2tree import K2Tree
    real = K2Tree._rank

    def rank(self, site, t, pos):
        out = real(self, site, t, pos)
        return out + (np.arange(len(out)) % 7 == 3)
    monkeypatch.setattr(K2Tree, "_rank", rank)


def _half_the_batch(monkeypatch):
    """Half of each request's patterns left out: answered empty."""
    from repro.serve.triple_service import MicroBatchService
    real = MicroBatchService.query_many

    def query_many(self, patterns):
        patterns = list(patterns)
        half = len(patterns) // 2 or 1
        return real(self, patterns[:half]) + [()] * (len(patterns) - half)
    monkeypatch.setattr(MicroBatchService, "query_many", query_many)


def _altered_tuple(monkeypatch):
    """One triple of every non-empty answer altered as the answer is built."""
    from repro.core.query import QueryResultView
    real = QueryResultView.entry_tuples

    def entry_tuples(self, index):
        out = real(self, index)
        if out:
            p, (s, o) = out[0]
            out[0] = (p, (s, o + 1))
        return out
    monkeypatch.setattr(QueryResultView, "entry_tuples", entry_tuples)


def _fails_sometimes(monkeypatch):
    """A request of the window that raises: it never comes back with an
    answer (the warm-up, on the main thread, is spared)."""
    import threading

    from repro.serve.triple_service import MicroBatchService
    real = MicroBatchService.query_many
    calls = {"n": 0}

    def query_many(self, patterns):
        if threading.current_thread() is threading.main_thread():
            return real(self, patterns)
        calls["n"] += 1
        if calls["n"] % 5 == 1:
            raise RuntimeError("planted fault")
        return real(self, patterns)
    monkeypatch.setattr(MicroBatchService, "query_many", query_many)


FAULTS = {"rank_off_by_one": (_off_by_one_rank, ("lookup",)),
          "half_the_batch": (_half_the_batch, ("lookup", "scan")),
          "altered_answer": (_altered_tuple, ("lookup", "scan")),
          "request_raises": (_fails_sometimes, ("lookup", "scan"))}
CASES = [(c, f) for c in CELLS for f, (_, kinds) in FAULTS.items()
         if c.rsplit(".", 1)[1] in kinds]


@pytest.mark.parametrize("cell,fault", CASES)
def test_planted_fault_is_not_correct(root, stores, cell, fault, monkeypatch):
    on_cpu(monkeypatch)
    # the store is built sound (in its own process); the timed path breaks
    FAULTS[fault][0](monkeypatch)
    out = run.run_cell(root, cell, SEED, 1.0, False, store_root=stores)
    assert out["correct"] is False
    assert any(c["value"] > c["limit"] for c in out["checks"].values())


@pytest.mark.parametrize("cell", CELLS)
def test_control_degraded_shard_is_not_correct(root, stores, cell, monkeypatch):
    """The control: the store's own degraded path (a shard whose snapshot
    would not load, served as an empty hole) breaks the configuration's
    exact-answer guarantee, and the check sees it."""
    from bench.control import degrade_one_shard
    on_cpu(monkeypatch)
    out = run.run_cell(root, cell, SEED, 1.0, False, store_root=stores,
                       prepare=degrade_one_shard)
    assert out["correct"] is False
    assert out["checks"]["wrong_counts"]["value"] > 0
