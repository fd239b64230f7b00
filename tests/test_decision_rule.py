"""The decision rule's batched rank against the per-job loop it replaced.

``TuningService._ranks`` ranks a ``[J, K]`` block of score rows in
one numpy reduce over workload-grouped bank columns.  The loop below
(``_reduce``/``_rank``) is the rule as it was, kept here as the oracle:
per workload the best score, NaN ignored and floored at -1.0, the first
maximum in first-appearance order leading, the runner-up the best of the
rest (-1.0 with one workload).  Leaders, scores, runner-ups,
probabilities and every ``scores`` dict (values and key order) must come
out equal to the loop's, on random blocks and through a whole service.
"""
import dataclasses
import os
import sys
import zlib

import numpy as np
import pytest

from repro import mrsim
from repro.core.database import SeriesBank, pack_series
from repro.core.filters import preprocess_bank
from repro.core.similarity import similarity_bank
from repro.serve.tuning import TuningService, _Rank


# -- the oracle: the per-job loop --------------------------------------------
def _reduce(labels, sims):
    scores = {}
    for lbl, s in zip(labels, sims):
        scores[lbl] = max(scores.get(lbl, -1.0), float(s))
    return scores


def _rank(scores):
    leader, ls = None, -np.inf
    for w, s in scores.items():
        if s > ls:
            leader, ls = w, s
    rs = max((s for w, s in scores.items() if w != leader), default=-1.0)
    return leader, ls, rs


def _oracle_ranks(self, sims, probs):
    """Drop-in for ``TuningService._ranks`` built on the loop."""
    out = []
    for j, row in enumerate(sims):
        scores = _reduce(self._labels, row)
        leader, s, r = _rank(scores)
        lp = None if probs is None else _reduce(self._labels, probs[j]).get(
            leader, 0.0)
        out.append(_Rank(np.array(list(scores.values())),
                         self._workloads.index(leader), s, r, lp))
    return out


# -- equivalence on random blocks --------------------------------------------
J = 64
PER = 5                       # bank columns a workload


def _labels(layout, w, rng):
    if layout == "unlabeled":
        return ()
    names = [f"app{i}" for i in (2, 0, 1)[:w]]   # not in sorted order
    if layout == "grouped":
        return tuple(n for n in names for _ in range(PER))
    if layout == "interleaved":
        return tuple(names[k % w] for k in range(w * PER))
    lbl = list(names) + [names[i] for i in rng.integers(0, w, w * PER - w)]
    rng.shuffle(lbl)
    return tuple(lbl)


def _service(labels, k):
    rng = np.random.default_rng(0)
    series = [0.5 + 0.3 * np.sin(np.linspace(0, 3 + i, 12))
              + 0.01 * rng.standard_normal(12) for i in range(k)]
    return TuningService(pack_series(series, labels=list(labels)), slots=2)


def _block(rng, k, labels, case):
    sims = rng.uniform(-1.0, 1.0, (J, k)).round(2)     # rounding -> ties
    if case in ("neginf", "all"):
        sims[rng.random((J, k)) < 0.3] = -np.inf
        sims[0] = -np.inf                             # a fully pruned row
    if case in ("nan", "all"):
        sims[rng.random((J, k)) < 0.2] = np.nan
        sims[1] = np.nan
    if case in ("ties", "all") and labels:
        cols = {}
        for c, lbl in enumerate(labels):
            cols.setdefault(lbl, []).append(c)
        groups = list(cols.values())
        for j in range(2, J):
            top = rng.uniform(0.5, 0.9)
            sims[j] = np.minimum(sims[j], top)
            if len(groups) >= 2:               # tie at the leader
                sims[j, rng.choice(groups[-1])] = top
                sims[j, rng.choice(groups[0])] = top
            if len(groups) >= 3 and j % 2:     # tie at the runner-up
                sims[j, groups[1]] = np.minimum(sims[j, groups[1]], top - .1)
                sims[j, rng.choice(groups[1])] = top - 0.1
                sims[j, groups[0]] = np.minimum(sims[j, groups[0]], top - .1)
                sims[j, rng.choice(groups[0])] = top - 0.1
    elif case == "ties":
        sims[2:, : min(k, 2)] = 0.75                  # unlabeled ties
    if case in ("masked", "all"):
        for j in range(J):
            allowed = rng.random(k) < 0.6
            sims[j, ~allowed] = -np.inf
    probs = rng.uniform(0.0, 1.0, (J, k)).round(1)
    probs[sims == -np.inf] = 0.0
    return sims, probs


@pytest.mark.parametrize("case", ["plain", "neginf", "nan", "ties",
                                  "masked", "all"])
@pytest.mark.parametrize("w", [1, 2, 3])
@pytest.mark.parametrize("layout", ["grouped", "interleaved", "shuffled",
                                    "unlabeled"])
def test_rank_rows_equals_loop(layout, w, case):
    rng = np.random.default_rng(zlib.crc32(f"{layout}-{w}-{case}".encode()))
    labels = _labels(layout, w, rng)
    k = len(labels) or w * PER
    svc = _service(labels, k)
    if layout == "grouped" or w == 1:
        np.testing.assert_array_equal(svc._col_perm, np.arange(k))
    names = svc._labels
    sims, probs = _block(rng, k, labels, case)

    ranks = svc._ranks(sims, probs)
    assert len(ranks) == J
    for j, rank in enumerate(ranks):
        assert rank.scores.shape == (len(set(names)),)
        scores = _reduce(names, sims[j])
        leader, want_ls, want_rs = _rank(scores)
        got = dict(zip(svc._workloads, rank.scores.tolist()))
        assert got == scores
        assert list(got) == list(scores)
        assert svc._workloads[rank.leader] == leader
        assert rank.score == want_ls
        assert rank.runner_up == want_rs
        assert rank.probability == _reduce(names, probs[j]).get(leader, 0.0)
    assert all(r.probability is None for r in svc._ranks(sims, None))


def test_rank_rows_edge_values():
    """Signed zeros, values under the floor and exact ties of every
    workload rank as the loop does."""
    labels = ("a", "b", "a", "c", "b", "c")
    svc = _service(labels, len(labels))
    sims = np.array([
        [0.0, -0.0, -0.0, 0.0, 0.0, -0.0],
        [-3.0, -2.0, -np.inf, np.nan, -1.5, -1.0],
        [0.5, 0.5, 0.5, 0.5, 0.5, 0.5],
        [np.nan] * 6,
        [np.inf, 0.2, 0.1, np.inf, 0.3, 0.0],
    ])
    for row, rank in zip(sims, svc._ranks(sims, None)):
        scores = _reduce(labels, row)
        assert dict(zip(svc._workloads, rank.scores.tolist())) == scores
        assert (svc._workloads[rank.leader], rank.score,
                rank.runner_up) == _rank(scores)


# -- the service against the loop --------------------------------------------
@pytest.fixture(scope="module")
def paper_bank():
    """Interleaved labels (wordcount, exim, terasort, wordcount, ...), so
    the rule's column permutation is not the identity."""
    series, labels = [], []
    for p in mrsim.paper_param_sets()[:2]:
        for run in (1, 2):
            for app in mrsim.APPS:
                series.append(mrsim.simulate_cpu_series(app, p, run=run,
                                                        dt=0.5))
                labels.append(app)
    bank = pack_series(series, labels=labels)
    return SeriesBank(preprocess_bank(bank.series, bank.lengths),
                      bank.lengths, bank.labels, bank.entries)


@pytest.fixture(scope="module")
def queries():
    p = mrsim.paper_param_sets()[0]
    return {f"{app}{r}": mrsim.simulate_cpu_series(app, p, run=r, dt=0.5)
            for app in mrsim.APPS for r in (3, 4)}


SERVICES = {
    "point": dict(),
    "prob": dict(min_probability=0.5),
    "prefilter": dict(prefilter_top=4, prefilter_min_fraction=0.05),
    "prob-prefilter": dict(min_probability=0.5, prefilter_top=4,
                           prefilter_min_fraction=0.05),
}


def _record(svc, queries, prob, chunk=8):
    """Stream every job, finishing each in a batch as its samples run
    out -> the per-tick decisions and job states, the verdicts, and
    whether a job's view of the bank was ever masked (each such view is
    checked: the columns pruned for the job before the tick read -inf,
    and a probability of 0)."""
    def view(d):
        return None if d is None else (
            d.workload, d.matched, d.corr, list(d.scores.items()),
            d.probability, d.fraction_seen, d.decided_at_fraction, d.final)
    for jid, q in queries.items():
        svc.submit(jid, expected_len=len(q))
    trace, verdicts, masked = [], {}, False
    for lo in range(0, max(len(q) for q in queries.values()), chunk):
        ending = []
        for jid, q in queries.items():
            part = q[lo: lo + chunk]
            if part.size:
                svc.push(jid, part, variance=np.zeros_like(part)
                         if prob else None)
                if lo + chunk >= len(q):
                    ending.append(jid)
        pruned = {j: ~job.allowed for j, job in svc._jobs.items()
                  if job.allowed is not None and not job.allowed.all()}
        out = svc.tick()
        trace.append(({j: view(d) for j, d in out.items()},
                      {j: (job.leader, job.stable_for)
                       for j, job in svc._jobs.items()}))
        for j, cols in pruned.items():
            job = svc._jobs[j]
            assert np.all(job.last_sims[cols] == -np.inf)
            if prob:
                assert np.all(job.last_probs[cols] == 0.0)
        masked |= bool(pruned)
        if len(ending) > 1:
            for jid, d in svc.finish_many(ending).items():
                verdicts[jid] = view(d)
        else:
            for jid in ending:
                svc.finish_later(jid)
    for jid, d in svc.drain_finishes().items():
        verdicts[jid] = view(d)
    return trace, verdicts, masked


@pytest.mark.parametrize("kind", sorted(SERVICES))
def test_service_decisions_equal_loop(paper_bank, queries, monkeypatch, kind):
    prob = "min_probability" in SERVICES[kind]

    def run():
        return _record(TuningService(paper_bank, band=8, slots=8,
                                     denoise=False, finish_batch=4,
                                     margin=0.01, **SERVICES[kind]),
                       queries, prob)
    got = run()
    with monkeypatch.context() as m:
        m.setattr(TuningService, "_ranks", _oracle_ranks)
        want = run()
    assert got == want
    trace, verdicts, masked = got
    assert set(verdicts) == set(queries)
    early = [d for out, _ in trace for d in out.values() if d is not None]
    assert early, "the stream must reach early decisions"
    if "prefilter" in kind:
        assert masked, "the prefilter must prune some job's columns"


# -- chip_smoke's checks, on the CPU ------------------------------------------
def _chip_smoke():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    import chip_smoke
    return chip_smoke


@pytest.mark.parametrize("kind", ["point", "prob"])
def test_chip_smoke_checks(paper_bank, queries, kind):
    """``chip_smoke.py``'s leader and final-verdict checks run on the
    service's rule: the leader is the loop's, a verdict passes against
    its own query's matrix-path scores, and a verdict whose leader or
    match moved fails."""
    cs = _chip_smoke()
    prob = "min_probability" in SERVICES[kind]
    svc = TuningService(paper_bank, band=8, slots=2, denoise=False,
                        **SERVICES[kind])
    jid, x = "exim3", queries["exim3"]
    svc.submit(jid, expected_len=len(x))
    svc.push(jid, x, variance=np.zeros_like(x) if prob else None)
    svc.tick()
    row = svc._jobs[jid].last_sims
    assert cs.leader(svc, row) == _rank(_reduce(svc._labels, row))[0]
    query = svc._jobs[jid].x.view().copy()
    d = svc.finish_many([jid])[jid]
    ref = similarity_bank(query, paper_bank, band=8, matrix_path=True)
    assert cs.check_final(kind, svc, d, ref, point=not prob) <= cs.FINAL_TOL

    lead = max(d.scores, key=d.scores.get)
    moved = dict(d.scores)
    moved[next(w for w in moved if w != lead)] = d.scores[lead] + 1.0
    with pytest.raises(AssertionError, match="final leader"):
        cs.check_final(kind, svc, dataclasses.replace(d, scores=moved), ref,
                       point=not prob)
    if not prob:
        with pytest.raises(AssertionError, match="final match"):
            cs.check_final(kind, svc, dataclasses.replace(d, matched="none"),
                           ref, point=True)
