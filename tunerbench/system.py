"""The system under test, built from a deployment: the program's
reference DB and its ``TuningService``.  The only module of the
benchmark that imports the program."""

from __future__ import annotations

import numpy as np


def build_db(runs, bank):
    """The program's ``ReferenceDB`` over the profiled runs, holding the
    bank's preprocessed rows (``reference.Bank``: the paper's pipeline,
    made by the benchmark in bulk) as the float32 series it stores."""
    from repro.core.database import ReferenceDB
    db = ReferenceDB()
    for k, (app, p, run, _) in enumerate(runs):
        db.add(app, p.as_dict(), bank.series[k, : bank.lengths[k]]
               .astype(np.float32), run=run)
    return db


def make_service(cfg, db):
    from repro.serve.tuning import TuningService
    return TuningService(db, **cfg["service"])


def verdict_warm(svc, jb: int, npad: int, uncertain: bool) -> None:
    """Compile (or load from the cache) the verdict program of a
    ``finish_many`` over ``jb`` jobs whose longest query pads to
    ``npad``: the same call ``finish_many`` makes, on dummy queries."""
    q = np.linspace(0.1, 0.9, npad, dtype=np.float32)
    queries = [q] * jb
    variances = [np.full((npad,), 1e-3, np.float32)] * jb if uncertain \
        else [None] * jb
    svc._verdict_scores(queries, variances)
