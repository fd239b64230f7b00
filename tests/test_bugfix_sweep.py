"""PR-7 matching-stack bugfix sweep regression tests.

Pins the three pre-existing defects fixed alongside the uncertain-series
tentpole:

* ``BoundedBuffer`` sample-conservation accounting under ``drop_oldest``
  multi-chunk sheds (``total_in`` used to count the post-shed size when a
  single chunk alone overflowed the limit);
* ``OnlineMatcher.final_scores`` re-running the full DP on device even
  when the streamed rows were already collected (the PR-5
  ``stream_offline_equiv`` throughput regression) — now a host backtrack
  of the collected rows, equal to the offline verdict;
* degenerate-variance NaNs in the host correlation tail (covered from
  the service side in ``test_uncertain_matching``).
"""

import numpy as np
import pytest

from hypothesis import given, settings, strategies as st

from repro.serve.ingest import BackpressureError, BoundedBuffer


class _Tape:
    """Replays one seeded push/drain interleaving against a BoundedBuffer
    and tracks the drained-sample total for the conservation check."""

    def __init__(self, seed: int, limit, policy: str) -> None:
        self.rng = np.random.default_rng(seed)
        self.buf = BoundedBuffer(limit, policy)
        self.drained = 0

    def step(self) -> None:
        if self.rng.random() < 0.7:
            # chunk sizes straddle the limit so single pushes can shed
            # multiple buffered chunks, or alone overflow the limit.
            n = int(self.rng.integers(1, 24))
            try:
                self.buf.append(self.rng.random(n).astype(np.float32))
            except BackpressureError:
                pass                       # rejected pushes enqueue nothing
        else:
            out = self.buf.drain()
            if out is not None:
                self.drained += out.shape[0]

    def check(self) -> None:
        assert self.buf.total_in == (self.drained + len(self.buf)
                                     + self.buf.dropped)


@settings(max_examples=25, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_bounded_buffer_conservation_drop_oldest(seed):
    """Conservation invariant ``pushed == drained + buffered + dropped``
    holds at EVERY step of random push/drain interleavings under
    drop_oldest, including multi-chunk sheds and chunks that alone
    overflow the limit (limit=10 < max chunk size 23)."""
    tape = _Tape(seed, limit=10, policy="drop_oldest")
    for _ in range(200):
        tape.step()
        tape.check()


@settings(max_examples=10, deadline=None)
@given(st.integers(min_value=0, max_value=10_000))
def test_bounded_buffer_conservation_reject(seed):
    """Same invariant under reject: a refused push enqueues (and counts)
    nothing, so the identical chunk can be retried."""
    tape = _Tape(seed, limit=16, policy="reject")
    for _ in range(200):
        tape.step()
        tape.check()
    assert tape.buf.dropped == 0


def test_bounded_buffer_chunk_alone_overflow_counts_full_push():
    """A single 25-sample push into a limit-10 buffer keeps the newest 10
    and counts all 25 accepted — 15 dropped, not silently uncounted."""
    buf = BoundedBuffer(10, "drop_oldest")
    buf.append(np.arange(25, dtype=np.float32))
    assert buf.total_in == 25
    assert buf.dropped == 15
    assert len(buf) == 10
    out = buf.drain()
    np.testing.assert_array_equal(out, np.arange(15, 25, dtype=np.float32))
    assert buf.total_in == out.shape[0] + buf.dropped


@pytest.mark.parametrize("band", [None, 8])
@pytest.mark.parametrize("collect_rows", [True, False])
def test_final_scores_equals_offline_bank(band, collect_rows):
    """`OnlineMatcher.final_scores` == the offline ``similarity_bank``
    verdict on the full query whether it backtracks collected rows (the
    fixed fast path) or re-solves matrix-free (collect_rows=False)."""
    from repro.core.database import pack_series
    from repro.core.similarity import similarity_bank
    from repro.core.tuner import OnlineMatcher

    rng = np.random.default_rng(7)
    refs = [rng.random(int(rng.integers(20, 40))).astype(np.float32)
            for _ in range(6)]
    bank = pack_series(refs)
    q = rng.random(30).astype(np.float32)

    m = OnlineMatcher(bank, band=band, collect_rows=collect_rows,
                      query_len=q.shape[0] if band is not None else None)
    for lo in range(0, q.shape[0], 7):
        m.extend(q[lo:lo + 7])
    got = m.final_scores()
    want = similarity_bank(q, bank, preprocess=False, band=band)
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_final_scores_rows_path_matches_rows_free_path():
    """Both final_scores paths agree with each other on the same stream
    (the rows backtrack is not a different verdict, just a cheaper one)."""
    from repro.core.database import pack_series
    from repro.core.tuner import OnlineMatcher

    rng = np.random.default_rng(11)
    bank = pack_series([rng.random(int(rng.integers(20, 40)))
                        .astype(np.float32) for _ in range(5)])
    q = rng.random(26).astype(np.float32)
    outs = []
    for collect in (True, False):
        m = OnlineMatcher(bank, collect_rows=collect)
        for lo in range(0, q.shape[0], 5):
            m.extend(q[lo:lo + 5])
        outs.append(m.final_scores())
    np.testing.assert_allclose(outs[0], outs[1], atol=1e-5)
