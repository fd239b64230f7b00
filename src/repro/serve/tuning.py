"""Streaming self-tuning service: match in-flight jobs WHILE they execute.

The paper's end goal is acting on a job *before* it finishes: compare the
utilization pattern observed so far against the reference database, and as
soon as the most probable execution pattern is clear, transfer that
workload's tuned configuration.  The offline ``AutoTuner.match`` scores
complete series only; this service runs the matching phase online.

Layered serving stack
---------------------
The service is a continuous-batching front split into four layers; this
module is the tick engine and verdict renderer, and the facade that wires
the stack together:

* **ingest** (``serve.ingest``): bounded per-job sample queues with
  backpressure, optional rotated trace persistence, the causal streaming
  Chebyshev filter, and heartbeat/straggler stamping of every push.
* **scheduler** (``serve.scheduler``): slot admission/eviction with
  power-of-two S-axis capacity buckets (the device state is sized to the
  ACTIVE job count, growing and compact-shrinking by on-device gathers —
  the S twin of the prefilter's K-axis re-pack), plus tick-rate cohorts
  so ``tick(now=...)`` drains a 4 Hz trace only on its own beats.
* **tick engine** (this module + ``core.dtw``): the device-resident
  fused scored-extend dispatch, unchanged numerics.
* **verdicts** (this module): matrix-free batched finish rendering.

Tick engine (device-resident tick)
----------------------------------
* Each in-flight job occupies one slot of the current S bucket.  Its
  incremental DTW state — the DP row against the whole reference bank,
  plus the warp-path correlation moments of every row cell — lives
  stacked with every other job's as ``[S, M, K]`` / ``[3, S, M, K]``
  device arrays (K last, so the reference axis both vectorizes and
  shards).
* :meth:`TuningService.tick` drains every due job's buffered samples in
  **one** jitted dispatch of the wavefront chunk-extend (``core.dtw``),
  with prefix scoring FUSED into the same dispatch: the device returns a
  ``[S, K]`` open-end warp-correlation array, not DP rows.
  ``dispatch_count`` records the invariant: dispatches == ticks(with
  data) no matter how many jobs are in flight.  Every tick mode runs
  the one entry point ``core.dtw.bank_extend_tick``; the mode only sets
  the channel set of the moment slab it is given (``"scored"`` 3,
  ``"approx_prob"`` 4, ``"prob"`` 6, ``"distance"`` none), and on TPU
  backends it routes to the Pallas streaming kernels
  (``kernels.dtw.stream``).
* ``mesh=`` shards the bank: a 1-D device mesh partitions the ``[M, K]``
  reference bank and every ``[.., K]`` state slab over its single axis
  via ``jax.shard_map``: each device runs the same tick dispatch (the
  Pallas kernel on TPU) on its K shard, and a batch of verdicts scores
  each device's shard of the bank there.  Sharded ticks and verdicts are
  bit-identical to unsharded ones, and a tick remains ONE dispatch.
  ``mesh={"bank": 4}`` (the form a deployment file states) is the
  ``bank`` axis over the first four devices.  :meth:`rescale`
  re-homes the state onto a different mesh mid-flight (or back to a
  single device) — the hook a ``runtime.fault.ElasticController``
  decision drives when hosts die or join.
* ``prefilter_top=`` prunes the bank at large K exactly as before (the
  streaming-Haar ranking with the in-flight DTW soundness veto, sticky
  per job, bucket-padded K-axis re-packs counted in ``repack_count``).
  S-axis slot re-packs are counted in ``slot_repack_count``; neither
  ever inflates ``dispatch_count``.
* The early-decision rule is confidence/abstain: emit a
  :class:`core.tuner.TuneDecision` only once the leading workload has
  cleared the threshold AND led the runner-up by ``margin`` for
  ``stable_ticks`` consecutive scoring ticks, with at least
  ``min_fraction`` of the job observed (>= 2 distinct workloads
  required — no vacuous margins).

Probabilistic (uncertain-series) mode
-------------------------------------
``min_probability=`` switches the decision gates from the point
correlation to a calibrated match probability (arXiv:1112.5505): pushes
may carry per-sample measurement variances (``push(..., variance=)``;
unsupplied variances default to the causal filter's squared residual,
or 0.0 without ``denoise``), the tick's moment slab doubles to SIX
channels ([6, S, M, K]: sy, syy, sxy and their variance-weighted twins
svy, svyy, svxy carried along the SAME backtrack-identical warp path)
beside a per-slot [S, 3] (sv, svx, svxx) fold, and the fused dispatch
returns a ``[S, K]`` probability array
``P[true warp correlation >= threshold]`` beside the scores
(``core.dtw._prob_from_moments`` — one factored tail shared by the
streaming tick, the offline jnp scorer and both Pallas kernels).  The
leader is still ranked by point correlation, but the commit gate
becomes ``P >= min_probability`` (in flight AND at the final verdict),
so the service *abstains* while the posterior is flat instead of
committing on a lucky noisy prefix; the emitted ``TuneDecision``
records the probability.  At zero input variance the probability is
exactly 1.0 iff the correlation clears ``threshold``, so probabilistic
decisions reduce bitwise to the point rule.  The scored tick's compiled
graph is untouched when the mode is off: the channel set is static, so
the three-channel tick traces no variance op.

Two probability tails share that machinery (``prob_mode=``):

* ``"exact"`` (default) — the six-channel slab above.  This is the tail
  that VERDICTS: :meth:`finish` / :meth:`finish_many` always recompute
  final probabilities offline through the exact six-channel scorer,
  whatever mode served the ticks, so verdict probabilities are bitwise
  independent of ``prob_mode``.
* ``"approx"`` — the tail that SERVES under tight tick budgets: the
  slab carries ONE variance channel (svy, the path-accumulated sigma^2
  proxy) beside (sy, syy, sxy), and the score tail reconstructs
  svyy/svxy from the per-slot (sv, svx, svxx) folds
  (``core.dtw._prob_from_moments_approx``), cutting per-cell slab
  traffic from 7 channels to 5 (~1.3x a scored tick instead of ~2x).
  In-flight probabilities sit within a small tolerance band of the
  exact tail (pinned by the calibration tests/bench) and reduce
  BITWISE to it at zero input variance; early decisions gate on the
  approx probability, final verdicts stay exact.  The overload ladder
  exposes the same trade as a rung: an exact-mode service capped at
  ``approx_prob`` keeps shipping (approximate) probabilities instead
  of losing them entirely (see ``serve.overload``).

Verdicts
--------
:meth:`TuningService.finish` recomputes the final verdict offline from
the job's full (causally filtered) query — matrix-free: one
``dtw.dtw_score_bank_many`` dispatch carries the warp-path correlation
moments through the DP on device and scores at the closed alignment
endpoint.  Verdicts BATCH: :meth:`finish_many` renders J decisions from
one drain tick + one dispatch, and :meth:`finish_later` parks completed
jobs in a drain queue (slot freed immediately) that
:meth:`drain_finishes` — or an automatic drain at ``finish_batch``
pending verdicts — renders in one dispatch, so
``offline_dispatch_count`` amortizes instead of growing 1:1 with
completions; batched and sequential verdicts are bit-identical by
construction.  When a :class:`ReferenceDB` backs the service, each
decision is recorded into the DB's decision history.

Multi-tenant serving
--------------------
:class:`MultiTenantTuningService` keys jobs to per-tenant reference
banks at submit: each tenant owns an isolated tick engine (its own
bank, device state and counters), the front routes
push/tick/finish by job id, and a tick dispatches only for engines
whose due jobs have data — total dispatches are bounded by data-ticks x
tenants (x cohorts within each engine).

The hard invariant across ALL of the above: a job's decisions (early
and final — matched workload, correlation, ``decided_at_fraction``) are
bit-for-bit independent of slot packing, admission order, tick-rate
cohort, capacity history, sharding and verdict batching.  Per-job DP
state is row-independent and per-reference, so none of the batching
machinery can touch the numbers.

``denoise=True`` pushes raw samples through the causal streaming
Chebyshev filter (``filters.StreamingFilter``) before matching.
Reference banks are expected to be stored pre-processed (as
``AutoTuner.profile`` does).

Profiler spans
--------------
The tick and verdict paths open ``jax.profiler.TraceAnnotation`` spans
(``tuner.*``), which cost about a microsecond each and record nothing
unless a profiler trace is being captured.  Captured with
``jax.profiler.trace(<dir>)`` around a live service, they sit on the
profiler's host timeline on the same clock as the device's ops
(TensorBoard's profile plugin or Perfetto show both), so a stretch in
which the device waited can be named by what the host was doing.  Each
span nests in its parent; its arguments are counts taken at the span's
boundary:

* ``tuner.tick`` (``tick``: the tick's id, ``internal``: 1 for the
  drain tick of a finish) over the region ``last_tick_latency`` times,
  holding in order ``tuner.drain`` (``jobs``, ``samples``, ``filtered``:
  the due-job loop around ``IngestFront.drain_many``, whose one batched
  causal-filter call of the drained jobs is a ``tuner.filter`` span,
  ``jobs``, ``samples``; ``filtered`` is 1 when it ran), ``tuner.repack``
  (``slot_repacks``, ``k_repacks``: this tick's), ``tuner.chunks``
  (``chunk``, ``slots``), ``tuner.dispatch`` (``mode``, ``k_live``,
  ``shards``: the number of devices the dispatch fans over, 1 without a
  mesh; the uploads and the tick dispatch), ``tuner.pull`` (the
  ``[S, K]`` pull and
  its scatter to bank columns), ``tuner.decide`` (``jobs``,
  ``ranked``: the rows ranked by the one batched call of the decision
  rule, ``decisions``) and ``tuner.prefilter``.
* ``tuner.finish_many`` (``jobs``), from :meth:`finish_many` and from
  each batched drain of the :meth:`finish_later` queue, holding the drain
  tick (a nested ``tuner.tick`` with ``internal=1``), ``tuner.retire``
  (``jobs``), ``tuner.verdict.pack`` (``jobs``, ``padded``, ``npad``),
  ``tuner.verdict.dispatch`` (``shards``, as for the tick),
  ``tuner.verdict.pull`` and
  ``tuner.verdict.render`` (``jobs``, ``ranked``: the verdict rows
  ranked in its one batched call).
"""

from __future__ import annotations

import dataclasses
import functools
import time
import warnings
from typing import Dict, List, Mapping, NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core import dtw as _dtw
from ..core import wavelet as _wavelet
from ..core.database import ReferenceDB, SeriesBank
from ..core.similarity import MATCH_THRESHOLD
from ..core.tuner import TuneDecision, _RowBuffer
from ..runtime.chaos import FaultPlan, InjectedDispatchError
from ..runtime.retry import CircuitBreaker, RetryPolicy, call_with_retry
from .ingest import IngestFront, PoisonedSampleError, TraceLog
from .overload import (RUNGS, AdmissionController, AdmissionPolicy,
                       AdmissionShedError, OverloadConfig,
                       OverloadController)
from .scheduler import SlotScheduler

__all__ = ["InFlightJob", "TuningService", "MultiTenantTuningService"]


def _auto_axes(mesh: Union[jax.sharding.Mesh, Dict[str, int], None]
               ) -> Optional[jax.sharding.Mesh]:
    """``mesh`` with every axis ``Auto`` (``jax.make_mesh`` defaults to
    ``Explicit``): the state re-pack gathers and the tick's shard_map
    then need no sharding annotations, whatever mesh the caller built.
    The dict form ``{"<axis>": n}``, which a deployment's JSON can state,
    is the 1-D mesh of that axis over the first ``n`` devices; a process
    with fewer devices gets all of them and a warning (the dispatch
    spans' ``shards`` argument records the fan-out either way)."""
    if mesh is None:
        return None
    if isinstance(mesh, dict):
        if len(mesh) != 1:
            raise ValueError("a mesh given as a dict names one bank axis "
                             f"and its device count; got {mesh}")
        (axis, n), = mesh.items()
        devices = jax.devices()[: int(n)]
        if len(devices) < int(n):
            warnings.warn(f"mesh {mesh}: this process has {len(devices)} "
                          "device(s); the bank shards over those",
                          RuntimeWarning, stacklevel=3)
        mesh = jax.make_mesh((len(devices),), (axis,), devices=devices)
    return jax.sharding.Mesh(
        mesh.devices, mesh.axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(mesh.axis_names))


#: moment channels of each tick mode's slab (0: none, the distance tick).
_TICK_CHANNELS = {"prob": 6, "approx_prob": 4, "scored": 3, "distance": 0}

#: the tick's arguments, in the order ``core.dtw.bank_extend_tick`` takes
#: them; a tick without variances leaves out ``vstats`` and ``vchunks``.
_TICK_ARGS = ("rows", "moms", "ns", "sx", "sxx", "vstats", "bank_t",
              "lengths", "chunks", "vchunks", "nvalid", "qlens")


def _tick_layout(nch: int) -> Tuple[str, ...]:
    """The positional arguments of a tick over ``nch`` moment channels."""
    return tuple(a for a in _TICK_ARGS
                 if nch > 3 or a not in ("vstats", "vchunks"))


def _tick_specs(nch: int, axis: str):
    """(in, out) partition specs of a tick's arguments and results: DP
    rows, moment slabs, bank, lengths and the ``[S, K]`` outputs split
    along K; per-slot arrays replicated."""
    P = jax.sharding.PartitionSpec
    split = {"rows": P(None, None, axis), "moms": P(None, None, None, axis),
             "bank_t": P(None, axis), "lengths": P(axis)}
    ins = tuple(split.get(a, P()) for a in _tick_layout(nch))
    outs = (split["rows"], split["moms"], P(), P(), P(), P(None, axis))
    return ins, outs + ((P(), P(None, axis)) if nch > 3 else ())


def tick_program(mode: str, mesh: Optional[jax.sharding.Mesh] = None,
                 **kw):
    """``core.dtw.bank_extend_tick`` over ``mode``'s channel set, taking
    the mode's arguments (:func:`_tick_layout`), with ``kw`` (``band``,
    ``threshold``, ``use_kernel``, ``interpret``) bound.  Over a 1-D
    ``mesh``: the same tick shard_mapped over the bank axis and jitted,
    each device running it on its K shard, named for the program it
    runs there so that a device trace reads both as one tick program."""
    if mode not in _TICK_CHANNELS:
        raise ValueError(f"unknown tick mode {mode!r}")
    nch = _TICK_CHANNELS[mode]
    layout = _tick_layout(nch)

    def tick(*args):
        named = dict(zip(layout, args))
        return _dtw.bank_extend_tick(*(named.get(a) for a in _TICK_ARGS),
                                     **kw)
    if mesh is None:
        return tick
    ins, outs = _tick_specs(nch, mesh.axis_names[0])
    sharded = jax.shard_map(tick, mesh=mesh, in_specs=ins, out_specs=outs,
                            check_vma=False)
    sharded.__name__ = ("_bank_extend_diag_impl" if kw.get("use_kernel")
                        is False else "_scored_kernel_tick") + "_sharded"
    return jax.jit(sharded)


class _Rank(NamedTuple):
    """One row's rank: the [W] per-workload scores, the leading
    workload's index and score, the runner-up's score, and (probabilistic
    mode) the leader's match probability."""
    scores: np.ndarray
    leader: int
    score: float
    runner_up: float
    probability: Optional[float]


@dataclasses.dataclass
class InFlightJob:
    """Host-side bookkeeping for one slot (device state lives stacked in
    the service's ``[S, M, K]`` arrays; buffering/filtering lives in the
    ingest layer)."""
    job_id: str
    slot: int
    expected_len: int
    tick_hz: Optional[float] = None
    x: _RowBuffer = dataclasses.field(default_factory=_RowBuffer)
    n: int = 0
    leader: Optional[str] = None
    stable_for: int = 0
    early: Optional[TuneDecision] = None
    #: per-sample measurement variances aligned with ``x`` (filled only
    #: in probabilistic mode; empty otherwise).
    vx: _RowBuffer = dataclasses.field(default_factory=_RowBuffer)
    #: last [K] on-device prefix-score row seen for this job (float64 on
    #: the host side; None until the first scoring tick touches the job).
    last_sims: Optional[np.ndarray] = None
    #: last [K] match-probability row (probabilistic mode only).
    last_probs: Optional[np.ndarray] = None
    #: streaming-Haar prefix coefficients of the (filtered) query — the
    #: wavelet prefilter's per-job transform state (None w/o prefilter).
    haar: Optional[_wavelet.StreamingHaar] = None
    #: bool [K] over the FULL bank: references still live for this job.
    #: None means "all" (prefilter off, or not engaged yet).  Monotone:
    #: once False a reference never comes back for this job, so its DP
    #: column may leave the packed tick without ever going stale for us.
    allowed: Optional[np.ndarray] = None
    #: QoS class (bronze/silver/gold) the job was admitted under.
    qos: str = "silver"
    #: staleness marker set by degraded (ladder) ticks — monotone per
    #: job, because a skipped side-channel contribution can never be
    #: recovered in flight.  0 = all channels exact; 1 = variance
    #: channels stale (probability-gated early decisions suppressed;
    #: point scores and the prefilter veto stay exact); 2 = all moment
    #: channels stale (``last_sims`` frozen, no early decisions ever —
    #: the final verdict recomputes offline from the full query and is
    #: bitwise unchanged).
    degraded_level: int = 0
    #: this tick's rank of ``last_sims``, from the decision rule's one
    #: batched call: the hand-off to :meth:`TuningService._maybe_decide`,
    #: which stays per job because fault injection wraps it per job.
    rank: Optional[_Rank] = None

    @property
    def fraction_seen(self) -> float:
        return self.n / max(self.expected_len, 1)


class TuningService:
    """Multiplexed online matcher over a fixed reference bank.

    ``refs`` is a :class:`ReferenceDB` (bank + config transfer) or a bare
    :class:`SeriesBank` (matching only).  ``min_probability=`` enables the
    probabilistic (uncertain-series) decision rule — see the module
    docstring; it requires ``score_in_flight=True`` and gates BOTH the
    early decision and the final verdict on the leader's calibrated match
    probability instead of its point correlation (``threshold`` keeps its
    role as the correlation level the probability is calibrated
    against).  ``prob_mode="approx"`` (requires ``min_probability=``)
    serves the IN-FLIGHT probability through the four-channel
    approximate tail — ~1.3x a scored tick instead of ~2x, probabilities
    within a calibrated tolerance band of exact — while :meth:`finish` /
    :meth:`finish_many` verdicts stay on the exact six-channel tail,
    bitwise unchanged (see the module docstring's "Two probability
    tails").  ``score_in_flight=False`` is the
    distance-only throughput mode: the tick skips the fused scoring (so no
    early decisions; :meth:`finish` still renders the offline verdict) and
    carries no moment slabs — marginally cheaper at very large K.
    ``collect_rows`` is accepted as a deprecated alias from the PR-2 API
    (rows are never collected any more; the name survives because the
    semantics — "score while in flight" — do).

    ``mesh=`` (a 1-D ``jax.sharding.Mesh``, or ``{"<axis>": n}`` for that
    axis over the first n devices) partitions the reference axis K over
    the mesh devices, for ticks and verdicts; the bank is padded up to a
    device-count multiple internally and padded rows never surface in
    scores.

    ``prefilter_top=P`` enables the streaming wavelet prefilter: ticks
    dispatch over the pruned survivor union instead of all K references
    (see the module docstring for the pruning rule and its soundness
    veto).  Composes with ``mesh=``; off by default.

    ``finish_batch=`` sets the drain-queue auto-flush threshold: once
    that many :meth:`finish_later` verdicts are pending they are rendered
    in one batched offline dispatch (:meth:`drain_finishes` flushes
    early).

    Serving-front knobs (the layered stack):

    * ``slots`` caps concurrent jobs; with ``elastic_slots=True`` (the
      default) the device state is sized to the power-of-two bucket of
      the ACTIVE job count and grows/compact-shrinks by S-axis device
      gathers (``slot_repack_count``), instead of paying for ``slots``
      rows around the clock.  ``elastic_slots=False`` pins the
      pre-refactor fixed-capacity layout.
    * ``queue_limit``/``queue_policy`` bound each job's ingest queue
      (``"reject"`` raises ``serve.ingest.BackpressureError`` at the
      producer, ``"drop_oldest"`` sheds and counts).
    * ``trace_log`` (a :class:`serve.ingest.TraceLog`) persists every
      accepted chunk with size/count rotation.
    * ``heartbeat_timeout`` arms per-job heartbeats: pushes carrying a
      ``now=`` timestamp beat the tracker, and :meth:`sweep_stalled`
      evicts jobs whose agent went silent (slot freed, no verdict,
      survivors untouched).
    * ``submit(..., tick_hz=)`` assigns the job to a tick-rate cohort;
      ``tick(now=...)`` drains only due cohorts.
    """

    def __init__(self, refs: Union[ReferenceDB, SeriesBank], *,
                 band: Optional[int] = None,
                 threshold: float = MATCH_THRESHOLD,
                 min_probability: Optional[float] = None,
                 prob_mode: str = "exact",
                 margin: float = 0.02, stable_ticks: int = 3,
                 min_fraction: float = 0.15, slots: int = 8,
                 denoise: bool = False,
                 score_in_flight: Optional[bool] = None,
                 collect_rows: Optional[bool] = None,
                 mesh: Union[jax.sharding.Mesh, Dict[str, int],
                             None] = None,
                 prefilter_top: Optional[int] = None,
                 prefilter_margin: float = 0.05,
                 prefilter_min_fraction: float = 0.1,
                 prefilter_coeffs: int = 64,
                 finish_batch: int = 16,
                 elastic_slots: bool = True,
                 queue_limit: Optional[int] = None,
                 queue_policy: str = "reject",
                 trace_log: Optional[TraceLog] = None,
                 heartbeat_timeout: Optional[float] = None,
                 retry_policy: Optional[RetryPolicy] = None,
                 chaos: Optional[FaultPlan] = None,
                 overload: Union[OverloadConfig, OverloadController,
                                 Dict, None] = None,
                 admission: Union[AdmissionPolicy, AdmissionController,
                                  Dict, None] = None,
                 breaker: Optional[CircuitBreaker] = None) -> None:
        if isinstance(refs, ReferenceDB):
            self.db: Optional[ReferenceDB] = refs
            self.bank = refs.bank()
        else:
            self.db = None
            self.bank = refs
        if len(self.bank) == 0:
            raise ValueError("empty reference bank")
        if score_in_flight is None:
            score_in_flight = True if collect_rows is None else collect_rows
        self._labels: Tuple[str, ...] = self.bank.labels or tuple(
            f"ref{k}" for k in range(len(self.bank)))
        # the decision rule's column grouping, read once from the labels:
        # workloads in first-appearance order (the rank's tie-break), a
        # stable permutation making each workload's columns contiguous
        # and each group's first position.
        self._workloads: Tuple[str, ...] = tuple(dict.fromkeys(self._labels))
        self._n_workloads = len(self._workloads)
        code = {w: i for i, w in enumerate(self._workloads)}
        group = np.array([code[lbl] for lbl in self._labels], np.int64)
        self._col_perm = np.argsort(group, kind="stable")
        self._col_starts = np.searchsorted(group[self._col_perm],
                                           np.arange(self._n_workloads))
        if min_probability is not None:
            if not (0.0 < min_probability <= 1.0):
                raise ValueError("min_probability must be in (0, 1]")
            if not score_in_flight:
                raise ValueError("min_probability needs "
                                 "score_in_flight=True (the probability "
                                 "rides the fused scoring tick)")
        if prob_mode not in ("exact", "approx"):
            raise ValueError("prob_mode must be 'exact' or 'approx', got "
                             f"{prob_mode!r}")
        if prob_mode == "approx" and min_probability is None:
            raise ValueError("prob_mode='approx' needs min_probability= "
                             "(the approximate tail serves the in-flight "
                             "probability gate)")
        self.band = band
        self.threshold = threshold
        self.min_probability = min_probability
        self.prob_mode = prob_mode
        self.margin = margin
        self.stable_ticks = stable_ticks
        self.min_fraction = min_fraction
        self.slots = slots
        self.denoise = denoise
        self.score_in_flight = score_in_flight
        mesh = _auto_axes(mesh)
        self.mesh = mesh
        if prefilter_top is not None and prefilter_top < 1:
            raise ValueError("prefilter_top must be >= 1 (or None)")
        if prefilter_top is not None and not score_in_flight:
            # without the fused tick's scores there is no DTW veto: the
            # warp-blind wavelet ranking alone evicts warp-matching
            # references (the paper's exim-vs-wordcount case), and sticky
            # pruning makes that irrecoverable in flight.
            raise ValueError("prefilter_top needs score_in_flight=True "
                             "(the prune rule's soundness veto runs on "
                             "the in-flight DTW scores)")
        self.prefilter_top = prefilter_top
        self.prefilter_margin = prefilter_margin
        self.prefilter_min_fraction = prefilter_min_fraction
        self.prefilter_coeffs = prefilter_coeffs
        if finish_batch < 1:
            raise ValueError("finish_batch must be >= 1")
        self.finish_batch = finish_batch
        self.retry_policy = retry_policy
        self.chaos = chaos
        self.breaker = breaker
        # dispatch signatures that have executed at least once (see
        # _dispatch_resilient: only these may retry a runtime error).
        self._executed: set = set()
        # overload control plane: the degradation-ladder controller and
        # the admission gate (see serve.overload's runbook docstring).
        # Dict forms are accepted so a snapshot's JSON config rebuilds
        # them; passing a live controller keeps its walked state.
        if isinstance(overload, dict):
            overload = OverloadConfig(**overload)
        if isinstance(overload, OverloadConfig):
            overload = OverloadController(overload)
        self._overload: Optional[OverloadController] = overload
        if isinstance(admission, dict):
            admission = AdmissionPolicy(**admission)
        if isinstance(admission, AdmissionPolicy):
            admission = AdmissionController(admission)
        self._admission: Optional[AdmissionController] = admission
        # replay suppression (serve.recovery): a replayed submit must
        # never be shed — the live run already admitted it.
        self._admission_suppressed = False
        # the serializable constructor config — what serve.recovery
        # persists in a snapshot's manifest so a restoring process can
        # rebuild an identical service without the caller re-supplying
        # every knob (mesh/trace_log/retry/chaos are process-local and
        # re-supplied at restore).
        self._config: Dict[str, object] = dict(
            band=band, threshold=threshold,
            min_probability=min_probability, prob_mode=prob_mode,
            margin=margin,
            stable_ticks=stable_ticks, min_fraction=min_fraction,
            slots=slots, denoise=denoise, score_in_flight=score_in_flight,
            prefilter_top=prefilter_top, prefilter_margin=prefilter_margin,
            prefilter_min_fraction=prefilter_min_fraction,
            prefilter_coeffs=prefilter_coeffs, finish_batch=finish_batch,
            elastic_slots=elastic_slots, queue_limit=queue_limit,
            queue_policy=queue_policy,
            heartbeat_timeout=heartbeat_timeout,
            overload=(dataclasses.asdict(self._overload.config)
                      if self._overload is not None else None),
            admission=(dataclasses.asdict(self._admission.policy)
                       if self._admission is not None else None))

        k, m = self.bank.series.shape
        self._k = k
        self._m = m
        ndev = 1
        axis = None
        if mesh is not None:
            if len(mesh.axis_names) != 1:
                raise ValueError("TuningService needs a 1-D mesh (one bank "
                                 f"axis); got axes {mesh.axis_names}")
            axis = mesh.axis_names[0]
            ndev = mesh.devices.size
        self._ndev = ndev
        self._axis = axis
        # full-bank host copies: the pruned tick re-packs (gathers) state
        # and bank columns from these, so the full [M, K] layout is the
        # single source of truth whatever subset is currently on device.
        self._full_series_t = np.ascontiguousarray(
            self.bank.series.T.astype(np.float32))
        self._full_lengths = self.bank.lengths.astype(np.int32)
        # admission cost proxy: expected job length over the bank's mean
        # reference length (the cumulative-CPU estimate stand-in).
        self._mean_ref_len = float(np.mean(self._full_lengths))
        self._wcoeff_cache: Dict[Tuple[int, int], np.ndarray] = {}
        self._jobs: Dict[str, InFlightJob] = {}
        # slots awaiting their fresh-state reset (applied in one masked
        # op at the top of the next data tick, see submit()).
        self._dirty: List[int] = []

        # serving-front layers: ingest (queues/filter/trace/heartbeats)
        # and the S-axis slot scheduler (buckets, cohorts).
        self._front = IngestFront(
            denoise=denoise, queue_limit=queue_limit,
            queue_policy=queue_policy, trace=trace_log,
            heartbeat_timeout=heartbeat_timeout,
            track_variance=min_probability is not None)
        self._sched = SlotScheduler(slots, elastic=elastic_slots)
        self._s_cap = self._sched.capacity

        self._ns = self._put(np.zeros((self._s_cap,), np.int32), (None,))
        self._sx = self._put(np.zeros((self._s_cap,), np.float32), (None,))
        self._sxx = self._put(np.zeros((self._s_cap,), np.float32), (None,))
        # probabilistic mode: per-slot (sv, svx, svxx) variance folds —
        # K-independent like sx/sxx, so replicated under a mesh.
        self._vstats = self._put(
            np.zeros((self._s_cap, 3), np.float32), (None, None)) \
            if min_probability is not None else None
        self._qlens = np.zeros((self._s_cap,), np.int32)
        self._packed_idx = np.arange(k)
        self._pack_device_state(self._packed_idx, rows=None, moms=None)
        # per-mode tick callables, built lazily: the configured mode is
        # compiled eagerly (the pre-overload behavior); the degraded
        # ladder modes compile on first use under load.
        self._tick_fns: Dict[str, Tuple] = {}
        self._tick_fn_for(self._base_mode())

        #: device dispatches issued by :meth:`tick` — the scaling invariant
        #: is one dispatch per data-carrying tick, however many jobs are
        #: live (and however many devices the bank is sharded over).
        self.dispatch_count = 0
        #: prefilter re-pack events: the (occasional) device uploads that
        #: shrink or re-grow the packed bank/state when the survivor set
        #: changes.  Counted SEPARATELY from ``dispatch_count`` — a
        #: re-pack is state motion, not a tick dispatch, and the
        #: dispatches == data-ticks invariant must survive pruning.
        self.repack_count = 0
        #: S-axis capacity changes (elastic grow / compact-shrink, plus
        #: stall evictions' compactions) — the slot twin of
        #: ``repack_count``, likewise never a dispatch.
        self.slot_repack_count = 0
        #: mesh re-homes driven by :meth:`rescale`.
        self.rescale_count = 0
        #: jobs dropped by :meth:`evict`/:meth:`sweep_stalled` (no
        #: verdict rendered).
        self.evicted_count = 0
        #: offline verdict dispatches (the matrix-free
        #: ``dtw.dtw_score_bank_many`` recompute): one per
        #: :meth:`finish`, but one per *drain* for :meth:`finish_many` /
        #: the :meth:`finish_later` queue — the counter grows sublinearly
        #: in completions when verdicts batch.
        self.offline_dispatch_count = 0
        self.ticks = 0
        #: failed dispatch attempts absorbed by the retry/backoff wrapper
        #: (transient device errors + injected chaos faults).
        self.retry_count = 0
        #: dispatches that exhausted their retries and were served by the
        #: degraded fallback path (Pallas kernel -> jnp wavefront twin —
        #: bit-identical results, degraded latency).
        self.degraded_dispatch_count = 0
        #: True when the most recent tick/verdict dispatch came from the
        #: fallback path — the per-tick ``degraded`` surface.
        self.last_tick_degraded = False
        #: {job_id: reason} for jobs evicted by the input-poison
        #: quarantine (NaN/Inf samples, bad variances).  Survivors are
        #: bit-identical to a run that never saw the poisoned job's tail:
        #: per-job state is row-independent and the poisoned push itself
        #: was rejected atomically before touching any queue.
        self.quarantined: Dict[str, str] = {}
        self.quarantined_count = 0
        #: pushes silently dropped because their job was already
        #: quarantined (a sick agent keeps pushing; the service must not
        #: crash on it, and must not resurrect the job either).
        self.quarantine_dropped = 0
        #: submits refused by admission control (monitoring only: a shed
        #: submit is never journaled — the job simply never existed as
        #: far as recovery is concerned).
        self.shed_count = 0
        self.shed_by_class: Dict[str, int] = {}
        #: top-level ticks observed while the ladder was above rung 0.
        self.overload_ticks = 0
        #: high-water ladder rung reached (see serve.overload.RUNGS).
        self.worst_rung = 0
        #: measured wall-clock latency of the most recent top-level tick
        #: (plus any chaos-injected slowdown) — what the ladder observes
        #: and what the recovery journal records per tick command.
        self.last_tick_latency = 0.0
        # early decisions emitted by a tick the caller didn't see (e.g.
        # the internal drain tick of another job's finish()); surfaced by
        # the next tick() return so no decision is ever dropped.
        self._undelivered: Dict[str, TuneDecision] = {}
        # deferred-finish drain queue: (job_id, full query, variances or
        # None, early decision) tuples awaiting one batched verdict
        # dispatch, plus auto-drained decisions not yet handed to the
        # caller.
        self._finish_queue: List[Tuple[str, np.ndarray,
                                       Optional[np.ndarray],
                                       Optional[TuneDecision]]] = []
        self._finished: Dict[str, TuneDecision] = {}

    # -- packed device state (full bank or pruned survivor subset) -----------
    def _put(self, arr, spec):
        if self.mesh is None:
            return jnp.asarray(arr)
        return jax.device_put(arr, jax.sharding.NamedSharding(
            self.mesh, jax.sharding.PartitionSpec(*spec)))

    def _k_pad(self, k: int) -> int:
        """``k`` padded to a device-count multiple, so the shard_map
        fan-out divides evenly, with each shard a whole number of the
        tick kernel's reference tiles when it is wider than one (the
        kernel would otherwise pad the state on every tick)."""
        if self.mesh is None:
            return k
        block = _dtw.TICK_BLOCK_K if _dtw._kernel_backend() else 1
        return _dtw.shard_width(k, self._ndev, block)

    def _k_bucket(self, k: int) -> int:
        """Padded width of a pruned pack: power-of-two (so re-packs cycle
        through at most log2(K) compiled tick shapes), at least one VPU
        sublane tile, and :meth:`_k_pad`-ded."""
        return self._k_pad(max(8, 1 << (max(k, 1) - 1).bit_length()))

    def _pack_device_state(self, idx: np.ndarray, rows, moms) -> None:
        """(Re)build the device-resident tick arrays over bank columns
        ``idx`` (full-bank order preserved).  ``rows``/``moms`` carry the
        surviving columns' DP state ([S, M, K_old] / [3, S, M, K_old]
        DEVICE arrays aligned with the PREVIOUS ``_packed_idx``) —
        re-packing gathers the surviving columns on device, so a re-pack
        never round-trips the state slabs through the host.  Columns
        without prior state start fresh (+inf row, zero moments) — exact
        for jobs that have consumed nothing, don't-care for jobs whose
        prefilter already dropped the reference (their scores for it are
        masked on the way out of every tick).

        The full pack pads K by :meth:`_k_pad`; pruned packs pad to
        :meth:`_k_bucket`.
        """
        k_new, m, axis = len(idx), self._m, self._axis
        kp = self._k_pad(self._k) if k_new == self._k \
            else self._k_bucket(k_new)
        series_t = np.zeros((m, kp), np.float32)
        series_t[:, :k_new] = self._full_series_t[:, idx]
        lengths = np.ones((kp,), np.int32)
        lengths[:k_new] = self._full_lengths[idx]
        self._bank_t = self._put(series_t, (None, axis))
        self._lengths = self._put(lengths, (axis,))
        if rows is None:
            self._rows = self._put(
                np.full((self._s_cap, m, kp), float(_dtw._INF), np.float32),
                (None, None, axis))
            if self.min_probability is None:
                nch = 3
            else:
                nch = 4 if self.prob_mode == "approx" else 6
            self._moms = self._put(
                np.zeros((nch, self._s_cap, m, kp), np.float32),
                (None, None, None, axis)) if self.score_in_flight else None
        else:
            pos = np.full((self._k,), -1, np.int64)
            pos[self._packed_idx] = np.arange(len(self._packed_idx))
            src = np.concatenate([pos[idx], np.full((kp - k_new,), -1)])
            gather = jnp.asarray(np.maximum(src, 0), jnp.int32)
            fresh = jnp.asarray(src < 0)
            new_rows = jnp.where(fresh[None, None, :],
                                 _dtw._INF, jnp.take(rows, gather, axis=2))
            self._rows = self._put(new_rows, (None, None, axis))
            if moms is not None:
                self._moms = self._put(
                    jnp.where(fresh[None, None, None, :], 0.0,
                              jnp.take(moms, gather, axis=3)),
                    (None, None, None, axis))
        self._packed_idx = np.asarray(idx)
        self._kp = kp

    def _repack_slots(self, src: np.ndarray) -> None:
        """Apply an S-axis gather plan from the scheduler (new slot ->
        old slot, -1 = fresh) to every slot-indexed array — on device,
        mirroring the K-axis `_pack_device_state` gather.  Per-job DP
        state is row-independent, so a slot move is bit-exact; fresh
        rows get the same +inf/zero init a ``submit`` reset would
        write."""
        axis = self._axis
        gather = jnp.asarray(np.maximum(src, 0), jnp.int32)
        fresh = jnp.asarray(src < 0)
        self._rows = self._put(
            jnp.where(fresh[:, None, None], _dtw._INF,
                      jnp.take(self._rows, gather, axis=0)),
            (None, None, axis))
        if self._moms is not None:
            self._moms = self._put(
                jnp.where(fresh[None, :, None, None], 0.0,
                          jnp.take(self._moms, gather, axis=1)),
                (None, None, None, axis))
        self._ns = self._put(jnp.where(fresh, 0,
                                       jnp.take(self._ns, gather, axis=0)),
                             (None,))
        self._sx = self._put(jnp.where(fresh, 0.0,
                                       jnp.take(self._sx, gather, axis=0)),
                             (None,))
        self._sxx = self._put(jnp.where(fresh, 0.0,
                                        jnp.take(self._sxx, gather, axis=0)),
                              (None,))
        if self._vstats is not None:
            self._vstats = self._put(
                jnp.where(fresh[:, None], 0.0,
                          jnp.take(self._vstats, gather, axis=0)),
                (None, None))
        self._qlens = np.where(src >= 0, self._qlens[np.maximum(src, 0)],
                               0).astype(np.int32)
        self._s_cap = len(src)
        self.slot_repack_count += 1

    def _apply_resets(self) -> None:
        """Fresh-initialize every slot submitted since the last data tick
        (+inf DP row, zero moments/query stats) in ONE masked op per
        array.  Runs before any state gather or dispatch, so lazy resets
        are indistinguishable from the eager per-submit resets they
        replace."""
        if not self._dirty:
            return
        axis = self._axis
        mask = np.zeros((self._s_cap,), bool)
        mask[self._dirty] = True
        md = jnp.asarray(mask)
        self._rows = self._put(
            jnp.where(md[:, None, None], _dtw._INF, self._rows),
            (None, None, axis))
        if self._moms is not None:
            self._moms = self._put(
                jnp.where(md[None, :, None, None], 0.0, self._moms),
                (None, None, None, axis))
        self._ns = self._put(jnp.where(md, 0, self._ns), (None,))
        self._sx = self._put(jnp.where(md, 0.0, self._sx), (None,))
        self._sxx = self._put(jnp.where(md, 0.0, self._sxx), (None,))
        if self._vstats is not None:
            self._vstats = self._put(
                jnp.where(md[:, None], 0.0, self._vstats), (None, None))
        self._dirty = []

    def _maybe_shrink_slots(self) -> None:
        """Compact-shrink the S axis when the active set fits a smaller
        power-of-two bucket (elastic mode; a data tick's preamble, like
        the K-axis ``_maybe_repack``)."""
        plan = self._sched.shrink_plan()
        if plan is None:
            return
        src, moves = plan
        self._repack_slots(src)
        for jid, s in moves.items():
            self._jobs[jid].slot = s

    # -- streaming wavelet prefilter -----------------------------------------
    def _ref_prefix_coeffs(self, size: int, n: int) -> np.ndarray:
        """Compressed Haar coefficient bank of every reference's first
        ``n`` samples, edge-extended to target length ``size`` — the
        apples-to-apples counterpart of a job's :class:`StreamingHaar`
        prefix coefficients (sampling rates are shared, so ``n`` job
        samples correspond to ~``n`` reference samples; comparing the
        prefix against FULL references would just correlate the job's
        constant extension tail against unseen reference structure).
        Cached per (size, n): lockstep jobs share the transform."""
        key = (size, n)
        cb = self._wcoeff_cache.get(key)
        if cb is None:
            series = self.bank.series.astype(np.float64)
            w = series.shape[1]
            cut = np.minimum(self._full_lengths, n)             # [K]
            edge = np.take_along_axis(series, (cut - 1)[:, None], axis=1)
            bp = np.where(np.arange(w)[None, :] < cut[:, None], series,
                          edge)
            bp = np.pad(bp, ((0, 0), (0, size - w)), mode="edge") \
                if size >= w else bp[:, :size]
            cb = _wavelet.compress_bank(_wavelet.haar_dwt_bank(bp),
                                        self.prefilter_coeffs)
            if len(self._wcoeff_cache) >= 16:
                self._wcoeff_cache.pop(next(iter(self._wcoeff_cache)))
            self._wcoeff_cache[key] = cb
        return cb

    @staticmethod
    def _top_p_with_margin(sims: np.ndarray, allowed: np.ndarray, p: int,
                           margin: float) -> np.ndarray:
        """Bool keep-mask: references ranking in the top ``p`` of ``sims``
        among ``allowed``, widened by ``margin`` (anything within margin
        of the p-th best survives too, so near-ties can't be evicted on
        ranking noise)."""
        ranked = np.where(allowed, sims, -np.inf)
        kth = np.partition(ranked, -p)[-p]
        return ranked >= kth - margin

    def _update_prefilter(self, pending) -> None:
        """Shrink each touched job's live-reference set.  Two top-P (+
        soundness margin) rules vote and the UNION survives:

        * the streaming-Haar ranking (coarse, warp-blind, cheap) proposes
          the bulk prune — at large K this is what collapses the tick;
        * the job's own in-flight open-end DTW scores (from the previous
          fused tick) veto the eviction of anything still plausibly
          winning — the Haar cosine compares prefixes rigidly, so a
          reference that matches the job only under warping (the paper's
          exim-vs-wordcount case) ranks poorly there while its warp
          correlation is already high; without the veto the prefilter
          would drop the eventual winner.

        Sticky per job: sets only ever shrink, so a dropped reference's
        DP column never has to re-enter for a job that already has
        samples (re-entry would be stale)."""
        p = self.prefilter_top
        if self._overload is not None:
            # deep_prune rung: survivor sets shrink harder (sticky, so
            # the deeper cut persists after de-escalation — monotone
            # like every other prune).
            p = max(1, p // self._overload.prefilter_divisor)
        for job, *_ in pending:
            if job.haar is None or job.n < 2:
                continue
            if job.degraded_level >= 2:
                # distance-only ticks froze this job's DTW veto scores;
                # pruning on a stale veto could evict the eventual
                # winner, so the live set just stops shrinking.
                continue
            if job.fraction_seen < self.prefilter_min_fraction:
                continue
            if self.score_in_flight and job.last_sims is None:
                continue          # no DTW veto yet: too early to prune
            allowed = job.allowed if job.allowed is not None \
                else np.ones((self._k,), bool)
            if int(allowed.sum()) <= p:
                continue                              # converged
            keep = self._top_p_with_margin(
                _wavelet.coeff_similarity_bank(
                    job.haar.compressed(self.prefilter_coeffs),
                    self._ref_prefix_coeffs(job.haar.size, job.n)),
                allowed, p, self.prefilter_margin)
            if job.last_sims is not None:
                dsims = np.where(allowed,
                                 np.nan_to_num(job.last_sims, neginf=-1.0),
                                 -np.inf)
                keep |= self._top_p_with_margin(dsims, allowed, p,
                                                self.prefilter_margin)
                # the early-decision margin compares the leader WORKLOAD
                # against the runner-up WORKLOAD: protect the best
                # reference of each of the current top-2 workloads, or
                # evicting the whole runner-up family would floor its
                # score to -1.0 and make the margin gate vacuously true.
                seen = set()
                for r in np.argsort(dsims)[::-1]:
                    if not np.isfinite(dsims[r]) or len(seen) == 2:
                        break
                    if self._labels[r] not in seen:
                        seen.add(self._labels[r])
                        keep[r] = True
            job.allowed = np.logical_and(allowed, keep)

    def _survivors(self) -> np.ndarray:
        """Union of the active jobs' live sets -> full-bank index array.
        A job whose prefilter has not engaged needs every reference."""
        mask = np.zeros((self._k,), bool)
        for job in self._jobs.values():
            if job.allowed is None:
                return np.arange(self._k)
            mask |= job.allowed
        return np.flatnonzero(mask)

    def _maybe_repack(self) -> None:
        """Re-pack the device state when the survivor union has outgrown
        the packed columns (a fresh job needs everything again) or when it
        has shrunk past the next power-of-two bucket.  A packed set that
        merely *contains* the survivors is left alone: the extra columns
        cost one bucket's worth of compute at most, while every re-pack
        is a state upload and (first time per shape) an XLA compile —
        chasing each membership change would churn far more than the
        stragglers cost."""
        if self.prefilter_top is None:
            return
        idx = self._survivors()
        grown = not np.isin(idx, self._packed_idx,
                            assume_unique=True).all()
        full = len(idx) == self._k
        kp_target = self._k_pad(self._k) if full \
            else self._k_bucket(len(idx))
        if not grown and kp_target >= self._kp:
            return
        self._pack_device_state(idx, self._rows, self._moms)
        self.repack_count += 1

    # -- tick compilation ----------------------------------------------------
    def _base_mode(self) -> str:
        """The configured (unloaded) tick mode: ``"prob"`` (exact
        6-channel probabilities), ``"approx_prob"`` (the 4-channel
        approximate tail — ``prob_mode="approx"``), ``"scored"`` or
        ``"distance"``."""
        if self.min_probability is not None:
            return "approx_prob" if self.prob_mode == "approx" else "prob"
        return "scored" if self.score_in_flight else "distance"

    def _tick_mode(self) -> str:
        """Effective tick mode this tick: the configured mode, capped by
        the overload ladder's current rung (a cap can only ever be
        CHEAPER than the configured mode — ``min`` over the expense
        order, so a distance-only service is never upgraded and an
        approx-probability service is never promoted to the exact
        tail)."""
        base = self._base_mode()
        if self._overload is None:
            return base
        order = {"prob": 0, "approx_prob": 1, "scored": 2, "distance": 3}
        cap = self._overload.tick_mode_cap
        return cap if order[cap] > order[base] else base

    def _tick_fn_for(self, mode: str):
        """Cached ``(tick_fn, fallback)`` per mode — the configured mode
        compiles at construction, degraded modes on first use."""
        fns = self._tick_fns.get(mode)
        if fns is None:
            fns = self._build_tick_fn(mode)
            self._tick_fns[mode] = fns
        return fns

    def _build_tick_fn(self, mode: str):
        """The ONE callable a tick dispatches, and its fallback:
        ``core.dtw.bank_extend_tick`` over ``mode``'s channel set,
        shard_mapped over the bank axis under a mesh
        (:func:`tick_program`).  Sharding is exact — every DP cell and
        score is a per-reference quantity, so the fan-out computes
        disjoint K slices and the [S, K] score gather is the only
        cross-device output.

        ``mode`` sets the channel set (``"prob"`` 6, ``"approx_prob"``
        4, ``"scored"`` 3, ``"distance"`` none): the configured mode in
        an unloaded service, or a cheaper ladder rung's under overload
        (every channel set updates the DP rows identically — same
        warp-path predecessor selection — so a degraded tick leaves the
        rows bitwise what the full tick would have computed and only
        side channels go stale).

        Returns ``(tick_fn, fallback_fn)``.  The fallback is the same
        tick pinned to the jnp wavefront twin (``use_kernel=False``)
        — bit-identical to the Pallas kernel, so a degraded tick after
        retry exhaustion changes latency, never results."""
        kw = dict(band=self.band, threshold=float(self.threshold))
        return (tick_program(mode, self.mesh, **kw),
                tick_program(mode, self.mesh, use_kernel=False, **kw))

    # -- dispatch resilience --------------------------------------------------
    def _dispatch_resilient(self, fn, fallback, args, kind: str):
        """Run one device dispatch, ``fn(*args)``, through the
        retry/backoff wrapper.

        ``fallback`` is the jnp wavefront twin of ``fn``, called with the
        same ``args``.
        Transient device errors — and chaos-injected ones, consulted per
        *attempt* so a fault burst spans retries — are retried per
        ``self.retry_policy``; after exhaustion the fallback serves the
        tick once and the service surfaces ``degraded``.  Results are
        bit-identical either way (the twin is pinned against the kernel),
        so injected faults move latency and counters, never scores or
        decisions.  With neither a policy nor a chaos plan nor a breaker
        armed this is a plain call — the hot path pays one attribute
        test.

        A runtime error counts as transient only for a dispatch signature
        (``kind`` plus argument shapes) that has already executed: the
        first call of a signature also lowers and compiles it, and a
        lowering or compile failure — a kernel the chip's compiler
        refuses, a program over the device's memory — must raise, never
        be retried or hidden behind the jnp twin.

        A :class:`runtime.retry.CircuitBreaker` (``breaker=``) wraps the
        whole ladder: while OPEN the fallback serves directly (no
        primary attempt, no chaos consult, no retry backoff — the point
        is not paying the failing kernel every tick); in HALF-OPEN a
        seeded probe re-tries the primary once per probe slot, and a
        success re-promotes the kernel path (``degraded`` clears)."""
        chaos = self.chaos
        breaker = self.breaker
        if chaos is None and self.retry_policy is None and breaker is None:
            return fn(*args)
        sig = (kind,) + tuple((a.shape, a.dtype) for a in args
                              if a is not None)
        transient = (InjectedDispatchError, jax.errors.JaxRuntimeError) \
            if sig in self._executed else (InjectedDispatchError,)

        def attempt():
            if chaos is not None:
                chaos.on_dispatch(kind)
            result = fn(*args)
            self._executed.add(sig)
            return result

        if breaker is not None:
            route = breaker.before_dispatch()
            if route == "fallback":
                self.degraded_dispatch_count += 1
                self.last_tick_degraded = True
                return fallback(*args)
            if route == "probe":
                try:
                    result = attempt()       # one un-retried attempt
                except transient:
                    breaker.record_failure()
                    self.degraded_dispatch_count += 1
                    self.last_tick_degraded = True
                    return fallback(*args)
                breaker.record_success()
                return result

        policy = self.retry_policy or RetryPolicy(max_retries=0,
                                                  base_delay=0.0)
        result, report = call_with_retry(
            attempt, policy=policy, transient=transient,
            fallback=lambda: fallback(*args))
        self.retry_count += report["retries"]
        if report["degraded"]:
            self.degraded_dispatch_count += 1
            self.last_tick_degraded = True
            if breaker is not None:
                breaker.record_failure()
        elif breaker is not None:
            breaker.record_success()
        return result

    # -- input quarantine -----------------------------------------------------
    def _quarantine(self, job_id: str, reason: str) -> None:
        """Evict a job whose stream produced a poisoned sample (NaN/Inf,
        bad variance).  The offending push was rejected atomically before
        touching any buffer, and per-job DP state is row-independent, so
        survivors are bit-identical to a run that never saw the sick
        job's tail — the same guarantee the churn-invariance suite pins
        for ordinary evictions.  Later pushes for the job are dropped
        (counted), not resurrected."""
        self.quarantined[job_id] = reason
        self.quarantined_count += 1
        self.evict(job_id)

    # -- elastic rescale ------------------------------------------------------
    def rescale(self, mesh: Union[jax.sharding.Mesh, Dict[str, int],
                                  None]) -> None:
        """Re-home the device state onto a different 1-D mesh (either
        form ``mesh=`` takes, or back to a single device with
        ``mesh=None``) mid-flight — the hook a
        ``runtime.fault.ElasticController`` rescale decision drives when
        hosts die or join.  The bank re-pads to the new device-count
        multiple and every state slab moves by the same on-device gather
        a prefilter re-pack uses, so scores and decisions are unchanged
        (sharding is exact); the tick callable recompiles for the new
        mesh."""
        mesh = _auto_axes(mesh)
        ndev, axis = 1, None
        if mesh is not None:
            if len(mesh.axis_names) != 1:
                raise ValueError("TuningService needs a 1-D mesh (one bank "
                                 f"axis); got axes {mesh.axis_names}")
            axis = mesh.axis_names[0]
            ndev = mesh.devices.size
        rows, moms = self._rows, self._moms
        self.mesh, self._ndev, self._axis = mesh, ndev, axis
        self._ns = self._put(np.asarray(self._ns), (None,))
        self._sx = self._put(np.asarray(self._sx), (None,))
        self._sxx = self._put(np.asarray(self._sxx), (None,))
        if self._vstats is not None:
            self._vstats = self._put(np.asarray(self._vstats), (None, None))
        self._pack_device_state(self._packed_idx, rows, moms)
        self._tick_fns = {}            # per-mode callables are mesh-bound
        self._executed.clear()
        self._tick_fn_for(self._base_mode())
        self.rescale_count += 1

    # -- job lifecycle -------------------------------------------------------
    @property
    def n_active(self) -> int:
        return len(self._jobs)

    @property
    def slot_capacity(self) -> int:
        """Current S bucket (== ``slots`` when ``elastic_slots=False``)."""
        return self._s_cap

    # -- overload surface (serve.overload runbook) ---------------------------
    @property
    def rung(self) -> int:
        """Current degradation-ladder rung (0 without a controller)."""
        return 0 if self._overload is None else self._overload.rung

    @property
    def rung_history(self) -> List[Tuple[int, int, int]]:
        """Ladder transitions ``(observation_index, from, to)`` — empty
        without a controller."""
        return [] if self._overload is None \
            else list(self._overload.rung_history)

    @property
    def degraded(self) -> bool:
        """True while the service is NOT serving its configured quality:
        the circuit breaker has demoted the kernel path, or the overload
        ladder sits above rung 0.  Clears when the breaker re-closes and
        the ladder de-escalates back to normal."""
        return (self.breaker is not None and self.breaker.engaged) \
            or self.rung > 0

    def overload_pressure(self) -> float:
        """Scalar [0, 1] rescale-ahead signal for
        ``runtime.fault.ElasticController.decide_ahead``: the worse of
        the ladder's latency pressure and the ingest queue fill."""
        p = self._front.queue_fill()
        if self._overload is not None:
            p = max(p, self._overload.pressure())
        return p

    def submit(self, job_id: str, expected_len: int,
               tick_hz: Optional[float] = None,
               qos: str = "silver") -> InFlightJob:
        """Register an in-flight job (``expected_len`` = predicted total
        sample count; it anchors the Sakoe-Chiba band and the
        fraction-seen gate of the early-decision rule).  ``tick_hz``
        assigns the job to a tick-rate cohort: ``tick(now=...)`` drains
        it only on its own period (None = every tick).

        ``qos`` (bronze/silver/gold) is the job's admission class: with
        an admission controller armed (``admission=``), a submit under
        measured overload raises
        :class:`serve.overload.AdmissionShedError` — bronze sheds first,
        gold last (see the ``serve.overload`` runbook).  A shed submit
        leaves NO state behind (and is never journaled): the producer
        retries later or routes the job elsewhere."""
        if job_id in self._jobs:
            raise ValueError(f"job {job_id!r} already in flight")
        if expected_len < 1:
            raise ValueError("expected_len must be >= 1")
        if self._admission is not None and not self._admission_suppressed:
            rung_frac = (self._overload.rung / max(1, len(RUNGS) - 1)
                         if self._overload is not None else 0.0)
            cost_fill = min(1.0, expected_len / (
                self._admission.policy.cost_scale * self._mean_ref_len))
            try:
                self._admission.admit(
                    job_id, qos=qos, cost_fill=cost_fill,
                    queue_fill=self._front.queue_fill(),
                    rung_frac=rung_frac)
            except AdmissionShedError:
                self.shed_count += 1
                self.shed_by_class[qos] = \
                    self.shed_by_class.get(qos, 0) + 1
                raise
        slot, grow_src = self._sched.admit(job_id, tick_hz)
        if grow_src is not None:
            self._repack_slots(grow_src)
        # the slot's device state is reset LAZILY (one masked op at the
        # next data tick covers every submit since the last one) — a
        # stale freed row is inert until then: its nvalid is 0 in every
        # dispatch and only pending jobs' scores are ever read.  Under
        # churn this turns S x M x K copies per *submit* into one per
        # *tick*.
        self._dirty.append(slot)
        self._qlens[slot] = expected_len
        job = InFlightJob(job_id=job_id, slot=slot, expected_len=expected_len,
                          tick_hz=tick_hz, qos=qos,
                          haar=_wavelet.StreamingHaar(expected_len)
                          if self.prefilter_top is not None else None)
        self._front.register(job_id)
        self._jobs[job_id] = job
        return job

    def push(self, job_id: str, samples: np.ndarray,
             variance: Optional[np.ndarray] = None,
             now: Optional[float] = None) -> None:
        """Buffer newly observed samples; consumed at the job's next due
        tick.  ``now`` stamps the heartbeat/straggler trackers (when
        armed) — a clock-less push is accepted but invisible to
        :meth:`sweep_stalled`.  ``variance`` (probabilistic mode only)
        carries aligned per-sample measurement variances; when omitted
        the ingest layer estimates them from the causal filter residual
        at drain time (0.0 without ``denoise`` — exact pushes stay
        exact).

        Poisoned payloads (NaN/Inf samples, negative or non-finite
        variances) QUARANTINE the job: the push is rejected atomically
        by the ingest layer, the job is evicted with the poison reason
        recorded in :attr:`quarantined`, and ``PoisonedSampleError`` is
        re-raised to the caller.  Survivors are untouched — bit-identical
        scores and decisions (see :meth:`_quarantine`)."""
        if job_id in self.quarantined:
            # a sick agent keeps streaming; swallow, never resurrect.
            self.quarantine_dropped += 1
            return
        if job_id not in self._jobs:
            raise KeyError(job_id)
        if self.chaos is not None:
            samples = self.chaos.corrupt(samples)
            now = self.chaos.skew(now)
        try:
            self._front.push(job_id, samples, variance=variance, now=now)
        except PoisonedSampleError as err:
            self._quarantine(job_id, err.reason)
            raise

    # -- the hot path --------------------------------------------------------
    def tick(self, now: Optional[float] = None, *,
             latency: Optional[float] = None,
             _observe: bool = True) -> Dict[str, Optional[TuneDecision]]:
        """Drain every due job's buffered samples in ONE jitted dispatch
        (DP extend + prefix scoring fused, sharded over the bank when a
        mesh is set), then apply the early-decision rule to the returned
        [S, K] score array.

        ``now`` meters the tick-rate cohorts: only cohorts whose period
        has elapsed drain (others keep buffering).  Without a clock
        every job is due — the legacy cadence.

        Overload plumbing (``overload=``): the rung decided by PRIOR
        observations is in force for this whole tick (mode cap, deeper
        pruning, cohort stretch — decided pre-dispatch, so replay can
        reproduce it), then the tick's measured wall-clock latency (plus
        any chaos-injected slowdown) feeds the ladder.  ``latency=``
        overrides the measurement — the recovery journal records each
        live tick's latency and replays it here, which is what makes the
        rung trajectory (hence tick modes and staleness markers)
        bit-identical across recovery.  ``_observe=False`` marks an
        internal drain tick (see :meth:`finish`): it must not advance
        the ladder, because only top-level tick commands are journaled
        with a latency.

        Returns {job_id: TuneDecision} for decisions *newly emitted* this
        tick (None for touched jobs where the service abstains), plus any
        decision a previous internal tick (see :meth:`finish`) emitted but
        could not deliver.
        """
        if self._overload is not None:
            self._sched.cohorts.rate_scale = self._overload.cohort_scale
            if _observe and self._overload.rung >= 1:
                self.overload_ticks += 1
        t0 = time.perf_counter()
        with TraceAnnotation("tuner.tick", tick=self.ticks + 1,
                             internal=int(not _observe)):
            out = self._tick_impl(now)
        if _observe:
            lat = time.perf_counter() - t0 if latency is None \
                else float(latency)
            if latency is None and self.chaos is not None:
                lat += self.chaos.slow_dispatch("tick")
            self.last_tick_latency = lat
            if self._overload is not None:
                self._overload.observe(lat)
                self.worst_rung = max(self.worst_rung,
                                      self._overload.rung)
        return out

    def _tick_impl(self, now: Optional[float]
                   ) -> Dict[str, Optional[TuneDecision]]:
        self.ticks += 1
        self.last_tick_degraded = False
        out: Dict[str, Optional[TuneDecision]] = self._undelivered
        self._undelivered = {}
        prob_mode = self.min_probability is not None
        pending: List[Tuple[InFlightJob, np.ndarray,
                            Optional[np.ndarray]]] = []
        with TraceAnnotation("tuner.drain") as span:
            filters0 = self._front.filter_count
            samples = 0
            due = self._sched.due_jobs(now, self._jobs.keys())
            jobs = [job for job in self._jobs.values() if job.job_id in due]
            drained = self._front.drain_many([job.job_id for job in jobs],
                                             with_variance=prob_mode)
            for job, got in zip(jobs, drained):
                chunk, vchunk = got if prob_mode else (got, None)
                if chunk is None:
                    continue
                job.x.append(chunk)
                if vchunk is not None:
                    job.vx.append(vchunk)
                if job.haar is not None:
                    job.haar.update(chunk)
                pending.append((job, chunk, vchunk))
                samples += chunk.shape[0]
            span.set_metadata(
                jobs=len(pending), samples=samples,
                filtered=self._front.filter_count - filters0)
        if not pending:
            return out

        # re-pack preamble (state motion, never a dispatch): deferred
        # fresh-slot resets first (so no gather ever moves stale rows),
        # then K-axis when the prefilter's survivor union crossed a
        # bucket boundary, then S-axis when the active set fits a
        # smaller slot bucket.
        with TraceAnnotation("tuner.repack") as span:
            slot0, k0 = self.slot_repack_count, self.repack_count
            self._apply_resets()
            if self.prefilter_top is not None:
                self._maybe_repack()
            self._maybe_shrink_slots()
            span.set_metadata(slot_repacks=self.slot_repack_count - slot0,
                              k_repacks=self.repack_count - k0)
        k_live = len(self._packed_idx)

        with TraceAnnotation("tuner.chunks") as span:
            c = _dtw._chunk_bucket(max(ch.shape[0] for _, ch, _ in pending))
            chunks = np.zeros((self._s_cap, c), np.float32)
            nvalid = np.zeros((self._s_cap,), np.int32)
            vchunks = np.zeros((self._s_cap, c), np.float32) if prob_mode \
                else None
            for job, ch, vch in pending:
                chunks[job.slot, : ch.shape[0]] = ch
                nvalid[job.slot] = ch.shape[0]
                if prob_mode:
                    vchunks[job.slot, : ch.shape[0]] = vch
            span.set_metadata(chunk=c, slots=self._s_cap)

        # Effective tick mode: the configured one, or a cheaper one
        # under the overload ladder.  Every mode updates the DP rows
        # (and ns) identically — the warp-path predecessor selection is
        # shared — so a degraded tick DELAYS decisions (side channels go
        # stale, marked on the job) but can never change them.
        mode = self._tick_mode()
        nch = _TICK_CHANNELS[mode]
        tick_fn, tick_fb = self._tick_fn_for(mode)
        # A rung cheaper than the configured mode ticks the first nch
        # channels of the service's wider slab (sy, syy, sxy, then svy:
        # the approx rung of an exact service keeps its probabilities
        # flowing).  The channels above stay what they were — stale,
        # never wrong-and-used, because degraded_level >= 1 suppresses
        # every read of them — and are spliced back after the tick.
        wide = nch > 0 and self._moms.shape[0] > nch
        moms = self._moms[:nch] if wide else (self._moms if nch else None)
        scores = probs = None
        with TraceAnnotation("tuner.dispatch", mode=mode, k_live=k_live,
                             shards=self._ndev):
            given = dict(
                rows=self._rows, moms=moms, ns=self._ns, sx=self._sx,
                sxx=self._sxx, vstats=self._vstats, bank_t=self._bank_t,
                lengths=self._lengths, chunks=jnp.asarray(chunks),
                vchunks=jnp.asarray(vchunks) if nch > 3 else None,
                nvalid=jnp.asarray(nvalid), qlens=jnp.asarray(self._qlens))
            res = self._dispatch_resilient(
                tick_fn, tick_fb, tuple(given[a] for a in _tick_layout(nch)),
                "tick")
            self._rows, moms, self._ns, self._sx, self._sxx, scores = res[:6]
            if nch > 3:
                self._vstats, probs = res[6:]
            if wide:
                self._moms = self._put(
                    jnp.concatenate([moms, self._moms[nch:]], axis=0),
                    (None, None, None, self._axis))
            elif nch:
                self._moms = moms
        self.dispatch_count += 1

        # the tick's ONLY device->host transfer: the [S, K_live] scores
        # (and probabilities), scattered back to full-bank columns
        # (pruned-out references read -inf — never a leader, never a
        # runner-up — and carry zero match probability).
        sims_all = probs_all = None
        if scores is not None:
            with TraceAnnotation("tuner.pull"):
                sims_all = np.full((self._s_cap, self._k), -np.inf)
                sims_all[:, self._packed_idx] = \
                    np.asarray(scores, np.float64)[:, :k_live]
                if probs is not None:
                    probs_all = np.zeros((self._s_cap, self._k))
                    probs_all[:, self._packed_idx] = \
                        np.asarray(probs, np.float64)[:, :k_live]

        with TraceAnnotation("tuner.decide") as span:
            if mode != self._base_mode():
                lvl = 2 if mode == "distance" else 1
                for job, *_ in pending:
                    job.degraded_level = max(job.degraded_level, lvl)

            # a level-2 job's moment/query-stat channels are stale, so
            # any score a later scored tick emits for its slot is
            # garbage: freeze last_sims/last_probs at their last exact
            # values instead of overwriting them.
            fresh = []
            for job, ch, _ in pending:
                job.n += ch.shape[0]
                if sims_all is not None and job.degraded_level < 2:
                    fresh.append(job)
            ranked: Dict[str, Optional[TuneDecision]] = {}
            if fresh:
                slots = np.array([job.slot for job in fresh])
                sims = sims_all[slots]
                prs = probs_all[slots] if probs_all is not None else None
                for i, job in enumerate(fresh):
                    if job.allowed is not None:
                        # a column another job kept alive may be pruned
                        # for THIS job: mask it out of this job's view.
                        sims[i, ~job.allowed] = -np.inf
                        if prs is not None:
                            prs[i, ~job.allowed] = 0.0
                    job.last_sims = sims[i]
                    if prs is not None:
                        job.last_probs = prs[i]
                rows = [i for i, job in enumerate(fresh)
                        if job.early is None and job.degraded_level == 0
                        and job.n >= 2]
                if rows:
                    for i, rank in zip(rows, self._ranks(
                            sims[rows], None if prs is None else prs[rows])):
                        fresh[i].rank = rank
                        ranked[fresh[i].job_id] = self._maybe_decide(fresh[i])
            for job, *_ in pending:
                if out.get(job.job_id) is None:
                    out[job.job_id] = ranked.get(job.job_id)
            span.set_metadata(
                jobs=len(pending), ranked=len(ranked),
                decisions=sum(d is not None for d in ranked.values()))
        # prune with THIS tick's information (scores just computed, n just
        # advanced): eviction decisions lag the data by zero ticks, the
        # re-pack they imply happens at the top of the next tick.
        if self.prefilter_top is not None:
            with TraceAnnotation("tuner.prefilter"):
                self._update_prefilter(pending)
        return out

    # -- decision rule -------------------------------------------------------
    def _workload_max(self, rows: np.ndarray) -> np.ndarray:
        """[J, W] per-workload best of [J, K] rows, workloads in
        first-appearance order: NaN ignored, floored at -1.0."""
        grouped = np.take(rows, self._col_perm, axis=1)
        return np.fmax(np.fmax.reduceat(grouped, self._col_starts, axis=1),
                       -1.0)

    def _ranks(self, sims: np.ndarray, probs: Optional[np.ndarray]
               ) -> List[_Rank]:
        """Rank [J, K] rows in one batched reduce -> each row's rank.
        The first maximum leads, so repeated ticks rank
        deterministically; the runner-up is -1.0 with one workload. In
        probabilistic mode ``probs`` holds the rows' match probabilities,
        reduced the same way and read at the leader."""
        w = self._workload_max(sims)
        at = np.arange(w.shape[0])
        lead = np.argmax(w, axis=1)
        if w.shape[1] < 2:
            rs = np.full(w.shape[0], -1.0)
        else:
            others = w.copy()
            others[at, lead] = -np.inf
            rs = others.max(axis=1)
        lps = (self._workload_max(probs)[at, lead].tolist()
               if probs is not None else [None] * len(lead))
        return [_Rank(*r) for r in zip(w, lead.tolist(),
                                       w[at, lead].tolist(), rs.tolist(),
                                       lps)]

    def _maybe_decide(self, job: InFlightJob) -> Optional[TuneDecision]:
        """Step the job's leader/stability state from its rank this tick
        (``job.rank``) -> its early decision, or None."""
        leader = self._workloads[job.rank.leader]
        ls, rs, lp = job.rank.score, job.rank.runner_up, job.rank.probability
        # the margin test needs a real runner-up: with < 2 workloads in
        # the bank it would be vacuously true (rs == -1.0), so the
        # service abstains in flight instead of fast-tracking the only
        # candidate (finish() still decides from the complete series).
        margin_ok = self._n_workloads >= 2 and ls - rs >= self.margin
        if leader == job.leader and margin_ok:
            job.stable_for += 1
        else:
            job.stable_for = 1 if margin_ok else 0
        job.leader = leader
        # confidence gate: the point correlation threshold, or in
        # probabilistic mode the leader workload's match probability —
        # a flat posterior (noisy prefix) keeps the service abstaining
        # even when the point estimate momentarily clears the threshold.
        # At zero input variance the probability is exactly
        # 1{corr >= threshold}, so the two gates coincide bitwise.
        if self.min_probability is not None:
            confident = lp >= self.min_probability
        else:
            confident = ls >= self.threshold
        if (job.fraction_seen >= self.min_fraction
                and confident
                and job.stable_for >= self.stable_ticks):
            cfg = self.db.best_config(leader) if self.db is not None else None
            job.early = TuneDecision(
                workload=job.job_id, matched=leader, corr=ls, config=cfg,
                scores=dict(zip(self._workloads, job.rank.scores.tolist())),
                fraction_seen=job.fraction_seen, final=False,
                decided_at_fraction=job.fraction_seen, probability=lp)
            return job.early
        return None

    # -- fault handling ------------------------------------------------------
    def evict(self, job_id: str) -> Optional[TuneDecision]:
        """Drop an in-flight job WITHOUT a verdict: slot freed, queue and
        heartbeat state discarded, device rows left to be compacted away
        by the next data tick's S-axis shrink.  Returns the job's early
        decision if one was emitted (the only tuning signal a stalled
        job ever produced).  Survivors are untouched — per-job state is
        row-independent, so eviction cannot perturb their scores."""
        if job_id not in self._jobs:
            raise KeyError(job_id)
        with TraceAnnotation("tuner.retire", jobs=1):
            _, _, early = self._retire(job_id)
        self.evicted_count += 1
        return early

    def sweep_stalled(self, now: float) -> Dict[str, Optional[TuneDecision]]:
        """Evict every job whose heartbeat (stamped by ``push(...,
        now=)``) is older than the service's ``heartbeat_timeout`` —
        stalled ingest must not pin a slot forever.  Returns {job_id:
        early decision or None} for the evicted set; a no-op (empty
        dict) when heartbeats are not armed."""
        return {jid: self.evict(jid) for jid in self._front.stalled(now)}

    def stragglers(self) -> List[str]:
        """In-flight jobs whose observed push cadence is consistently
        slower than the cohort median (``runtime.fault
        .StragglerDetector`` over inter-push gaps) — candidates for a
        slower tick-rate cohort or eviction."""
        return [j for j in self._front.stragglers.stragglers()
                if j in self._jobs]

    # -- completion ----------------------------------------------------------
    #
    # Final verdicts are MATRIX-FREE and batchable: one
    # ``dtw.dtw_score_bank_many`` dispatch carries the warp-path
    # correlation moments through the DP on device and reads them at the
    # closed alignment endpoint, so J completed jobs cost one dispatch —
    # not J ``[K, N, M]`` matrix materializations with host backtracking.
    # Per-job scores are bitwise independent of how verdicts are batched
    # (per-cell arithmetic plus host-side per-query moment folds), so
    # ``finish``, ``finish_many`` and the deferred drain queue all render
    # identical decisions for the same job.

    def _verdict_scores(self, queries, variances=None):
        """[J, K] float64 offline scores (and, in probabilistic mode, the
        [J, K] match probabilities) for J completed queries in ONE
        matrix-free dispatch, the Sakoe-Chiba band re-derived from each
        query's TRUE length (the in-flight corridor was anchored to the
        ``expected_len`` prediction).  Queries with fewer than 2 samples
        score 0 without touching the device; the bank's tiled device
        upload is memoized on the SeriesBank (``score_plan``), so
        verdicts move query bytes only."""
        prob_mode = self.min_probability is not None
        out = np.zeros((len(queries), self._k), np.float64)
        pout = np.zeros((len(queries), self._k), np.float64) \
            if prob_mode else None
        live = [i for i, q in enumerate(queries) if q.shape[0] >= 2]
        if not live:
            return out, pout
        # pow2 buckets on both axes so repeat drains reuse jit shapes
        jb = _dtw._pad_pow2(len(live), lo=1)
        npad = _dtw._pad_pow2(max(queries[i].shape[0] for i in live))
        with TraceAnnotation("tuner.verdict.pack", jobs=len(live),
                             padded=jb, npad=npad):
            xs = np.zeros((jb, npad), np.float32)
            xl = np.zeros((jb,), np.int32)
            sx = np.zeros((jb,), np.float32)
            sxx = np.zeros((jb,), np.float32)
            xv = np.zeros((jb, npad), np.float32) if prob_mode else None
            for r, i in enumerate(live):
                q = queries[i]
                xs[r, : q.shape[0]] = q
                xl[r] = q.shape[0]
                sx[r], sxx[r] = _dtw.query_moments(q)
                if prob_mode:
                    v = variances[i]
                    if v is not None and v.shape[0] == q.shape[0]:
                        xv[r, : q.shape[0]] = v
        kw = dict(xvars=xv, threshold=float(self.threshold)) \
            if prob_mode else {}

        def call(xs, xl, sx, sxx, use_kernel=None):
            return _dtw.dtw_score_bank_many(
                xs, self.bank.series, self.bank.lengths, xlens=xl,
                band=self.band, sx=sx, sxx=sxx,
                plan=self.bank.score_plan(self.mesh), use_kernel=use_kernel,
                **kw)
        with TraceAnnotation("tuner.verdict.dispatch", shards=self._ndev):
            res = self._dispatch_resilient(
                call, functools.partial(call, use_kernel=False),
                (xs, xl, sx, sxx), "verdict")
        scores, probs = res if prob_mode else (res, None)
        with TraceAnnotation("tuner.verdict.pull"):
            if prob_mode:
                probs = np.asarray(probs, np.float64)
            scores = np.asarray(scores, np.float64)
            self.offline_dispatch_count += 1
            for r, i in enumerate(live):
                out[i] = scores[r]
                if prob_mode:
                    pout[i] = probs[r]
        return out, pout

    def _render_verdicts(self, ids: List[str], sims: np.ndarray,
                         probs: Optional[np.ndarray],
                         earlies: List[Optional[TuneDecision]]
                         ) -> Dict[str, TuneDecision]:
        """Final decisions for the [J, K] verdict block, ranked in one
        call."""
        with TraceAnnotation("tuner.verdict.render", jobs=len(ids)) as span:
            out = {jid: self._render_verdict(jid, early, rank)
                   for jid, early, rank in zip(ids, earlies,
                                               self._ranks(sims, probs))}
            span.set_metadata(ranked=len(ids))
        return out

    def _render_verdict(self, job_id: str, early: Optional[TuneDecision],
                        rank: _Rank) -> TuneDecision:
        leader, ls, lp = self._workloads[rank.leader], rank.score, \
            rank.probability
        if self.min_probability is not None:
            matched = leader if lp >= self.min_probability else None
        else:
            matched = leader if ls >= self.threshold else None
        cfg = self.db.best_config(matched) \
            if self.db is not None and matched is not None else None
        decision = TuneDecision(
            workload=job_id, matched=matched, corr=ls, config=cfg,
            scores=dict(zip(self._workloads, rank.scores.tolist())),
            fraction_seen=1.0, final=True,
            decided_at_fraction=(early.decided_at_fraction
                                 if early is not None else 1.0),
            probability=lp)
        if self.db is not None:
            self.db.record_decision(decision)
        return decision

    def _drain_tick_for(self, finishing) -> None:
        """Flush buffered samples before a verdict (ONE tick covering
        every live job) and park early decisions emitted for jobs that
        are NOT being finished, so they surface from the next tick().
        Internal ticks never advance the overload ladder
        (``_observe=False``): only top-level tick commands are journaled
        with a latency, so replay could not reproduce an observation
        made here."""
        if any(self._front.has_data(j) for j in finishing):
            emitted = self.tick(_observe=False)
            for jid, d in emitted.items():
                if jid not in finishing and d is not None:
                    self._undelivered[jid] = d

    def _retire(self, job_id: str):
        """Free a job's slot, returning its (full query, per-sample
        variances or None, early decision).  A parked early decision
        must not outlive the job (the id is reusable), so it is purged
        here."""
        job = self._jobs.pop(job_id)
        self._undelivered.pop(job_id, None)
        self._sched.release(job_id)
        self._front.retire(job_id)
        vx = job.vx.view() if self.min_probability is not None else None
        return job.x.view(), vx, job.early

    def finish(self, job_id: str) -> TuneDecision:
        """Final verdict for a completed job, recomputed offline from the
        full streamed (causally filtered) query by the matrix-free
        closed-end scorer.  Frees the slot and, when a ReferenceDB backs
        the service, records the decision history.  For many jobs ending
        together prefer :meth:`finish_many` (or the
        :meth:`finish_later` drain queue): the verdict dispatch amortizes
        across jobs instead of growing 1:1 with completions."""
        return self.finish_many((job_id,))[job_id]

    def finish_many(self, job_ids) -> Dict[str, TuneDecision]:
        """Final verdicts for several completed jobs — ONE buffer-drain
        tick plus ONE batched offline scoring dispatch
        (``offline_dispatch_count`` grows per *drain*, not per job), each
        decision identical to what a sequential :meth:`finish` would have
        rendered."""
        ids = list(job_ids)
        if len(set(ids)) != len(ids):
            raise ValueError("duplicate job ids in finish_many")
        missing = [j for j in ids if j not in self._jobs]
        if missing:
            raise KeyError(f"unknown job(s): {missing}")
        if not ids:
            return {}
        with TraceAnnotation("tuner.finish_many", jobs=len(ids)):
            self._drain_tick_for(set(ids))
            with TraceAnnotation("tuner.retire", jobs=len(ids)):
                retired = [self._retire(j) for j in ids]
            sims, probs = self._verdict_scores([x for x, _, _ in retired],
                                               [v for _, v, _ in retired])
            return self._render_verdicts(ids, sims, probs,
                                         [early for _, _, early in retired])

    def finish_later(self, job_id: str) -> None:
        """Deferred finish: the job leaves its slot now (so slots
        recycle), but its verdict joins the drain queue and is rendered
        by the next :meth:`drain_finishes` — or automatically once
        ``finish_batch`` verdicts are pending — in one batched dispatch
        with the others.

        Job ids are reusable once retired, but a pending verdict claims
        the id until it is delivered: deferring a reused id while its
        predecessor's verdict is still undelivered would silently drop
        one of the two decisions (they are keyed by id), so that is
        refused — drain first.
        """
        if any(jid == job_id for jid, *_ in self._finish_queue) \
                or job_id in self._finished:
            raise ValueError(
                f"a verdict for job {job_id!r} is already pending "
                "delivery; drain_finishes() before deferring a reused id")
        self._drain_tick_for({job_id})
        with TraceAnnotation("tuner.retire", jobs=1):
            x, vx, early = self._retire(job_id)
        self._finish_queue.append((job_id, x, vx, early))
        if len(self._finish_queue) >= self.finish_batch:
            self._finished.update(self._drain_queue())

    def _drain_queue(self) -> Dict[str, TuneDecision]:
        if not self._finish_queue:
            return {}
        queued, self._finish_queue = self._finish_queue, []
        with TraceAnnotation("tuner.finish_many", jobs=len(queued)):
            sims, probs = self._verdict_scores(
                [x for _, x, _, _ in queued], [v for _, _, v, _ in queued])
            return self._render_verdicts([jid for jid, *_ in queued], sims,
                                         probs, [e for *_, e in queued])

    def drain_finishes(self) -> Dict[str, TuneDecision]:
        """Render every deferred verdict (one batched dispatch), plus any
        decisions an automatic drain already rendered but has not yet
        delivered."""
        out = self._finished
        self._finished = {}
        out.update(self._drain_queue())
        return out

    @property
    def pending_finishes(self) -> int:
        """Verdicts owed to the caller: queued by :meth:`finish_later`
        and not yet rendered, PLUS auto-drained decisions not yet
        delivered — ``if svc.pending_finishes: svc.drain_finishes()`` is
        the intended polling idiom and must not skip either kind."""
        return len(self._finish_queue) + len(self._finished)


class MultiTenantTuningService:
    """Continuous-batching front over per-tenant reference banks.

    ``banks`` maps tenant name -> :class:`ReferenceDB` or
    :class:`SeriesBank`; each tenant gets an isolated
    :class:`TuningService` engine (its own bank, device state, cohorts
    and counters) built with the shared ``**engine_kwargs``.  Jobs are
    keyed to a tenant at :meth:`submit` and routed by job id afterwards
    — ids are unique across the front, so ``push``/``finish`` need no
    tenant argument.  A :meth:`tick` drains every engine (each engine
    dispatches only when one of its due jobs has data), so total device
    dispatches are bounded by data-ticks x tenants, and by data-ticks x
    cohorts within each engine when tick rates are declared.
    """

    def __init__(self, banks: Mapping[str, Union[ReferenceDB, SeriesBank]],
                 **engine_kwargs) -> None:
        if not banks:
            raise ValueError("no tenants")
        self._engines: Dict[str, TuningService] = {
            t: TuningService(bank, **engine_kwargs)
            for t, bank in banks.items()}
        self._tenant_of: Dict[str, str] = {}

    # -- routing --------------------------------------------------------------
    def engine(self, tenant: str) -> TuningService:
        """The tenant's tick engine (for counters/diagnostics)."""
        return self._engines[tenant]

    @property
    def tenants(self) -> Tuple[str, ...]:
        return tuple(self._engines)

    @property
    def n_active(self) -> int:
        return sum(e.n_active for e in self._engines.values())

    @property
    def dispatch_count(self) -> int:
        return sum(e.dispatch_count for e in self._engines.values())

    @property
    def offline_dispatch_count(self) -> int:
        return sum(e.offline_dispatch_count for e in self._engines.values())

    @property
    def quarantined(self) -> Dict[str, str]:
        """{job_id: poison reason} across every tenant engine."""
        out: Dict[str, str] = {}
        for e in self._engines.values():
            out.update(e.quarantined)
        return out

    def _engine_of(self, job_id: str) -> TuningService:
        return self._engines[self._tenant_of[job_id]]

    # -- lifecycle ------------------------------------------------------------
    def submit(self, job_id: str, expected_len: int, *, tenant: str,
               tick_hz: Optional[float] = None,
               qos: str = "silver") -> InFlightJob:
        if tenant not in self._engines:
            raise KeyError(f"unknown tenant {tenant!r}")
        if job_id in self._tenant_of:
            raise ValueError(f"job {job_id!r} already in flight "
                             f"(tenant {self._tenant_of[job_id]!r})")
        job = self._engines[tenant].submit(job_id, expected_len,
                                           tick_hz=tick_hz, qos=qos)
        self._tenant_of[job_id] = tenant
        return job

    def push(self, job_id: str, samples,
             variance: Optional[np.ndarray] = None,
             now: Optional[float] = None) -> None:
        self._engine_of(job_id).push(job_id, samples, variance=variance,
                                     now=now)

    def tick(self, now: Optional[float] = None
             ) -> Dict[str, Optional[TuneDecision]]:
        out: Dict[str, Optional[TuneDecision]] = {}
        for engine in self._engines.values():
            out.update(engine.tick(now=now))
        return out

    def finish(self, job_id: str) -> TuneDecision:
        return self.finish_many((job_id,))[job_id]

    def finish_many(self, job_ids) -> Dict[str, TuneDecision]:
        """Batched verdicts, grouped per tenant: one drain tick + one
        offline dispatch per tenant with completing jobs."""
        ids = list(job_ids)
        if len(set(ids)) != len(ids):
            raise ValueError("duplicate job ids in finish_many")
        missing = [j for j in ids if j not in self._tenant_of]
        if missing:
            raise KeyError(f"unknown job(s): {missing}")
        by_tenant: Dict[str, List[str]] = {}
        for jid in ids:
            by_tenant.setdefault(self._tenant_of[jid], []).append(jid)
        out: Dict[str, TuneDecision] = {}
        for tenant, group in by_tenant.items():
            out.update(self._engines[tenant].finish_many(group))
            for jid in group:
                del self._tenant_of[jid]
        return out

    def finish_later(self, job_id: str) -> None:
        self._engine_of(job_id).finish_later(job_id)
        del self._tenant_of[job_id]

    def drain_finishes(self) -> Dict[str, TuneDecision]:
        out: Dict[str, TuneDecision] = {}
        for engine in self._engines.values():
            out.update(engine.drain_finishes())
        return out

    @property
    def pending_finishes(self) -> int:
        return sum(e.pending_finishes for e in self._engines.values())

    def sweep_stalled(self, now: float) -> Dict[str, Optional[TuneDecision]]:
        out: Dict[str, Optional[TuneDecision]] = {}
        for engine in self._engines.values():
            evicted = engine.sweep_stalled(now)
            for jid in evicted:
                self._tenant_of.pop(jid, None)
            out.update(evicted)
        return out
