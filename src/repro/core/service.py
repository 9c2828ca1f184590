"""Streaming query service core: admission control + pipelined flights.

``optimize_many`` batches a *closed* list of queries; a service sees an open
stream and has to decide, per query, which device pass to ride.  This module
adds that layer:

  * **admission control** — incoming queries are grouped into *flights* by
    ``(NMAX bucket, lane space)``: only queries sharing a memo shape and an
    evaluate decode can fuse into one batched pass, so the admission key is
    exactly the executable-cache key prefix.  Flights are capped at
    ``max_flight`` queries per shard (the ``BatchEngine`` sub-batch bound),
    and repeated flight shapes hit the process-wide executable cache with
    zero retraces.
  * **flight pipelining** — flight i's host-only finalize (memo fetch, plan
    extraction, cache insertion, latency bookkeeping) is *deferred* until
    after flight i+1's levels are dispatched (``run_levels``), so it
    overlaps flight i+1's trailing device work; inside each flight the
    engines additionally run their own level pipeline when ``pipeline`` is
    on (host compaction of level k+1 under device evaluate of level k).
  * **plan cache** — probed before admission (hits never spawn an engine),
    with intra-stream dedup of canonically-equal queries, exactly like
    ``optimize_many``; computed plans are inserted at flight finalize.

**Flight lifecycle.**  Every admitted flight moves through four states,
and the double-buffered stream loop interleaves them across flights:

    admitted   bucket_pending grouped it; FlightReport created with its
               (NMAX, space) key and member stream indices
    dispatched _spawn built the (Sharded)BatchEngine and called
               run_levels(): all DP levels are dispatched; trailing
               evaluate chunks may still be executing on the device
    finalized  _finalize called collect(): host-only memo fetch, plan
               extraction, plan-cache insertion, latency stamping — runs
               while the NEXT flight's device work is in flight
    reported   appended to StreamReport.flights with wall_s (dispatch ->
               finalize done) and finalize_s (the overlappable share)

Solo queries (bucket rejects: n > NMAX cap, exotic statics) fall back to
per-query ``engine.optimize`` after all flights land, each under a
``service.solo`` span and recorded as a solo telemetry record
(``StreamReport.solo_telemetry``); deferred duplicates resolve last, off
the canonical results (``resolve_deferred``).  With a
mesh, oversized-but-exact-eligible queries (``nmax_bucket(n) > NMAX_BATCH``,
``n <= lattice.NMAX_LATTICE``) are instead admitted as single-query
**lattice flights** (``lattice.LatticeShardedEngine``: the one query's lane
space sharded over the mesh) — they ride the same flight lifecycle, marked
``FlightReport.lattice`` and counted in ``StreamReport.lattice``, so big
queries stop falling out of the exact path entirely.

Results are bit-identical to ``optimize_many`` over the same stream by
construction: the probe/dedup/bucket stages are the *same functions*
(``batch.probe_stream``/``dedup_pending``/``bucket_pending``/
``resolve_deferred``), and each flight runs the same engines on the same
sub-batches — only the finalize timing differs.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from . import bitset as bs
from . import faults
from . import telemetry as _telemetry
from .telemetry import span
from .batch import (PEND_WINDOW, BatchEngine, bucket_pending, dedup_pending,
                    lattice_pending, probe_stream, resolve_deferred)
from .config import UNSET, OptimizerConfig, resolve_config
from .joingraph import JoinGraph
from .plan import OptimizeResult


@dataclasses.dataclass
class FlightReport:
    """One admitted flight: its admission key, members and measured times."""
    nmax: int
    space: str
    queries: list[int]             # stream indices, admission order
    lattice: bool = False          # single-query intra-query lattice flight
    wall_s: float = 0.0            # run_levels dispatch -> finalize done
    finalize_s: float = 0.0        # host-only finalize share (overlappable)
    # execution profile captured at finalize (telemetry.FlightTelemetry);
    # ``space`` above is the ADMISSION space, ``telemetry.space`` the lane
    # space actually executed (they differ only under a learned policy)
    telemetry: object | None = None

    @property
    def key(self) -> tuple[int, str]:
        return (self.nmax, self.space)


@dataclasses.dataclass
class StreamReport:
    """Whole-stream accounting returned next to the results."""
    flights: list[FlightReport] = dataclasses.field(default_factory=list)
    latency_s: list[float] = dataclasses.field(default_factory=list)
    wall_s: float = 0.0
    cache_hits: int = 0
    solo: int = 0                  # queries that fell back to per-query runs
    lattice: int = 0               # finalized intra-query lattice flights
    # one telemetry.FlightTelemetry record (``solo`` set) per solo run
    solo_telemetry: list = dataclasses.field(default_factory=list)

    def latency_percentiles(self, ps=(50, 95, 99)) -> dict[int, float]:
        if not self.latency_s:
            return {p: 0.0 for p in ps}
        xs = np.asarray(self.latency_s, np.float64)
        return {p: float(np.percentile(xs, p)) for p in ps}

    def telemetry_summary(self) -> dict:
        """Stream-wide roll-up of the per-flight and solo telemetry
        records."""
        return _telemetry.aggregate(
            [fl.telemetry for fl in self.flights] + self.solo_telemetry)


class StreamOptimizer:
    """Admission-controlled, flight-pipelined optimizer for query streams.

    Parameters mirror ``optimize_many``; ``max_flight`` is the per-shard
    flight size cap (multiplied by the mesh size when sharding).  All knobs
    can be passed as one ``config=OptimizerConfig(...)`` instead of the
    legacy kwargs (never both); the resolved config is kept on
    ``self.config`` — the daemon (``repro.daemon``) builds one
    ``StreamOptimizer`` per request from the wire config this way.
    """

    def __init__(self, algorithm=UNSET, chunk=UNSET, cache=UNSET,
                 devices=UNSET, mesh=UNSET, pipeline=UNSET, max_flight=UNSET,
                 policy=UNSET, *, config: OptimizerConfig | None = None):
        cfg = resolve_config(config, algorithm=algorithm, chunk=chunk,
                             cache=cache, devices=devices, mesh=mesh,
                             pipeline=pipeline, max_flight=max_flight,
                             policy=policy)
        self.config = cfg
        self.algorithm = cfg.algorithm
        self.chunk = cfg.chunk
        self.cache = cfg.cache
        self.pipeline = cfg.pipeline
        self.max_flight = cfg.max_flight
        # learned policies steer only the auto dispatcher (an explicit lane
        # space is a user decision); flights record telemetry either way
        self.policy = (cfg.policy
                       if cfg.algorithm in ("auto", "mpdp") else None)
        self.mesh = None
        if cfg.mesh is not None or cfg.devices is not None:
            from . import shard as _shard
            self.mesh = _shard.batch_mesh(
                cfg.mesh if cfg.mesh is not None else cfg.devices)
        # armed per-stream: absolute expiry shared by every flight/solo so
        # the whole stream answers within ~cfg.deadline_s (anytime results)
        self._deadline_at: float | None = None

    def _left(self) -> float | None:
        """Remaining stream budget (None when no deadline is armed)."""
        if self._deadline_at is None:
            return None
        return max(self._deadline_at - faults.now(), 1e-9)

    # -------------------------------------------------------- admission ----
    def admit(self, graphs: list[JoinGraph], idxs: list[int]
              ) -> tuple[list[FlightReport], list[int]]:
        """Group ``idxs`` into (NMAX bucket, lane space) flights — the
        shared ``batch.bucket_pending`` grouping, split at the flight cap;
        ungroupable queries come back as the solo list.  With a mesh,
        oversized exact-eligible queries become single-query lattice
        flights instead of solos (``batch.lattice_pending``)."""
        buckets, solo = bucket_pending(graphs, idxs, self.algorithm)
        step = self.max_flight
        latt: list[tuple[int, str]] = []
        if self.mesh is not None:
            from . import shard as _shard
            step *= _shard.mesh_size(self.mesh)
            latt, solo = lattice_pending(graphs, solo, self.algorithm)
        flights = [FlightReport(b, space, idxs_b[s0: s0 + step])
                   for (b, space, _typed), idxs_b in sorted(buckets.items())
                   for s0 in range(0, len(idxs_b), step)]
        if latt:
            from .lattice import lattice_bucket
            flights += [FlightReport(lattice_bucket(graphs[qi].n), space,
                                     [qi], lattice=True)
                        for qi, space in latt]
        return flights, solo

    def _spawn(self, graphs: list[JoinGraph], fl: FlightReport):
        """Build the flight's engine and dispatch its level loop.  With a
        policy table the batched paths run under its learned lane-space /
        chunk / drain-window decision (``fl.space`` stays the admission
        space; the executed space lands in ``fl.telemetry``)."""
        members = [graphs[qi] for qi in fl.queries]
        space, chunk, kw = fl.space, self.chunk, {}
        if self.policy is not None and not fl.lattice:
            dec = self.policy.choose(fl.nmax, fl.space,
                                     default_chunk=self.chunk,
                                     default_pend=PEND_WINDOW)
            if dec.space is not None:
                space = dec.space
            if dec.chunk is not None:
                chunk = dec.chunk
            if dec.pend_window is not None:
                kw["pend_window"] = dec.pend_window
        if fl.lattice:
            from .lattice import LatticeShardedEngine
            eng = LatticeShardedEngine(members[0], self.mesh,
                                       chunk=self.chunk, algorithm=fl.space,
                                       pipeline=self.pipeline,
                                       deadline_s=self._left())
        elif self.mesh is None:
            eng = BatchEngine(members, chunk=chunk, algorithm=space,
                              pipeline=self.pipeline,
                              deadline_s=self._left(), **kw)
        else:
            from . import shard as _shard
            eng = _shard.ShardedBatchEngine(members, self.mesh,
                                            chunk=chunk,
                                            algorithm=space,
                                            pipeline=self.pipeline,
                                            deadline_s=self._left(), **kw)
            try:
                eng.run_levels()
            except _shard.REDISPATCH_ERRORS as e:
                # device-runtime failure: re-dispatch the whole flight on
                # the degenerate single-device path (same members, same
                # space — bit-identical costs), flag it at finalize
                _shard.log_redispatch(e, len(members))
                eng = BatchEngine(members, chunk=chunk, algorithm=space,
                                  pipeline=self.pipeline,
                                  deadline_s=self._left(), **kw)
                eng.run_levels()
                eng.redispatched = True
            return eng
        eng.run_levels()
        return eng

    def _finalize(self, graphs, fl: FlightReport, eng, t_flight, t_stream,
                  results, report) -> None:
        """Host-only flight finalize: fetch + extract + cache insert.  Runs
        while the *next* flight's trailing device work is still in flight."""
        t0 = time.perf_counter()
        with span("engine.collect"):
            collected = eng.collect()
        for qi, r in zip(fl.queries, collected):
            if getattr(eng, "redispatched", False):
                r.info["redispatched"] = True
            results[qi] = r
            # degraded (deadline-stitched) plans are best-effort — never
            # cached, so a later unhurried run recomputes the exact plan
            if self.cache is not None and "degraded" not in r.info:
                self.cache.put(graphs[qi], r)
        done = time.perf_counter()
        fl.finalize_s = done - t0
        fl.wall_s = done - t_flight
        # telemetry is pure host bookkeeping over counters the engine
        # already kept — recorded unconditionally, policy on or off
        fl.telemetry = _telemetry.capture(
            eng, collected, nmax=fl.nmax, queries=len(fl.queries),
            lattice=fl.lattice, wall_s=fl.wall_s, finalize_s=fl.finalize_s)
        if self.policy is not None and not fl.lattice:
            self.policy.observe(fl.nmax, fl.space, eng.algorithm,
                                fl.telemetry)
        for qi in fl.queries:
            report.latency_s[qi] = done - t_stream
        if fl.lattice:
            report.lattice += 1
        report.flights.append(fl)

    # ------------------------------------------------------------ stream ---
    def optimize_stream(self, graphs: list[JoinGraph]
                        ) -> tuple[list[OptimizeResult], StreamReport]:
        """Optimize the stream; returns results in stream order plus the
        flight/latency report.  Costs are bit-identical to
        ``optimize_many`` over the same list."""
        from . import engine as _eng
        t_stream = time.perf_counter()
        self._deadline_at = (None if self.config.deadline_s is None
                             else faults.now() + self.config.deadline_s)
        report = StreamReport(latency_s=[0.0] * len(graphs))
        results: list[OptimizeResult | None] = [None] * len(graphs)
        # same probe/dedup stages as optimize_many (shared helpers)
        with span("service.admit"):
            pending = probe_stream(graphs, results, self.cache,
                                   self.algorithm)
            for qi, r in enumerate(results):
                if r is not None:
                    report.latency_s[qi] = time.perf_counter() - t_stream
                    if r.algorithm.startswith("cache["):
                        report.cache_hits += 1
            pending, deferred, dup_rep = dedup_pending(graphs, pending,
                                                       self.cache)
            flights, solo = self.admit(graphs, pending)
        report.solo = len(solo)

        # double-buffered flight loop: finalize of flight i happens after
        # flight i+1's levels have been dispatched
        prev = None                        # (flight, engine, t_flight)
        for fl in flights:
            t_flight = time.perf_counter()
            eng = self._spawn(graphs, fl)
            if prev is not None:
                with span("service.finalize"):
                    self._finalize(graphs, *prev, t_stream, results, report)
            prev = (fl, eng, t_flight)
        if prev is not None:
            with span("service.finalize"):
                self._finalize(graphs, *prev, t_stream, results, report)

        for qi in solo:
            n = graphs[qi].n
            nmax = bs.nmax_bucket(n)  # the bucket admission refused it at
            t_solo = time.perf_counter()
            with span("service.solo", n=n, nmax=nmax):
                if self.config.deadline_s is None:
                    r = _eng.optimize(graphs[qi], self.algorithm,
                                      chunk=self.chunk)
                else:
                    r = _eng.optimize(graphs[qi], config=OptimizerConfig(
                        algorithm=self.algorithm, chunk=self.chunk,
                        deadline_s=self._left()))
            report.solo_telemetry.append(_telemetry.capture_solo(
                r, nmax=nmax, wall_s=time.perf_counter() - t_solo))
            results[qi] = r
            report.latency_s[qi] = time.perf_counter() - t_stream
            if self.cache is not None and "degraded" not in r.info:
                self.cache.put(graphs[qi], r)
        resolve_deferred(graphs, results, self.cache, deferred, dup_rep)
        for qi in deferred:
            report.latency_s[qi] = time.perf_counter() - t_stream
            report.cache_hits += 1
        report.wall_s = time.perf_counter() - t_stream
        return results, report


def optimize_stream(graphs: list[JoinGraph], algorithm=UNSET, chunk=UNSET,
                    cache=UNSET, devices=UNSET, mesh=UNSET, pipeline=UNSET,
                    max_flight=UNSET, policy=UNSET, *,
                    config: OptimizerConfig | None = None
                    ) -> tuple[list[OptimizeResult], StreamReport]:
    """One-shot convenience wrapper around ``StreamOptimizer``."""
    cfg = resolve_config(config, algorithm=algorithm, chunk=chunk,
                         cache=cache, devices=devices, mesh=mesh,
                         pipeline=pipeline, max_flight=max_flight,
                         policy=policy)
    return StreamOptimizer(config=cfg).optimize_stream(graphs)
