"""Per-flight execution telemetry.

Every batched flight — ``BatchEngine``, ``ShardedBatchEngine``,
``LatticeShardedEngine``, whether spawned by ``optimize_many`` or the
streaming service — can be summarized as one :class:`FlightTelemetry`
record: how many lanes the device actually evaluated, how full the
dispatched chunks were, how long the flight took, whether it retraced,
and what total plan cost it produced.  The record is pure host
bookkeeping assembled *after* the flight from counters the engines
already maintain (plus a ``chunks_dispatched`` tally incremented once
per chunk dispatch), so capturing it cannot perturb costs, plans, or
lane counters — which is what lets ``core.service`` attach telemetry to
every ``FlightReport`` unconditionally, policy learning on or off.

A query the batched engines cannot take runs alone on the solo
``engine.ExactEngine``; :func:`capture_solo` folds each such run into a
record of its own, ``queries=1`` and ``solo`` set, from the counters on
its result: the lane slots the connected-set filter launched, the sets it
found and the bytes of the dense memo.

Records feed :class:`repro.core.policy.PolicyTable`, which EMA-learns
per-(NMAX bucket, lane space) execution profiles, and the daemon's
STATS reply, which aggregates them across requests.  See
``docs/telemetry.md`` for the schema and the bench gates built on it.

:func:`span` marks where the host time goes: a named span on the JAX
profiler's host timeline, the clock the device events share.  The names
are ``<layer>.<step>`` (``daemon.job``, ``service.admit``,
``level.fetch``, ``uniondp.reopt``; the list is in ``docs/telemetry.md``).
:func:`fetch` is the level loops' one blocking device-to-host copy, under
``level.fetch``.
"""
from __future__ import annotations

import dataclasses

import jax
from jax.profiler import TraceAnnotation


def span(name: str, **meta) -> TraceAnnotation:
    """Context manager: one host span named ``name`` with ``meta`` as its
    arguments, kept by the profiler when a trace runs (``jax.profiler``).
    With no trace running it costs one object and a flag test."""
    return TraceAnnotation(name, **meta)


def fetch(tree):
    """Blocking device-to-host copy of ``tree`` (an array or a tuple of
    them) under a ``level.fetch`` span: one host round trip of a level
    loop."""
    with span("level.fetch"):
        return jax.device_get(tree)


# launches dispatched ahead of one fetch of all their outputs: a blocking
# round trip costs a v5e's host several ms, as much as a chunk's work, and
# this many chunks' outputs stay small beside the memo
FETCH_WAVE = 256


def fetch_in_waves(launch, items):
    """``launch(item)`` gives ``(meta, device outputs)``; dispatches them
    ``FETCH_WAVE`` at a time and yields ``(meta, fetched outputs)`` in
    order, one ``fetch`` a wave."""
    wave = []
    for item in items:
        wave.append(launch(item))
        if len(wave) == FETCH_WAVE:
            yield from zip([m for m, _ in wave], fetch([o for _, o in wave]))
            wave = []
    if wave:
        yield from zip([m for m, _ in wave], fetch([o for _, o in wave]))


@dataclasses.dataclass
class FlightTelemetry:
    """One flight's execution profile.  All fields are plain host scalars."""
    nmax: int                 # bucket the flight was admitted under
    space: str                # lane space actually executed (post-policy)
    queries: int              # real (non-padding) queries in the flight
    lattice: bool = False     # intra-query lattice-sharded flight
    evaluated_lanes: int = 0  # lanes surviving the CCP filter (device work)
    ccp_lanes: int = 0        # raw candidate lanes before filtering
    chunk: int = 0            # chunk size the flight ran with
    chunks: int = 0           # chunk dispatches across all levels/stages
    blocks_sets: int = 0      # sets given to phase A's block finding
    blocks_slots: int = 0     # set slots its launches held (>= blocks_sets)
    solo: bool = False        # one query run alone on the solo engine
    filter_lanes: int = 0     # lane slots the set filter launched (padded)
    filter_sets: int = 0      # connected sets (>= 2 relations) it found
    memo_bytes: int = 0       # bytes of the dense memo tables
    retraces: int = 0         # executable-cache retraces charged to the flight
    result_cost: float = 0.0  # sum of final plan costs (f32 exact-min costs)
    wall_s: float = 0.0       # run_levels wall (service: stamped in _finalize)
    finalize_s: float = 0.0   # host collect/cache wall (service only)

    @property
    def occupancy(self) -> float:
        """Fraction of dispatched lane slots that held real work."""
        denom = self.chunks * self.chunk
        return self.evaluated_lanes / denom if denom else 0.0

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["occupancy"] = self.occupancy
        return d


def capture(eng, results, *, nmax: int, queries: int, lattice: bool = False,
            wall_s: float = 0.0, finalize_s: float = 0.0) -> FlightTelemetry:
    """Build a :class:`FlightTelemetry` from a finished engine.

    ``eng`` is any engine exposing ``algorithm``, ``chunk``, ``counters``
    (list of per-graph ``Counters``), ``chunks_dispatched``,
    ``blocks_sets``, ``blocks_slots``, and ``stats``
    (the ``exec_cache.stats_for`` dict); ``results`` the collected
    ``PlanResult`` list (only ``.cost`` is read).  Missing attributes
    record as zeros so stand-in engines (service test spies) still
    produce a well-formed record.
    """
    counters = getattr(eng, "counters", None) or ()
    evaluated = sum(int(c.evaluated) for c in counters)
    ccp = sum(int(c.ccp) for c in counters)
    stats = getattr(eng, "stats", None) or {}
    return FlightTelemetry(
        nmax=int(nmax),
        space=str(getattr(eng, "algorithm", "?")),
        queries=int(queries),
        lattice=bool(lattice),
        evaluated_lanes=evaluated,
        ccp_lanes=ccp,
        chunk=int(getattr(eng, "chunk", 0) or 0),
        chunks=int(getattr(eng, "chunks_dispatched", 0)),
        blocks_sets=int(getattr(eng, "blocks_sets", 0)),
        blocks_slots=int(getattr(eng, "blocks_slots", 0)),
        retraces=int(stats.get("retraces", 0)),
        result_cost=float(sum(float(r.cost) for r in results)),
        wall_s=float(wall_s),
        finalize_s=float(finalize_s),
    )


def capture_solo(result, *, nmax: int, wall_s: float = 0.0
                 ) -> FlightTelemetry:
    """A :class:`FlightTelemetry` record of one solo run, from the
    ``Counters`` on its ``OptimizeResult``."""
    c = result.counters
    return FlightTelemetry(
        nmax=int(nmax), space=str(result.algorithm), queries=1, solo=True,
        evaluated_lanes=int(c.evaluated), ccp_lanes=int(c.ccp),
        filter_lanes=int(c.filter_lanes), filter_sets=int(c.filter_sets),
        memo_bytes=int(c.memo_bytes), result_cost=float(result.cost),
        wall_s=float(wall_s))


def aggregate(records) -> dict:
    """Fold an iterable of telemetry records into one summary dict: the
    flights' records into the flight keys, the solo runs' into the
    ``solo_*`` keys (``solo_memo_bytes`` the largest memo, the rest sums).

    ``None`` entries are skipped so callers can pass
    ``[fl.telemetry for fl in report.flights]`` without filtering.
    """
    recs = [r for r in records if r is not None]
    solo = [r for r in recs if r.solo]
    recs = [r for r in recs if not r.solo]
    out = {
        "flights": len(recs),
        "queries": sum(r.queries for r in recs),
        "evaluated_lanes": sum(r.evaluated_lanes for r in recs),
        "ccp_lanes": sum(r.ccp_lanes for r in recs),
        "chunks": sum(r.chunks for r in recs),
        "blocks_sets": sum(r.blocks_sets for r in recs),
        "blocks_slots": sum(r.blocks_slots for r in recs),
        "retraces": sum(r.retraces for r in recs),
        "result_cost": float(sum(r.result_cost for r in recs)),
        "wall_s": float(sum(r.wall_s for r in recs)),
        "solo_queries": len(solo),
        "solo_filter_lanes": sum(r.filter_lanes for r in solo),
        "solo_filter_sets": sum(r.filter_sets for r in solo),
        "solo_memo_bytes": max((r.memo_bytes for r in solo), default=0),
    }
    slots = sum(r.chunks * r.chunk for r in recs)
    out["occupancy"] = (out["evaluated_lanes"] / slots) if slots else 0.0
    return out


# summary keys that roll up as the largest value seen, not as a sum
LARGEST = frozenset({"solo_memo_bytes"})


def roll_up(total: dict, summary: dict) -> None:
    """Add one :func:`aggregate` summary into a running ``total`` (its keys
    only): sums, and the largest value for the :data:`LARGEST` keys."""
    for k in total:
        v = int(summary.get(k, 0))
        total[k] = max(total[k], v) if k in LARGEST else total[k] + v
