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


def aggregate(records) -> dict:
    """Fold an iterable of flight telemetry records into one summary dict.

    ``None`` entries are skipped so callers can pass
    ``[fl.telemetry for fl in report.flights]`` without filtering.
    """
    recs = [r for r in records if r is not None]
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
    }
    slots = sum(r.chunks * r.chunk for r in recs)
    out["occupancy"] = (out["evaluated_lanes"] / slots) if slots else 0.0
    return out
