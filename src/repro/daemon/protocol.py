"""Daemon wire protocol: length-prefixed JSON frames, pure-literal codecs.

**Framing.**  Every message is one frame: a 4-byte big-endian unsigned
length followed by that many bytes of UTF-8 JSON.  Frames are capped at
``MAX_FRAME`` (a malformed or hostile length prefix must not allocate
gigabytes); a peer that closes mid-frame raises ``ProtocolError``, a close
*between* frames is a clean EOF (``recv_msg`` returns ``None``).

**Literal discipline.**  The payloads are JSON only — the same pickle-free
stance as ``PlanCache.save``: a hostile client (or a tampered socket) can
produce garbage, never code execution.  Graphs cross the wire as their
log2 statistics (f32 -> f64 -> shortest-repr JSON -> f64 -> f32 is exact,
so graph round-trips are bit-identical); plans cross as their *shape* only
(nested [left, right] lists over leaf bitmaps, exactly the
``plancache._encode_plan`` form) and are re-costed canonically on the
receiving side's graph — the same discipline as a plan-cache hit.  The
``OptimizeResult.cost`` crosses as the f32-exact float computed by the
server's engines, so daemon results compare bit-identical to in-process
``optimize_many``.

**Requests** (``op`` selects; all other fields per op):

  optimize   {"op": "optimize", "tenant": str, "config": <to_wire dict>,
              "graphs": [<graph wire>, ...]}
  stats      {"op": "stats"}
  ping       {"op": "ping"}
  drain      {"op": "drain"}        # graceful shutdown request
  trace      {"op": "trace", "dir": str, "seconds": float}
                                    # profile the daemon into dir

**Responses**: ``{"ok": true, ...}`` on success; ``{"ok": false,
"shed": true, "reason": ...}`` when admission control rejects (queue or
per-tenant saturation — the client should back off and retry);
``{"ok": false, "error": ...}`` on a request-level error (the connection
stays usable).
"""
from __future__ import annotations

import json
import math
import socket
import struct

from ..core import faults

MAX_FRAME = 64 << 20     # 64 MiB: a ~1000-relation heuristic-tier graph is
                         # a few hundred KiB; anything near this is garbage

_LEN = struct.Struct(">I")


class ProtocolError(ConnectionError):
    """Malformed frame: oversized length prefix or EOF mid-frame."""


class FrameTimeout(ProtocolError):
    """The peer stalled mid-frame past the socket's receive deadline.

    Distinct from a bare ``socket.timeout`` so callers can tell a stalled
    *daemon* (retryable with a fresh connection) from their own misuse;
    subclassing ``ProtocolError`` keeps every existing handler working.
    """


def send_msg(sock: socket.socket, obj) -> None:
    """Serialize ``obj`` to one length-prefixed JSON frame and send it."""
    data = json.dumps(obj, separators=(",", ":")).encode()
    if len(data) > MAX_FRAME:
        raise ProtocolError(f"frame too large: {len(data)} > {MAX_FRAME}")
    buf = _LEN.pack(len(data)) + data
    if faults.active():
        rule = faults.check("socket_send")
        if rule is not None and rule.action == "stall":
            # injected mid-frame stall: half the frame, a pause, the rest —
            # the peer's recv deadline (FrameTimeout) is what's under test
            mid = max(len(buf) // 2, 1)
            sock.sendall(buf[:mid])
            import time
            time.sleep(rule.delay_s)
            sock.sendall(buf[mid:])
            return
    sock.sendall(buf)


def recv_msg(sock: socket.socket):
    """Receive one frame; ``None`` on clean EOF at a frame boundary."""
    head = _recv_exactly(sock, _LEN.size, eof_ok=True)
    if head is None:
        return None
    (length,) = _LEN.unpack(head)
    if length > MAX_FRAME:
        raise ProtocolError(f"frame too large: {length} > {MAX_FRAME}")
    body = _recv_exactly(sock, length, eof_ok=False)
    return json.loads(body.decode())


def _recv_exactly(sock: socket.socket, n: int, *, eof_ok: bool):
    chunks, got = [], 0
    while got < n:
        try:
            chunk = sock.recv(min(n - got, 1 << 20))
        except TimeoutError as e:
            raise FrameTimeout(
                f"peer stalled mid-frame ({got}/{n} bytes)") from e
        if not chunk:
            if eof_ok and got == 0:
                return None
            raise ProtocolError(f"peer closed mid-frame ({got}/{n} bytes)")
        chunks.append(chunk)
        got += len(chunk)
    return b"".join(chunks)


# ============================================================ graph codec ==

def graph_to_wire(g) -> dict:
    """``JoinGraph`` -> pure literals.  Stats ship in log2 space (the
    internal representation): float(np.float32) widens exactly and JSON's
    shortest-repr floats round-trip f64 exactly, so ``graph_from_wire``
    rebuilds a bit-identical graph.  Typed graphs ship the *raw* per-edge
    selectivities plus ``kinds``/``ldirs`` (effective selectivities are a
    pure f32 function of those and re-derive bit-identically on receive);
    explicit m:n fan-outs ship as ``fans_l2`` (``None`` = derived).  All
    three keys are omitted for plain inner queries, so their wire dicts —
    and every pre-typed client/server pairing — are unchanged."""
    d = {"n": g.n,
         "edges": [[u, v] for (u, v) in g.edges],
         "cards_l2": [float(c) for c in g.log2_card],
         "sels_l2": [float(s) for s in (g.log2_sel_raw if g.typed
                                        else g.log2_sel)],
         "names": list(g.names)}
    if g.typed:
        d["kinds"] = list(g.kinds)
        d["ldirs"] = list(g.ldirs)
    if g.fan_l2 is not None and len(g.fan_l2):
        d["fans_l2"] = [float(f) if math.isfinite(float(f)) else None
                        for f in g.fan_l2]
    return d


def graph_from_wire(d: dict):
    from ..core.joingraph import JoinGraph
    return JoinGraph.from_log2(
        n=int(d["n"]),
        edges=[(int(u), int(v)) for u, v in d["edges"]],
        cards_l2=d["cards_l2"],
        sels_l2=d["sels_l2"],
        names=tuple(d["names"]),
        kinds=[int(k) for k in d.get("kinds", [])],
        ldirs=[int(x) for x in d.get("ldirs", [])],
        fans_l2=d.get("fans_l2"))


# =========================================================== result codec ==

def plan_shape_to_wire(p):
    """Plan tree -> nested [left, right] lists over leaf bitmaps (ints) —
    the JSON twin of ``plancache._encode_plan``."""
    if p.is_leaf:
        return p.rel_set
    return [plan_shape_to_wire(p.left), plan_shape_to_wire(p.right)]


def plan_shape_from_wire(e, g):
    """Rebuild the plan from its wire shape, re-costing canonically on
    ``g``'s exact stats (``cost_plan`` — the plan-cache hit discipline)."""
    from ..core.plan import Plan, cost_plan

    def decode(x):
        if isinstance(x, int):
            return Plan(rel_set=x, cost=0.0, rows_log2=0.0)
        l, r = x
        lp, rp = decode(l), decode(r)
        return Plan(rel_set=lp.rel_set | rp.rel_set, cost=0.0,
                    rows_log2=0.0, left=lp, right=rp)

    return cost_plan(decode(e), g)


def result_to_wire(r) -> dict:
    d = {"cost": float(r.cost),
         "algorithm": r.algorithm,
         "levels": r.levels,
         "wall_s": r.wall_s,
         "evaluated": r.counters.evaluated,
         "ccp": r.counters.ccp,
         "plan": plan_shape_to_wire(r.plan)}
    # degraded metadata (deadline stitch / re-dispatch) is already pure
    # literals — pass it through so clients can see best-effort results
    if "degraded" in r.info:
        d["degraded"] = r.info["degraded"]
    if r.info.get("redispatched"):
        d["redispatched"] = True
    return d


def result_from_wire(d: dict, g):
    from ..core.plan import Counters, OptimizeResult
    r = OptimizeResult(
        plan=plan_shape_from_wire(d["plan"], g),
        cost=d["cost"],
        counters=Counters(evaluated=d["evaluated"], ccp=d["ccp"]),
        algorithm=d["algorithm"],
        wall_s=d["wall_s"],
        levels=d["levels"])
    if "degraded" in d:
        r.info["degraded"] = d["degraded"]
    if d.get("redispatched"):
        r.info["redispatched"] = True
    return r
