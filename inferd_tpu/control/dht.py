"""Swarm membership & load store: gossip-replicated, owner-writes-only.

Capability replacement for the reference's Kademlia DHT usage
(/root/reference/petals/kademlia_client.py:9-85; record schema
`str(stage) -> {node_id: {"load": int, "cap": int}}`, task_scheduler.py:32-34),
redesigned around how the records are actually used:

  * every node publishes exactly ONE record — its own membership/load entry —
    and only its owner ever writes it. The reference's read-modify-write of a
    shared per-stage dict raced between nodes (SURVEY B6); here a per-stage
    view is *derived* by merging single-owner records, so clobbering is
    impossible by construction (LWW on (owner, version)).
  * records carry a liveness TTL: a dead node's record expires and routing
    stops picking it (the reference had no TTL — dead nodes lingered).
  * reads (`get_stage`, `get_all`) are local-memory merges — a routing hop
    costs zero network round-trips, vs one Kademlia UDP lookup per hop in
    the reference (path_finder.py:72).
  * transport is msgpack-over-UDP gossip: push own record every period to K
    random peers + full-state answer to HELLO (bootstrap anti-entropy).

The public surface mirrors the reference's DistributedHashTableServer
(start/stop/get/set/get_all) so the rest of the control plane maps 1:1.

Determinism seams (the fleet simulator, inferd_tpu.sim, drives thousands
of these in one process on a virtual clock): `clock` replaces every
time.time() read, `rng` every random draw, and `transport` swaps the UDP
socket for an in-process datagram network — with all three injected, a
SwarmDHT is a pure state machine whose gossip behavior replays
byte-identically under a seed. Production code passes none of them and
gets wall-clock UDP exactly as before.
"""

from __future__ import annotations

import asyncio
import logging
import random
import sys
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import msgpack

log = logging.getLogger(__name__)

DEFAULT_TTL_S = 15.0
GOSSIP_PERIOD_S = 1.0
GOSSIP_FANOUT = 3


def sess_hash(session_id: str) -> str:
    """Short stable hash for gossip session-location advertising (the
    `sess` list in a node's record — see runtime.node._advertised_sessions):
    64 bits keeps the per-node record small (128 sessions ~ 2 KB); a
    collision's worst case is routing a chunk to a replica without the
    session, which 409s into the client's normal restart path. Lives here —
    with the record schema — so jax-free clients can consult the adverts."""
    import hashlib

    return hashlib.blake2b(session_id.encode(), digest_size=8).hexdigest()


class Record:
    """One owner's entry: value + (version, ts) for LWW merge."""

    __slots__ = ("owner", "value", "version", "ts", "addr", "_wire", "_wire_key")

    def __init__(self, owner: str, value: Any, version: int, ts: float, addr: Tuple[str, int]):
        self.owner = owner
        self.value = value
        self.version = version
        self.ts = ts
        self.addr = tuple(addr)
        self._wire: Optional[Dict[str, Any]] = None
        self._wire_key: Tuple[int, float] = (-1, 0.0)

    def refresh_ts(self, ts: float) -> None:
        """Liveness-heartbeat ts update that keeps the wire cache HOT:
        heartbeats touch essentially every record once per gossip period,
        so invalidating the cached dict on each would make the cache miss
        on nearly every serialization round — patch it in place instead."""
        self.ts = ts
        if self._wire is not None:
            self._wire["ts"] = ts
            self._wire_key = (self.version, ts)

    def to_wire(self) -> Dict[str, Any]:
        # cached per (version, ts): full-state gossip re-serializes every
        # record once per send round, and at fleet scale (1000 records x
        # fanout x 1 Hz) rebuilding identical dicts dominated the gossip
        # path. Callers only read the returned dict (msgpack.packb).
        key = (self.version, self.ts)
        if self._wire is None or self._wire_key != key:
            self._wire = {
                "owner": self.owner,
                "value": self.value,
                "version": self.version,
                "ts": self.ts,
                "addr": list(self.addr),
            }
            self._wire_key = key
        return self._wire

    @staticmethod
    def from_wire(d: Dict[str, Any]) -> "Record":
        value = d["value"]
        if isinstance(value, dict):
            # intern the schema keys: a 1000-node swarm fully replicates
            # ~1e6 records, and msgpack allocates a fresh "stage"/"load"/
            # "cap"/... str per unpack — interning collapses the key set
            # to one copy per process (measured: the dominant resident
            # cost of full-state gossip at fleet scale)
            value = {sys.intern(k): v for k, v in value.items()}
        return Record(
            sys.intern(str(d["owner"])), value, int(d["version"]),
            float(d["ts"]), tuple(d["addr"]),
        )


class _Proto(asyncio.DatagramProtocol):
    def __init__(self, store: "SwarmDHT"):
        self.store = store

    def datagram_received(self, data: bytes, addr: Tuple[str, int]) -> None:
        try:
            msg = msgpack.unpackb(data, raw=False)
        except Exception:
            return
        self.store._on_message(msg, addr)


class SwarmDHT:
    """Gossip store. One instance per node process."""

    def __init__(
        self,
        node_id: str,
        port: int,
        bootstrap: Optional[List[Tuple[str, int]]] = None,
        ttl_s: float = DEFAULT_TTL_S,
        gossip_period_s: float = GOSSIP_PERIOD_S,
        host: str = "0.0.0.0",
        clock: Callable[[], float] = time.time,
        rng: Optional[random.Random] = None,
        transport: Optional[Any] = None,
        fanout: int = GOSSIP_FANOUT,
        anti_entropy_every: int = 1,
    ):
        self.node_id = node_id
        self.host = host
        self.port = port
        self.bootstrap = [tuple(b) for b in (bootstrap or [])]
        self.ttl_s = ttl_s
        self.gossip_period_s = gossip_period_s
        # determinism seams (module docstring): wall clock, the process
        # RNG, and the UDP socket unless the caller injects replacements
        self._clock = clock
        self._rng: Any = rng if rng is not None else random
        self._ext_transport = transport
        self.fanout = int(fanout)
        self.anti_entropy_every = max(1, int(anti_entropy_every))
        self._tick_n = 0
        # the owner's hook, called before its own record is READ (merged
        # into a local view, serialised for a peer): a node whose load
        # moves many times between two reads rebuilds its record then,
        # not at every move (runtime.node.Node._refresh_record). None =
        # the record is whatever announce() last left (the simulator, the
        # observers)
        self.before_read: Optional[Callable[[], None]] = None

        self._records: Dict[str, Record] = {}  # owner -> record
        self._own_value: Dict[str, Any] = {}
        self._own_version = 0
        self._peers: Dict[str, Tuple[str, int]] = {}  # owner -> gossip addr
        self._peer_seen: Dict[str, float] = {}  # owner -> last datagram ts
        self._transport: Optional[asyncio.DatagramTransport] = None
        self._gossip_task: Optional[asyncio.Task] = None
        self._started = False

    # ------------------------------------------------------------------ api

    def start_local(self) -> None:
        """Start over an injected in-process transport (the simulator's
        seam): no socket, no asyncio gossip task — the driver
        (inferd_tpu.sim) delivers datagrams straight into _on_message and
        schedules gossip_tick() on its virtual clock. Everything above
        the transport — merge rules, TTL expiry, anti-entropy, pruning —
        is the same code the UDP path runs."""
        if self._ext_transport is None:
            raise RuntimeError("start_local() requires an injected transport")
        self._started = True
        for addr in self.bootstrap:
            self._send({"t": "hello", "from": self.node_id, "port": self.port}, addr)

    async def start(self) -> None:
        if self._ext_transport is not None:
            self.start_local()
            return
        loop = asyncio.get_running_loop()
        self._transport, _ = await loop.create_datagram_endpoint(
            lambda: _Proto(self), local_addr=(self.host, self.port)
        )
        # port 0 = ephemeral bind: adopt the kernel-assigned port so HELLOs
        # advertise a reachable address (and our own record's addr is right)
        self.port = self._transport.get_extra_info("sockname")[1]
        own = self._records.get(self.node_id)
        if own is not None:
            own.addr = (self.host, self.port)
            own._wire = None  # addr isn't part of the wire-cache key
        self._started = True
        for addr in self.bootstrap:
            self._send({"t": "hello", "from": self.node_id, "port": self.port}, addr)
        self._gossip_task = asyncio.create_task(self._gossip_loop())

    async def stop(self) -> None:
        self._started = False
        if self._gossip_task:
            self._gossip_task.cancel()
            try:
                await self._gossip_task
            except asyncio.CancelledError:
                pass
        if self._transport:
            self._transport.close()

    def announce(self, value: Dict[str, Any], urgent: bool = True) -> None:
        """Publish/refresh this node's own record (stage, load, cap, addr...).

        The only write path — a node can never clobber another's record.
        urgent=True gossips immediately (membership changes: join, migrate,
        withdraw); urgent=False only updates the local record and lets the
        periodic gossip loop carry it (keeps full-state serialization +
        UDP fan-out off the request hot path). What moves with every
        request (a node's load) is not announced at all: the owner
        rebuilds its record when it is about to be read (`before_read`).

        The version bumps only when the VALUE changes; re-announcing an
        identical payload is a liveness heartbeat (ts refresh) that peers
        merge in place without materializing a new record — at fleet
        scale the steady state is overwhelmingly heartbeats, and this is
        what keeps a 1000-node swarm's merge cost sub-linear in announce
        rate. The LWW invariant the fuzz suite pins still holds: an
        honest owner never emits two DIFFERENT values under one version.
        The version floor is the epoch MILLISECOND, so a restarted node
        (own counter reset to zero) immediately outranks its pre-restart
        records instead of being ignored until they prune — millisecond
        granularity keeps the floor ahead of the counter for any
        sustained value-change rate under 1000/s (a per-second floor
        lost that race to ordinary per-request load announces).
        """
        now = self._clock()
        cur = self._records.get(self.node_id)
        if (
            cur is not None
            and not self._own_value.get("_tombstone")
            and value == self._own_value
        ):
            cur.refresh_ts(now)
        else:
            self._own_version = max(self._own_version + 1, int(now * 1000.0))
            self._own_value = dict(value)
            self._records[self.node_id] = Record(
                self.node_id, self._own_value, self._own_version, now,
                (self.host, self.port),
            )
        if self._started and urgent:
            self._gossip_now()

    def withdraw(self) -> None:
        """Announce departure (value=None tombstone gossiped immediately)."""
        self.announce({"_tombstone": True})

    def kill(self) -> None:
        """Hard-crash simulation: close the socket with NO tombstone — peers
        only learn of the death when this node's record TTLs out (the path
        real process crashes exercise). Fault-injection/testing hook."""
        self._started = False
        if self._gossip_task:
            self._gossip_task.cancel()
            self._gossip_task = None
        if self._transport:
            self._transport.close()
            self._transport = None

    # -- reads (local, already-merged) ---------------------------------

    def _fresh_own(self) -> None:
        """Let the owner bring its record up to date before a read. Not
        before its first announce(), nor after withdraw(): a read must
        neither create the record nor bring a tombstone back to life."""
        if (
            self.before_read is not None
            and self.node_id in self._records
            and not self._own_value.get("_tombstone")
        ):
            self.before_read()

    def alive_records(self) -> List[Record]:
        self._fresh_own()
        return self._alive()

    def _alive(self) -> List[Record]:
        now = self._clock()
        out = []
        for r in self._records.values():
            if r.value.get("_tombstone"):
                continue
            if now - r.ts > self.ttl_s:
                continue
            out.append(r)
        return out

    def get_stage(self, stage: int) -> Dict[str, Dict[str, Any]]:
        """Reference schema view: {node_id: {"load": .., "cap": .., ...}}."""
        if self._own_value.get("stage") == stage:
            self._fresh_own()  # another stage's view never holds the own record
        return {
            r.owner: r.value
            for r in self._alive()
            if r.value.get("stage") == stage
        }

    def get_all(self, num_stages: Optional[int] = None) -> Dict[int, Dict[str, Dict[str, Any]]]:
        """Whole-map view {stage: {node_id: value}} (reference get_all,
        kademlia_client.py:71-85)."""
        out: Dict[int, Dict[str, Dict[str, Any]]] = {}
        for r in self.alive_records():
            s = r.value.get("stage")
            if s is None:
                continue
            out.setdefault(int(s), {})[r.owner] = r.value
        if num_stages is not None:
            for s in range(num_stages):
                out.setdefault(s, {})
        return out

    def peers(self) -> List[Tuple[str, int]]:
        return list(self._peers.values())

    # ------------------------------------------------------------ internals

    def _send(self, msg: Dict[str, Any], addr: Tuple[str, int]) -> None:
        self._send_raw(msgpack.packb(msg, use_bin_type=True), addr)

    def _send_raw(self, data: bytes, addr: Tuple[str, int]) -> None:
        if self._ext_transport is not None:
            if self._started:
                self._ext_transport.sendto(self, data, tuple(addr))
            return
        if self._transport is None:
            return
        try:
            self._transport.sendto(data, tuple(addr))
        except Exception as e:  # e.g. EMSGSIZE — must not die silently
            log.warning("gossip send to %s failed: %s", addr, e)

    def _wire_records(self) -> List[Dict[str, Any]]:
        """What every send serialises (push, HELLO answer, anti-entropy):
        a peer receives the own record as it is AT the send."""
        self._fresh_own()
        return [r.to_wire() for r in self._records.values()]

    def _prune(self) -> None:
        """Drop long-dead records so full-state gossip doesn't grow without
        bound with node churn (and eventually exceed the UDP datagram limit).
        Expired records and tombstones are kept for a grace window (2×/3× ttl)
        first, so their deletion still propagates before they vanish."""
        now = self._clock()
        drop = [
            owner
            for owner, r in self._records.items()
            if owner != self.node_id
            and now - r.ts > self.ttl_s * (3.0 if r.value.get("_tombstone") else 2.0)
        ]
        for owner in drop:
            del self._records[owner]
            self._peers.pop(owner, None)
            self._peer_seen.pop(owner, None)
        # record-less peers (dashboard/collector observers) have no record to
        # expire — drop them once their datagrams stop, or gossip fanout
        # increasingly lands on dead addresses and _peers leaks with churn
        stale_peers = [
            p
            for p in self._peers
            if p not in self._records
            and now - self._peer_seen.get(p, 0.0) > self.ttl_s * 2.0
        ]
        for p in stale_peers:
            self._peers.pop(p, None)
            self._peer_seen.pop(p, None)

    def _merge(
        self,
        wire_records: List[Dict[str, Any]],
        sender: Tuple[str, int],
        sender_id: Optional[str] = None,
    ) -> None:
        for w in wire_records:
            try:
                owner = w["owner"]
                if owner == self.node_id:
                    continue  # nobody else may write our record
                cur = self._records.get(owner)
                # strict >: an exact (version, ts) tie keeps the first-seen
                # record. That is convergent because announce() bumps the
                # version on every VALUE change — an honest owner can never
                # emit two different values under the same version, so ties
                # only come from frames carrying identical records
                # (tests/test_dht_fuzz.py pins both properties).
                # Staleness checks run BEFORE materializing a Record, and a
                # same-version frame (a liveness heartbeat) merges as a
                # ts refresh IN PLACE: steady-state full-state gossip is
                # overwhelmingly heartbeats of already-known records, and
                # at fleet scale (1000 nodes x 1000 records per frame)
                # constructing each one dominated the gossip path's CPU.
                if cur is not None and int(w["version"]) == cur.version:
                    ts = float(w["ts"])
                    if ts > cur.ts:
                        cur.refresh_ts(ts)
                    addr = cur.addr
                elif cur is None or (
                    (int(w["version"]), float(w["ts"]))
                    > (cur.version, cur.ts)
                ):
                    rec = Record.from_wire(w)
                    self._records[rec.owner] = rec
                    owner, addr = rec.owner, rec.addr
                else:
                    addr = tuple(w["addr"])
            except Exception:
                continue
            # learn gossip addresses. An unroutable bind address (0.0.0.0)
            # can only be corrected for the SENDER's own record (we know its
            # source ip); third-party records with unroutable addrs are
            # useless as peers and are skipped.
            if addr[0] in ("0.0.0.0", "::"):
                if owner == sender_id:
                    addr = (sender[0], addr[1])
                else:
                    continue
            self._peers[owner] = addr

    def _on_message(self, msg: Dict[str, Any], addr: Tuple[str, int]) -> None:
        t = msg.get("t")
        if t == "hello":
            # bootstrap: remember the peer, send full state back. An
            # advertised port of 0 means the sender bound ephemerally and
            # didn't know its port — the datagram source port is the truth
            # (every send goes out of the bound gossip socket).
            peer_port = int(msg.get("port", addr[1])) or addr[1]
            peer_id = msg.get("from", f"{addr[0]}:{peer_port}")
            self._peers[peer_id] = (addr[0], peer_port)
            self._peer_seen[peer_id] = self._clock()
            self._send(
                {"t": "state", "from": self.node_id, "recs": self._wire_records()},
                (addr[0], peer_port),
            )
        elif t in ("state", "gossip"):
            # learn the sender as a peer from the datagram source: every send
            # goes out of the sender's bound gossip socket, so the source
            # addr IS its listening addr. This lets a records-less peer (a
            # fresh node, a dashboard observer) become reachable for gossip
            # even before it has anything to merge.
            sender_id = msg.get("from")
            if sender_id and sender_id != self.node_id:
                # overwrite, don't setdefault: the live datagram source is
                # fresher than whatever a stale hello recorded
                self._peers[sender_id] = addr
                self._peer_seen[sender_id] = self._clock()
            self._merge(msg.get("recs", []), addr, sender_id=sender_id)
            if t == "state":
                # answer anti-entropy with our own state once
                if msg.get("reply", False):
                    self._send(
                        {
                            "t": "state",
                            "from": self.node_id,
                            "recs": self._wire_records(),
                            "reply": False,
                        },
                        addr,
                    )

    def _gossip_now(self) -> None:
        self._prune()
        targets = list(self._peers.values()) or list(self.bootstrap)
        self._rng.shuffle(targets)
        # ONE serialization per fanout round: the identical frame goes to
        # every target (at 1000 records the pack dominates the send)
        data = msgpack.packb(
            {"t": "gossip", "from": self.node_id, "recs": self._wire_records()},
            use_bin_type=True,
        )
        for addr in targets[: self.fanout]:
            self._send_raw(data, addr)

    def gossip_tick(self) -> None:
        """One gossip period's worth of work: liveness heartbeat,
        bootstrap retry, fanout push, anti-entropy pull. The asyncio loop
        runs it on wall time; the fleet simulator schedules it on the
        virtual clock — same logic, either driver."""
        # periodic refresh of own record's ts (liveness heartbeat)
        own = self._records.get(self.node_id)
        if own is not None and not own.value.get("_tombstone"):
            own.refresh_ts(self._clock())
        if not self._peers and self.bootstrap:
            # bootstrap retry: our initial HELLO was lost (seed not up
            # yet) — keep knocking until someone answers (the reference
            # retried its Kademlia bootstrap too, kademlia_client.py:25-37)
            for addr in self.bootstrap:
                self._send(
                    {"t": "hello", "from": self.node_id, "port": self.port}, addr
                )
        self._gossip_now()
        # every anti_entropy_every-th tick, ask a random peer for full
        # state with a reply (pull repair; the fanout push above is the
        # steady-state carrier, so the pull can be sparse at fleet scale)
        self._tick_n += 1
        peers = list(self._peers.values())
        if peers and self._tick_n % self.anti_entropy_every == 0:
            self._send(
                {
                    "t": "state",
                    "from": self.node_id,
                    "recs": self._wire_records(),
                    "reply": True,
                },
                self._rng.choice(peers),
            )

    async def _gossip_loop(self) -> None:
        while True:
            await asyncio.sleep(self.gossip_period_s)
            self.gossip_tick()
