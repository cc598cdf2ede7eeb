"""Property-based convergence of the gossip store's merge (control/dht.py):
last-writer-wins on (version, ts) must be commutative, idempotent, and
order-independent — any two stores that saw the same record set in ANY
order and multiplicity hold identical state. This is the property that
makes the reference's read-modify-write races (SURVEY B6) impossible by
construction, so it gets pinned adversarially rather than by example."""

from hypothesis import given, settings
from hypothesis import strategies as st

from inferd_tpu.control.dht import Record, SwarmDHT

OWNERS = [f"10.0.0.{i}:7050" for i in range(1, 5)]


def mk_store():
    # no start(): _merge/_records are pure state machine surface
    return SwarmDHT("127.0.0.9:9", 0, bootstrap=[], host="127.0.0.1")


# Protocol invariant (dht.announce bumps _own_version on EVERY value change):
# an owner never issues two different values under one VERSION — a frame
# with a known version is a liveness heartbeat, which _merge takes as a ts
# refresh in place — so the generator derives the value from the version
# alone. The same version at several ts (heartbeats) and exact duplicates
# (repeated frames) are both covered.
records = st.builds(
    lambda owner, version, ts: Record(
        owner=owner,
        value={
            "stage": version % 3,
            "load": version * 10,
            "host": owner.split(":")[0],
            "port": 7050,
        },
        version=version,
        ts=float(ts),
        addr=(owner.split(":")[0], 7050),
    ),
    st.sampled_from(OWNERS),
    st.integers(0, 5),
    st.integers(0, 3),
)


def state(store):
    return {
        o: (r.version, r.ts, r.value) for o, r in store._records.items()
    }


@settings(max_examples=150, deadline=None)
@given(st.lists(records, max_size=12), st.permutations(range(12)))
def test_merge_order_independent(recs, perm):
    a, b = mk_store(), mk_store()
    sender = ("10.0.0.1", 7050)
    for r in recs:
        a._merge([r.to_wire()], sender, sender_id=r.owner)
    order = [recs[i] for i in perm if i < len(recs)]
    for r in order:  # permuted order, same multiset
        b._merge([r.to_wire()], sender, sender_id=r.owner)
    assert state(a) == state(b)


@settings(max_examples=100, deadline=None)
@given(st.lists(records, max_size=10))
def test_merge_idempotent(recs):
    a = mk_store()
    sender = ("10.0.0.1", 7050)
    wires = [r.to_wire() for r in recs]
    a._merge(wires, sender)
    snap = state(a)
    a._merge(wires, sender)  # replay everything
    a._merge(list(reversed(wires)), sender)
    assert state(a) == snap


@settings(max_examples=100, deadline=None)
@given(st.lists(records, min_size=1, max_size=10))
def test_highest_version_wins(recs):
    a = mk_store()
    a._merge([r.to_wire() for r in recs], ("10.0.0.1", 7050))
    for owner in {r.owner for r in recs}:
        best = max(
            (r for r in recs if r.owner == owner), key=lambda r: (r.version, r.ts)
        )
        got = a._records[owner]
        assert (got.version, got.ts) == (best.version, best.ts)


def test_own_record_never_overwritten():
    a = mk_store()
    foreign = Record(
        owner=a.node_id, value={"stage": 9}, version=99, ts=9e9,
        addr=("1.2.3.4", 1),
    )
    a._merge([foreign.to_wire()], ("10.0.0.1", 7050))
    assert a.node_id not in a._records  # owner-writes-only held
