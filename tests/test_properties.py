"""Hypothesis property tests for the two host-side structures whose
parallelization contracts are pure invariants: the EventOp aggregation
monoid (shard-safety) and the bilinear neighbor layout (no-loss slot
permutation). Isolated in their own module so a hypothesis-less
environment skips exactly these tests, not their subjects' suites."""

import random

import numpy as np
import pytest

pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st  # noqa: E402

from predictionio_tpu.storage import EventOp  # noqa: E402
from tests.helpers import assert_layout_invariants, special  # noqa: E402


# ---------------------------------------------------------------------------
# EventOp monoid: the shard-safety claim, under adversarial timestamp
# ties and key collisions (the regime where a non-commutative merge
# would diverge).

_special_events = st.lists(
    st.tuples(
        st.sampled_from(["$set", "$unset", "$delete"]),
        # tiny pools force key collisions and timestamp TIES
        st.dictionaries(st.sampled_from("abc"), st.integers(0, 2),
                        min_size=0, max_size=2),
        st.integers(0, 4),  # minutes: only 5 distinct times
    ),
    min_size=0, max_size=14,
)


def _resolve(op):
    pm = op.to_property_map()
    return None if pm is None else (pm.to_dict(), pm.first_updated,
                                    pm.last_updated)


@settings(max_examples=200, deadline=None)
@given(evs=_special_events, seed=st.integers(0, 2**32 - 1))
def test_monoid_partition_and_order_invariant(evs, seed):
    """Any partition of the event stream into shards, each folded
    locally and merged in any order, must resolve to the same entity
    state as the sequential fold — the property that makes
    aggregate_properties safe to parallelize over processes (the
    reference aggregateByKey's contract)."""
    events = [special(e, "u1", p, m) for e, p, m in evs]

    sequential = EventOp()
    for e in events:
        sequential = sequential.merge(EventOp.from_event(e))

    rng = random.Random(seed)
    n_shards = rng.randint(1, 4)
    shards = [EventOp() for _ in range(n_shards)]
    for e in events:
        i = rng.randrange(n_shards)
        shards[i] = shards[i].merge(EventOp.from_event(e))
    rng.shuffle(shards)
    merged = EventOp()
    for s in shards:
        merged = merged.merge(s)

    assert _resolve(merged) == _resolve(sequential)

    # full associativity at the EventOp level too: right-fold == left-fold
    ops = [EventOp.from_event(e) for e in events]
    right = EventOp()
    for op in reversed(ops):
        right = op.merge(right)
    assert _resolve(right) == _resolve(sequential)


# ---------------------------------------------------------------------------
# Bilinear layout: the invariants of test_als.test_bilinear_layout_no_loss,
# searched over random shapes, skew, tier ladders, and alignments.


@settings(max_examples=60, deadline=None)
@given(
    nu=st.integers(1, 20), ni=st.integers(1, 15),
    n=st.integers(1, 200), seed=st.integers(0, 999),
    heavy=st.booleans(),  # pile entries on one row to force chunking
    tiers=st.sampled_from([(4,), (4, 16), (8, 64)]),
    chunk_cap=st.sampled_from([4, 16]),
    align=st.sampled_from([1, 5]),
)
def test_bilinear_layout_invariants_property(nu, ni, n, seed, heavy, tiers,
                                             chunk_cap, align):
    """Every random instance must keep the full entry multiset, assign
    each entity exactly one in-range slot, remap neighbor ids into the
    other side's slot space (padding at its zero slot), keep chunked-tier
    owner segments sorted, and honor the model-axis alignment."""
    from predictionio_tpu.ops.neighbors import build_bilinear_layout

    rng = np.random.default_rng(seed)
    rows = rng.integers(0, nu, n).astype(np.int64)
    if heavy:
        rows[: n // 2] = rng.integers(0, nu)  # one hot row
    cols = rng.integers(0, ni, n).astype(np.int64)
    vals = (rng.random(n).astype(np.float32) + 0.5)
    u_lay, i_lay = build_bilinear_layout(rows, cols, vals, nu, ni,
                                         tiers=tiers, chunk_cap=chunk_cap,
                                         align=align)
    for lay, other in ((u_lay, i_lay), (i_lay, u_lay)):
        assert_layout_invariants(lay, other, vals, n)
        assert lay.slots % np.lcm(align, 8) == 0


@settings(max_examples=40, deadline=None)
@given(
    nu=st.integers(8, 60), ni=st.integers(8, 60),
    n=st.integers(50, 600), seed=st.integers(0, 999),
    exponent=st.sampled_from([0.0, 0.9, 1.5]),  # 0.0: uniform popularity
    chunk_cap=st.sampled_from([16, 64]),
    slice_rows=st.sampled_from([8, 16, 24]),
    sigmas=st.sampled_from([0.0, 1.0]),
)
def test_hot_split_layout_invariants_property(nu, ni, n, seed, exponent,
                                              chunk_cap, slice_rows, sigmas):
    """Whatever the skew and the slice's size: every rating lies in
    exactly one part of one bucket, hot ids are local to the slice and
    padded with its zero row, the cold part takes a neighbor of the slice
    only from a row whose hot part is full, and a bucket's two widths add
    up to the unsplit bucket's."""
    from unittest import mock

    from predictionio_tpu.ops import neighbors

    rng = np.random.default_rng(seed)
    p = 1.0 / np.arange(1, ni + 1) ** exponent
    cols = rng.choice(ni, n, p=p / p.sum()).astype(np.int64)
    q = 1.0 / np.arange(1, nu + 1) ** exponent
    rows = rng.choice(nu, n, p=q / q.sum()).astype(np.int64)
    vals = rng.random(n).astype(np.float32) + 0.5
    build = lambda: neighbors.build_bilinear_layout(  # noqa: E731
        rows, cols, vals, nu, ni, tiers=(8, 64), chunk_cap=chunk_cap)
    with mock.patch.object(neighbors, "GATHER_NS_BY_TABLE_ROWS",
                           ((slice_rows, 4.0),)), \
            mock.patch.object(neighbors, "COLD_WIDTH_SIGMAS", sigmas):
        u_lay, i_lay = build()
    with mock.patch.object(neighbors, "GATHER_NS_BY_TABLE_ROWS", ()):
        u_old, i_old = build()
    for lay, other, old in ((u_lay, i_lay, u_old), (i_lay, u_lay, i_old)):
        assert_layout_invariants(lay, other, vals, n)
        for b, b_old in zip(lay.buckets, old.buckets):
            hot_width = 0 if b.hot_ids is None else b.hot_ids.shape[2]
            assert b.ids.shape[2] + hot_width == b_old.ids.shape[2]
            assert b.ids.shape[1] - b_old.ids.shape[1] in (0, 8)
        assert 0 <= lay.hot_rows <= slice_rows
        if not lay.hot_rows:
            assert all(b.hot_ids is None for b in lay.buckets)
            assert other.pos.tobytes() == (
                i_old if lay is u_lay else u_old).pos.tobytes()


# ---------------------------------------------------------------------------
# Event wire codec: to_api_dict ∘ from_api_dict must be the identity on
# every valid event — searched over unicode ids, nested property values,
# and sub-second timestamps (the SDK-facing JSON contract).

from datetime import datetime, timezone  # noqa: E402

_json_scalars = st.one_of(st.booleans(), st.integers(-1000, 1000),
                          st.floats(-1e6, 1e6, allow_nan=False),
                          st.text(max_size=8))
_json_values = st.recursive(
    _json_scalars,
    lambda children: st.one_of(
        st.lists(children, max_size=3),
        st.dictionaries(st.text(min_size=1, max_size=6), children,
                        max_size=3)),
    max_leaves=8)
_ids = st.text(min_size=1, max_size=12).filter(
    lambda s: s.strip() == s and s and not s.startswith(("$", "pio_")))


@settings(max_examples=150, deadline=None)
@given(
    event=st.sampled_from(["view", "rate", "like", "$set"]),
    eid=_ids, etype=_ids,
    props=st.dictionaries(
        st.text(min_size=1, max_size=8).filter(
            lambda k: not k.startswith(("$", "pio_"))),
        _json_values, max_size=4),
    micros=st.integers(0, 999_999),
    tags=st.lists(_ids, max_size=3),
)
def test_event_wire_codec_roundtrip(event, eid, etype, props, micros, tags):
    from predictionio_tpu.storage import DataMap
    from predictionio_tpu.storage.event import (
        Event, event_from_api_dict, event_to_api_dict)

    e = Event(
        event=event, entity_type=etype, entity_id=eid,
        properties=DataMap(props),
        event_time=datetime(2021, 3, 4, 5, 6, 7, micros,
                            tzinfo=timezone.utc),
        tags=tuple(tags),
    )
    e2 = event_from_api_dict(event_to_api_dict(e))
    assert e2.event == e.event
    assert e2.entity_type == e.entity_type and e2.entity_id == e.entity_id
    assert e2.properties == e.properties
    assert e2.tags == e.tags
    # sub-second precision must survive the ISO text form
    assert e2.event_time == e.event_time
