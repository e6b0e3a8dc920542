"""Native C++ kernels vs numpy/pure-Python fallbacks — bit-exact parity.

The native layer (predictionio_tpu/native/pio_native.cpp) plays the role
of the reference's JVM-native host substrate (Spark ALS shuffle layout,
HBase row-key sharding, TableInputFormat scans). Every kernel must agree
exactly with its fallback so `PIO_NO_NATIVE=1` is purely a perf switch.
"""

from __future__ import annotations

import json

import numpy as np
import pytest

from predictionio_tpu import native
from predictionio_tpu.ops import neighbors
from predictionio_tpu.storage.partition import (
    _fnv1a64,
    entity_key,
    hash64,
    partition_events,
    shard_of,
)
from predictionio_tpu.storage.event import Event, event_from_api_dict
from predictionio_tpu.tools.import_export import _parse_jsonl_native

pytestmark = pytest.mark.skipif(
    not native.available(), reason="native library failed to build"
)


def _coo(n, num_rows, num_cols, seed=0, heavy_row=None, heavy_n=0):
    rng = np.random.default_rng(seed)
    rows = rng.integers(0, num_rows, n).astype(np.int64)
    if heavy_row is not None:
        rows = np.concatenate([rows, np.full(heavy_n, heavy_row, np.int64)])
    cols = rng.integers(0, num_cols, len(rows)).astype(np.int32)
    vals = rng.random(len(rows)).astype(np.float32)
    return rows, cols, vals


def _both_paths(rows, cols, vals, num_rows, **kw):
    nat = neighbors.build_neighbor_blocks(rows, cols, vals, num_rows, **kw)
    orig = neighbors.native.available
    neighbors.native.available = lambda: False
    try:
        ref = neighbors.build_neighbor_blocks(rows, cols, vals, num_rows, **kw)
    finally:
        neighbors.native.available = orig
    return nat, ref


class TestNeighborBlocksParity:
    def test_no_overflow(self):
        rows, cols, vals = _coo(5000, 300, 200)
        nat, ref = _both_paths(rows, cols, vals, 300, block_rows=64)
        np.testing.assert_array_equal(nat.ids, ref.ids)
        np.testing.assert_array_equal(nat.vals, ref.vals)
        np.testing.assert_array_equal(nat.mask, ref.mask)
        assert nat.dropped == ref.dropped == 0
        assert nat.max_degree == ref.max_degree

    def test_overflow_subsample_identical(self):
        # two heavy rows far past the cap force the hash-keyed subsample
        rows, cols, vals = _coo(3000, 100, 500, heavy_row=7, heavy_n=400)
        rows2 = np.concatenate([rows, np.full(350, 42, np.int64)])
        cols2 = np.concatenate([cols, np.arange(350, dtype=np.int32)])
        vals2 = np.concatenate([vals, np.ones(350, np.float32)])
        nat, ref = _both_paths(rows2, cols2, vals2, 100,
                               block_rows=32, degree_cap=64, seed=3)
        assert nat.dropped == ref.dropped > 0
        np.testing.assert_array_equal(nat.ids, ref.ids)
        np.testing.assert_array_equal(nat.vals, ref.vals)
        np.testing.assert_array_equal(nat.mask, ref.mask)

    def test_seed_changes_subsample(self):
        rows, cols, vals = _coo(200, 10, 400, heavy_row=0, heavy_n=300)
        a = neighbors.build_neighbor_blocks(rows, cols, vals, 10,
                                            block_rows=8, degree_cap=32, seed=0)
        b = neighbors.build_neighbor_blocks(rows, cols, vals, 10,
                                            block_rows=8, degree_cap=32, seed=1)
        assert not np.array_equal(a.ids, b.ids)

    def test_empty(self):
        nat, ref = _both_paths(
            np.zeros(0, np.int64), np.zeros(0, np.int32),
            np.zeros(0, np.float32), 10, block_rows=8)
        np.testing.assert_array_equal(nat.ids, ref.ids)

    @pytest.mark.parametrize("seed,d_hot_room", [(0, "wide"), (1, "tight")])
    def test_hot_split_identical(self, seed, d_hot_room, monkeypatch):
        """`_split_hot` through the C++ pass and through numpy: the same
        widths and the same four arrays, spill and padding included."""
        rows, cols, vals = _coo(6000, 200, 900, seed=seed)
        if d_hot_room == "tight":  # most entries inside the slice: spill
            cols = (cols % 300 + 500).astype(np.int32)
        b = neighbors.build_neighbor_blocks(rows, cols, vals, 200,
                                            block_rows=40, pad_id=999)
        monkeypatch.setattr(neighbors, "COLD_WIDTH_SIGMAS", 0.0)
        args = (b, 500, 301, 10**6, 999, 1.0 if d_hot_room == "tight" else 0.3)
        nat = neighbors._split_hot(*args)
        assert nat.hot_ids is not None
        monkeypatch.setattr(neighbors.native, "hot_split_native",
                            lambda *a: None)
        ref = neighbors._split_hot(*args)
        for name in ("ids", "vals", "hot_ids", "hot_vals"):
            np.testing.assert_array_equal(getattr(nat, name),
                                          getattr(ref, name))
        assert nat.hot_ids.shape[2] + nat.ids.shape[2] == b.ids.shape[2]

    def test_bilinear_layout_uses_native(self):
        rows, cols, vals = _coo(4000, 200, 300, heavy_row=3, heavy_n=200)
        u_lay, i_lay = neighbors.build_bilinear_layout(
            rows, cols, vals, 200, 300)
        total = sum(int(b.mask.sum()) for b in u_lay.buckets)
        assert total == len(rows)


class TestHashParity:
    def test_matches_pure_python(self):
        keys = [entity_key("user", f"u{i}") for i in range(50)] + [b"", b"\x00ab"]
        nat = hash64(keys, seed=7)
        ref = np.array([_fnv1a64(k, 7) for k in keys], dtype=np.uint64)
        np.testing.assert_array_equal(nat, ref)

    def test_shard_stability_and_spread(self):
        shards = [shard_of("item", f"i{i}", 8) for i in range(1000)]
        assert all(0 <= s < 8 for s in shards)
        counts = np.bincount(shards, minlength=8)
        assert counts.min() > 60  # roughly uniform

    def test_partition_keeps_entity_together(self):
        evs = [Event(event="$set", entity_type="user", entity_id=f"u{i % 5}")
               for i in range(40)]
        parts = partition_events(evs, 4)
        assert sum(len(p) for p in parts) == 40
        for p in parts:
            for e in p:
                assert shard_of(e.entity_type, e.entity_id, 4) == parts.index(p)


class TestJsonlScanner:
    def _roundtrip(self, dicts):
        data = "\n".join(json.dumps(d) for d in dicts).encode()
        parsed = _parse_jsonl_native(data)
        assert parsed is not None
        assert len(parsed) == len(dicts)
        for got, want in zip(parsed, dicts):
            assert got == want
        return parsed

    def test_basic_events(self):
        self._roundtrip([
            {"event": "rate", "entityType": "user", "entityId": "u1",
             "targetEntityType": "item", "targetEntityId": "i1",
             "properties": {"rating": 4.5}, "eventTime": "2026-01-01T00:00:00.000Z"},
            {"event": "$set", "entityType": "user", "entityId": "u2",
             "properties": {"a": [1, 2, {"b": None}], "s": "x"},
             "tags": ["t1", "t2"]},
        ])

    def test_escapes_and_unicode(self):
        self._roundtrip([
            {"event": "buy", "entityType": "user", "entityId": 'q"\\uote\n',
             "properties": {"note": "caf\u00e9 \u2603"}},
        ])

    def test_blank_lines_and_whitespace(self):
        data = b'\n  {"event":"e","entityType":"t","entityId":"i"}  \n\n'
        n, starts, ends = native.scan_jsonl(data)
        assert n == 1

    def test_malformed_falls_back(self):
        assert native.scan_jsonl(b'{"event": "unterminated') is None
        assert native.scan_jsonl(b"[1, 2]") is None
        assert native.scan_jsonl(b'{"event":"a"} trailing') is None

    def test_escaped_key_falls_back(self):
        # "event" decodes to key "event"; raw-byte matching cannot see
        # that, so the whole line must fall back to the full parser
        assert native.scan_jsonl(
            b'{"\\u0065vent":"rate","entityType":"t","entityId":"i"}') is None

    def test_raw_control_chars_rejected(self):
        # strict JSON rejects unescaped control bytes inside strings; the
        # native path must fall back rather than accept what json.loads won't
        assert native.scan_jsonl(b'{"event":"a\tb","entityType":"t","entityId":"i"}') is None
        assert native.scan_jsonl(b'{"event":"a\x01b"}') is None

    def test_invalid_scalars_rejected(self):
        # native accept/reject must match the full JSON parser
        for bad in (b'{"a": not_json}', b'{"a": 01}', b'{"a": 1.2.3}',
                    b'{"a": -}', b'{"a": 1e}', b'{"a": truex}'):
            assert native.scan_jsonl(bad) is None, bad
        for ok in (b'{"a": -0.5e+10}', b'{"a": 0}', b'{"a": true}',
                   b'{"a": null}', b'{"a": 123e2}'):
            assert native.scan_jsonl(ok) is not None, ok

    def test_import_error_reports_true_line_number(self, tmp_path):
        from predictionio_tpu.tools.import_export import import_events
        p = tmp_path / "ev.jsonl"
        good = '{"event":"e","entityType":"t","entityId":"i"}'
        p.write_text(f"{good}\n\n{good.replace(chr(34)+'entityId'+chr(34)+':'+chr(34)+'i'+chr(34), chr(34)+'x'+chr(34)+':1')}\n")
        with pytest.raises(ValueError, match=r"ev\.jsonl:3"):
            import_events(p, app_id=1)

    def test_import_streams_chunked(self, tmp_path, monkeypatch):
        import predictionio_tpu.tools.import_export as ie
        monkeypatch.setattr(ie, "_CHUNK", 64)  # force many chunks
        p = tmp_path / "ev.jsonl"
        with open(p, "w") as f:
            for i in range(200):
                f.write('{"event":"rate","entityType":"user","entityId":"u%d",'
                        '"targetEntityType":"item","targetEntityId":"i%d",'
                        '"properties":{"rating":%d}}\n' % (i, i % 7, i % 5 + 1))
        assert ie.import_events(p, app_id=1) == 200

    def test_events_parse_to_valid_events(self):
        dicts = self._roundtrip([
            {"event": "rate", "entityType": "user", "entityId": "u1",
             "targetEntityType": "item", "targetEntityId": "i9",
             "properties": {"rating": 3.0},
             "eventTime": "2026-02-03T04:05:06.789Z"},
        ])
        e = event_from_api_dict(dicts[0])
        assert e.target_entity_id == "i9"
        assert e.properties["rating"] == 3.0


class TestCountingArgsort:
    """Native parallel counting argsort — must be BIT-IDENTICAL to
    np.argsort(kind="stable") (the layout permutation feeds the training
    math; any divergence reorders factors)."""

    def test_matches_numpy_stable(self):
        from predictionio_tpu.native import available, counting_argsort

        if not available():
            import pytest

            pytest.skip("native lib unavailable")
        rng = np.random.default_rng(0)
        for n, kmax in ((0, 5), (1, 0), (1000, 3), (100_000, 17),
                        (300_000, 100_000)):
            keys = rng.integers(0, kmax + 1, n).astype(np.int32)
            got = counting_argsort(keys, kmax)
            np.testing.assert_array_equal(
                got, np.argsort(keys, kind="stable"),
                err_msg=f"n={n} kmax={kmax}")

    def test_out_of_range_returns_none(self):
        from predictionio_tpu.native import available, counting_argsort

        if not available():
            import pytest

            pytest.skip("native lib unavailable")
        assert counting_argsort(np.array([0, 5], np.int32), 3) is None
        assert counting_argsort(np.array([-1, 0], np.int32), 3) is None

    def test_layout_identical_with_and_without_native(self, monkeypatch):
        """The full bilinear layout must not depend on which argsort ran."""
        import predictionio_tpu.ops.neighbors as nb

        rng = np.random.default_rng(3)
        n, nu, ni = 20_000, 300, 150
        rows = rng.integers(0, nu, n).astype(np.int64)
        cols = rng.integers(0, ni, n).astype(np.int64)
        vals = rng.random(n).astype(np.float32)
        # a few heavy rows to exercise the chunked path's sort too
        rows[: n // 4] = 7
        a_u, a_i = nb.build_bilinear_layout(rows, cols, vals, nu, ni)
        monkeypatch.setattr(nb, "_stable_argsort_bounded",
                            lambda k, m: np.argsort(k, kind="stable"))
        b_u, b_i = nb.build_bilinear_layout(rows, cols, vals, nu, ni)
        for a, b in ((a_u, b_u), (a_i, b_i)):
            assert len(a.buckets) == len(b.buckets)
            for ba, bb in zip(a.buckets, b.buckets):
                np.testing.assert_array_equal(ba.ids, bb.ids)
                np.testing.assert_array_equal(ba.vals, bb.vals)
            np.testing.assert_array_equal(a.pos, b.pos)

    def test_int64_out_of_range_returns_none(self):
        """int64 keys outside int32 must NOT wrap into range (review r4:
        a wrapped key passes the native check and returns a silently
        wrong permutation; the contract is None -> numpy fallback)."""
        from predictionio_tpu.native import available, counting_argsort

        if not available():
            import pytest

            pytest.skip("native lib unavailable")
        assert counting_argsort(np.array([2**32, 1], np.int64), 3) is None
        got = counting_argsort(np.array([2, 0, 1], np.int64), 2)
        np.testing.assert_array_equal(got, [1, 2, 0])
