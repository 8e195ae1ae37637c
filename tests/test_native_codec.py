"""Native codec tests — parity between the C fast path and the numpy
fallback, and bit-identity of string hashing with the Python router
(keys must land on the same shard regardless of which side encodes)."""
import numpy as np
import pytest

from flink_tpu import native_codec as nc
from flink_tpu.records import hash_string_key


class TestNativeCodec:
    def test_builds(self):
        assert nc.native_available(), nc.unavailable_reason()

    def test_tokenize_hash_matches_python(self):
        lines = ["to be or not to be", "  leading  and   double spaces ",
                 "", "tab\tseparated words", "unicode café naïve"]
        ids, lix = nc.tokenize_hash(lines)
        pids, plix = nc._tokenize_hash_numpy(lines)
        assert ids.tolist() == pids.tolist()
        assert lix.tolist() == plix.tolist()
        # and bit-identical with the keyBy router hash
        assert ids[0] == hash_string_key("to")

    def test_hash_strings(self):
        ss = ["alpha", "beta", "café", ""]
        got = nc.hash_strings(ss)
        assert got.tolist() == [hash_string_key(s) for s in ss]

    def test_parse_i64_table(self):
        data = b"1,2,3\n-4,5,6\n7,8,9\n"
        out = nc.parse_i64_table(data, 3)
        assert out.tolist() == [[1, 2, 3], [-4, 5, 6], [7, 8, 9]]

    def test_parse_f32_table(self):
        data = b"1.5,2\n-0.25,4.125\n"
        out = nc.parse_f32_table(data, 2)
        assert out.tolist() == [[1.5, 2.0], [-0.25, 4.125]]

    def test_encode_roundtrip(self):
        vals = np.array([[10, -20, 3], [0, 99999999999, -1]], np.int64)
        enc = nc.encode_i64_rows(vals)
        back = nc.parse_i64_table(enc, 3)
        assert back.tolist() == vals.tolist()

    def test_crc32_bit_identical_to_zlib(self):
        """The native CRC (slice-by-8 + the PCLMUL-folded fast path on
        CPUs that have it, ISSUE 13) must be BIT-IDENTICAL to
        ``zlib.crc32`` for every length, alignment, and init value —
        files and frames checksummed natively verify on fallback
        readers and vice versa. Lengths cover the PCLMUL entry
        threshold (64B), its 64B-block main loop, 16B folds, tails,
        the native-vs-zlib cutover (16KB), and unaligned starts."""
        import zlib

        rng = np.random.default_rng(42)
        base = rng.integers(0, 256, 1 << 17, dtype=np.uint8).tobytes()
        lengths = [0, 1, 7, 63, 64, 65, 80, 127, 128, 200, 1023,
                   (1 << 14) - 1, 1 << 14, (1 << 14) + 13, 1 << 16,
                   (1 << 17) - 3]
        for ln in lengths:
            for off in (0, 1, 3, 8):
                for init in (0, 1, 0xDEADBEEF, 0xFFFFFFFF):
                    buf = base[off:off + ln]
                    assert nc.crc32(buf, init) == zlib.crc32(buf, init), (
                        ln, off, hex(init))

    def test_crc32_chaining_equals_concatenation(self):
        """The scatter writer's chained CRC over column parts must
        equal the CRC of the concatenated payload (the byte-identity
        contract of the columnar block format)."""
        import zlib

        rng = np.random.default_rng(43)
        parts = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
                 for n in (100, 1 << 15, 17, 0, 1 << 14)]
        crc = 0
        for p in parts:
            crc = nc.crc32(p, crc)
        assert crc == zlib.crc32(b"".join(parts))

    def test_throughput_sanity(self):
        """The native tokenizer should beat the python fallback clearly
        on a sizable corpus (sanity, not a benchmark)."""
        import time

        lines = ["the quick brown fox jumps over the lazy dog"] * 20000
        t0 = time.perf_counter()
        ids, _ = nc.tokenize_hash(lines)
        native_t = time.perf_counter() - t0
        t0 = time.perf_counter()
        pids, _ = nc._tokenize_hash_numpy(lines)
        py_t = time.perf_counter() - t0
        assert ids.tolist() == pids.tolist()
        assert native_t < py_t, (native_t, py_t)


class TestNativeHashTable:
    """The C key-directory table must agree bit-for-bit with the numpy
    reference: same splitmix64 hash, same lookup/insert semantics —
    host ingest and device keyBy route by this hash."""

    def test_hash_parity(self):
        import numpy as np
        from flink_tpu import native_codec as nc
        from flink_tpu import records

        if not nc.native_available():
            import pytest
            pytest.skip("codec library unavailable")
        rng = np.random.default_rng(3)
        keys = rng.integers(-2**62, 2**62, 10_000)
        # reference mix in pure numpy (small slices dodge the native
        # fast path inside hash_keys_numpy)
        ref = np.concatenate([records.hash_keys_numpy(keys[i:i + 100])
                              for i in range(0, len(keys), 100)])
        assert np.array_equal(ref, nc.hash_keys_native(keys))

    def test_table_matches_numpy_reference(self):
        import numpy as np
        from flink_tpu import native_codec as nc
        from flink_tpu.records import hash_keys_numpy
        from flink_tpu.state.keyed import _NumpyHashTable

        t = nc.NativeHashTable.create(16)
        if t is None:
            import pytest
            pytest.skip("codec library unavailable")
        ref = _NumpyHashTable(16)
        rng = np.random.default_rng(4)
        for round_ in range(5):
            ks = np.unique(rng.integers(0, 5_000, 800))
            vs = rng.integers(-2, 10_000, len(ks))  # incl. negative sentinels
            t.insert_batch(ks, None, vs)
            ref.insert_batch(ks, hash_keys_numpy(ks), vs)
            q = rng.integers(0, 8_000, 3_000)
            v1, f1 = t.lookup_keys(q)
            v2, f2 = ref.lookup_keys(q)
            assert np.array_equal(f1, f2)
            assert np.array_equal(v1[f1], v2[f2])
            assert t._count == ref._count
            # delete (backward shift, the same algorithm bucket for
            # bucket): a third of what was asked for, present or not
            gone = q[::3]
            assert t.delete_batch(gone) == ref.delete_batch(gone)
            v1, f1 = t.lookup_keys(q)
            v2, f2 = ref.lookup_keys(q)
            assert np.array_equal(f1, f2) and not f1[::3].any()
            assert np.array_equal(v1[f1], v2[f2])
            assert t._count == ref._count
            # no tombstones: runs as short as the load (<= 0.5) makes them
            assert max(t.longest_run(), ref.longest_run()) < 40

    def test_lookup_claim_finds_the_distinct_misses_in_one_pass(self):
        import numpy as np
        from flink_tpu import native_codec as nc

        t = nc.NativeHashTable.create(16)
        if t is None:
            import pytest
            pytest.skip("codec library unavailable")
        have = np.arange(0, 400, 2, dtype=np.int64)
        t.insert_batch(have, None, have + 7)
        rng = np.random.default_rng(8)
        q = rng.integers(0, 400, 5_000)
        vals, uniq = t.lookup_claim(q)
        odd = q % 2 == 1
        assert np.array_equal(vals[~odd], q[~odd] + 7)
        # the misses, once each, in the order they first appear
        _, first = np.unique(q[odd], return_index=True)
        assert np.array_equal(uniq, q[odd][np.sort(first)])
        # every record of a missed key reads that key's placeholder
        assert np.array_equal(uniq[t.PENDING - vals[odd]], q[odd])
        t.insert_batch(uniq, None, uniq + 7)       # the caller's half
        v, f = t.lookup_keys(q)
        assert f.all() and np.array_equal(v, q + 7)

    def test_directory_native_vs_numpy(self):
        import numpy as np
        from flink_tpu.state.keyed import KeyDirectory, _NumpyHashTable

        rng = np.random.default_rng(5)
        d1 = KeyDirectory(8, 32)
        d2 = KeyDirectory(8, 32)
        d2._table = _NumpyHashTable()  # force the fallback
        for _ in range(4):
            ks = rng.integers(0, 1_000, 5_000)
            assert np.array_equal(d1.assign(ks), d2.assign(ks))
        assert d1.num_keys() == d2.num_keys()


class TestContentKeyedBuild:
    """The library is always the one built from the source beside it:
    the rebuild decision is keyed on the source's CONTENT (a hash in the
    .so name), never on mtimes, and a failed build keeps its reason."""

    TINY = 'extern "C" int answer() { return 42; }\n'

    def _src(self, tmp_path, text=None):
        src = tmp_path / "codec.cc"
        src.write_text(self.TINY if text is None else text)
        return str(src)

    def test_touching_mtimes_changes_nothing(self, tmp_path):
        import os

        src = self._src(tmp_path)
        so = nc.build_library(src)
        before = os.stat(so)
        # the staleness an mtime rule would act on, both ways round
        os.utime(src, (before.st_mtime + 3600, before.st_mtime + 3600))
        assert nc.build_library(src) == so
        os.utime(so, (1, 1))
        assert nc.build_library(src) == so
        after = os.stat(so)
        assert after.st_ino == before.st_ino  # never recompiled

    def test_changing_the_source_rebuilds_under_a_new_name(self, tmp_path):
        import ctypes
        import os

        src = self._src(tmp_path)
        old = nc.build_library(src)
        with open(src, "a") as f:
            f.write('extern "C" int more() { return 7; }\n')
        new = nc.build_library(src)
        assert new != old and os.path.exists(new)
        assert not os.path.exists(old)  # superseded library swept
        assert ctypes.CDLL(new).more() == 7

    def test_foreign_library_is_not_picked_up(self, tmp_path):
        import os
        import shutil

        src = self._src(tmp_path)
        real = nc.build_library(src)
        # a .so copied in from another tree: right directory, wrong name
        foreign = str(tmp_path / "libflinktpucodec.so")
        shutil.copy(real, foreign)
        os.unlink(real)
        assert nc.build_library(src) == real != foreign
        assert os.path.exists(real)

    def test_failing_compiler_leaves_a_retrievable_reason(self, tmp_path):
        src = self._src(tmp_path, "this is not C++\n")
        with pytest.raises(nc.CodecBuildError, match="error"):
            nc.build_library(src)
        with pytest.raises(nc.CodecBuildError, match="compiler not found"):
            nc.build_library(self._src(tmp_path), cxx="no-such-compiler")
        with pytest.raises(nc.CodecBuildError, match="cannot read"):
            nc.build_library(str(tmp_path / "absent.cc"))

    def test_library_lacking_a_symbol_is_refused_with_its_name(self, tmp_path):
        so = nc.build_library(self._src(tmp_path))
        with pytest.raises(nc.CodecBuildError, match="tokenize_hash"):
            nc.load_library(so)

    def test_package_keeps_the_reason_and_falls_back(self, tmp_path,
                                                     monkeypatch):
        # an unbuildable source: the numpy fallbacks serve, and the
        # compiler's words are kept instead of a silent None
        monkeypatch.setattr(nc, "_SRC", self._src(tmp_path, "int x = ;\n"))
        monkeypatch.setattr(nc, "_lib", None)
        monkeypatch.setattr(nc, "_tried", False)
        monkeypatch.setattr(nc, "_reason", None)
        assert not nc.native_available()
        assert "error" in nc.unavailable_reason()
        assert nc.hash_keys_native(np.arange(4)) is None
        assert nc.hash_strings(["a"]).tolist() == [hash_string_key("a")]
