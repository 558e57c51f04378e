"""Native (C++) document store: parity with the Python backend, shared
WAL format, CSV ingest engine."""

import json
import threading

import pytest

from learningorchestra_tpu import native
from learningorchestra_tpu.store.document_store import (
    DocumentStore,
    DuplicateKey,
    NoSuchCollection,
)

pytestmark = pytest.mark.skipif(
    not native.native_available(), reason="native library not built"
)


@pytest.fixture
def store(tmp_path):
    st = native.NativeDocumentStore(tmp_path / "store")
    yield st
    st.close()


class TestNativeStoreBasics:
    def test_insert_and_find_one(self, store):
        _id = store.insert_one("c", {"a": 1, "b": "x"})
        assert _id == 0
        doc = store.find_one("c", 0)
        assert doc == {"a": 1, "b": "x", "_id": 0}

    def test_auto_increment_ids(self, store):
        ids = [store.insert_one("c", {"i": i}) for i in range(5)]
        assert ids == [0, 1, 2, 3, 4]

    def test_insert_many_and_count(self, store):
        n = store.insert_many("c", [{"i": i} for i in range(100)])
        assert n == 100
        assert store.count("c") == 100

    def test_insert_unique_conflict(self, store):
        store.insert_unique("c", {"meta": True}, 0)
        with pytest.raises(DuplicateKey):
            store.insert_unique("c", {"meta": 2}, 0)

    def test_update_merges_top_level(self, store):
        store.insert_one("c", {"a": 1, "nested": {"x": 1}})
        assert store.update_one("c", 0, {"a": 2, "new": [1, 2]})
        doc = store.find_one("c", 0)
        assert doc["a"] == 2
        assert doc["new"] == [1, 2]
        assert doc["nested"] == {"x": 1}

    def test_update_missing(self, store):
        store.insert_one("c", {})
        assert not store.update_one("c", 99, {"a": 1})

    def test_delete(self, store):
        store.insert_one("c", {"a": 1})
        assert store.delete_one("c", 0)
        assert store.find_one("c", 0) is None
        assert not store.delete_one("c", 0)

    def test_find_sorted_skip_limit(self, store):
        store.insert_many("c", [{"i": i} for i in range(10)])
        docs = store.find("c", skip=3, limit=2)
        assert [d["_id"] for d in docs] == [3, 4]

    def test_find_with_query_operators(self, store):
        store.insert_many("c", [{"i": i} for i in range(10)])
        docs = store.find("c", query={"i": {"$gte": 8}})
        assert [d["i"] for d in docs] == [8, 9]
        docs = store.find("c", query={"i": 4})
        assert len(docs) == 1

    def test_missing_collection_raises(self, store):
        with pytest.raises(NoSuchCollection):
            store.find("nope")
        assert store.find_one("nope", 0) is None

    def test_unicode_and_specials_roundtrip(self, store):
        doc = {"s": 'quote " backslash \\ newline \n tab \t héllo ünïcode',
               "f": 1.5, "n": None, "b": True, "neg": -7}
        store.insert_one("c", doc)
        got = store.find_one("c", 0)
        for k, v in doc.items():
            assert got[k] == v

    def test_value_counts(self, store):
        store.insert_unique("c", {"meta": True}, 0)  # excluded (_id=0)
        store.insert_many("c", [{"color": "red"}, {"color": "red"},
                                {"color": "blue"}, {"other": 1}])
        store.insert_one("c", {"color": "x", "docType": "execution"})
        counts = store.aggregate_counts("c", "color")
        assert counts == {"red": 2, "blue": 1, None: 1}

    def test_drop_and_list(self, store):
        store.insert_one("a1", {})
        store.insert_one("b1", {})
        assert store.list_collections() == ["a1", "b1"]
        assert store.drop("a1")
        assert store.list_collections() == ["b1"]
        assert not store.drop("a1")

    def test_compact_preserves_state(self, tmp_path):
        st = native.NativeDocumentStore(tmp_path / "s")
        st.insert_many("c", [{"i": i} for i in range(10)])
        for i in range(5):
            st.delete_one("c", i)
        st.update_one("c", 7, {"i": 70})
        st.compact("c")
        st.close()
        st2 = native.NativeDocumentStore(tmp_path / "s")
        docs = st2.find("c")
        assert [d["_id"] for d in docs] == [5, 6, 7, 8, 9]
        assert st2.find_one("c", 7)["i"] == 70
        # next_id watermark survives compaction
        assert st2.insert_one("c", {}) == 10
        st2.close()


class TestWALInterchange:
    """Both backends share one on-disk format."""

    def test_python_write_native_read(self, tmp_path):
        py = DocumentStore(tmp_path / "s")
        py.insert_unique("c", {"name": "ds", "finished": False}, 0)
        py.insert_many("c", [{"i": i, "tag": "t"} for i in range(20)])
        py.update_one("c", 0, {"finished": True})
        py.delete_one("c", 3)
        py.close()

        nt = native.NativeDocumentStore(tmp_path / "s")
        assert nt.count("c") == 20  # 21 inserted - 1 deleted
        assert nt.find_one("c", 0)["finished"] is True
        assert nt.find_one("c", 3) is None
        assert nt.insert_one("c", {}) == 21
        nt.close()

    def test_native_write_python_read(self, tmp_path):
        nt = native.NativeDocumentStore(tmp_path / "s")
        nt.insert_unique("c", {"name": "ds", "finished": False}, 0)
        nt.insert_many("c", [{"i": i, "x": i * 0.5} for i in range(20)])
        nt.update_one("c", 0, {"finished": True, "rows": 20})
        nt.delete_one("c", 5)
        nt.close()

        py = DocumentStore(tmp_path / "s")
        assert py.count("c") == 20
        meta = py.find_one("c", 0)
        assert meta["finished"] is True and meta["rows"] == 20
        assert py.find_one("c", 5) is None
        assert py.find_one("c", 2)["x"] == 0.5  # _id=2 is row i=1
        py.close()


class TestNativeCSV:
    def test_parse_with_inference(self):
        data = b"Name,Age!,Score\nalice,30,1.5\nbob,,x\n"
        fields, jsonl = native.csv_parse(data)
        assert fields == ["Name", "Age", "Score"]
        docs = [json.loads(ln) for ln in jsonl.splitlines()]
        assert docs[0] == {"Name": "alice", "Age": 30, "Score": 1.5}
        assert docs[1] == {"Name": "bob", "Age": None, "Score": "x"}

    def test_parse_no_inference(self):
        data = b"a,b\n1,2.5\n"
        _, jsonl = native.csv_parse(data, infer_types=False)
        assert json.loads(jsonl.splitlines()[0]) == {"a": "1", "b": "2.5"}

    def test_quoted_fields_with_commas_newlines(self):
        data = b'a,b\n"x,y","line1\nline2"\n"he said ""hi""",2\n'
        _, jsonl = native.csv_parse(data)
        docs = [json.loads(ln) for ln in jsonl.splitlines()]
        assert docs[0] == {"a": "x,y", "b": "line1\nline2"}
        assert docs[1] == {"a": 'he said "hi"', "b": 2}

    def test_crlf_and_bom(self):
        data = b"\xef\xbb\xbfa,b\r\n1,2\r\n3,4\r\n"
        fields, jsonl = native.csv_parse(data)
        assert fields == ["a", "b"]
        docs = [json.loads(ln) for ln in jsonl.splitlines()]
        assert docs == [{"a": 1, "b": 2}, {"a": 3, "b": 4}]

    def test_header_cleaning_matches_python(self):
        from learningorchestra_tpu.services.dataset import _clean_header

        raw = ["First Name", "a.b(c)", "  ", "ok_1", "%%%"]
        fields, _ = native.csv_parse(
            (",".join(raw) + "\n" + ",".join("12345")).encode()
        )
        assert fields == _clean_header(list(raw))

    def test_short_rows_and_floats_roundtrip(self):
        data = b"a,b,c\n0.1,-3e7,\n7,,\n"
        _, jsonl = native.csv_parse(data)
        docs = [json.loads(ln) for ln in jsonl.splitlines()]
        assert docs[0] == {"a": 0.1, "b": -3e7, "c": None}
        assert docs[1] == {"a": 7, "b": None, "c": None}

    def test_inference_parity_with_python(self):
        """Both ingest paths must store identical values (backends are
        interchangeable) — including the awkward cells."""
        from learningorchestra_tpu.services.dataset import _infer

        cells = ["7", "-3", "+5", "007", " 12 ", "0.5", ".5", "5.", "1e5",
                 "-2.5E-3", "9223372036854775808", "1_000", "0x10", "NaN",
                 "Infinity", "-inf", "abc", "", "true", "12abc", "3.14.15"]
        # "" must be written quoted: a bare empty line is a blank ROW
        # (skipped by both paths), not a row with one empty cell.
        data = ("c\n" + "\n".join(c if c else '""' for c in cells)
                + "\n").encode()
        _, jsonl = native.csv_parse(data)
        native_vals = [json.loads(ln)["c"] for ln in jsonl.splitlines()]
        python_vals = [_infer(c) for c in cells]
        assert native_vals == python_vals, list(
            zip(cells, native_vals, python_vals)
        )

    def test_ingest_jsonl_into_store(self, store):
        data = b"x,y\n1,2\n3,4\n5,6\n"
        fields, jsonl = native.csv_parse(data)
        n = store.insert_jsonl("ds", jsonl)
        assert n == 3
        assert store.find_one("ds", 1) == {"x": 3, "y": 4, "_id": 1}


class TestNativeConcurrency:
    def test_parallel_inserts_unique_ids(self, store):
        errs = []

        def worker():
            try:
                for _ in range(200):
                    store.insert_one("c", {"t": threading.get_ident()})
            except Exception as e:  # pragma: no cover
                errs.append(e)

        threads = [threading.Thread(target=worker) for _ in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert not errs
        docs = store.find("c")
        assert len(docs) == 1600
        assert len({d["_id"] for d in docs}) == 1600


class TestThreadSanitizer:
    def test_tsan_stress_clean(self, tmp_path):
        """Build the -fsanitize=thread stress binary and run it: any data
        race in the native store fails this test (TSAN halt_on_error).
        The reference ships no race detection at all (SURVEY §5.2)."""
        import os
        import subprocess

        native_dir = (
            __import__("pathlib").Path(__file__).parent.parent / "native"
        )
        try:
            build = subprocess.run(
                ["make", "-C", str(native_dir), "tsan"],
                capture_output=True, timeout=120,
            )
        except FileNotFoundError:
            pytest.skip("make not installed")
        if build.returncode != 0:
            pytest.skip(f"tsan build unavailable: {build.stderr[-200:]}")
        run = subprocess.run(
            [str(native_dir / "build" / "stress_tsan"), str(tmp_path / "s")],
            capture_output=True, timeout=120,
            env={**os.environ, "TSAN_OPTIONS": "halt_on_error=1"},
        )
        assert run.returncode == 0, (
            run.stdout[-500:], run.stderr[-2000:]
        )


class TestNativeProjection:
    def test_project_matches_python_semantics(self, store):
        store.insert_unique("src", {"name": "src", "finished": True}, 0)
        store.insert_many("src", [
            {"a": i, "b": i * 2, "c": f"s{i}"} for i in range(10)
        ])
        store.insert_one("src", {"a": 99, "docType": "execution"})
        store.insert_unique("dst", {"name": "dst"}, 0)  # metadata first
        n = store.project("src", "dst", ["a", "c", "missing"])
        assert n == 10  # execution doc and metadata excluded
        rows = [d for d in store.find("dst") if d["_id"] >= 1]
        assert rows[0] == {"a": 0, "c": "s0", "missing": None, "_id": 1}
        assert rows[-1]["a"] == 9

    def test_project_missing_source(self, store):
        with pytest.raises((NoSuchCollection, RuntimeError)):
            store.project("ghost", "dst2", ["a"])


class TestNativeNumericChunkParser:
    """lods_csv_numeric_chunk — the sharded-ingest hot path."""

    def test_chunk_semantics_and_nan_contract(self):
        import numpy as np

        data = b"1,2.5,3\n4,,x\n7,8,9"
        bad = np.zeros(3, np.int64)
        block, consumed = native.csv_numeric_chunk(
            data, 3, is_final=False, bad_counts=bad
        )
        # Partial trailing record ("7,8,9" without newline) held back.
        assert consumed == len(b"1,2.5,3\n4,,x\n")
        assert block.shape == (2, 3)
        assert block[0].tolist() == [1, 2.5, 3]
        assert block[1][0] == 4
        assert np.isnan(block[1][1])  # empty cell -> NaN, not bad
        assert np.isnan(block[1][2])  # unparseable -> NaN AND bad
        assert bad.tolist() == [0, 0, 1]
        block2, c2 = native.csv_numeric_chunk(
            data[consumed:], 3, is_final=True, bad_counts=bad
        )
        assert block2.shape == (1, 3)
        assert block2[0].tolist() == [7, 8, 9]

    def test_chunk_boundary_inside_quoted_field(self):
        """A chunk ending on a newline INSIDE a quoted field must roll
        the record back (buf[-1]=='\\n' alone is not record-complete)."""
        import numpy as np

        bad = np.zeros(2, np.int64)
        full = b'1,2\n3,"4\n'  # quoted cell containing the newline...
        block, consumed = native.csv_numeric_chunk(
            full, 2, is_final=False, bad_counts=bad
        )
        assert block.shape == (1, 2) and block[0].tolist() == [1, 2]
        assert consumed == len(b"1,2\n")  # partial quoted record held
        rest = full[consumed:] + b'5"\n'
        block2, c2 = native.csv_numeric_chunk(
            rest, 2, is_final=True, bad_counts=bad
        )
        # The quoted cell "4\n5" is non-numeric -> NaN + bad count,
        # but the record boundary is right.
        assert block2.shape == (1, 2) and block2[0][0] == 3
        assert bad.tolist() == [0, 1]

    def test_numeric_contract_matches_python_infer(self):
        """inf/nan/hex/'_' spellings are non-numeric (same as _infer);
        subnormal underflow is a fine number."""
        import numpy as np

        bad = np.zeros(5, np.int64)
        data = b"inf,nan,0x10,1_0,1e-310\n"
        block, consumed = native.csv_numeric_chunk(
            data, 5, is_final=True, bad_counts=bad
        )
        assert consumed == len(data)
        assert bad.tolist() == [1, 1, 1, 1, 0]
        assert np.isnan(block[0][:4]).all()
        assert block[0][4] == 1e-310

    def test_quotes_short_rows_and_blanks(self):
        import numpy as np

        bad = np.zeros(4, np.int64)
        data = b'"5","6.5",7,8\n\n1,2\n'
        block, consumed = native.csv_numeric_chunk(
            data, 4, is_final=True, bad_counts=bad
        )
        assert consumed == len(data)
        assert block.shape == (2, 4)
        assert block[0].tolist() == [5, 6.5, 7, 8]
        assert block[1][0] == 1 and block[1][1] == 2
        assert np.isnan(block[1][2]) and np.isnan(block[1][3])
        assert bad.sum() == 0  # short rows pad NaN without flagging


class TestNativeShardedIngest:
    """REST sharded ingest runs through the native block path and
    matches the Python row path bit-for-bit."""

    def _serve(self, tmp_path):
        from learningorchestra_tpu.api.server import APIServer
        from learningorchestra_tpu.config import Config

        cfg = Config()
        cfg.store.root = str(tmp_path / "store")
        cfg.store.volume_root = str(tmp_path / "volumes")
        server = APIServer(cfg)
        port = server.start_background()
        return server, f"http://127.0.0.1:{port}/api/learningOrchestra/v1"

    def test_parity_with_python_path(self, tmp_path):
        import glob as _glob
        import time

        import numpy as np
        import requests

        import learningorchestra_tpu.services.dataset as dsmod
        from learningorchestra_tpu.store.sharded import ShardedDataset

        rng = np.random.default_rng(0)
        n = 3000
        X = rng.standard_normal((n, 3)).astype(np.float32)
        y = (X.sum(1) > 0).astype(np.int32)
        path = tmp_path / "d.csv"
        with open(path, "w") as fh:
            fh.write("a,b,c,label\n")
            for i in range(n):
                fh.write(",".join(f"{v:.5f}" for v in X[i])
                         + f",{y[i]}\n")

        server, base = self._serve(tmp_path)

        def poll(p):
            for _ in range(300):
                m = requests.get(base + p).json()[0]
                if m.get("jobState") in ("finished", "failed"):
                    return m
                time.sleep(0.05)
            raise AssertionError("timeout")

        try:
            r = requests.post(base + "/dataset/csv", json={
                "datasetName": "nat", "url": f"file://{path}",
                "shardRows": 1024})
            assert r.status_code == 201, r.text
            m = poll("/dataset/csv/nat")
            assert m["jobState"] == "finished", m
            assert m.get("engine") == "native"
            assert m["rows"] == n and m["shards"] == 3
            assert m["previewRows"] == 100

            orig = dsmod.DatasetService._ingest_sharded_native
            dsmod.DatasetService._ingest_sharded_native = (
                lambda *a, **k: None
            )
            try:
                r = requests.post(base + "/dataset/csv", json={
                    "datasetName": "pyp", "url": f"file://{path}",
                    "shardRows": 1024})
                assert r.status_code == 201, r.text
                m2 = poll("/dataset/csv/pyp")
                assert m2["jobState"] == "finished", m2
                assert "engine" not in m2
            finally:
                dsmod.DatasetService._ingest_sharded_native = orig

            vols = str(tmp_path / "volumes")
            dsn = ShardedDataset(
                _glob.glob(vols + "/**/nat", recursive=True)[0]
            )
            dsp = ShardedDataset(
                _glob.glob(vols + "/**/pyp", recursive=True)[0]
            )
            assert dsn.dtypes == dsp.dtypes  # int label survives
            for k in range(dsn.n_shards):
                sa = dsn.load_shard(k)
                sb = dsp.load_shard(k)
                for col in sa:
                    np.testing.assert_allclose(
                        sa[col], sb[col], atol=1e-5
                    )

            # Non-numeric column fails the job with the same message
            # shape as the Python path.
            bad_csv = tmp_path / "bad.csv"
            bad_csv.write_text(
                "a,word\n1,hello\n2,world\n"
            )
            r = requests.post(base + "/dataset/csv", json={
                "datasetName": "badn", "url": f"file://{bad_csv}",
                "shardRows": 8})
            assert r.status_code == 201
            m3 = poll("/dataset/csv/badn")
            assert m3["jobState"] == "failed"
            assert "not numeric" in str(m3.get("exception", m3))
        finally:
            server.shutdown()


class TestNativeIngestProperty:
    def test_random_csvs_match_python_path(self, tmp_path):
        """Property check: random numeric CSVs (empties, short rows,
        \\r\\n, quoted cells, blank lines) shard identically through
        the native block path and the Python row path."""
        import glob as _glob
        import time

        import numpy as np
        import requests

        import learningorchestra_tpu.services.dataset as dsmod
        from learningorchestra_tpu.api.server import APIServer
        from learningorchestra_tpu.config import Config
        from learningorchestra_tpu.store.sharded import ShardedDataset

        rng = np.random.default_rng(7)
        cfg = Config()
        cfg.store.root = str(tmp_path / "store")
        cfg.store.volume_root = str(tmp_path / "volumes")
        server = APIServer(cfg)
        port = server.start_background()
        base = f"http://127.0.0.1:{port}/api/learningOrchestra/v1"

        def poll(p):
            for _ in range(400):
                m = requests.get(base + p).json()[0]
                if m.get("jobState") in ("finished", "failed"):
                    return m
                time.sleep(0.05)
            raise AssertionError("timeout")

        def random_csv(path, n, ncols, seed):
            r = np.random.default_rng(seed)
            eol = "\r\n" if seed % 2 else "\n"
            with open(path, "w", newline="") as fh:
                fh.write(",".join(f"c{i}" for i in range(ncols)) + eol)
                for _ in range(n):
                    cells = []
                    for c in range(ncols):
                        u = r.random()
                        if u < 0.05:
                            cells.append("")  # empty -> NaN
                        elif u < 0.1:
                            cells.append(f'"{r.integers(0, 99)}"')
                        elif u < 0.5:
                            cells.append(str(int(r.integers(-50, 50))))
                        else:
                            cells.append(f"{r.standard_normal():.6f}")
                    if r.random() < 0.05:
                        cells = cells[: max(1, ncols - 2)]  # short row
                    fh.write(",".join(cells) + eol)
                    if r.random() < 0.03:
                        fh.write(eol)  # blank line
        try:
            for seed in range(3):
                n, ncols = int(rng.integers(200, 800)), int(
                    rng.integers(2, 6)
                )
                path = tmp_path / f"r{seed}.csv"
                random_csv(path, n, ncols, seed)
                names = []
                for label, patch in (("nat", False), ("pyp", True)):
                    name = f"{label}{seed}"
                    names.append(name)
                    orig = dsmod.DatasetService._ingest_sharded_native
                    if patch:
                        dsmod.DatasetService._ingest_sharded_native = (
                            lambda *a, **k: None
                        )
                    try:
                        r = requests.post(base + "/dataset/csv", json={
                            "datasetName": name,
                            "url": f"file://{path}",
                            "shardRows": 128})
                        assert r.status_code == 201, r.text
                        m = poll(f"/dataset/csv/{name}")
                        assert m["jobState"] == "finished", m
                    finally:
                        dsmod.DatasetService._ingest_sharded_native = orig
                vols = str(tmp_path / "volumes")
                a = ShardedDataset(_glob.glob(
                    vols + f"/**/{names[0]}", recursive=True)[0])
                b = ShardedDataset(_glob.glob(
                    vols + f"/**/{names[1]}", recursive=True)[0])
                assert a.n_rows == b.n_rows == n
                assert a.dtypes == b.dtypes, (seed, a.dtypes, b.dtypes)
                for k in range(a.n_shards):
                    sa, sb = a.load_shard(k), b.load_shard(k)
                    for col in sa:
                        np.testing.assert_array_equal(
                            np.isnan(sa[col].astype(np.float64)),
                            np.isnan(sb[col].astype(np.float64)),
                            err_msg=f"seed {seed} shard {k} {col}",
                        )
                        np.testing.assert_allclose(
                            np.nan_to_num(sa[col].astype(np.float64)),
                            np.nan_to_num(sb[col].astype(np.float64)),
                            atol=1e-6,
                            err_msg=f"seed {seed} shard {k} {col}",
                        )
        finally:
            server.shutdown()


class TestBuildOnDemand:
    """``ensure_built`` decides staleness by the source's content hash
    (a copy or checkout resets mtimes) and never swallows a compiler
    failure."""

    def _isolate(self, tmp_path, monkeypatch):
        monkeypatch.setattr(native, "_LIB_PATH", tmp_path / "lib.so")
        monkeypatch.setattr(native, "_build_failed", False)

    def test_failed_build_is_logged_with_compiler_stderr(
        self, tmp_path, monkeypatch
    ):
        import subprocess

        self._isolate(tmp_path, monkeypatch)

        def failing_make(cmd, **kw):
            raise subprocess.CalledProcessError(
                2, cmd, stderr="docstore.cpp:1:1: error: no such type"
            )

        monkeypatch.setattr(subprocess, "run", failing_make)
        # The repo's logger does not propagate to pytest's capture
        # (log.py): listen on it directly.
        import logging

        records: list = []
        handler = logging.Handler()
        handler.emit = records.append
        logger = logging.getLogger("lo.native")
        logger.addHandler(handler)
        try:
            assert native.ensure_built() is None
        finally:
            logger.removeHandler(handler)
        assert [r.levelname for r in records] == ["ERROR"]
        assert "docstore.cpp:1:1: error: no such type" in \
            records[0].getMessage()

    def test_hash_mismatch_rebuilds_whatever_the_mtimes_say(
        self, tmp_path, monkeypatch
    ):
        import subprocess

        self._isolate(tmp_path, monkeypatch)
        lib = tmp_path / "lib.so"
        lib.write_bytes(b"stale")  # newer than the source by mtime
        lib.with_suffix(".srchash").write_text("not-the-source-hash")
        calls = []
        monkeypatch.setattr(
            subprocess, "run",
            lambda cmd, **kw: calls.append(cmd),
        )
        assert native.ensure_built() == lib
        assert calls and "-B" in calls[0]
        # Stamped with the real hash: the next call builds nothing.
        assert native.ensure_built() == lib
        assert len(calls) == 1
