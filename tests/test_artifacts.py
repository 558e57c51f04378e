"""Tests for metadata/lineage/ledger semantics (SURVEY §1 cross-cutting
data model)."""

import pytest

from learningorchestra_tpu.store import LineageError


def test_metadata_lifecycle(artifacts):
    meta = artifacts.metadata.create("ds1", "dataset/csv")
    assert meta["finished"] is False
    assert meta["jobState"] == "pending"
    assert artifacts.metadata.exists("ds1")
    assert not artifacts.metadata.is_finished("ds1")

    artifacts.metadata.mark_running("ds1")
    assert artifacts.metadata.read("ds1")["jobState"] == "running"

    artifacts.metadata.mark_finished("ds1", {"fields": ["a", "b"]})
    doc = artifacts.metadata.read("ds1")
    assert doc["finished"] is True
    assert doc["fields"] == ["a", "b"]


def test_metadata_failure_and_restart(artifacts):
    artifacts.metadata.create("j", "train/tensorflow")
    artifacts.metadata.mark_failed("j", "ValueError('boom')")
    doc = artifacts.metadata.read("j")
    assert doc["jobState"] == "failed"
    assert doc["finished"] is False
    artifacts.metadata.restart("j")
    doc = artifacts.metadata.read("j")
    assert doc["jobState"] == "pending"
    assert doc["exception"] is None


def test_lineage_walk_to_model(artifacts):
    """A predict step must find the model spec behind a train step by
    walking parentName upward (reference:
    binary_executor_image/utils.py:261-280)."""
    artifacts.metadata.create(
        "m", "model/tensorflow", module_path="zoo.cnn", class_name="MnistCNN"
    )
    artifacts.metadata.create("t", "train/tensorflow", parent_name="m")
    artifacts.metadata.create("p", "predict/tensorflow", parent_name="t")
    model = artifacts.metadata.find_model_ancestor("p")
    assert model["name"] == "m"
    assert model["class"] == "MnistCNN"


def test_lineage_missing_parent_raises(artifacts):
    artifacts.metadata.create("t", "train/x", parent_name="ghost")
    with pytest.raises(LineageError):
        artifacts.metadata.parent_chain("t")


def test_lineage_cycle_detected(artifacts):
    artifacts.metadata.create("a", "train/x", parent_name="b")
    artifacts.metadata.create("b", "train/x", parent_name="a")
    with pytest.raises(LineageError):
        artifacts.metadata.parent_chain("a")


def test_ledger_records_and_history(artifacts):
    artifacts.metadata.create("j", "train/x")
    artifacts.ledger.record(
        "j", description="run 1", method="fit", state="finished",
        metrics={"loss": 0.5},
    )
    artifacts.ledger.record(
        "j", description="run 2", state="failed", exception="OOM"
    )
    hist = artifacts.ledger.history("j")
    assert len(hist) == 2
    assert hist[0]["metrics"]["loss"] == 0.5
    assert hist[1]["exception"] == "OOM"


def test_read_page_metadata_first(artifacts):
    """Clients read `finished` from the first doc of page 1 — metadata is
    _id=0 and results sort by _id (reference: database_api_image/
    server.py:52-80)."""
    artifacts.metadata.create("r", "predict/x")
    for i in range(5):
        artifacts.documents.insert_one("r", {"row": i})
    page = artifacts.read_page("r", limit=3)
    assert page[0]["_id"] == 0
    assert "finished" in page[0]


def test_list_by_type(artifacts):
    artifacts.metadata.create("d1", "dataset/csv")
    artifacts.metadata.create("d2", "dataset/generic")
    artifacts.metadata.create("m1", "model/tensorflow")
    names = {m["name"] for m in artifacts.list_by_type("dataset")}
    assert names == {"d1", "d2"}


def test_volume_roundtrip(volumes):
    import numpy as np

    tree = {"w": np.arange(6).reshape(2, 3), "b": np.zeros(3)}
    volumes.save_pytree("train/tensorflow", "t1", tree)
    back = volumes.read_pytree("train/tensorflow", "t1")
    assert np.array_equal(back["w"], tree["w"])

    volumes.save_object("model/scikitlearn", "m1", {"k": 1})
    assert volumes.read_object("model/scikitlearn", "m1") == {"k": 1}
    assert volumes.exists("model/scikitlearn", "m1")
    assert volumes.delete("model/scikitlearn", "m1")
    assert not volumes.exists("model/scikitlearn", "m1")


def test_large_object_is_written_as_parts(volumes, monkeypatch):
    """An object bigger than the part size never becomes one big file
    (a host's file-size limit fails that write with EFBIG): the
    artifact's path holds a manifest, the bytes sit in part files, and
    a rewrite or a delete takes the superseded parts with it."""
    import numpy as np

    volumes.part_bytes = 1000
    big = {"w": np.arange(5000, dtype=np.float32), "tag": "x" * 10}
    path = volumes.save_object("train/tensorflow", "t1", big)
    parts = path.with_name(".t1.parts")
    assert path.is_file() and len(list(parts.iterdir())) > 20
    assert max(p.stat().st_size for p in parts.iterdir()) <= 1000
    back = volumes.read_object("train/tensorflow", "t1")
    assert np.array_equal(back["w"], big["w"]) and back["tag"] == big["tag"]
    assert volumes.exists("train/tensorflow", "t1")

    # Rewritten larger: only the new generation's parts remain.
    volumes.save_object("train/tensorflow", "t1", {"w": np.arange(9000)})
    names = {p.name.split("-")[0] for p in parts.iterdir()}
    assert len(names) == 1
    assert np.array_equal(
        volumes.read_object("train/tensorflow", "t1")["w"], np.arange(9000)
    )
    # Rewritten to fit one part: the plain dill file it always was.
    volumes.save_object("train/tensorflow", "t1", {"k": 1})
    assert not parts.exists()
    assert volumes.read_object("train/tensorflow", "t1") == {"k": 1}

    # A reader that loses its parts to a concurrent rewrite (here: at
    # its first read) loads what the rewrite published instead.
    from learningorchestra_tpu.store import volumes as volumes_mod

    volumes.save_object("train/tensorflow", "t1", big)
    readinto = volumes_mod._PartReader.readinto
    rewrites = []

    def racing_readinto(self, buf):
        if not rewrites:
            rewrites.append(volumes.save_object(
                "train/tensorflow", "t1", {"w": np.arange(7000)}
            ))
        return readinto(self, buf)

    monkeypatch.setattr(volumes_mod._PartReader, "readinto", racing_readinto)
    assert np.array_equal(
        volumes.read_object("train/tensorflow", "t1")["w"], np.arange(7000)
    )

    assert volumes.delete_everywhere("t1")
    assert list(path.parent.iterdir()) == []


def test_part_size_yields_to_the_file_size_limit():
    import resource

    from learningorchestra_tpu.store.volumes import PART_BYTES, max_file_bytes

    soft, hard = resource.getrlimit(resource.RLIMIT_FSIZE)
    limit = PART_BYTES // 4
    resource.setrlimit(resource.RLIMIT_FSIZE, (limit, hard))
    try:
        assert max_file_bytes() == limit
        resource.setrlimit(resource.RLIMIT_FSIZE, (PART_BYTES * 4, hard))
        assert max_file_bytes() == PART_BYTES
    finally:
        resource.setrlimit(resource.RLIMIT_FSIZE, (soft, hard))


def _raw_parts(path):
    return sorted(
        p for p in path.with_name("." + path.name + ".parts").iterdir()
        if "-r" in p.name
    )


@pytest.mark.parametrize("dtype", ["float32", "bfloat16", "int32"])
def test_large_arrays_round_trip_raw(volumes, dtype):
    """An array of at least RAW_MIN_BYTES goes raw beside the pickle
    stream and comes back bit for bit and writable; small,
    non-contiguous and object-dtype arrays stay in the stream; one
    array reached twice comes back as one array."""
    import ml_dtypes
    import numpy as np

    from learningorchestra_tpu.store.volumes import RAW_MIN_BYTES

    rng = np.random.default_rng(0)
    n = RAW_MIN_BYTES // 4 + 3
    big = rng.integers(-(2**31), 2**31 - 1, n, dtype=np.int64).astype(
        np.int32
    ).view(np.float32 if dtype == "float32" else np.int32)
    if dtype == "bfloat16":
        big = big.view(ml_dtypes.bfloat16).reshape(2, -1)
    obj = {
        "big": big, "again": big, "small": np.arange(100.0),
        "strided": rng.standard_normal((512, 512))[:, ::2],
        "objects": np.array([1, "x", None] * 50000, dtype=object),
        "tag": "x",
    }
    volumes.save_object("train/tensorflow", "t1", obj)
    assert volumes.raw_extent("train/tensorflow", "t1") == {
        "raw_bytes": big.nbytes, "raw_arrays": 1,
    }
    back = volumes.read_object("train/tensorflow", "t1")
    assert back["big"].dtype == big.dtype and back["big"].shape == big.shape
    assert back["big"].tobytes() == big.tobytes()
    assert back["big"].flags.writeable and back["again"] is back["big"]
    assert np.array_equal(back["small"], obj["small"])
    assert np.array_equal(back["strided"], obj["strided"])
    assert back["objects"].dtype == object
    assert list(back["objects"]) == list(obj["objects"])
    assert back["tag"] == "x"


@pytest.mark.parametrize("axes", [(0, 1, 2), (2, 1, 0), (0, 2, 1)],
                         ids=["c", "fortran", "turned"])
def test_a_dense_array_goes_raw_in_its_own_order(volumes, axes):
    """A dense array whose axes lie in memory in another order than C's
    (what a TPU's host copy of a 3-d kernel holds) goes raw as it lies
    and comes back bit for bit with the same strides."""
    import numpy as np

    stored = np.arange(64 * 36 * 128, dtype=np.float32).reshape(
        [(64, 36, 128)[i] for i in axes]
    )
    array = stored.transpose(np.argsort(axes))
    assert array.shape == (64, 36, 128)
    path = volumes.save_object("train/tensorflow", "t1", {"k": array})
    assert volumes.raw_extent("train/tensorflow", "t1") == {
        "raw_bytes": array.nbytes, "raw_arrays": 1,
    }
    assert [p.read_bytes() for p in _raw_parts(path)] == [stored.tobytes()]
    back = volumes.read_object("train/tensorflow", "t1")["k"]
    assert back.strides == array.strides and back.flags.writeable
    assert np.array_equal(back, array)


def test_raw_parts_stay_under_the_part_size(volumes):
    import numpy as np

    volumes.part_bytes = 256 << 10
    big = np.arange(3 << 18, dtype=np.float32)  # 3 MiB: 12 parts
    path = volumes.save_object("train/tensorflow", "t1", {"w": big})
    parts = _raw_parts(path)
    assert len(parts) == 12
    assert max(p.stat().st_size for p in parts) <= volumes.part_bytes
    assert np.array_equal(
        volumes.read_object("train/tensorflow", "t1")["w"], big
    )


def test_raw_rewrite_leaves_only_the_new_generation(volumes):
    import numpy as np

    volumes.part_bytes = 256 << 10
    path = volumes.save_object(
        "train/tensorflow", "t1", {"w": np.ones(1 << 19, np.float32)}
    )
    parts = path.with_name(".t1.parts")
    volumes.save_object(
        "train/tensorflow", "t1", {"w": np.zeros(1 << 18, np.float64)}
    )
    assert len({p.name.split("-")[0] for p in parts.iterdir()}) == 1
    assert len(_raw_parts(path)) == 8
    back = volumes.read_object("train/tensorflow", "t1")["w"]
    assert back.dtype == np.float64 and not back.any()
    volumes.save_object("train/tensorflow", "t1", {"k": 1})
    assert not parts.exists()
    assert volumes.delete("train/tensorflow", "t1")


def test_a_racing_rewrite_during_a_raw_read(volumes, monkeypatch):
    """A reader whose raw parts a concurrent rewrite removes loads what
    the rewrite published."""
    import numpy as np

    from learningorchestra_tpu.store import volumes as volumes_mod

    volumes.part_bytes = 256 << 10
    first = {"w": np.ones(1 << 19, np.float32)}
    after = {"w": np.full(1 << 19, 7, np.int32)}
    volumes.save_object("train/tensorflow", "t1", first)
    read_file = volumes_mod._read_file
    rewrites = []

    def racing_read_file(path, buf):
        if not rewrites:
            rewrites.append(
                volumes.save_object("train/tensorflow", "t1", after)
            )
        return read_file(path, buf)

    monkeypatch.setattr(volumes_mod, "_read_file", racing_read_file)
    back = volumes.read_object("train/tensorflow", "t1")["w"]
    assert rewrites and back.dtype == np.int32
    assert np.array_equal(back, after["w"])


@pytest.mark.parametrize("form", ["plain", "manifest"])
def test_artifacts_written_before_raw_arrays_still_read(volumes, form):
    """A plain dill file, and a manifest with no ``raw`` list, as the
    store wrote them before arrays went raw."""
    import json
    import uuid

    import dill
    import numpy as np

    from learningorchestra_tpu.store import volumes as volumes_mod

    obj = {"w": np.arange(1 << 19, dtype=np.float32), "tag": "old"}
    path = volumes.path_for("train/tensorflow", "t1")
    if form == "plain":
        path.write_bytes(dill.dumps(obj))
    else:
        directory = path.with_name(".t1.parts")
        writer = volumes_mod._PartWriter(directory, uuid.uuid4().hex, 1 << 20)
        dill.dump(obj, writer)
        writer.close()
        assert len(writer.parts) > 1
        path.write_bytes(volumes_mod._MANIFEST_MAGIC + json.dumps({
            "gen": writer.gen, "parts": len(writer.parts),
        }).encode())
    back = volumes.read_object("train/tensorflow", "t1")
    assert np.array_equal(back["w"], obj["w"]) and back["tag"] == "old"
    assert volumes.raw_extent("train/tensorflow", "t1") == {
        "raw_bytes": 0, "raw_arrays": 0,
    }


def test_object_bytes_counts_raw_parts(volumes):
    import numpy as np

    path = volumes.save_object("train/tensorflow", "t1", {
        "w": np.ones(3 << 18, np.float32), "b": np.ones(4 << 18, np.int8),
    })
    on_disk = sum(
        p.stat().st_size for p in path.with_name(".t1.parts").iterdir()
    )
    assert volumes.object_bytes("train/tensorflow", "t1") == on_disk
    assert on_disk > volumes.raw_extent(
        "train/tensorflow", "t1"
    )["raw_bytes"] == (3 << 20) + (4 << 18)


@pytest.mark.parametrize("part_bytes", [1 << 30, 4096], ids=["one", "parts"])
def test_an_object_with_no_large_array_is_written_as_before(
    volumes, part_bytes
):
    """No array reaches RAW_MIN_BYTES: the bytes on disk are dill's
    stream, as one plain file or as parts behind the same manifest."""
    import json

    import dill
    import numpy as np

    from learningorchestra_tpu.store import volumes as volumes_mod

    volumes.part_bytes = part_bytes
    obj = {
        "w": np.arange((1 << 18) - 1, dtype=np.float32),
        "big_strided": np.ones((1024, 1024))[:, ::2], "k": [1, "two"],
    }
    path = volumes.save_object("train/tensorflow", "t1", obj)
    manifest = volumes_mod._manifest_of(path)
    if manifest is None:
        written = path.read_bytes()
    else:
        assert set(manifest) == {"gen", "parts"}
        written = b"".join(
            p.read_bytes() for p in volumes_mod._part_paths(
                path.with_name(".t1.parts"), manifest
            )
        )
    assert written == dill.dumps(obj)
    assert (manifest is None) == (part_bytes > len(written))
    assert json.dumps(volumes.raw_extent("train/tensorflow", "t1")) == (
        '{"raw_bytes": 0, "raw_arrays": 0}'
    )


@pytest.mark.parametrize("async_save", [False, True], ids=["sync", "async"])
def test_checkpoint_files_stay_under_the_file_size_bound(
    tmp_path, monkeypatch, async_save
):
    """orbax's data-file size is a target, not a bound: a file under it
    takes one more chunk.  With both at a quarter of the bound a file
    of arrays that do not compress stays under half of it (the rest is
    the root node's, which no option bounds)."""
    import numpy as np

    from learningorchestra_tpu.store import volumes as volumes_mod
    from learningorchestra_tpu.train import checkpoint

    bound = 128 << 10
    monkeypatch.setattr(volumes_mod, "max_file_bytes", lambda: bound)
    rng = np.random.default_rng(0)
    state = {
        "params": {
            f"w{i}": rng.integers(
                0, 2**32, size=(rows, 128), dtype=np.uint32
            )
            for i, rows in enumerate((127, 127, 127, 120, 100, 64, 33, 200))
        },
        "opt_state": {"count": np.zeros((), np.int32)},
    }
    checkpoint.save(tmp_path, 1, state, async_save=async_save)
    checkpoint.finalize_async(tmp_path)
    largest = max(
        p.stat().st_size for p in tmp_path.rglob("*") if p.is_file()
    )
    assert largest <= bound // 2 + 1024
    back, step, _ = checkpoint.load_latest(tmp_path, state)
    assert step == 1
    for name, want in state["params"].items():
        assert np.array_equal(back["params"][name], want)
