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
