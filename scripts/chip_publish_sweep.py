"""Where a job's ``publish`` goes on the chip's host, old path against
new: the measurement ``store/volumes.py``'s raw arrays were built on.

    python scripts/chip_publish_sweep.py              # bert-base, gpt2-xl
    python scripts/chip_publish_sweep.py bert-base    # one tree

on a machine that holds one TPU chip (it runs on the CPU too, where it
times the host alone).

Each tree is a ``NeuralEstimator`` as a train job publishes it:
``bert-base`` is ``bert-base.fit-s512``'s model with its parameters and
Adam state on the device (1.31 GB of float32), ``gpt2-xl`` the weights
job's estimator of ``gpt2-xl.gen-decode`` (6.2 GB, parameters only).
The leaves come from ``benchmarks/lobench/weights.py``.  Phases, each
timed ``REPS`` times on an artifact name of its own, medians reported:

- ``device_get``: the estimator's ``__getstate__`` fetch of its trees;
- ``old_pickle``: dill of the host estimator into a sink that keeps
  nothing (the stream's copy, no file);
- ``old_write`` / ``new_write``: the whole write of the host estimator,
  the old one ``dill.dump`` into 16 MiB parts, the new ``save_object``;
- ``old_publish`` / ``new_publish``: the same from the device estimator,
  as the job's ``publish`` span holds it (``device_get`` inside);
- ``old_read`` / ``new_read``: the artifact read back.

One JSON line a phase goes to stdout and the table to
``chiprun_out/publish_sweep.json``.
"""

from __future__ import annotations

import io
import json
import os
import shutil
import statistics
import sys
import time
import uuid
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
sys.path.insert(0, str(REPO))
sys.path.insert(0, str(REPO / "benchmarks"))

import dill
import jax
import jax.numpy as jnp
import optax

from learningorchestra_tpu.store import volumes
from learningorchestra_tpu.toolkit import registry
from lobench import loader, weights

REPS = 3
TREES = {
    "bert-base": ("bert-base-uncased", True),
    "gpt2-xl": ("gpt2-xl", False),
}


class _Sink:
    def write(self, data) -> int:
        return len(memoryview(data))


def old_dump(path: Path, obj, part_bytes: int) -> None:
    """The write before raw arrays: one dill stream through 16 MiB
    parts, then the manifest."""
    directory = volumes._parts_dir(path)
    writer = volumes._PartWriter(directory, uuid.uuid4().hex, part_bytes)
    try:
        dill.dump(obj, writer)
    finally:
        writer.close()
    tmp = directory / f"{writer.gen}-manifest"
    tmp.write_bytes(volumes._MANIFEST_MAGIC + json.dumps({
        "gen": writer.gen, "parts": len(writer.parts),
    }).encode())
    os.replace(tmp, path)


def old_read(path: Path):
    manifest = volumes._manifest_of(path)
    stream = io.BufferedReader(volumes._PartReader(
        volumes._part_paths(volumes._parts_dir(path), manifest)
    ), buffer_size=1 << 20)
    with stream:
        return dill.load(stream)


def estimator(config_name: str, with_adam: bool):
    bench = loader.benchmark()
    config, module = loader.config(loader.config_path(bench, config_name))
    cp = config["class_parameters"]
    params = module.program_params(
        weights.make_flat(1, module.leaves(cp)), cp
    )
    est = registry.resolve(config["module_path"], config["class"])(**cp)
    est.params = params
    est.opt_state = optax.adam(1e-3).init(params) if with_adam else None
    jax.block_until_ready((est.params, est.opt_state))
    return est


def refresh(est) -> None:
    """Fresh device arrays for the estimator's trees, made leaf by leaf
    (one tree on the device at a time): jax keeps a fetched array's
    host copy, so each timed fetch must be of arrays never fetched."""
    leaves, treedef = jax.tree_util.tree_flatten((est.params,
                                                  est.opt_state))
    est.params = est.opt_state = None
    for i, leaf in enumerate(leaves):
        leaves[i] = jnp.copy(leaf)
        del leaf
    est.params, est.opt_state = jax.block_until_ready(
        jax.tree_util.tree_unflatten(treedef, leaves)
    )


def timed(fn) -> float:
    t0 = time.perf_counter()
    fn()
    return time.perf_counter() - t0


def sweep(name: str, store: volumes.VolumeStorage) -> dict:
    config_name, with_adam = TREES[name]
    est = estimator(config_name, with_adam)
    host = type(est).__new__(type(est))
    host.__dict__.update(est.__dict__)
    host.params, host.opt_state = jax.device_get((est.params,
                                                  est.opt_state))
    leaves = jax.tree_util.tree_leaves((host.params, host.opt_state))
    row = {
        "tree": name, "bytes": int(sum(a.nbytes for a in leaves)),
        "leaves": len(leaves),
        "raw_leaves": sum(
            volumes._raw_axes(a) is not None for a in leaves
        ),
        # Large leaves the host copy holds in another order than C's
        # (shape, byte strides): they go raw in that order.
        "turned_leaves": sorted({
            (a.shape, a.strides) for a in leaves
            if a.nbytes >= volumes.RAW_MIN_BYTES and not a.flags.c_contiguous
        }),
    }
    times: dict[str, list] = {}
    for rep in range(REPS):
        def path(phase):
            return store.path_for("train/tensorflow", f"{phase}{rep}")

        refresh(est)
        times.setdefault("device_get", []).append(timed(
            lambda: jax.device_get((est.params, est.opt_state))
        ))
        times.setdefault("old_pickle", []).append(timed(
            lambda: dill.dump(host, _Sink())
        ))
        for phase, obj in (("write", host), ("publish", est)):
            refresh(est)
            times.setdefault(f"old_{phase}", []).append(timed(
                lambda: old_dump(path(f"old{phase}"), obj, store.part_bytes)
            ))
            refresh(est)
            times.setdefault(f"new_{phase}", []).append(timed(
                lambda: store._dump_atomic(path(f"new{phase}"), obj)
            ))
        row["old_disk_bytes"] = store.object_bytes(
            "train/tensorflow", f"oldwrite{rep}")
        row["new_disk_bytes"] = store.object_bytes(
            "train/tensorflow", f"newwrite{rep}")
        row.update(store.raw_extent("train/tensorflow", f"newwrite{rep}"))
        times.setdefault("old_read", []).append(timed(
            lambda: old_read(path("oldwrite"))
        ))
        times.setdefault("new_read", []).append(timed(
            lambda: store.read_object("train/tensorflow", f"newwrite{rep}")
        ))
        back = store.read_object("train/tensorflow", f"newwrite{rep}")
        row["bit_identical"] = all(
            a.dtype == b.dtype and a.tobytes() == b.tobytes()
            for a, b in zip(leaves, jax.tree_util.tree_leaves(
                (back.params, back.opt_state)))
        )
        del back
        for phase in ("oldwrite", "newwrite", "oldpublish", "newpublish"):
            store.delete("train/tensorflow", f"{phase}{rep}")
    row["median_s"] = {k: statistics.median(v) for k, v in times.items()}
    row["runs_s"] = times
    return row


def main(argv: list[str]) -> int:
    names = argv or list(TREES)
    out = REPO / "chiprun_out"
    out.mkdir(exist_ok=True)
    root = REPO / ".scratch" / "publish_sweep"
    shutil.rmtree(root, ignore_errors=True)
    store = volumes.VolumeStorage(root)
    rows = []
    try:
        for name in names:
            row = sweep(name, store)
            print(json.dumps({k: v for k, v in row.items()
                              if k != "runs_s"}), flush=True)
            rows.append(row)
    finally:
        shutil.rmtree(root, ignore_errors=True)
    (out / "publish_sweep.json").write_text(json.dumps({
        "device": str(jax.devices()[0].device_kind), "rows": rows,
    }, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
