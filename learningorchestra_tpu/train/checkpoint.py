"""Managed in-loop training checkpoints (orbax-backed, shard-aware).

The reference has NO intra-training checkpointing: a mid-job failure
loses the job and distributed training returns weights only at the end
(reference: training_function/train_function.py:84-87; README.md:193-197
documents that a task running when the cluster dies "is lost").  On TPU,
preemption is routine, so the train executor checkpoints the estimator
state every N epochs and PATCH re-runs resume instead of restarting —
closing the gap SURVEY §5.4 calls out.

Sharding contract:
- ``save`` takes the state tree AS IS — sharded ``jax.Array`` leaves are
  written by orbax shard-by-shard from the process(es) that own them;
  there is **no host gather** (a v4-32 ResNet/BERT state never
  materializes on one host).
- ``load_latest`` restores INTO the template's placement: a template of
  mesh-sharded arrays yields sharded arrays on that mesh (which may be a
  *different* mesh shape than the one that saved — orbax reshards on
  read); a host-numpy template yields numpy.
- Multi-process: ``save``/``load_latest`` are collective — every process
  calls them; only process 0 writes the ``latest.json`` marker and
  prunes old steps.

Layout under ``<dir>``::

    step_<n>/        orbax pytree checkpoint (params + opt_state)
    latest.json      {"step": n, "history": {...}} — atomically replaced

``latest.json`` is written AFTER the step directory commits, so a crash
mid-save leaves the previous checkpoint intact and discoverable.
"""

from __future__ import annotations

import json
import os
import shutil
from pathlib import Path

from learningorchestra_tpu.concurrency_rt import make_lock

KEEP = 2  # retained checkpoints; older ones are pruned after each save


def _checkpointer():
    import orbax.checkpoint as ocp

    return ocp.StandardCheckpointer()


def _save_args(state: dict):
    """orbax save arguments that keep every file under the size the
    volumes split artifacts at (store/volumes.py ``max_file_bytes``):
    orbax's own default packs a step into data files of up to 2 GB,
    and a host's file-size limit fails that write with EFBIG.  The
    target is no bound: a data file under it takes one more write,
    which is a chunk or, at the commit, the root node that lists every
    key and array header (one node whatever its size: orbax pins
    OCDBT's ``max_decoded_node_bytes`` at 100 MB).  With target and
    chunk at a quarter of the bound a data file stays under half of it
    and the root node has three quarters.  ``StandardSave`` cannot
    carry the target, hence the PyTree handler; what it writes is what
    ``StandardCheckpointer`` restores."""
    import jax
    import orbax.checkpoint as ocp

    from learningorchestra_tpu.store.volumes import max_file_bytes

    quarter = max_file_bytes() // 4
    return ocp.args.PyTreeSave(
        state,
        save_args=jax.tree.map(
            lambda _: ocp.SaveArgs(chunk_byte_size=quarter), state
        ),
        ocdbt_target_data_file_size=quarter,
    )


def _is_primary() -> bool:
    import jax

    return jax.process_index() == 0


def _barrier(tag: str) -> None:
    import jax

    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(tag)


def _publish(directory: Path, step: int, history: dict | None) -> None:
    """Commit point: the ``latest.json`` marker names the newest FULLY
    WRITTEN checkpoint; readers never see a step the data hasn't
    landed for.  Also prunes old steps."""
    marker = {"step": step, "history": history or {}}
    tmp = directory / "latest.json.tmp"
    tmp.write_text(json.dumps(marker))
    os.replace(tmp, directory / "latest.json")
    for old in sorted(directory.glob("step_*")):
        try:
            n = int(old.name.split("_", 1)[1])
        except ValueError:
            continue
        if n <= step - KEEP:
            shutil.rmtree(old, ignore_errors=True)


# Async bookkeeping is PER CHECKPOINT DIRECTORY: the job engine runs
# fits concurrently on worker threads (jobs/engine.py, max_workers=8),
# so a single global slot would let one job's finalize swallow (or
# republish over) another's marker.  Each directory gets its own
# AsyncCheckpointer + one-pending-save slot, guarded by its own lock.


class _AsyncSlot:
    def __init__(self):
        self.lock = make_lock("_AsyncSlot.lock")
        self.ckpt = None
        self.pending = None  # (step, history) awaiting publish


_SLOTS: dict[str, _AsyncSlot] = {}
_SLOTS_LOCK = make_lock("checkpoint._SLOTS_LOCK")
_ATEXIT = {"registered": False}


def _slot(directory: Path) -> _AsyncSlot:
    key = str(directory)
    with _SLOTS_LOCK:
        if key not in _SLOTS:
            _SLOTS[key] = _AsyncSlot()
            if not _ATEXIT["registered"]:
                import atexit

                # A process must never exit with a written-but-
                # unpublished checkpoint (the marker is the commit
                # point).
                atexit.register(finalize_async)
                _ATEXIT["registered"] = True
        return _SLOTS[key]


def _finalize_slot(key: str, slot: _AsyncSlot) -> None:
    with slot.lock:
        if slot.pending is None:
            return
        step, history = slot.pending
        slot.pending = None
        slot.ckpt.wait_until_finished()
        _publish(Path(key), step, history)


def finalize_async(directory: str | Path | None = None) -> None:
    """Block until in-flight async saves commit and publish their
    markers — for one checkpoint directory, or (``None``) all of them.
    Fit loops call this at loop exit so the last checkpoint is durable
    when fit() returns — the same guarantee the sync path gives per
    save."""
    if directory is not None:
        key = str(Path(directory))
        with _SLOTS_LOCK:
            slot = _SLOTS.get(key)
        if slot is not None:
            _finalize_slot(key, slot)
        return
    with _SLOTS_LOCK:
        items = list(_SLOTS.items())
    for key, slot in items:
        _finalize_slot(key, slot)


def save(directory: str | Path, step: int, state: dict,
         history: dict | None = None, *,
         async_save: bool = False) -> Path:
    """Persist {params, opt_state} at ``step``; returns the step path.

    Collective under multi-process JAX; sharded leaves are written
    without gathering to host.

    ``async_save=True`` (single-process only) returns as soon as the
    device arrays are snapshotted: serialization runs on a background
    thread while training continues — on a remote-TPU link the
    device→host transfer dominates save time, so overlapping it buys
    a whole checkpoint's wall-clock per save.  The marker publishes at
    the NEXT save or at :func:`finalize_async`, so a crash mid-write
    resumes from the previous durable step (the same fallback a crash
    mid-sync-save has).
    """
    import jax

    directory = Path(directory)
    if async_save and jax.process_count() == 1:
        import orbax.checkpoint as ocp

        slot = _slot(directory)
        with slot.lock:
            # Previous save to THIS directory commits + publishes
            # first (one in flight per directory).
            if slot.pending is not None:
                p_step, p_history = slot.pending
                slot.pending = None
                slot.ckpt.wait_until_finished()
                _publish(directory, p_step, p_history)
            if slot.ckpt is None:
                # The temporary step directory is made before ``save``
                # returns, not on the background thread: orbax tells a
                # background write that its directory exists through
                # signals keyed by ONE process-wide operation counter,
                # which every save (sync or async, any directory)
                # advances — saves that start together from two
                # threads read each other's key, write into a
                # directory not yet made and lose it.  Made up front,
                # no save waits on a signal.
                slot.ckpt = ocp.AsyncCheckpointer(
                    ocp.PyTreeCheckpointHandler(),
                    async_options=ocp.options.AsyncOptions(
                        create_directories_asynchronously=False
                    ),
                )
            directory.mkdir(parents=True, exist_ok=True)
            path = directory / f"step_{step}"
            if path.exists():
                shutil.rmtree(path)
            slot.ckpt.save(path, args=_save_args(state))
            slot.pending = (step, history)
        return path
    # Sync path: flush any pending ASYNC save to this directory first —
    # otherwise a stale pending marker could later publish OVER this
    # save's marker and rewind latest.json to an older step.
    finalize_async(directory)
    if _is_primary():
        directory.mkdir(parents=True, exist_ok=True)
        path = directory / f"step_{step}"
        if path.exists():
            shutil.rmtree(path)
    path = directory / f"step_{step}"
    _barrier(f"ckpt-pre-{step}")
    import orbax.checkpoint as ocp

    with ocp.Checkpointer(ocp.PyTreeCheckpointHandler()) as ck:
        ck.save(path, args=_save_args(state))
    # StandardCheckpointer.save commits (atomic rename) before returning,
    # on every process, so the marker write below cannot race the data.
    if _is_primary():
        _publish(directory, step, history)
    _barrier(f"ckpt-post-{step}")
    return path


def load_latest(directory: str | Path, template: dict):
    """Restore the newest checkpoint as (state, step, history), or None.

    ``template`` is a concrete pytree with the target structure (e.g. a
    freshly-initialized {params, opt_state}) — orbax uses it to rebuild
    optax's namedtuple states exactly, and restores each leaf onto the
    template leaf's placement: numpy template → numpy out; mesh-sharded
    ``jax.Array`` template → sharded arrays on that mesh (any mesh
    shape — restore-time resharding is how a job resumes on a different
    slice than the one that saved).
    """
    directory = Path(directory)
    # Flush any in-flight async save first: a reader in this process
    # must see the newest step, not the marker from one save ago.
    finalize_async(directory)
    marker_path = directory / "latest.json"
    if not marker_path.exists():
        return None
    try:
        marker = json.loads(marker_path.read_text())
        step = int(marker["step"])
    except (ValueError, KeyError, json.JSONDecodeError):
        return None
    path = directory / f"step_{step}"
    if not path.exists():
        return None
    with _checkpointer() as ck:
        state = ck.restore(path, template)
    return state, step, marker.get("history") or {}


def load_step(directory: str | Path, step: int, template: dict):
    """Restore one SPECIFIC step (template-placed, like
    ``load_latest``), or None when that step directory is absent.  The
    MPMD fit surface uses this to pull every stage partition back to
    the newest COMMON step — a crash between partition saves must not
    resume stages from different epochs."""
    directory = Path(directory)
    finalize_async(directory)
    path = directory / f"step_{step}"
    if not path.exists():
        return None
    with _checkpointer() as ck:
        return ck.restore(path, template)


def publish_marker(directory: str | Path, step: int,
                   history: dict | None = None) -> None:
    """Public commit-point writer for fit surfaces that persist state
    in their OWN sub-layout (MPMD writes one orbax directory per
    pipeline stage under ``<dir>/<part>/``): the same atomic
    ``latest.json`` the single-directory path writes, at the top
    level, AFTER every partition has committed — so the journal's
    marker wait and a resuming fit see only whole checkpoints.  The
    prune pass inside ``_publish`` globs ``step_*`` at this level,
    which a partitioned layout doesn't create."""
    directory = Path(directory)
    if _is_primary():
        directory.mkdir(parents=True, exist_ok=True)
        _publish(directory, step, history)
    _barrier(f"ckpt-marker-{step}")


def resume_or_none(directory, template: dict):
    """``load_latest`` with configuration-mismatch errors translated to
    an actionable message — the shared resume front door for every fit
    surface (NeuralEstimator, PipelinedTransformer, DistributedTrainer
    uses load_latest directly with a mesh template)."""
    try:
        return load_latest(directory, template)
    except (ValueError, TypeError) as exc:
        raise ValueError(
            "checkpoint resume failed: the saved state does not match "
            "the current configuration (model, optimizer, or "
            "accumulate_steps changed since the checkpoint was "
            "written). Re-run with resume=False or the original "
            "settings."
        ) from exc


def should_save(epoch_i: int, epochs: int, every: int,
                min_interval_s: float, last_save: float,
                *, stopped: bool = False) -> bool:
    """One save policy for every fit loop: periodic saves every
    ``every`` epochs (``every <= 0`` disables checkpointing entirely —
    including the final/stop saves below) throttled to one per
    ``min_interval_s`` (fast epochs on big models must not stall the
    loop on full-state transfers); the FINAL epoch always saves when
    checkpointing is enabled, and ``stopped=True`` (an early-stop
    callback ended training) counts as final."""
    import time as _time

    if every <= 0:
        return False
    return (
        epoch_i + 1 == epochs
        or stopped
        or (
            (epoch_i + 1) % every == 0
            and _time.monotonic() - last_save >= min_interval_s
        )
    )
