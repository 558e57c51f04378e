"""The system under test, booted in this process and driven through its
stock HTTP client: the in-process ``APIServer`` on an ephemeral port and
``client.Context`` (plain urllib).  The plumbing is copied from
``chip_smoke.py`` (``boot``, ``_token_csv``, ``_ingest``, ``_finished``,
``_history``), which stays the bring-up check.

Store and volume roots go under ``.bench_run/`` in the checkout (removed
when the run ends); every other setting is the code's default unless
the configuration's ``server`` block names it."""

from __future__ import annotations

import shutil
from pathlib import Path

import numpy as np

SCRATCH_NAME = ".bench_run"

#: What a ``/function/python`` job runs to turn ``--seed`` into the
#: artifact a user with weights of their own would bring.  It executes
#: in the server's process, where ``lobench`` is importable.
_WEIGHTS_JOB = (
    "from lobench import rest\n"
    "response = rest.weights_artifact({config_path!r}, {seed}, {as_!r})\n"
)


def boot(root: Path, server_settings: dict | None = None):
    """(server, client) on ``root``'s scratch store."""
    from learningorchestra_tpu.api import APIServer
    from learningorchestra_tpu.client import Context
    from learningorchestra_tpu.config import Config

    cfg = Config()
    cfg.store.root = str(root / "store")
    cfg.store.volume_root = str(root / "volumes")
    for section, values in (server_settings or {}).items():
        target = getattr(cfg, section)
        for key, value in values.items():
            if not hasattr(target, key):
                raise SystemExit(f"no server setting {section}.{key}")
            setattr(target, key, value)
    server = APIServer(cfg)
    port = server.start_background()
    return server, Context(f"http://127.0.0.1:{port}")


def scratch(repo: Path) -> Path:
    root = repo / SCRATCH_NAME
    shutil.rmtree(root, ignore_errors=True)
    root.mkdir(parents=True)
    return root


def finished(ctx, name: str, timeout: float = 1100.0) -> dict:
    meta = ctx.observe.wait(name, timeout=timeout)
    if not meta.get("finished"):
        raise RuntimeError(f"job {name!r} did not finish: {meta}")
    return meta


def token_csv(path: Path, tokens: np.ndarray, labels: np.ndarray):
    """Token ids and one class label a row as a CSV; zero-padded field
    names keep positional order under any column sort."""
    fields = [f"t{i:03d}" for i in range(tokens.shape[1])]
    with open(path, "w") as fh:
        fh.write(",".join(fields + ["label"]) + "\n")
        for xr, yr in zip(tokens.tolist(), labels.tolist()):
            fh.write(",".join(map(str, [*xr, yr])) + "\n")
    return fields


def ingest(ctx, root: Path, name: str, tokens, labels) -> None:
    """CSV -> /dataset/csv (file://) -> projection ``<name>_x``."""
    path = root / f"{name}.csv"
    fields = token_csv(path, tokens, labels)
    ctx.dataset_csv.insert(name, f"file://{path}")
    finished(ctx, name)
    ctx.projection.create(f"{name}_x", name, fields)
    finished(ctx, f"{name}_x")


def history(ctx, name: str) -> list[dict]:
    docs = ctx.search(
        "train/tensorflow", name, query={"docType": "history"}, limit=100
    )
    return sorted(docs, key=lambda d: d["epoch"])


def spans(ctx, name: str) -> list[dict]:
    """The job's recorded spans, flat: ``name``, ``start`` (epoch
    seconds), ``durationS``."""
    out: list[dict] = []

    def walk(node):
        if isinstance(node, dict):
            if "name" in node and "durationS" in node:
                out.append({
                    "name": node["name"], "start": node.get("start"),
                    "durationS": node["durationS"],
                })
            for value in node.values():
                walk(value)
        elif isinstance(node, list):
            for value in node:
                walk(value)

    walk(ctx.observability.trace(name))
    return out


def weights_artifact(config_path: str, seed: int, as_: str):
    """Runs inside the ``/function/python`` job: the seed's weights,
    made on the device in one jitted call, as ``state`` (a state dict
    for ``load_state_dict``) or as ``estimator`` (the configuration's
    estimator holding them, on the host: a servable artifact)."""
    import jax

    from lobench import loader, weights

    config, module = loader.config(Path(config_path))
    cp = config["class_parameters"]
    flat = weights.make_flat(seed, module.leaves(cp))
    variables = module.program_params(flat, cp)
    if as_ == "state":
        return {"params": variables, "opt_state": None}
    if as_ != "estimator":
        raise ValueError(as_)
    from learningorchestra_tpu.toolkit import registry

    est = registry.resolve(config["module_path"], config["class"])(**cp)
    est.params = jax.device_get(variables)
    return est


def submit_weights(ctx, name: str, config_path: Path, seed: int,
                   as_: str) -> dict:
    ctx.function.create(name, function=_WEIGHTS_JOB.format(
        config_path=str(config_path), seed=int(seed), as_=as_,
    ))
    return finished(ctx, name)
