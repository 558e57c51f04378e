"""End-to-end pipeline demo — the reference's Titanic-style walkthrough
(reference README.md:53) against a local in-process server, using the
Python client the way `learning-orchestra-client` drives the reference.

Runs on CPU out of the box::

    JAX_PLATFORMS=cpu python examples/full_pipeline.py

Steps: ingest CSV → project features → cast a column → histogram →
model → train → evaluate → predict → t-SNE explore PNG → function
escape hatch — every step an async job polled to completion, every
artifact named and re-runnable (PATCH).
"""

from __future__ import annotations

import os
import tempfile

try:  # repo on sys.path, for both invocation styles
    import _demo_env  # noqa: F401  (python examples/<name>.py)
except ImportError:
    from examples import _demo_env  # noqa: F401  (python -m examples.<name>)
import numpy as np


def main() -> None:
    workdir = tempfile.mkdtemp(prefix="lo_demo_")
    os.environ.setdefault("LO_TPU_STORE_ROOT", f"{workdir}/store")
    os.environ.setdefault("LO_TPU_VOLUME_ROOT", f"{workdir}/volumes")

    from learningorchestra_tpu.api.server import APIServer
    from learningorchestra_tpu.client import Context

    server = APIServer()
    port = server.start_background()
    ctx = Context(f"http://127.0.0.1:{port}")

    # 1. Ingest ------------------------------------------------------------
    rng = np.random.default_rng(0)
    n = 300
    age = rng.uniform(1, 80, n)
    fare = rng.uniform(5, 500, n)
    pclass = rng.integers(1, 4, n)
    survived = (
        (fare / 500 + (3 - pclass) / 3 + rng.normal(0, 0.2, n)) > 0.8
    ).astype(int)
    csv = os.path.join(workdir, "titanic.csv")
    with open(csv, "w") as fh:
        fh.write("age,fare,pclass,survived\n")
        for row in zip(age, fare, pclass, survived):
            fh.write("{:.1f},{:.2f},{},{}\n".format(*row))

    ctx.dataset_csv.insert("titanic", f"file://{csv}")
    meta = ctx.dataset_csv.wait("titanic")
    print("ingested:", meta["fields"])

    # 2. Transform ---------------------------------------------------------
    ctx.projection.create("titanic_X", "titanic",
                          ["age", "fare", "pclass"])
    ctx.projection.wait("titanic_X")
    ctx.data_type.update("titanic", {"pclass": "number"})
    ctx.dataset_csv.wait("titanic")

    # 3. Explore -----------------------------------------------------------
    ctx.histogram.create("titanic_hist", "titanic", ["survived"])
    ctx.histogram.wait("titanic_hist")
    hist = [d for d in ctx.histogram.search("titanic_hist")
            if d.get("field") == "survived"][0]
    print("class balance:", hist["counts"])

    # 4. Model + train -----------------------------------------------------
    ctx.model.create(
        "rf",
        module_path="learningorchestra_tpu.toolkit.estimators.trees",
        class_name="RandomForestClassifier",
        class_parameters={"n_estimators": 16, "max_depth": 5},
    )
    ctx.model.wait("rf")
    ctx.train.create(
        "rf_fit", parent_name="rf", method="fit",
        method_parameters={"x": "$titanic_X", "y": "$titanic.survived"},
    )
    ctx.train.wait("rf_fit")

    # 5. Evaluate + predict ------------------------------------------------
    ctx.evaluate.create(
        "rf_eval", parent_name="rf_fit", method="score",
        method_parameters={"x": "$titanic_X", "y": "$titanic.survived"},
    )
    ctx.evaluate.wait("rf_eval")
    score = [d["result"] for d in ctx.evaluate.search("rf_eval")
             if "result" in d][0]
    print(f"train accuracy: {score:.3f}")

    ctx.predict.create(
        "rf_pred", parent_name="rf_fit", method="predict",
        method_parameters={"x": "$titanic_X"},
    )
    ctx.predict.wait("rf_pred")

    # 6. Explore plot (the framework's jitted t-SNE) -----------------------
    ctx.explore_sklearn.create(
        "titanic_tsne",
        module_path="learningorchestra_tpu.toolkit.estimators.decomposition",
        class_name="TSNE",
        class_parameters={"n_components": 2, "perplexity": 12.0,
                          "n_iter": 100, "random_state": 0},
        method="fit_transform",
        method_parameters={"x": "$titanic_X"},
        color_by="$titanic.survived",
    )
    ctx.explore_sklearn.wait("titanic_tsne")
    png = ctx.explore_sklearn.image("titanic_tsne")
    out = os.path.join(workdir, "tsne.png")
    with open(out, "wb") as fh:
        fh.write(png)
    print("t-SNE scatter written to", out)

    # 7. Function escape hatch ($titanic resolves to a DataFrame) ----------
    ctx.function.create(
        "summary",
        function=(
            "response = {'rows': int(len(titanic)),\n"
            "            'mean_fare': float(titanic['fare'].mean())}\n"
        ),
        function_parameters={"titanic": "$titanic"},
    )
    meta = ctx.function.wait("summary")
    assert meta.get("jobState") == "finished", meta.get("exception")
    print("function result recorded; gateway metrics:",
          len(ctx.metrics()["routes"]), "routes tracked")

    server.shutdown()
    print("PIPELINE COMPLETE")


if __name__ == "__main__":
    main()
