"""BASELINE.md configs 1, 3, 4 exercised END-TO-END through the REST
surface at tiny shapes (VERDICT r1 next-round item 10):

- config 1: Titanic-style tabular CSV → RandomForest-class estimator
  via the Training API (CPU path);
- config 3: IMDb-style sentiment LSTM — token data built via
  function/python (the reference's codeExecutor wildcard), trained,
  evaluated, then explored with a t-SNE scatter PNG;
- config 4: BERT fine-tune driven by the Tune grid-search route.

Config 2 (MNIST-style CNN flow) is covered by test_api.py;
config 5's multi-chip shape by test_multihost.py + the dryrun entries.
"""

import json
import time

import numpy as np
import pytest

pytestmark = pytest.mark.slow  # compile-heavy: full tier only
import requests

from learningorchestra_tpu.api import APIServer
from learningorchestra_tpu.config import Config

PREFIX = "/api/learningOrchestra/v1"


@pytest.fixture(scope="module")
def api(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("baseline_api")
    cfg = Config()
    cfg.store.root = str(tmp / "store")
    cfg.store.volume_root = str(tmp / "volumes")
    server = APIServer(cfg)
    port = server.start_background()
    base = f"http://127.0.0.1:{port}{PREFIX}"
    yield base
    server.shutdown()


def poll(base, path, timeout=180):
    deadline = time.time() + timeout
    while time.time() < deadline:
        docs = requests.get(f"{base}{path}", timeout=10).json()
        meta = docs[0] if isinstance(docs, list) and docs else {}
        if meta.get("finished"):
            return meta
        if meta.get("jobState") == "failed":
            raise AssertionError(f"job failed: {meta.get('exception')}")
        time.sleep(0.05)
    raise AssertionError(f"timeout polling {path}")


# Synthetic IMDb-like data: class-dependent token distributions so the
# LSTM has signal to learn; function/python is the reference's path for
# bringing non-tabular data into the pipeline (codeExecutor, SURVEY
# §2.1 — users run tfds loads there).
MAKE_IMDB = """
import numpy as np
rng = np.random.default_rng(0)
n, seq = 48, 12
y = rng.integers(0, 2, n)
x = np.where(
    (y[:, None] == 1),
    rng.integers(1, 25, (n, seq)),
    rng.integers(25, 49, (n, seq)),
).astype(np.int32)
response = (x, y.astype(np.int32))
"""


@pytest.fixture(scope="module")
def imdb_data(api):
    resp = requests.post(
        f"{api}/function/python",
        json={"name": "imdb_mini", "function": MAKE_IMDB},
    )
    assert resp.status_code == 201, resp.text
    poll(api, "/function/python/imdb_mini")
    return "imdb_mini"


class TestConfig3ImdbLSTM:
    def test_lstm_train_evaluate_tsne_flow(self, api, imdb_data):
        resp = requests.post(
            f"{api}/model/tensorflow",
            json={
                "name": "imdb_lstm",
                "modulePath": "learningorchestra_tpu.models.text",
                "class": "LSTMClassifier",
                "classParameters": {
                    "vocab_size": 50, "embed_dim": 8, "hidden_dim": 8,
                    "num_classes": 2, "learning_rate": 5e-3,
                },
            },
        )
        assert resp.status_code == 201, resp.text
        poll(api, "/model/tensorflow/imdb_lstm")

        resp = requests.post(
            f"{api}/train/tensorflow",
            json={
                "name": "imdb_fit",
                "parentName": "imdb_lstm",
                "method": "fit",
                "methodParameters": {
                    "x": "$imdb_mini.0", "y": "$imdb_mini.1",
                    "epochs": 25, "batch_size": 16,
                },
            },
        )
        assert resp.status_code == 201, resp.text
        meta = poll(api, "/train/tensorflow/imdb_fit")
        assert meta["jobState"] == "finished"

        resp = requests.post(
            f"{api}/evaluate/tensorflow",
            json={
                "name": "imdb_eval",
                "parentName": "imdb_fit",
                "method": "evaluate",
                "methodParameters": {
                    "x": "$imdb_mini.0", "y": "$imdb_mini.1",
                },
            },
        )
        assert resp.status_code == 201, resp.text
        poll(api, "/evaluate/tensorflow/imdb_eval")
        docs = requests.get(
            f"{api}/evaluate/tensorflow/imdb_eval",
            params={"limit": 20},
        ).json()
        rows = [d for d in docs if "accuracy" in d]
        assert rows, docs
        # Separable-by-construction data: the LSTM must beat chance.
        assert rows[0]["accuracy"] > 0.6

        # Explore: t-SNE scatter over the token matrix, colored by label
        # (BASELINE config 3's "Evaluate + Explore t-SNE").
        resp = requests.post(
            f"{api}/explore/scikitlearn",
            json={
                "name": "imdb_tsne",
                # The framework's own jitted t-SNE estimator (toolkit/
                # estimators/decomposition.py), resolved via the registry.
                "modulePath":
                    "learningorchestra_tpu.toolkit.estimators.decomposition",
                "class": "TSNE",
                "classParameters": {
                    "n_components": 2, "perplexity": 5.0,
                    "n_iter": 50, "random_state": 0,
                },
                "method": "fit_transform",
                "methodParameters": {"x": "$imdb_mini.0"},
                "colorBy": "$imdb_mini.1",
            },
        )
        assert resp.status_code == 201, resp.text
        poll(api, "/explore/scikitlearn/imdb_tsne/metadata")
        img = requests.get(f"{api}/explore/scikitlearn/imdb_tsne")
        assert img.status_code == 200
        assert img.content[:8] == b"\x89PNG\r\n\x1a\n"


class TestConfig4BertTuneGrid:
    def test_bert_tune_grid_search(self, api, imdb_data):
        resp = requests.post(
            f"{api}/model/tensorflow",
            json={
                "name": "bert_mini",
                "modulePath": "learningorchestra_tpu.models.text",
                "class": "BertModel",
                "classParameters": {
                    "vocab_size": 50, "hidden_dim": 16, "num_layers": 1,
                    "num_heads": 2, "max_len": 12, "num_classes": 2,
                },
            },
        )
        assert resp.status_code == 201, resp.text
        poll(api, "/model/tensorflow/bert_mini")

        resp = requests.post(
            f"{api}/tune/tensorflow",
            json={
                "name": "bert_tune",
                "parentName": "bert_mini",
                "method": "fit",
                "paramGrid": {
                    "learning_rate": [1e-3, 1e-4],
                    "vocab_size": [50],
                    "hidden_dim": [16],
                    "num_layers": [1],
                    "num_heads": [2],
                    "max_len": [12],
                    "num_classes": [2],
                },
                "methodParameters": {
                    "x": "$imdb_mini.0", "y": "$imdb_mini.1",
                    "epochs": 2, "batch_size": 16,
                },
            },
        )
        assert resp.status_code == 201, resp.text
        meta = poll(api, "/tune/tensorflow/bert_tune", timeout=300)
        assert meta["jobState"] == "finished"

        docs = requests.get(
            f"{api}/tune/tensorflow/bert_tune", params={"limit": 50}
        ).json()
        trials = [d for d in docs if "score" in d and d.get("_id", 0) >= 1]
        assert len(trials) == 2, docs
        # Best candidate recorded in metadata for downstream steps.
        assert "bestParams" in meta and "bestScore" in meta, meta
        assert meta["bestParams"]["learning_rate"] in (1e-3, 1e-4)


class TestConfig1TitanicRF:
    def test_random_forest_via_training_api(self, api, tmp_path_factory):
        """BASELINE config 1: tabular CSV ingest → RandomForest-class
        estimator through the model/train/evaluate/predict routes on
        CPU (the reference's Titanic demo, README.md:53)."""
        tmp = tmp_path_factory.mktemp("titanic")
        rng = np.random.default_rng(7)
        n = 200
        age = rng.uniform(1, 80, n)
        fare = rng.uniform(5, 500, n)
        pclass = rng.integers(1, 4, n)
        # Survival correlates with fare and class — learnable signal.
        y = ((fare / 500 + (3 - pclass) / 3 + rng.normal(0, 0.2, n)) > 0.8)
        csv = tmp / "titanic.csv"
        with open(csv, "w") as fh:
            fh.write("age,fare,pclass,survived\n")
            for a, f, p, s in zip(age, fare, pclass, y.astype(int)):
                fh.write(f"{a:.1f},{f:.2f},{p},{s}\n")

        resp = requests.post(
            f"{api}/dataset/csv",
            json={"datasetName": "titanic", "url": f"file://{csv}"},
        )
        assert resp.status_code == 201, resp.text
        poll(api, "/dataset/csv/titanic")

        resp = requests.post(
            f"{api}/transform/projection",
            json={"name": "titanic_X", "parentName": "titanic",
                  "fields": ["age", "fare", "pclass"]},
        )
        assert resp.status_code == 201, resp.text
        poll(api, "/transform/projection/titanic_X")

        resp = requests.post(
            f"{api}/model/scikitlearn",
            json={
                "name": "rf",
                "modulePath":
                    "learningorchestra_tpu.toolkit.estimators.trees",
                "class": "RandomForestClassifier",
                "classParameters": {"n_estimators": 8, "max_depth": 4},
            },
        )
        assert resp.status_code == 201, resp.text
        poll(api, "/model/scikitlearn/rf")

        resp = requests.post(
            f"{api}/train/scikitlearn",
            json={
                "name": "rf_fit", "parentName": "rf", "method": "fit",
                "methodParameters": {
                    "x": "$titanic_X", "y": "$titanic.survived",
                },
            },
        )
        assert resp.status_code == 201, resp.text
        meta = poll(api, "/train/scikitlearn/rf_fit")
        assert meta["jobState"] == "finished"

        resp = requests.post(
            f"{api}/evaluate/scikitlearn",
            json={
                "name": "rf_eval", "parentName": "rf_fit",
                "method": "score",
                "methodParameters": {
                    "x": "$titanic_X", "y": "$titanic.survived",
                },
            },
        )
        assert resp.status_code == 201, resp.text
        poll(api, "/evaluate/scikitlearn/rf_eval")
        docs = requests.get(
            f"{api}/evaluate/scikitlearn/rf_eval", params={"limit": 10}
        ).json()
        scores = [d["result"] for d in docs if "result" in d]
        assert scores and scores[0] > 0.75, docs

        resp = requests.post(
            f"{api}/predict/scikitlearn",
            json={
                "name": "rf_pred", "parentName": "rf_fit",
                "method": "predict",
                "methodParameters": {"x": "$titanic_X"},
            },
        )
        assert resp.status_code == 201, resp.text
        poll(api, "/predict/scikitlearn/rf_pred")
        rows = requests.get(
            f"{api}/predict/scikitlearn/rf_pred", params={"limit": 100}
        ).json()
        preds = [d for d in rows if "result" in d]
        assert len(preds) >= 90
