"""Text-classification pipeline demo — the reference's IMDb-style
walkthrough (reference README.md:53 runs Titanic/IMDb/MNIST demos)
against a local in-process server, through the Python client.

Runs on CPU out of the box::

    JAX_PLATFORMS=cpu python examples/text_pipeline.py

Steps: ingest a raw-text CSV → BPE-tokenize the text column into a
tensor-sharded int32 dataset (`/transform/text` — the framework-native
front end the reference leaves to user preprocessing) → train a small
transformer on the tokens (streaming fit) → tokenize a HELD-OUT split
with the training tokenizer → evaluate + predict on it.
"""

from __future__ import annotations

import os
import tempfile

try:  # repo on sys.path, for both invocation styles
    import _demo_env  # noqa: F401  (python examples/<name>.py)
except ImportError:
    from examples import _demo_env  # noqa: F401  (python -m examples.<name>)
import numpy as np

POS = ["great fun film", "loved this great movie", "fun and great",
       "loved it", "a great watch", "really fun and moving"]
NEG = ["terrible boring film", "hated this boring movie",
       "boring and terrible", "hated it", "a terrible watch",
       "really dull and boring"]


def _write_reviews(path: str, n: int, seed: int) -> None:
    rng = np.random.default_rng(seed)
    rows = [(POS[i % len(POS)], "pos") for i in range(n // 2)] + \
           [(NEG[i % len(NEG)], "neg") for i in range(n // 2)]
    rng.shuffle(rows)
    with open(path, "w") as fh:
        fh.write("review,sentiment\n")
        for text, label in rows:
            fh.write(f'"{text}",{label}\n')


def main() -> None:
    workdir = tempfile.mkdtemp(prefix="lo_text_demo_")
    os.environ.setdefault("LO_TPU_STORE_ROOT", f"{workdir}/store")
    os.environ.setdefault("LO_TPU_VOLUME_ROOT", f"{workdir}/volumes")

    from learningorchestra_tpu.api.server import APIServer
    from learningorchestra_tpu.client import Context

    server = APIServer()
    port = server.start_background()
    ctx = Context(f"http://127.0.0.1:{port}")

    # 1. Ingest raw text ---------------------------------------------------
    train_csv = os.path.join(workdir, "reviews.csv")
    _write_reviews(train_csv, 160, seed=0)
    ctx.dataset_csv.insert("reviews", f"file://{train_csv}")
    ctx.dataset_csv.wait("reviews")
    print("ingested raw text rows")

    # 2. Tokenize: text column -> tensor-sharded int32 dataset -------------
    ctx.text.create(
        "reviews_tok", "reviews", text_field="review",
        label_field="sentiment", vocab_size=128, max_len=16,
        shard_rows=64,
    )
    meta = ctx.text.wait("reviews_tok")
    print("tokenized:", meta["rows"], "rows, vocab", meta["vocabSize"],
          "classes", meta["labelClasses"])

    # 3. Train a small transformer on the tokens ---------------------------
    ctx.model.create(
        "clf",
        module_path="learningorchestra_tpu.models.text",
        class_name="TransformerClassifier",
        class_parameters={
            "vocab_size": 128, "hidden_dim": 32, "num_layers": 1,
            "num_heads": 2, "max_len": 16, "num_classes": 2,
            "learning_rate": 1e-2,
        },
    )
    ctx.model.wait("clf")
    ctx.train.create(
        "clf_fit", parent_name="clf", method="fit",
        method_parameters={"x": "$reviews_tok",
                           "y": "$reviews_tok.label",
                           "epochs": 6, "batch_size": 32},
    )
    ctx.train.wait("clf_fit")
    hist = [d for d in ctx.train.search("clf_fit", limit=100)
            if d.get("docType") == "history"]
    print(f"loss {hist[0]['loss']:.3f} -> {hist[-1]['loss']:.3f} "
          f"over {len(hist)} epochs")

    # 4. Held-out split, encoded with the TRAINING tokenizer ---------------
    test_csv = os.path.join(workdir, "reviews_test.csv")
    _write_reviews(test_csv, 40, seed=1)
    ctx.dataset_csv.insert("reviews_test", f"file://{test_csv}")
    ctx.dataset_csv.wait("reviews_test")
    ctx.text.create(
        "test_tok", "reviews_test", text_field="review",
        label_field="sentiment", max_len=16,
        tokenizer_from="reviews_tok", shard_rows=64,
    )
    ctx.text.wait("test_tok")

    # 5. Evaluate + predict on the held-out tokens -------------------------
    ctx.evaluate.create(
        "clf_eval", parent_name="clf_fit", method="evaluate",
        method_parameters={"x": "$test_tok", "y": "$test_tok.label"},
    )
    ctx.evaluate.wait("clf_eval")
    result = [d for d in ctx.evaluate.search("clf_eval")
              if "accuracy" in d][0]
    print("held-out eval:",
          {k: round(float(result[k]), 3) for k in ("loss", "accuracy")})
    assert result["accuracy"] > 0.6, result

    ctx.predict.create(
        "clf_pred", parent_name="clf_fit", method="predict_classes",
        method_parameters={"x": "$test_tok"},
    )
    ctx.predict.wait("clf_pred")
    preds = [d["result"] for d in ctx.predict.search("clf_pred", limit=10)
             if "result" in d]
    print("first predicted classes:", preds[:5])

    server.shutdown()
    print("TEXT PIPELINE COMPLETE")


if __name__ == "__main__":
    main()
