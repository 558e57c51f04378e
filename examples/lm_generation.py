"""Decoder-LM walkthrough: train a small GPT-style model and generate
from it — greedy and sampled — through the Python client, with the
modern LM geometry on (RoPE positions, grouped-query attention, a
sliding attention window, gradient accumulation).

Runs on CPU out of the box::

    JAX_PLATFORMS=cpu python examples/lm_generation.py

The reference system has no generative path at all; this demo shows the
same async-job/named-artifact contract (POST → poll → GET) carrying a
language-model workflow end to end.
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
    workdir = tempfile.mkdtemp(prefix="lo_lm_demo_")
    os.environ.setdefault("LO_TPU_STORE_ROOT", f"{workdir}/store")
    os.environ.setdefault("LO_TPU_VOLUME_ROOT", f"{workdir}/volumes")

    from learningorchestra_tpu.api.server import APIServer
    from learningorchestra_tpu.client import Context

    server = APIServer()
    port = server.start_background()
    ctx = Context(f"http://127.0.0.1:{port}")

    # 1. Token data: sequences with a learnable pattern (ascending
    # runs mod vocab), as a CSV of token-id columns.
    vocab, seq = 48, 12
    rng = np.random.default_rng(0)
    starts = rng.integers(1, vocab, (96, 1))
    xs = (starts + np.arange(seq)) % (vocab - 1) + 1  # ids in [1, vocab)
    ys = np.concatenate(
        [xs[:, 1:], np.zeros((len(xs), 1), xs.dtype)], axis=1
    )  # next-token targets: x shifted left, pad-terminated

    def write_csv(path, mat):
        with open(path, "w") as fh:
            fh.write(",".join(f"t{i}" for i in range(seq)) + "\n")
            for row in mat:
                fh.write(",".join(map(str, row)) + "\n")

    write_csv(f"{workdir}/tokens.csv", xs)
    write_csv(f"{workdir}/targets.csv", ys)
    ctx.dataset_csv.insert("tok", f"file://{workdir}/tokens.csv")
    ctx.dataset_csv.insert("tok_y", f"file://{workdir}/targets.csv")
    ctx.dataset_csv.wait("tok")
    ctx.dataset_csv.wait("tok_y")
    print("ingested", len(xs), "sequences")

    # 2. Model: RoPE positions, 2 KV heads for 4 query heads (GQA),
    # an 8-token sliding attention window.
    ctx.model.create(
        "lm",
        module_path="learningorchestra_tpu.models.text",
        class_name="DecoderLM",
        class_parameters={
            "vocab_size": vocab, "hidden_dim": 32, "num_layers": 2,
            "num_heads": 4, "mlp_dim": 64, "max_len": 2 * seq,
            "positional": "rope", "num_kv_heads": 2,
            "attention_window": 8, "learning_rate": 3e-3,
        },
    )
    ctx.model.wait("lm")

    # 3. Teacher-forced next-token training: y = x shifted left.
    ctx.train.create(
        "lm_fit", model_name="lm", method="fit",
        method_parameters={
            "x": "$tok", "y": "$tok_y", "epochs": 30, "batch_size": 16,
            "accumulate_steps": 2,  # effective batch 32
        },
    )
    meta = ctx.train.wait("lm_fit", timeout=600)
    print("trained: loss", round(meta.get("fitTime", 0), 2), "s fit")

    # 4. Greedy continuation of fresh prompts.
    prompts = ((rng.integers(1, vocab, (4, 1))
                + np.arange(6)) % (vocab - 1) + 1).tolist()
    ctx.predict.create(
        "lm_greedy", model_name="lm_fit", method="generate",
        method_parameters={"prompts": prompts, "max_new_tokens": 6},
    )
    ctx.predict.wait("lm_greedy")
    rows = [d for d in ctx.predict.search("lm_greedy", limit=10)
            if "result" in d]
    print("greedy:", rows[0]["result"])

    # 5. Sampled continuation (temperature + top-k), same artifact
    # contract — re-runnable via PATCH like every step.
    ctx.predict.create(
        "lm_sampled", model_name="lm_fit", method="generate",
        method_parameters={
            "prompts": prompts, "max_new_tokens": 6,
            "temperature": 0.8, "top_k": 8, "seed": 3,
        },
    )
    ctx.predict.wait("lm_sampled")
    rows = [d for d in ctx.predict.search("lm_sampled", limit=10)
            if "result" in d]
    print("sampled:", rows[0]["result"])

    server.shutdown()
    print("done")


if __name__ == "__main__":
    main()
