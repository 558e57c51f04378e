"""Round-3 features end-to-end through the Python client.

Runs against a local in-process server (no cluster needed):

    JAX_PLATFORMS=cpu python examples/beyond_ram_pipeline.py

Flow — the beyond-host-RAM contract plus push notifications and
quantized artifacts:

1. sharded CSV ingest (``shard_rows``): rows land in columnar volume
   shards, never materializing as one host array;
2. tensor ingest: image-shaped ``.npy`` features, memory-mapped and
   copied shard by shard;
3. a webhook registered on the training artifact — the server POSTs us
   when the job finishes (no polling);
4. streaming training straight off the shards
   (``x="$big", y="$big.label"``), saved as an int8-quantized artifact;
5. predict from the quantized binary.
"""

import http.server
import json
import os
import tempfile
import threading

try:  # repo on sys.path, for both invocation styles
    import _demo_env  # noqa: F401  (python examples/<name>.py)
except ImportError:
    from examples import _demo_env  # noqa: F401  (python -m examples.<name>)
import numpy as np

tmp = tempfile.mkdtemp()
os.environ.setdefault("LO_TPU_STORE_ROOT", tmp + "/store")
os.environ.setdefault("LO_TPU_VOLUME_ROOT", tmp + "/volumes")

from learningorchestra_tpu.api.server import APIServer  # noqa: E402
from learningorchestra_tpu.client import Context  # noqa: E402

server = APIServer()
port = server.start_background()
ctx = Context(f"http://127.0.0.1:{port}")

# A little webhook receiver standing in for your service.
events = []
delivered = threading.Event()


class Hook(http.server.BaseHTTPRequestHandler):
    def do_POST(self):
        n = int(self.headers.get("Content-Length") or 0)
        events.append(json.loads(self.rfile.read(n)))
        delivered.set()
        self.send_response(200)
        self.end_headers()

    def log_message(self, *a):
        pass


receiver = http.server.ThreadingHTTPServer(("127.0.0.1", 0), Hook)
threading.Thread(target=receiver.serve_forever, daemon=True).start()

# 1. Sharded CSV ingest — works for files of ANY size; host memory
# stays O(shard).
rng = np.random.default_rng(0)
csv_path = tmp + "/big.csv"
with open(csv_path, "w") as fh:
    fh.write("a,b,label\n")
    for _ in range(3000):
        a, b = rng.standard_normal(2)
        fh.write(f"{a:.5f},{b:.5f},{int(a + b > 0) + int(a - b > 0)}\n")
ctx.dataset_csv.insert("big", csv_path, shard_rows=512)
ctx.observe.wait("big")
print("sharded CSV:", ctx.dataset_csv.metadata("big")["shards"],
      "shards")

# 2. Tensor ingest — image-shaped features from .npy (mmap'd).
imgs = rng.standard_normal((600, 28, 28, 1)).astype(np.float32)
labels = (imgs.mean(axis=(1, 2, 3)) > 0).astype(np.int64)
np.save(tmp + "/imgs.npy", imgs)
np.save(tmp + "/labels.npy", labels)
ctx.dataset_tensor.insert("imgs", tmp + "/imgs.npy",
                          labels_url=tmp + "/labels.npy",
                          shard_rows=128)
ctx.observe.wait("imgs")
print("tensor dataset:", ctx.dataset_tensor.metadata("imgs")["shards"],
      "shards of", ctx.dataset_tensor.metadata("imgs")["featureShape"])

# 3-4. Model + streaming train with a webhook + quantized artifact.
ctx.model.create("mlp", module_path="learningorchestra_tpu.models.mlp",
                 class_name="MLPClassifier",
                 class_parameters={"hidden_layer_sizes": [128],
                                   "num_classes": 3})
ctx.observe.wait("mlp")
ctx.train.create("fit1", model_name="mlp", method_parameters={
    "x": "$big", "y": "$big.label", "epochs": 10, "batch_size": 128,
    "quantize_checkpoint": True,
})
hook_url = f"http://127.0.0.1:{receiver.server_address[1]}/done"
ctx.observe.webhook("fit1", hook_url)
assert delivered.wait(300), "webhook never arrived"
print("webhook delivered:", events[0]["event"], "for",
      events[0]["name"])

# 5. Predict from the quantized serving artifact.
ctx.predict.create("pred1", model_name="fit1", parent_name="fit1",
                   method="predict_classes",
                   method_parameters={"x": "$big"})
ctx.observe.wait("pred1")
rows = ctx.predict.search("pred1", limit=5, skip=1)
print("predictions:", [r["result"] for r in rows])

receiver.server_close()
server.shutdown()
print("BEYOND-RAM PIPELINE DONE")
