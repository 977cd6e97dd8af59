"""The train→deploy seam end to end (the KFP→object-store→KServe story):

1. publish a text dataset into the platform artifact store,
2. train() on it (the worker resolves artifact:// through the store),
3. publish the run's checkpoint as a named, versioned model artifact,
4. serve it by that name — `storage_uri="artifact://demo-model@1"` —
   with an explainer hop on the side.

Run:  python examples/train_publish_serve.py
"""

import json
import os
import tempfile
import urllib.request

from kubeflow_tpu.core.object import ObjectMeta
from kubeflow_tpu.core.serving import (
    BatchingSpec, ExplainerSpec, InferenceService, InferenceServiceSpec,
    ModelSpec, PredictorSpec,
)
from kubeflow_tpu.sdk import Client


def main() -> None:
    base_dir = tempfile.mkdtemp(prefix="kftpu-seam-")
    print("platform dir (checkpoints, artifact store, logs):", base_dir)
    client = Client.local(base_dir=base_dir)
    try:
        # 1. dataset → artifact://corpus@1
        corpus = os.path.join(client.cp.config.base_dir, "corpus.txt")
        with open(corpus, "w") as f:
            f.write("The quick brown fox jumps over the lazy dog. " * 200)
        ds = client.publish_file(corpus, name="corpus")
        print("dataset:", ds)

        # 2. train on the published dataset (BPE trained from it too)
        client.train(
            "seam", model="tiny",
            model_overrides={"vocab_size": 512, "max_seq_len": 64},
            steps=30, dataset_uri=ds, train_tokenizer_vocab=300,
            data={"global_batch": 8}, checkpoint=True,
            wait=True, timeout=600)

        # 3. checkpoint dir → artifact://demo-model@1 (a tree artifact)
        ckpt = os.path.join(client.cp.config.base_dir, "default", "seam",
                            "ckpt")
        model_uri = client.publish_model(ckpt, name="demo-model", version="1")
        print("model:", model_uri)

        # 4. serve by name — no file paths cross the subsystems
        isvc = client.apply(InferenceService(
            metadata=ObjectMeta(name="demo"),
            spec=InferenceServiceSpec(
                predictor=PredictorSpec(
                    model=ModelSpec(
                        model_name="demo", storage_uri=model_uri,
                        config={"preset": "tiny",
                                "overrides": {"vocab_size": 512,
                                              "max_seq_len": 64}}),
                    batching=BatchingSpec(max_batch_size=4, max_seq_len=64,
                                          page_size=16,
                                          chunked_prefill_tokens=32)),
                explainer=ExplainerSpec(handler="grad_x_input"))))
        ready = client.wait_for(isvc, "Ready", timeout=300)

        def post(path, body):
            req = urllib.request.Request(
                ready.status.url + path, data=json.dumps(body).encode(),
                headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=180) as r:
                return json.loads(r.read())

        out = post("/v1/completions", {"prompt": "The quick",
                                       "max_tokens": 8})
        print("completion:", repr(out["choices"][0]["text"]))
        exp = post("/v1/models/demo:explain", {"instances": ["The quick"]})
        scores = exp["explanations"][0]
        print("attribution:", list(zip(scores["tokens"],
                                       [round(s, 3)
                                        for s in scores["scores"]])))
    finally:
        client.shutdown()


if __name__ == "__main__":
    main()
