"""Quickstart: the full train → tune → deploy → predict loop via the SDK.

Mirrors the reference's examples/ quickstart scripts (SURVEY.md §4: the
quickstart doubles as the integration flow). Run a stack first:

    rafiki-tpu stack start --workdir ./rafiki_stack
    JAX_PLATFORMS=cpu python examples/quickstart.py \
        --admin http://127.0.0.1:3000

On a CPU-only host keep JAX_PLATFORMS=cpu; on a TPU VM drop it.
"""

from __future__ import annotations

import argparse
import tempfile

from rafiki_tpu.utils.platform import apply_platform_env

apply_platform_env()

import numpy as np  # noqa: E402

from rafiki_tpu.client import Client  # noqa: E402
from rafiki_tpu.data import \
    generate_image_classification_dataset  # noqa: E402
from rafiki_tpu.models.mlp import JaxFeedForward  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--admin", default="http://127.0.0.1:3000")
    ap.add_argument("--trials", type=int, default=3)
    args = ap.parse_args()

    client = Client(args.admin)
    client.login("superadmin@rafiki", "rafiki")

    with tempfile.TemporaryDirectory() as d:
        train_p, val_p = f"{d}/train.npz", f"{d}/val.npz"
        generate_image_classification_dataset(train_p, 1024, seed=0)
        val = generate_image_classification_dataset(val_p, 256, seed=1)

        model = client.create_model("quickstart-mlp",
                                    "IMAGE_CLASSIFICATION", JaxFeedForward)
        job = client.create_train_job(
            app="quickstart", task="IMAGE_CLASSIFICATION",
            train_dataset_id=train_p, val_dataset_id=val_p,
            budget={"TRIAL_COUNT": args.trials},
            model_ids=[model["id"]])
        print("train job:", job["id"], job["status"])

        job = client.wait_until_train_job_finished(job["id"], timeout=900)
        best = client.get_best_trials_of_train_job(job["id"])
        print("best trial score:", best[0]["score"])

        ijob = client.create_inference_job(job["id"], max_workers=2)
        print("predictor:", ijob["predictor_url"])
        preds = client.predict(ijob["predictor_url"],
                               [val.images[i] for i in range(8)],
                               timeout=120)
        acc = np.mean([int(np.argmax(p)) == val.labels[i]
                       for i, p in enumerate(preds)])
        print(f"deployed ensemble accuracy on 8 queries: {acc:.2f}")
        client.stop_inference_job(ijob["id"])


if __name__ == "__main__":
    main()
