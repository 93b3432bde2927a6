"""LLM fine-tune + continuous-batch serving (BASELINE.md config #5).

Trains a small LlamaLoRA under the advisor, deploys it, and sends
overlapping generation requests — the inference worker serves them
through the slot-based continuous-batching decode loop.

    rafiki-tpu stack start --workdir ./rafiki_stack
    JAX_PLATFORMS=cpu python examples/serve_llm.py \
        --admin http://127.0.0.1:3000
"""

from __future__ import annotations

import argparse
import tempfile
import threading

from rafiki_tpu.utils.platform import apply_platform_env

apply_platform_env()

from rafiki_tpu.client import Client  # noqa: E402
from rafiki_tpu.data import \
    generate_text_classification_dataset  # noqa: E402
from rafiki_tpu.models.llama_lora import LlamaLoRA  # noqa: E402

#: tiny in-domain pins so the demo fits a laptop; drop for a real run
SMALL = {"hidden_dim": 64, "depth": 2, "n_heads": 4, "kv_ratio": 2,
         "lora_rank": 4, "max_len": 32, "model_parallel": 1,
         "learning_rate": 1e-2, "batch_size": 8, "bf16": False,
         "quick_train": True, "share_params": False}


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--admin", default="http://127.0.0.1:3000")
    args = ap.parse_args()

    client = Client(args.admin)
    client.login("superadmin@rafiki", "rafiki")

    with tempfile.TemporaryDirectory() as d:
        tr, va = f"{d}/train.jsonl", f"{d}/val.jsonl"
        generate_text_classification_dataset(tr, 128, seed=0)
        generate_text_classification_dataset(va, 32, seed=1)

        model = client.create_model("demo-llama", "LANGUAGE_MODELING",
                                    LlamaLoRA)
        job = client.create_train_job(
            app="llm-demo", task="LANGUAGE_MODELING",
            train_dataset_id=tr, val_dataset_id=va,
            budget={"TRIAL_COUNT": 2},
            model_ids=[model["id"]],
            train_args={"advisor": "random", "knob_overrides": SMALL})
        job = client.wait_until_train_job_finished(job["id"], timeout=900)
        print("train job:", job["status"])

        # deploy the best trial WITH speculative decoding: the other
        # completed trial serves as the draft MODEL (swap in a smaller
        # parameterization for a real speedup; prompt-lookup drafting
        # needs only SPECULATE_K). MAX_NEW_TOKENS caps generations.
        trials = [t for t in client.get_trials_of_train_job(job["id"])
                  if t["status"] == "COMPLETED"]
        best_list = client.get_best_trials_of_train_job(job["id"])
        if not best_list:
            raise SystemExit(
                f"no deployable trial (trials: "
                f"{[t['status'] for t in trials] or 'none completed'})")
        deploy_budget = {"SPECULATE_K": 4, "MAX_NEW_TOKENS": 8}
        others = [t["id"] for t in trials
                  if t["id"] != best_list[0]["id"]]
        if others:
            deploy_budget["DRAFT_TRIAL_ID"] = others[0]
        ijob = client.create_inference_job(job["id"], max_workers=1,
                                           budget=deploy_budget)
        url = ijob["predictor_url"]
        print("predictor:", url)

        # overlapping clients: requests admitted into free KV slots
        # mid-flight share one decode loop on the worker
        def ask(prompt: str) -> None:
            out = client.predict(url, [prompt], timeout=180)
            print(f"  {prompt!r} -> {out[0]!r}")

        threads = [threading.Thread(target=ask, args=(p,))
                   for p in ("tok1 tok2 tok3", "tok4 tok5",
                             "tok6 tok7 tok8 tok9")]
        for t in threads:
            t.start()
        for t in threads:
            t.join()

        # live serving health: req/s, latency percentiles, and the
        # engine's speculation counters (acceptance shows up here).
        # Counters publish every ~50 worker-loop iterations — keep a
        # little traffic flowing until a fresh snapshot lands.
        w = {}
        for i in range(40):
            client.predict(url, [f"tok{i % 5 + 1} tok2"], timeout=60)
            health = client.get_inference_job_health(ijob["id"])
            w = next(iter(health.get("workers", {}).values()), {})
            if w.get("engine_spec_calls", 0):
                break
        print("speculative calls:",
              w.get("engine_spec_draft_model_calls")
              or w.get("engine_spec_calls", 0),
              "accepted:", w.get("engine_spec_accepted", 0),
              "drafted:", w.get("engine_spec_drafted", 0))

        # seeded sampling: reproducible under any serving load
        samp = {"temperature": 0.8, "top_k": 40, "seed": 1234}
        a = client.predict(url, ["tok1 tok2"], timeout=180,
                           sampling=samp)
        b = client.predict(url, ["tok1 tok2"], timeout=180,
                           sampling=samp)
        print("seeded sampling reproducible:", a == b)

        # token streaming: SSE deltas as the decode loop produces them
        print("streaming:", end="", flush=True)
        for ev in client.predict_stream(url, ["tok1 tok2 tok3"],
                                        timeout=180):
            if "delta" in ev:
                print(" +", "".join(ev["delta"].values()),
                      end="", flush=True)
            elif ev.get("done") and ev.get("error"):
                print(f"\nstream failed: {ev['error']} "
                      f"(partial: {ev.get('partial')})")
            elif ev.get("done"):
                print("\nfinal:", (ev.get("predictions") or [""])[0])

        client.stop_inference_job(ijob["id"])


if __name__ == "__main__":
    main()
