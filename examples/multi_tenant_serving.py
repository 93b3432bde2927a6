"""Multi-tenant LLM serving: N LoRA fine-tunes, one base, one engine.

The round-4 serving features end to end, library-level (no stack):

1. train TWO ``adapters_only`` LoRA fine-tunes of one base — only
   ``lora_a``/``lora_b`` move, so the trials share every other leaf;
2. stack them into ONE continuous-batching engine
   (``make_multi_adapter_engine``) — the base matmul runs once per
   fused step for the whole mixed-tenant batch, each request selecting
   its fine-tune by ``adapter_id``;
3. give each tenant its own system-prompt KV snapshot
   (``register_prefix(..., adapter_id=i)``) so shared prefixes skip
   prefill per tenant;
4. stream tokens as they decode (``poll_partial``).

Against the full stack the same features ride the REST API: deploy
with ``client.create_inference_job(job_id, budget={"MULTI_ADAPTER": 1})``,
route with ``client.predict(url, qs, sampling={"adapter_id": i})``, and
stream with ``client.predict_stream(url, qs)``.

    JAX_PLATFORMS=cpu python examples/multi_tenant_serving.py
"""

from __future__ import annotations

import tempfile

from rafiki_tpu.utils.platform import apply_platform_env

apply_platform_env()

from rafiki_tpu.data import \
    generate_text_classification_dataset  # noqa: E402
from rafiki_tpu.models.llama_lora import LlamaLoRA  # noqa: E402

KNOBS = {"max_epochs": 2, "vocab_size": 1 << 10, "hidden_dim": 64,
         "depth": 2, "n_heads": 4, "kv_ratio": 2, "lora_rank": 4,
         "max_len": 32, "model_parallel": 1, "learning_rate": 1e-2,
         "batch_size": 8, "bf16": False, "quick_train": True,
         "share_params": False, "adapters_only": True}


def main() -> None:
    with tempfile.TemporaryDirectory() as d:
        tenants = []
        for seed in (0, 1):  # two "tenants" fine-tune on their own data
            tr = f"{d}/tenant{seed}.jsonl"
            generate_text_classification_dataset(tr, 64, seed=seed)
            m = LlamaLoRA(**KNOBS)
            m.train(tr)
            tenants.append(m)

    base = tenants[0]
    engine = base.make_multi_adapter_engine(
        [m._params for m in tenants], max_slots=4, max_new_tokens=8)
    print(f"one engine, {engine.engine.n_adapters} tenants, "
          "one base model's HBM")

    # per-tenant system prompts: each adapter gets its own KV snapshot
    for aid in range(2):
        n = engine.register_prefix("tok1 tok2 tok3", adapter_id=aid)
        print(f"tenant {aid}: prefix KV cached ({n} tokens)")

    # mixed-tenant traffic decodes in the SAME fused steps, streaming
    prompt = "tok1 tok2 tok3 tok4"
    engine.submit("tenant-0", prompt, adapter_id=0)
    engine.submit("tenant-1", prompt, adapter_id=1)
    finals = {}
    while engine.busy:
        engine.step()
        for rid, delta in engine.poll_partial():
            print(f"  {rid} += {delta!r}")
        for rid, text in engine.poll():
            finals[rid] = text
    for rid in sorted(finals):
        print(f"{rid}: {finals[rid]!r}")
    assert finals["tenant-0"] != finals["tenant-1"]
    stats = engine.stats
    print(f"prefix hits: {stats['prefix_hits']}, "
          f"concurrent: {stats['max_concurrent']}")


if __name__ == "__main__":
    main()
