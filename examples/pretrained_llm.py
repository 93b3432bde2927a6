"""Pretrained-base LLM fine-tune with a real tokenizer (config #5).

The full round-trip a user with an HF-style Llama checkpoint follows:

1. train a byte-level BPE tokenizer on a local corpus and save the
   artifact;
2. point the ``LlamaLoRA`` template at the checkpoint
   (``pretrained_path`` — single ``.safetensors``, a sharded
   ``model-*-of-*.safetensors`` + index directory, or the index file)
   and the tokenizer (``tokenizer_path``); each base weight streams
   from the (mmap'd) file straight into its 2-D fsdp x tensor-parallel
   sharding — no host ever holds the full tree;
3. LoRA-fine-tune (base frozen, adapters/norms/head train) and
   generate with EXACT detokenization (the merge table travels inside
   dumped parameters, so serving hosts need no artifact file).

Zero egress here, so the "pretrained" checkpoint is synthesized by
exporting a freshly initialized base with
``export_llama_safetensors`` — byte-for-byte the layout conversion a
real HF download takes.

    JAX_PLATFORMS=cpu python examples/pretrained_llm.py
"""

from __future__ import annotations

import json
import tempfile

from rafiki_tpu.utils.platform import apply_platform_env

apply_platform_env()

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from rafiki_tpu.data import (ByteBPETokenizer,  # noqa: E402
                             generate_text_classification_dataset)
from rafiki_tpu.models.convert import \
    export_llama_safetensors  # noqa: E402
from rafiki_tpu.models.llama_lora import LlamaLoRA  # noqa: E402

KNOBS = {"max_epochs": 2, "vocab_size": 0,  # vocab follows the artifact
         "hidden_dim": 64, "depth": 2, "n_heads": 4, "kv_ratio": 2,
         "lora_rank": 4, "max_len": 32, "model_parallel": 1,
         "learning_rate": 1e-2, "batch_size": 8, "bf16": False,
         "remat": False, "moe_experts": 0, "quick_train": True,
         "share_params": False}


def main() -> None:
    with tempfile.TemporaryDirectory() as d:
        corpus = f"{d}/corpus.jsonl"
        generate_text_classification_dataset(corpus, 64, seed=0)

        # 1) tokenizer: train byte-BPE on the corpus text, save artifact
        texts = [rec["text"] for line in open(corpus) if line.strip()
                 for rec in [json.loads(line)] if "text" in rec]
        tok = ByteBPETokenizer.train(texts, vocab_size=300)
        tok_path = f"{d}/bpe.json"
        tok.save(tok_path)
        sample = texts[0][:40]
        assert tok.decode(tok.encode_ids(sample)) == sample  # lossless
        print(f"tokenizer: vocab={tok.vocab_size}, artifact={tok_path}")

        # 2) the "pretrained" base (stand-in for an HF download)
        base = LlamaLoRA(**KNOBS, tokenizer_path=tok_path,
                         pretrained_path="")
        module = base._module()
        params = module.init(jax.random.PRNGKey(7),
                             jnp.zeros((1, 8), jnp.int32))["params"]
        ckpt = f"{d}/base.safetensors"
        export_llama_safetensors(params, ckpt)
        print(f"checkpoint: {ckpt}")

        # 3) fine-tune over the imported base + serve
        model = LlamaLoRA(**KNOBS, tokenizer_path=tok_path,
                          pretrained_path=ckpt)
        model.train(corpus)
        score = model.evaluate(corpus)
        out = model.predict([sample])
        print(f"fine-tuned: inverse-perplexity={score:.4f}")
        print(f"prompt:     {sample!r}")
        print(f"generated:  {out[0]!r}")


if __name__ == "__main__":
    main()
