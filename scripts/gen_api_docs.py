"""Generate docs/api.md — a compact API reference from live docstrings.

Walks the public modules, pulls classes/functions with their signatures
and first docstring paragraph. Regenerate after API changes:

    JAX_PLATFORMS=cpu python scripts/gen_api_docs.py
"""

from __future__ import annotations

import inspect
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ.setdefault("JAX_PLATFORMS", "cpu")

from rafiki_tpu.utils.platform import apply_platform_env

apply_platform_env()  # shared helper: the compile cache

MODULES = [
    ("rafiki_tpu.client.client", "Client SDK"),
    ("rafiki_tpu.cli", "CLI"),
    ("rafiki_tpu.analysis.cfg", "Lint: control-flow graphs"),
    ("rafiki_tpu.analysis.dataflow", "Lint: dataflow engines"),
    ("rafiki_tpu.analysis.threads", "Lint: thread model"),
    ("rafiki_tpu.analysis.summaries", "Lint: shared-state summaries"),
    ("rafiki_tpu.admin.admin", "Admin control plane"),
    ("rafiki_tpu.admin.services_manager", "Services manager"),
    ("rafiki_tpu.model.base", "Model contract"),
    ("rafiki_tpu.model.knob", "Knob system"),
    ("rafiki_tpu.model.dev", "Developer harness"),
    ("rafiki_tpu.model.loop", "Training loop helpers"),
    ("rafiki_tpu.data.dataset", "Datasets"),
    ("rafiki_tpu.data.loader", "Batch loading"),
    ("rafiki_tpu.advisor.base", "Advisors"),
    ("rafiki_tpu.worker.train", "Train worker"),
    ("rafiki_tpu.worker.inference", "Inference worker"),
    ("rafiki_tpu.serving.predictor", "Predictor"),
    ("rafiki_tpu.serving.decode_engine", "Continuous-batch decoding"),
    ("rafiki_tpu.store.meta_store", "MetaStore"),
    ("rafiki_tpu.store.param_store", "ParamStore"),
    ("rafiki_tpu.parallel.mesh", "Sub-mesh scheduling"),
    ("rafiki_tpu.parallel.sharding", "Sharding vocabulary"),
    ("rafiki_tpu.parallel.multihost", "Multi-host backend"),
    ("rafiki_tpu.ops.attention", "Flash attention (Pallas)"),
    ("rafiki_tpu.ops.paged_attention", "Paged flash attention (Pallas)"),
    ("rafiki_tpu.ops.patch_embed", "Patch embed (Pallas)"),
    ("rafiki_tpu.ops.latent_attention", "Latent paged attention (Pallas)"),
    ("rafiki_tpu.ops.ssm", "State-space step and chunked scan (Pallas)"),
    ("rafiki_tpu.ops.window_attention",
     "Sliding-window attention over per-slot rings"),
    ("rafiki_tpu.ops.ring_attention", "Ring attention"),
    ("rafiki_tpu.ops.ulysses", "Ulysses sequence parallelism"),
    ("rafiki_tpu.models.mlp", "Zoo: MLP"),
    ("rafiki_tpu.models.resnet", "Zoo: ResNet"),
    ("rafiki_tpu.models.vgg", "Zoo: VGG"),
    ("rafiki_tpu.models.densenet", "Zoo: DenseNet"),
    ("rafiki_tpu.models.vit", "Zoo: ViT"),
    ("rafiki_tpu.models.bert", "Zoo: BERT"),
    ("rafiki_tpu.models.llama_lora", "Zoo: Llama LoRA"),
    ("rafiki_tpu.models.latent_moe",
     "Decoder: latent attention + routed experts"),
    ("rafiki_tpu.models.hybrid_ssm_moe",
     "Decoder: a layer pattern over state-space, attention (plain, rotary, "
     "windowed), routed experts"),
    ("rafiki_tpu.models.pos_tagging", "Zoo: POS tagging"),
    ("rafiki_tpu.models.tabular", "Zoo: tabular"),
    ("rafiki_tpu.models.sklearn_models", "Zoo: sklearn/GBDT/SVM"),
]


def first_para(doc: str) -> str:
    import re

    if not doc:
        return ""
    para = doc.strip().split("\n\n")[0]
    text = " ".join(line.strip() for line in para.splitlines())
    # object reprs in auto-generated docstrings embed memory addresses —
    # normalize so regeneration is deterministic
    return re.sub(r"0x[0-9a-f]+", "0x...", text)


def sig_of(obj) -> str:
    import re

    try:
        sig = str(inspect.signature(obj))
    except (TypeError, ValueError):
        return "(...)"
    # default-value reprs can embed memory addresses (e.g. flax
    # sentinels) — normalize so regeneration is deterministic
    return re.sub(r"0x[0-9a-f]+", "0x...", sig)


def main() -> None:
    import importlib

    out = ["# API reference",
           "",
           "Generated by `scripts/gen_api_docs.py` from live docstrings —",
           "regenerate after API changes; do not edit by hand.",
           ""]
    for mod_name, title in MODULES:
        mod = importlib.import_module(mod_name)
        out.append(f"## {title} — `{mod_name}`")
        out.append("")
        mdoc = first_para(mod.__doc__ or "")
        if mdoc:
            out.append(mdoc)
            out.append("")
        for name, obj in sorted(vars(mod).items()):
            if name.startswith("_") or getattr(obj, "__module__", None) \
                    != mod_name:
                continue
            if inspect.isclass(obj):
                out.append(f"### `{name}`")
                doc = first_para(obj.__doc__ or "")
                if doc:
                    out.append(f"\n{doc}\n")
                methods = [
                    (mn, m) for mn, m in sorted(vars(obj).items())
                    if not mn.startswith("_")
                    and (inspect.isfunction(m)
                         or isinstance(m, (staticmethod, classmethod,
                                           property)))]
                for mn, m in methods:
                    if isinstance(m, property):
                        out.append(f"- `{mn}` (property) — "
                                   f"{first_para((m.fget and m.fget.__doc__) or '') or '—'}")
                        continue
                    fn = m.__func__ if isinstance(
                        m, (staticmethod, classmethod)) else m
                    out.append(f"- `{mn}{sig_of(fn)}` — "
                               f"{first_para(fn.__doc__ or '') or '—'}")
                out.append("")
            elif inspect.isfunction(obj):
                out.append(f"### `{name}{sig_of(obj)}`")
                doc = first_para(obj.__doc__ or "")
                if doc:
                    out.append(f"\n{doc}")
                out.append("")
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "docs", "api.md")
    with open(path, "w") as f:
        f.write("\n".join(out).rstrip() + "\n")
    print(f"wrote {path} ({len(out)} blocks)")


if __name__ == "__main__":
    main()
