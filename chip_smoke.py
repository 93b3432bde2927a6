#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that rafiki-tpu still starts on the chip.

Drives the two hot paths once, through the entry points a user calls, on
one TPU chip: ViT-B/16 at its published width trains through
``tune_model``; a ``LlamaLoRA`` at the top of its knob space trains and is
then served from a paged KV pool through ``make_decode_engine`` /
``DecodeEngine.step`` (the ``InferenceWorker``'s path) with the Pallas
block-table kernels checked against the page gather and the contiguous
engine; and ``rafiki-tpu stack start`` trains, deploys and answers three
predictions. Weights are random, from ``--seed``; sizes are a smoke's.

    python chip_smoke.py                 # one chip: device train serve stack
    python chip_smoke.py --chips 4       # four chips: lanes + mesh, nothing else
    python chip_smoke.py --rehearse      # tiny sizes on the CPU; never "ok"

One process holds a chip at a time: THIS process never imports jax. Each
phase is a child (``--phase NAME``), run one after another; each prints
one JSON object on its last line (what it asserted, seconds, compile
seconds, cache hits, ``peak_bytes_in_use``). The last line of a passing
run is exactly

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

with the device as the first child saw it. Any failed phase, any platform
but ``tpu``, or a missing repo beside this file: exit code != 0 and no
such line. ``--rehearse`` shrinks the sizes and runs the paged kernels
through the Pallas interpreter so the control flow can be walked on the
CPU; it still fails at the device check, and so at the end.

All children and all services of the stack share one compile cache:
``$JAX_COMPILATION_CACHE_DIR`` where set, ``<checkout>/.jax_cache``
otherwise (``rafiki_tpu.utils.platform``). Logs of every child land in
``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
LOG_DIR = os.path.join(HERE, "chiprun_out", "chip_smoke")
#: the contract's limit is 1200 s; leave the parent room to clean up
DEADLINE_S = 1140.0
ONE_CHIP_PHASES = ("device", "train", "serve", "stack")
#: bf16 keeps 8 significant bits; the kernel accumulates in f32 straight
#: off the bf16 pool while the gather's einsums round scores and
#: probabilities to bf16, and the difference compounds once per layer.
#: Logits of two paths may differ by this fraction of the reference's
#: largest |logit| — the same bound decides whether a diverging greedy
#: token was a tie.
LOGIT_TOL = 0.05
#: relative tolerance on each epoch's mean loss, 2x2 mesh vs one device
MESH_LOSS_TOL = 0.02

SIZES = {
    "full": {
        # ViT-B/16 as published: patch 16, hidden 768, depth 12, 12
        # heads, MLP 4x768 = 3072, 224x224x3, bf16, batch 64
        "vit": {"image_size": 224, "n_classes": 1000, "n_train": 256,
                "n_val": 64,
                "knobs": {"patch_size": 16, "hidden_dim": 768, "depth": 12,
                          "n_heads": 12, "batch_size": 64, "bf16": True}},
        # the top of LlamaLoRA's knob space: 8 q heads / 2 kv heads of 64
        "lm": {"n_train": 64, "n_val": 16, "words": 100,
               "knobs": {"hidden_dim": 512, "depth": 8, "n_heads": 8,
                         "kv_ratio": 4, "max_len": 128, "lora_rank": 8,
                         "batch_size": 16, "bf16": True}},
        "serve": {"prompt_words": [3, 9, 20, 40, 70], "max_new": 12,
                  "max_slots": 4, "prefill_chunk": 32},
        "stack": {"n_train": 512, "n_val": 128},
    },
    "rehearse": {
        "vit": {"image_size": 32, "n_classes": 10, "n_train": 64,
                "n_val": 16,
                "knobs": {"patch_size": 16, "hidden_dim": 96, "depth": 2,
                          "n_heads": 4, "batch_size": 16, "bf16": True}},
        "lm": {"n_train": 16, "n_val": 8, "words": 40,
               "knobs": {"hidden_dim": 64, "depth": 2, "n_heads": 4,
                         "kv_ratio": 2, "max_len": 64, "lora_rank": 4,
                         "batch_size": 8, "bf16": True}},
        "serve": {"prompt_words": [3, 9, 20, 40], "max_new": 6,
                  "max_slots": 2, "prefill_chunk": 16},
        "stack": {"n_train": 128, "n_val": 32},
    },
}
VIT_PINS = {"learning_rate": 3e-4, "weight_decay": 1e-4, "warmup_frac": 0.1}
LM_PINS = {"learning_rate": 3e-3, "lora_scale": 1.0, "model_parallel": 1,
           "remat_policy": "none", "overlap_collectives": False}
KV_PAGE_SIZE = 16


# ---------------------------------------------------------------------
# children: everything below here may import jax
# ---------------------------------------------------------------------


def _sizes(args) -> dict:
    return SIZES["rehearse" if args.rehearse else "full"]


class _Phase:
    """What one child reports: checks (all must hold), facts, timings —
    and, for a child that runs ``on_device``, what it compiled and the
    device's peak memory. A child that must leave the chip to others
    (``on_device=False``) never touches a backend here."""

    def __init__(self, name: str, on_device: bool = True) -> None:
        self.name = name
        self.on_device = on_device
        self.checks = {}
        self.facts = {}
        self.t0 = time.monotonic()
        self.compile_s = 0.0
        self.cache_hits = 0
        self.cache_misses = 0
        if not on_device:
            return
        import jax.monitoring as monitoring

        def on_duration(event: str, secs: float, **_kw) -> None:
            if event == "/jax/core/compile/backend_compile_duration":
                self.compile_s += secs

        def on_event(event: str, **_kw) -> None:
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1
            elif event == "/jax/compilation_cache/cache_misses":
                self.cache_misses += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)

    def check(self, name: str, ok, detail=None) -> None:
        self.checks[name] = bool(ok)
        if detail is not None:
            self.facts[name] = detail

    def finish(self) -> int:
        ok = bool(self.checks) and all(self.checks.values())
        report = {"phase": self.name, "ok": ok,
                  "seconds": round(time.monotonic() - self.t0, 2)}
        if self.on_device:
            import jax

            stats = jax.local_devices()[0].memory_stats() or {}
            report.update(
                compile_seconds=round(self.compile_s, 2),
                cache_hits=self.cache_hits,
                cache_misses=self.cache_misses,
                peak_bytes_in_use=stats.get("peak_bytes_in_use"))
        print(json.dumps({**report, "checks": self.checks, **self.facts}),
              flush=True)
        return 0 if ok else 1


def _device_facts() -> dict:
    import jax

    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _on_tpu(tree) -> bool:
    import jax

    leaves = jax.tree_util.tree_leaves(tree)
    return bool(leaves) and all(
        d.platform == "tpu" for leaf in leaves for d in leaf.devices())


def phase_device(args) -> int:
    from importlib import metadata

    import jax
    import jaxlib

    from rafiki_tpu.utils.platform import compile_cache_path
    from rafiki_tpu.worker.train import _PEAK_FLOPS_BF16, _device_peak_flops

    ph = _Phase("device")
    dev = _device_facts()
    ph.facts["device"] = dev
    ph.check("platform_is_tpu", dev["platform"] == "tpu")
    ph.check("device_count", dev["count"] == args.chips,
             f"{dev['count']} (want {args.chips})")
    try:
        libtpu = metadata.version("libtpu")
    except metadata.PackageNotFoundError:
        libtpu = None
    ph.facts["versions"] = {"jax": jax.__version__,
                            "jaxlib": jaxlib.__version__, "libtpu": libtpu}
    ph.facts["compile_cache"] = compile_cache_path()
    ph.facts["cache_dir_from_env_at_start"] = args.cache_env_at_start
    kind = dev["kind"].lower()
    entry = next((k for k, _ in _PEAK_FLOPS_BF16 if k in kind), None)
    ph.facts["peak_flops_bf16"] = _device_peak_flops()
    ph.facts["peak_flops_table_entry"] = entry
    if dev["platform"] == "tpu":
        ph.check("peak_flops_known", ph.facts["peak_flops_bf16"] > 0)
    return ph.finish()


def _probe_template(base, sink: dict, lowered_step=None):
    """``base`` with a ``train`` that, once the template's own train has
    run, notes what ``tune_model`` throws away with the model: where the
    parameters live and, given ``lowered_step(model) -> text``, whether
    the step's program carries a Pallas kernel."""
    import jax

    class Probed(base):
        def train(self, dataset_path, ctx=None):
            super().train(dataset_path, ctx)
            sink["params_on_tpu"] = _on_tpu(self._params)
            sink["n_params"] = int(sum(
                x.size for x in jax.tree_util.tree_leaves(self._params)))
            if lowered_step is not None:
                sink["step_has_tpu_custom_call"] = (
                    "tpu_custom_call" in lowered_step(self))

    Probed.__name__ = base.__name__
    return Probed


def _train_vit(ph, sizes, work, seed) -> None:
    import jax
    import jax.numpy as jnp
    import optax

    from rafiki_tpu.data import generate_image_classification_dataset
    from rafiki_tpu.model import tune_model
    from rafiki_tpu.models.vit import ViTBase16
    from rafiki_tpu.ops import attention

    cfg = sizes["vit"]
    train_p, val_p = f"{work}/vit_train.npz", f"{work}/vit_val.npz"
    geom = dict(image_size=cfg["image_size"], n_channels=3,
                n_classes=cfg["n_classes"])
    generate_image_classification_dataset(train_p, cfg["n_train"],
                                          seed=seed, **geom)
    generate_image_classification_dataset(val_p, cfg["n_val"],
                                          seed=seed + 1, **geom)
    knobs = {**cfg["knobs"], **VIT_PINS}
    sink = {}

    def lowered_step(model) -> str:
        """The template's own module under ``value_and_grad`` at the
        train step's shapes and dtype (the step itself is a closure of
        ``ViTBase16.train``)."""
        module, dtype = model._module(), model._dtype()
        b, hw = int(model.knobs["batch_size"]), cfg["image_size"]

        def loss(p, xb, yb):
            logits = module.apply({"params": p}, xb.astype(dtype))
            return optax.softmax_cross_entropy_with_integer_labels(
                logits.astype(jnp.float32), yb).mean()

        return jax.jit(jax.value_and_grad(loss)).lower(
            model._params,
            jax.ShapeDtypeStruct((b, hw, hw, 3), jnp.float32),
            jax.ShapeDtypeStruct((b,), jnp.int32)).as_text()

    t0 = time.monotonic()
    result = tune_model(_probe_template(ViTBase16, sink, lowered_step),
                        train_p, val_p, total_trials=1,
                        advisor_type="random", seed=seed,
                        knob_overrides=knobs)
    losses = [float(v) for v in
              result.trials[0].logger.get_values("loss")]
    seq = (cfg["image_size"] // knobs["patch_size"]) ** 2 + 1
    ph.facts["vit"] = {
        "knobs": knobs, "mlp_dim": 4 * knobs["hidden_dim"],
        "image": [cfg["image_size"], cfg["image_size"], 3],
        "n_params": sink.get("n_params"),
        "steps": len(losses) * -(-cfg["n_train"] // knobs["batch_size"]),
        "epoch_loss": losses, "val_accuracy": result.best_score,
        "seconds": round(time.monotonic() - t0, 2),
        # seq <= XLA_SHORT_SEQ takes XLA's fused attention ON PURPOSE
        # (ops/attention.py: measured faster at short sequences), so the
        # step's only Pallas kernel is patch_embed
        "attention_route": (
            f"xla_short_seq (seq {seq} <= XLA_SHORT_SEQ "
            f"{attention.XLA_SHORT_SEQ})" if seq <= attention.XLA_SHORT_SEQ
            else "pallas flash_attention")}
    ph.check("vit_loss_finite", all(map(math.isfinite, losses)) and losses)
    ph.check("vit_loss_fell", len(losses) > 1 and losses[-1] < losses[0])
    ph.check("vit_params_on_tpu", sink.get("params_on_tpu"))
    ph.check("vit_step_has_patch_embed_tpu_custom_call",
             sink.get("step_has_tpu_custom_call"))


def _lm_datasets(cfg, work, seed, tag="lm"):
    from rafiki_tpu.data import generate_text_classification_dataset

    train_p, val_p = f"{work}/{tag}_train.jsonl", f"{work}/{tag}_val.jsonl"
    generate_text_classification_dataset(train_p, cfg["n_train"],
                                         max_len=cfg["words"], seed=seed)
    generate_text_classification_dataset(val_p, cfg["n_val"],
                                         max_len=cfg["words"],
                                         seed=seed + 1)
    return train_p, val_p


def _train_lm(ph, sizes, work, seed) -> None:
    from rafiki_tpu.model import tune_model
    from rafiki_tpu.models.llama_lora import LlamaLoRA
    from rafiki_tpu.store.param_store import ParamStore

    cfg = sizes["lm"]
    train_p, val_p = _lm_datasets(cfg, work, seed)
    knobs = {**cfg["knobs"], **LM_PINS}
    sink = {}
    t0 = time.monotonic()
    result = tune_model(_probe_template(LlamaLoRA, sink), train_p, val_p,
                        total_trials=1, advisor_type="random", seed=seed,
                        knob_overrides=knobs)
    losses = [float(v) for v in
              result.trials[0].logger.get_values("loss")]
    # hand the trial to the serve phase the way a train worker hands it
    # to an inference worker: parameters through the ParamStore, knobs
    # beside them
    ParamStore.from_uri(f"file://{work}/params").save(
        "smoke-lm", result.best_params)
    with open(f"{work}/lm_knobs.json", "w") as f:
        json.dump(result.best_knobs, f)
    heads = knobs["n_heads"]
    ph.facts["llama_lora"] = {
        "knobs": knobs, "q_heads": heads,
        "kv_heads": heads // knobs["kv_ratio"],
        "head_dim": knobs["hidden_dim"] // heads,
        "n_params": sink.get("n_params"), "epoch_loss": losses,
        "steps": len(losses) * -(-cfg["n_train"] // knobs["batch_size"]),
        "score": result.best_score,
        "seconds": round(time.monotonic() - t0, 2)}
    ph.check("lm_loss_finite", all(map(math.isfinite, losses)) and losses)
    ph.check("lm_loss_fell", len(losses) > 1 and losses[-1] < losses[0])
    ph.check("lm_params_on_tpu", sink.get("params_on_tpu"))


def phase_train(args) -> int:
    ph = _Phase("train")
    sizes = _sizes(args)
    _train_vit(ph, sizes, args.workdir, args.seed)
    _train_lm(ph, sizes, args.workdir, args.seed)
    return ph.finish()


# ---- serve ----------------------------------------------------------


def _prompts(words_per_prompt, seed):
    """Prompts over the training corpus' vocabulary (``tokN`` words)."""
    import numpy as np

    rng = np.random.default_rng(seed + 17)
    return [" ".join(f"tok{w}" for w in rng.integers(0, 500, size=n))
            for n in words_per_prompt]


def _drive_engine(model, prompts, cfg, **engine_kw):
    """Serve ``prompts`` the way ``InferenceWorker`` does: one
    continuous-batching engine from ``make_decode_engine``, requests
    submitted as text, ``step()`` until every reply is in. Returns
    (generated token ids per prompt, the core engine)."""
    text_engine = model.make_decode_engine(
        max_slots=cfg["max_slots"], max_new_tokens=cfg["max_new"],
        steps_per_sync=4, prefill_chunk=cfg["prefill_chunk"], **engine_kw)
    core = text_engine.engine
    for i, text in enumerate(prompts):
        text_engine.submit(i, text)
    done = {}
    for _ in range(10_000):
        if len(done) == len(prompts):
            break
        text_engine.step()
        done.update(dict(core.poll()))
    if len(done) != len(prompts):
        raise RuntimeError(f"engine stalled: only {sorted(done)} replied")
    return [list(map(int, done[i])) for i in range(len(prompts))], core


def _step_lowered_text(core) -> str:
    """The engine's own jitted greedy step, lowered with the operands
    ``DecodeEngine.step`` hands it."""
    import jax.numpy as jnp

    return core._step_fns[False].lower(
        core.params, core._cache, jnp.asarray(core._tok),
        jnp.asarray(core._pos), jnp.asarray(core._prompt_buf),
        jnp.asarray(core._prompt_len), jnp.asarray(core._stop_pos),
        jnp.asarray(core._temp), jnp.asarray(core._topk),
        jnp.asarray(core._topp), jnp.asarray(core._seed),
        jnp.asarray(core._aid), core._ptab_arg()).as_text()


def _next_token_logits(core, contexts):
    """f32 logits of the token after each of ``contexts`` (lists of ids)
    through ``core``'s own module, parameters and cache layout, in the
    engine's own order of calls: every token but the last through ONE
    multi-token window (the chunked-prefill leg), the last one as a
    single-token step (the decode leg) — so a paged-kernel module runs
    its window kernel and then its step kernel. Contexts are served in
    batches of the engine's slot count, on a fresh cache."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    module, B = core.module, core.B
    width = max(len(c) for c in contexts) - 1
    kw = {}
    if core.paged:  # slot i owns pool pages 1 + i*T .. (i+1)*T
        tabs = 1 + np.arange(B * core._n_table, dtype=np.int32).reshape(
            B, core._n_table)
        if tabs.max() >= core.n_pages:
            raise RuntimeError("the logits probe needs a full-cover pool")
        kw["page_tables"] = jnp.asarray(tabs)

    @jax.jit
    def run(params, cache, toks, pos, last_tok, last_pos):
        if width > 0:
            _, muts = module.apply(
                {"params": params, "cache": cache}, toks, positions=pos,
                decode=True, mutable=["cache"], **kw)
            cache = muts["cache"]
        logits, _ = module.apply(
            {"params": params, "cache": cache}, last_tok[:, None],
            positions=last_pos[:, None], decode=True, mutable=["cache"],
            **kw)
        return logits[:, -1].astype(jnp.float32)

    out = []
    for lo in range(0, len(contexts), B):
        rows = [np.asarray(c, np.int32) for c in contexts[lo:lo + B]]
        rows += [rows[-1]] * (B - len(rows))  # pad the batch
        toks = np.zeros((B, max(width, 1)), np.int32)
        pos = np.zeros((B, max(width, 1)), np.int32)
        for i, r in enumerate(rows):
            n = len(r) - 1  # window tokens; overhang repeats the last
            idx = np.minimum(np.arange(max(width, 1)), max(n - 1, 0))
            toks[i], pos[i] = r[idx], idx
        cache = module.init(jax.random.PRNGKey(0),
                            jnp.zeros((B, 1), jnp.int32),
                            decode=True)["cache"]
        last = np.asarray([r[-1] for r in rows], np.int32)
        last_pos = np.asarray([len(r) - 1 for r in rows], np.int32)
        logits = run(core.params, cache, jnp.asarray(toks),
                     jnp.asarray(pos), jnp.asarray(last),
                     jnp.asarray(last_pos))
        out.extend(np.asarray(logits)[:len(contexts[lo:lo + B])])
    return out


def _serve_variant(ph, tag, model, prompts, cfg, kernel_flag) -> None:
    """One KV dtype: the paged engine as the code selects it, the same
    engine forced onto the page gather, and the contiguous engine."""
    import jax
    import numpy as np

    kernel_toks, kernel = _drive_engine(
        model, prompts, cfg, kv_page_size=KV_PAGE_SIZE,
        paged_kernel=kernel_flag)
    gather_toks, gather = _drive_engine(
        model, prompts, cfg, kv_page_size=KV_PAGE_SIZE, paged_kernel=False)
    contig_toks, contig = _drive_engine(model, prompts, cfg)
    stats = kernel.stats_snapshot()
    mode = int(kernel.paged_kernel_mode)
    ph.facts[f"{tag}_paged_kernel_mode"] = mode
    ph.facts[f"{tag}_kv_cache_dtypes"] = sorted({
        str(leaf.dtype) for leaf in jax.tree_util.tree_leaves(
            kernel._cache)})
    ph.facts[f"{tag}_kernel_tokens"] = {
        k: int(stats[k]) for k in ("paged_kernel_step_tokens",
                                   "paged_kernel_window_tokens",
                                   "prefill_calls", "tokens_generated",
                                   "kv_pages_high_water")}
    ph.check(f"{tag}_paged_kernel_mode_is_windowed", mode == 2)
    ph.check(f"{tag}_gather_engine_mode_is_0",
             gather.paged_kernel_mode == 0 and
             contig.paged_kernel_mode == 0)
    ph.check(f"{tag}_step_kernel_carried_tokens",
             mode >= 1 and stats["paged_kernel_step_tokens"] > 0)
    ph.check(f"{tag}_window_kernel_carried_tokens",
             mode >= 2 and stats["paged_kernel_window_tokens"] > 0)
    if kernel_flag is None:  # on the chip: Mosaic, not the interpreter
        ph.check(f"{tag}_step_has_tpu_custom_call",
                 "tpu_custom_call" in _step_lowered_text(kernel))
    ph.check(f"{tag}_all_replies_full_length",
             all(len(t) == cfg["max_new"] for t in kernel_toks))

    # first-step logits: every prompt, three paths
    enc = model.tokenizer.encode
    max_len = int(model.knobs["max_len"])
    contexts = []
    for text in prompts:
        row, n = enc(text, max_len)
        contexts.append([int(t) for t in row[:max(1, int(n))]])
    ref = _next_token_logits(gather, contexts)
    worst = {}
    for name, core in (("kernel", kernel), ("contiguous", contig)):
        got = _next_token_logits(core, contexts)
        worst[name] = max(
            float(np.max(np.abs(g - r)) / np.max(np.abs(r)))
            for g, r in zip(got, ref))
    ph.facts[f"{tag}_first_step_logit_diff_vs_gather"] = worst
    ph.check(f"{tag}_kernel_logits_match_gather",
             worst["kernel"] <= LOGIT_TOL)
    ph.check(f"{tag}_contiguous_logits_match_gather",
             worst["contiguous"] <= LOGIT_TOL)

    # greedy tokens: the same, or a tie inside the tolerance at the
    # FIRST divergence (on the chip the kernel and XLA sum in different
    # orders; later tokens then follow a different prefix)
    divergences = []
    for name, toks in (("kernel", kernel_toks), ("contiguous",
                                                 contig_toks)):
        for i, (a, b) in enumerate(zip(toks, gather_toks)):
            j = next((j for j, (x, y) in enumerate(zip(a, b)) if x != y),
                     None)
            if j is None:
                continue
            lg = _next_token_logits(gather, [contexts[i] + b[:j]])[0]
            gap = float(lg[b[j]] - lg[a[j]]) / float(np.max(np.abs(lg)))
            divergences.append({"path": name, "prompt": i, "token": j,
                                "gather_token": b[j], "path_token": a[j],
                                "top_two_gap": gap})
    ph.facts[f"{tag}_token_divergences"] = divergences
    ph.check(f"{tag}_greedy_tokens_same_or_tied",
             all(abs(d["top_two_gap"]) <= LOGIT_TOL for d in divergences))
    for core in (kernel, gather, contig):
        core.close()


def phase_serve(args) -> int:
    from rafiki_tpu.models.llama_lora import LlamaLoRA
    from rafiki_tpu.store.param_store import ParamStore

    ph = _Phase("serve")
    cfg = _sizes(args)["serve"]
    with open(f"{args.workdir}/lm_knobs.json") as f:
        knobs = json.load(f)
    dump = ParamStore.from_uri(f"file://{args.workdir}/params").load(
        "smoke-lm")
    if dump is None:
        raise RuntimeError("the train phase left no parameters")
    prompts = _prompts(cfg["prompt_words"], args.seed)
    # None = what the code selects (the kernels, on a TPU); a rehearsal
    # forces them through the Pallas interpreter instead
    kernel_flag = True if args.rehearse else None
    ph.facts.update(kv_page_size=KV_PAGE_SIZE, logit_tolerance=LOGIT_TOL,
                    prompt_words=cfg["prompt_words"],
                    new_tokens=cfg["max_new"],
                    kernel_through="pallas interpreter (rehearsal)"
                    if args.rehearse else "mosaic")
    for tag, extra in (("bf16", {}), ("int8kv", {"kv_cache_int8": True})):
        model = LlamaLoRA(**{**knobs, **extra})
        model.load_parameters(dump)
        ph.check(f"{tag}_params_on_tpu", _on_tpu(model._params))
        _serve_variant(ph, tag, model, prompts, cfg, kernel_flag)
    return ph.finish()


# ---- stack ----------------------------------------------------------


def _cli(*argv, env=None, timeout=300):
    return subprocess.run([sys.executable, "-m", "rafiki_tpu.cli", *argv],
                          env=env, timeout=timeout, check=True,
                          capture_output=True, text=True)


def _jiffies_now() -> float:
    with open("/proc/uptime") as f:
        return float(f.read().split()[0]) * os.sysconf("SC_CLK_TCK")


def _wait(what: str, fn, timeout: float, poll: float = 0.25):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        out = fn()
        if out:
            return out
        time.sleep(poll)
    raise TimeoutError(f"timed out after {timeout:.0f}s waiting for {what}")


def phase_stack(args) -> int:
    """The normal entry point, small. This child never initialises a jax
    backend: the chip belongs to the stack's workers, one at a time."""
    import re

    import numpy as np

    from rafiki_tpu.client import Client
    from rafiki_tpu.data import generate_image_classification_dataset
    from rafiki_tpu.models.mlp import JaxFeedForward
    from rafiki_tpu.store.meta_store import MetaStore

    ph = _Phase("stack", on_device=False)
    checks, facts = ph.checks, ph.facts
    cfg = _sizes(args)["stack"]
    platform = "cpu" if args.rehearse else "tpu"
    work = os.path.join(args.workdir, "stack")
    os.makedirs(work, exist_ok=True)
    env = dict(os.environ, JAX_PLATFORMS=platform)
    facts["asked_platform"] = platform

    native = os.path.join(HERE, "rafiki_tpu", "native", "build")
    facts["native_prebuilt"] = sorted(os.listdir(native)) \
        if os.path.isdir(native) else []
    _cli("stack", "start", "--workdir", work, "--port", "0", env=env)
    try:
        url = open(os.path.join(work, "admin.url")).read().strip()
        client = Client(url)
        client.login("superadmin@rafiki", "rafiki")
        health = client._call("GET", "/health")
        facts["admin"] = {"platform": health["platform"],
                          "n_slots": health["n_slots"],
                          "free_slots": health["free_slots"]}
        checks["admin_reports_platform"] = health["platform"] == platform
        checks["admin_one_slot"] = health["n_slots"] == 1
        dp = health["data_plane"]
        facts["data_plane"] = {
            "up": dp["up"], "native_kvd": os.path.exists(
                os.path.join(native, "rafiki-kvd")),
            "native_bpe": os.path.exists(
                os.path.join(native, "librbpe.so"))}
        checks["data_plane_native_and_up"] = bool(
            dp["up"] and facts["data_plane"]["native_kvd"])

        train_p, val_p = f"{work}/train.npz", f"{work}/val.npz"
        generate_image_classification_dataset(train_p, cfg["n_train"],
                                              seed=args.seed)
        val = generate_image_classification_dataset(val_p, cfg["n_val"],
                                                    seed=args.seed + 1)
        model = client.create_model("smoke-mlp", "IMAGE_CLASSIFICATION",
                                    JaxFeedForward)
        job = client.create_train_job(
            app="smoke", task="IMAGE_CLASSIFICATION",
            train_dataset_id=train_p, val_dataset_id=val_p,
            budget={"TRIAL_COUNT": 1}, model_ids=[model["id"]])
        job = client.wait_until_train_job_finished(job["id"], timeout=420)
        best = client.get_best_trials_of_train_job(job["id"])
        facts["train_job"] = {"status": job["status"],
                              "best_score": best[0]["score"] if best
                              else None}
        checks["train_job_finished_with_a_trial"] = (
            job["status"] == "STOPPED" and bool(best))

        # one chip: the train worker must be GONE before an inference
        # worker may open it. Watch its pid die; only then deploy.
        meta = MetaStore(os.path.join(work, "meta.db"), read_only=True)

        def workers(kind):
            return [r for r in meta.get_services()
                    if r["service_type"] == kind]

        train_workers = workers("TRAIN_WORKER")
        pids = [int(r["pid"]) for r in train_workers]

        def all_gone():
            return not any(os.path.exists(f"/proc/{p}") for p in pids)

        _wait("the train worker to exit", all_gone, 60)
        gone_at = _jiffies_now()
        _wait("the train worker's slot to be released",
              lambda: client._call("GET", "/health")["free_slots"] == 1,
              30)
        ijob = client.create_inference_job(job["id"], max_workers=1)
        queries = [val.images[i] for i in range(3)]
        preds = client.predict(ijob["predictor_url"], queries, timeout=240)
        inf_workers = workers("INFERENCE_WORKER")
        started = [float(r["start_time"]) for r in inf_workers]
        facts["order"] = {"train_worker_gone_at_jiffies": gone_at,
                          "inference_worker_started_at_jiffies": started}
        checks["train_worker_exited_before_inference_worker_started"] = (
            bool(pids) and bool(started) and min(started) >= gone_at - 1)
        checks["three_predictions"] = (
            len(preds) == 3 and all(
                len(p) == 10 and all(map(math.isfinite, p)) for p in preds))
        facts["predicted_classes"] = [int(np.argmax(p)) for p in preds]
        facts["true_classes"] = [int(val.labels[i]) for i in range(3)]
        client.stop_inference_job(ijob["id"])

        def platforms(rows):
            seen = []
            for r in rows:
                tag = r["spawn_spec"]["tag"]
                with open(os.path.join(work, f"{tag}.log"),
                          errors="replace") as f:
                    seen += re.findall(r"worker: platform=(\w+) "
                                       r"devices=(\[.*\])", f.read())
            return seen

        for kind, rows in (("train", train_workers),
                           ("inference", inf_workers)):
            seen = platforms(rows)
            facts[f"{kind}_worker_devices"] = seen
            checks[f"{kind}_worker_on_{platform}"] = bool(seen) and all(
                p == platform for p, _ in seen)
    finally:
        _cli("stack", "stop", "--workdir", work, env=env)
        # keep the services' logs where the run's output is collected
        os.makedirs(LOG_DIR, exist_ok=True)
        for name in os.listdir(work):
            if name.endswith(".log"):
                shutil.copy(os.path.join(work, name),
                            os.path.join(LOG_DIR, f"stack-{name}"))
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR", "")
    facts["compile_cache"] = cache
    facts["compile_cache_entries"] = len(os.listdir(cache)) \
        if os.path.isdir(cache) else 0
    checks["never_initialised_a_backend"] = "jax" not in sys.modules or \
        not sys.modules["jax"]._src.xla_bridge._backends
    return ph.finish()


# ---- four chips -----------------------------------------------------


def phase_inventory(args) -> int:
    """The inventory as the ServicesManager takes it (``probe_devices``
    in a throwaway subprocess — this child opens no chip) and the
    environment ``submesh_env_vars`` gives each one-chip slot."""
    from rafiki_tpu.admin.services_manager import probe_devices
    from rafiki_tpu.parallel.mesh import (DeviceSpec, SubMeshAllocator,
                                          submesh_env_vars)

    ph = _Phase("inventory", on_device=False)
    inv = probe_devices()
    alloc = SubMeshAllocator(
        [DeviceSpec.from_probe(d) for d in inv["devices"]], 1)
    envs = []
    for _ in range(alloc.n_slots):
        slot = alloc.acquire(timeout=0)
        envs.append(submesh_env_vars(inv["platform"], slot))
    ph.check("platform", inv["platform"] == ("cpu" if args.rehearse
                                             else "tpu"))
    ph.check("four_devices_four_slots",
             len(inv["devices"]) == 4 and alloc.n_slots == 4)
    ph.facts.update(platform=inv["platform"], devices=inv["devices"],
                    slot_envs=envs)
    return ph.finish()


def _barrier(work: str, tag: str, lane: int, n: int, timeout=180) -> None:
    open(os.path.join(work, f"{tag}.{lane}"), "w").close()
    _wait(f"all {n} lanes at {tag}", lambda: all(
        os.path.exists(os.path.join(work, f"{tag}.{i}"))
        for i in range(n)), timeout, poll=0.1)


def _chip_files() -> list:
    """Accelerator device files this process holds open."""
    seen = set()
    for fd in os.listdir("/proc/self/fd"):
        try:
            target = os.readlink(f"/proc/self/fd/{fd}")
        except OSError:
            continue
        if target.startswith(("/dev/accel", "/dev/vfio/")) and \
                target != "/dev/vfio/vfio":
            seen.add(target)
    return sorted(seen)


def phase_lane(args) -> int:
    """One of four concurrent one-chip trials, in the environment a
    train worker of a four-slot allocator gets."""
    import jax

    ph = _Phase(f"lane{args.lane}")
    devs = jax.devices()
    want = "cpu" if args.rehearse else "tpu"
    ph.check("exactly_one_device_of_the_platform",
             len(devs) == 1 and devs[0].platform == want)
    ph.facts.update(
        device={"id": devs[0].id, "kind": devs[0].device_kind,
                "coords": list(getattr(devs[0], "coords", []) or []),
                "str": str(devs[0])},
        chip_files=_chip_files(),
        env={k: os.environ.get(k) for k in (
            "JAX_PLATFORMS", "TPU_VISIBLE_CHIPS",
            "TPU_CHIPS_PER_PROCESS_BOUNDS", "TPU_PROCESS_BOUNDS",
            "ALLOW_MULTIPLE_LIBTPU_LOAD")})
    # all four hold their chip before any trains: the trials overlap
    _barrier(args.workdir, "lane-up", args.lane, 4)
    sizes = _sizes(args)
    lane_work = os.path.join(args.workdir, f"lane{args.lane}")
    os.makedirs(lane_work, exist_ok=True)
    ph.facts["train_started"] = time.time()
    _train_lm(ph, sizes, lane_work, args.seed + args.lane)
    ph.facts["train_ended"] = time.time()
    return ph.finish()


def phase_mesh(args) -> int:
    """``model_parallel=2`` over all four chips (fsdp 2 x tp 2) against
    the same program, same seed, on one of them."""
    import random

    import jax

    from rafiki_tpu.model.base import TrainContext
    from rafiki_tpu.model.knob import sample_knobs
    from rafiki_tpu.model.log import ModelLogger
    from rafiki_tpu.models.llama_lora import LlamaLoRA

    ph = _Phase("mesh")
    dev = _device_facts()
    ph.facts["device"] = dev
    want = "cpu" if args.rehearse else "tpu"
    ph.check("four_devices_of_the_platform",
             dev["count"] == 4 and dev["platform"] == want)
    sizes = _sizes(args)
    train_p, _ = _lm_datasets(sizes["lm"], args.workdir, args.seed, "mesh")
    knobs = {**sample_knobs(LlamaLoRA.get_knob_config(),
                            random.Random(args.seed)),
             **sizes["lm"]["knobs"], **LM_PINS, "model_parallel": 2}

    def train(devices):
        model, logger = LlamaLoRA(**knobs), ModelLogger()
        model.train(train_p, TrainContext(logger=logger, devices=devices))
        return model, [float(v) for v in logger.get_values("loss")]

    sharded, mesh_loss = train(list(jax.devices()))
    leaves = jax.tree_util.tree_leaves(sharded._params)
    holders = {d.id for leaf in leaves for d in leaf.devices()}
    split = [leaf for leaf in leaves
             if not leaf.sharding.is_fully_replicated]
    ph.check("parameter_shards_on_four_devices",
             len(holders) == 4 and bool(split),
             {"devices_holding_parameters": sorted(holders),
              "sharded_leaves": len(split), "leaves": len(leaves),
              "example": str(split[0].sharding) if split else None})
    _, one_loss = train([jax.devices()[0]])
    rel = [abs(a - b) / max(abs(b), 1e-9)
           for a, b in zip(mesh_loss, one_loss)]
    ph.facts.update(mesh_epoch_loss=mesh_loss, one_device_epoch_loss=one_loss,
                    loss_rel_diff=rel, loss_tolerance=MESH_LOSS_TOL,
                    knobs={k: knobs[k] for k in sizes["lm"]["knobs"]},
                    mesh="fsdp 2 x tp 2 (model_parallel=2 over 4 devices)")
    ph.check("losses_finite_and_falling",
             all(map(math.isfinite, mesh_loss)) and len(mesh_loss) > 1
             and mesh_loss[-1] < mesh_loss[0])
    ph.check("mesh_loss_matches_one_device",
             len(rel) == len(one_loss) > 0 and max(rel) <= MESH_LOSS_TOL)
    return ph.finish()


CHILD_PHASES = {"device": phase_device, "train": phase_train,
                "serve": phase_serve, "stack": phase_stack,
                "inventory": phase_inventory, "lane": phase_lane,
                "mesh": phase_mesh}


def child_main(args) -> int:
    sys.path.insert(0, HERE)
    args.cache_env_at_start = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    from rafiki_tpu.utils.platform import apply_platform_env

    apply_platform_env()  # the repo's own compile-cache placement
    return CHILD_PHASES[args.phase](args)


# ---------------------------------------------------------------------
# the parent: never imports jax
# ---------------------------------------------------------------------


class _Run:
    def __init__(self, args) -> None:
        self.args = args
        self.t0 = time.monotonic()
        self.failed = []
        self.workdir = tempfile.mkdtemp(prefix="chip_smoke_")
        os.makedirs(LOG_DIR, exist_ok=True)
        self.env = dict(os.environ)
        self.env["PYTHONPATH"] = HERE + (
            os.pathsep + self.env["PYTHONPATH"]
            if self.env.get("PYTHONPATH") else "")
        self.env.setdefault("TPU_LOG_DIR", "disabled")
        # the TPU library's lock is what keeps two processes off one
        # chip: no child of this script runs with it lifted
        self.env.pop("ALLOW_MULTIPLE_LIBTPU_LOAD", None)

    def remaining(self) -> float:
        return DEADLINE_S - (time.monotonic() - self.t0)

    def start(self, phase: str, *extra: str, env=None, tag=None):
        tag = tag or phase
        cmd = [sys.executable, os.path.abspath(__file__), "--phase", phase,
               "--workdir", self.workdir, "--seed", str(self.args.seed),
               "--chips", str(self.args.chips), *extra]
        if self.args.rehearse:
            cmd.append("--rehearse")
        err = open(os.path.join(LOG_DIR, f"{tag}.stderr"), "wb")
        proc = subprocess.Popen(cmd, env=env or self.env, cwd=HERE,
                                stdout=subprocess.PIPE, stderr=err,
                                start_new_session=True)
        err.close()
        return tag, proc

    def finish(self, started, timeout: float):
        """Wait for one child; echo what it printed; return its report
        (``None`` when it left none: crashed, killed or overdue)."""
        tag, proc = started
        try:
            out, _ = proc.communicate(timeout=max(1.0, timeout))
        except subprocess.TimeoutExpired:
            os.killpg(proc.pid, signal.SIGKILL)
            out, _ = proc.communicate()
            print(json.dumps({"phase": tag, "ok": False,
                              "error": f"overdue after {timeout:.0f}s, "
                                       "killed"}), flush=True)
        text = out.decode(errors="replace")
        sys.stdout.write(text)
        sys.stdout.flush()
        report = None
        for line in text.splitlines():
            if line.startswith("{"):
                try:
                    report = json.loads(line)
                except ValueError:
                    continue
        if proc.returncode != 0 or not report or not report.get("ok"):
            self.failed.append(tag)
            with open(os.path.join(LOG_DIR, f"{tag}.stderr"), "rb") as f:
                tail = f.read()[-3000:].decode(errors="replace")
            print(f"--- {tag}: exit code {proc.returncode}; end of its "
                  f"stderr ---\n{tail}", file=sys.stderr, flush=True)
        return report

    def phase(self, phase: str, *extra: str, env=None, tag=None):
        return self.finish(self.start(phase, *extra, env=env, tag=tag),
                           self.remaining())

    def cleanup(self) -> None:
        stack = os.path.join(self.workdir, "stack")
        if os.path.exists(os.path.join(stack, "admin.pid")):
            # a stack phase that was killed could not stop its stack
            subprocess.run([sys.executable, "-m", "rafiki_tpu.cli",
                            "stack", "stop", "--workdir", stack],
                           env=self.env, cwd=HERE, timeout=60,
                           capture_output=True)
        shutil.rmtree(self.workdir, ignore_errors=True)


def run_one_chip(run: _Run):
    device = run.phase("device")
    if "device" in run.failed and not run.args.rehearse:
        return device  # no accelerator: nothing else can mean anything
    for name in ONE_CHIP_PHASES[1:]:
        run.phase(name)
    return device


def run_four_chips(run: _Run):
    """Trials as processes pinned to disjoint chips, then one program
    over all four — and nothing else."""
    if run.args.rehearse:  # four virtual CPU devices stand in
        run.env["XLA_FLAGS"] = (run.env.get("XLA_FLAGS", "") +
                                " --xla_force_host_platform_device_count=4"
                                ).strip()
    inv = run.phase("inventory")
    if "inventory" in run.failed and not run.args.rehearse:
        return None
    envs = (inv or {}).get("slot_envs") or []
    if len(envs) == 4:
        lanes = [run.start("lane", "--lane", str(i),
                           env={**run.env, **envs[i]}, tag=f"lane{i}")
                 for i in range(4)]
        reports = [run.finish(l, run.remaining()) for l in lanes]
        reports = [r for r in reports if r]
        by_file = [tuple(r.get("chip_files") or ()) for r in reports]
        by_id = [(r["device"]["id"], tuple(r["device"]["coords"]))
                 for r in reports]
        spans = [(r.get("train_started"), r.get("train_ended"))
                 for r in reports]
        overlap = len(reports) == 4 and all(
            s and e for s, e in spans) and \
            max(s for s, _ in spans) < min(e for _, e in spans)
        # what tells four chips apart: the device files the processes
        # hold, where the platform shows them; else the ids jax reports
        # (a process held to one chip may number it 0 whichever it is);
        # else ownership — a TPU chip belongs to one process at a time,
        # and all four held one while the others did (the barrier)
        on_tpu = all(r["checks"].get("exactly_one_device_of_the_platform")
                     for r in reports) and not run.args.rehearse
        if all(by_file) and len(set(by_file)) == 4:
            evidence, ident = "device files", by_file
        elif len(set(by_id)) == 4:
            evidence, ident = "device ids", by_id
        else:
            evidence, ident = "exclusive ownership at the barrier", by_id
        checks = {"four_lanes_reported": len(reports) == 4,
                  "four_distinct_chips": len(reports) == 4 and (
                      len(set(ident)) == 4 or on_tpu),
                  "trials_overlapped_in_time": overlap}
        summary = {"phase": "lanes", "ok": all(checks.values()),
                   "checks": checks, "told_apart_by": evidence,
                   "chip_files": [list(f) for f in by_file],
                   "device_ids": [list(map(str, i)) for i in by_id]}
        print(json.dumps(summary), flush=True)
        if not summary["ok"]:
            run.failed.append("lanes")
    else:
        run.failed.append("lanes")
    return run.phase("mesh")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny sizes + interpreted kernels, for the CPU; "
                         "never prints ok")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--phase", choices=sorted(CHILD_PHASES),
                    help=argparse.SUPPRESS)
    ap.add_argument("--workdir", help=argparse.SUPPRESS)
    ap.add_argument("--lane", type=int, default=0, help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.phase:
        return child_main(args)

    if not os.path.isdir(os.path.join(HERE, "rafiki_tpu")):
        print("chip_smoke.py drives the repository it sits in: no "
              "rafiki_tpu/ beside it", file=sys.stderr)
        return 2
    run = _Run(args)
    try:
        first = (run_one_chip if args.chips == 1 else run_four_chips)(run)
    finally:
        run.cleanup()
    device = (first or {}).get("device") or {}
    assert "jax" not in sys.modules, "the parent must stay off jax"
    ok = (not run.failed and not args.rehearse
          and device.get("platform") == "tpu"
          and device.get("count") == args.chips)
    if not ok:
        print(f"chip_smoke: FAILED ({', '.join(run.failed) or 'no chip'})"
              f" after {time.monotonic() - run.t0:.0f}s; logs in {LOG_DIR}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
