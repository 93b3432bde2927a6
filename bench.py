"""Headline benchmark — prints ONE JSON line for the driver.

Metric: ViT-B/16 training throughput (samples/sec) on the available
accelerator. The reference published no numbers (BASELINE.md:
``"published": {}``), so ``vs_baseline`` compares against the last
locally recorded run in ``.bench_history.json`` (ratio >1 = faster),
else 1.0.

Architecture (BENCH r01 was rc=1, r02 rc=124 — both driver-window
failures): a PARENT process that never imports jax owns the deadline;
ALL accelerator work runs in a CHILD that appends a JSON record per
completed stage to a scratch file. A hung backend init or compile can
block Python signal delivery inside a C call, so in-process alarms are
not a defense — the parent's ``subprocess`` timeout is. Whatever the
child managed before the deadline is what gets emitted, always as one
parseable line, always rc=0.

Stages (child, accelerator): backend probe → ViT-B/16 bs=32 step timing
→ varlen Pallas kernel check (interpret=False fwd+bwd — the full-batch
kernels are already proven by the ViT stage itself, which runs Mosaic
flash attention + patch embed) → ViT-B/16 bs=128 → bs=256. Later
stages are skipped when the child's budget runs low (the headroom
floor scales with batch size) and a failing batch (e.g. OOM at 256 on
a smaller core) records an error stage without killing the sweep; the
best completed throughput wins. ``tpu_kernels_ok`` in the emitted line = ViT-on-TPU ran AND the
varlen check passed (VERDICT.md round-2 item #5).

Serving-side metrics (predictor req/s + p50, advisor trials/hour —
SURVEY.md §6) live in ``bench_extra.py``.

Deadline: ``RAFIKI_BENCH_DEADLINE`` seconds (default 280 — r02's driver
window outlived the old probe's 315s budget, so the window is assumed
≥300s; the parent emits and exits rc=0 well before that).
"""

from __future__ import annotations

import json
import os
import sys
import time

from _bench_common import (collect_errors, record as _record,
                           run_with_cpu_fallback)

DEADLINE = float(os.environ.get("RAFIKI_BENCH_DEADLINE", "280"))
METRIC = "vit_b16_train_throughput"


def _child(out_path: str, budget: float) -> None:
    """Run stages, appending a record per completed stage. May hang or
    die at any point — the parent only trusts what reached the file."""
    t_start = time.monotonic()

    def left() -> float:
        return budget - (time.monotonic() - t_start)

    from rafiki_tpu.utils.platform import apply_platform_env

    apply_platform_env()  # parent sets JAX_PLATFORMS=cpu on fallback

    import jax
    import jax.numpy as jnp

    backend = jax.default_backend()
    x = jnp.ones((256, 256), jnp.bfloat16)
    (x @ x).block_until_ready()
    _record(out_path, {"stage": "probe", "backend": backend})

    on_accel = backend not in ("cpu",)

    import optax

    from rafiki_tpu.models.vit import ViT

    if on_accel:
        # bf16 compute (params f32): f32 matmuls lower to multi-pass bf16
        # on the MXU at ~3x the cost — never benchmark the promoted path
        module = ViT(patch_size=16, hidden_dim=768, depth=12, n_heads=12,
                     mlp_dim=3072, n_classes=1000, dtype=jnp.bfloat16)
        # 256 rides only when budget remains (the per-stage gate below):
        # bf16 halved activation memory, so the throughput knee may sit
        # past 128 — the sweep's bs=64 rows already showed bf16+XLA
        # leading, and larger batches amortize dispatch further
        img, batches, metric = 224, (32, 128, 256), METRIC
    else:  # fallback: prove the path end-to-end in seconds. A toy model
        # under its OWN metric name — never comparable to B/16 history.
        module = ViT(patch_size=8, hidden_dim=96, depth=2, n_heads=4,
                     mlp_dim=384, n_classes=10)
        img, batches, metric = 64, (8,), "vit_s64_cpu_train_throughput"

    tx = optax.adam(1e-3)
    params0 = module.init(
        jax.random.PRNGKey(0),
        jnp.zeros((1, img, img, 3), jnp.bfloat16))["params"]

    import functools

    # donate params/opt_state: no copy of the 86M-param trees per step
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, xb, yb):
        def loss_fn(p):
            logits = module.apply({"params": p}, xb)
            return jnp.mean(
                optax.softmax_cross_entropy_with_integer_labels(
                    logits.astype(jnp.float32), yb))

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    def time_batch(bs: int) -> float:
        xb = jnp.zeros((bs, img, img, 3), jnp.bfloat16)
        yb = jnp.zeros((bs,), jnp.int32)
        params = jax.tree_util.tree_map(jnp.copy, params0)
        opt_state = tx.init(params)
        params, opt_state, loss = step(params, opt_state, xb, yb)
        float(loss)  # sync: drains remote-execution backends too
        iters = 20 if on_accel else 3
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt_state, loss = step(params, opt_state, xb, yb)
        float(loss)
        return bs * iters / (time.perf_counter() - t0)

    # stage: bs=32 first — the known-good compile, guarantees a number
    v = time_batch(batches[0])
    _record(out_path, {"stage": f"vit{batches[0]}", "value": v,
                       "batch": batches[0], "metric": metric})

    # stage: varlen Pallas kernels with real Mosaic lowering (TPU only).
    # The ViT stage above already ran the full-batch flash-attention
    # fwd+bwd and the patch-embed kernel on silicon; this covers the
    # scalar-prefetch varlen path.
    if backend == "tpu" and left() > 20:
        try:
            from rafiki_tpu.ops.attention import flash_attention

            q = jax.random.normal(jax.random.PRNGKey(1), (2, 2, 200, 64),
                                  jnp.bfloat16)
            lens = jnp.asarray([200, 77], jnp.int32)

            def loss_fn(q):
                o = flash_attention(q, q, q, kv_lens=lens, causal=True,
                                    interpret=False)
                return jnp.sum(o.astype(jnp.float32) ** 2)

            val, g = jax.jit(jax.value_and_grad(loss_fn))(q)
            ok = bool(jnp.isfinite(val)) and bool(
                jnp.all(jnp.isfinite(g.astype(jnp.float32))))
            _record(out_path, {"stage": "kernels", "tpu_kernels_ok": ok})
        except Exception as e:  # noqa: BLE001 — report, don't die
            _record(out_path, {"stage": "kernels", "tpu_kernels_ok": False,
                               "error": repr(e)[:200]})

    # stage: bigger batches while budget remains (compile ~30-60s each;
    # the headroom floor scales with batch — step time grows ~linearly)
    for bs in batches[1:]:
        if left() < 60 + bs // 8:
            break
        try:
            v = time_batch(bs)
        except Exception as e:  # noqa: BLE001 — e.g. OOM at the
            # largest batch on a smaller core: keep the failure visible
            # and keep sweeping/finishing instead of dying mid-stage
            _record(out_path, {"stage": f"vit{bs}_error",
                               "error": repr(e)[:200]})
            continue
        _record(out_path, {"stage": f"vit{bs}", "value": v, "batch": bs,
                           "metric": metric})

    _record(out_path, {"stage": "done"})


# ---------------------------------------------------------------- parent

def _emit(metric: str, value: float, batch: int, backend: str, kernels_ok,
          stages) -> None:
    hist_path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             ".bench_history.json")
    vs = 1.0
    hist = {}
    try:
        with open(hist_path) as f:
            loaded = json.load(f)
        if isinstance(loaded, dict):
            hist = loaded
        prev = hist.get(metric)
        if isinstance(prev, (int, float)) and prev > 0:
            vs = value / prev
    except (OSError, ValueError):
        pass
    if backend == "tpu" and value > 0:
        hist[metric] = value
        try:
            with open(hist_path, "w") as f:
                json.dump(hist, f)
        except OSError:
            pass
    print(json.dumps({
        "metric": metric, "value": round(value, 2), "unit": "samples/sec",
        "vs_baseline": round(vs, 3), "backend": backend, "batch": batch,
        "tpu_kernels_ok": kernels_ok, "stages": stages,
    }))


def main() -> None:
    t0 = time.monotonic()
    out_path = os.path.abspath(f".bench_stages_{os.getpid()}.jsonl")

    def _no_throughput(records: list) -> bool:
        # rerun on CPU unless the accel child produced an actual number:
        # a hang can strike AFTER the probe (e.g. mid-compile — the r02
        # class), and a probe alone is not a benchmark
        return not any(r.get("stage", "").startswith("vit")
                       and "value" in r for r in records)

    # reserve ~70s upfront for the CPU-fallback child: if the accelerator
    # child hangs it consumes its whole budget and the fallback still has
    # to produce a labeled number before the deadline
    records, fallback_used = run_with_cpu_fallback(
        __file__, out_path, DEADLINE, time.monotonic, t0,
        fallback_reserve=70.0, need_rerun=_no_throughput)

    backend = next((r["backend"] for r in records
                    if r.get("stage") == "probe"), "none")
    kernels_ok = next((r["tpu_kernels_ok"] for r in records
                       if r.get("stage") == "kernels"), None)
    vits = [r for r in records if r.get("stage", "").startswith("vit")
            and "value" in r]
    stages = [r.get("stage") for r in records]
    if vits:
        best = max(vits, key=lambda r: r["value"])
        label = "cpu-fallback" if fallback_used else backend
        _emit(best.get("metric", METRIC), best["value"],
              best.get("batch", 0), label, kernels_ok, stages)
    else:
        print(json.dumps({
            "metric": "bench_error", "value": 0.0, "unit": "samples/sec",
            "vs_baseline": 0.0, "backend": backend,
            "tpu_kernels_ok": kernels_ok, "stages": stages,
            "errors": collect_errors(records),
        }))


if __name__ == "__main__":
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        try:
            _child(sys.argv[2], float(sys.argv[3]))
        except Exception as e:  # noqa: BLE001
            _record(sys.argv[2], {"stage": "child_error",
                                  "error": repr(e)[:300]})
            sys.exit(1)
        sys.exit(0)
    try:
        main()
    except Exception as e:  # noqa: BLE001 — a parseable failure record
        # beats rc!=0 with no metric (the r01 failure class)
        print(json.dumps({"metric": "bench_error", "value": 0.0,
                          "unit": "samples/sec", "vs_baseline": 0.0,
                          "backend": "none", "error": repr(e)[:300]}))
        sys.exit(0)
