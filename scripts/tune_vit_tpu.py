"""Hardware throughput experiments for the ViT-B/16 train step.

Times the full adam train step (donated buffers, like bench.py) on the
real TPU across: compute dtype (f32 promote vs bf16), attention impl
(Pallas flash vs pure-XLA), and batch size. Run manually on a TPU;
feeds the block-size/MFU work (VERDICT r02 weak #3).

Usage: python scripts/tune_vit_tpu.py [bs ...]
"""

from __future__ import annotations

import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import optax

import rafiki_tpu.models.vit as vit_mod
from rafiki_tpu.ops.attention import _attention_reference

# ViT-B/16 train-step FLOPs/sample ≈ 3x fwd; fwd ≈ 17.6 GF @ 224
STEP_GFLOP_PER_SAMPLE = 52.8
PEAK_TFLOPS_BF16 = 197.0  # v5e


def build_step(bs: int, dtype, attn: str, remat: bool = False):
    """The ONE ViT-B/16 donated-buffer adam train step every hardware
    experiment measures (this sweep AND scripts/profile_vit_tpu.py —
    a profiled step that silently differs from the benchmarked one
    misdirects the MFU work). ``attn='xla'`` swaps the module's
    attention to the pure-XLA reference — the config that holds the
    r4 throughput record. Returns ``(step, params, opt_state, img,
    lbl, restore)``; call ``restore()`` when done (monkeypatch)."""
    restore = lambda: None  # noqa: E731
    if attn == "xla":
        orig = vit_mod.flash_attention
        vit_mod.flash_attention = (
            lambda q, k, v, *a, **kw: _attention_reference(
                q, k, v, 1.0 / (q.shape[-1] ** 0.5), False))

        def restore():
            vit_mod.flash_attention = orig

    module = vit_mod.ViT(patch_size=16, hidden_dim=768, depth=12,
                         n_heads=12, mlp_dim=3072, n_classes=1000,
                         dtype=dtype, remat=remat)
    tx = optax.adam(1e-3)
    img = jnp.zeros((bs, 224, 224, 3), jnp.bfloat16)
    lbl = jnp.zeros((bs,), jnp.int32)
    params = module.init(jax.random.PRNGKey(0), img[:1])["params"]
    opt_state = tx.init(params)

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

    return step, params, opt_state, img, lbl, restore


def time_step(bs: int, dtype, attn: str, iters: int = 20,
              remat: bool = False) -> dict:
    step, params, opt_state, img, lbl, restore = build_step(
        bs, dtype, attn, remat)
    try:
        t_c0 = time.perf_counter()
        params, opt_state, loss = step(params, opt_state, img, lbl)
        float(loss)
        compile_s = time.perf_counter() - t_c0
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt_state, loss = step(params, opt_state, img, lbl)
        float(loss)
        dt = time.perf_counter() - t0
        sps = bs * iters / dt
        mfu = sps * STEP_GFLOP_PER_SAMPLE / 1e3 / PEAK_TFLOPS_BF16
        return {"bs": bs, "dtype": str(dtype), "attn": attn,
                "remat": remat,
                "samples_per_s": round(sps, 1), "mfu_pct": round(100 * mfu, 1),
                "compile_s": round(compile_s, 1)}
    finally:
        restore()


def main() -> None:
    assert jax.default_backend() == "tpu", jax.default_backend()
    sizes = [int(a) for a in sys.argv[1:]] or [64]
    out = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".tune_vit_tpu.jsonl")
    configs = [(jnp.bfloat16, "xla", False), (jnp.bfloat16, "pallas", False)]
    if not os.environ.get("RAFIKI_TUNE_BF16_ONLY"):
        # the f32 Pallas config is the slowest to compile and decides
        # no headline number: RAFIKI_TUNE_BF16_ONLY skips it
        configs.append((None, "pallas", False))
    for bs in sizes:
        cfgs = list(configs)
        if bs == max(sizes):
            # remat at the biggest batch: where activation HBM binds,
            # rematerialization may net out faster via utilization
            cfgs.append((jnp.bfloat16, "xla", True))
        for dtype, attn, remat in cfgs:
            r = time_step(bs, dtype, attn, remat=remat)
            line = json.dumps(r)
            print(line, flush=True)
            with open(out, "a") as f:  # survive parent timeouts
                f.write(line + "\n")
                f.flush()
                os.fsync(f.fileno())


if __name__ == "__main__":
    main()
