"""Name the ViT-B/16 MFU gap (VERDICT r4 item 2): XLA cost analysis +
roofline classification + a jax.profiler trace of the train step.

The r4 sweep measured 21.7% MFU (811 samples/s, bs=64, bf16 + XLA
attention) with no committed analysis of WHERE the other ~78% goes.
This script, run on a TPU:

1. builds the EXACT step every hardware experiment measures
   (``tune_vit_tpu.build_step`` — both the record-holding XLA-attention
   arm and the Pallas arm),
2. AOT-compiles it once (``lower().compile()``) and pulls the
   executable's own ``cost_analysis()`` — XLA's FLOP count and
   bytes-accessed estimate for the REAL optimized HLO. (Pallas-arm
   caveat recorded per row: cost_analysis undercounts custom-call
   FLOPs, so its roofline is a lower bound),
3. computes the roofline bound ``max(flops/PEAK, bytes/HBM_BW)`` per
   step and labels it compute-bound or HBM-bound,
4. times the SAME compiled executable and reports roofline efficiency
   (what's left after the binding resource — scheduling, overheads),
5. captures a ``jax.profiler.trace`` of 5 steps under
   ``.profiles/vit_{attn}_bs{N}/`` for TensorBoard/Perfetto reading.

Appends one JSON row per (attn, bs) to ``.profile_vit_tpu.jsonl`` so a
mid-window outage keeps completed rows (the chain's append-to-file
discipline). Usage: python scripts/profile_vit_tpu.py [bs ...]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import jax
import jax.numpy as jnp

from tune_vit_tpu import PEAK_TFLOPS_BF16, build_step

HBM_GBPS = 819.0  # v5e HBM bandwidth


def profile_step(bs: int, attn: str) -> dict:
    step, params, opt_state, img, lbl, restore = build_step(
        bs, jnp.bfloat16, attn)
    try:
        compiled = step.lower(params, opt_state, img, lbl).compile()
        cost = compiled.cost_analysis() or {}
        flops = float(cost.get("flops", 0.0))
        bytes_acc = float(cost.get("bytes accessed", 0.0))

        # run the SAME executable we analyzed (donated buffers: feed
        # each step's outputs back in)
        params, opt_state, loss = compiled(params, opt_state, img, lbl)
        float(loss)
        iters = 20
        t0 = time.perf_counter()
        for _ in range(iters):
            params, opt_state, loss = compiled(params, opt_state, img,
                                               lbl)
        float(loss)
        step_s = (time.perf_counter() - t0) / iters

        # roofline: the binding resource's minimum time for this step
        t_compute = flops / (PEAK_TFLOPS_BF16 * 1e12)
        t_hbm = bytes_acc / (HBM_GBPS * 1e9)
        bound = "compute" if t_compute >= t_hbm else "hbm"
        roofline_s = max(t_compute, t_hbm)

        trace_dir = os.path.abspath(f".profiles/vit_{attn}_bs{bs}")
        os.makedirs(trace_dir, exist_ok=True)
        with jax.profiler.trace(trace_dir):
            for _ in range(5):
                params, opt_state, loss = compiled(params, opt_state,
                                                   img, lbl)
            float(loss)

        return {
            "bs": bs, "attn": attn,
            "samples_per_s": round(bs / step_s, 1),
            "step_ms": round(step_s * 1e3, 2),
            "xla_flops_per_step": flops,
            "xla_bytes_per_step": bytes_acc,
            "roofline_ms": round(roofline_s * 1e3, 2),
            "t_compute_ms": round(t_compute * 1e3, 2),
            "t_hbm_ms": round(t_hbm * 1e3, 2),
            "bound": bound,
            # fraction of the BINDING resource's peak actually achieved
            # — mfu alone can't distinguish "HBM-bound and efficient"
            # from "compute-bound and stalling"
            "roofline_efficiency_pct": round(
                100 * roofline_s / step_s, 1),
            "mfu_pct": round(
                100 * flops / (step_s * PEAK_TFLOPS_BF16 * 1e12), 1),
            # Pallas custom calls are invisible to cost_analysis: the
            # pallas arm's flops/roofline are LOWER bounds
            "flops_undercounted": attn == "pallas",
            "trace_dir": trace_dir,
        }
    finally:
        restore()


def main() -> None:
    assert jax.default_backend() == "tpu", jax.default_backend()
    batches = [int(a) for a in sys.argv[1:]] or [64, 128, 256]
    for bs in batches:
        for attn in ("xla", "pallas"):
            try:
                row = profile_step(bs, attn)
            except Exception as e:  # noqa: BLE001 — e.g. OOM at 256
                row = {"bs": bs, "attn": attn, "error": repr(e)[:200]}
            with open(".profile_vit_tpu.jsonl", "a") as f:
                f.write(json.dumps(row) + "\n")
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
