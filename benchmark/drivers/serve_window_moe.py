"""``kind: serve_window_moe`` — a decoder whose layers mix sliding-window and
full attention, each kind with a rotary table of its own, over routed SwiGLU
experts (``rafiki_tpu.models.hybrid_ssm_moe.HybridSSMMoEDecoder``: a
published layer is two entries of its pattern, ``W`` or ``R`` then ``E``), as
ONE CHIP'S SHARE of a stated deployment, served by the program's
``DecodeEngine`` through ``submit`` / ``step`` / ``poll_partial`` / ``poll``
exactly as the other serving kinds are: the closed loop, the warm-up of
every shape, the sample and the window's helpers are IMPORTED from
``serve_engine``, and the comparison with the reference (the mean gap, its
99th percentile and the share of served tokens off the reference's first
choice: a routed-expert model's numbers) from ``serve_latent_moe``. What is
this file's own is what those hard-code: the module (built here from the
configuration's own keys: the pattern from ``layer_types`` x
``mlp_layer_types``, the two rotary tables from ``rope_parameters``, the
window layers' ring from the engine's own prefill shape) and the weights
(``weights_window_moe``), drawn and KEPT in the configuration's
``param_dtype``; and so the window itself, which calls them (PERF.md section
7 row 0 asks a ``benchmark`` PR for ONE serving driver).

The router keeps ``published.num_experts`` outputs; the module holds the
configuration's ``num_experts`` experts from
``deployment.experts_held_first``.
"""

from __future__ import annotations

import math
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from benchmark import harness, traffic_gen, weights_window_moe
from benchmark.drivers.serve_engine import (
    ClosedLoop, _drain, _percentile, _tpot_ms, _ttft_ms, sample_finished,
    warm_every_shape)
# COMPARED: the numbers a run is held to, re-exported for the tests
from benchmark.drivers.serve_latent_moe import (  # noqa: F401
    COMPARED, _dtype, abstract_params, check_against_reference, compare)

#: the engine's counters a run prints on its ``counters`` line
COUNTERS = (
    "steps", "tokens_generated", "requests_done", "prefill_calls",
    "prefill_tokens", "max_concurrent", "admission_stalls",
    "kv_pages_high_water", "kv_pages_total", "paged_kernel_mode",
    "paged_kernel_step_tokens", "paged_kernel_window_tokens",
    "preemptions", "weight_bytes", "kv_pool_bytes_per_token",
    "window_kv_bytes_per_slot",
    "moe_assignments", "moe_assignments_held", "moe_expert_slots",
    "moe_experts_touched", "moe_step_assignments_held",
    "moe_step_experts_touched", "moe_step_row_tiles",
    "win_step_live_keys", "win_step_keys_fetched", "full_step_live_keys")

#: ``layer_types`` -> the pattern decoder's attention kinds
KINDS = {"sliding_attention": "W", "full_attention": "R"}


def layer_pattern(cfg: Dict[str, Any]) -> str:
    """A published layer is two pattern entries: its attention, then its
    routed experts."""
    kinds, mlps = cfg["layer_types"], cfg["mlp_layer_types"]
    if not len(kinds) == len(mlps) == int(cfg["num_hidden_layers"]):
        raise ValueError("layer_types / mlp_layer_types do not list "
                         f"{cfg['num_hidden_layers']} layers")
    if set(mlps) != {"sparse"} or not set(kinds) <= set(KINDS):
        raise ValueError(f"layers {sorted(set(kinds) | set(mlps))}: only "
                         f"{sorted(KINDS)} over sparse experts are built")
    return "".join(KINDS[k] + "E" for k in kinds)


def rotary(rp: Dict[str, Any]) -> Tuple[float, Any, float]:
    """One kind's ``rope_parameters`` as the decoder takes them: (theta,
    YaRN's four numbers or None, the factor on cos and sin)."""
    if rp.get("rope_type", "default") != "yarn":
        return float(rp["rope_theta"]), None, 1.0
    factor = float(rp["factor"])
    return (float(rp["rope_theta"]),
            (factor, int(rp["original_max_position_embeddings"]),
             float(rp["beta_fast"]), float(rp["beta_slow"])),
            float(rp.get("attention_factor")
                  or 0.1 * math.log(factor) + 1.0))


def build_module(cfg: Dict[str, Any]):
    from rafiki_tpu.models.hybrid_ssm_moe import HybridSSMMoEDecoder
    from rafiki_tpu.ops.window_attention import ring_positions
    from rafiki_tpu.serving.decode_engine import PREFILL_LANES

    eng, assumed = cfg["engine"], cfg.get("assumed") or {}
    dep, rp = cfg.get("deployment") or {}, cfg["rope_parameters"]
    max_len = int(cfg["max_position_embeddings"])
    page, slots = int(eng["kv_page_size"]), int(eng["max_slots"])
    if eng["kv_pages"] != "full_coverage":
        raise ValueError("only kv_pages: full_coverage is built so far")
    compute = _dtype(assumed.get("compute_dtype", "bfloat16"))
    if _dtype(eng.get("kv_dtype", "bfloat16")) != compute:
        raise ValueError("the pool and the rings are kept in the compute "
                         "dtype")
    window = int(cfg["sliding_window"])
    return HybridSSMMoEDecoder(
        vocab_size=int(cfg["vocab_size"]), max_len=max_len,
        hidden_dim=int(cfg["hidden_size"]),
        layer_pattern=layer_pattern(cfg),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        ssm_heads=0, ssm_head_dim=0, ssm_groups=0, ssm_state=0,
        # the router's width is the SOURCE's; the experts held are the
        # configuration's own count, from the deployment's first id
        n_experts=int((cfg.get("published") or cfg)["num_experts"]),
        experts_per_token=int(cfg["num_experts_per_tok"]),
        expert_dim=int(cfg["moe_intermediate_size"]),
        experts_held=(int(dep.get("experts_held_first", 0)),
                      int(cfg["num_experts"])),
        renormalize_gates=bool(cfg.get("norm_topk_prob", True)),
        experts_gated=True, sigmoid_scores=False,
        rope_full=rotary(rp["full_attention"]),
        rope_window=rotary(rp["sliding_attention"]),
        window=window,
        # the window, and what one prefill call may write for a slot: the
        # engine deals a prompt's chunks to up to PREFILL_LANES rows
        kv_ring=ring_positions(
            window, min(slots, PREFILL_LANES) * int(eng["prefill_chunk"]),
            page),
        eps=float(cfg["rms_norm_eps"]),
        dtype=None if compute == _dtype("float32") else compute,
        kv_page_size=page,
        # one scratch page plus every slot able to reach max_len
        kv_pages=1 + slots * (max_len // page),
        paged_kernel=eng.get("paged_kernel"))


def make_weights(cfg: Dict[str, Any], abstract: Any, seed: int) -> Any:
    import jax

    params = weights_window_moe.make_weights(
        abstract, seed, _dtype((cfg.get("assumed") or {}).get(
            "param_dtype", "bfloat16")))
    return jax.block_until_ready(params)


def build_engine(cfg: Dict[str, Any], seed: int, phases: harness.Phases):
    """The engine (its cache — the full layers' paged pool and the window
    layers' per-slot rings — comes from shapes alone), then the weights in
    the dtype they are stored in, then ``core.params``: the engine's
    serving form of leaves already in its compute dtype is those leaves,
    so ``params`` — kept for the reference — and the tree the engine reads
    are one set of buffers."""
    from rafiki_tpu.serving.decode_engine import DecodeEngine

    eng = cfg["engine"]
    module = build_module(cfg)
    core = DecodeEngine(module, None, max_slots=int(eng["max_slots"]),
                        max_len=int(cfg["max_position_embeddings"]),
                        steps_per_sync=int(eng["steps_per_sync"]),
                        prefill_chunk=int(eng["prefill_chunk"]),
                        table_floor=int(eng.get("table_floor_pages", 1)))
    phases.mark("engine_and_kv_pool")
    abstract = abstract_params(module)
    params = make_weights(cfg, abstract, seed)
    core.params = params
    phases.mark("weights_from_seed")
    return module, core, params, abstract


def calibrate(ctx: Dict[str, Any], seeds: List[int], control: str
              ) -> None:
    """As ``serve_engine.calibrate``: one process, for every seed a short
    window at the cell's own load, then the program's numbers and each
    control's on the same requests."""
    cfg, traffic = ctx["config"], ctx["traffic"]
    vocab, clients = int(cfg["vocab_size"]), int(traffic["clients"])
    module, core, params, abstract = build_engine(cfg, seeds[0],
                                                  ctx["phases"])
    warm_every_shape(core, cfg, vocab)
    for seed in seeds:
        if seed != seeds[0]:
            core.params = params = None
            params = make_weights(cfg, abstract, seed)
            core.params = params
        loop = ClosedLoop(core, traffic, vocab, seed, stream=1)
        loop.start()
        while len(loop.finished) < clients:
            loop.turn()
        loop.gen = traffic_gen.closed_loop_lm(traffic, vocab, seed, 0)
        loop.finished = []
        t_close = time.monotonic() + float(ctx["seconds"])
        while time.monotonic() < t_close:
            loop.turn()
        finished = list(loop.finished)
        _drain(core, loop, 120.0)
        sample = sample_finished(finished, traffic, seed)
        prog = compare(cfg, params, sample)
        ctrl = {q: compare(cfg, params, sample, quant=q)
                for q in control.split(",") if q}
        harness.emit("calibrate", seed=seed, finished=len(finished),
                     program=prog, control=ctrl)


def run(ctx: Dict[str, Any]) -> Dict[str, Any]:
    cfg, traffic = ctx["config"], ctx["traffic"]
    phases, monitor = ctx["phases"], ctx["monitor"]
    seed, tracer = ctx["seed"], ctx["tracer"]
    vocab = int(cfg["vocab_size"])

    module, core, params, _abstract = build_engine(cfg, seed, phases)
    want_mode = cfg["engine"].get("expect_paged_kernel_mode")
    mode_ok = want_mode is None or core.paged_kernel_mode == want_mode
    sent = warm_every_shape(core, cfg, vocab)
    phases.mark("warm_every_shape")
    harness.emit("warm", requests=sent, **monitor.report())

    # warm-up: the same generator on another stream, until every client
    # has had one reply — the window then opens on a full, staggered batch
    loop = ClosedLoop(core, traffic, vocab, seed, stream=1)
    loop.start()
    clients = int(traffic["clients"])
    t_limit = time.monotonic() + 600.0
    while len(loop.finished) < clients and time.monotonic() < t_limit:
        loop.turn()
    warm_finished = len(loop.finished)
    phases.mark("warm_traffic")

    # the window: same engine, same loop, the measured stream from here
    loop.gen = traffic_gen.closed_loop_lm(traffic, vocab, seed, stream=0)
    core.reset_stats()
    loop.finished, loop.delivered, loop.failed = [], 0, 0
    in_flight_at_open = set(loop.req)
    submitted_at_open = loop.submitted
    live_sum, turn_at, delivered_at = 0, [], []
    monitor.fence()
    setup_s = phases.since_start()
    t_open = time.monotonic()
    t_close = t_open + float(ctx["seconds"])
    while time.monotonic() < t_close:
        live_sum += loop.live_tokens()
        loop.turn()
        turn_at.append(time.monotonic())
        delivered_at.append(loop.delivered)
    t_end = turn_at[-1]
    turns = len(turn_at)
    turn_ms = 1e3 * np.diff([t_open] + turn_at)
    monitor.unfence()
    window_s = t_end - t_open
    stats = core.stats_snapshot()
    window_finished = list(loop.finished)
    delivered = loop.delivered
    failed = loop.failed
    submitted = loop.submitted - submitted_at_open

    traced = {}
    if tracer is not None:
        # the traced stretch FOLLOWS the window, on the same traffic
        core.reset_stats()
        t_live, t_turns, d0 = 0, 0, loop.delivered
        tracer.start()
        t_stop = time.monotonic() + float(traffic.get("trace_seconds", 3.0))
        while time.monotonic() < t_stop:
            t_live += loop.live_tokens()
            t_turns += 1
            loop.turn()
        tracer.stop()
        traced = {"stats": core.stats_snapshot(), "turns": t_turns,
                  "live_tokens": t_live / max(t_turns, 1),
                  "delivered": loop.delivered - d0,
                  "window_s": tracer.window_s}

    # let what is in flight finish (late is late, not wrong), then free
    _drain(core, loop, 120.0)
    never = len(loop.req)
    memory = harness.memory_peak()
    kernel_mode = core.paged_kernel_mode
    core.span_sink = None
    core.params = None
    loop.core = None
    del core, module  # the engine's cache goes with its last reference

    # ---- end-to-end, over ALL requests of the window ----
    ttft = _ttft_ms(loop.finished, t_open, t_end)
    tpot = _tpot_ms(window_finished, t_end)
    end_to_end = {"setup_s": setup_s,
                  "serve_tokens_per_s": delivered / window_s}
    # a tail the manifest does not list for this cell stays off the
    # result line and goes to the window line (``window_value``)
    listed = {m["name"] for m in harness.cell_metrics(
        harness.load_manifest(), ctx["cell"]["name"], "end_to_end")}
    if ttft and "ttft_p95_ms" in listed:
        end_to_end["ttft_p95_ms"] = harness.percentile(ttft, 95)

    i = int(np.searchsorted(turn_at, t_open + (t_end - t_open) * 2 / 3))
    t_sub = turn_at[i]
    sub = {"seconds": t_sub - t_open,
           "serve_tokens_per_s": delivered_at[i] / (t_sub - t_open),
           "ttft_p95_ms": _percentile(
               _ttft_ms(loop.finished, t_open, t_sub), 95),
           "tpot_p95_ms": _percentile(_tpot_ms(window_finished, t_sub), 95)}

    prompt_tokens = sum(len(r["prompt"]) for r in window_finished)
    checks = check_against_reference(cfg, traffic, params, window_finished,
                                     seed, ctx.get("control"))
    checks += [
        {"name": "paged_kernel_mode", "value": kernel_mode,
         "limit": want_mode, "ok": bool(mode_ok)},
        {"name": "requests_never_answered", "value": never, "limit": 0,
         "ok": never == 0},
        {"name": "requests_failed", "value": failed, "limit": 0,
         "ok": failed == 0},
        {"name": "tokens_delivered_equal_engine_count",
         "value": delivered, "limit": stats["tokens_generated"],
         "ok": delivered == stats["tokens_generated"]},
    ]
    phases.mark("window_and_reference")
    return {
        "attempted": submitted + len(in_flight_at_open),
        "failed": failed + never, "end_to_end": end_to_end,
        "checks": checks, "memory": memory,
        "counters": {k: stats[k] for k in COUNTERS if k in stats},
        "window": {
            "window_s": window_s, "turns": turns,
            "requests_finished": len(window_finished),
            "requests_submitted": submitted,
            "requests_warm_up": warm_finished,
            "tokens_delivered": delivered,
            "prompt_tokens_finished": prompt_tokens,
            "ttft_samples": len(ttft), "tpot_samples": len(tpot),
            "ttft_p50_ms": _percentile(ttft, 50),
            "ttft_p95_ms": _percentile(ttft, 95),
            "tpot_p50_ms": _percentile(tpot, 50),
            "tpot_p95_ms": _percentile(tpot, 95),
            "mean_live_tokens": live_sum / max(turns, 1),
            "turn_ms_p50": float(np.median(turn_ms)),
            "turn_ms_max": float(turn_ms.max()),
            "first_two_thirds": sub,
            "generator_lateness_s": 0.0},
        # what the per-layer readers may look at
        "stats": stats, "max_slots": int(cfg["engine"]["max_slots"]),
        "window_s": window_s, "tokens_out": delivered,
        "tokens_in": stats["prefill_tokens"], "traced": traced,
    }
