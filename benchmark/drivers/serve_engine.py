"""``kind: serve_engine`` — a decoder served by the program's
``DecodeEngine`` (continuous batching over a paged KV pool), driven as the
inference worker drives it: ``submit`` / ``step`` / ``poll_partial`` /
``poll`` from one thread, with ``span_sink`` wired for the request stamps.

What the benchmark builds itself, because the template cannot
(``LlamaLoRA._module`` fixes ``mlp_dim = 4 * hidden``): the flax module
``Llama(...)`` with every published width as a field, handed to
``DecodeEngine`` exactly as ``LlamaLoRA._build_text_engine`` hands its own.
The engine, its cache manager and its kernels are the program's; template,
tokenizer, ``InferenceWorker`` and hub are bypassed (PERF.md, owed).
"""

from __future__ import annotations

import time
from typing import Any, Dict, List

import numpy as np

from benchmark import harness, traffic_gen, weights


def build_module(cfg: Dict[str, Any]):
    import jax.numpy as jnp

    from rafiki_tpu.models.llama_lora import Llama

    eng = cfg["engine"]
    assumed = cfg.get("assumed") or {}
    max_len = int(cfg["max_position_embeddings"])
    page = int(eng["kv_page_size"])
    if eng["kv_pages"] != "full_coverage":
        raise ValueError("only kv_pages: full_coverage is built so far")
    # one scratch page plus every slot able to reach max_len: what
    # make_decode_engine takes when the operator names no pool size
    n_pages = 1 + int(eng["max_slots"]) * (max_len // page)
    compute = {"bfloat16": jnp.bfloat16, "float32": None}[
        assumed.get("compute_dtype", "bfloat16")]
    return Llama(
        vocab_size=int(cfg["vocab_size"]), max_len=max_len,
        hidden_dim=int(cfg["hidden_size"]),
        depth=int(cfg["num_hidden_layers"]),
        n_heads=int(cfg["num_attention_heads"]),
        n_kv_heads=int(cfg["num_key_value_heads"]),
        mlp_dim=int(cfg["intermediate_size"]),
        lora_rank=int(assumed.get("lora_rank", 0)), dtype=compute,
        rope_theta=float(cfg.get("rope_theta", 10000.0)),
        kv_int8=eng.get("kv_dtype") == "int8",
        kv_page_size=page, kv_pages=n_pages,
        paged_kernel=eng.get("paged_kernel"))


def build_engine(cfg: Dict[str, Any], seed: int, phases: harness.Phases):
    """The engine FIRST, with no weights: ``DecodeEngine.__init__`` draws
    and drops a whole second set of them for its cache's shapes
    (``module.init`` un-jitted; PERF.md, owed), and at these widths two
    sets do not fit the chip. Then the weights, then ``core.params`` —
    which the engine reads only when it dispatches."""
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.serving.decode_engine import DecodeEngine

    eng = cfg["engine"]
    module = build_module(cfg)
    core = DecodeEngine(module, None, max_slots=int(eng["max_slots"]),
                        max_len=int(cfg["max_position_embeddings"]),
                        steps_per_sync=int(eng["steps_per_sync"]),
                        prefill_chunk=int(eng["prefill_chunk"]))
    phases.mark("engine_and_kv_pool")
    abstract = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    params = weights.make_weights(abstract, seed)
    jax.block_until_ready(params)
    core.params = params
    phases.mark("weights_from_seed")
    return module, core, params, abstract


def warm_every_shape(core, cfg: Dict[str, Any], vocab: int) -> int:
    """Meet every program the engine can be handed, through ``submit`` /
    ``step`` / ``poll`` alone. The engine compiles three programs (the
    fused decode step, the ``prefill_chunk``-wide prefill and a narrow
    prefill for short remainders) at every power-of-two width of the page
    table up to ``max_len / page``, and picks the width from the longest
    sequence alive. So, width by width: a lone HOLDER whose sequence ends
    in the width's last page decodes one dispatch there; then a PROBE is
    admitted beside it and is prefilled at the holder's width — once with
    a prompt of a page (the wide program), once with three tokens (the
    narrow one). The window can then meet no shape that was not loaded,
    whatever the traffic's timing; the count of compilations inside the
    window stays the guard. Returns the number of requests sent."""
    eng = cfg["engine"]
    page, k = int(eng["kv_page_size"]), int(eng["steps_per_sync"])
    n_table = int(cfg["max_position_embeddings"]) // page
    probes = (min(int(eng["prefill_chunk"]), page), 3)
    widths, w = [], 1
    while w < n_table:
        widths.append(w)
        w *= 2
    widths.append(n_table)
    rng, sent = np.random.default_rng(0), 0

    def send(tokens: int, max_new: int) -> None:
        nonlocal sent
        core.submit(f"warm-{sent}", rng.integers(
            0, vocab, size=tokens).astype(np.int32), max_new)
        sent += 1

    for w in widths:
        for probe in probes:
            # last position written: page * w - 2, in the width's last page
            send(max(2, page * w - k - 1), k + 1)
            core.step()
            send(probe, 1)
            while core.busy:
                core.step()
            core.poll()
    return sent


class ClosedLoop:
    """``clients`` callers, each with one request in flight: the next one
    goes in the moment the last one's reply is polled. Stamps come from
    the engine's own ``span_sink`` events on the host's clock."""

    def __init__(self, core, traffic: Dict[str, Any], vocab: int,
                 seed: int, stream: int) -> None:
        self.core = core
        self.traffic = traffic
        self.gen = traffic_gen.closed_loop_lm(traffic, vocab, seed, stream)
        self.req: Dict[str, Dict[str, Any]] = {}
        self.finished: List[Dict[str, Any]] = []
        self.delivered = 0      # output tokens seen through the polls
        self.submitted = 0
        self.failed = 0
        core.span_sink = self._on_span

    def _on_span(self, event: str, rid: Any, attrs: Dict[str, Any]) -> None:
        r = self.req.get(rid)
        if r is not None and event in ("admitted", "first_token", "done") \
                and event not in r:
            r[event] = time.monotonic()

    def submit_one(self) -> None:
        r = next(self.gen)
        r.update(submit=time.monotonic(), seen=0)
        self.req[r["id"]] = r
        self.core.submit(r["id"], r["prompt"], r["max_new"],
                         slo=self.traffic.get("slo_class", ""))
        self.submitted += 1

    def start(self) -> None:
        for _ in range(int(self.traffic["clients"])):
            self.submit_one()

    def turn(self, resubmit: bool = True) -> int:
        """One turn of the worker's loop. Returns requests finished."""
        core = self.core
        core.step()
        for rid, toks in core.poll_partial():
            r = self.req.get(rid)
            if r is not None:
                self.delivered += len(toks) - r["seen"]
                r["seen"] = len(toks)
        done = core.poll()
        for rid, toks in done:
            r = self.req.pop(rid, None)
            if r is None:
                continue
            self.delivered += len(toks) - r["seen"]
            r["tokens"] = list(toks)
            if len(toks) != r["max_new"] or "first_token" not in r:
                self.failed += 1  # no EOS in this traffic: a short or
                #                   unstamped reply is a failed request
            self.finished.append(r)
            if resubmit:
                self.submit_one()
        return len(done)

    def live_tokens(self) -> int:
        """Cached positions over all requests in flight (prompt plus
        generated so far): what the step kernel has to read."""
        return sum(len(r["prompt"]) + r["seen"] for r in self.req.values())


def _ttft_ms(reqs, since: float, until: float) -> List[float]:
    """Submit -> first token of every request submitted in [since, until),
    whenever its first token came."""
    return [1e3 * (r["first_token"] - r["submit"]) for r in reqs
            if since <= r["submit"] < until and "first_token" in r]


def _tpot_ms(reqs, until: float) -> List[float]:
    """(done - first token) / (tokens - 1) of every request done by
    ``until``: the gap a reader feels."""
    return [1e3 * (r["done"] - r["first_token"]) / (len(r["tokens"]) - 1)
            for r in reqs if "done" in r and "first_token" in r
            and len(r["tokens"]) > 1 and r["done"] <= until]


def _percentile(values: List[float], q: float):
    return harness.percentile(values, q) if values else None


def _drain(core, loop: ClosedLoop, limit_s: float) -> None:
    t_end = time.monotonic() + limit_s
    while core.busy and time.monotonic() < t_end:
        loop.turn(resubmit=False)


def sample_finished(finished, traffic, seed: int) -> List[Dict[str, Any]]:
    """The requests to compare: the longest the window finished, and
    ``check_requests - 1`` more drawn from the seed."""
    n = min(int(traffic.get("check_requests", 6)), len(finished))
    if n == 0:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i]["prompt"])
                                   + len(finished[i]["tokens"])))
    rng = np.random.default_rng(np.random.SeedSequence(
        [int(seed) & 0xFFFFFFFFFFFF, 99]))
    rest = [int(i) for i in rng.permutation(order[1:])[:n - 1]]
    return [finished[i] for i in [order[0]] + rest]


def widest_gap(cfg, params, sample, quant=None) -> Dict[str, Any]:
    """Teacher-force each sampled request through the plain reference: the
    widest gap by which a served token's logit (with ``quant``: the logit
    of the token the control precision puts first) lies below the
    reference's best."""
    ref = harness.load_reference(cfg)
    worst, tokens, agree, stds = 0.0, 0, 0, []
    for r in sample:
        out = ref.served_token_gaps(
            params, cfg, r["prompt"], np.asarray(r["tokens"], np.int32),
            pad_to=int(cfg["max_position_embeddings"]), quant=quant)
        worst = max(worst, float(out["gaps"].max()))
        tokens += out["n"]
        agree += out["agree"]
        stds.append(out["logit_std"])
    return {"requests": len(sample), "served_tokens": tokens,
            "tokens_equal_to_reference_argmax": agree,
            "widest_logit_gap": worst,
            "reference_logit_std": float(np.mean(stds)) if stds else None}


def check_against_reference(cfg, traffic, params, finished, seed: int,
                            limit: float, control=None
                            ) -> List[Dict[str, Any]]:
    """``control`` (a precision) puts the control in the program's place:
    the gap read is that of the token the reference computed in that
    precision puts first, at every position of the same requests."""
    sample = sample_finished(finished, traffic, seed)
    if not sample:
        return [{"name": "served_requests_to_compare", "value": 0,
                 "limit": ">=1", "ok": False}]
    out = widest_gap(cfg, params, sample, quant=control)
    harness.emit("reference", control=control, **out)
    worst, tokens = out["widest_logit_gap"], out["served_tokens"]
    return [{"name": "served_token_logit_gap", "value": worst,
             "limit": limit, "ok": bool(worst <= limit)},
            {"name": "served_tokens_compared", "value": tokens,
             "limit": ">=1", "ok": tokens >= 1}]


def calibrate(ctx: Dict[str, Any], seeds: List[int], control: str
              ) -> None:
    """The readings a limit is set from, in ONE process (set-up is long):
    for every seed a short window at the cell's own load, then the
    program's widest gap and the control's on the same requests."""
    cfg, traffic = ctx["config"], ctx["traffic"]
    vocab, clients = int(cfg["vocab_size"]), int(traffic["clients"])
    module, core, params, abstract = build_engine(cfg, seeds[0],
                                                  ctx["phases"])
    warm_every_shape(core, cfg, vocab)
    for seed in seeds:
        if seed != seeds[0]:
            core.params = params = None
            params = weights.make_weights(abstract, seed)
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
        _drain(core, loop, 60.0)
        sample = sample_finished(finished, traffic, seed)
        prog = widest_gap(cfg, params, sample)
        ctrl = {q: widest_gap(cfg, params, sample, quant=q)
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
    mode_ok = want_mode is None or core.paged_kernel_mode == want_mode \
        or ctx["rehearse"]
    sent = warm_every_shape(core, cfg, vocab)
    phases.mark("warm_every_shape")
    harness.emit("warm", requests=sent, **monitor.report())

    # warm-up: the same generator on another stream, until every client
    # has had one reply — the window then opens on a full, staggered batch
    loop = ClosedLoop(core, traffic, vocab, seed, stream=1)
    loop.start()
    clients = int(traffic["clients"])
    t_limit = time.monotonic() + 240.0
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
        # the traced stretch FOLLOWS the window, on the same traffic: the
        # profiler's start and stop cost no measured time
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
    _drain(core, loop, 60.0)
    never = len(loop.req)
    memory = harness.memory_peak()
    kernel_mode = core.paged_kernel_mode
    core.span_sink = None
    core.params = None
    loop.core = None
    del core, module  # the engine's cache goes with its last reference

    # ---- end-to-end, over ALL requests of the window ----
    # every request SUBMITTED in the window counts, its first token's
    # stamp taken from the drain where it came after the close
    ttft = _ttft_ms(loop.finished, t_open, t_end)
    tpot = _tpot_ms(window_finished, t_end)
    end_to_end = {"setup_s": setup_s,
                  "serve_tokens_per_s": delivered / window_s}
    if ttft:
        end_to_end["ttft_p95_ms"] = harness.percentile(ttft, 95)

    # the same numbers over the window's first two thirds alone, so that
    # every run also says what a shorter window would have read
    i = int(np.searchsorted(turn_at, t_open + (t_end - t_open) * 2 / 3))
    t_sub = turn_at[i]
    sub = {"seconds": t_sub - t_open,
           "serve_tokens_per_s": delivered_at[i] / (t_sub - t_open),
           "ttft_p95_ms": _percentile(
               _ttft_ms(loop.finished, t_open, t_sub), 95),
           "tpot_p95_ms": _percentile(_tpot_ms(window_finished, t_sub), 95)}

    prompt_tokens = sum(len(r["prompt"]) for r in window_finished)
    limits = cfg["limits"]
    checks = check_against_reference(
        cfg, traffic, params, window_finished, seed,
        float(limits["served_token_logit_gap"]), ctx.get("control"))
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
        "counters": {k: stats[k] for k in (
            "steps", "tokens_generated", "requests_done", "prefill_calls",
            "prefill_tokens", "max_concurrent", "admission_stalls",
            "kv_pages_high_water", "kv_pages_total", "paged_kernel_mode",
            "paged_kernel_step_tokens", "paged_kernel_window_tokens",
            "preemptions")},
        "window": {
            "window_s": window_s, "turns": turns,
            "requests_finished": len(window_finished),
            "requests_submitted": submitted,
            "requests_warm_up": warm_finished,
            "tokens_delivered": delivered,
            "prompt_tokens_finished": prompt_tokens,
            "ttft_samples": len(ttft), "tpot_samples": len(tpot),
            "ttft_p50_ms": _percentile(ttft, 50),
            "tpot_p50_ms": _percentile(tpot, 50),
            "tpot_p95_ms": _percentile(tpot, 95),
            "mean_live_tokens": live_sum / max(turns, 1),
            # a stall of the host or the chip shows as one long turn
            "turn_ms_p50": float(np.median(turn_ms)),
            "turn_ms_max": float(turn_ms.max()),
            "first_two_thirds": sub,
            "generator_lateness_s": 0.0},
        # what the per-layer readers may look at
        "stats": stats, "max_slots": int(cfg["engine"]["max_slots"]),
        "window_s": window_s, "tokens_out": delivered,
        "tokens_in": stats["prefill_tokens"], "traced": traced,
    }
