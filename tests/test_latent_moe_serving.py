"""Serving the latent-attention / routed-expert decoder: ``DecodeEngine``
over the latent pool against the plain reference, what the engine had to
learn for it (a cache from shapes alone, the serving form of stacked
kernels, device counters inside the output sync, two gauges), and the
benchmark's driver for this kind end to end on a tiny configuration —
sound -> correct; the control and a planted fault -> not correct. The
model's own tests are ``test_latent_moe.py``."""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers import serve_latent_moe as driver
from benchmark.reference import latent_moe as ref
from rafiki_tpu.models.latent_moe import LatentMoEDecoder
from rafiki_tpu.models.llama_lora import serving_llama_params
from rafiki_tpu.ops import moe
from rafiki_tpu.serving import decode_engine
from rafiki_tpu.serving.decode_engine import DecodeEngine

def tiny_cfg(**over):
    cfg = harness.load_json("configs", "tiny-latent-moe.json")
    cfg.update(over)
    return cfg


def weights(cfg, seed=3):
    module = driver.build_module(cfg)
    return module, driver.make_weights(cfg, driver.abstract_params(module),
                                       seed)


def serve(module, params, requests, slots=3, k=4, chunk=8):
    eng = DecodeEngine(module, params, max_slots=slots,
                       max_len=module.max_len, steps_per_sync=k,
                       prefill_chunk=chunk)
    for rid, (prompt, n) in enumerate(requests):
        eng.submit(rid, prompt, n)
    out = {}
    while eng.busy:
        eng.step()
        out.update(dict(eng.poll()))
    return eng, out


def requests(vocab, sizes=((5, 6), (13, 9), (2, 12), (9, 5), (20, 7), (41, 20))):
    rng = np.random.default_rng(7)
    return [(rng.integers(0, vocab, size=p).astype(np.int32), n)
            for p, n in sizes]


# ------------------------------------------- engine against reference
@pytest.mark.parametrize("layout", ["paged_kernel", "paged_gather",
                                    "contiguous"])
def test_engine_served_logits_agree_with_reference_forward(layout):
    cfg = tiny_cfg()
    cfg["engine"]["paged_kernel"] = layout == "paged_kernel"
    module, params = weights(cfg)
    if layout == "contiguous":
        module = module.clone(kv_page_size=0, kv_pages=0)
    reqs = requests(cfg["vocab_size"])
    eng, out = serve(module, params, reqs)
    assert eng.paged_kernel_mode == int(layout == "paged_kernel")
    for rid, (prompt, n) in enumerate(reqs):
        got = ref.served_token_gaps(params, cfg, prompt,
                                    np.asarray(out[rid], np.int32),
                                    pad_to=module.max_len)
        # f32 compute against f32 highest: roundoff, and every served
        # token is the reference's own first choice
        assert got["n"] == n and got["agree"] == n
        assert float(got["gaps"].max()) < 1e-4


# ------------------------------------------------------------- engine
def test_engine_builds_where_init_would_not_fit():
    """The published model whole (36 layers, 128 experts of 25 M
    parameters each: 119 B, 476 GB in float32): the engine gets its cache
    from shapes alone and materialises no weight leaf."""
    module = LatentMoEDecoder(
        vocab_size=131072, max_len=64, hidden_dim=4096, depth=36,
        n_heads=32, q_rank=1024, kv_rank=256, nope_dim=64, rope_dim=64,
        v_dim=128, n_experts=128, experts_per_token=4, expert_dim=2048,
        shared_dim=2048, dtype=jnp.bfloat16, kv_page_size=16, kv_pages=9)
    t0 = time.monotonic()
    eng = DecodeEngine(module, None, max_slots=2, max_len=64)
    assert time.monotonic() - t0 < 60
    leaves = jax.tree_util.tree_leaves(eng._cache)
    # a layer's latents, and its rotary keys two positions a row
    assert sorted(x.shape for x in leaves) == (
        [(9, 8, 128)] * 36 + [(9, 16, 256)] * 36)
    assert all(x.dtype == jnp.bfloat16 and not x.any() for x in leaves)
    assert eng.stats["kv_pool_bytes_per_token"] == 36 * 320 * 2
    assert eng.params is None and eng.stats["weight_bytes"] == 0
    eng.reset()  # the same path again
    assert len(jax.tree_util.tree_leaves(eng._cache)) == 72


def test_serving_form_identity_on_bf16_and_casts_stacked_f32():
    cfg = tiny_cfg()
    cfg["assumed"]["param_dtype"] = "bfloat16"
    module, stored = weights(cfg)
    assert all(x.dtype == jnp.bfloat16
               for x in jax.tree_util.tree_leaves(stored))
    served = serving_llama_params(stored, jnp.bfloat16)
    same = jax.tree_util.tree_map(lambda a, b: a is b, stored, served)
    assert all(jax.tree_util.tree_leaves(same))  # no copy of any leaf
    f32 = jax.tree_util.tree_map(lambda x: x.astype(jnp.float32), stored)
    cast = serving_llama_params(f32, jnp.bfloat16)
    moe_leaves = cast["block_0"]["moe"]
    for name in ("experts_gate", "experts_up", "experts_down", "router"):
        assert moe_leaves[name]["kernel"].dtype == jnp.bfloat16, name
    assert moe_leaves["experts_gate"]["kernel"].ndim == 3
    assert cast["block_0"]["attn"]["wkv_b"]["kernel"].dtype == jnp.bfloat16
    # norms and the embedding stay the caller's own leaves
    assert cast["final_norm"]["scale"] is f32["final_norm"]["scale"]
    assert cast["tok_embed"]["embedding"] is f32["tok_embed"]["embedding"]
    eng = DecodeEngine(module.clone(dtype=jnp.bfloat16), stored,
                       max_slots=2, max_len=module.max_len)
    assert eng.stats["weight_bytes"] == sum(
        x.nbytes for x in jax.tree_util.tree_leaves(stored))


def test_prefill_rows_are_gathered_lanes_over_the_latent_pool(monkeypatch):
    """Six prompts admitted at once with two rows a prefill call: the
    same tokens as one call over all the lanes, and the device counters
    count the rows the programs computed."""
    cfg = tiny_cfg()
    module, params = weights(cfg)
    reqs = requests(cfg["vocab_size"])
    wide, want = serve(module, params, reqs, slots=6)
    monkeypatch.setattr(decode_engine, "PREFILL_LANES", 2)
    eng, out = serve(module, params, reqs, slots=6)
    assert (wide._prefill_lanes, eng._prefill_lanes) == (6, 2)
    assert out == want
    s, w = eng.stats_snapshot(), wide.stats_snapshot()
    assert s["prefill_tokens"] == w["prefill_tokens"]
    assert s["prefill_calls"] > w["prefill_calls"]
    k, layers = cfg["num_experts_per_tok"], 2
    # a step routes 6 rows; a prefill call 2 rows x 8 or x 4 tokens
    assert (s["moe_assignments"] - 6 * k * layers * s["steps"]) % (
        2 * 4 * k * layers) == 0
    assert s["moe_assignments"] < w["moe_assignments"]


def test_a_prompt_spread_over_a_calls_rows_agrees_with_reference():
    """A 41-token prompt is ONE prefill call of 5 rows x 8 tokens over
    the latent pool (kernel step, gathered windows): the served tokens
    are the reference's own, to roundoff."""
    cfg = tiny_cfg()
    module, params = weights(cfg)
    reqs = requests(cfg["vocab_size"], sizes=((41, 12),))
    eng, out = serve(module, params, reqs, slots=6)
    assert eng.stats["prefill_calls"] == 1
    assert eng.stats["prefill_tokens"] == 40
    got = ref.served_token_gaps(params, cfg, reqs[0][0],
                                np.asarray(out[0], np.int32),
                                pad_to=module.max_len)
    assert got["n"] == 12 and got["agree"] == 12
    assert float(got["gaps"].max()) < 1e-4


def test_counters_and_gauges_of_a_served_batch():
    cfg = tiny_cfg()
    module, params = weights(cfg)
    reqs = requests(cfg["vocab_size"])
    eng, out = serve(module, params, reqs, slots=4)
    s = eng.stats_snapshot()
    layers, k, held = 2, cfg["num_experts_per_tok"], 4
    calls = s["steps"] + s["prefill_calls"]
    assert s["moe_expert_slots"] == held * layers * calls
    # every row a program computes is routed: slots x steps, and
    # slots x chunk rows a prefill call (the wide program's 8, the
    # narrow one's 4)
    assert s["moe_assignments"] % (4 * k * layers) == 0
    assert s["moe_assignments"] >= 4 * k * layers * calls
    assert 0 < s["moe_assignments_held"] < s["moe_assignments"]
    assert 0 < s["moe_experts_touched"] <= s["moe_expert_slots"]
    assert 0 < s["moe_step_experts_touched"] <= held * layers * s["steps"]
    assert s["moe_step_assignments_held"] <= s["moe_assignments_held"]
    # pool bytes a position: layers x (latent 16 + rotary key 8) x 4 B
    assert s["kv_pool_bytes_per_token"] == layers * 24 * 4
    assert s["paged_kernel_mode"] == 1
    assert s["paged_kernel_step_tokens"] > 0
    assert s["paged_kernel_window_tokens"] == 0  # windows on the gather
    eng.reset_stats()
    kept = eng.stats_snapshot()
    assert kept["moe_assignments"] == 0
    assert kept["kv_pool_bytes_per_token"] == layers * 24 * 4
    # the dense decoder's gauge still follows its flag, and its pool is
    # K and V apart
    from rafiki_tpu.models.llama_lora import Llama

    dense = Llama(vocab_size=32, max_len=32, hidden_dim=16, depth=2,
                  n_heads=2, n_kv_heads=1, mlp_dim=32, kv_page_size=8,
                  kv_pages=9, paged_kernel=True)
    d_eng = DecodeEngine(dense, None, max_slots=2, max_len=32)
    assert d_eng.paged_kernel_mode == 2
    assert d_eng.stats["kv_pool_bytes_per_token"] == 2 * 2 * 8 * 4


def test_latent_pages_ship_and_park_unchanged():
    """The engine's page bookkeeping treats a cache leaf as ``(pages,
    page, ...)`` whatever follows: a prefill-only shipment installed on
    another engine, and slots parked to a host tier and brought back,
    serve the tokens a plain engine serves."""
    cfg = tiny_cfg()
    cfg["engine"]["paged_kernel"] = False
    module, params = weights(cfg)
    reqs = requests(cfg["vocab_size"])
    _, plain = serve(module, params, reqs)

    prefill = DecodeEngine(module, params, max_slots=3,
                           max_len=module.max_len, prefill_chunk=8)
    decode = DecodeEngine(module, params, max_slots=3,
                          max_len=module.max_len, prefill_chunk=8)
    for rid, (prompt, n) in enumerate(reqs):
        prefill.submit(rid, prompt, n, prefill_only=True)
    blobs = {}
    while prefill.busy:
        prefill.step()
        blobs.update(dict(prefill.poll_kv()))
    assert set(blobs) == set(range(len(reqs)))
    for rid, (prompt, n) in enumerate(reqs):
        decode.submit(rid, prompt, n,
                      kv_import=decode.stage_kv_blob(blobs[rid]))
    shipped = {}
    while decode.busy:
        decode.step()
        shipped.update(dict(decode.poll()))
    assert shipped == plain
    assert decode.stats["kv_imports"] == len(reqs)
    assert decode.stats["prefill_tokens"] == 0  # nothing recomputed

    # a pool too small for every slot at once, a host tier behind it
    small = module.clone(kv_pages=1 + 10)
    tiered = DecodeEngine(small, params, max_slots=3,
                          max_len=module.max_len, prefill_chunk=8,
                          host_kv_pages=24)
    for rid, (prompt, n) in enumerate(reqs):
        tiered.submit(rid, prompt, n)
    parked = {}
    while tiered.busy:
        tiered.step()
        parked.update(dict(tiered.poll()))
    assert parked == plain
    assert tiered.stats["kv_evictions_total"] > 0


def test_kernels_own_copies_serve_the_gathers_tokens_through_page_moves():
    """Leaves the step kernel can slice in HBM (256 latent values a row,
    the 64-wide rotary keys two positions a row) under everything the
    engine does with pages: the kernel's own copies emit the tokens the
    gather emits through admission, release and reuse of pages (six
    requests over three slots), a prefill-only shipment out of one engine
    and into another (``_extract_slot_kv`` / ``_install_kv``), and slots
    parked to a host tier and brought back."""
    from rafiki_tpu.ops.latent_attention import copies_own_pages

    cfg = tiny_cfg(kv_lora_rank=256, qk_rope_head_dim=64, qk_head_dim=72)
    cfg["engine"].update(kv_page_size=16, paged_kernel=False)
    gather, params = weights(cfg)
    kernel = gather.clone(paged_kernel=True)
    reqs = requests(cfg["vocab_size"])
    _, want = serve(gather, params, reqs)

    eng, got = serve(kernel, params, reqs)
    leaves = eng._cache["block_0"]["attn"]
    assert leaves["kv"].shape[1:] == (16, 256)
    assert leaves["k_rope"].shape[1:] == (8, 128)
    assert copies_own_pages(leaves["kv"], leaves["k_rope"])
    assert got == want
    s = eng.stats_snapshot()
    assert s["paged_kernel_mode"] == 1
    assert 0 < s["latent_step_live_pages"] == s["latent_step_page_fetches"]
    # float32 here; the cell's bf16 leaves cost 640 B a layer
    assert s["kv_pool_bytes_per_token"] == 2 * (256 + 64) * 4
    served = DecodeEngine(kernel.clone(dtype=jnp.bfloat16), None,
                          max_slots=2, max_len=kernel.max_len)
    assert served.stats["kv_pool_bytes_per_token"] == 2 * 640

    prefill = DecodeEngine(gather, params, max_slots=3,
                           max_len=gather.max_len, prefill_chunk=8)
    decode = DecodeEngine(kernel, params, max_slots=3,
                          max_len=kernel.max_len, prefill_chunk=8)
    for rid, (prompt, n) in enumerate(reqs):
        prefill.submit(rid, prompt, n, prefill_only=True)
    blobs = {}
    while prefill.busy:
        prefill.step()
        blobs.update(dict(prefill.poll_kv()))
    for rid, (prompt, n) in enumerate(reqs):
        decode.submit(rid, prompt, n,
                      kv_import=decode.stage_kv_blob(blobs[rid]))
    shipped = {}
    while decode.busy:
        decode.step()
        shipped.update(dict(decode.poll()))
    assert shipped == want
    assert decode.stats["kv_imports"] == len(reqs)
    assert decode.stats["prefill_tokens"] == 0  # nothing recomputed

    # a pool too small for every slot at once, a host tier behind it
    tiered = DecodeEngine(kernel.clone(kv_pages=1 + 5), params, max_slots=3,
                          max_len=kernel.max_len, prefill_chunk=8,
                          host_kv_pages=12)
    for rid, (prompt, n) in enumerate(reqs):
        tiered.submit(rid, prompt, n)
    parked = {}
    while tiered.busy:
        tiered.step()
        parked.update(dict(tiered.poll()))
    assert parked == want
    assert tiered.stats["kv_evictions_total"] > 0


@pytest.mark.parametrize("layout", ["paged", "contiguous"])
def test_registered_prefix_installs_over_the_latent_pool(layout):
    """``register_prefix`` on a module that names device counters: the
    snapshot's prefill is handed no counters and returns the cache alone;
    installed over latent pages (or rows) it serves the tokens a plain
    engine serves, and the hit slots skip the prefix's prefill."""
    cfg = tiny_cfg()
    cfg["engine"]["paged_kernel"] = False
    module, params = weights(cfg)
    if layout == "contiguous":
        module = module.clone(kv_page_size=0, kv_pages=0)
    rng = np.random.default_rng(11)
    prefix = rng.integers(0, cfg["vocab_size"], size=11).astype(np.int32)
    reqs = [(np.concatenate([prefix, tail]), n)
            for tail, n in requests(cfg["vocab_size"])[:4]]
    reqs.append(requests(cfg["vocab_size"])[4])  # one that misses
    _, plain = serve(module, params, reqs)

    eng = DecodeEngine(module, params, max_slots=3, max_len=module.max_len,
                       steps_per_sync=4, prefill_chunk=8)
    assert eng.register_prefix(prefix) == len(prefix)
    # the snapshot is a contiguous cache's rows: keys unpacked, and
    # packed two positions a row by the paged install
    snap = jax.tree_util.tree_leaves(eng._prefixes[0]["cache"])
    assert sorted(x.shape for x in snap) == sorted(
        [(1, len(prefix), cfg["qk_rope_head_dim"]),
         (1, len(prefix), cfg["kv_lora_rank"])] * 2)
    for rid, (prompt, n) in enumerate(reqs):
        eng.submit(rid, prompt, n)
    hit = {}
    while eng.busy:
        eng.step()
        hit.update(dict(eng.poll()))
    assert hit == plain
    assert eng.stats["prefix_hits"] == 4
    assert eng.stats["prefix_tokens"] == 4 * len(prefix)
    # the registration's own pass is not a call of the served batch
    whole = sum(len(p) - 1 for p, _ in reqs)
    assert eng.stats["prefill_tokens"] < whole - 3 * len(prefix)


# ------------------------------------------------- the benchmark's driver
class _NoMonitor:
    in_window = 0

    def fence(self): pass
    def unfence(self): pass
    def report(self): return {}


def _ctx(tmp_path, seed, control=None, check_requests=8, kernel=False):
    cfg = tiny_cfg()
    # the Pallas interpreter is slow to compile at every table width:
    # one driver test keeps the step kernel, the others take the gather
    cfg["engine"].update(paged_kernel=kernel,
                         expect_paged_kernel_mode=int(kernel))
    traffic = harness.load_json("traffic", "tiny-chat.json")
    traffic["check_requests"] = check_requests
    traffic["max_new_tokens"]["high"] = 40  # 20 + 40 < max_len 64
    return dict(
        cell={"name": "tiny-latent-moe.tiny-chat",
              "config": "tiny-latent-moe", "traffic": "tiny-chat",
              "chips": 1},
        seed=seed, seconds=1.0, rehearse=True, tracer=None, config=cfg,
        traffic=traffic, phases=harness.Phases(0.0), monitor=_NoMonitor(),
        work_dir=str(tmp_path), peaks=None, control=control)


def _bad(run):
    return {c["name"]: c for c in run["checks"] if not c["ok"]}


def test_driver_sound_run_is_correct(tmp_path):
    run = driver.run(_ctx(tmp_path, 21, kernel=True))
    assert not _bad(run), _bad(run)
    assert run["counters"]["moe_assignments_held"] > 0
    assert set(run["end_to_end"]) == {"setup_s", "serve_tokens_per_s"}
    assert run["window"]["ttft_p95_ms"] > 0


@pytest.mark.parametrize("seed", [31, 32])
def test_driver_control_is_not_correct(seed, tmp_path):
    ctx = _ctx(tmp_path, seed, check_requests=24)
    ctx["control"] = ctx["config"]["control_precision"]
    bad = _bad(driver.run(ctx))
    # not correct by one of the limits, not by each: the 99th percentile
    # of a sample's gaps is 0 where under a hundredth of its tokens flip
    assert set(bad) <= set(driver.COMPARED)
    gap = bad["served_token_logit_gap_mean"]
    assert gap["value"] > 3 * gap["limit"]


def test_driver_half_of_the_experts_a_token_is_not_correct(
        tmp_path, monkeypatch):
    """The fault planted underneath the timed path: the router keeps
    half of a token's experts (and renormalises over them)."""
    real = moe.route_top_k

    def half(logits, top_k, *a, **kw):
        return real(logits, max(1, top_k // 2), *a, **kw)

    monkeypatch.setattr(moe, "route_top_k", half)
    # the engine's programs are cached by module: compile them anew
    decode_engine._make_step.cache_clear()
    decode_engine._make_prefill.cache_clear()
    try:
        bad = _bad(driver.run(_ctx(tmp_path, 21)))
    finally:
        decode_engine._make_step.cache_clear()
        decode_engine._make_prefill.cache_clear()
    assert set(bad) == set(driver.COMPARED)


def test_costs_against_hand_worked_numbers():
    from benchmark import costs_latent_moe as costs

    cfg = harness.load_json("configs", "mistral-small-4-1chip.json")
    assert costs.attention_params(cfg) == (
        4096 * 1024 + 1024 * 4096 + 4096 * 320 + 256 * 6144 + 4096 * 4096)
    assert costs.expert_params(cfg) == 3 * 4096 * 2048
    n = costs.latent_moe_param_count(cfg)
    assert abs(n - 5.37e9) < 0.01e9
    assert abs(costs.latent_moe_weight_bytes(cfg) / 2 ** 30 - 10.0) < 0.05
    assert costs.latent_bytes_per_token(cfg) == 3200
    # a token: attention + shared + router + ONE routed expert (4 x
    # 32/128) a layer, and the head
    per = 28_049_408 + 25_165_824 + 524_288 + 25_165_824
    assert costs.latent_moe_flops_per_token(cfg) == 2.0 * (
        5 * per + 4096 * 131072)
    c = costs.moe_grouped_cost(cfg, experts_touched=28, rows_held=64)
    assert c["flops"] == 64 * 3 * 2 * 4096 * 2048
    assert abs(c["bytes"] - 28 * 3 * 4096 * 2048 * 2) < 0.01 * c["bytes"]
    s = costs.latent_step_cost(cfg, live_tokens=1000)
    assert s["bytes"] == 1000 * 320 * 2
    assert s["flops"] == 1000 * 32 * (320 + 256) * 2
