"""Serving the pattern decoder's window layers: ``DecodeEngine`` with a
RING of keys a slot beside the full layers' paged pool, against the plain
reference — prompts and outputs past the window and past the ring; one
prompt's chunks dealt to several rows of a call across the window's edge; a
slot taken by a short request after a long one; lanes that are empty or
froze mid-scan; the gauges and the counters; what the engine asks of the
ring at construction; and the benchmark's driver for this kind end to end
on a tiny configuration — sound -> correct, the control -> not correct.
The model's own tests are ``test_window_moe.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers import serve_window_moe as driver
from benchmark.reference import window_moe as ref
from rafiki_tpu.serving.decode_engine import DecodeEngine

CHUNK = 8


def tiny_cfg(periods=1, kernels=False):
    """The tiny configuration at ``periods`` of its [sliding x 3, full]
    (the file has 2), on the masked ``jax.numpy`` forms unless
    ``kernels`` (the Pallas interpreter: slow, one test keeps it)."""
    cfg = harness.load_json("configs", "tiny-window-moe.json")
    n = 4 * periods
    cfg.update(num_hidden_layers=n, layer_types=cfg["layer_types"][:n],
               mlp_layer_types=cfg["mlp_layer_types"][:n],
               sliding_layers=3 * periods, full_layers=periods)
    cfg["engine"]["paged_kernel"] = kernels
    return cfg


def weights(cfg, seed=3):
    module = driver.build_module(cfg)
    return module, driver.make_weights(cfg, driver.abstract_params(module),
                                       seed)


def engine(module, params, slots=4, k=4, table_floor=32, **kw):
    """One table width (the whole table: 128 / 4 pages) unless a test
    asks: every width is a compilation of each program."""
    return DecodeEngine(module, params, max_slots=slots,
                        max_len=module.max_len, steps_per_sync=k,
                        prefill_chunk=CHUNK, table_floor=table_floor, **kw)


def drain(eng, out=None):
    out = {} if out is None else out
    while eng.busy:
        eng.step()
        out.update(dict(eng.poll()))
    return out


def prompts(vocab, sizes, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=p).astype(np.int32) for p in sizes]


def agrees(params, cfg, prompt, served, n):
    got = ref.served_token_gaps(params, cfg, prompt,
                                np.asarray(served, np.int32), pad_to=128)
    # f32 compute against f32 highest: roundoff, and every served token
    # is the reference's own first choice
    assert got["n"] == n and got["agree"] == n
    assert float(got["gaps"].max()) < 1e-4


def rings(eng):
    """The cache's rings, by path."""
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf in
            jax.tree_util.tree_flatten_with_path(eng._cache)[0]
            if path[-1].key in ("ring_k", "ring_v")}


# ------------------------------------------- engine against reference
@pytest.mark.parametrize("kernels", [False, True],
                         ids=["masked_ring", "pallas_interpreter"])
def test_engine_served_logits_agree_with_reference_forward(kernels):
    """Chunked prefill, then decode through the cache, against the
    reference's full forward at every served position. Window 8, ring 44
    (window + 4 rows x 8 tokens + a page of 4): prompts inside the
    window (5), a chunk (9), several chunks past the window (30, 41 —
    dealt to the rows of ONE call with the others, so a row's first
    queries read keys a row before it wrote, across the window's edge)
    and past the ring (70: position 44 lands on position 0's entry)."""
    cfg = tiny_cfg(periods=1 if kernels else 2, kernels=kernels)
    layers = cfg["sliding_layers"], cfg["full_layers"]
    module, params = weights(cfg)
    assert module.kv_ring == 44
    reqs = prompts(cfg["vocab_size"], (5, 9, 30, 41, 3, 17, 70))
    eng = engine(module, params)
    assert eng.paged_kernel_mode == 2 * int(kernels)
    for rid, p in enumerate(reqs):
        eng.submit(rid, p, 12)
    out = drain(eng)
    for rid, p in enumerate(reqs):
        agrees(params, cfg, p, out[rid], 12)
    s = eng.stats
    assert s["prefill_tokens"] == sum(len(p) - 1 for p in reqs)
    # a single-token step for each generated token, in each layer: the
    # sliding layers of at most 8 live keys, the full layers of pos + 1
    steps = s["tokens_generated"]
    assert 0 < s["win_step_live_keys"] <= layers[0] * 8 * steps
    assert s["full_step_live_keys"] == layers[1] * sum(
        sum(range(len(p), len(p) + 12)) for p in reqs)
    if kernels:  # whole pages of 4 around a window of 8: 8 or 12 keys
        assert s["win_step_live_keys"] <= s["win_step_keys_fetched"] \
            <= 1.5 * s["win_step_live_keys"]
    else:  # the masked form is handed the ring
        assert s["win_step_keys_fetched"] == layers[0] * 44 * steps
    # the prefill calls alone, 4 rows each: of the chunk's 8 tokens or
    # the narrow program's 4, a row one query tile. A full layer walks
    # the table's 32 pages a row, a sliding layer the pages that a
    # window of 8 and a tile of 8 (4) tokens can span: 5 (4)
    assert eng.module.device_counters[-2:] == (
        "attn_prefill_grid_steps", "attn_prefill_tokens")
    def a_call(pages_walked_by_a_sliding_layer):
        return int(kernels) * 4 * (
            layers[1] * 32 + layers[0] * pages_walked_by_a_sliding_layer)

    calls = s["prefill_calls"]
    assert s["attn_prefill_tokens"] == 32 * calls
    assert s["attn_prefill_grid_steps"] == calls * a_call(5)
    eng.submit(99, reqs[0][:4], 2)  # 3 tokens to ingest: the narrow one
    drain(eng)
    assert eng.stats["prefill_calls"] == calls + 1
    assert eng.stats["attn_prefill_tokens"] == 32 * calls + 16
    assert eng.stats["attn_prefill_grid_steps"] == calls * a_call(5) \
        + a_call(4)


def test_one_long_prompt_is_one_call_whose_rows_see_each_other():
    """8 slots: 8 rows a call, so a prompt of 65 tokens is ONE prefill
    call of 8 consecutive chunks. Row r's queries see the last 8 keys,
    most of them written by row r - 1 in this very call; the ring (76)
    holds the window and the call's 64 tokens, so row 7 overwrites
    nothing row 0 reads."""
    cfg = tiny_cfg()
    cfg["engine"]["max_slots"] = 8
    module, params = weights(cfg)
    assert module.kv_ring == 76
    (p,) = prompts(cfg["vocab_size"], (8 * CHUNK + 1,))
    eng = engine(module, params, slots=8)
    eng.submit(0, p, 10)
    out = drain(eng)
    assert eng.stats["prefill_calls"] == 1
    assert eng.stats["prefill_tokens"] == 8 * CHUNK
    agrees(params, cfg, p, out[0], 10)


def test_a_ring_of_the_window_alone_would_lose_keys_and_the_engine_asks():
    """The engine asks the module at construction whether its ring takes
    the tokens one call may write for a slot: a ring of the window and a
    page is refused by name."""
    cfg = tiny_cfg()
    module, params = weights(cfg)
    with pytest.raises(ValueError, match="kv_ring"):
        engine(module.clone(kv_ring=12), params)
    engine(module.clone(kv_ring=40), params)  # window + 4 x 8: just


# ------------------------------------------------- slots change hands
def test_a_short_request_after_a_long_one_in_the_same_slot_is_exact():
    """One slot: a request of 70 + 20 positions wraps the ring twice,
    then 6 + 5 takes the slot. Nothing is zeroed between them: what the
    first left lies at positions the second has not reached."""
    cfg = tiny_cfg()
    module, params = weights(cfg)
    a, b = prompts(cfg["vocab_size"], (70, 6))
    eng = engine(module, params, slots=1)
    eng.submit("a", a, 20)
    eng.submit("b", b, 5)
    out = drain(eng)
    agrees(params, cfg, a, out["a"], 20)
    agrees(params, cfg, b, out["b"], 5)
    # and a preempted request resumes from position 0, token for token
    c, d = prompts(cfg["vocab_size"], (33, 11), seed=8)
    alone = engine(module, params, slots=1)
    alone.submit("c", c, 12)
    fresh = drain(alone)
    eng = engine(module, params, slots=1)
    eng.submit("c", c, 12, slo="background")
    eng.step()
    eng.step()
    eng.submit("d", d, 6, slo="interactive")
    out = drain(eng)
    assert eng.stats["preemptions"] >= 1
    assert out["c"] == fresh["c"]


def test_empty_and_frozen_lanes_step_without_touching_a_ring():
    """One request decodes in a 4-slot engine while three lanes are
    empty, and finishes mid-scan (30 = 7 x 4 + 2: its lane re-feeds its
    last token for the rest of the fused call). No empty lane's ring
    moves — what is not real writes to the scratch row — and the
    finished lane holds no key above its last real position."""
    cfg = tiny_cfg()
    module, params = weights(cfg)
    (p,) = prompts(cfg["vocab_size"], (6,))
    eng = engine(module, params)
    zero = rings(eng)
    eng.submit("a", p, 30)
    out = drain(eng)
    agrees(params, cfg, p, out["a"], 30)
    for path, leaf in rings(eng).items():
        assert np.array_equal(leaf[1:4], zero[path][1:4]), path
        # positions 0-34 were fed (the 30th output never is): 35 entries
        assert np.count_nonzero(np.abs(leaf[0]).sum((-1, -2))) == 35, path


def test_padding_and_spare_rows_write_to_the_scratch_row():
    """Straight at the module, as the engine's prefill program calls it:
    row 0 is slot 2 with 5 real tokens of 8 (3 padded), row 1 slot 1
    with nothing real, rows 2-7 spare. Slot 2's ring takes 5 entries;
    every other slot's ring is bit-equal to what it was."""
    cfg = tiny_cfg()
    module, params = weights(cfg)
    eng = engine(module, params)
    rng = np.random.default_rng(0)
    before = jax.tree_util.tree_map(
        lambda c: jnp.asarray(rng.normal(size=c.shape), c.dtype), eng._cache)
    tok = np.zeros((8, CHUNK), np.int32)
    pos = np.zeros((8, CHUNK), np.int32)
    tok[0, :5] = rng.integers(0, cfg["vocab_size"], 5)
    tok[0, 5:] = tok[0, 4]
    pos[0] = np.minimum(np.arange(CHUNK), 4) + 16
    tok[1], pos[1] = 9, 3
    slots = np.array([2, 1] + [4] * 6, np.int32)  # 4 = max_slots: scratch
    real = np.array([5, 0] + [0] * 6, np.int32)
    ptab = np.zeros((8, 8), np.int32)
    ptab[0] = np.arange(1, 9)
    _, muts = module.apply(
        {"params": params, "cache": before}, jnp.asarray(tok),
        positions=jnp.asarray(pos), decode=True,
        page_tables=jnp.asarray(ptab), slot_ids=jnp.asarray(slots),
        row_tokens=jnp.asarray(real), mutable=["cache", "counters"])
    for name in ("ring_k", "ring_v"):
        a, b = (np.asarray(t["block_0"]["mixer"][name])
                for t in (before, muts["cache"]))
        for untouched in (0, 1, 3):
            assert np.array_equal(a[untouched], b[untouched]), name
        moved = np.flatnonzero(np.abs(a[2] - b[2]).sum((-1, -2)))
        assert moved.tolist() == [16, 17, 18, 19, 20], name


# ------------------------------------------------------------ the gauges
def test_pool_and_ring_gauges_count_their_own_leaves():
    cfg = tiny_cfg(periods=2)
    module, params = weights(cfg)
    eng = engine(module, params)
    # two full layers: K and V of 2 kv heads x 16, float32
    assert eng.stats["kv_pool_bytes_per_token"] == 2 * 2 * 2 * 16 * 4
    # six sliding layers: a ring of 44 positions of the same
    per_slot = 6 * 44 * 2 * 2 * 16 * 4
    assert eng.stats["window_kv_bytes_per_slot"] == per_slot
    assert eng.stats["ssm_state_bytes_per_slot"] == 0
    eng.reset_stats()
    assert eng.stats["window_kv_bytes_per_slot"] == per_slot
    from benchmark import costs_window_moe as costs

    assert costs.kv_bytes_per_token(cfg) == 2 * 2 * 2 * 16 * 4
    assert costs.window_kv_bytes_per_slot(cfg) == per_slot
    # the state-space configuration's gauges are what they were
    from benchmark.drivers import serve_hybrid_ssm

    other = harness.load_json("configs", "tiny-hybrid-ssm.json")
    m = serve_hybrid_ssm.build_module(other)
    e = DecodeEngine(m, None, max_slots=4, max_len=m.max_len,
                     prefill_chunk=8)
    assert e.stats["window_kv_bytes_per_slot"] == 0
    assert e.stats["ssm_state_bytes_per_slot"] \
        == 2 * (8 * 8 * 16 * 4 + 3 * 128 * 4)


def test_a_table_floor_hands_every_call_one_width():
    """``table_floor`` = the whole table: the page-table operand has one
    width whatever is alive, so each program compiles once; the default
    doubles up from 1 as it did."""
    cfg = tiny_cfg()
    module, params = weights(cfg)
    (p,) = prompts(cfg["vocab_size"], (5,))
    eng = engine(module, params, table_floor=10 ** 6)
    assert eng._live_table_width() == 128 // 4
    eng.submit(0, p, 3)
    out = drain(eng)
    agrees(params, cfg, p, out[0], 3)
    assert eng._live_table_width() == 128 // 4
    assert engine(module, params, table_floor=1)._live_table_width() == 1
    assert engine(module, params, table_floor=8)._live_table_width() == 8


# ------------------------------------------------- the benchmark's driver
class _NoMonitor:
    in_window = 0

    def fence(self): pass
    def unfence(self): pass
    def report(self): return {}


def _ctx(tmp_path, seed, control=None, check_requests=8):
    cfg = tiny_cfg()
    # the masked forms (the engine test keeps the kernels) at one table
    # width: every width is a compilation of each program
    cfg["engine"].update(expect_paged_kernel_mode=0, table_floor_pages=32)
    traffic = harness.load_json("traffic", "tiny-chat.json")
    traffic["check_requests"] = check_requests
    traffic["prompt_tokens"]["high"] = 60  # past the window and the ring
    traffic["max_new_tokens"].update(low=8, high=24)
    return dict(
        cell={"name": "tiny-window-moe.tiny-chat",
              "config": "tiny-window-moe", "traffic": "tiny-chat",
              "chips": 1},
        seed=seed, seconds=3.0, rehearse=True, tracer=None, config=cfg,
        traffic=traffic, phases=harness.Phases(0.0), monitor=_NoMonitor(),
        work_dir=str(tmp_path), peaks=None, control=control)


def _bad(run):
    return {c["name"]: c for c in run["checks"] if not c["ok"]}


def test_driver_sound_run_is_correct(tmp_path):
    run = driver.run(_ctx(tmp_path, 21))
    assert not _bad(run), _bad(run)
    counters = run["counters"]
    assert counters["win_step_live_keys"] > 0
    assert counters["full_step_live_keys"] > 0
    assert counters["moe_assignments_held"] > 0
    assert counters["window_kv_bytes_per_slot"] > 0
    assert set(run["end_to_end"]) == {"setup_s", "serve_tokens_per_s"}
    assert run["window"]["ttft_p95_ms"] > 0


def test_driver_control_is_not_correct(tmp_path):
    ctx = _ctx(tmp_path, 31, check_requests=24)
    ctx["control"] = ctx["config"]["control_precision"]
    bad = _bad(driver.run(ctx))
    # not correct by one of the limits, not by each
    assert bad and set(bad) <= set(driver.COMPARED)
    gap = bad["served_token_logit_gap_mean"]
    assert gap["value"] > 3 * gap["limit"]
