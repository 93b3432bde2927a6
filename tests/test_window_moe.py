"""The pattern decoder's rotary attention kinds — ``R`` (every key, a
rotary table) and ``W`` (the last ``window`` keys, a table of its own, its
keys in a per-slot ring) — over gated softmax-routed experts, against the
plain reference ``benchmark/reference/window_moe.py`` on logits; the two
windowed kernels in the Pallas interpreter against the masked ring at the
window's edges; the rotary tables against hand-written ones; and what the
new fields may NOT change: the tree, the cache and the counters of a
pattern without such layers. Serving is ``test_window_moe_serving.py``."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers import serve_window_moe as driver
from benchmark.reference import window_moe as ref
from rafiki_tpu.models.hybrid_ssm_moe import (MOE_COUNTERS, SSM_COUNTERS,
                                              WINDOW_COUNTERS,
                                              HybridSSMMoEDecoder,
                                              rotary_table)
from rafiki_tpu.models.llama_lora import rope
from rafiki_tpu.ops import window_attention as wa
from rafiki_tpu.ops.paged_attention import (_paged_attention_reference,
                                            _paged_window_reference,
                                            paged_decode_attention,
                                            paged_window_attention)


def tiny_cfg(periods=2):
    """The tiny configuration at ``periods`` of its [sliding x 3, full]
    (the file has 2)."""
    cfg = harness.load_json("configs", "tiny-window-moe.json")
    n = 4 * periods
    cfg.update(num_hidden_layers=n, layer_types=cfg["layer_types"][:n],
               mlp_layer_types=cfg["mlp_layer_types"][:n])
    return cfg


def weights(cfg, seed=3):
    module = driver.build_module(cfg)
    return module, driver.make_weights(cfg, driver.abstract_params(module),
                                       seed)


# ------------------------------------------------- model against reference
def test_module_full_forward_equals_reference_logits():
    """40 tokens through 2 periods of [sliding x 3, full]: five windows
    of 8 deep, so a sliding layer's mask, each kind's rotary table and
    the held share of the experts all show in the logits."""
    cfg = tiny_cfg()
    module, params = weights(cfg)
    assert module.layer_pattern == "WEWEWERE" * 2
    ids = np.random.default_rng(0).integers(0, 256, size=(2, 40))
    got = module.apply({"params": params}, jnp.asarray(ids, jnp.int32))
    for row in range(2):
        want = ref.forward(params, jnp.asarray(ids[row], jnp.int32), cfg)
        # f32 against f32 highest: roundoff of 16 layers
        assert float(jnp.abs(got[row] - want).max()) < 2e-5


@pytest.mark.parametrize("change", ["window", "full_table", "window_table",
                                     "attention_factor"])
def test_each_layer_kinds_own_field_shows_in_the_logits(change):
    """The reference reads the mask and the table by LAYER KIND: a module
    built with one kind's field wrong no longer agrees."""
    cfg = tiny_cfg(periods=1)
    module, params = weights(cfg)
    theta, yarn, scale = module.rope_full
    wrong = {"window": dict(window=12),
             "full_table": dict(rope_full=(theta, None, scale)),
             "window_table": dict(rope_window=(theta, yarn, 1.0)),
             "attention_factor": dict(rope_full=(theta, yarn, 1.0))}[change]
    ids = np.random.default_rng(1).integers(0, 256, size=(1, 40))
    want = ref.forward(params, jnp.asarray(ids[0], jnp.int32), cfg)
    got = module.clone(**wrong).apply({"params": params},
                                      jnp.asarray(ids, jnp.int32))[0]
    assert float(jnp.abs(got - want).max()) > 1e-3


# ------------------------------------------------------- rotary tables
def test_yarn_with_attention_factor_on_half_split_pairs():
    """Dim 8, theta 10000, factor 4 over 32 original positions, beta 32
    / 1: by hand, the correction dims are floor(8 ln(32 / (32 x 2 pi)) /
    (2 ln 10000)) = -1 -> 0 and ceil(8 ln(32 / (2 pi)) / (2 ln 10000)) =
    1, so the ramp is (0, 1, 1, 1): pair 0 keeps its frequency and pairs
    1-3 turn 4 times slower; cos and sin come times the factor; pair j
    is (x[j], x[j + 4])."""
    plain = np.array([1.0, 0.1, 0.01, 0.001])
    want = np.array([1.0, 0.1 / 4, 0.01 / 4, 0.001 / 4], np.float32)
    table = rotary_table(8, 10000.0, (4.0, 32, 32.0, 1.0))
    np.testing.assert_allclose(table, want, rtol=1e-6)
    np.testing.assert_allclose(rotary_table(8, 10000.0, None), plain,
                               rtol=1e-6)
    factor = 0.1 * math.log(4.0) + 1.0
    x = np.random.default_rng(2).normal(size=(1, 3, 2, 8)).astype(np.float32)
    pos = np.array([[0, 5, 37]])
    got = np.asarray(rope(jnp.asarray(x), jnp.asarray(pos), inv_freq=table,
                          scale=factor))
    for i, p in enumerate(pos[0]):
        for j in range(4):
            c, s = math.cos(p * want[j]), math.sin(p * want[j])
            a, b = x[0, i, :, j], x[0, i, :, j + 4]
            np.testing.assert_allclose(got[0, i, :, j],
                                       factor * (a * c - b * s), atol=1e-5)
            np.testing.assert_allclose(got[0, i, :, j + 4],
                                       factor * (a * s + b * c), atol=1e-5)
    # and the reference's own table and factor are these
    rp = {"full_attention": {
        "rope_type": "yarn", "rope_theta": 10000, "factor": 4,
        "original_max_position_embeddings": 32, "beta_fast": 32,
        "beta_slow": 1}}
    inv, f = ref.rotary({"rope_parameters": rp, "head_dim": 8},
                        "full_attention")
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    assert abs(f - factor) < 1e-12
    assert driver.rotary(rp["full_attention"]) == (
        10000.0, (4.0, 32, 32.0, 1.0), factor)


def test_rope_without_a_table_is_the_rope_it_was():
    x = jnp.asarray(np.random.default_rng(3).normal(size=(2, 5, 2, 16)),
                    jnp.float32)
    pos = jnp.asarray([[0, 1, 2, 3, 4], [7, 8, 9, 10, 11]])
    table = 500000.0 ** (-np.arange(8) / 8.0)
    np.testing.assert_allclose(
        np.asarray(rope(x, pos, 500000.0)),
        np.asarray(rope(x, pos, inv_freq=table)), atol=1e-5)


# ------------------------------------------- the windowed kernels, alone
def _ring_case(dh, nkv, rep, window, page, ring, positions, seed=0):
    """Rings of 3 slots + scratch holding, for each slot, the keys of the
    positions up to its query's (written through ``ring_write`` in
    order, so a long sequence has wrapped), and the same keys laid out
    by position for the oracle."""
    rng = np.random.default_rng(seed)
    b, top = len(positions), max(positions) + 1
    k_all = rng.normal(size=(b, top, nkv, dh)).astype(np.float32)
    v_all = rng.normal(size=(b, top, nkv, dh)).astype(np.float32)
    # garbage where a last occupant would have left some: finite
    ring_k = jnp.asarray(rng.normal(size=(b + 1, ring, nkv, dh)), jnp.float32)
    ring_v = jnp.asarray(rng.normal(size=(b + 1, ring, nkv, dh)), jnp.float32)
    slots = jnp.arange(b)
    for lo in range(0, top, ring - window):  # a call's worth at a time
        pos = jnp.broadcast_to(jnp.arange(lo, min(lo + ring - window, top)),
                               (b, min(ring - window, top - lo)))
        real = pos <= jnp.asarray(positions)[:, None]
        ring_k = wa.ring_write(ring_k, slots, pos, real,
                               jnp.asarray(k_all[:, lo:lo + ring - window]))
        ring_v = wa.ring_write(ring_v, slots, pos, real,
                               jnp.asarray(v_all[:, lo:lo + ring - window]))
    q = jnp.asarray(rng.normal(size=(b, nkv * rep, dh)), jnp.float32)
    return q, ring_k, ring_v, slots, k_all, v_all


def _by_position(k_all, page):
    """(b, top, ...) keys as a paged pool of one table row a slot."""
    b, top = k_all.shape[:2]
    n = -(-top // page)
    pad = np.zeros((b, n * page) + k_all.shape[2:], np.float32)
    pad[:, :top] = k_all
    pool = pad.reshape((b * n, page) + k_all.shape[2:])
    return jnp.asarray(pool), jnp.arange(b * n, dtype=jnp.int32).reshape(b, n)


#: (head dim, kv heads, q heads a kv head): the published heads, whose
#: step kernel copies its own pages, and narrow ones on the pipeline
WIDTHS = pytest.mark.parametrize("dh,nkv,rep", [(128, 4, 8), (16, 2, 2)],
                                 ids=["own_copies", "pipeline"])


@WIDTHS
def test_window_step_kernel_at_the_windows_edges(dh, nkv, rep):
    """Window 8 over pages of 4 in a ring of 20: a query below the
    window (position 5: keys 0-5), at it (7: keys 0-7), one past (8:
    keys 1-8, the first page half dead), and far past the ring (62:
    the ring has wrapped three times, the window straddles its end)."""
    window, page, ring = 8, 4, 20
    positions = [5, 7, 8, 62]
    q, ring_k, ring_v, slots, k_all, v_all = _ring_case(
        dh, nkv, rep, window, page, ring, positions)
    t = jnp.asarray(positions)
    got, fetched = wa.window_ring_attention(
        q[:, None], ring_k, ring_v, slots, t[:, None], window, page, 64,
        dh ** -0.5, kernel=True, interpret=True)
    masked, handed = wa.window_ring_attention(
        q[:, None], ring_k, ring_v, slots, t[:, None], window, page, 64,
        dh ** -0.5, kernel=False)
    got, masked = got[:, 0], masked[:, 0]
    k_pool, tabs = _by_position(k_all, page)
    v_pool, _ = _by_position(v_all, page)
    want = _paged_attention_reference(q, k_pool, v_pool, tabs, t,
                                      dh ** -0.5, window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(masked), np.asarray(want),
                               atol=2e-5)
    # the same kernel over the keys by position reads the same window
    direct, by_position = paged_decode_attention(
        q, k_pool, v_pool, tabs, t, sm_scale=dh ** -0.5, window=window,
        interpret=True)
    np.testing.assert_allclose(np.asarray(direct), np.asarray(want),
                               atol=2e-5)
    # counted where the kernel fetches: whole pages from the window's
    # first key to the query's own and nothing behind the window, where
    # a window as long as the table fetches every page up to the
    # query's; the masked form is handed the ring
    assert np.asarray(fetched).tolist() == [8, 8, 12, 12]
    assert np.asarray(by_position).tolist() == [8, 8, 12, 12]
    _, every = paged_decode_attention(q, k_pool, v_pool, tabs, t,
                                      sm_scale=dh ** -0.5, window=64,
                                      interpret=True)
    assert np.asarray(every).tolist() == [8, 8, 12, 64]
    assert np.asarray(handed).tolist() == [ring] * 4


@WIDTHS
def test_window_prefill_kernel_across_the_windows_edge(dh, nkv, rep):
    """Rows of 8 queries (tiles of 4) whose window crosses position 0,
    lies wholly inside, and straddles the ring's end after a wrap; one
    row padded by repeating its last position, as the engine pads."""
    window, page, ring, s = 8, 4, 20, 8
    starts = [0, 3, 52]
    rng = np.random.default_rng(5)
    _, ring_k, ring_v, slots, k_all, v_all = _ring_case(
        dh, nkv, rep, window, page, ring, [p + s - 1 for p in starts])
    pos = np.stack([np.arange(p, p + s) for p in starts])
    pos[1, 5:] = pos[1, 4]  # 5 real tokens, 3 repeats
    q = jnp.asarray(rng.normal(size=(3, s, nkv * rep, dh)), jnp.float32)
    got, _ = wa.window_ring_attention(
        q, ring_k, ring_v, slots, jnp.asarray(pos), window, page, 64,
        dh ** -0.5, kernel=True, interpret=True)
    masked, none = wa.window_ring_attention(
        q, ring_k, ring_v, slots, jnp.asarray(pos), window, page, 64,
        dh ** -0.5, kernel=False)
    assert none is None  # fetched keys are a single-token call's count
    k_pool, tabs = _by_position(k_all, page)
    v_pool, _ = _by_position(v_all, page)
    want = _paged_window_reference(q, k_pool, v_pool, tabs,
                                   jnp.asarray(pos), dh ** -0.5,
                                   window=window)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)
    np.testing.assert_allclose(np.asarray(masked), np.asarray(want),
                               atol=2e-5)
    direct = paged_window_attention(q, k_pool, v_pool, tabs,
                                    jnp.asarray(pos), sm_scale=dh ** -0.5,
                                    window=window, block_q=4,
                                    interpret=True)
    np.testing.assert_allclose(np.asarray(direct), np.asarray(want),
                               atol=2e-5)


def test_the_windowed_kernels_walk_a_windows_blocks_and_have_their_names():
    """What a profile and the grid say: with a window the kernels are
    ``window_attn_step`` / ``window_attn_prefill`` and their last grid
    axis is as long as a window can span (1,024 keys: 5 blocks of 256,
    35 pages of 32 beside a tile of the chunk's 64 queries), not the
    table's 28 blocks / 224 pages; without one they are what they were.
    A row of the prefill call is ONE query tile, and the step's four
    slots one tile of slots."""
    pool = jnp.zeros((1 + 4 * 49, 32, 4, 128), jnp.bfloat16)
    tabs = jnp.zeros((4, 224), jnp.int32)
    q1, t1 = jnp.zeros((4, 32, 128), jnp.bfloat16), jnp.zeros((4,), jnp.int32)
    qs = jnp.zeros((4, 64, 32, 128), jnp.bfloat16)
    ts = jnp.zeros((4, 64), jnp.int32)

    def text(fn, *args, **kw):
        return str(jax.make_jaxpr(lambda *a: fn(
            *a, sm_scale=0.1, interpret=False, **kw))(*args))

    step = text(paged_decode_attention, q1, pool, pool, tabs, t1)
    wstep = text(paged_decode_attention, q1, pool, pool, tabs, t1,
                 window=1024)
    pre = text(paged_window_attention, qs, pool, pool, tabs, ts)
    wpre = text(paged_window_attention, qs, pool, pool, tabs, ts,
                window=1024)
    assert "paged_attn_step" in step and "window_attn" not in step
    assert "window_attn_step" in wstep and "paged_attn" not in wstep
    assert "paged_attn_window" in pre and "window_attn" not in pre
    assert "window_attn_prefill" in wpre and "paged_attn" not in wpre
    assert "grid=(1, 1, 28)" in step and "grid=(1, 1, 5)" in wstep
    assert "grid=(4, 1, 1, 224)" in pre and "grid=(4, 1, 1, 35)" in wpre


def test_ring_positions_hold_the_window_and_a_calls_tokens():
    assert wa.ring_positions(1024, 8 * 64, 32) == 1568
    assert wa.ring_positions(8, 4 * 8, 4) == 44
    tabs = np.asarray(wa.ring_table(jnp.asarray([0, 2]), 7, 3))
    assert tabs.tolist() == [[0, 1, 2, 0, 1, 2, 0], [6, 7, 8, 6, 7, 8, 6]]


# --------------------------------------- what the new fields do not move
def test_a_pattern_without_rotary_layers_is_the_decoder_it_was():
    """The state-space configuration's module: its counters are the two
    groups it had, its cache has no ring, and its ``E`` layer builds the
    expert rule it had (two kernels, sigmoid scores with a bias)."""
    cfg = harness.load_json("configs", "tiny-hybrid-ssm.json")
    from benchmark.drivers import serve_hybrid_ssm

    module = serve_hybrid_ssm.build_module(cfg)
    assert module.device_counters == MOE_COUNTERS + SSM_COUNTERS
    fields = dict(dict(module.layer_fields("E"))["expert_fields"])
    assert fields["gated"] is False and fields["sigmoid_scores"] is True
    assert dict(module.layer_fields("*")) == dict(
        n_heads=4, n_kv_heads=2, head_dim=16, kv_page_size=8,
        kv_pages=module.kv_pages, paged_kernel=True)
    cache = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((4, 1), jnp.int32),
        decode=True)["cache"])
    names = {path[-1].key for path, _ in
             jax.tree_util.tree_flatten_with_path(cache)[0]}
    assert names == {"k", "v", "ssm", "conv"}
    params = serve_hybrid_ssm.abstract_params(module)
    moe = params["block_1"]["mixer"]["moe"]
    assert set(moe) == {"router", "score_bias", "experts_up",
                        "experts_down"}
    # and the rotary pattern's own
    mine = driver.build_module(tiny_cfg())
    assert mine.device_counters == (MOE_COUNTERS + SSM_COUNTERS
                                    + WINDOW_COUNTERS)
    moe = driver.abstract_params(mine)["block_1"]["mixer"]["moe"]
    assert set(moe) == {"router", "experts_gate", "experts_up",
                        "experts_down"}


def test_a_ring_too_short_for_a_calls_tokens_is_refused():
    module = driver.build_module(tiny_cfg())
    module.ring_holds_call(32)
    with pytest.raises(ValueError, match="kv_ring"):
        module.ring_holds_call(40)
    with pytest.raises(ValueError, match="whole pages"):
        module.clone(kv_ring=42).layer_fields("W")
    # a pattern without window layers has no ring to hold anything
    HybridSSMMoEDecoder.ring_holds_call(
        module.clone(layer_pattern="RE", kv_ring=0), 10 ** 6)
