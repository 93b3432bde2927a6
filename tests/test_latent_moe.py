"""The latent-attention / routed-expert decoder
(``rafiki_tpu.models.latent_moe``, ``ops/latent_attention.py``, the serving
expert layer of ``ops/moe.py``): tiny sizes, seeded weights, the CPU. The
plain reference is the benchmark's (``benchmark/reference/latent_moe.py``:
float32, ``highest``, no cache, keys and values expanded, nothing of the
program imported). Serving it through ``DecodeEngine`` is
``test_latent_moe_serving.py``.

- the module's full forward equals the reference's logits;
- attending in latent space through the cache (chunked prefill, then
  single-token steps, on the Pallas step kernel and on the page gather)
  equals expand-then-attend; the step kernel equals the gather, on the
  BlockSpec pipeline and on its own copies of live pages at the
  positions, table widths and stale buffers that can go wrong; which of
  the two fetches runs is what the leaves' shapes say, and the two
  device counters say what was fetched;
- YaRN frequencies, interleaved rotary pairs, the position-dependent query
  scale and the softmax scale against their formulas;
- the shares add up: four ``experts_held`` shares of one layer, the shared
  expert counted once, equal the uncut reference layer;
- no dropped token: a row's output is the same alone and in a skewed
  batch; rows of absent experts are left out; the gates' rule — on both
  routes of the grouped products (``jax.lax.ragged_dot``, and the
  narrow-tile Pallas kernel of ``ops/grouped_matmul.py`` through the
  interpreter);
- the narrow-tile kernel against a per-expert loop in f32 and against
  the ``ragged_dot`` route over the shapes of groups that can go wrong;
  the route and the engagement counter are what the shapes say.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers import serve_latent_moe as driver
from benchmark.reference import latent_moe as ref
from rafiki_tpu.models.latent_moe import (LatentMoEBlock,
                                          position_query_scale,
                                          rope_interleaved, yarn_inv_freq)
from rafiki_tpu.ops import latent_attention, moe
from rafiki_tpu.ops.latent_attention import (copies_own_pages,
                                             latent_decode_attention,
                                             latent_gather_attention,
                                             packed_key_rows,
                                             packed_key_write)
from rafiki_tpu.serving import decode_engine

YARN = {"beta_fast": 32, "beta_slow": 1, "factor": 128,
        "llama_4_scaling_beta": 0.1, "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 64, "rope_theta": 10000,
        "rope_type": "yarn", "type": "yarn"}


def tiny_cfg(**over):
    cfg = harness.load_json("configs", "tiny-latent-moe.json")
    cfg.update(over)
    return cfg


def weights(cfg, seed=3):
    module = driver.build_module(cfg)
    return module, driver.make_weights(cfg, driver.abstract_params(module),
                                       seed)


#: the two routes of ``grouped_experts``'s products, by its ``interpret``:
#: None off the TPU = ``jax.lax.ragged_dot``; True = the narrow-tile
#: Pallas kernel in the interpreter
ROUTES = pytest.mark.parametrize(
    "interpret", [None, True], ids=["ragged_dot", "narrow_tile"])


# ------------------------------------------------- model against reference
def test_module_full_forward_equals_reference_logits():
    cfg = tiny_cfg()
    module, params = weights(cfg)
    ids = np.random.default_rng(1).integers(0, cfg["vocab_size"], size=40)
    got = module.apply({"params": params}, jnp.asarray(ids[None]))[0]
    want = ref.forward(params, jnp.asarray(ids), cfg)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=1e-5)


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "gather"])
def test_latent_space_decode_equals_expand_then_attend(kernel):
    """Chunked prefill then single-token steps through the latent pool
    (absorbed up-projection) against ONE cacheless call that expands keys
    and values: the same logits at every position."""
    cfg = tiny_cfg()
    cfg["engine"]["paged_kernel"] = kernel
    module, params = weights(cfg, seed=5)
    b, t, page = 2, 21, module.kv_page_size
    ids = np.random.default_rng(2).integers(0, cfg["vocab_size"],
                                            size=(b, t)).astype(np.int32)
    want = module.apply({"params": params}, jnp.asarray(ids))
    cache = decode_engine._empty_cache(module, b)
    tabs = jnp.asarray(1 + np.arange(b * 4).reshape(b, 4), jnp.int32)
    got = []

    @jax.jit
    def call(cache, ids, pos):
        return module.apply(
            {"params": params, "cache": cache}, ids, positions=pos,
            decode=True, page_tables=tabs, mutable=["cache"])

    for lo, hi in ((0, 8), (8, 16)) + tuple((i, i + 1)
                                            for i in range(16, t)):
        pos = jnp.broadcast_to(jnp.arange(lo, hi), (b, hi - lo))
        logits, muts = call(cache, jnp.asarray(ids[:, lo:hi]), pos)
        cache = muts["cache"]
        got.append(logits)
    assert page * 4 >= t
    np.testing.assert_allclose(np.asarray(jnp.concatenate(got, 1)),
                               np.asarray(want), atol=2e-5, rtol=1e-5)


def _pool(rng, b, r, dr, page, n_tables, dtype, big=()):
    """A pool of ``b`` slots' pages under a shuffled block table: the
    latents, the rotary keys in the packed leaf (written by the op the
    model writes them with) AND as plain rows, for the oracle. Slots in
    ``big`` hold rows 1e3 times the others'."""
    n_pages = 1 + b * n_tables
    lat = rng.normal(size=(n_pages, page, r))
    key = rng.normal(size=(n_pages, page, dr))
    tabs = rng.permutation(np.arange(1, n_pages)).reshape(b, n_tables)
    for i in big:
        lat[tabs[i]] *= 1e3
        key[tabs[i]] *= 1e3
    lat, key = jnp.asarray(lat, dtype), jnp.asarray(key, dtype)
    where = np.indices((n_pages, page))
    packed = packed_key_write(
        jnp.zeros((n_pages, page // 2, 2 * dr), dtype),
        jnp.asarray(where[0]), jnp.asarray(where[1]), key)
    return lat, key, packed, jnp.asarray(tabs, jnp.int32)


def _step_against_gather(lat, key, packed, tabs, t, heads, dtype, rng,
                         pages_per_step, atol):
    b, n_tables = tabs.shape
    page, r, dr = lat.shape[1], lat.shape[2], key.shape[2]
    t = jnp.asarray(t, jnp.int32)
    q_lat = jnp.asarray(rng.normal(size=(b, heads, r)) * .3, dtype)
    q_rope = jnp.asarray(rng.normal(size=(b, heads, dr)) * .3, dtype)
    got = latent_decode_attention(q_lat, q_rope, lat, packed, tabs, t,
                                  pages_per_step=pages_per_step,
                                  interpret=True)
    key_rows = packed_key_rows(packed, tabs)
    np.testing.assert_array_equal(  # the packed leaf, unpacked
        np.asarray(key_rows, np.float32),
        np.asarray(key[tabs].reshape(b, n_tables * page, dr), np.float32))
    want = latent_gather_attention(
        q_lat[:, None], q_rope[:, None],
        lat[tabs].reshape(b, n_tables * page, r), key_rows,
        t[:, None])[:, 0]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=atol,
                               rtol=atol)


@pytest.mark.parametrize("pages_per_step", [None, 1, 2, 8])
def test_latent_step_kernel_equals_gather(pages_per_step):
    """The BlockSpec-pipeline fetch: widths no HBM slice could take."""
    rng = np.random.default_rng(0)
    lat, key, packed, tabs = _pool(rng, 3, 16, 8, 4, 8, jnp.float32)
    assert not copies_own_pages(lat, packed)
    _step_against_gather(lat, key, packed, tabs, [0, 13, 31], 4,
                         jnp.float32, rng, pages_per_step, 1e-5)


#: the kernel's own copies, pages of 32: two a step (64 positions), two
#: steps a block (128). (table width, the three slots' positions, slots
#: whose rows are huge)
OWN_COPY_CASES = {
    "position_0": (8, [0, 0, 0], ()),
    "a_pages_last_row": (8, [31, 95, 32], ()),
    "a_steps_last_row_and_its_first": (8, [63, 64, 191], ()),
    "a_blocks_last_row": (8, [127, 255, 128], ()),
    "a_blocks_first_row": (8, [128, 1, 129], ()),
    "a_table_of_one_block": (4, [127, 5, 70], ()),
    "a_table_no_multiple_of_the_block": (7, [223, 192, 100], ()),
    # slot i long and slot i + 1 at position 0: the copy started a slot
    # ahead is block 0 of the next slot
    "long_then_position_0": (7, [223, 0, 130], ()),
    # both buffers hold slot 0's huge rows when slot 1 meets a partly
    # live step, and the call's first step is partly live over the
    # zeroed buffers: dead rows meet probabilities of exactly 0
    "stale_rows_of_another_slot": (8, [255, 33, 2], (0,)),
    "zero_primed_first_block": (8, [2, 140, 0], ()),
}


@pytest.mark.parametrize("dtype,atol", [(jnp.float32, 2e-5),
                                        (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(OWN_COPY_CASES))
def test_latent_step_kernel_own_copies_equals_gather(case, dtype, atol,
                                                     monkeypatch):
    """The kernel's own DMAs of live pages, a block ahead, at leaves
    whose minor dimensions fill the lanes (128 latent, 2 x 64 keys),
    against the gather over the same leaves."""
    monkeypatch.setattr(latent_attention, "ROWS_PER_BLOCK", 128)
    n_tables, t, big = OWN_COPY_CASES[case]
    rng = np.random.default_rng(sorted(OWN_COPY_CASES).index(case))
    lat, key, packed, tabs = _pool(rng, 3, 128, 64, 32, n_tables, dtype,
                                   big)
    assert copies_own_pages(lat, packed)
    _step_against_gather(lat, key, packed, tabs, t, 4, dtype, rng, 2,
                         atol)


def _attention_counts(r, dr, page, dtype, n_tables, positions):
    """One single-token call of a ``LatentAttention`` layer through the
    step kernel: the two counts it sows."""
    from rafiki_tpu.models.latent_moe import LatentAttention

    b = len(positions)
    layer = LatentAttention(
        n_heads=2, q_rank=8, kv_rank=r, nope_dim=8, rope_dim=dr, v_dim=8,
        max_len=n_tables * page, kv_page_size=page,
        kv_pages=1 + b * n_tables, paged_kernel=True)
    x = jnp.ones((b, 1, 16), dtype)
    pos = jnp.asarray(positions, jnp.int32)[:, None]
    tabs = jnp.asarray(1 + np.arange(b * n_tables).reshape(b, n_tables),
                       jnp.int32)
    variables = layer.init(jax.random.PRNGKey(0), x, pos, True, tabs)
    _, muts = layer.apply(variables, x, pos, True, tabs,
                          mutable=["cache", "counters"])
    cache = variables["cache"]
    return (copies_own_pages(cache["kv"], cache["k_rope"]),
            [int(c) for c in muts["counters"]["latent"]])


def test_the_fetch_is_what_the_shapes_say_and_the_counters_what_ran():
    """The kernel copies its own pages where both leaves fill the lanes
    and a half page fills the sublane tiles — the published 256 + 2 x 64
    in bf16 at pages of 32, 128 + 2 x 64 in f32 at pages of 16 — and
    leaves the tiny widths, and half pages under a tile, to the
    pipeline; the fetches counted are the live pages on the first and
    every entry of the table on the second."""
    spec = jax.ShapeDtypeStruct
    for r, dr, page, dtype, own in (
            (256, 64, 32, jnp.bfloat16, True),
            (128, 64, 16, jnp.float32, True),
            (256, 64, 16, jnp.bfloat16, False),   # 8 rows < a bf16 tile
            (192, 32, 32, jnp.bfloat16, False),   # 2 x 32: half the lanes
            (16, 8, 8, jnp.float32, False)):      # tiny-latent-moe
        assert copies_own_pages(
            spec((9, page, r), dtype),
            spec((9, page // 2, 2 * dr), dtype)) == own, (r, dr, page)
    positions = [0, 15, 16, 47]  # 1 + 1 + 2 + 3 live pages of 16
    assert _attention_counts(128, 64, 16, jnp.float32, 4, positions) == (
        True, [7, 7])
    assert _attention_counts(16, 8, 16, jnp.float32, 4, positions) == (
        False, [7, 4 * 4])


# ----------------------------------------------------- rotary formulas
def test_yarn_frequencies_against_the_formula():
    dim, theta, factor, orig = 64, 1e4, 128.0, 8192
    got = yarn_inv_freq(dim, theta, factor, orig, 32.0, 1.0)
    plain = theta ** (-np.arange(0, dim, 2) / dim)
    # correction dims, by hand: dim ln(orig / (turns 2 pi)) / (2 ln theta)
    low = math.floor(dim * math.log(orig / (32 * 2 * math.pi))
                     / (2 * math.log(theta)))
    high = math.ceil(dim * math.log(orig / (1 * 2 * math.pi))
                     / (2 * math.log(theta)))
    assert (low, high) == (12, 25)
    np.testing.assert_allclose(got[:low + 1], plain[:low + 1], rtol=1e-6)
    np.testing.assert_allclose(got[high:], plain[high:] / factor,
                               rtol=1e-6)
    mid = 18  # inside the ramp: a blend by (18 - 12) / (25 - 12)
    w = (mid - low) / (high - low)
    np.testing.assert_allclose(
        got[mid], plain[mid] / factor * w + plain[mid] * (1 - w),
        rtol=1e-6)
    # the reference computes its own, from the configuration's keys
    rp = dict(YARN, original_max_position_embeddings=orig)
    np.testing.assert_allclose(ref.yarn_inv_freq(rp, dim), got, rtol=1e-6)


def test_rotary_turns_interleaved_pairs():
    inv = np.asarray([0.5, 0.25], np.float32)
    x = jnp.asarray([[[1.0, 0.0, 0.0, 2.0]]])  # pairs (1, 0) and (0, 2)
    got = np.asarray(rope_interleaved(x, jnp.asarray([[3]]), inv))[0, 0]
    a, b = 3 * 0.5, 3 * 0.25
    want = [math.cos(a), math.sin(a), -2 * math.sin(b), 2 * math.cos(b)]
    np.testing.assert_allclose(got, want, atol=1e-6)
    # per head alike: (b, s, heads, dim) takes the same angles
    xh = jnp.broadcast_to(x[:, :, None, :], (1, 1, 3, 4))
    np.testing.assert_allclose(
        np.asarray(rope_interleaved(xh, jnp.asarray([[3]]), inv))[0, 0, 2],
        want, atol=1e-6)


def test_query_scale_is_the_identity_below_the_original_context():
    pos = jnp.asarray([[0, 1, 2047, 8191, 8192, 16383, 16384]])
    got = np.asarray(position_query_scale(pos, 0.1, 8192))[0]
    np.testing.assert_array_equal(got[:4], np.ones(4, np.float32))
    np.testing.assert_allclose(got[4:], [1 + 0.1 * math.log(2)] * 2
                               + [1 + 0.1 * math.log(3)], rtol=1e-6)
    cfg = {"rope_parameters": dict(
        YARN, original_max_position_embeddings=8192)}
    np.testing.assert_allclose(np.asarray(ref.query_scale(cfg, pos[0])),
                               got, rtol=1e-6)


def test_softmax_scale_carries_mscale_all_dim_squared():
    cfg = {"qk_head_dim": 128, "rope_parameters": dict(
        YARN, original_max_position_embeddings=8192)}
    rot, s = ref.attention_scales(cfg)
    m = 0.1 * math.log(128) + 1
    assert rot == 1.0 and abs(m - 1.4852) < 1e-4
    assert abs(s - 128 ** -0.5 * m * m) < 1e-9


# ------------------------------------------------- the chip's share
def _layer_weights(n_held, n_router=8, seed=11, **over):
    cfg = tiny_cfg(n_routed_experts=n_held, num_hidden_layers=1, **over)
    cfg["published"] = {"n_routed_experts": n_router}
    cfg["deployment"] = {"experts_held_first": 0}
    return cfg, weights(cfg, seed)[1]["block_0"]


@ROUTES
@pytest.mark.parametrize("n, over", [
    (8, {}), (64, {"num_experts_per_tok": 8, "n_shared_experts": 0})],
    ids=["8_top2_shared", "64_top8_no_shared"])
def test_the_shares_add_up_to_the_uncut_layer(n, over, interpret,
                                              monkeypatch):
    """Four chips, a quarter of the experts each, of one layer — 2 of 8
    with 2 a token beside a shared expert, and 16 of 64 with 8 a token
    and no shared expert: what the shares add to the residual stream,
    the shared expert counted once, is what the uncut layer adds — in
    the reference, and the program's share equals the reference's
    share."""
    if interpret:  # the module's own call, steered onto the kernel here
        monkeypatch.setattr(moe, "use_xla_fallback", lambda _: False)
    cfg, full = _layer_weights(n, n, **over)
    per = n // 4
    x = jnp.asarray(np.random.default_rng(4).normal(size=(24, 64)),
                    jnp.float32)
    pos = jnp.arange(24)
    uncut = ref.layer(full, x, pos, cfg, held=None)
    nothing_routed = ref.layer(
        {**full, "moe": {**full["moe"], **{
            k: {"kernel": full["moe"][k]["kernel"][:0]}
            for k in ("experts_gate", "experts_up", "experts_down")}}},
        x, pos, cfg, held=(0, 0), shared=False)  # x + attention
    total = nothing_routed
    for chip in range(4):
        first = per * chip
        share = {**full, "moe": {**full["moe"], **{
            k: {"kernel": full["moe"][k]["kernel"][first:first + per]}
            for k in ("experts_gate", "experts_up", "experts_down")}}}
        part = ref.layer(share, x, pos, cfg, held=(first, per),
                         shared=chip == 0)
        total = total + (part - nothing_routed)
        # the program's layer, told the same share
        mine = dict(cfg, n_routed_experts=per)
        mine["deployment"] = {"experts_held_first": first}
        got = _apply_block(driver.build_module(mine), share, x)
        want = ref.layer(share, x, pos, cfg, held=(first, per))
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=2e-5)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               atol=2e-5)


def _apply_block(module, block_params, x):
    """One decoder layer of the program on hidden rows ``x`` (t, d)."""
    block = LatentMoEBlock(*module.block_fields(), module.shared_dim,
                           module.eps)
    return block.apply({"params": block_params}, x[None],
                       jnp.arange(x.shape[0])[None], False)[0]


@ROUTES
def test_no_dropped_token_in_a_skewed_batch(interpret):
    """63 rows that all choose the same two experts beside one row of
    another kind: that row's output is what it is alone (a capacity
    would have dropped most of the 63, and the order of rows would
    matter). On the narrow-tile route the hot groups of 63 and 64 rows
    share the first row tile and the second."""
    rng = np.random.default_rng(9)
    d, f, n = 16, 24, 4
    w = [jnp.asarray(rng.normal(size=s) / 4, jnp.float32)
         for s in ((n, d, f), (n, d, f), (n, f, d))]
    hot = rng.normal(size=(1, d))
    x = jnp.asarray(np.concatenate([np.repeat(hot, 63, 0),
                                    rng.normal(size=(1, d))]), jnp.float32)
    experts = jnp.asarray([[0, 1]] * 63 + [[1, 3]], jnp.int32)
    gates = jnp.asarray([[0.7, 0.3]] * 63 + [[0.4, 0.6]], jnp.float32)
    full, counts = moe.grouped_experts(x, gates, experts, *w,
                                       interpret=interpret)
    alone, _ = moe.grouped_experts(x[-1:], gates[-1:], experts[-1:], *w,
                                   interpret=interpret)
    first, _ = moe.grouped_experts(x[:1], gates[:1], experts[:1], *w,
                                   interpret=interpret)
    np.testing.assert_allclose(np.asarray(full[-1]), np.asarray(alone[0]),
                               atol=1e-5)
    np.testing.assert_allclose(np.asarray(full[:63]),
                               np.repeat(np.asarray(first), 63, 0),
                               atol=1e-5)
    # by hand, for the lone row: 0.4 E1(x) + 0.6 E3(x)
    def expert(e, row):
        return (jax.nn.silu(row @ w[0][e]) * (row @ w[1][e])) @ w[2][e]
    np.testing.assert_allclose(
        np.asarray(full[-1]),
        np.asarray(0.4 * expert(1, x[-1]) + 0.6 * expert(3, x[-1])),
        atol=1e-5)
    assert [int(c) for c in counts[:4]] == [128, 128, 4, 3]
    # rows 0-62 | 63-126 | 127: tiles of 64 -> 1 + 2 + 1 visits
    assert moe.narrow_row_tile(128, 4) == 64
    assert int(counts[4]) == (4 if interpret else 0)


@ROUTES
def test_rows_of_absent_experts_are_left_out_not_zeroed(interpret):
    """Held experts 2-3 of 8: a row that chose 5 and 2 gets expert 2's
    part alone; the counts say how many assignments were held."""
    rng = np.random.default_rng(10)
    d, f = 8, 12
    w = [jnp.asarray(rng.normal(size=s) / 3, jnp.float32)
         for s in ((2, d, f), (2, d, f), (2, f, d))]
    x = jnp.asarray(rng.normal(size=(3, d)), jnp.float32)
    experts = jnp.asarray([[5, 2], [0, 7], [3, 2]], jnp.int32)
    gates = jnp.asarray([[0.5, 0.5], [0.9, 0.1], [0.2, 0.8]], jnp.float32)
    y, counts = moe.grouped_experts(x, gates, experts, *w, first=2,
                                    interpret=interpret)

    def expert(e, row):
        return (jax.nn.silu(row @ w[0][e]) * (row @ w[1][e])) @ w[2][e]
    np.testing.assert_allclose(np.asarray(y[0]),
                               np.asarray(0.5 * expert(0, x[0])), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(y[1]), np.zeros(d))
    np.testing.assert_allclose(
        np.asarray(y[2]),
        np.asarray(0.2 * expert(1, x[2]) + 0.8 * expert(0, x[2])),
        atol=1e-5)
    assert [int(c) for c in counts] == [6, 3, 2, 2, 2 if interpret else 0]


def _tiles_overlapped(sizes, tile):
    """By hand: the row tiles each non-empty group overlaps, summed."""
    total, start = 0, 0
    for size in sizes:
        if size:
            total += (start + size - 1) // tile - start // tile + 1
        start += size
    return total


def _experts_by_loop(x, gates, experts, w, first=0):
    """``grouped_experts`` by a plain loop over (row, choice) in f32."""
    x = np.asarray(x, np.float32)
    w = [np.asarray(a, np.float32) for a in w]
    y = np.zeros((x.shape[0], w[2].shape[2]), np.float32)
    for t, (row_gates, row_experts) in enumerate(zip(np.asarray(gates),
                                                     np.asarray(experts))):
        for g, e in zip(row_gates, row_experts):
            e = int(e) - first
            if 0 <= e < w[0].shape[0]:
                a = x[t] @ w[0][e]
                h = a / (1.0 + np.exp(-a)) * (x[t] @ w[1][e])
                y[t] += g * (h @ w[2][e])
    return y


#: (held experts, the expert of each (row, choice) in turn — ids past the
#: held ones are absent; choices a row: 4, or 3 where 4 does not divide).
#: Each call's shape gives the smallest row tile, 64
GROUP_CASES = {
    # 160 of 176 assignments on ONE expert: a group of two and a half tiles
    "group_larger_than_the_tile": (4, [1] * 160 + [0, 2, 3, 3] * 2 + [9] * 8),
    "empty_experts_first": (6, [2, 3, 4, 5] * 4),
    "empty_experts_last": (6, [0, 1, 2, 3] * 4),
    "empty_experts_in_a_run": (8, [0, 7] * 8 + [0, 7, 0, 0]),
    # groups of 64, 64 and 8: the first two start and end ON a boundary
    "group_from_boundary_to_boundary": (4, [0] * 64 + [1] * 64 + [3] * 8),
    # groups of 65 and 63: one row past the boundary, then up to the next
    "group_one_row_past_a_boundary": (4, [0] * 65 + [1] * 63 + [2] * 4),
    # 23 rows x 3 choices = 69 assignments: the ROWS are padded to the
    # tile, never the kernels
    "assignments_no_multiple_of_the_tile": (4, [0, 1, 2] * 11 + [3, 0, 1] * 12),
    "no_row_held_at_all": (4, [9, 8, 7, 6] * 4),
    # a prefill call's share: 256 rows x 4 of 128 experts, 32 held: ~8
    # rows an expert
    "prefill_rows_an_expert": (32, None),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(GROUP_CASES))
def test_narrow_tile_kernel_equals_a_loop_and_the_other_route(case, dtype):
    """The narrow-tile kernel (interpreter) against a per-expert loop in
    f32 and against the ``ragged_dot`` route, over the shapes of groups
    where a tiled grouped product can go wrong."""
    n, chosen = GROUP_CASES[case]
    rng = np.random.default_rng(sorted(GROUP_CASES).index(case))
    k = 4 if chosen is None else (3 if len(chosen) % 4 else 4)
    if chosen is None:
        experts = np.stack([rng.choice(128, 4, replace=False)
                            for _ in range(256)])
    else:
        experts = np.asarray(chosen).reshape(-1, k)
    t = experts.shape[0]
    d, f = 16, 24
    w = [jnp.asarray(rng.normal(size=s) / 4, dtype)
         for s in ((n, d, f), (n, d, f), (n, f, d))]
    x = jnp.asarray(rng.normal(size=(t, d)), dtype)
    gates = rng.uniform(0.1, 1.0, size=(t, k))
    gates = jnp.asarray(gates / gates.sum(-1, keepdims=True), jnp.float32)
    experts = jnp.asarray(experts, jnp.int32)
    tile = moe.narrow_row_tile(t * k, n)
    assert tile == 64

    mine, counts = moe.grouped_experts(x, gates, experts, *w,
                                       interpret=True)
    other, other_counts = moe.grouped_experts(x, gates, experts, *w)
    loop = _experts_by_loop(x, gates, experts, w)
    # f32: summation order alone; bf16: the three products' roundings of
    # gate, up and down, the same on both routes
    tol = 2e-5 if dtype == jnp.float32 else 0.05
    np.testing.assert_allclose(np.asarray(mine), loop, atol=tol)
    np.testing.assert_allclose(np.asarray(mine), np.asarray(other),
                               atol=tol)
    sizes = np.bincount(np.asarray(experts).ravel(), minlength=128)[:n]
    assert [int(c) for c in counts[:4]] == [int(c) for c in
                                            other_counts[:4]]
    assert int(counts[4]) == _tiles_overlapped(sizes, tile)
    assert int(other_counts[4]) == 0
    if case == "no_row_held_at_all":
        np.testing.assert_array_equal(np.asarray(mine), 0.0)
        assert int(counts[4]) == 0


def test_the_route_is_what_the_shapes_say_and_the_counter_what_ran():
    """A decode step's call (64 rows x 4 over 32 held experts) and a
    prefill call's (256 rows) take the narrowest tile, more rows an
    expert a wider one up to 256 — from the call's shapes alone; and
    ``moe_step_row_tiles`` is the (expert, row tile) pairs the kernel
    visited in single-token calls, 0 on the other route and in windows."""
    assert moe.narrow_row_tile(64 * 4, 32) == 64
    assert moe.narrow_row_tile(256 * 4, 32) == 64
    assert moe.narrow_row_tile(1024 * 4, 32) == 128
    assert moe.narrow_row_tile(16384 * 4, 32) == 256
    assert moe.MOE_COUNTERS[-1] == "moe_step_row_tiles"

    layer = moe.ExpertShare(n_experts=8, top_k=2, mlp_dim=24, held=(2, 4))
    rng = np.random.default_rng(5)
    step = jnp.asarray(rng.normal(size=(12, 1, 16)), jnp.float32)
    window = step.reshape(3, 4, 16)
    params = layer.init(jax.random.PRNGKey(0), step)["params"]

    def sown(x):
        _, state = layer.apply({"params": params}, x, mutable=["counters"])
        return dict(zip(moe.MOE_COUNTERS,
                        (int(c) for c in state["counters"]["moe"])))

    off = sown(step)  # off the TPU: the ``ragged_dot`` route
    assert off["moe_step_experts_touched"] > 0
    assert off["moe_step_row_tiles"] == 0
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(moe, "use_xla_fallback", lambda _: False)
        on, on_window = sown(step), sown(window)
    assert on["moe_step_experts_touched"] == off["moe_step_experts_touched"]
    # 24 assignments lie in one row tile: every touched expert is
    # visited once
    assert on["moe_step_row_tiles"] == on["moe_step_experts_touched"]
    assert on_window["moe_step_row_tiles"] == 0
    assert on_window["moe_experts_touched"] == on["moe_experts_touched"]


def test_gates_are_the_renormalised_top_k_of_a_softmax():
    logits = jnp.asarray([[2.0, 0.0, 1.0, -1.0, 3.0]])
    gates, experts = moe.route_top_k(logits, 2)
    assert [int(e) for e in experts[0]] == [4, 0]
    # renormalised top-k probabilities = a softmax over the chosen logits
    np.testing.assert_allclose(np.asarray(gates[0]),
                               np.asarray(jax.nn.softmax(
                                   jnp.asarray([3.0, 2.0]))), rtol=1e-6)
    raw, _ = moe.route_top_k(logits, 2, renormalize=False, scaling=2.0)
    np.testing.assert_allclose(
        np.asarray(raw[0]),
        2.0 * np.asarray(jax.nn.softmax(logits[0]))[[4, 0]], rtol=1e-6)


