"""Paged-native flash decode kernel — LSE partial-softmax equivalence.

Property tests for ``ops/paged_attention.py``: the Pallas kernel (run
through the interpreter so CPU tier-1 exercises the REAL kernel math,
not a fallback) must match the pure-XLA page-gather oracle across page
counts, partial last pages, blocks of pages (one narrower than the
table, several of them, positions on either side of a block's edge),
scratch-page garbage, GQA ratios, head tiles, int8 scale rows, and
bf16 pools. The oracle is the same math
``_DecoderAttention``'s gather path computes, which is what makes the
engine-level kernel-vs-gather bit-exactness in ``test_paged_kv.py``
plausible rather than lucky.
"""

import functools
import math

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from rafiki_tpu.ops import paged_attention as pa
from rafiki_tpu.ops.paged_attention import (TILE_BYTES, _decode_grid,
                                            _default_block_q,
                                            _paged_attention_reference,
                                            _paged_window_reference,
                                            _tile_bytes,
                                            paged_decode_attention,
                                            paged_decode_grid_steps,
                                            paged_window_attention,
                                            paged_window_grid_steps,
                                            resolve_paged_kernel,
                                            resolve_paged_window_kernel)


def _setup(positions, n_kv=2, rep=2, dh=8, ps=8, n_tables=4,
           n_pages=12, seed=0, int8=False, scale=1.0, dtype=np.float32):
    """Random pools + a permuted block table per slot: live pages drawn
    from a shuffled free list (page 0 never live — the engine's scratch
    invariant), dead entries left at 0. Scratch page filled with large
    garbage so any leak past the position mask is loud."""
    rng = np.random.default_rng(seed)
    b = len(positions)
    heads = n_kv * rep
    q = (rng.normal(size=(b, heads, dh)) * scale).astype(dtype)
    if int8:
        kp = rng.integers(-127, 128,
                          size=(n_pages, ps, n_kv, dh)).astype(np.int8)
        vp = rng.integers(-127, 128,
                          size=(n_pages, ps, n_kv, dh)).astype(np.int8)
        ks = rng.uniform(1e-3, 0.1,
                         size=(n_pages, ps, n_kv)).astype(np.float32)
        vs = rng.uniform(1e-3, 0.1,
                         size=(n_pages, ps, n_kv)).astype(np.float32)
        scales = (ks, vs)
    else:
        kp = (rng.normal(size=(n_pages, ps, n_kv, dh))
              * scale).astype(dtype)
        vp = (rng.normal(size=(n_pages, ps, n_kv, dh))
              * scale).astype(dtype)
        kp[0], vp[0] = 1e3, -1e3  # scratch garbage: leaks are loud
        scales = None
    t = np.asarray(positions, np.int32)
    tabs = np.zeros((b, n_tables), np.int32)
    free = list(rng.permutation(np.arange(1, n_pages)))
    for i in range(b):
        for pg in range(int(t[i]) // ps + 1):
            tabs[i, pg] = free.pop()
    return q, kp, vp, tabs, t, scales


def _both(q, kp, vp, tabs, t, scales=None, **kw):
    sm = 1.0 / np.sqrt(q.shape[-1])
    sk, sv = scales if scales else (None, None)
    out = paged_decode_attention(q, kp, vp, tabs, t, sm_scale=sm,
                                 k_scale=sk, v_scale=sv,
                                 interpret=True, **kw)
    ref = _paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tabs), t, sm,
        None if sk is None else jnp.asarray(sk),
        None if sv is None else jnp.asarray(sv))
    return np.asarray(out, np.float32), np.asarray(ref, np.float32)


#: the one-block geometry most cases here were written at: 4 pages of
#: 8, narrower than a block of ``BLOCK_KEYS`` key positions
ONE_BLOCK = dict()
#: the served geometry: 32 q / 8 kv heads of 128 over pages of 16, a
#: table of 32 pages = TWO blocks of 16 pages (256 key positions each).
#: Heads of 128 fill the lanes, so here the kernel copies its own pages
#: out of the pools; the 8-wide heads of the other cases leave the
#: fetch to the BlockSpec pipeline, a page an operand
TWO_BLOCKS = dict(n_kv=8, rep=4, dh=128, ps=16, n_tables=32, n_pages=129)
GEOMETRIES = pytest.mark.parametrize(
    "geom,positions", [(ONE_BLOCK, [2, 9, 17, 30]),
                       (TWO_BLOCKS, [2, 100, 257, 300])],
    ids=["one_block", "two_blocks"])


@pytest.mark.parametrize("positions", [
    [0, 0, 0, 0],          # single live key, page count 1
    [3, 5, 1, 6],          # partial first page everywhere
    [7, 8, 15, 16],        # exact page boundaries and first-past-it
    [0, 7, 12, 31],        # mixed: 1..4 live pages, full last table
])
def test_kernel_matches_reference_across_page_counts(positions):
    q, kp, vp, tabs, t, _ = _setup(positions)
    out, ref = _both(q, kp, vp, tabs, t)
    np.testing.assert_allclose(out, ref, atol=2e-6, rtol=1e-5)


@pytest.mark.parametrize("positions", [
    [5, 250, 255, 256],    # block 0: last live key on its first page,
                           # on its last page, ON the block's edge, and
                           # one past it (block 1's first key)
    [260, 500, 511, 271],  # block 1: first page, last page, the
                           # table's last key, first page's last key
    [0, 15, 16, 239],      # block 1 dead: skipped, never fetched
], ids=["block0_edges", "block1_edges", "block1_dead"])
@pytest.mark.parametrize("dh", [128, 8], ids=["own_copies", "pipeline"])
def test_blocks_of_pages_match_reference(dh, positions):
    """A grid step consumes a BLOCK of pages: the position mask has to
    hide a partly live block's dead pages and the last page's tail on
    either side of a block's edge, and a dead block must add nothing —
    whichever way the pages are fetched."""
    q, kp, vp, tabs, t, _ = _setup(positions, **dict(TWO_BLOCKS, dh=dh))
    out, ref = _both(q, kp, vp, tabs, t)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("width", [1, 2, 4, 32])
def test_table_widths_from_one_page_to_several_blocks(width):
    """A table narrower than a block is ONE block of that width (1, 2,
    4 pages); 32 pages of 16 are two blocks — at 32 q / 8 kv heads."""
    hi = width * 16 - 1
    q, kp, vp, tabs, t, _ = _setup(
        [0, hi // 3, hi - 1, hi], **dict(TWO_BLOCKS, n_tables=width))
    out, ref = _both(q, kp, vp, tabs, t)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-4)


@GEOMETRIES
def test_scratch_page_garbage_never_leaks(geom, positions):
    """Dead table entries point at pool page 0 (the engine's scratch
    page). Its 1e3-magnitude garbage must not move the output: the
    kernel skips dead blocks entirely and masks a partly live block's
    dead pages and the live tail, so the answer equals an oracle run
    over a pool whose scratch page is ZEROED (not merely the garbage
    oracle agreeing with itself)."""
    q, kp, vp, tabs, t, _ = _setup(positions, **geom)
    out, _ = _both(q, kp, vp, tabs, t)
    kz, vz = kp.copy(), vp.copy()
    kz[0], vz[0] = 0.0, 0.0
    ref0 = np.asarray(_paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kz), jnp.asarray(vz),
        jnp.asarray(tabs), t, 1.0 / np.sqrt(q.shape[-1])), np.float32)
    np.testing.assert_allclose(out, ref0, atol=1e-5, rtol=1e-4)


@pytest.mark.parametrize("geom,positions,live_width", [
    (dict(n_tables=8), [5, 9, 2, 0], 2),      # both one block
    (TWO_BLOCKS, [5, 200, 77, 255], 16),      # two blocks against one
], ids=["one_block", "two_blocks"])
def test_live_width_table_slice_matches_full_width(geom, positions,
                                                   live_width):
    """The engine passes its live-width table slice; the kernel's
    answer must not depend on how many dead columns — or dead BLOCKS —
    ride along."""
    q, kp, vp, tabs, t, _ = _setup(positions, **geom)
    full, ref = _both(q, kp, vp, tabs, t)
    narrow, _ = _both(q, kp, vp, tabs[:, :live_width], t)
    np.testing.assert_allclose(full, ref, atol=1e-5, rtol=1e-4)
    np.testing.assert_allclose(full, narrow, atol=2e-6, rtol=1e-5)


def test_lse_merge_across_magnitude_spread():
    """Pages with wildly different score magnitudes: the cross-page
    LSE merge must stay stable where a naive sum-of-exps would
    overflow/underflow."""
    q, kp, vp, tabs, t, _ = _setup([31, 31, 31, 31], n_pages=20,
                                   scale=1.0)
    # scale each LIVE page's keys by 10^page so the running max moves
    # on every merge step
    for i in range(tabs.shape[0]):
        for pg in range(4):
            kp[tabs[i, pg]] *= 10.0 ** pg
    out, ref = _both(q, kp, vp, tabs, t)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-4)


def test_gqa_ratios_and_block_h():
    """rep in {1, 2, 4} (MHA through 4:1 GQA) and the block_h kv-head
    tile both reproduce the oracle; an indivisible block_h fails
    loudly like flash_attention's."""
    for n_kv, rep in ((4, 1), (2, 2), (1, 4)):
        q, kp, vp, tabs, t, _ = _setup([4, 11, 19, 26], n_kv=n_kv,
                                       rep=rep, seed=n_kv)
        out, ref = _both(q, kp, vp, tabs, t)
        np.testing.assert_allclose(out, ref, atol=2e-6, rtol=1e-5)
    q, kp, vp, tabs, t, _ = _setup([4, 11, 19, 26], n_kv=4, rep=2)
    out, ref = _both(q, kp, vp, tabs, t, block_h=2)
    np.testing.assert_allclose(out, ref, atol=2e-6, rtol=1e-5)
    with pytest.raises(ValueError, match="block_h"):
        paged_decode_attention(q, kp, vp, tabs, t, sm_scale=0.3,
                               block_h=3, interpret=True)


@GEOMETRIES
def test_int8_scale_rows_dequant_in_kernel(geom, positions):
    """int8 pools + per-(page, pos, head) f32 absmax scale rows: the
    fused in-kernel dequant — every page of a block by its own scale
    rows — matches the dequantize-then-attend oracle (both accumulate
    in f32)."""
    q, kp, vp, tabs, t, scales = _setup(positions, int8=True, **geom)
    out, ref = _both(q, kp, vp, tabs, t, scales=scales)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-4)


@GEOMETRIES
def test_bf16_pools_and_output_dtype(geom, positions):
    q, kp, vp, tabs, t, _ = _setup(positions, dtype=np.float32, **geom)
    qb = jnp.asarray(q, jnp.bfloat16)
    kb, vb = jnp.asarray(kp, jnp.bfloat16), jnp.asarray(vp, jnp.bfloat16)
    sm = 1.0 / np.sqrt(q.shape[-1])
    out = paged_decode_attention(qb, kb, vb, tabs, t, sm_scale=sm,
                                 interpret=True)
    assert out.dtype == jnp.bfloat16
    ref = _paged_attention_reference(qb, kb, vb, jnp.asarray(tabs), t, sm)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               atol=2e-2, rtol=2e-2)


def test_kernel_composes_with_jit():
    """The serving engine calls the kernel from inside jitted step
    programs — the pallas_call must trace cleanly under jit with the
    positions/table as traced operands."""
    q, kp, vp, tabs, t, _ = _setup([2, 9, 17, 30])
    sm = 1.0 / np.sqrt(q.shape[-1])

    @jax.jit
    def step(q, kp, vp, tabs, t):
        return paged_decode_attention(q, kp, vp, tabs, t, sm_scale=sm,
                                      interpret=True)

    out = np.asarray(step(q, kp, vp, tabs, t), np.float32)
    _, ref = _both(q, kp, vp, tabs, t)
    np.testing.assert_allclose(out, ref, atol=2e-6, rtol=1e-5)


#: the step kernel's tile of slots: heads of 128 fill the lanes, so the
#: kernel copies a float pool's page rows itself and a grid step takes
#: up to ``TILE_SLOTS`` slots; 32 pages of 16 are TWO blocks
TILE = dict(n_kv=2, rep=2, dh=128, ps=16, n_tables=32, n_pages=257)


@pytest.mark.parametrize("positions,window,tile,geom", [
    # position 0 beside the table's last: slots of one tile with 1 and
    # 2 live blocks, and two slots with no key left in block 1
    ([0, 511, 17, 300], None, 4, TILE),
    # blocks of 1 MB (8 kv heads): two tiles of 2, the first with no key
    # past block 0, so its second tile-step is dead
    ([5, 250, 256, 511], None, 2, TWO_BLOCKS),
    ([300], None, 1, TILE),
    # a window of 512 over 64 pages whose first key lies in blocks 0,
    # 2, 0 and 1: whole pages at its edges, ~1.02 keys fetched a live key
    ([600, 1023, 515, 777], 512, 4, dict(TILE, n_tables=64)),
], ids=["g4_first_and_last", "g2_dead_tile_step", "g1", "g4_window"])
def test_tiles_of_slots_match_reference(monkeypatch, positions, window,
                                        tile, geom):
    """A grid step takes a TILE of slots (from the call's shapes), each
    at its own position, window and table row: against the page-gather
    oracle, and equal to the same call one slot a grid step — a slot's
    arithmetic is the same whatever its tile. Keys fetched: whole pages
    from the window's first key to the query's own, slot by slot."""
    q, kp, vp, tabs, t, _ = _setup(positions, **geom)
    assert _decode_grid(q, geom["n_kv"], geom["ps"], geom["n_tables"],
                        kp.dtype, window, None)[2] == tile
    sm = 1.0 / np.sqrt(q.shape[-1])

    def call():
        out = paged_decode_attention(q, kp, vp, tabs, t, sm_scale=sm,
                                     interpret=True, window=window)
        return out if window is not None else (out, None)

    out, fetched = call()
    ref = _paged_attention_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tabs), t, sm, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-4)
    if window is None and tile == 4:
        monkeypatch.setattr(pa, "_slots_per_tile", lambda *a: 1)
        np.testing.assert_array_equal(np.asarray(out),
                                      np.asarray(call()[0]))
    if window is not None:
        ps = geom["ps"]
        lo = np.maximum(t - window + 1, 0)
        want = (t // ps - lo // ps + 1) * ps
        assert np.asarray(fetched).tolist() == want.tolist()
        assert 1.0 <= want.sum() / np.minimum(t + 1, window).sum() <= 1.03


def _pallas_grids(jaxpr):
    """The grid of every ``pallas_call`` in ``jaxpr``, nested ones (the
    interpreter's replicated region on a many-device backend) too."""
    grids = []
    for e in jaxpr.eqns:
        if e.primitive.name == "pallas_call":
            grids.append(e.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(e.params):
            grids += _pallas_grids(sub)
    return grids


#: (slots, q heads, kv heads, page, table, window, pool) of the step
#: kernel's served calls, and the grid each is made with; an int8 pool
#: keeps one slot a grid step
STEP_GRIDS = {
    "window_moe_sliding": ((32, 32, 4, 32, 224, 1024, jnp.bfloat16),
                           (4, 1, 5)),
    "window_moe_full": ((32, 32, 4, 32, 224, None, jnp.bfloat16),
                        (4, 1, 28)),
    "hybrid_ssm": ((64, 32, 2, 32, 128, None, jnp.bfloat16), (8, 1, 16)),
    "dense": ((32, 32, 8, 16, 32, None, jnp.bfloat16), (8, 1, 2)),
    "dense_narrow": ((32, 32, 8, 16, 1, None, jnp.bfloat16), (4, 1, 1)),
    "dense_int8": ((32, 32, 8, 16, 32, None, jnp.int8), (32, 1, 2)),
}


@pytest.mark.parametrize("call", list(STEP_GRIDS))
def test_step_grid_steps_of_the_served_calls(call):
    """What a caller counts its step work by is the grid the wrapper
    builds: slot tiles (8 slots whose 8-page blocks fit VMEM, 4 of the
    dense cell's 16-page blocks) x kv-head tiles x blocks walked — read
    here off the traced call itself."""
    (b, nh, nkv, page, n_tables, window, dtype), want = STEP_GRIDS[call]
    q = jax.ShapeDtypeStruct((b, nh, 128), jnp.bfloat16)
    pool = jax.ShapeDtypeStruct((8, page, nkv, 128), dtype)
    scales = {}
    if dtype == jnp.int8:
        scales = dict.fromkeys(("k_scale", "v_scale"), jax.ShapeDtypeStruct(
            (8, page, nkv), jnp.float32))
    jaxpr = jax.make_jaxpr(lambda q, k, v, tabs, t, **sc: (
        paged_decode_attention(q, k, v, tabs, t, sm_scale=0.1,
                               window=window, interpret=True, **sc)))(
        q, pool, pool, jax.ShapeDtypeStruct((b, n_tables), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32), **scales)
    assert _pallas_grids(jaxpr.jaxpr) == [want]
    assert _decode_grid(q, nkv, page, n_tables, dtype, window,
                        None)[3] == want
    if dtype == jnp.bfloat16:  # what the decoder counts, pools as q
        assert paged_decode_grid_steps(q, nkv, page, n_tables,
                                       window) == math.prod(want)


def test_resolve_paged_kernel_dispatch_rule():
    """None = auto (kernel only on TPU — CPU tier-1 must resolve to
    the gather fallback); explicit booleans always win."""
    auto = resolve_paged_kernel(None)
    assert auto == (jax.default_backend() == "tpu")
    assert resolve_paged_kernel(True) is True
    assert resolve_paged_kernel(False) is False


# ---------------------------------------------------------------------
# multi-token WINDOW kernel (ISSUE 19): chunked prefill and speculative
# verify attend (s >= 1) query windows straight off the pool, causal
# INSIDE the window
# ---------------------------------------------------------------------


def _wsetup(positions, n_kv=2, rep=2, dh=8, ps=8, n_tables=4,
            n_pages=12, seed=0, int8=False, scale=1.0,
            dtype=np.float32):
    """Window twin of ``_setup``: ``positions`` is (b, s) with
    NONDECREASING rows (the engine's window invariant). Live pages
    cover each row's maximum position; scratch page 0 carries loud
    garbage."""
    t = np.asarray(positions, np.int32)
    b, s = t.shape
    rng = np.random.default_rng(seed)
    heads = n_kv * rep
    q = (rng.normal(size=(b, s, heads, dh)) * scale).astype(dtype)
    if int8:
        kp = rng.integers(-127, 128,
                          size=(n_pages, ps, n_kv, dh)).astype(np.int8)
        vp = rng.integers(-127, 128,
                          size=(n_pages, ps, n_kv, dh)).astype(np.int8)
        ks = rng.uniform(1e-3, 0.1,
                         size=(n_pages, ps, n_kv)).astype(np.float32)
        vs = rng.uniform(1e-3, 0.1,
                         size=(n_pages, ps, n_kv)).astype(np.float32)
        scales = (ks, vs)
    else:
        kp = (rng.normal(size=(n_pages, ps, n_kv, dh))
              * scale).astype(dtype)
        vp = (rng.normal(size=(n_pages, ps, n_kv, dh))
              * scale).astype(dtype)
        kp[0], vp[0] = 1e3, -1e3  # scratch garbage: leaks are loud
        scales = None
    tabs = np.zeros((b, n_tables), np.int32)
    free = list(rng.permutation(np.arange(1, n_pages)))
    for i in range(b):
        for pg in range(int(t[i].max()) // ps + 1):
            tabs[i, pg] = free.pop()
    return q, kp, vp, tabs, t, scales


def _wboth(q, kp, vp, tabs, t, scales=None, window=None, **kw):
    sm = 1.0 / np.sqrt(q.shape[-1])
    sk, sv = scales if scales else (None, None)
    out = paged_window_attention(q, kp, vp, tabs, t, sm_scale=sm,
                                 k_scale=sk, v_scale=sv,
                                 interpret=True, window=window, **kw)
    ref = _paged_window_reference(
        jnp.asarray(q), jnp.asarray(kp), jnp.asarray(vp),
        jnp.asarray(tabs), t, sm,
        None if sk is None else jnp.asarray(sk),
        None if sv is None else jnp.asarray(sv), window=window)
    return np.asarray(out, np.float32), np.asarray(ref, np.float32)


@pytest.mark.parametrize("positions", [
    [[0, 1, 2, 3], [0, 1, 2, 3]],          # fresh prompts from zero
    [[3, 4, 5, 6], [1, 2, 3, 4]],          # partial first page
    [[5, 6, 7, 8], [13, 14, 15, 16]],      # window STRADDLES a page
                                           # boundary (7→8, 15→16)
    [[20, 21, 22, 23], [9, 9, 9, 9]],      # deep window + frozen row
                                           # (an idle verify lane)
    [[0, 1, 1, 1], [26, 27, 28, 28]],      # overhang rows repeating
                                           # the last real entry
])
def test_window_causal_mask_matches_reference(positions):
    """Per-ROW causality: window token i sees keys only up to its OWN
    position — including rows mid-page, rows at page boundaries, and
    frozen/padded rows."""
    q, kp, vp, tabs, t, _ = _wsetup(positions)
    out, ref = _wboth(q, kp, vp, tabs, t)
    np.testing.assert_allclose(out, ref, atol=2e-6, rtol=1e-5)


def test_window_scratch_garbage_never_leaks():
    """The in-window causal mask must keep every row clear of the
    scratch page's 1e3 garbage AND of later tokens' freshly-written
    keys: the kernel answer equals an oracle over a pool whose scratch
    page is ZEROED."""
    q, kp, vp, tabs, t, _ = _wsetup([[2, 3, 4, 5], [14, 15, 16, 17],
                                     [25, 26, 27, 28]])
    sm = 1.0 / np.sqrt(q.shape[-1])
    out = np.asarray(paged_window_attention(
        q, kp, vp, tabs, t, sm_scale=sm, interpret=True), np.float32)
    kz, vz = kp.copy(), vp.copy()
    kz[0], vz[0] = 0.0, 0.0
    ref0 = np.asarray(_paged_window_reference(
        jnp.asarray(q), jnp.asarray(kz), jnp.asarray(vz),
        jnp.asarray(tabs), t, sm), np.float32)
    np.testing.assert_allclose(out, ref0, atol=2e-6, rtol=1e-5)


def test_window_partial_last_pages_and_live_width():
    """Rows whose last live page is partial, plus the live-width table
    slice: the answer must not depend on dead trailing columns."""
    pos = [[9, 10, 11, 12], [1, 2, 3, 4]]
    q, kp, vp, tabs, t, _ = _wsetup(pos, n_tables=8)
    full, ref = _wboth(q, kp, vp, tabs, t)
    np.testing.assert_allclose(full, ref, atol=2e-6, rtol=1e-5)
    narrow, _ = _wboth(q, kp, vp, tabs[:, :2], t)
    np.testing.assert_allclose(full, narrow, atol=2e-6, rtol=1e-5)


def test_window_lse_merge_across_magnitude_spread():
    """Cross-page LSE merge stability with a per-row mask in play:
    live pages scaled by 10^page move the running max on every merge
    step for every window row."""
    q, kp, vp, tabs, t, _ = _wsetup([[28, 29, 30, 31]] * 4, n_pages=20)
    for i in range(tabs.shape[0]):
        for pg in range(4):
            kp[tabs[i, pg]] *= 10.0 ** pg
    out, ref = _wboth(q, kp, vp, tabs, t)
    # keys span 3 decades; the merge reorders the reduction, so allow
    # a touch more roundoff than the unscaled cases
    np.testing.assert_allclose(out, ref, atol=5e-5, rtol=1e-3)


def test_window_gqa_ratios_block_h_and_block_q():
    """rep in {1, 2, 4} × head tiling × window tiling all reproduce
    the oracle; indivisible block_q fails as loudly as block_h."""
    pos = [[4, 5, 6, 7, 8, 9], [17, 18, 19, 20, 21, 22]]
    for n_kv, rep in ((4, 1), (2, 2), (1, 4)):
        q, kp, vp, tabs, t, _ = _wsetup(pos, n_kv=n_kv, rep=rep,
                                        seed=n_kv)
        out, ref = _wboth(q, kp, vp, tabs, t)
        np.testing.assert_allclose(out, ref, atol=2e-6, rtol=1e-5)
    q, kp, vp, tabs, t, _ = _wsetup(pos, n_kv=4, rep=2)
    for bq in (1, 2, 3, 6):
        out, ref = _wboth(q, kp, vp, tabs, t, block_h=2, block_q=bq)
        np.testing.assert_allclose(out, ref, atol=2e-6, rtol=1e-5)
    with pytest.raises(ValueError, match="block_q"):
        paged_window_attention(q, kp, vp, tabs, t, sm_scale=0.3,
                               block_q=4, interpret=True)
    with pytest.raises(ValueError, match="block_h"):
        paged_window_attention(q, kp, vp, tabs, t, sm_scale=0.3,
                               block_h=3, interpret=True)


def test_window_int8_scale_rows_dequant_in_kernel():
    """int8 pools + f32 absmax scale rows through the window kernel:
    fused dequant matches the dequantize-then-attend oracle."""
    q, kp, vp, tabs, t, scales = _wsetup([[3, 4, 5, 6], [13, 14, 15, 16],
                                          [27, 28, 29, 30]], int8=True)
    out, ref = _wboth(q, kp, vp, tabs, t, scales=scales)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-4)


def test_window_s1_degenerate_agrees_with_step_kernel():
    """s == 1 through the window kernel is the SAME attention as the
    step kernel's, f32 and int8 alike, in another summation order: the
    window kernel folds one page at a time and one head at a time, the
    step kernel a block of pages over every head of its tile at once.
    Both match the page-gather oracle, and each other, to f32 roundoff
    — nothing in the engine relies on more: its hot loop runs the step
    kernel alone and the window kernel serves everything else."""
    for int8 in (False, True):
        q, kp, vp, tabs, t, scales = _setup([2, 9, 17, 30], int8=int8,
                                            seed=int(int8))
        step, ref = _both(q, kp, vp, tabs, t, scales=scales)
        win, _ = _wboth(q[:, None], kp, vp, tabs, t[:, None],
                        scales=scales)
        for got in (step, win[:, 0]):
            np.testing.assert_allclose(got, ref, atol=1e-5, rtol=1e-4,
                                       err_msg=f"int8={int8}")
        np.testing.assert_allclose(step, win[:, 0], atol=1e-5, rtol=1e-4,
                                   err_msg=f"int8={int8}")


#: what the kernel may differ from the f32 oracle by, a pool dtype: a
#: float32 pool as every case above (another summation order); an int8
#: pool dequantised in f32 as its own case above; a bfloat16 pool
#: against the oracle on the SAME bf16-rounded pool and queries — the
#: operands are those values widened, and the result is returned in
#: the queries' bf16 (8 bits of mantissa)
POOL_TOLERANCE = {"float32": dict(atol=2e-6, rtol=1e-5),
                  "int8": dict(atol=1e-5, rtol=1e-4),
                  "bfloat16": dict(atol=2e-2, rtol=2e-2)}


@pytest.mark.parametrize("rep", [4, 8, 16])
@pytest.mark.parametrize("window", [None, 24], ids=["full", "window24"])
@pytest.mark.parametrize("pool", ["float32", "bfloat16", "int8"])
@pytest.mark.parametrize("block_q", [16, 32, 64])
def test_window_tiles_of_a_chunk_match_reference(block_q, pool, window,
                                                 rep):
    """A query tile of up to a prefill chunk (64 tokens x ``rep`` rows a
    kv head) on operands in the pool's own dtype: a fresh prompt, a
    chunk deep in a context that straddles pages, and a chunk whose
    overhang repeats its last real entry — against the page-gather
    oracle, with and without a window shorter than the context."""
    s = 64
    pos = np.stack([np.arange(s), 37 + np.arange(s),
                    np.minimum(70 + np.arange(s), 70 + 40)])
    q, kp, vp, tabs, t, scales = _wsetup(
        pos, n_kv=2, rep=rep, ps=8, n_tables=16, n_pages=41,
        seed=block_q + rep, int8=pool == "int8",
        dtype=jnp.bfloat16 if pool == "bfloat16" else np.float32)
    out, ref = _wboth(q, kp, vp, tabs, t, scales=scales, window=window,
                      block_q=block_q)
    np.testing.assert_allclose(out, ref, **POOL_TOLERANCE[pool])


@pytest.mark.parametrize("s,want,cut", [
    (4, 4, 4), (6, 6, 6), (16, 16, 16), (32, 32, 32), (64, 64, 32),
    (128, 64, 32), (96, 48, 48)])
def test_default_window_tile_is_a_chunk_inside_the_vmem_budget(s, want,
                                                               cut):
    """The default tile divides the window, is at most 64 tokens and
    fits the VMEM budget at every shape the cells call with — and is
    cut to fit where heads are many and wide; a window of 16 tokens or
    fewer (speculative verify) keeps the tile it had, whatever the
    heads."""
    for rep, block_h in ((8, 4), (16, 2), (4, 8), (1, 32)):
        tile = _default_block_q(s, rep, block_h, 128, 2)
        assert tile == want and s % tile == 0
        assert tile <= 16 or _tile_bytes(
            tile, rep, block_h, 128, 2) <= TILE_BYTES
    # 64 query heads over 16 kv heads of 128: 64 tokens do not fit
    assert _default_block_q(s, 4, 16, 128, 2) == cut
    assert cut <= 16 or _tile_bytes(cut, 4, 16, 128, 2) <= TILE_BYTES
    assert _tile_bytes(64, 4, 16, 128, 2) > TILE_BYTES
    # and the 16 tokens a verify window had stand whatever the heads
    assert _default_block_q(s, 8, 64, 256, 4) == min(want, 16)


@pytest.mark.parametrize("shape,n_kv,page,n_tables,window,want", [
    ((8, 64, 32, 128), 4, 32, 224, None, 8 * 224),   # a full layer,
    ((8, 64, 32, 128), 4, 32, 224, 1024, 8 * 35),    # a sliding layer
    ((8, 128, 32, 128), 2, 32, 128, None, 8 * 2 * 128),
    ((8, 32, 32, 128), 8, 16, 32, None, 8 * 32),
    ((32, 4, 32, 128), 8, 16, 32, None, 32 * 32),    # a verify window
], ids=["window_moe_full", "window_moe_sliding", "hybrid_ssm", "dense",
        "verify"])
def test_window_grid_steps_of_the_served_calls(shape, n_kv, page,
                                               n_tables, window, want):
    """What a caller counts its prefill work by is the grid the call is
    made with: rows x query tiles x pages walked (every kv head in one
    tile). The 28-layer cell's call of 8 x 64 tokens: 7 x 1,792 + 21 x
    280 = 18,424 grid steps (73,024 at 16 queries a tile)."""
    q = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    assert paged_window_grid_steps(q, n_kv, page, n_tables,
                                   window) == want


def test_window_composes_with_jit():
    """Prefill/verify programs call the window kernel from inside jit
    with traced positions/tables — must trace cleanly."""
    q, kp, vp, tabs, t, _ = _wsetup([[2, 3, 4, 5], [14, 15, 16, 17]])
    sm = 1.0 / np.sqrt(q.shape[-1])

    @jax.jit
    def win(q, kp, vp, tabs, t):
        return paged_window_attention(q, kp, vp, tabs, t, sm_scale=sm,
                                      interpret=True)

    out = np.asarray(win(q, kp, vp, tabs, t), np.float32)
    _, ref = _wboth(q, kp, vp, tabs, t)
    np.testing.assert_allclose(out, ref, atol=2e-6, rtol=1e-5)


def test_resolve_paged_window_kernel_rule(monkeypatch):
    """Windows follow the same tri-state flag as the step kernel, with
    the RAFIKI_PAGED_KERNEL_WINDOWS escape hatch on top: unset/enabled
    means windows go wherever the step kernel goes; 0/false/off forces
    step-only mode."""
    monkeypatch.delenv("RAFIKI_PAGED_KERNEL_WINDOWS", raising=False)
    assert resolve_paged_window_kernel(True) is True
    assert resolve_paged_window_kernel(False) is False
    assert resolve_paged_window_kernel(None) == resolve_paged_kernel(None)
    for off in ("0", "false", "off"):
        monkeypatch.setenv("RAFIKI_PAGED_KERNEL_WINDOWS", off)
        assert resolve_paged_window_kernel(True) is False
    monkeypatch.setenv("RAFIKI_PAGED_KERNEL_WINDOWS", "1")
    assert resolve_paged_window_kernel(True) is True


# ---------------------------------------------------------------------
# the head tiles the CHIP runs: Mosaic takes a kv-head tile only when
# it is the whole kv axis (the default) or a multiple of 8 — never the
# per-head tile the first tests here were written against
# (tests/test_tpu_compile.py asks the compiler itself)
# ---------------------------------------------------------------------


@pytest.mark.parametrize("int8", [False, True], ids=["f32", "int8"])
@pytest.mark.parametrize("kernel", ["step", "window"])
def test_head_tiles_agree_at_serving_shape(kernel, int8):
    """GQA 4:1 over 16 kv heads at page 16 (the served page size): the
    default tile (whole kv axis), the smallest legal narrower tile (8)
    and the per-head tile all match the page-gather oracle — heads
    never mix, and the narrow-tile int8 path picks its scale columns
    with an exact lane mask. The window kernel computes a head at a
    time whatever the tile, so its outputs are BIT-identical across
    tiles; the step kernel puts a tile's heads into one product (the
    other heads' columns masked to exactly 0), so the tile sets the
    summation order and its outputs agree to f32 roundoff. The window
    case runs s=6 over block_q=3, so the per-row position operand spans
    two query tiles."""
    n_kv, geom = 16, dict(n_kv=16, rep=4, dh=8, ps=16, n_tables=3,
                          n_pages=16, int8=int8, seed=3)
    if kernel == "step":
        q, kp, vp, tabs, t, scales = _setup([5, 15, 16, 40], **geom)
        run = _both
    else:
        q, kp, vp, tabs, t, scales = _wsetup(
            [[11, 12, 13, 14, 15, 16], [30, 31, 32, 33, 33, 33]], **geom)
        run = functools.partial(_wboth, block_q=3)
    out, ref = run(q, kp, vp, tabs, t, scales=scales)
    np.testing.assert_allclose(out, ref, atol=1e-5, rtol=1e-4)
    for block_h in (n_kv, 8, 1):
        tiled, _ = run(q, kp, vp, tabs, t, scales=scales, block_h=block_h)
        if kernel == "window" or block_h == n_kv:
            assert np.array_equal(out, tiled), f"block_h={block_h}"
        else:
            np.testing.assert_allclose(tiled, out, atol=1e-5, rtol=1e-4,
                                       err_msg=f"block_h={block_h}")
            np.testing.assert_allclose(tiled, ref, atol=1e-5, rtol=1e-4,
                                       err_msg=f"block_h={block_h}")
