"""The main path's kernels compile for the v5e — no chip attached.

The TPU compiler is installed beside the CPU backend and compiles for a
chip that is *described*, not present. The interpret-mode suites prove
the kernels' math; only Mosaic can say whether it takes their block
shapes and memory spaces, and it refused both paged kernels for as long
as nothing here asked it. Each test lowers one kernel at a real width
through its DEFAULT arguments (what ``_DecoderAttention`` and the ViT
template call) with ``interpret=False`` and checks the compiled program
carries the kernel.

The topology is described inside a module-scoped fixture and nowhere
else: only one process may load the TPU library, xdist imports this
file in every worker, and only the worker that runs these tests may
make the call. Everything compiles in the test's own process, and all
of it lives in this one file, for the same reason.
"""

import os

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from rafiki_tpu.ops.attention import flash_attention
from rafiki_tpu.ops.paged_attention import (paged_decode_attention,
                                            paged_window_attention)
from rafiki_tpu.ops.patch_embed import patch_embed

PAGE = 16
#: (q heads, kv heads, head dim): Llama-3-8B's attention, an MHA model
#: at head size 128, and the top of ``LlamaLoRA``'s knob space
HEADS = [(32, 8, 128), (16, 16, 128), (8, 2, 64)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described-device executable can be written to the persistent
    # cache but never read back without a chip: keep the cache off
    # around these compiles, whatever the session had
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield desc
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    one_chip = SingleDeviceSharding(topo.devices[0])

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return shape


def _compiled_text(fn, *shapes) -> str:
    return jax.jit(fn).lower(*shapes).compile().as_text()


def _pool(spec, n_kv, dh, int8):
    """Pool + scale shapes (scales empty for a bf16 pool)."""
    n_pages = 64
    kv = spec((n_pages, PAGE, n_kv, dh),
              jnp.int8 if int8 else jnp.bfloat16)
    scales = ((spec((n_pages, PAGE, n_kv), jnp.float32),) * 2
              if int8 else ())
    return kv, scales


#: (heads, slots, table width) of the step kernel's calls: a small
#: engine at half a block of pages in every head geometry, then the
#: dense benchmark cell's 32 slots at its narrowest table (one block, a
#: single page wide) and at its widest (several blocks of pages), and
#: 64 kv heads of 128, whose block VMEM bounds below 256 key positions
STEP_CALLS = [(h, 4, 8) for h in HEADS] + [(HEADS[0], 32, 1),
                                           (HEADS[0], 32, 32),
                                           ((64, 64, 128), 4, 32)]


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("heads,b,n_tables", STEP_CALLS)
def test_paged_decode_kernel_compiles(spec, heads, b, n_tables, int8):
    n_heads, n_kv, dh = heads
    kv, scales = _pool(spec, n_kv, dh, int8)

    def step(q, k, v, tabs, t, *sc):
        return paged_decode_attention(
            q, k, v, tabs, t, sm_scale=dh ** -0.5,
            k_scale=sc[0] if sc else None,
            v_scale=sc[1] if sc else None, interpret=False)

    text = _compiled_text(
        step, spec((b, n_heads, dh), jnp.bfloat16), kv, kv,
        spec((b, n_tables), jnp.int32), spec((b,), jnp.int32), *scales)
    assert "tpu_custom_call" in text


#: the step kernel's calls of the cells that serve it, bf16 pools:
#: (slots, q heads, kv heads, page, table, window, the kernel's name)
STEP_CELL_CALLS = {
    "window_moe_sliding": (32, 32, 4, 32, 224, 1024, "window_attn_step"),
    "window_moe_full": (32, 32, 4, 32, 224, None, "paged_attn_step"),
    "hybrid_ssm": (64, 32, 2, 32, 128, None, "paged_attn_step"),
    "dense": (32, 32, 8, 16, 32, None, "paged_attn_step"),
}


@pytest.mark.parametrize("call", list(STEP_CELL_CALLS))
def test_step_kernel_compiles_at_the_cells_calls(spec, call):
    """A TILE of slots a grid step at the served shapes — 8 slots of
    8-page blocks on the 28-layer and the state-space cells, 4 of the
    dense cell's 16-page blocks — each slot's K and V block copied as
    its page rows and double-buffered: Mosaic takes the tile's VMEM."""
    b, n_heads, n_kv, page, n_tables, window, name = STEP_CELL_CALLS[call]
    kv = spec((1 + b * n_tables, page, n_kv, 128), jnp.bfloat16)

    def step(q, k, v, tabs, t):
        return paged_decode_attention(q, k, v, tabs, t, sm_scale=128 ** -0.5,
                                      window=window, interpret=False)

    text = _compiled_text(
        step, spec((b, n_heads, 128), jnp.bfloat16), kv, kv,
        spec((b, n_tables), jnp.int32), spec((b,), jnp.int32))
    assert name in text and "tpu_custom_call" in text


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
@pytest.mark.parametrize("n_heads,n_kv,dh", HEADS)
def test_paged_window_kernel_compiles(spec, n_heads, n_kv, dh, int8):
    """Chunked prefill (s=64) and a speculative-verify window (s=4)."""
    b, n_tables = 4, 8
    kv, scales = _pool(spec, n_kv, dh, int8)

    def window(q, k, v, tabs, t, *sc):
        return paged_window_attention(
            q, k, v, tabs, t, sm_scale=dh ** -0.5,
            k_scale=sc[0] if sc else None,
            v_scale=sc[1] if sc else None, interpret=False)

    for s in (64, 4):
        text = _compiled_text(
            window, spec((b, s, n_heads, dh), jnp.bfloat16), kv, kv,
            spec((b, n_tables), jnp.int32), spec((b, s), jnp.int32),
            *scales)
        assert "tpu_custom_call" in text, f"s={s}"


@pytest.mark.parametrize("int8", [False, True], ids=["bf16", "int8"])
def test_paged_kernels_compile_at_a_narrow_head_tile(spec, int8):
    """An explicit tile of 8 of 16 kv heads — the one narrower tile the
    compiler takes. On an int8 pool the scale column of each head is
    then picked with a lane mask (``_head_scale``): lanes cannot be
    indexed by a program id."""
    b, n_tables, n_heads, n_kv, dh = 4, 8, 16, 16, 128
    kv, scales = _pool(spec, n_kv, dh, int8)

    def both(q, qw, k, v, tabs, t, tw, *sc):
        kw = dict(sm_scale=dh ** -0.5, block_h=8, interpret=False,
                  k_scale=sc[0] if sc else None,
                  v_scale=sc[1] if sc else None)
        return (paged_decode_attention(q, k, v, tabs, t, **kw),
                paged_window_attention(qw, k, v, tabs, tw, **kw))

    text = _compiled_text(
        both, spec((b, n_heads, dh), jnp.bfloat16),
        spec((b, 4, n_heads, dh), jnp.bfloat16), kv, kv,
        spec((b, n_tables), jnp.int32), spec((b,), jnp.int32),
        spec((b, 4), jnp.int32), *scales)
    assert text.count("tpu_custom_call") >= 2


@pytest.mark.parametrize("shape,causal", [
    ((64, 12, 197, 64), False),    # ViT-B/16, batch 64
    ((4, 16, 2048, 128), True),    # a causal LM at head size 128
], ids=["vit_b16", "lm_2048_causal"])
def test_flash_attention_fwd_bwd_compiles(spec, shape, causal):
    def loss(q, k, v):
        return flash_attention(q, k, v, causal=causal,
                               interpret=False).astype(jnp.float32).sum()

    x = spec(shape, jnp.bfloat16)
    text = _compiled_text(jax.grad(loss, argnums=(0, 1, 2)), x, x, x)
    # forward, dQ and dK/dV are three separate kernels
    assert text.count("tpu_custom_call") >= 3


def test_patch_embed_fwd_bwd_compiles(spec):
    """ViT-B/16's patch embedding: 224x224x3 → 196 patches of 768."""
    def loss(images, w, b):
        return patch_embed(images, w, b, 16,
                           False).astype(jnp.float32).sum()

    # value AND grad: the loss is linear in the kernel's output, so a
    # bare grad would leave the forward kernel dead code
    text = _compiled_text(
        jax.value_and_grad(loss, argnums=(1, 2)),
        spec((64, 224, 224, 3), jnp.bfloat16),
        spec((16 * 16 * 3, 768), jnp.bfloat16), spec((768,), jnp.bfloat16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("page,n_tables", [
    (32, 64), (32, 1), (16, 128), (16, 4), (32, 8), (32, 16), (32, 32)])
def test_latent_step_kernel_compiles(spec, page, n_tables):
    """The latent pool's single-token step at the widths the benchmark
    serves: 64 slots, 32 heads, 256 latent values a row and the 64-wide
    rotary keys two positions a row (128) — at every table width the
    cell's engine passes and the narrowest. Pages of 32 leave both leaves in HBM (``pl.ANY``) for the
    kernel's own copies; pages of 16 halve to 8 rows, under a bf16
    sublane tile, and take the BlockSpec pipeline. Either way ONE custom
    call named ``latent_attn_step``, and no copy of a pool leaf."""
    from rafiki_tpu.ops.latent_attention import (PIPELINE_ROWS_PER_STEP,
                                                 copies_own_pages,
                                                 latent_decode_attention)

    def step(q_lat, q_rope, latents, keys, tabs, t):
        return latent_decode_attention(q_lat, q_rope, latents, keys, tabs,
                                       t, interpret=False)

    n_pages = 1 + 64 * 2048 // page
    latents = spec((n_pages, page, 256), jnp.bfloat16)
    keys = spec((n_pages, page // 2, 128), jnp.bfloat16)
    assert copies_own_pages(latents, keys) == (page == 32)
    text = _compiled_text(
        step, spec((64, 32, 256), jnp.bfloat16),
        spec((64, 32, 64), jnp.bfloat16), latents, keys,
        spec((64, n_tables), jnp.int32), spec((64,), jnp.int32))
    assert text.count("tpu_custom_call") == 1
    assert "latent_attn_step" in text
    # a leaf is an operand ONCE where it stays in HBM (the whole of it
    # would not fit VMEM) and once a page of the block on the pipeline
    operands = text.split("operand_layout_constraints={")[1].split(
        "custom_call_target")[0].split("frontend_attributes")[0]
    times = 1 if page == 32 else min(n_tables,
                                         PIPELINE_ROWS_PER_STEP // page)
    assert operands.count(f"bf16[{n_pages},2,{page // 2},256]") == times
    assert operands.count(f"bf16[{n_pages},{page // 2},128]") == times
    assert not [line for line in text.splitlines()
                if " copy(" in line and f"[{n_pages}," in line]


@pytest.mark.parametrize("rows", [64, 256, 4096, 16384], ids=[
    "decode_step", "prefill_call", "largest_tile", "many_rows"])
def test_grouped_experts_compile_to_grouped_kernels(spec, rows):
    """The serving expert layer's three products at the benchmark's
    sizes (``rows`` x 4 sorted assignments over 32 held experts of 4096
    x 2048, bf16) lower to the repo's narrow-tile Pallas kernel, gate
    and up in one call and down in another: Mosaic takes its blocks and
    its VMEM at the served widths, at a decode step's and a prefill
    call's row tile (64) and at the largest (256), however many rows an
    expert gets — and no product is XLA's ``ragged-dot`` with its
    256-row tile, nor a dense one over every expert."""
    from rafiki_tpu.ops.moe import grouped_experts

    def layer(x, gates, experts, wg, wu, wd):
        return grouped_experts(x, gates, experts, wg, wu, wd, first=0,
                               interpret=False)

    text = _compiled_text(
        layer, spec((rows, 4096), jnp.bfloat16),
        spec((rows, 4), jnp.float32), spec((rows, 4), jnp.int32),
        spec((32, 4096, 2048), jnp.bfloat16),
        spec((32, 4096, 2048), jnp.bfloat16),
        spec((32, 2048, 4096), jnp.bfloat16))
    assert text.count("moe_grouped_matmul") >= 2
    assert "tpu_custom_call" in text and "ragged-dot" not in text


# ---- the pattern-driven state-space / latent-expert decoder's kernels at
# ---- the widths the benchmark serves (64 slots, pages of 32)
def test_ssm_state_step_kernel_compiles_in_place(spec):
    """The single-token state step at 64 rows x 128 heads x 64 x 128 in
    float32: Mosaic takes a whole slot's state (4 MB) as one tile going
    in and one coming out, and the table is updated IN PLACE (the
    program's output aliases its 273 MB operand: no second table)."""
    from rafiki_tpu.ops.ssm import ssm_state_step

    def step(state, slots, advance, fresh, x, dt, a, b, c, d):
        return ssm_state_step(state, slots, advance, fresh, x, dt, a, b, c,
                              d, interpret=False)

    compiled = jax.jit(step, donate_argnums=(0,)).lower(
        spec((65, 128, 64, 128), jnp.float32), spec((64,), jnp.int32),
        spec((64,), jnp.bool_), spec((64,), jnp.bool_),
        spec((64, 128, 64), jnp.bfloat16), spec((64, 128), jnp.float32),
        spec((128,), jnp.float32), spec((64, 8, 128), jnp.bfloat16),
        spec((64, 8, 128), jnp.bfloat16), spec((128,), jnp.float32)
    ).compile()
    text = compiled.as_text()
    assert "ssm_state_step" in text and "tpu_custom_call" in text
    table = 65 * 128 * 64 * 128 * 4
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= table
    assert memory.temp_size_in_bytes < table // 8


@pytest.mark.parametrize("rows", [64, 1024], ids=["decode_step",
                                                  "prefill_call"])
def test_latent_experts_compile_to_grouped_kernels(spec, rows):
    """Experts of TWO kernels with ``relu^2`` between, in a latent of
    1024: ``rows`` x 22 sorted assignments over 128 held experts of 1024
    x 2688 (column tiles of 896) and 2688 x 1024 (512), bf16 — up with
    its activation in one call of the narrow-tile kernel, down in
    another."""
    from rafiki_tpu.ops.moe import grouped_experts

    def layer(x, gates, experts, wu, wd):
        return grouped_experts(x, gates, experts, None, wu, wd, first=0,
                               interpret=False)

    text = _compiled_text(
        layer, spec((rows, 1024), jnp.bfloat16),
        spec((rows, 22), jnp.float32), spec((rows, 22), jnp.int32),
        spec((128, 1024, 2688), jnp.bfloat16),
        spec((128, 2688, 1024), jnp.bfloat16))
    assert text.count("moe_grouped_matmul") >= 2
    assert "tpu_custom_call" in text and "ragged-dot" not in text


@pytest.mark.parametrize("n_tables", [1, 128])
def test_paged_kernels_compile_at_two_kv_heads_and_pages_of_32(
        spec, n_tables):
    """32 query heads over 2 kv heads of 128, pages of 32, 64 slots: the
    step kernel, and the window kernel at a prefill call's 8 rows of
    128 tokens, at the narrowest and the widest page table."""
    kv = spec((1 + 64 * 128, 32, 2, 128), jnp.bfloat16)

    def step(q, k, v, tabs, t):
        return paged_decode_attention(q, k, v, tabs, t, sm_scale=128 ** -0.5,
                                      interpret=False)

    def window(q, k, v, tabs, t):
        return paged_window_attention(q, k, v, tabs, t,
                                      sm_scale=128 ** -0.5, interpret=False)

    assert "tpu_custom_call" in _compiled_text(
        step, spec((64, 32, 128), jnp.bfloat16), kv, kv,
        spec((64, n_tables), jnp.int32), spec((64,), jnp.int32))
    assert "tpu_custom_call" in _compiled_text(
        window, spec((8, 128, 32, 128), jnp.bfloat16), kv, kv,
        spec((8, n_tables), jnp.int32), spec((8, 128), jnp.int32))


# ---- the windowed forms of the two paged kernels at the widths the
# ---- benchmark serves (32 slots, 4 kv heads of 128, pages of 32)
def test_windowed_kernels_compile_over_rings_of_a_window_and_a_call(spec):
    """Window 1,024 over rings of 1,568 positions (49 pages of 32) a
    slot, seen as one pool of 33 x 49 pages, table width 224: the step
    kernel with its own copies of the window's pages (5 blocks of 256
    keys, counting the pages it copies into a second, SMEM result), and
    the query-window kernel at a prefill call's 8 rows of 64 tokens (35
    pages a tile of the chunk's 64 queries) — each under its own name in the
    program."""
    from rafiki_tpu.ops.window_attention import window_ring_attention

    ring = spec((33, 1568, 4, 128), jnp.bfloat16)

    def attend(q, k, v, slots, t):
        return window_ring_attention(q, k, v, slots, t, 1024, 32, 7168,
                                     128 ** -0.5, kernel=True,
                                     interpret=False)

    step = _compiled_text(
        attend, spec((32, 1, 32, 128), jnp.bfloat16), ring, ring,
        spec((32,), jnp.int32), spec((32, 1), jnp.int32))
    assert "window_attn_step" in step and "tpu_custom_call" in step
    prefill = _compiled_text(
        attend, spec((8, 64, 32, 128), jnp.bfloat16), ring, ring,
        spec((8,), jnp.int32), spec((8, 64), jnp.int32))
    assert "window_attn_prefill" in prefill and "tpu_custom_call" in prefill


def test_paged_kernels_compile_at_four_kv_heads_and_a_table_of_224(spec):
    """The full layers' two kernels as the same cell calls them: 32
    query heads over 4 kv heads of 128, 32 slots, every slot able to
    reach 7,168 positions (224 pages of 32)."""
    kv = spec((1 + 32 * 224, 32, 4, 128), jnp.bfloat16)

    def step(q, k, v, tabs, t):
        return paged_decode_attention(q, k, v, tabs, t, sm_scale=128 ** -0.5,
                                      interpret=False)

    def window(q, k, v, tabs, t):
        return paged_window_attention(q, k, v, tabs, t,
                                      sm_scale=128 ** -0.5, interpret=False)

    assert "paged_attn_step" in _compiled_text(
        step, spec((32, 32, 128), jnp.bfloat16), kv, kv,
        spec((32, 224), jnp.int32), spec((32,), jnp.int32))
    assert "paged_attn_window" in _compiled_text(
        window, spec((8, 64, 32, 128), jnp.bfloat16), kv, kv,
        spec((8, 224), jnp.int32), spec((8, 64), jnp.int32))


#: the query-window kernel's calls of the three serving cells that run
#: it, bf16 pools: (rows, chunk, q heads, kv heads, page, table, window,
#: the kernel's name)
WINDOW_CALLS = {
    "window_moe_full": (8, 64, 32, 4, 32, 224, None, "paged_attn_window"),
    "window_moe_sliding": (8, 64, 32, 4, 32, 224, 1024,
                           "window_attn_prefill"),
    "hybrid_ssm": (8, 128, 32, 2, 32, 128, None, "paged_attn_window"),
    "dense": (8, 32, 32, 8, 16, 32, None, "paged_attn_window"),
}


@pytest.mark.parametrize("call", list(WINDOW_CALLS))
def test_query_window_kernel_compiles_at_the_cells_calls(spec, call):
    """A prefill call's rows at the DEFAULT query tile — a chunk's worth
    of tokens, 128 to 1,024 query rows a kv head — and products on the
    bf16 pool as stored: Mosaic takes the tile's blocks and its state
    fits the kernel's VMEM."""
    b, s, n_heads, n_kv, page, n_tables, window, name = WINDOW_CALLS[call]
    kv = spec((1 + 32 * 49, page, n_kv, 128), jnp.bfloat16)

    def prefill(q, k, v, tabs, t):
        return paged_window_attention(q, k, v, tabs, t, sm_scale=128 ** -0.5,
                                      window=window, interpret=False)

    text = _compiled_text(
        prefill, spec((b, s, n_heads, 128), jnp.bfloat16), kv, kv,
        spec((b, n_tables), jnp.int32), spec((b, s), jnp.int32))
    assert name in text and "tpu_custom_call" in text


def test_grouped_experts_compile_at_2304_by_896(spec):
    """16 held experts of 2304 x 896 (18 x 128 by 7 x 128), 8 choices a
    row of 64 over a router of 64: tile shapes the grouped kernel had
    not met."""
    from rafiki_tpu.ops.moe import grouped_experts

    def layer(x, gates, experts, wg, wu, wd):
        return grouped_experts(x, gates, experts, wg, wu, wd, first=0,
                               interpret=False)

    for rows in (32, 512):  # a decode step, a prefill call
        text = _compiled_text(
            layer, spec((rows, 2304), jnp.bfloat16),
            spec((rows, 8), jnp.float32), spec((rows, 8), jnp.int32),
            spec((16, 2304, 896), jnp.bfloat16),
            spec((16, 2304, 896), jnp.bfloat16),
            spec((16, 896, 2304), jnp.bfloat16))
        assert text.count("moe_grouped_matmul") >= 2
        assert "ragged-dot" not in text


#: the four serving configurations' model kinds, at the tiny widths of
#: their rehearsal configs: (config file, the benchmark driver that
#: builds the module)
SERVING_KINDS = {"dense": ("tiny-lm.json", "serve_engine"),
                 "expert": ("tiny-latent-moe.json", "serve_latent_moe"),
                 "state_space": ("tiny-hybrid-ssm.json", "serve_hybrid_ssm"),
                 "window": ("tiny-window-moe.json", "serve_window_moe")}


@pytest.mark.parametrize("kind", list(SERVING_KINDS))
def test_engine_programs_compile_on_one_packed_operand(spec, kind,
                                                        monkeypatch):
    """The decode step (greedy and sampling) and the prefill program of
    each serving model, handed their host inputs as ONE packed int32
    operand at the whole table's width: the programs' static slices
    lower for the chip beside its kernels, with no transfer to or from
    the host inside the program. The module is traced as on the chip
    (``default_backend`` says tpu), so it takes the Mosaic kernels."""
    import importlib

    from benchmark import harness
    from rafiki_tpu.serving import decode_engine as de

    fname, driver = SERVING_KINDS[kind]
    cfg = harness.load_json("configs", fname)
    module = importlib.import_module(
        f"benchmark.drivers.{driver}").build_module(cfg)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    eng = cfg["engine"]
    b, k, chunk = eng["max_slots"], eng["steps_per_sync"], eng["prefill_chunk"]
    rows = min(b, de.PREFILL_LANES)
    width = module.max_len // module.kv_page_size

    def specs(tree):
        return jax.tree_util.tree_map(lambda a: spec(a.shape, a.dtype), tree)

    f32 = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    params = specs(jax.eval_shape(lambda p: de._serving_tree(module, p),
                                  f32))
    cache = specs(jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((b, 1), jnp.int32),
        decode=True)["cache"]))
    n_counts = len(getattr(module, "device_counters", ()))
    counts = (spec((n_counts,), jnp.int32),) if n_counts else ()
    per_slot = 2 if getattr(module, "slot_state", ()) else 0
    texts = [de._make_step(module, b, k, sampling).lower(
        params, cache, spec((b, de.N_SLOT_COLUMNS + width), jnp.int32),
        spec((b, module.max_len), jnp.int32), *counts).compile().as_text()
        for sampling in (False, True)]
    texts.append(de._make_prefill(module, rows, chunk).lower(
        params, cache,
        spec((rows, 2 * chunk + 1 + per_slot + width), jnp.int32),
        *counts).compile().as_text())
    for text in texts:
        assert "tpu_custom_call" in text
        for host in ("send", "recv", "infeed", "outfeed", "callback"):
            assert host not in text, host
