"""The engine serves from a compute-dtype tree made ONCE, where the
weights enter (``serving_llama_params`` + ``DecodeEngine.params``).

Covers: the served mathematics is unchanged to the bit (logits on the
f32 tree == logits on the engine's tree; greedy tokens == a plain
``module.apply`` loop on the f32 tree); which leaves are cast and which
are the caller's own; f32-compute engines take the identity; the lowered
step program holds no f32 tensor of a kernel's shape; the setter's
``None`` / swap protocol and that the engine keeps no reference to the
f32 leaves it cast; the ``weight_bytes`` gauge.
"""

import gc
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from rafiki_tpu.models.llama_lora import (Llama, quantize_llama_params,
                                          serving_llama_params,
                                          stack_lora_adapters)
from rafiki_tpu.serving.decode_engine import DecodeEngine

# sizes chosen so that no activation shares a kernel's 2-D shape
DIMS = dict(vocab_size=80, max_len=32, hidden_dim=32, depth=2, n_heads=4,
            n_kv_heads=2, mlp_dim=96, lora_rank=4)
KERNEL_SHAPES = {(32, 32), (32, 16), (32, 96), (96, 32), (32, 80)}


def _random_tree(module, seed):
    """Every leaf random (``lora_b`` too: flax would init it to zeros),
    f32, numpy-backed."""
    abstract = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"])
    rng = np.random.default_rng(seed)
    return jax.tree_util.tree_map(
        lambda a: (0.2 * rng.standard_normal(a.shape)).astype(np.float32),
        abstract)


def _variant(name, seed=0):
    """(module, f32 tree as the caller would hand it, apply kwargs)."""
    if name == "plain":
        module = Llama(dtype=jnp.bfloat16, **DIMS)
        return module, _random_tree(module, seed), {}
    if name == "quantized":
        module = Llama(dtype=jnp.bfloat16, quantized=True, **DIMS)
        base = _random_tree(Llama(dtype=jnp.bfloat16, **DIMS), seed)
        return module, quantize_llama_params(base), {}
    assert name == "stacked"
    module = Llama(dtype=jnp.bfloat16, n_adapters=2, **DIMS)
    base = Llama(dtype=jnp.bfloat16, **DIMS)
    one, two = _random_tree(base, seed), _random_tree(base, seed + 1)
    two = jax.tree_util.tree_map_with_path(
        lambda kp, a, b: b if "lora_" in str(kp[-1]) else a, one, two)
    return (module, stack_lora_adapters([one, two]),
            {"adapter_ids": jnp.asarray([1, 0], jnp.int32)})


def _paths(tree):
    return {"/".join(str(k.key) for k in kp): leaf for kp, leaf in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


@pytest.mark.parametrize("name", ["plain", "quantized", "stacked"])
def test_logits_bit_equal_on_the_engines_tree(name):
    module, tree, kw = _variant(name)
    eng = DecodeEngine(module, tree, max_slots=2, max_len=32)
    ids = jnp.asarray([[1, 5, 9, 13, 2], [7, 3, 11, 1, 4]], jnp.int32)
    want = module.apply({"params": tree}, ids, **kw)
    got = module.apply({"params": eng.params}, ids, **kw)
    assert want.dtype == got.dtype == jnp.bfloat16
    np.testing.assert_array_equal(
        np.asarray(want.astype(jnp.float32)),
        np.asarray(got.astype(jnp.float32)))


def test_engine_greedy_tokens_equal_plain_apply_loop_on_f32_tree():
    module, tree, _ = _variant("plain")
    prompt, max_new = np.asarray([1, 5, 9, 13], np.int32), 8

    # the plain loop: one token a call through the decode cache, on the
    # caller's f32 tree — LoRADense casts at use, as it always has
    @jax.jit
    def one(params, cache, tok, pos):
        return module.apply({"params": params, "cache": cache}, tok,
                            positions=pos, decode=True, mutable=["cache"])

    cache = module.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 1), jnp.int32), decode=True)["cache"]
    tok, want = int(prompt[0]), []
    for pos in range(len(prompt) - 1 + max_new):
        logits, muts = one(tree, cache, jnp.asarray([[tok]], jnp.int32),
                           jnp.asarray([[pos]], jnp.int32))
        cache = muts["cache"]
        nxt = int(jnp.argmax(logits[0, -1].astype(jnp.float32)))
        if pos + 1 < len(prompt):
            tok = int(prompt[pos + 1])
        else:
            want.append(nxt)
            tok = nxt

    eng = DecodeEngine(module, tree, max_slots=1, max_len=32,
                       steps_per_sync=1, prefill_chunk=1)
    eng.submit("r", prompt, max_new)
    done = {}
    while eng.busy:
        eng.step()
        done.update(dict(eng.poll()))
    assert list(done["r"]) == want


def test_dtype_map_of_engine_params():
    module, tree, _ = _variant("plain")
    got = _paths(DecodeEngine(module, tree, max_slots=2, max_len=32).params)
    want = _paths(tree)
    assert set(got) == set(want) and "lm_head/kernel" in got
    for path, leaf in got.items():
        last = path.rsplit("/", 1)[-1]
        if last in ("kernel", "lora_a", "lora_b"):
            assert leaf.dtype == jnp.bfloat16, path
        else:  # RMSNorm scale, tok_embed: the caller's own f32 leaves
            assert last in ("scale", "embedding"), path
            assert leaf.dtype == np.float32, path
            assert leaf is want[path], path


def test_dtype_map_quantized_and_stacked():
    module, tree, _ = _variant("quantized")
    got = _paths(DecodeEngine(module, tree, max_slots=2, max_len=32).params)
    wq = "block_0/attn/wq/"
    assert got[wq + "qkernel"].dtype == jnp.int8
    assert got[wq + "qkernel"] is _paths(tree)[wq + "qkernel"]
    assert got[wq + "qscale"].dtype == jnp.bfloat16
    assert got[wq + "lora_a"].dtype == jnp.bfloat16
    assert got["lm_head/qscale"].dtype == jnp.bfloat16
    assert got["final_norm/scale"].dtype == jnp.float32

    module, tree, _ = _variant("stacked")
    got = _paths(DecodeEngine(module, tree, max_slots=2, max_len=32).params)
    down = "block_1/down/"
    assert got[down + "lora_a"].shape == (2, 96, 4)
    assert got[down + "lora_b"].shape == (2, 4, 32)
    assert got[down + "lora_a"].dtype == jnp.bfloat16
    assert got[down + "lora_b"].dtype == jnp.bfloat16
    assert got[down + "kernel"].dtype == jnp.bfloat16


def test_moe_leaves_pass_through():
    module = Llama(dtype=jnp.bfloat16, n_experts=2, **DIMS)
    tree = _random_tree(module, 0)
    got, want = _paths(serving_llama_params(tree, jnp.bfloat16)), _paths(tree)
    moe = [p for p in got if "/moe/" in p]
    assert moe and all(got[p] is want[p] for p in moe)
    assert got["block_0/attn/wq/kernel"].dtype == jnp.bfloat16


def test_f32_compute_takes_the_callers_very_leaves():
    module = Llama(**DIMS)  # dtype=None: f32 compute
    tree = _random_tree(module, 0)
    assert serving_llama_params(tree, None) is tree
    eng = DecodeEngine(module, tree, max_slots=2, max_len=32)
    assert eng.params is tree
    want = _paths(tree)
    assert all(leaf is want[p] for p, leaf in _paths(eng.params).items())


def test_lowered_step_takes_no_f32_kernel():
    module, tree, _ = _variant("plain")
    eng = DecodeEngine(module, tree, max_slots=3, max_len=32)
    # the per-slot inputs as the launch packs them, then the prompts
    operands = (eng._cache, eng._slot_host, eng._prompt_buf)
    step_fn = eng._step_fns[False]
    jaxpr = jax.make_jaxpr(step_fn)(eng.params, *operands)
    f32_in = {tuple(a.shape) for a in jaxpr.in_avals
              if a.dtype == jnp.float32}
    assert not f32_in & KERNEL_SHAPES
    # ... nor does any f32 tensor of a kernel's shape exist INSIDE the
    # program: nothing is left to convert
    text = step_fn.lower(eng.params, *operands).as_text()
    assert "tensor<32x96xbf16>" in text
    for shape in KERNEL_SHAPES:
        assert "tensor<%dx%dxf32>" % shape not in text, shape
    # the f32 tree lowers WITH them: the check can fail
    text32 = step_fn.lower(tree, *operands).as_text()
    assert "tensor<32x96xf32>" in text32


def test_setter_protocol_and_no_reference_to_f32_leaves():
    module = Llama(dtype=jnp.bfloat16, **DIMS)
    eng = DecodeEngine(module, None, max_slots=2, max_len=32)
    assert eng.params is None and eng.stats["weight_bytes"] == 0

    tree = _random_tree(module, 0)
    refs = [weakref.ref(tree["block_0"]["attn"]["wq"]["kernel"]),
            weakref.ref(tree["block_1"]["up"]["lora_b"]),
            weakref.ref(tree["lm_head"]["kernel"])]
    eng.params = tree
    eng.submit("a", np.asarray([1, 5, 9], np.int32), 4)
    while eng.busy:
        eng.step()
    first = dict(eng.poll())["a"]
    del tree
    gc.collect()
    assert all(r() is None for r in refs)  # the caller's drop frees them

    eng.params = None
    assert eng.params is None and eng.stats["weight_bytes"] == 0

    tree2 = _random_tree(module, 1)
    eng.params = tree2
    assert eng.params["lm_head"]["kernel"].dtype == jnp.bfloat16
    eng.submit("b", np.asarray([1, 5, 9], np.int32), 4)
    while eng.busy:
        eng.step()
    second = dict(eng.poll())["b"]
    assert len(first) == len(second) == 4
    ref = DecodeEngine(module, tree2, max_slots=2, max_len=32)
    ref.submit("b", np.asarray([1, 5, 9], np.int32), 4)
    while ref.busy:
        ref.step()
    assert dict(ref.poll())["b"] == second  # the swapped tree serves


def test_weight_bytes_gauge():
    module, tree, _ = _variant("plain")
    eng = DecodeEngine(module, tree, max_slots=2, max_len=32)
    held = sum(x.nbytes for x in jax.tree_util.tree_leaves(eng.params))
    full = sum(x.nbytes for x in jax.tree_util.tree_leaves(tree))
    assert eng.stats["weight_bytes"] == held
    # kernels and adapters at 2 bytes, embedding and norms at 4
    keep = 4 * (80 * 32 + 32 * (2 * DIMS["depth"] + 1))
    assert held == keep + (full - keep) // 2
    eng.reset_stats()
    assert eng.stats_snapshot()["weight_bytes"] == held

    draft = Llama(dtype=jnp.bfloat16, **{**DIMS, "depth": 1})
    d_tree = _random_tree(draft, 2)
    spec = DecodeEngine(module, tree, max_slots=2, max_len=32,
                        speculate_k=3, draft=(draft, d_tree))
    assert spec.draft_params["lm_head"]["kernel"].dtype == jnp.bfloat16
    assert spec.draft_params["final_norm"]["scale"] is \
        d_tree["final_norm"]["scale"]
    assert spec.stats["weight_bytes"] == held  # the target's alone


def test_admission_estimate_counts_the_engines_copy():
    from rafiki_tpu.models.llama_lora import LlamaLoRA

    knobs = dict(max_epochs=1, vocab_size=1 << 10, hidden_dim=64, depth=2,
                 n_heads=4, kv_ratio=2, lora_rank=4, max_len=32,
                 batch_size=8, learning_rate=1e-2)
    for bf16 in (True, False):
        m = LlamaLoRA(**{**knobs, "bf16": bf16})
        abstract = m.estimate_serving_device_bytes(max_slots=4)
        m._params = m._module().init(
            jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
        b = m.estimate_serving_device_bytes(max_slots=4)
        assert b == abstract  # loaded or not, the same budget
        core = m.make_decode_engine(max_slots=4, max_new_tokens=4).engine
        copied = sum(x.nbytes for x in jax.tree_util.tree_leaves(core.params)
                     if x.dtype == jnp.bfloat16)
        assert b["engine_params"] == copied
        assert (copied > 0) == bf16
        assert b["total"] == sum(v for k, v in b.items() if k != "total")
        assert m.estimate_serving_device_bytes(
            max_slots=0)["engine_params"] == 0
        core.close()
