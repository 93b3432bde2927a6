"""``reference/window_moe.py`` against the program's own module at the tiny
configuration, float32 on the CPU: the same weights give the same logits, a
lower precision does not; the reference imports nothing of the program; a
sliding layer's blocks of queries see the same keys whatever the block; and
the manifest's new names resolve."""

import ast
import os

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.drivers import serve_window_moe as driver
from benchmark.reference import window_moe as ref


def _tiny():
    cfg = harness.load_json("configs", "tiny-window-moe.json")
    module = driver.build_module(cfg)
    params = driver.make_weights(cfg, driver.abstract_params(module), 11)
    return cfg, module, params


def test_window_reference_matches_the_module():
    cfg, module, params = _tiny()
    assert module.layer_pattern == "WEWEWERE" * 2
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], 37)
    with jax.default_matmul_precision("highest"):
        want = module.apply({"params": params}, jnp.asarray(ids)[None])[0]
    got = ref.forward(params, jnp.asarray(ids), cfg)
    assert float(jnp.abs(got - want).max()) < 2e-5 * float(
        jnp.abs(want).max())
    low = ref.forward(params, jnp.asarray(ids), cfg, quant="int8")
    assert float(jnp.abs(low - want).max()) > 100 * float(
        jnp.abs(got - want).max())


def test_blocks_of_queries_see_what_one_block_sees(monkeypatch):
    """48 tokens in blocks of 16 against one block of 48: a sliding
    layer's slice of the padded keys is the window, wherever the block."""
    cfg, _, params = _tiny()
    ids = jnp.asarray(np.random.default_rng(1).integers(0, 256, 48))
    whole = ref.forward(params, ids, cfg)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 16)
    blocks = ref.forward(params, ids, cfg)
    assert float(jnp.abs(whole - blocks).max()) < 1e-5


def test_the_reference_imports_nothing_of_the_program():
    for name in ("window_moe", "latent_moe"):
        tree = ast.parse(open(os.path.join(
            harness.HERE, "reference", f"{name}.py")).read())
        for node in ast.walk(tree):
            names = [a.name for a in node.names] \
                if isinstance(node, ast.Import) else \
                [node.module or ""] if isinstance(node, ast.ImportFrom) \
                else []
            assert not any(n.startswith("rafiki_tpu") for n in names), name


def test_the_manifests_new_names_resolve():
    manifest = harness.load_manifest()
    (cell,) = [w for w in manifest["workloads"]
               if w["name"] == "mellum2-12b.code-assist-c32"]
    assert cell["chips"] == 1
    cfg = harness.load_json("configs", f"{cell['config']}.json")
    assert cfg["kind"] == "serve_window_moe"
    assert harness.load_reference(cfg) is ref
    assert harness.load_driver(cfg["kind"]) is driver.run
    traffic = harness.load_json("traffic", f"{cell['traffic']}.json")
    assert traffic["clients"] == cfg["engine"]["max_slots"] == 32
    assert traffic["prompt_tokens"]["high"] + \
        traffic["max_new_tokens"]["high"] == cfg["max_position_embeddings"]
    e2e = [m["name"] for m in harness.cell_metrics(
        manifest, cell["name"], "end_to_end")]
    assert e2e == ["serve_tokens_per_s", "setup_s"]
    names = {m["name"] for m in harness.cell_metrics(
        manifest, cell["name"], "per_layer")}
    assert {"serve_mfu.window_moe", "window_step_roofline",
            "paged_step_roofline.window_moe",
            "moe_grouped_matmul_roofline.window_moe",
            "window_keys_fetched_per_live_key", "window_kv_bytes_per_slot",
            "kv_pool_bytes_per_token", "serve_weight_gib"} <= names
    # every number of the source stands in the file; two are reduced
    for key, val in cfg["published"].items():
        if key not in ("num_experts", "max_position_embeddings"):
            assert cfg[key] == val, key
    module = driver.build_module(cfg)
    assert module.layer_pattern == "WEWEWERE" * 7
    assert (module.window, module.kv_ring) == (1024, 1568)
    assert module.rope_full[1] == (16.0, 8192, 32.0, 1.0)
    assert abs(module.rope_full[2] - 1.2772588722239782) < 1e-12
    assert module.rope_window == (500000.0, None, 1.0)
