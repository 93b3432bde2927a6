"""The plain references against the program's own modules at tiny sizes,
float32 on the CPU: the same weights give the same logits (and, for the
ViT, the same gradients)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax

from benchmark import harness, weights
from benchmark.drivers import serve_engine
from benchmark.reference import llama as ref_llama
from benchmark.reference import vit as ref_vit


def test_llama_reference_matches_the_module():
    cfg = harness.load_json("configs", "tiny-lm.json")
    cfg["assumed"]["compute_dtype"] = "float32"
    module = serve_engine.build_module(cfg)
    abstract = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    params = weights.make_weights(abstract, 11)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], 24)
    with jax.default_matmul_precision("highest"):
        want = module.apply({"params": params}, jnp.asarray(ids)[None])[0]
    got = ref_llama.forward(params, jnp.asarray(ids), cfg)
    assert float(jnp.abs(got - want).max()) < 2e-4 * float(
        jnp.abs(want).max())
    assert float(jnp.abs(params["block_0"]["attn"]["wq"]["lora_b"]).max()) \
        > 0, "the adapter path must not be a no-op"
    low = ref_llama.forward(params, jnp.asarray(ids), cfg, quant="int8")
    assert float(jnp.abs(low - want).max()) > 10 * float(
        jnp.abs(got - want).max())


def test_vit_reference_matches_the_module():
    from rafiki_tpu.models.vit import ViT

    cfg = harness.load_json("configs", "tiny-vit.json")
    module = ViT(patch_size=16, hidden_dim=96, depth=2, n_heads=4,
                 mlp_dim=384, n_classes=10, dtype=jnp.float32)
    x = np.random.default_rng(1).normal(size=(8, 32, 32, 3)).astype(
        np.float32)
    y = np.arange(8) % 10
    abstract = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 32, 32, 3)))["params"])
    params = weights.make_weights(abstract, 5)

    def loss(p):
        logits = module.apply({"params": p}, jnp.asarray(x))
        return optax.softmax_cross_entropy_with_integer_labels(
            logits, jnp.asarray(y)).mean()

    with jax.default_matmul_precision("highest"):
        want_l, want_g = jax.value_and_grad(loss)(params)
    got_l, got_g = ref_vit.loss_and_grads(params, x, y.astype(np.int32),
                                          cfg, block=4)
    assert abs(got_l - float(want_l)) < 1e-5 * abs(float(want_l))
    a, b = ref_vit.leaf_norms(got_g), ref_vit.leaf_norms(want_g)
    assert np.abs(a - b).max() < 1e-4 * b.max()


def test_adamw_and_schedule_match_optax():
    sched = optax.warmup_cosine_decay_schedule(0.0, 1e-3, 1, 1000)
    for step in (0, 1, 2, 500, 999):
        assert abs(ref_vit.learning_rate(step, 1e-3, 1, 1000)
                   - float(sched(step))) < 1e-9
    tx = optax.adamw(sched, weight_decay=1e-2)
    p = {"w": jnp.asarray([1.0, -2.0, 3.0])}
    st = tx.init(p)
    m = v = jax.tree_util.tree_map(jnp.zeros_like, p)
    q = p
    for t in range(3):
        g = {"w": jnp.asarray([0.1, -0.2, 0.3]) * (t + 1)}
        up, st = tx.update(g, st, p)
        p = optax.apply_updates(p, up)
        q, m, v = ref_vit.adamw_step(
            q, g, m, v, t + 1, ref_vit.learning_rate(t, 1e-3, 1, 1000),
            1e-2)
    assert float(jnp.abs(p["w"] - q["w"]).max()) < 1e-7
