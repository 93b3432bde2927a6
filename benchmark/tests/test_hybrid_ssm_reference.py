"""``reference/hybrid_ssm_moe.py`` against the program's own module at the
tiny configuration, float32 on the CPU: the same weights give the same
logits, a lower precision does not, and the weights' rules draw a recurrence
that carries history."""

import jax
import jax.numpy as jnp
import numpy as np

from benchmark import harness
from benchmark.drivers import serve_hybrid_ssm as driver
from benchmark.reference import hybrid_ssm_moe as ref


def _tiny():
    cfg = harness.load_json("configs", "tiny-hybrid-ssm.json")
    module = driver.build_module(cfg)
    params = driver.make_weights(cfg, driver.abstract_params(module), 11)
    return cfg, module, params


def test_hybrid_reference_matches_the_module():
    cfg, module, params = _tiny()
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], 37)
    with jax.default_matmul_precision("highest"):
        want = module.apply({"params": params}, jnp.asarray(ids)[None])[0]
    got = ref.forward(params, jnp.asarray(ids), cfg)
    # the module's chunked scan against the token scan here, float32 both
    assert float(jnp.abs(got - want).max()) < 2e-4 * float(
        jnp.abs(want).max())
    low = ref.forward(params, jnp.asarray(ids), cfg, quant="int8")
    assert float(jnp.abs(low - want).max()) > 10 * float(
        jnp.abs(got - want).max())


def test_weights_follow_the_mamba_conventions():
    cfg, _, params = _tiny()
    mixer = params["block_0"]["mixer"]
    a = np.exp(np.asarray(mixer["A_log"]))
    assert a.min() >= 1.0 and a.max() <= 16.0
    dt = np.log1p(np.exp(np.asarray(mixer["dt_bias"])))  # softplus
    assert dt.min() >= cfg["time_step_min"] * 0.999
    assert dt.max() <= cfg["time_step_max"] * 1.001
    assert np.array_equal(np.asarray(mixer["D"]), np.ones_like(a))
    conv = np.asarray(mixer["conv1d"]["kernel"])
    assert np.abs(conv).max() <= 0.5 and np.abs(conv).mean() > 0.2
    bias = np.asarray(params["block_1"]["mixer"]["moe"]["score_bias"])
    assert 0 < np.abs(bias).max() <= 0.01
    for name in ("A_log", "dt_bias", "D"):
        assert mixer[name].dtype == jnp.float32
