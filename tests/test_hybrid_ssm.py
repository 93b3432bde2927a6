"""The pattern-driven decoder of ``models/hybrid_ssm_moe.py`` and what it
stands on: the state-space primitives of ``ops/ssm.py`` (chunked scan, the
single-token state kernel in ``interpret`` mode, the convolution) against a
token-by-token recurrence; the second routing rule and the experts of two
kernels in ``ops/moe.py``; ``column_tile``; the module's full forward
against the plain reference; and the share of the experts tied to the uncut
layer. Small sizes, float32, seeded random weights drawn by the benchmark's
rules (the Mamba-2 conventions among them). Serving through the engine is
``test_hybrid_ssm_serving.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers import serve_hybrid_ssm as driver
from benchmark.reference import hybrid_ssm_moe as ref
from rafiki_tpu.ops import moe, ssm
from rafiki_tpu.ops.grouped_matmul import column_tile


def tiny_cfg(**over):
    cfg = harness.load_json("configs", "tiny-hybrid-ssm.json")
    cfg.update(over)
    return cfg


def weights(cfg, seed=3):
    module = driver.build_module(cfg)
    return module, driver.make_weights(cfg, driver.abstract_params(module),
                                       seed)


# ----------------------------------------------------------- ops/ssm.py
R, L, H, P, G, N = 4, 8, 4, 8, 2, 16
#: float32 sums of a few dozen terms of size ~1 in another order: roundoff
TOL = 2e-5


def _inputs(seed=0):
    rng = np.random.default_rng(seed)
    f = lambda *s: rng.normal(size=s).astype(np.float32)  # noqa: E731
    return dict(
        x=f(R, L, H, P), b=f(R, L, G, N), c=f(R, L, G, N),
        # steps and decays as the Mamba-2 conventions draw them, large
        # enough that a state lives for many tokens and matters
        dt=rng.uniform(0.02, 0.3, (R, L, H)).astype(np.float32),
        a=-rng.uniform(1, 4, (H,)).astype(np.float32),
        d=np.ones((H,), np.float32), init=f(R, H, P, N))


def _by_token(x, dt, a, b, c, d, s):
    """The recurrence a token at a time, numpy float64 -> the oracle."""
    s, ys = s.astype(np.float64), []
    for t in range(x.shape[0]):
        bh, ch = (np.repeat(v[t], H // G, 0) for v in (b, c))
        s = np.exp(dt[t] * a)[:, None, None] * s \
            + (dt[t][:, None] * x[t])[:, :, None] * bh[:, None, :]
        ys.append(np.einsum("hpn,hn->hp", s, ch) + d[:, None] * x[t])
    return np.stack(ys), s


def test_chunked_scan_is_the_recurrence_and_carries_the_state():
    v = _inputs()
    chained = np.array([False, True, False, True])
    y, final = ssm.ssd_chunk_scan(
        *(jnp.asarray(v[k]) for k in ("x", "dt", "a", "b", "c", "d",
                                      "init")), jnp.asarray(chained))
    worst_dropped = 0.0
    for r0 in (0, 2):  # rows r0, r0 + 1 are ONE sequence from init[r0]
        two = {k: np.concatenate([v[k][r0], v[k][r0 + 1]])
               for k in ("x", "dt", "b", "c")}
        want, end = _by_token(two["x"], two["dt"], v["a"], two["b"],
                              two["c"], v["d"], v["init"][r0])
        got = np.asarray(y[r0:r0 + 2]).reshape(2 * L, H, P)
        assert np.abs(got - want).max() < TOL
        assert np.abs(np.asarray(final[r0 + 1]) - end).max() < TOL
        # the same second row from ITS OWN init instead of the carried
        # state: what a call that lost the chain would compute
        alone, _ = _by_token(v["x"][r0 + 1], v["dt"][r0 + 1], v["a"],
                             v["b"][r0 + 1], v["c"][r0 + 1], v["d"],
                             v["init"][r0 + 1])
        worst_dropped = max(worst_dropped,
                            np.abs(alone - want[L:]).max())
    assert worst_dropped > 100 * TOL, worst_dropped


def test_chunked_scan_padding_advances_nothing():
    v = _inputs(1)
    dt = v["dt"].copy()
    dt[:, 5:] = 0.0  # tokens 5.. of every row are padding
    args = [jnp.asarray(v[k]) for k in ("a", "b", "c", "d", "init")]
    _, final = ssm.ssd_chunk_scan(jnp.asarray(v["x"]), jnp.asarray(dt),
                                  *args, jnp.zeros((R,), bool))
    for r in range(R):
        _, end = _by_token(v["x"][r, :5], dt[r, :5], v["a"], v["b"][r, :5],
                           v["c"][r, :5], v["d"], v["init"][r])
        assert np.abs(np.asarray(final[r]) - end).max() < TOL


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["xla", "pallas_interpreter"])
def test_state_step_is_one_token_of_the_recurrence_in_place(interpret):
    v = _inputs(2)
    rng = np.random.default_rng(5)
    table = rng.normal(size=(6, H, P, N)).astype(np.float32)  # 5 + scratch
    slots = np.array([3, 0, 4, 1], np.int32)
    advance = np.array([True, True, False, True])
    fresh = np.array([False, True, False, False])
    y, out = ssm.ssm_state_step(
        jnp.asarray(table), jnp.asarray(slots), jnp.asarray(advance),
        jnp.asarray(fresh), *(jnp.asarray(v[k][:, 0]) for k in ("x", "dt")),
        jnp.asarray(v["a"]), jnp.asarray(v["b"][:, 0]),
        jnp.asarray(v["c"][:, 0]), jnp.asarray(v["d"]),
        interpret=interpret)
    out = np.asarray(out)
    dropped = 0.0
    for r in np.flatnonzero(advance):
        start = np.zeros_like(table[0]) if fresh[r] else table[slots[r]]
        one = {k: v[k][r, :1] for k in ("x", "dt", "b", "c")}
        want, end = _by_token(one["x"], one["dt"], v["a"], one["b"],
                              one["c"], v["d"], start)
        assert np.abs(np.asarray(y[r]) - want[0]).max() < TOL
        assert np.abs(out[slots[r]] - end).max() < TOL
        if not fresh[r]:  # the same token from a ZERO state
            lost, _ = _by_token(one["x"], one["dt"], v["a"], one["b"],
                                one["c"], v["d"], 0 * start)
            dropped = max(dropped, np.abs(lost[0] - want[0]).max())
    assert dropped > 100 * TOL, dropped
    # slot 4's row advanced nothing, slot 2 had no row: bit-equal
    assert np.array_equal(out[4], table[4])
    assert np.array_equal(out[2], table[2])


def test_causal_conv_window_step_and_tail():
    rng = np.random.default_rng(3)
    c, k = 6, 4
    x = rng.normal(size=(2, 9, c)).astype(np.float32)
    w = rng.uniform(-0.5, 0.5, (k, c)).astype(np.float32)
    bias = rng.normal(size=(c,)).astype(np.float32)
    padded = np.pad(x, ((0, 0), (k - 1, 0), (0, 0)))
    want = sum(padded[:, j:j + 9] * w[j] for j in range(k)) + bias
    zeros = jnp.zeros((2, k - 1, c))
    full, tail = ssm.causal_conv(jnp.asarray(x), zeros, jnp.asarray(w),
                                 jnp.asarray(bias), jnp.asarray([9, 9]))
    assert np.abs(np.asarray(full) - want).max() < 1e-6
    assert np.array_equal(np.asarray(tail), x[:, -3:])
    # two windows (5 real tokens of 6, then 4), then single tokens: the
    # tail carries across all of them
    first, tail = ssm.causal_conv(
        jnp.asarray(np.concatenate([x[:, :5], x[:, 4:5]], 1)), zeros,
        jnp.asarray(w), jnp.asarray(bias), jnp.asarray([5, 5]))
    assert np.array_equal(np.asarray(tail), x[:, 2:5])
    second, tail = ssm.causal_conv(jnp.asarray(x[:, 5:8]), tail,
                                   jnp.asarray(w), jnp.asarray(bias),
                                   jnp.asarray([3, 0]))
    assert np.abs(np.asarray(first[:, :5]) - want[:, :5]).max() < 1e-6
    assert np.abs(np.asarray(second[0]) - want[0, 5:8]).max() < 1e-6
    assert np.array_equal(np.asarray(tail[1]), x[1, 2:5])  # 0 real: kept
    step, tail = ssm.causal_conv(jnp.asarray(x[:1, 8:9]), tail[:1],
                                 jnp.asarray(w), jnp.asarray(bias),
                                 jnp.asarray([1]))
    assert np.abs(np.asarray(step[0, 0]) - want[0, 8]).max() < 1e-6
    assert np.array_equal(np.asarray(tail[0]), x[0, 6:9])


# ------------------------------------------------------------ ops/moe.py
def test_softmax_rule_is_bit_equal_to_what_it_was():
    logits = jnp.asarray(np.random.default_rng(0).normal(
        size=(12, 8)).astype(np.float32))
    for renorm, scaling in ((True, 1.0), (False, 2.0)):
        gates, experts = moe.route_top_k(logits, 3, renorm, scaling)
        probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
        want, idx = jax.lax.top_k(probs, 3)
        if renorm:
            want = want / jnp.sum(want, axis=-1, keepdims=True)
        assert np.array_equal(np.asarray(gates), np.asarray(want * scaling))
        assert np.array_equal(np.asarray(experts), np.asarray(idx))


def test_sigmoid_rule_selects_on_score_plus_bias_and_weighs_by_score():
    cfg = tiny_cfg()
    rng = np.random.default_rng(1)
    h = jnp.asarray(rng.normal(size=(16, 64)).astype(np.float32))
    p = {"router": {"kernel": jnp.asarray(
        rng.normal(size=(64, 8)).astype(np.float32) / 8)},
        # large beside the scores' spread: it changes who is chosen
        "score_bias": jnp.asarray(rng.uniform(-0.5, 0.5, 8).astype(
            np.float32))}
    logits = jnp.matmul(h, p["router"]["kernel"],
                        precision=jax.lax.Precision.HIGHEST)
    gates, experts = moe.route_top_k(logits, 3, True, 2.5,
                                     score_bias=p["score_bias"])
    want_g, want_e = ref.route(p, h, cfg)
    assert np.array_equal(np.asarray(experts), np.asarray(want_e))
    # one rounding of a sum of three sigmoids apart
    assert np.abs(np.asarray(gates) - np.asarray(want_g)).max() < 1e-6
    unbiased = jax.lax.top_k(jax.nn.sigmoid(logits), 3)[1]
    assert not np.array_equal(np.asarray(unbiased), np.asarray(experts)), \
        "the bias must change the selection for this test to mean anything"
    s = np.asarray(jax.nn.sigmoid(logits))
    chosen = np.take_along_axis(s, np.asarray(experts), 1)
    assert np.abs(np.asarray(gates)
                  - 2.5 * chosen / chosen.sum(1, keepdims=True)).max() < 1e-6


@pytest.mark.parametrize("interpret", [None, True],
                         ids=["ragged_dot", "pallas_interpreter"])
def test_experts_of_two_kernels_with_relu2_between(interpret):
    rng = np.random.default_rng(2)
    t, d, f, n, k = 24, 32, 48, 4, 3
    x = jnp.asarray(rng.normal(size=(t, d)).astype(np.float32))
    w_up = jnp.asarray(rng.normal(size=(n, d, f)).astype(np.float32) / 6)
    w_dn = jnp.asarray(rng.normal(size=(n, f, d)).astype(np.float32) / 7)
    logits = jnp.asarray(rng.normal(size=(t, 8)).astype(np.float32))
    gates, experts = moe.route_top_k(logits, k, True, 2.5,
                                     score_bias=jnp.zeros((8,)))
    y, counts = moe.grouped_experts(x, gates, experts, None, w_up, w_dn,
                                    first=2, interpret=interpret)
    want = np.zeros((t, d))
    for i in range(t):
        for g, e in zip(np.asarray(gates[i]), np.asarray(experts[i])):
            if 2 <= e < 2 + n:
                hid = np.maximum(np.asarray(x[i], np.float64)
                                 @ np.asarray(w_up[e - 2], np.float64), 0)
                want[i] += g * (hid ** 2 @ np.asarray(w_dn[e - 2]))
    # float32 products of width 32 and 48 against float64: roundoff
    assert np.abs(np.asarray(y) - want).max() < 1e-4 * np.abs(want).max()
    assert int(counts[0]) == t * k and int(counts[2]) == n


@pytest.mark.parametrize("k,f,want", [
    (1024, 2688, 896),    # 21 x 128 columns: three tiles of 1.8 MB
    (2688, 1024, 512),
    (4096, 2048, 512),    # the accepted expert cell's two shapes: the
    (2048, 4096, 1024),   # tiles they had before the rule changed
])
def test_column_tile(k, f, want):
    assert column_tile(k, f, 2) == want


# ------------------------------------------------- module against reference
def test_full_forward_agrees_with_the_reference():
    """A pattern holding all three kinds of layer; a sequence of three
    chunks and a remainder, so that the chunked scan's state recurrence
    and its padding are both in the comparison."""
    cfg = tiny_cfg()
    assert set(cfg["hybrid_override_pattern"]) == {"M", "E", "*"}
    module, params = weights(cfg)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], 29)
    with jax.default_matmul_precision("highest"):
        got = module.apply({"params": params}, jnp.asarray(ids)[None])[0]
    want = ref.forward(params, jnp.asarray(ids), cfg)
    # float32 both sides; the chunked form sums in another order than the
    # token scan, through five layers: roundoff relative to the logits
    assert float(jnp.abs(got - want).max()) < 2e-4 * float(
        jnp.abs(want).max())
    low = ref.forward(params, jnp.asarray(ids), cfg, quant="bfloat16")
    assert float(jnp.abs(low - want).max()) > 10 * float(
        jnp.abs(got - want).max())


def test_the_state_matters_at_these_weights():
    """The Mamba-2 conventions draw steps and decays under which a token
    still feels what came 8 and more positions before it — beyond the
    convolution's 3, so through the STATE alone: one Mamba layer's logits
    move by ~0.9% (0.08% from 20 positions back), where float32 roundoff
    is 1e-6. Drawn like kernels, the decays would forget within a token
    and no comparison here could see a lost state."""
    cfg = tiny_cfg()
    _, params = weights(cfg)
    ids = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    other = ids.copy()
    other[:32] = (other[:32] + 1) % cfg["vocab_size"]
    mamba_only = cfg | {"hybrid_override_pattern": "M",
                        "num_hidden_layers": 1}
    a, b = (ref.forward(params, jnp.asarray(s), mamba_only)[-1]
            for s in (ids, other))
    assert float(jnp.abs(a - b).max()) > 2e-3 * float(jnp.abs(a).max())


def test_the_shares_add_up_to_the_uncut_layer():
    """Two chips' routed parts through ``W_up`` plus the shared expert
    ONCE are the whole layer: the program's share of experts 4-7, the
    reference's share of 0-3, against the reference uncut."""
    cfg = tiny_cfg()
    module, params = weights(cfg)
    rng = np.random.default_rng(4)
    whole = dict(params["block_1"]["mixer"])
    n_all, k, f = 8, cfg["moe_latent_size"], cfg["moe_intermediate_size"]
    up = jnp.asarray(rng.normal(size=(n_all, k, f)).astype(np.float32)
                     / np.sqrt(k))
    down = jnp.asarray(rng.normal(size=(n_all, f, k)).astype(np.float32)
                       / np.sqrt(f))

    def part(lo, hi):
        p = dict(whole)
        p["moe"] = dict(whole["moe"], experts_up={"kernel": up[lo:hi]},
                        experts_down={"kernel": down[lo:hi]})
        return p

    h = jnp.asarray(rng.normal(size=(11, cfg["hidden_size"])).astype(
        np.float32))
    uncut = ref.experts(part(0, 8), h, cfg, None, held=None)
    low = ref.experts(part(0, 4), h, cfg, None, held=(0, 4), shared=False)
    from rafiki_tpu.models.hybrid_ssm_moe import LatentExperts

    fields = dict(module.layer_fields("E"))
    assert dict(fields["expert_fields"])["held"] == (4, 4)
    with jax.default_matmul_precision("highest"):
        high = LatentExperts(**fields).apply({"params": part(4, 8)},
                                             h[None])[0]
    # ``high`` holds the shared expert; ``low`` left it out
    assert float(jnp.abs(low + high - uncut).max()) < 2e-5 * float(
        jnp.abs(uncut).max())
    assert float(jnp.abs(low).max()) > 0.05 * float(jnp.abs(uncut).max())
