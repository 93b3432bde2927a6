"""Draft-MODEL speculative decoding: a smaller model drafts, the
target verifies — greedy-lossless by construction, with the draft's
KV cache synced through prompts/scan/verify by mirrored multi-token
passes (serving/decode_engine.py ``draft=``)."""

import numpy as np
import pytest

from rafiki_tpu.models.llama_lora import LlamaLoRA

from test_decode_engine import KNOBS  # noqa: F401 — shared knobs
from test_multi_adapter import _lora_variant  # noqa: F401


def _drain(eng):
    got = {}
    for _ in range(400):
        if not eng.busy:
            break
        eng.step()
        for rid, text in eng.poll():
            got[rid] = text
    assert not eng.busy, "engine failed to drain"
    return got


def _serve(trained, reqs, **engine_kwargs):  # noqa: F811
    eng = trained.make_decode_engine(max_slots=4, max_new_tokens=8,
                                     **engine_kwargs)
    for rid, text in reqs:
        eng.submit(rid, text)
    return _drain(eng), eng


def test_draft_model_speculation_is_lossless(trained):  # noqa: F811
    """Outputs are token-identical to plain greedy decoding whether
    the draft is PERFECT (the target itself — near-total acceptance)
    or BAD (perturbed adapters — low acceptance): the verify step is
    target-authoritative either way."""
    reqs = [("a", "tok1 tok2 tok3"), ("b", "tok4 tok5"),
            ("c", "tok6 tok7 tok8")]
    plain, _ = _serve(trained, reqs)

    # perfect draft: a sibling carrying the same params
    perfect = LlamaLoRA(**KNOBS)
    perfect.load_parameters(trained.dump_parameters())
    out_p, eng_p = _serve(trained, reqs, speculate_k=4,
                          draft_model=perfect)
    assert out_p == plain
    s = eng_p.stats
    assert s.get("spec_draft_model_calls", 0) > 0, s
    assert s["spec_accepted"] > 0
    # a perfect draft should accept nearly everything it drafts
    assert s["spec_accepted"] >= 0.9 * s["spec_drafted"], s

    # bad draft: same base, perturbed adapters — still lossless
    bad = LlamaLoRA(**KNOBS)
    dump = trained.dump_parameters()
    dump = dict(dump)
    dump["params"] = _lora_variant(trained._params, scale=0.5)
    bad.load_parameters(dump)
    out_b, eng_b = _serve(trained, reqs, speculate_k=4, draft_model=bad)
    assert out_b == plain
    assert eng_b.stats["requests_done"] == len(reqs)


def _small_draft_setup():
    """A depth-4 target and a depth-1 draft at 1/4 its width, the draft
    distilled for 220 steps on the target's own greedy continuations
    of a 12-prompt family. Returns ``(t_mod, t_params, d_mod,
    d_params, evs, max_new)``: four corpus prompts primed 8 tokens
    deep, and a ``max_new`` that runs 2 tokens PAST the distillation
    horizon — the design point at which acceptance lands inside
    (0, 1)."""
    import jax
    import jax.numpy as jnp
    import optax

    from rafiki_tpu.models.llama_lora import Llama, greedy_generate

    vocab, max_len = 1 << 14, 64
    t_mod = Llama(vocab_size=vocab, max_len=max_len, lora_rank=0,
                  hidden_dim=128, depth=4, n_heads=4, n_kv_heads=2,
                  mlp_dim=512)
    t_params = t_mod.init(jax.random.PRNGKey(0),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    d_mod = Llama(vocab_size=vocab, max_len=max_len, lora_rank=0,
                  hidden_dim=32, depth=1, n_heads=4, n_kv_heads=2,
                  mlp_dim=64)

    rng = np.random.default_rng(7)
    plen, glen = 12, 20
    prompts = rng.integers(1, 10, size=(12, plen)).astype(np.int32)
    gens = np.asarray(greedy_generate(
        t_mod, t_params, prompts,
        np.full((12,), plen, np.int32), glen)).astype(np.int32)
    ids = np.concatenate([prompts, gens], axis=1)

    d_params = d_mod.init(jax.random.PRNGKey(1),
                          jnp.zeros((1, 8), jnp.int32))["params"]
    tx = optax.adam(3e-3)
    opt = tx.init(d_params)
    xb, yb = jnp.asarray(ids[:, :-1]), jnp.asarray(ids[:, 1:])

    @jax.jit
    def dstep(p, o):
        def loss_fn(p):
            logits = d_mod.apply({"params": p}, xb)
            return jnp.mean(
                optax.softmax_cross_entropy_with_integer_labels(
                    logits.astype(jnp.float32), yb))

        loss, g = jax.value_and_grad(loss_fn)(p)
        u, o = tx.update(g, o)
        return optax.apply_updates(p, u), o, loss

    for _ in range(220):
        d_params, opt, _ = dstep(d_params, opt)

    # greedy decode is deterministic, so the 8-token priming is the
    # corpus continuation's own prefix
    max_new = (glen - 8) + 2
    evs = [np.concatenate([prompts[i], gens[i][:8]]) for i in
           (0, 3, 5, 8)]
    return t_mod, t_params, d_mod, d_params, evs, max_new


@pytest.mark.slow
def test_distilled_small_draft_partial_acceptance():
    """A genuinely smaller draft (depth 1, 1/4 width) distilled on the
    target's own greedy continuations, evaluated 2 tokens past the
    distillation horizon, must (a) land acceptance STRICTLY inside
    (0, 1) — neither the degenerate self-draft 1.0 nor a gated-off 0 —
    and (b) stay lossless: token-identical to plain greedy decode."""
    from rafiki_tpu.serving.decode_engine import DecodeEngine

    t_mod, t_params, d_mod, d_params, evs, max_new = \
        _small_draft_setup()

    def run(spec_k, draft=None):
        eng = DecodeEngine(t_mod, t_params, max_slots=4,
                           max_len=t_mod.max_len, speculate_k=spec_k,
                           draft=draft)
        for r, e in enumerate(evs):
            eng.submit(("r", r), e, max_new)
        got = {}
        for _ in range(500):
            if not eng.busy:
                break
            eng.step()
            for rid, toks in eng.poll():
                got[rid] = list(toks)
        assert not eng.busy
        return got, dict(eng.stats)

    plain, _ = run(0)
    spec, st = run(4, draft=(d_mod, d_params))
    assert spec == plain  # lossless regardless of acceptance
    acc = st["spec_accepted"] / max(1, st["spec_drafted"])
    assert st["spec_drafted"] > 0, st
    assert 0.0 < acc < 1.0, (acc, st)


def test_draft_model_mid_flight_admission(trained):  # noqa: F811
    """Requests admitted while others are mid-generation keep the
    draft cache synced (the scan/prefill mirrors): outputs still match
    solo plain decoding per request."""
    perfect = LlamaLoRA(**KNOBS)
    perfect.load_parameters(trained.dump_parameters())
    eng = trained.make_decode_engine(max_slots=2, max_new_tokens=6,
                                     speculate_k=3,
                                     draft_model=perfect)
    plain_eng = trained.make_decode_engine(max_slots=2,
                                           max_new_tokens=6)
    for rid, text in [("a", "tok1 tok2 tok3"), ("b", "tok4 tok5"),
                      ("c", "tok6 tok7")]:
        plain_eng.submit(rid, text)
    plain = _drain(plain_eng)
    eng.submit("a", "tok1 tok2 tok3")
    got = {}
    stepped = 0
    while eng.busy or stepped == 0:
        eng.step()
        stepped += 1
        if stepped == 2:  # admit mid-flight
            eng.submit("b", "tok4 tok5")
        if stepped == 4:
            eng.submit("c", "tok6 tok7")
        for rid, text in eng.poll():
            got[rid] = text
        if stepped > 400:
            raise AssertionError("no drain")
    assert got == plain


def test_draft_model_vocab_mismatch_rejected(trained):  # noqa: F811
    other = LlamaLoRA(**{**KNOBS, "vocab_size": 1 << 9})
    other._params = other._module().init(
        __import__("jax").random.PRNGKey(0),
        np.zeros((1, int(KNOBS["max_len"])), np.int32))["params"]
    with pytest.raises(ValueError, match="vocab"):
        trained.make_decode_engine(speculate_k=3, draft_model=other)


def test_draft_with_prefix_cache_stays_accepted(trained):  # noqa: F811
    """system_prefix + draft_model: the prefix KV installs into BOTH
    caches, so prefix-hit requests keep near-total acceptance with a
    perfect draft (and stay lossless)."""
    perfect = LlamaLoRA(**KNOBS)
    perfect.load_parameters(trained.dump_parameters())
    prefix = "tok1 tok2 tok3"
    plain = trained.make_decode_engine(max_slots=2, max_new_tokens=6,
                                       system_prefix=prefix)
    # spec_k=3 divides max_new: no stop-boundary clamp, so acceptance
    # measures draft quality alone
    eng = trained.make_decode_engine(max_slots=2, max_new_tokens=6,
                                     speculate_k=3, draft_model=perfect,
                                     system_prefix=prefix)
    reqs = [("a", prefix + " tok4 tok5"), ("b", prefix + " tok6")]
    for rid, text in reqs:
        plain.submit(rid, text)
    ref = _drain(plain)
    for rid, text in reqs:
        eng.submit(rid, text)
    got = _drain(eng)
    assert got == ref
    s = eng.stats
    assert s["prefix_hits"] == 2
    assert s["spec_accepted"] >= 0.9 * s["spec_drafted"], s


def test_draft_resync_after_gated_stretch(trained):  # noqa: F811
    """Force the gate off (sampling traffic skips spec and the mirror),
    then greedy traffic re-probes: the engine resyncs the draft cache
    from accepted contexts and keeps outputs lossless."""
    perfect = LlamaLoRA(**KNOBS)
    perfect.load_parameters(trained.dump_parameters())
    eng = trained.make_decode_engine(max_slots=2, max_new_tokens=6,
                                     speculate_k=3, draft_model=perfect)
    # sampling requests ride the scan path; the engine skips mirrors
    # while the spec path is unavailable only if gated — force the gate
    # down artificially to exercise resync deterministically
    eng.engine._spec_ema = 0.0
    eng.submit("warm", "tok1 tok2")
    _drain(eng)
    assert eng.engine._draft_synced is False
    eng.engine._spec_ema = eng.engine._spec_floor + 1.0  # re-open
    plain = trained.make_decode_engine(max_slots=2, max_new_tokens=6)
    plain.submit("x", "tok1 tok2 tok3")
    ref = _drain(plain)
    eng.submit("x", "tok1 tok2 tok3")
    got = _drain(eng)
    assert got == ref
    assert eng.engine.stats["draft_resyncs"] >= 1
