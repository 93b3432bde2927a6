"""A compiled call's host inputs travel as ONE packed int32 array.

The decode launch, the verify call and the prefill call each take their
per-slot inputs and page table as one operand made by one host-to-device
copy (``DecodeEngine._upload``), and take it apart with static slices
(``_step_columns`` / ``_verify_columns`` / ``_prefill_columns``). Covers:
packing then unpacking through the programs' own slicing gives every
operand back bit for bit, the floats included; engines emit the tokens
they emitted when every operand was an array of its own (a fixed
expectation recorded then); each launch makes exactly one copy.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from test_phase_spans import _CountingJnp

from rafiki_tpu.serving import decode_engine as de

TEMPS = np.array([0.7, 1e-8, 1.0, 0.0, 1.3], np.float32)
TOP_PS = np.array([1e-8, 0.7, 1.0, 0.5, 0.9], np.float32)
#: table widths a call meets: one page, a power of two, the whole table
WIDTHS = {"one": 1, "eight": 8, "whole": None}


def _lm():
    """The benchmark's tiny LM (float32 compute, paged, the page gather)
    and weights drawn from a fixed seed: no training, so the tokens are a
    fixed function of the code."""
    from benchmark import harness, weights
    from benchmark.drivers import serve_engine

    cfg = harness.load_json("configs", "tiny-lm.json")
    cfg = dict(cfg, engine=dict(cfg["engine"], paged_kernel=False))
    module = serve_engine.build_module(cfg)
    abstract = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    return module, weights.make_weights(abstract, 5)


def _hybrid():
    """The benchmark's tiny pattern decoder: Mamba state and a ring of
    keys a slot (per-slot state), weights from a fixed seed."""
    from benchmark import harness
    from benchmark.drivers import serve_hybrid_ssm as driver

    cfg = harness.load_json("configs", "tiny-hybrid-ssm.json")
    module = driver.build_module(cfg)
    return module, driver.make_weights(cfg, driver.abstract_params(module),
                                       3)


@pytest.fixture(scope="module")
def lm():
    return _lm()


@pytest.fixture(scope="module")
def hybrid():
    return _hybrid()


def _filled_engine(module):
    """An engine whose host mirrors hold a value in every column: five
    slots, the sampling knobs among them floats that must not round."""
    eng = de.DecodeEngine(module, None, max_slots=5, max_len=module.max_len)
    rng = np.random.default_rng(7)
    for col in (eng._tok, eng._pos, eng._prompt_len, eng._stop_pos,
                eng._topk, eng._seed, eng._aid):
        col[:] = rng.integers(0, 2**31 - 1, eng.B)
    eng._temp[:] = TEMPS
    eng._topp[:] = TOP_PS
    eng._ptab[:] = rng.integers(0, 2**31 - 1, eng._ptab.shape)
    return eng


def _same_bits(got, want):
    got, want = np.asarray(got), np.asarray(want)
    assert got.dtype == want.dtype and got.shape == want.shape
    if want.dtype == np.float32:
        assert not np.isnan(got).any()
        got, want = got.view(np.int32), want.view(np.int32)
    np.testing.assert_array_equal(got, want)


def _width(eng, name):
    return WIDTHS[name] or eng._n_table


@pytest.mark.parametrize("width", list(WIDTHS))
def test_a_launch_operand_unpacks_to_every_slot_column(lm, width):
    eng = _filled_engine(lm[0])
    w = _width(eng, width)
    packed = eng._upload(eng._slot_host[:, :de.N_SLOT_COLUMNS + w])
    assert packed.dtype == jnp.int32
    got = jax.jit(de._step_columns)(packed)
    want = (eng._tok, eng._pos, eng._prompt_len, eng._stop_pos, eng._temp,
            eng._topk, eng._topp, eng._seed, eng._aid, eng._ptab[:, :w])
    assert len(got) == len(want) == len(de.SLOT_COLUMNS) + 1
    for g, x in zip(got, want):
        _same_bits(g, x)


@pytest.mark.parametrize("width", list(WIDTHS))
def test_a_verify_operand_unpacks_to_its_inputs(lm, width):
    eng, k = _filled_engine(lm[0]), 4
    w = _width(eng, width)
    drafts = np.arange(eng.B * (k - 1), dtype=np.int32).reshape(eng.B, -1)
    packed = eng._upload(eng._slot_host[:, :de.N_SLOT_COLUMNS], drafts,
                         eng._ptab[:, :w])
    got = jax.jit(de._verify_columns, static_argnums=1)(packed, k)
    for g, x in zip(got, (eng._tok, eng._pos, drafts, eng._stop_pos,
                          eng._aid, eng._ptab[:, :w])):
        _same_bits(g, x)


@pytest.mark.parametrize("slot_state", [False, True],
                         ids=["rows", "slot_state"])
@pytest.mark.parametrize("width", list(WIDTHS))
def test_a_prefill_operand_unpacks_to_its_rows(lm, width, slot_state):
    eng, chunk, n_rows = _filled_engine(lm[0]), 8, 3
    w = _width(eng, width)
    rng = np.random.default_rng(3)
    tok, pos = (rng.integers(0, 2**31 - 1, (n_rows, chunk)).astype(np.int32)
                for _ in range(2))
    aid, slots, real = (rng.integers(0, 2**31 - 1, n_rows).astype(np.int32)
                        for _ in range(3))
    table = eng._ptab[:n_rows, :w]
    packed = eng._upload(tok, pos, aid,
                         *((slots, real) if slot_state else ()), table)
    assert packed.shape == (n_rows, 2 * chunk + 1 + 2 * slot_state + w)
    got_tok, got_pos, got_aid, rows, got_table = jax.jit(
        de._prefill_columns, static_argnums=(1, 2))(packed, chunk,
                                                    slot_state)
    for g, x in zip((got_tok, got_pos, got_aid, got_table),
                    (tok, pos, aid, table)):
        _same_bits(g, x)
    if slot_state:
        _same_bits(rows[0], slots)
        _same_bits(rows[1], real)
    else:
        assert rows is None


def test_pack_takes_floats_by_their_bits_and_ints_by_value():
    floats = np.array([0.7, 1e-8, 1.0, -0.0], np.float32)
    ints = np.array([1, 2**31 - 1, 0, 5], np.int64)
    packed = de._pack(floats, ints, np.ones((4, 2), np.int32))
    assert packed.dtype == np.int32 and packed.shape == (4, 4)
    np.testing.assert_array_equal(packed[:, 0], floats.view(np.int32))
    np.testing.assert_array_equal(packed[:, 1], ints)
    np.testing.assert_array_equal(packed[:, 2:], 1)


# ----------------------------------------------- tokens, as they were

PROMPTS = [[1, 5, 9, 200, 17], list(range(3, 23)), [7, 7],
           [250, 3, 99, 12, 44, 61, 2, 8, 31, 5, 77], [42]]
#: (temperature, top_k, top_p, seed) a request
SAMPLING = [(0.7, 0, 1.0, 11), (1.0, 20, 0.9, 2**31 + 7),
            (1.3, 5, 1e-8, 5), (0.7, 0, 0.5, 123456789), (0.0, 0, 1.0, 0)]
GREEDY_TOKENS = {
    "g0": [173, 131, 162, 99, 240, 125, 8, 130, 21],
    "g1": [198, 144, 158, 198, 81, 1, 72, 57, 5, 51],
    "g2": [8, 223, 226, 80, 172, 130, 140, 149, 120, 85, 211],
    "g3": [239, 239, 239, 239, 135, 77, 222, 74, 127, 173, 44, 238],
    "g4": [150, 147, 150, 94, 174, 165, 241, 147, 200, 85, 61, 203, 101]}
#: what the engines emitted when each operand was uploaded on its own
EXPECTED = {
    "greedy": GREEDY_TOKENS,
    "sampling": {
        "s0": [82, 82, 234, 96, 250, 96, 65, 105, 18],
        "s1": [198, 81, 134, 162, 82, 230, 233, 153, 173, 44],
        # top_p 1e-8 keeps one token: the greedy one
        "s2": GREEDY_TOKENS["g2"],
        "s3": [48, 242, 62, 241, 166, 250, 59, 42, 25, 110, 122, 125],
        "s4": GREEDY_TOKENS["g4"]},
    "verify": GREEDY_TOKENS,
    "slot_state": {
        "g0": [247, 220, 90, 244, 135, 75, 185, 106, 138],
        "g1": [199, 8, 235, 57, 164, 232, 77, 14, 124, 118],
        "s1": [199, 77, 102, 55, 199, 47, 175, 156, 99, 62],
        "s2": [232, 69, 203, 134, 42, 53, 244, 105, 168, 129, 106]}}


def _requests(kind):
    greedy = [(f"g{i}", p, 9 + i, {}) for i, p in enumerate(PROMPTS)]
    sampled = [(f"s{i}", p, 9 + i,
                dict(temperature=t, top_k=k, top_p=tp, seed=s))
               for i, (p, (t, k, tp, s)) in enumerate(zip(PROMPTS,
                                                          SAMPLING))]
    return {"greedy": greedy, "sampling": sampled, "verify": greedy,
            "slot_state": greedy[:2] + sampled[1:3]}[kind]


def _engine(kind, lm, hybrid):
    """Three slots for five requests (admissions mid-flight); the verify
    path's drafts by prompt lookup; per-slot state on two slots."""
    if kind == "slot_state":
        module, params = hybrid
        return de.DecodeEngine(module, params, max_slots=2,
                               max_len=module.max_len, prefill_chunk=8)
    module, params = lm
    return de.DecodeEngine(module, params, max_slots=3, max_len=128,
                           steps_per_sync=4, prefill_chunk=8,
                           speculate_k=3 if kind == "verify" else 0)


def _drive(eng, requests):
    for rid, prompt, max_new, kw in requests:
        eng.submit(rid, np.asarray(prompt, np.int32), max_new, **kw)
    out = {}
    for _ in range(500):
        if not eng.busy:
            break
        eng.step()
        out.update(dict(eng.poll()))
    assert not eng.busy
    return {k: [int(t) for t in v] for k, v in out.items()}


@pytest.mark.parametrize("kind", list(EXPECTED))
def test_engines_emit_the_recorded_tokens(lm, hybrid, kind):
    eng = _engine(kind, lm, hybrid)
    assert _drive(eng, _requests(kind)) == EXPECTED[kind]
    if kind == "verify":
        assert eng.stats["spec_calls"] > 0


# ---------------------------------------------------- one copy a launch

@pytest.mark.parametrize("kind", ["greedy", "verify", "slot_state"],
                         ids=["scan", "verify", "slot_state"])
def test_each_decode_launch_makes_one_copy(lm, hybrid, kind, monkeypatch):
    eng = _engine(kind, lm, hybrid)
    counting = _CountingJnp(de.jnp)
    monkeypatch.setattr(de, "jnp", counting)
    prep = {}
    made = {"scan": [], "spec": []}
    decode_prep = eng._decode_prep

    def mark(*args, **kwargs):
        # copies from here to the launch: its operand, and the prompts
        # where the turn refreshed them
        prep.update(copies=counting.copies, prompts=eng._prompt_dev)
        return decode_prep(*args, **kwargs)

    def launched(path, fn):
        def call(*args):
            made[path].append(counting.copies - prep["copies"]
                              - (eng._prompt_dev is not prep["prompts"]))
            return fn(*args)
        return call

    eng._decode_prep = mark
    eng._step_fns = {s: launched("scan", fn)
                     for s, fn in eng._step_fns.items()}
    if eng._verify_fn is not None:
        eng._verify_fn = launched("spec", eng._verify_fn)
    _drive(eng, _requests(kind))
    assert made["scan"] or made["spec"]
    if kind == "verify":
        assert made["spec"]
    assert set(made["scan"] + made["spec"]) == {1}
