"""Serving the pattern-driven state-space / attention / latent-expert
decoder: ``DecodeEngine`` with PER-SLOT STATE beside the paged pool against
the plain reference — prompts of less than a chunk, exactly one, and
several chained chunks in ONE prefill call; what may never advance a slot's
state (padding, spare rows, empty and frozen lanes); a slot's next occupant
and a preempted request's resume; what the engine refuses for such a
module; its two gauges; and the benchmark's driver for this kind end to end
on a tiny configuration — sound -> correct, the control -> not correct. The
model's own tests are ``test_hybrid_ssm.py``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import harness
from benchmark.drivers import serve_hybrid_ssm as driver
from benchmark.reference import hybrid_ssm_moe as ref
from rafiki_tpu.serving.decode_engine import DecodeEngine

CHUNK = 8


def tiny_cfg(**over):
    cfg = harness.load_json("configs", "tiny-hybrid-ssm.json")
    cfg.update(over)
    return cfg


def weights(cfg, seed=3):
    module = driver.build_module(cfg)
    return module, driver.make_weights(cfg, driver.abstract_params(module),
                                       seed)


def engine(module, params, slots=4, k=4, **kw):
    return DecodeEngine(module, params, max_slots=slots,
                        max_len=module.max_len, steps_per_sync=k,
                        prefill_chunk=CHUNK, **kw)


def drain(eng, out=None):
    out = {} if out is None else out
    while eng.busy:
        eng.step()
        out.update(dict(eng.poll()))
    return out


def prompts(vocab, sizes, seed=7):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, size=p).astype(np.int32) for p in sizes]


def state_leaves(eng):
    """The cache's slot-indexed leaves, by path."""
    return {jax.tree_util.keystr(path): np.asarray(leaf) for path, leaf in
            jax.tree_util.tree_flatten_with_path(eng._cache)[0]
            if path[-1].key in ("ssm", "conv")}


# ------------------------------------------- engine against reference
@pytest.mark.parametrize("kernels", [False, True],
                         ids=["gather_and_xla", "pallas_interpreter"])
def test_engine_served_logits_agree_with_reference_forward(kernels):
    """Chunked prefill, then decode through the cache, against the
    reference's full forward at every served position: prompts of less
    than one chunk (5 -> 4 tokens to prefill), exactly one (9 -> 8),
    three chunks and a remainder (30), five (41) — admitted together, so
    ONE prefill call holds several lanes and one lane's consecutive
    chunks."""
    cfg = tiny_cfg()
    cfg["engine"]["paged_kernel"] = kernels
    module, params = weights(cfg)
    reqs = prompts(cfg["vocab_size"], (5, 9, 30, 41, 3, 17))
    eng = engine(module, params)
    assert eng.paged_kernel_mode == 2 * int(kernels)
    for rid, p in enumerate(reqs):
        eng.submit(rid, p, 7)
    out = drain(eng)
    for rid, p in enumerate(reqs):
        got = ref.served_token_gaps(params, cfg, p,
                                    np.asarray(out[rid], np.int32),
                                    pad_to=module.max_len)
        # f32 compute against f32 highest: roundoff, and every served
        # token is the reference's own first choice
        assert got["n"] == 7 and got["agree"] == 7
        assert float(got["gaps"].max()) < 1e-4
    s = eng.stats
    # the first four prompts are 4 + 8 + 29 + 40 = 81 tokens = 1 + 1 + 4 +
    # 5 = 11 rows of 8: three calls of 4 rows (an engine of 4 slots deals
    # 4 rows a call), not one call a chunk a lane; then 2 + 16 tokens
    assert s["prefill_tokens"] == sum(len(p) - 1 for p in reqs)
    assert s["prefill_calls"] <= 5
    layers = cfg["mamba_layers"]
    # chained: 3 of the 30-token prompt, 1 + 2 of the 41-token one (its
    # first row of the second call starts from the slot's stored state)
    assert s["ssm_rows_chained"] >= layers * 6
    assert s["ssm_prefill_rows"] >= layers * 11
    # every prompt token but the last goes in by a prefill call; a
    # request then takes a single-token step for each token it generates
    assert s["ssm_step_rows"] == layers * s["tokens_generated"]


def test_one_long_prompt_is_one_call_of_chained_rows():
    cfg = tiny_cfg()
    module, params = weights(cfg)
    (p,) = prompts(cfg["vocab_size"], (8 * CHUNK + 1,))
    eng = engine(module, params, slots=8)  # 8 slots: 8 rows a call
    eng.submit(0, p, 3)
    out = drain(eng)
    assert eng.stats["prefill_calls"] == 1
    assert eng.stats["prefill_tokens"] == 8 * CHUNK
    assert eng.stats["ssm_rows_chained"] == cfg["mamba_layers"] * 7
    got = ref.served_token_gaps(params, cfg, p, np.asarray(out[0], np.int32),
                                pad_to=module.max_len)
    assert got["agree"] == 3 and float(got["gaps"].max()) < 1e-4


# ----------------------------------------- what may not advance a state
def test_padding_and_spare_rows_advance_no_slots_state():
    """Straight at the module, as the engine's prefill program calls it:
    8 rows of which row 0 is slot 2 with 5 real tokens of 8 (3 padded),
    row 1 is slot 1 with NOTHING real, rows 2-7 spare (the scratch row).
    Slot 2 ends where 5 tokens alone would put it; every other slot's
    state and tail are bit-equal to what they were."""
    cfg = tiny_cfg()
    module, params = weights(cfg)
    eng = engine(module, params)
    rng = np.random.default_rng(0)
    before = jax.tree_util.tree_map(
        lambda c: jnp.asarray(rng.normal(size=c.shape), c.dtype), eng._cache)
    tok = np.zeros((8, CHUNK), np.int32)
    pos = np.zeros((8, CHUNK), np.int32)
    tok[0, :5] = rng.integers(0, cfg["vocab_size"], 5)
    tok[0, 5:] = tok[0, 4]
    pos[0] = np.minimum(np.arange(CHUNK), 4) + 16  # mid-sequence: not fresh
    tok[1], pos[1] = 9, 3
    slots = np.array([2, 1] + [4] * 6, np.int32)  # 4 = max_slots: scratch
    real = np.array([5, 0] + [0] * 6, np.int32)
    ptab = np.zeros((8, 4), np.int32)
    ptab[0] = [1, 2, 3, 4]

    def run(tok, pos, real):
        _, muts = module.apply(
            {"params": params, "cache": before}, jnp.asarray(tok),
            positions=jnp.asarray(pos), decode=True,
            page_tables=jnp.asarray(ptab), slot_ids=jnp.asarray(slots),
            row_tokens=jnp.asarray(real), mutable=["cache", "counters"])
        return muts["cache"]

    after = run(tok, pos, real)
    alone = run(tok[:, :5], pos[:, :5], real)  # the 5 tokens, no padding
    for kind in ("ssm", "conv"):
        for i in (0, 4):  # the two Mamba layers
            a, b, c = (np.asarray(t[f"block_{i}"]["mixer"][kind])
                       for t in (before, after, alone))
            for untouched in (0, 1, 3):
                assert np.array_equal(a[untouched], b[untouched]), kind
            assert not np.array_equal(a[2], b[2])
            # another chunk length sums in another order: roundoff
            assert np.abs(b[2] - c[2]).max() < 1e-5, kind
    # the padded tokens' keys went to the scratch page, not over slot 2's
    k_before, k_after = (np.asarray(t["block_2"]["mixer"]["k"])
                         for t in (before, after))
    assert np.array_equal(k_before[1:3], k_after[1:3])   # positions 0-15
    assert np.array_equal(k_before[3, 5:], k_after[3, 5:])
    assert not np.array_equal(k_before[3, :5], k_after[3, :5])


def test_empty_and_frozen_lanes_step_without_touching_their_state():
    """One request decodes in a 4-slot engine for 30 steps while three
    lanes are empty; then it finishes mid-scan (its lane re-feeds its
    last token for the rest of the fused call). No empty lane's state
    moves, and the finished lane's state is the one its LAST REAL token
    left: a second request of the same prompt plus the first's output
    reaches the same state at that position."""
    cfg = tiny_cfg()
    module, params = weights(cfg)
    (p,) = prompts(cfg["vocab_size"], (6,))
    eng = engine(module, params)
    zero = state_leaves(eng)
    eng.submit("a", p, 30)  # 5 prefilled, then 30 steps: 30 = 7 x 4 + 2
    out = drain(eng)
    held = state_leaves(eng)
    for path, leaf in held.items():
        assert np.array_equal(leaf[1:], zero[path][1:]), path
        assert not np.array_equal(leaf[0], zero[path][0]), path
    # the same 35 consumed tokens (the 30th output is never fed back), all
    # but the last by prefill, in a fresh engine
    again = engine(module, params)
    again.submit("b", np.concatenate([p, out["a"][:-1]]).astype(np.int32),
                 1)
    drain(again)
    for path, leaf in state_leaves(again).items():
        if "ssm" in path:  # chunked scan against 30 single steps: roundoff
            assert np.abs(leaf[0] - held[path][0]).max() < 1e-4, path


# ------------------------------------------------- slots change hands
def test_a_slots_next_occupant_and_a_preempted_resume_are_exact():
    cfg = tiny_cfg()
    module, params = weights(cfg)
    a, b, c = prompts(cfg["vocab_size"], (21, 13, 11))
    fresh = {}
    for rid, p, n in (("a", a, 12), ("b", b, 9), ("c", c, 6)):
        eng = engine(module, params, slots=1)
        eng.submit(rid, p, n)
        drain(eng, fresh)
    # one slot: b takes the lane a just left, state and all
    eng = engine(module, params, slots=1)
    eng.submit("a", a, 12)
    eng.submit("b", b, 9)
    assert drain(eng) == {"a": fresh["a"], "b": fresh["b"]}
    # one slot: a (background) is mid-generation when c (interactive)
    # arrives; a is evicted, c served, a resumes by re-prefilling its
    # prompt and its own output from position 0
    eng = engine(module, params, slots=1)
    eng.submit("a", a, 12, slo="background")
    eng.step()
    eng.step()
    eng.submit("c", c, 6, slo="interactive")
    out = drain(eng)
    assert eng.stats["preemptions"] >= 1
    assert out == {"a": fresh["a"], "c": fresh["c"]}


# ------------------------------------------------------ what is refused
def _dense_draft():
    from rafiki_tpu.models.llama_lora import Llama

    module = Llama(vocab_size=256, max_len=128, hidden_dim=32, depth=1,
                   n_heads=2, n_kv_heads=2, mlp_dim=64, lora_rank=0)
    return module, module.init(jax.random.PRNGKey(0),
                               jnp.zeros((1, 4), jnp.int32))["params"]


@pytest.mark.parametrize("what", ["host_tier", "prefix", "import_prefix",
                                  "kv_blob", "prefill_only", "draft",
                                  "speculation", "contiguous"])
def test_what_cannot_carry_per_slot_state_is_refused_by_name(what):
    cfg = tiny_cfg()
    module, params = weights(cfg)
    build = {
        "host_tier": lambda: engine(module, params, host_kv_pages=8),
        "draft": lambda: engine(module, params, speculate_k=3,
                                draft=_dense_draft()),
        "speculation": lambda: engine(module, params, speculate_k=3),
        "contiguous": lambda: engine(
            module.clone(kv_page_size=0, kv_pages=0), params),
    }
    named = {"host_tier": "host_kv_pages", "draft": "draft",
             "speculation": "speculate_k", "contiguous": "contiguous",
             "prefix": "register_prefix", "import_prefix": "import_prefix",
             "kv_blob": "kv_import", "prefill_only": "prefill_only"}[what]
    with pytest.raises(ValueError, match="per-slot state") as err:
        if what in build:
            build[what]()
        else:
            eng = engine(module, params)
            ids = np.arange(9, dtype=np.int32)
            {"prefix": lambda: eng.register_prefix(ids),
             "import_prefix": lambda: eng.import_prefix({}),
             "kv_blob": lambda: eng.submit(0, ids, 4, kv_import={}),
             "prefill_only": lambda: eng.submit(0, ids, 4,
                                                prefill_only=True)}[what]()
    assert named in str(err.value)


# ------------------------------------------------------------ the gauges
def test_pool_and_state_gauges_count_their_own_leaves():
    cfg = tiny_cfg()
    module, params = weights(cfg)
    eng = engine(module, params)
    # one attention layer: K and V of 2 kv heads x 16, float32
    assert eng.stats["kv_pool_bytes_per_token"] == 2 * 2 * 16 * 4
    # two Mamba layers: 8 heads x 8 x 16 float32 + a tail of 3 x (64 +
    # 2 x 2 x 16) float32 channels
    per_slot = 2 * (8 * 8 * 16 * 4 + 3 * 128 * 4)
    assert eng.stats["ssm_state_bytes_per_slot"] == per_slot
    eng.reset_stats()
    assert eng.stats["ssm_state_bytes_per_slot"] == per_slot
    from benchmark import costs_hybrid_ssm as costs

    assert costs.kv_bytes_per_token(cfg) == 2 * 2 * 16 * 4
    assert costs.ssm_state_bytes_per_slot(cfg) == per_slot


def test_a_dense_module_is_told_nothing_new():
    """A module without per-slot state: its gauge reads 0, and its
    programs are called as they were."""
    module, params = _dense_draft()
    eng = DecodeEngine(module.clone(kv_page_size=8, kv_pages=17), params,
                       max_slots=2, max_len=64, prefill_chunk=8)
    assert eng.stats["ssm_state_bytes_per_slot"] == 0
    eng.submit(0, np.arange(20, dtype=np.int32), 5)
    assert len(drain(eng)[0]) == 5


# ------------------------------------------------- the benchmark's driver
class _NoMonitor:
    in_window = 0

    def fence(self): pass
    def unfence(self): pass
    def report(self): return {}


def _ctx(tmp_path, seed, control=None, check_requests=8):
    cfg = tiny_cfg()
    # the Pallas interpreter is slow to compile at every table width: the
    # driver's runs take the gather (the engine test above keeps kernels)
    cfg["engine"].update(paged_kernel=False, expect_paged_kernel_mode=0)
    traffic = harness.load_json("traffic", "tiny-chat.json")
    traffic["check_requests"] = check_requests
    traffic["prompt_tokens"]["high"] = 60  # up to 8 chained rows of 8
    traffic["max_new_tokens"].update(low=8, high=24)
    return dict(
        cell={"name": "tiny-hybrid-ssm.tiny-chat",
              "config": "tiny-hybrid-ssm", "traffic": "tiny-chat",
              "chips": 1},
        seed=seed, seconds=3.0, rehearse=True, tracer=None, config=cfg,
        traffic=traffic, phases=harness.Phases(0.0), monitor=_NoMonitor(),
        work_dir=str(tmp_path), peaks=None, control=control)


def _bad(run):
    return {c["name"]: c for c in run["checks"] if not c["ok"]}


def test_driver_sound_run_is_correct(tmp_path):
    run = driver.run(_ctx(tmp_path, 21))
    assert not _bad(run), _bad(run)
    counters = run["counters"]
    assert counters["ssm_rows_chained"] > 0
    assert counters["ssm_step_rows"] > 0
    assert counters["moe_assignments_held"] > 0
    assert counters["ssm_state_bytes_per_slot"] > 0
    assert set(run["end_to_end"]) == {"setup_s", "serve_tokens_per_s"}
    assert run["window"]["ttft_p95_ms"] > 0


def test_driver_control_is_not_correct(tmp_path):
    ctx = _ctx(tmp_path, 31, check_requests=24)
    ctx["control"] = ctx["config"]["control_precision"]
    bad = _bad(driver.run(ctx))
    # not correct by one of the limits, not by each
    assert bad and set(bad) <= set(driver.COMPARED)
    gap = bad["served_token_logit_gap_mean"]
    assert gap["value"] > 3 * gap["limit"]
