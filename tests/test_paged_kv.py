"""Paged (block-table) KV serving — ISSUE 5 tentpole; ISSUE 10 adds
the kernel-vs-gather equivalence suite at the bottom.

Per layer, decode K/V live in a ``(kv_pages, page_size, heads, dh)``
pool; each slot maps logical pages → pool pages via a host page table
fed to the compiled step (static shapes, no recompiles). The oracle
throughout is the CONTIGUOUS engine on the same weights: the paged
engine must be token-BIT-EXACT on mixed-length traffic across greedy,
sampled, int8-KV, multi-adapter, and speculative decoding, while
allocating pages lazily (positions, not max_len), backpressuring
admission on the pool without deadlock, and freeing everything at
completion/reset.
"""

import threading

import numpy as np
import pytest

from rafiki_tpu.models.llama_lora import LlamaLoRA, stack_lora_adapters
from rafiki_tpu.serving.decode_engine import DecodeEngine

from test_decode_engine import KNOBS  # noqa: F401 — shared knobs
from test_multi_adapter import _lora_variant  # noqa: F401

L = int(KNOBS["max_len"])
PS = 8  # page size used throughout (divides max_len=32 into 4 tables)


def _mixed_reqs(n=8, seed=0, max_new=6, vocab=64):
    """Deterministic mixed-length traffic: prompts 2..14 tokens."""
    rng = np.random.default_rng(seed)
    return [(r, rng.integers(1, vocab,
                             size=int(rng.integers(2, 15))
                             ).astype(np.int32), max_new)
            for r in range(n)]


def _drain(eng, reqs, submit_kw=None):
    for i, (rid, p, mn) in enumerate(reqs):
        eng.submit(rid, p, mn, **(submit_kw(i) if submit_kw else {}))
    done = {}
    for _ in range(600):
        eng.step()
        done.update(dict(eng.poll()))
        if len(done) == len(reqs):
            return done
    raise AssertionError(f"undrained: {sorted(done)} / {eng.stats}")


def _nbytes(cache):
    """Measured bytes of an engine's decode cache."""
    import jax

    return sum(int(np.prod(v.shape)) * v.dtype.itemsize
               for v in jax.tree_util.tree_leaves(cache))


def _pair(trained, reqs, pages, engine_kw=None, submit_kw=None,
          module_kw=None, params=None):
    """(contiguous outputs, paged outputs, paged engine) on identical
    traffic — the parity harness every test below goes through."""
    engine_kw = engine_kw or {}
    module_kw = module_kw or {}
    params = trained._params if params is None else params
    contig = DecodeEngine(trained._module(**module_kw), params,
                          max_slots=4, max_len=L, **engine_kw)
    paged = DecodeEngine(
        trained._module(kv_page_size=PS, kv_pages=pages, **module_kw),
        params, max_slots=4, max_len=L, **engine_kw)
    ref = _drain(contig, reqs, submit_kw)
    got = _drain(paged, reqs, submit_kw)
    assert got == ref, (got, ref)
    return ref, got, paged


def test_paged_matches_contiguous_mixed_greedy(trained):
    """8 mixed-length greedy requests through 4 slots and a TIGHT pool
    (stalls expected): token-bit-exact, pages recycle to zero."""
    _, _, eng = _pair(trained, _mixed_reqs(8), pages=9)
    s = eng.stats
    assert s["kv_pages_total"] == 8
    assert 0 < s["kv_pages_high_water"] <= 8
    assert s["kv_pages_used"] == 0          # drained → all pages freed
    assert len(eng._free_pages) == 8        # allocator agrees
    assert s["max_concurrent"] >= 2         # traffic really overlapped


def test_paged_matches_contiguous_fused_and_chunked(trained):
    """Parity holds across steps_per_sync/prefill_chunk combinations
    (the fused-scan and chunked-prefill write paths both page)."""
    reqs = _mixed_reqs(6, seed=3)
    for kw in ({"steps_per_sync": 1, "prefill_chunk": 1},
               {"steps_per_sync": 3, "prefill_chunk": 4}):
        _pair(trained, reqs, pages=9, engine_kw=kw)


def test_paged_sampled_parity(trained):
    """Seeded sampling draws are position-keyed, so the paged engine
    must reproduce the contiguous engine's sampled streams exactly —
    greedy and sampled slots mixed in one batch."""

    def samp(i):
        if i % 2 == 0:
            return {}
        return {"temperature": 0.9, "top_k": 8, "top_p": 0.95,
                "seed": 100 + i}

    _pair(trained, _mixed_reqs(6, seed=1), pages=9, submit_kw=samp)


def test_paged_int8_kv_parity_and_pool_bytes(trained):
    """int8 KV pages identically (int8 pools + f32 scale pools): exact
    parity within the quantized world, and the paged pool's measured
    bytes sit well under the contiguous int8 cache's."""
    m8 = LlamaLoRA(**{**KNOBS, "kv_cache_int8": True})
    m8._params = trained._params
    reqs = _mixed_reqs(6, seed=2)
    contig = DecodeEngine(m8._module(), m8._params, max_slots=4,
                          max_len=L)
    paged = DecodeEngine(m8._module(kv_page_size=PS, kv_pages=9),
                         m8._params, max_slots=4, max_len=L)
    assert _drain(contig, reqs) == _drain(paged, reqs)

    # 9 pages * 8 positions = 72 vs 4 slots * 32 = 128 positions
    assert _nbytes(paged._cache) < 0.6 * _nbytes(contig._cache)


def test_worst_case_pool_fills_every_slot_on_fewer_bytes(trained):
    """A pool sized to the traffic's worst case (prompt + max_new a
    request, not max_len a slot) keeps all 4 slots busy and never
    stalls an admission, token-exact, on fewer measured cache bytes
    than the contiguous engine's max_slots x max_len."""
    pages = 1 + 4 * 3  # scratch + 3 pages (<= 14 + 6 tokens) a slot
    _, _, paged = _pair(trained, _mixed_reqs(12, seed=4), pages=pages)
    s = paged.stats
    assert s["admission_stalls"] == 0, dict(s)
    assert s["max_concurrent"] == 4
    assert s["kv_pages_high_water"] <= pages - 1
    # 13 pages * 8 positions = 104 vs 4 slots * 32 = 128 positions
    contig = DecodeEngine(trained._module(), trained._params,
                          max_slots=4, max_len=L)
    assert _nbytes(paged._cache) < _nbytes(contig._cache)


def test_paged_multi_adapter_parity(trained):
    """Mixed-adapter batches on one paged pool: every request matches
    the contiguous stacked engine token-for-token."""
    stacked = stack_lora_adapters(
        [trained._params, _lora_variant(trained._params)])
    _pair(trained, _mixed_reqs(6, seed=4), pages=9,
          module_kw={"n_adapters": 2}, params=stacked,
          submit_kw=lambda i: {"adapter_id": i % 2})


def test_paged_speculative_parity(trained):
    """Greedy speculation (prompt-lookup drafting) over a paged cache:
    lossless vs the contiguous speculative engine AND vs plain paged
    decoding; the verify path's multi-token window writes page."""
    reqs = [(0, np.asarray([1, 7, 2, 7, 2, 7, 2], np.int32), 8),
            (1, np.asarray([1, 5, 9, 13], np.int32), 8),
            (2, np.asarray([1, 3], np.int32), 8)]
    ref, _, _ = _pair(trained, reqs, pages=13)  # plain paged == contig
    _, spec, eng = _pair(trained, reqs, pages=13,
                         engine_kw={"speculate_k": 4})
    assert spec == ref
    assert eng.stats["spec_calls"] > 0


def test_paged_prefix_cache_parity(trained):
    """register_prefix on a paged engine: the snapshot computes through
    a contiguous twin and scatters into the hit slots' pages — hits
    stay exact and still skip the prefix's prefill."""
    module = trained._module(kv_page_size=PS, kv_pages=9)
    prefix = np.asarray([1, 5, 9, 13, 2], np.int32)
    prompts = {"hit": np.concatenate([prefix, [7, 4]]).astype(np.int32),
               "miss": np.asarray([2, 5, 9, 3], np.int32)}

    def run(register):
        eng = DecodeEngine(module, trained._params, max_slots=2,
                           max_len=L)
        if register:
            assert eng.register_prefix(prefix) == len(prefix)
        return (_drain(eng, [(n, p, 6) for n, p in prompts.items()]),
                eng.stats)

    plain, _ = run(False)
    cached, stats = run(True)
    assert cached == plain
    assert stats["prefix_hits"] == 1


def test_page_backpressure_waits_without_deadlock(trained):
    """A pool that fits ONE request at a time serves a 3-deep queue
    sequentially: admissions wait (stall counter moves), nothing
    deadlocks, every completion frees its pages for the next."""
    module = trained._module(kv_page_size=PS, kv_pages=3)  # 2 usable
    eng = DecodeEngine(module, trained._params, max_slots=4, max_len=L)
    reqs = [(r, np.asarray([1, 5 + r, 9], np.int32), 8)
            for r in range(3)]  # stop=10 → 2 pages each, pool-filling
    done = _drain(eng, reqs)
    solo = DecodeEngine(trained._module(), trained._params,
                        max_slots=1, max_len=L)
    assert done == _drain(solo, reqs)
    s = eng.stats
    assert s["admission_stalls"] > 0
    assert s["max_concurrent"] == 1         # the pool, not slots, bound
    assert s["kv_pages_used"] == 0 and len(eng._free_pages) == 2


def test_submit_rejects_request_larger_than_pool(trained):
    """A request whose worst case exceeds the WHOLE pool would stall
    the FIFO queue forever — submit refuses it loudly instead."""
    module = trained._module(kv_page_size=PS, kv_pages=3)
    eng = DecodeEngine(module, trained._params, max_slots=2, max_len=L)
    with pytest.raises(ValueError, match="KV pages"):
        eng.submit("big", np.arange(1, 20, dtype=np.int32), 12)


@pytest.mark.parametrize("lanes,kw", [(2, {}), (3, {"prefill_chunk": 4}),
                                      (2, {"speculate_k": 3})])
def test_prefill_gathers_the_lanes_with_prompt_left(trained, monkeypatch,
                                                    lanes, kw):
    """A paged engine's prefill call computes ``PREFILL_LANES`` rows —
    the lanes that have prompt left, the others taking the next call —
    not one row a slot: six requests admitted at once into six slots
    with fewer rows than that are token-exact with the contiguous
    engine (one row a slot), in more calls, and leave no page behind."""
    from rafiki_tpu.serving import decode_engine

    monkeypatch.setattr(decode_engine, "PREFILL_LANES", lanes)
    reqs = _mixed_reqs(6, seed=3)
    contig = DecodeEngine(trained._module(), trained._params,
                          max_slots=6, max_len=L, **kw)
    paged = DecodeEngine(
        trained._module(kv_page_size=PS, kv_pages=25),
        trained._params, max_slots=6, max_len=L, **kw)
    assert (contig._prefill_lanes, paged._prefill_lanes) == (0, lanes)
    assert _drain(paged, reqs) == _drain(contig, reqs)
    assert paged.stats["prefill_tokens"] == contig.stats["prefill_tokens"]
    assert paged.stats["prefill_calls"] > contig.stats["prefill_calls"]
    assert paged.stats["kv_pages_used"] == 0
    # an engine narrower than the rows has a row a slot
    small = DecodeEngine(
        trained._module(kv_page_size=PS, kv_pages=9),
        trained._params, max_slots=2, max_len=L, **kw)
    assert small._prefill_lanes == 2


def test_one_long_prompt_fills_a_prefill_call_with_its_chunks(trained):
    """A lane takes a row for every chunk it has left: a 27-token prompt
    alone on a paged engine is ONE call of 7 rows x 4 tokens (26 tokens:
    the last is the scan's), where the contiguous engine, a row a slot,
    makes 7 — token-exact, because every layer writes the call's rows to
    the pool before any row attends. Two such prompts are 14 rows: the
    first takes 7 of a call's 8, the second the eighth and the next
    call."""
    prompt = np.arange(3, 30, dtype=np.int32)
    reqs = [("long", prompt, 4)]
    kw = dict(max_slots=8, max_len=L, prefill_chunk=4)
    contig = DecodeEngine(trained._module(), trained._params, **kw)
    paged = DecodeEngine(trained._module(kv_page_size=PS, kv_pages=33),
                         trained._params, **kw)
    assert _drain(paged, reqs) == _drain(contig, reqs)
    assert paged.stats["prefill_tokens"] == 26
    assert (contig.stats["prefill_calls"], paged.stats["prefill_calls"]
            ) == (7, 1)
    two = [("a", prompt, 4), ("b", prompt[::-1].copy(), 3)]
    contig.reset_stats()
    paged.reset_stats()
    assert _drain(paged, two) == _drain(contig, two)
    assert paged.stats["prefill_tokens"] == 52
    assert paged.stats["prefill_calls"] == 2  # 7 + 1 rows, then 6


def test_lazy_allocation_tracks_positions(trained):
    """Pages are allocated as positions cross boundaries — mid-flight a
    long-generation slot holds fewer pages than its reservation — and
    chunked prefill of a prompt longer than one page maps pages chunk
    by chunk, with output parity against the contiguous engine."""
    module = trained._module(kv_page_size=PS, kv_pages=9)
    # long prompt (19 tokens > 2 pages) through chunked prefill
    long_prompt = np.arange(1, 20, dtype=np.int32)
    reqs = [("lp", long_prompt, 5)]
    contig = DecodeEngine(trained._module(), trained._params,
                          max_slots=2, max_len=L, prefill_chunk=8)
    paged = DecodeEngine(module, trained._params, max_slots=2,
                         max_len=L, prefill_chunk=8)
    assert _drain(paged, reqs) == _drain(contig, reqs)
    assert paged.stats["prefill_calls"] >= 1  # took the chunked path
    assert paged.stats["kv_pages_high_water"] == 3  # 23 positions

    # long generation: after ONE fused call the slot holds pages for
    # where it IS (position ~K), not its full reservation
    eng = DecodeEngine(module, trained._params, max_slots=2, max_len=L,
                       steps_per_sync=4, prefill_chunk=1)
    eng.submit("g", np.asarray([1, 5], np.int32), 20)  # stop=21: 3 pages
    eng.step()
    assert int(eng._n_res[0]) == 3
    assert int(eng._n_alloc[0]) < 3         # lazy: only ~K positions in
    while eng.busy:
        eng.step()
    eng.poll()
    assert int(eng._n_alloc[0]) == 0 and eng.stats["kv_pages_used"] == 0


def test_paged_reset_frees_pool(trained):
    """reset() mid-flight returns every page and reservation, and the
    rebuilt engine serves fresh traffic correctly."""
    module = trained._module(kv_page_size=PS, kv_pages=9)
    eng = DecodeEngine(module, trained._params, max_slots=4, max_len=L)
    for r, p, mn in _mixed_reqs(4, seed=5):
        eng.submit(r, p, mn)
    eng.step()
    assert eng.stats["kv_pages_used"] > 0
    eng.reset()
    assert eng.stats["kv_pages_used"] == 0
    assert len(eng._free_pages) == 8 and eng._res_total == 0
    assert not eng._ptab.any()
    reqs = _mixed_reqs(3, seed=6)
    ref = _drain(DecodeEngine(trained._module(), trained._params,
                              max_slots=4, max_len=L), reqs)
    assert _drain(eng, reqs) == ref


def test_estimator_models_page_pool(trained):
    """estimate_serving_device_bytes(kv_page_size, kv_pages): the
    kv_cache term equals the PAGED ENGINE'S measured pool bytes (f32
    and int8 flavors), and the kv_pages=0 default mirrors the engine's
    full-coverage default."""
    def cache_bytes(model, **mk):
        return _nbytes(DecodeEngine(model._module(**mk), model._params,
                                    max_slots=4, max_len=L)._cache)

    b = trained.estimate_serving_device_bytes(
        max_slots=4, kv_page_size=PS, kv_pages=9)
    assert b["kv_cache"] == cache_bytes(trained, kv_page_size=PS,
                                        kv_pages=9)
    m8 = LlamaLoRA(**{**KNOBS, "kv_cache_int8": True})
    m8._params = trained._params
    b8 = m8.estimate_serving_device_bytes(
        max_slots=4, kv_page_size=PS, kv_pages=9)
    assert b8["kv_cache"] == cache_bytes(m8, kv_page_size=PS,
                                         kv_pages=9)
    # default pool (kv_pages=0) = scratch + full coverage, exactly what
    # make_decode_engine builds
    bd = trained.estimate_serving_device_bytes(max_slots=4,
                                               kv_page_size=PS)
    full = 1 + 4 * (L // PS)
    assert bd["kv_cache"] == cache_bytes(trained, kv_page_size=PS,
                                         kv_pages=full)
    # and a sized-down pool really is the smaller admission number
    assert b["kv_cache"] < bd["kv_cache"] < \
        trained.estimate_serving_device_bytes(max_slots=4)["kv_cache"] \
        + b["kv_cache"]
    # the estimator enforces the ENGINE'S validity rules: admission
    # must never bless a pool geometry the engine build will refuse
    with pytest.raises(ValueError, match="divide max_len"):
        trained.estimate_serving_device_bytes(max_slots=4,
                                              kv_page_size=5)
    with pytest.raises(ValueError, match="kv_pages >= 2"):
        trained.estimate_serving_device_bytes(
            max_slots=4, kv_page_size=PS, kv_pages=1)


def test_worker_admission_consumes_paged_estimate(trained, monkeypatch):
    """Both inference-worker deployment paths (single-trial decode loop
    and multi-adapter) hand the page-pool geometry to the estimator: a
    device limit sized between the paged and contiguous footprints
    refuses the contiguous boot and admits the paged one."""
    from rafiki_tpu.serving.queues import InProcQueueHub
    from rafiki_tpu.store.param_store import ParamStore
    from rafiki_tpu.worker.inference import InferenceWorker

    store = ParamStore.from_uri("mem://")
    store.save("t0", trained.dump_parameters())
    variant = LlamaLoRA(**KNOBS)
    dump = dict(trained.dump_parameters())
    dump["params"] = _lora_variant(trained._params)
    variant.load_parameters(dump)
    store.save("t1", variant.dump_parameters())

    paged = trained.estimate_serving_device_bytes(
        max_slots=4, kv_page_size=PS, kv_pages=9)["total"]
    contig = trained.estimate_serving_device_bytes(max_slots=4)["total"]
    assert paged < contig
    limit = (paged + contig) // 2
    monkeypatch.setenv("RAFIKI_DEVICE_HBM_BYTES", str(limit))

    def boot(**kw):
        return InferenceWorker(LlamaLoRA, "t0", KNOBS, store,
                               InProcQueueHub(), "w0", decode_loop=True,
                               max_slots=4, max_new_tokens=4, **kw)

    with pytest.raises(ValueError, match="admission control"):
        boot()                                  # contiguous: too big
    w = boot(kv_page_size=PS, kv_pages=9)       # paged: fits
    assert w.engine.engine.paged
    # pool stats flow worker → hub (→ /health → dashboard)
    w._publish_stats()
    s = w.hub.get_worker_stats("w0")
    assert s["engine_kv_pages_total"] == 8
    assert "engine_admission_stalls" in s
    # kernel-vs-gather visibility rides the same plane: the dispatch
    # gauge publishes (gather on CPU tier-1) and the worker's /metrics
    # carries the decode_step_seconds histogram the kernel difference
    # shows up in
    assert s["engine_paged_kernel_mode"] == 0
    prom = w.metrics.render_prometheus()
    assert "decode_step_seconds" in prom
    assert "paged_kernel_mode" in prom
    assert "paged_kernel_window_tokens" in prom
    assert "paged_kernel_step_tokens" in prom
    # multi-adapter path: same limit arithmetic through its estimator
    # call (re-centred between ITS paged/contiguous totals — the
    # stacked adapters add a term of their own)
    paged_ma = trained.estimate_serving_device_bytes(
        max_slots=4, n_extra_adapters=1, kv_page_size=PS,
        kv_pages=9)["total"]
    contig_ma = trained.estimate_serving_device_bytes(
        max_slots=4, n_extra_adapters=1)["total"]
    monkeypatch.setenv("RAFIKI_DEVICE_HBM_BYTES",
                       str((paged_ma + contig_ma) // 2))
    with pytest.raises(ValueError, match="admission control"):
        boot(extra_adapter_trials=["t1"])
    w2 = boot(extra_adapter_trials=["t1"], kv_page_size=PS, kv_pages=9)
    assert w2.engine.engine.paged
    assert w2.engine.engine.n_adapters == 2


def _kernel_vs_gather(trained, reqs, engine_kw=None, submit_kw=None,
                      module_kw=None, params=None, pages=9):
    """Same paged traffic through the gather fallback and the Pallas
    block-table kernels (forced on — the interpreter on CPU): tokens
    must match exactly, and the obs mode gauge must tell the paths
    apart (0 = gather, 2 = step + window kernels; prefill and verify
    windows dispatch through the window kernel too). Returns the
    kernel run's outputs and its pre-scrub stats snapshot."""
    engine_kw = engine_kw or {}
    module_kw = module_kw or {}
    params = trained._params if params is None else params
    outs = {}
    kstats = None
    for flag in (False, True):
        eng = DecodeEngine(
            trained._module(kv_page_size=PS, kv_pages=pages,
                            paged_kernel=flag, **module_kw),
            params, max_slots=4, max_len=L, **engine_kw)
        outs[flag] = _drain(eng, reqs, submit_kw)
        assert eng.stats["paged_kernel_mode"] == (2 if flag else 0)
        if flag:
            kstats = eng.stats_snapshot()
        else:
            # the gather engine's kernel token counters must not move
            assert eng.stats["paged_kernel_step_tokens"] == 0
            assert eng.stats["paged_kernel_window_tokens"] == 0
        eng.reset_stats()  # the worker's warmup scrub keeps the gauge
        assert eng.stats["paged_kernel_mode"] == (2 if flag else 0)
        assert eng.stats["paged_kernel_step_tokens"] == 0
    assert outs[True] == outs[False], (outs[True], outs[False])
    return outs[True], kstats


def test_kernel_matches_gather_greedy_and_sampled(trained):
    """ISSUE 10 equivalence bar, greedy + seeded-sampled lanes: the
    kernel's single-token steps interleave with chunked prefill (which
    keeps the gather) and both engines emit identical tokens."""
    def samp(i):
        if i % 2 == 0:
            return {}
        return {"temperature": 0.9, "top_k": 8, "top_p": 0.95,
                "seed": 100 + i}

    _kernel_vs_gather(trained, _mixed_reqs(6, seed=7))
    _kernel_vs_gather(trained, _mixed_reqs(6, seed=8), submit_kw=samp,
                      engine_kw={"steps_per_sync": 3,
                                 "prefill_chunk": 4})


def test_kernel_matches_gather_int8_kv(trained):
    """int8-KV pools: the kernel dequantizes in-register off the SAME
    scale rows the gather path reads — tokens match the gather engine
    exactly (the logits-close bar collapses to token-equal here)."""
    m8 = LlamaLoRA(**{**KNOBS, "kv_cache_int8": True})
    m8._params = trained._params
    _kernel_vs_gather(m8, _mixed_reqs(6, seed=9))


def test_kernel_matches_gather_multi_adapter(trained):
    """Mixed-adapter batches: per-row adapters change q/k/v, not the
    page walk — kernel tokens match the gather engine per tenant."""
    stacked = stack_lora_adapters(
        [trained._params, _lora_variant(trained._params)])
    _kernel_vs_gather(trained, _mixed_reqs(6, seed=10),
                      module_kw={"n_adapters": 2}, params=stacked,
                      submit_kw=lambda i: {"adapter_id": i % 2})


def test_kernel_matches_gather_speculative(trained):
    """Prompt-lookup speculation: scan steps take the step kernel AND
    verify windows take the WINDOW kernel — the interleaving is still
    greedy-lossless and token-identical to the all-gather engine, and
    the window-token counter proves the verify windows actually rode
    the kernel."""
    reqs = [(0, np.asarray([1, 7, 2, 7, 2, 7, 2], np.int32), 8),
            (1, np.asarray([1, 5, 9, 13], np.int32), 8),
            (2, np.asarray([1, 3], np.int32), 8)]
    out, ks = _kernel_vs_gather(trained, reqs, pages=13,
                                engine_kw={"speculate_k": 4})
    assert out  # all three drained through the all-kernel path
    assert ks["spec_calls"] > 0
    # every verify call pushed a k-wide window per live lane through
    # the window kernel (k=4, >= 1 live lane per call)
    assert ks["paged_kernel_window_tokens"] >= 4 * ks["spec_calls"]


def test_kernel_matches_gather_draft_model_verify(trained):
    """Draft-MODEL speculation on a paged target: the draft's own
    contiguous mirror passes stay off the paged kernels, but the
    TARGET's verify window must dispatch through the window kernel —
    token-identical to the all-gather engine."""
    perfect = LlamaLoRA(**KNOBS)
    perfect.load_parameters(trained.dump_parameters())
    reqs = [(0, np.asarray([1, 7, 2, 7, 2, 7, 2], np.int32), 8),
            (1, np.asarray([1, 5, 9, 13], np.int32), 8)]
    outs = {}
    kstats = None
    for flag in (False, True):
        eng = trained.make_decode_engine(
            max_slots=4, max_new_tokens=8, speculate_k=4,
            draft_model=perfect, kv_page_size=PS, kv_pages=13,
            paged_kernel=flag).engine
        for rid, p, mn in reqs:
            eng.submit(rid, p, mn)
        done = {}
        for _ in range(600):
            eng.step()
            done.update(dict(eng.poll()))
            if len(done) == len(reqs):
                break
        assert len(done) == len(reqs), (flag, sorted(done))
        outs[flag] = done
        assert eng.stats["paged_kernel_mode"] == (2 if flag else 0)
        if flag:
            kstats = eng.stats_snapshot()
    assert outs[True] == outs[False], (outs[True], outs[False])
    assert kstats["spec_draft_model_calls"] > 0
    assert kstats["paged_kernel_window_tokens"] >= \
        4 * kstats["spec_draft_model_calls"]


def test_windowed_prefill_kernel_exact_and_counters(trained):
    """Chunked prefill dispatches through the window kernel: long
    prompts ingest token-exact vs the gather engine, and every prefill
    token is accounted to ``paged_kernel_window_tokens`` (no spec
    traffic here, so the two counters must agree exactly) while the
    fused scan keeps feeding ``paged_kernel_step_tokens``."""
    reqs = [("lp", np.arange(1, 20, dtype=np.int32), 5),
            ("sp", np.asarray([3, 1, 4, 1, 5], np.int32), 5)]
    _, ks = _kernel_vs_gather(trained, reqs,
                              engine_kw={"prefill_chunk": 8})
    assert ks["prefill_calls"] >= 1
    assert ks["paged_kernel_window_tokens"] == ks["prefill_tokens"] > 0
    assert ks["paged_kernel_step_tokens"] > 0


def test_window_escape_hatch_forces_step_only_mode(trained, monkeypatch):
    """RAFIKI_PAGED_KERNEL_WINDOWS=0: the engine reports step-only mode
    (gauge 1), window traffic goes back to the gather (window-token
    counter stays 0) while the s==1 hot loop keeps the step kernel —
    and tokens stay exact vs the all-gather engine. A fresh pool
    geometry (pages=11) keeps the cached compiled fns from other tests
    (traced with windows enabled) out of this engine."""
    monkeypatch.setenv("RAFIKI_PAGED_KERNEL_WINDOWS", "0")
    reqs = _mixed_reqs(5, seed=11)
    outs = {}
    for flag in (False, True):
        eng = DecodeEngine(
            trained._module(kv_page_size=PS, kv_pages=11,
                            paged_kernel=flag),
            trained._params, max_slots=4, max_len=L, prefill_chunk=8)
        outs[flag] = _drain(eng, reqs)
        assert eng.stats["paged_kernel_mode"] == (1 if flag else 0)
        assert eng.stats["paged_kernel_window_tokens"] == 0
        if flag:
            assert eng.stats["paged_kernel_step_tokens"] > 0
    assert outs[True] == outs[False]


def test_multi_token_gather_window_rides_live_width_slice(trained):
    """Satellite: the gather-fallback prefill window consumes the
    engine's LIVE-WIDTH page-table slice (and the width-following
    mask), not the full table — off-TPU prefill must not gather dead
    pages. Page size 4 gives an 8-wide table of which this traffic
    can only ever light up half."""
    module = trained._module(kv_page_size=4, kv_pages=17,
                             paged_kernel=False)
    eng = DecodeEngine(module, trained._params, max_slots=4, max_len=L,
                       prefill_chunk=8)
    widths = []
    orig = eng._ptab_arg

    def spy():
        out = orig()
        widths.append(int(out.shape[1]))
        return out

    eng._ptab_arg = spy
    eng.submit("lp", np.arange(1, 11, dtype=np.int32), 4)  # 14 positions
    while eng.busy:
        eng.step()
    eng.poll()
    assert widths, "no compiled call consumed the table"
    assert max(widths) <= 4 < eng._n_table  # live slice, never full width


def test_paged_worker_serves_end_to_end(trained):
    """A paged decode-loop worker serves overlapping messages through
    the queue hub identically to a contiguous worker."""
    from rafiki_tpu.serving.predictor import Predictor
    from rafiki_tpu.serving.queues import InProcQueueHub
    from rafiki_tpu.store.param_store import ParamStore
    from rafiki_tpu.worker.inference import InferenceWorker

    store = ParamStore.from_uri("mem://")
    store.save("t0", trained.dump_parameters())
    queries = ["tok1 tok2 tok3", "tok4 tok5"]

    def serve(**kw):
        hub = InProcQueueHub()
        worker = InferenceWorker(LlamaLoRA, "t0", KNOBS, store, hub,
                                 "w0", decode_loop=True, max_slots=4,
                                 max_new_tokens=5, **kw)
        wt = threading.Thread(target=worker.run, daemon=True)
        wt.start()
        try:
            preds, info = Predictor(hub, ["w0"],
                                    gather_timeout=120.0).predict(queries)
            assert info["workers_answered"] == 1
            return preds, worker
        finally:
            worker.stop()
            wt.join(timeout=10)

    ref, _ = serve()
    got, worker = serve(kv_page_size=PS, kv_pages=9)
    assert got == ref
    assert worker.engine.engine.stats["kv_pages_used"] == 0
