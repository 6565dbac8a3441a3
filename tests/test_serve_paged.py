"""Paged KV serving tests: page-table indirection vs the whole-slot slab
engine (token-identical greedy), prefix-cache bit-identity and refcount
safety, LRU eviction, chunked prefill interleaving, and speculative
decoding (token-identical, >1 accepted/verify).  Multi-device variants
run in subprocesses via testing/checks.py.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.serve import PagedKVPool, ServeEngine, steps
from repro.testing.subproc import run_checks
from repro.train.policy import make_policy
from repro.train.state import param_specs


@pytest.fixture(scope="module")
def served():
    """(model, mesh, params) — tiny dense arch, f32 for determinism."""
    mesh = make_mesh((1,), ("model",))
    arch = get_config("qwen3-0.6b").reduced()
    pol = make_policy(arch, mesh.axis_names, param_dtype=jnp.float32,
                      compute_dtype=jnp.float32)
    model = Model(arch, pol.zcfg, world=1)
    params = model.init_params(jax.random.PRNGKey(0), dtype=jnp.float32)
    p_specs = param_specs(model, tuple(mesh.axis_names))
    params = {k: jax.device_put(v, NamedSharding(mesh, p_specs[k]))
              for k, v in params.items()}
    return model, mesh, params


JOBS = [(5, 6), (11, 4), (8, 5), (3, 7)]      # (prompt_len, max_new) x4
KV = 32
PAGE = 8


def _prompts(arch, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, arch.vocab, p).astype(np.int32) for p, _ in JOBS]


def _slab_results(model, mesh, params, prompts, jobs):
    eng = ServeEngine(model, mesh, params, n_slots=3, kv_len=KV)
    uids = [eng.submit(pr, max_new_tokens=n)
            for pr, (_, n) in zip(prompts, jobs)]
    return uids, eng.run(max_steps=200)


def _paged_engine(model, mesh, params, **kw):
    kw.setdefault("n_slots", 3)
    kw.setdefault("kv_len", KV)
    kw.setdefault("pool", "paged")
    kw.setdefault("page_size", PAGE)
    kw.setdefault("chunk_size", PAGE)
    kw.setdefault("cache_dtype", jnp.float32)
    return ServeEngine(model, mesh, params, **kw)


def test_paged_engine_matches_slab_greedy(served):
    """The paged engine (page-table indirection, chunked prefill) must
    emit, per request, exactly the token stream of the slab engine —
    continuous batching, staggered admission and all."""
    model, mesh, params = served
    prompts = _prompts(model.cfg)
    s_uids, s_res = _slab_results(model, mesh, params, prompts, JOBS)
    eng = _paged_engine(model, mesh, params)
    p_uids = [eng.submit(pr, max_new_tokens=n)
              for pr, (_, n) in zip(prompts, JOBS)]
    p_res = eng.run(max_steps=200)
    for su, pu in zip(s_uids, p_uids):
        assert p_res[pu] == s_res[su], (pu, p_res[pu], s_res[su])
    # full drain: every page unpinned (cached pages may park in the LRU)
    assert eng.pool.n_free == 3
    assert (eng.pool.refcount == 0).all()


def _chunked_prefill(pool, step, params, prompt, chunk, max_new=4):
    """Drive pool + jitted paged step directly through a chunked prefill;
    returns (slot, matched, logits_row) with logits_row the last prompt
    token's logits (np.float32, bitwise-comparable)."""
    res = pool.alloc(prompt, max_new, align=chunk)
    assert res is not None
    slot, matched = res
    P = len(prompt)
    done = matched
    last = None
    while done < P:
        end = min(done + chunk, P)
        toks = np.zeros((1, chunk), np.int32)
        toks[0, : end - done] = prompt[done:end]
        logits, pool.caches = step.fn(
            params, pool.caches, {"tokens": jnp.asarray(toks)},
            jnp.asarray(pool.table[slot: slot + 1]),
            jnp.asarray([done], jnp.int32))
        if end >= P:
            last = np.asarray(logits[0, (P - 1) - done])
        done = end
    pool.lengths[slot] = P
    pool.register_prefix(slot, prompt)
    return slot, matched, last


def test_prefix_hit_bitwise_identical_logits(served):
    """A prefix-cache hit skips the matched chunks but must produce the
    SAME memory as the cold prefill — the recomputed final chunk then
    yields bitwise-identical first-token logits (same pages, same chunk
    boundaries, same fixed attention view)."""
    model, mesh, params = served
    pool = PagedKVPool(model, mesh, n_slots=2, kv_len=KV, page_size=PAGE,
                       kv_axes=("model",), dtype=jnp.float32)
    step = steps.build_paged_step(model, mesh, ("model",), donate=False)
    rng = np.random.default_rng(7)
    prompt = rng.integers(0, model.cfg.vocab, 20).astype(np.int32)

    slot, matched, cold = _chunked_prefill(pool, step, params, prompt, PAGE)
    assert matched == 0
    pool.free(slot)            # full prompt pages park in the LRU
    assert pool.counters["prefix_hits"] == 0

    slot2, matched2, warm = _chunked_prefill(pool, step, params, prompt, PAGE)
    # 20 tokens / page 8: pages 0,1 are full prompt pages -> 16 matched
    assert matched2 == 16
    assert pool.counters["prefix_hits"] == 1
    assert pool.counters["prefix_tokens_reused"] == 16
    np.testing.assert_array_equal(cold, warm)


def test_refcounted_pages_never_reclaimed_while_referenced(served):
    """Two live slots sharing prefix pages: freeing one must keep the
    shared pages out of the free list AND out of the LRU until the last
    reference drops; eviction only ever claims refcount-0 pages."""
    model, mesh, params = served
    # 4 slots x 4 pages capacity but only 8 physical pages: real pressure
    pool = PagedKVPool(model, mesh, n_slots=4, kv_len=KV, page_size=PAGE,
                       n_pages=8, kv_axes=("model",), dtype=jnp.float32)
    step = steps.build_paged_step(model, mesh, ("model",), donate=False)
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, model.cfg.vocab, 17).astype(np.int32)

    a, _, _ = _chunked_prefill(pool, step, params, prompt, PAGE, max_new=4)
    b, matched, _ = _chunked_prefill(pool, step, params, prompt, PAGE,
                                     max_new=4)
    shared = [int(pg) for pg in pool.table[a][:2]]
    assert matched == 16 and list(pool.table[b][:2]) == shared
    assert all(pool.refcount[pg] == 2 for pg in shared)

    pool.free(a)
    # still referenced by b: active, not cached, not free
    assert all(pool.refcount[pg] == 1 for pg in shared)
    assert all(pg not in pool._free_pages for pg in shared)
    assert all(pg not in pool._lru.values() for pg in shared)

    # a third request needing more pages than the free list holds must
    # evict — but only refcount-0 (LRU) pages, never b's live pages.
    # b holds 3 pages; a's free dropped its non-shared page to the free
    # list (unregistered 3rd page) — force eviction pressure:
    other = rng.integers(0, model.cfg.vocab, 24).astype(np.int32)
    res = pool.alloc(other, max_new=8, align=PAGE)   # needs 4 pages
    assert res is not None
    c = res[0]
    assert set(int(p) for p in pool.table[c]) \
        .isdisjoint({pg for pg in shared})
    assert all(pool.refcount[pg] == 1 for pg in shared)

    # with every page now referenced, a further admission must refuse
    # (all-or-nothing) rather than steal a live page
    assert pool.free_pages + pool.n_free >= 0
    assert pool.alloc(other, max_new=8, align=PAGE) is None
    assert (pool.refcount[[int(p) for p in pool.table[b] if p >= 0]]
            >= 1).all()


def test_lru_eviction_frees_only_refcount_zero(served):
    """Park two prompts' pages in the LRU, then admit a request that
    needs them back: eviction claims the OLDEST parked pages first and
    the evicted hashes stop matching."""
    model, mesh, params = served
    pool = PagedKVPool(model, mesh, n_slots=2, kv_len=KV, page_size=PAGE,
                       n_pages=4, kv_axes=("model",), dtype=jnp.float32)
    step = steps.build_paged_step(model, mesh, ("model",), donate=False)
    rng = np.random.default_rng(9)
    p1 = rng.integers(0, model.cfg.vocab, 9).astype(np.int32)
    p2 = rng.integers(0, model.cfg.vocab, 9).astype(np.int32)

    s1, _, _ = _chunked_prefill(pool, step, params, p1, PAGE, max_new=4)
    pool.free(s1)                                    # 1 page -> LRU
    s2, _, _ = _chunked_prefill(pool, step, params, p2, PAGE, max_new=4)
    pool.free(s2)                                    # 1 more page -> LRU
    assert pool.utilization()["pages_cached"] == 2

    big = rng.integers(0, model.cfg.vocab, 25).astype(np.int32)
    res = pool.alloc(big, max_new=4, align=PAGE)     # needs all 4 pages
    assert res is not None
    u = pool.utilization()
    assert u["evicted"] == 2 and u["pages_cached"] == 0
    # both parked prefixes are gone from the cache
    assert pool.match_prefix(p1)[0] == 0
    assert pool.match_prefix(p2)[0] == 0


def test_chunked_prefill_interleaves_decode(served):
    """A long prompt prefills in fixed chunks WHILE an already-active
    request keeps decoding: some step must emit a token for the short
    request while the long one is still mid-prefill."""
    model, mesh, params = served
    eng = _paged_engine(model, mesh, params, n_slots=2)
    rng = np.random.default_rng(10)
    short = rng.integers(0, model.cfg.vocab, 4).astype(np.int32)
    long = rng.integers(0, model.cfg.vocab, 24).astype(np.int32)
    u_short = eng.submit(short, max_new_tokens=8)
    eng.step()                                       # short goes active
    u_long = eng.submit(long, max_new_tokens=4)
    interleaved = False
    for _ in range(50):
        if eng.done:
            break
        emitted = eng.step()
        if eng._prefilling and any(u == u_short for u, _ in emitted):
            interleaved = True
    assert interleaved, "no decode tick overlapped the chunked prefill"
    # 24-token prompt / 8-token chunks = 3 chunks; short took 1
    assert eng.stats()["prefill_chunks"] == 4
    # both streams still exactly the solo greedy references
    for uid, pr, n in ((u_short, short, 8), (u_long, long, 4)):
        solo = ServeEngine(model, mesh, params, n_slots=1, kv_len=KV)
        su = solo.submit(pr, max_new_tokens=n)
        assert eng.results[uid] == solo.run(max_steps=100)[su]


def test_speculative_greedy_token_identical(served):
    """Self-draft speculative decoding (drafter == target) must emit
    exactly the plain greedy streams while accepting >1 token per verify
    step (a perfect drafter accepts the g-1 cap every round)."""
    model, mesh, params = served
    prompts = _prompts(model.cfg, seed=12)
    s_uids, s_res = _slab_results(model, mesh, params, prompts, JOBS)
    eng = _paged_engine(model, mesh, params, draft=(model, params),
                        spec_tokens=4)
    p_uids = [eng.submit(pr, max_new_tokens=n)
              for pr, (_, n) in zip(prompts, JOBS)]
    p_res = eng.run(max_steps=200)
    for su, pu in zip(s_uids, p_uids):
        assert p_res[pu] == s_res[su], (pu, p_res[pu], s_res[su])
    acc = eng.stats()["spec_accepted"]
    assert acc["n"] > 0 and acc["mean"] > 1.0, acc


def test_speculative_rejects_sampling(served):
    model, mesh, params = served
    eng = _paged_engine(model, mesh, params, draft=(model, params),
                        spec_tokens=2)
    with pytest.raises(ValueError, match="greedily"):
        eng.submit(np.zeros(4, np.int32), max_new_tokens=2, temperature=0.7)


def test_paged_engine_rejects_bad_configs(served):
    model, mesh, params = served
    with pytest.raises(ValueError, match="chunk_size"):
        _paged_engine(model, mesh, params, chunk_size=12)
    with pytest.raises(ValueError, match="pool"):
        ServeEngine(model, mesh, params, n_slots=1, kv_len=KV, pool="heap")
    with pytest.raises(ValueError, match="paged"):
        ServeEngine(model, mesh, params, n_slots=1, kv_len=KV,
                    draft=(model, params))
    with pytest.raises(ValueError, match="spec_tokens"):
        _paged_engine(model, mesh, params, draft=(model, params),
                      spec_tokens=1)


# ---------------------------------------------------------------------------
# multi-device engine checks (subprocess; see testing/checks.py)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("n", [4, 8])
def test_paged_engine_sharded_int8_boot(n):
    run_checks(["check_serve_engine_paged"], n_devices=n, timeout=900)


@pytest.mark.slow
def test_speculative_engine_sharded(n=8):
    run_checks(["check_serve_engine_speculative"], n_devices=n, timeout=900)
