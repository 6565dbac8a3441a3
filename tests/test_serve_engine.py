"""Continuous-batching engine tests: slot recycling, per-sequence
cache_pos batched decode == per-request sequential decode (bit-identical
greedy), sampling invariants, scheduler admission, and an 8-device
shard_map engine smoke (subprocess).
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding

from repro.configs import get_config
from repro.launch.mesh import make_mesh
from repro.models.model import Model
from repro.serve import FIFOScheduler, Request, ServeEngine, steps
from repro.serve import sampling
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


def _prompts(arch, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, arch.vocab, p).astype(np.int32) for p, _ in JOBS]


def _reference_greedy(model, mesh, params, prompt, n, kv_len=KV):
    """One request alone through the raw prefill+decode path."""
    ps = steps.build_prefill_step(model, mesh, (), ())
    ds = steps.build_decode_step(model, mesh, (), ("model",), donate=False)
    logits, caches = ps.fn(params, {"tokens": prompt[None, :]})
    caches = steps.pad_prefill_caches(model, caches, kv_len)
    toks = [int(jnp.argmax(logits[0, -1]))]
    for i in range(1, n):
        logits, caches = ds.fn(
            params, caches, {"tokens": jnp.array([[toks[-1]]], jnp.int32)},
            jnp.full((1,), len(prompt) + i - 1, jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks


def test_engine_batched_greedy_bit_identical(served):
    """4 requests with mixed prompt lengths over 3 slots: the continuously
    batched decode (rows at different positions, staggered admission) must
    emit, per request, exactly the tokens of that request run alone."""
    model, mesh, params = served
    eng = ServeEngine(model, mesh, params, n_slots=3, kv_len=KV)
    prompts = _prompts(model.cfg)
    uids = [eng.submit(pr, max_new_tokens=n)
            for pr, (_, n) in zip(prompts, JOBS)]
    res = eng.run(max_steps=100)
    for uid, pr, (_, n) in zip(uids, prompts, JOBS):
        want = _reference_greedy(model, mesh, params, pr, n)
        assert res[uid] == want, (uid, res[uid], want)


def test_engine_slot_recycling(served):
    """More requests than slots: a retired slot must be reused, and the
    recycled request's output must be unpolluted (checked above)."""
    model, mesh, params = served
    eng = ServeEngine(model, mesh, params, n_slots=2, kv_len=KV)
    prompts = _prompts(model.cfg, seed=1)
    for pr, (_, n) in zip(prompts, JOBS):
        eng.submit(pr, max_new_tokens=n)
    eng.run(max_steps=100)
    slots = list(eng.slot_history.values())
    assert len(slots) == 4
    assert set(slots) == {0, 1}            # both slots used...
    assert len(slots) > len(set(slots))    # ...and reused after retirement
    assert eng.pool.n_free == 2            # everything released at the end
    assert (eng.pool.lengths == 0).all()


def test_engine_streaming_and_eos(served):
    model, mesh, params = served
    eng = ServeEngine(model, mesh, params, n_slots=2, kv_len=KV)
    pr = _prompts(model.cfg, seed=2)[0]
    first = _reference_greedy(model, mesh, params, pr, 1)[0]
    streamed = []
    uid = eng.submit(pr, max_new_tokens=10, eos_id=first,
                     on_token=lambda u, t: streamed.append((u, t)))
    res = eng.run(max_steps=50)
    # the very first sampled token is the EOS -> request retires at length 1
    assert res[uid] == [first]
    assert streamed == [(uid, first)]


def test_engine_temperature_zero_equals_argmax(served):
    """A sampled run at temperature -> 0 converges to the greedy run."""
    model, mesh, params = served
    pr = _prompts(model.cfg, seed=3)[1]
    want = _reference_greedy(model, mesh, params, pr, 5)
    eng = ServeEngine(model, mesh, params, n_slots=1, kv_len=KV)
    uid = eng.submit(pr, max_new_tokens=5, temperature=1e-6, seed=11)
    assert eng.run(max_steps=50)[uid] == want


def test_engine_seeded_sampling_deterministic(served):
    model, mesh, params = served
    pr = _prompts(model.cfg, seed=4)[2]

    def run_once(seed):
        eng = ServeEngine(model, mesh, params, n_slots=1, kv_len=KV)
        uid = eng.submit(pr, max_new_tokens=8, temperature=1.0, top_k=20,
                         top_p=0.95, seed=seed)
        return eng.run(max_steps=50)[uid]

    a, b, c = run_once(5), run_once(5), run_once(6)
    assert a == b                      # same seed -> same stream
    assert a != c                      # (overwhelmingly) different seed


# ---------------------------------------------------------------------------
# sampling invariants
# ---------------------------------------------------------------------------

def test_top_k_masks_exactly_k():
    rng = np.random.default_rng(0)
    logits = jnp.asarray(rng.normal(size=(3, 64)).astype(np.float32))
    for k in (1, 5, 63):
        m = np.asarray(sampling.top_k_mask(logits, k))
        assert (m.sum(-1) == k).all()
        # the kept set IS the top-k: min kept > max dropped (no ties here)
        kept = np.where(m, np.asarray(logits), np.inf).min(-1)
        drop = np.where(~m, np.asarray(logits), -np.inf).max(-1)
        assert (kept > drop).all()
    # ties: still exactly k kept
    tied = jnp.zeros((1, 16), jnp.float32)
    assert np.asarray(sampling.top_k_mask(tied, 4)).sum() == 4


def test_top_p_mask_smallest_covering_set():
    rng = np.random.default_rng(1)
    logits = jnp.asarray(rng.normal(size=(4, 32)).astype(np.float32) * 3)
    p = 0.7
    m = np.asarray(sampling.top_p_mask(logits, p))
    probs = np.asarray(jax.nn.softmax(logits, axis=-1))
    for row in range(4):
        keep = m[row]
        # argmax always kept; kept mass reaches p; minimal: dropping the
        # smallest kept token would fall below p
        assert keep[probs[row].argmax()]
        assert probs[row][keep].sum() >= p - 1e-6
        smallest = probs[row][keep].min()
        assert probs[row][keep].sum() - smallest < p + 1e-6
    assert np.asarray(sampling.top_p_mask(logits, 1.0)).all()


def test_sample_logits_temperature_to_zero_is_argmax():
    rng = np.random.default_rng(2)
    logits = jnp.asarray(rng.normal(size=(8, 50)).astype(np.float32))
    key = jax.random.PRNGKey(0)
    greedy = np.asarray(sampling.sample_logits(logits, key, temperature=0.0))
    cold = np.asarray(sampling.sample_logits(logits, key, temperature=1e-5))
    assert (greedy == np.asarray(logits).argmax(-1)).all()
    assert (cold == greedy).all()


# ---------------------------------------------------------------------------
# scheduler admission
# ---------------------------------------------------------------------------

def test_scheduler_buckets_and_admission():
    s = FIFOScheduler(kv_len=64)
    assert s.buckets[-1] == 64
    assert s.bucket_for(5) == 8 and s.bucket_for(8) == 8
    assert s.bucket_for(33) == 64
    for plen in (3, 9, 17):
        s.submit(Request(prompt=np.zeros(plen, np.int32)))
    assert len(s) == 3
    adm = s.admit(2)                   # keyed on free slots
    assert [len(r.prompt) for r, _ in adm] == [3, 9]   # FIFO
    assert [b for _, b in adm] == [8, 16]              # padded lengths
    assert len(s) == 1
    assert s.admit(0) == []


def test_default_buckets_never_degenerate():
    """start >= kv_len used to collapse the ladder to (kv_len,), silently
    padding every short prompt to full KV capacity in prefill."""
    from repro.serve.scheduler import default_buckets

    assert default_buckets(64) == (8, 16, 32, 64)
    # start clamped to kv_len // 2: a sub-capacity bucket always exists
    assert default_buckets(16, start=32) == (8, 16)
    assert default_buckets(12, start=100) == (6, 12)
    assert default_buckets(4) == (2, 4)
    s = FIFOScheduler(kv_len=16, buckets=default_buckets(16, start=64))
    assert s.bucket_for(3) < 16
    with pytest.raises(ValueError, match="degenerate"):
        default_buckets(1)
    with pytest.raises(ValueError, match="start must be >= 1"):
        default_buckets(64, start=0)


def test_scheduler_rejects_oversized_prompt():
    s = FIFOScheduler(kv_len=16)
    with pytest.raises(ValueError, match="no room to generate"):
        s.submit(Request(prompt=np.zeros(16, np.int32)))
    capped = FIFOScheduler(kv_len=32, buckets=(8,))
    with pytest.raises(ValueError, match="exceeds the largest bucket"):
        capped.submit(Request(prompt=np.zeros(9, np.int32)))
    with pytest.raises(ValueError, match="exceeds KV capacity"):
        FIFOScheduler(kv_len=8, buckets=(16,))
    with pytest.raises(ValueError, match="max_new_tokens"):
        s.submit(Request(prompt=np.zeros(4, np.int32), max_new_tokens=0))
    # multi-row prompts validate at their FLAT length, at submit time
    with pytest.raises(ValueError, match="no room to generate"):
        FIFOScheduler(kv_len=16).submit(
            Request(prompt=np.zeros((2, 8), np.int32)))


def test_engine_run_exact_step_budget(served):
    """Draining in exactly max_steps is success, not a timeout."""
    model, mesh, params = served
    pr = _prompts(model.cfg, seed=5)[3]
    probe = ServeEngine(model, mesh, params, n_slots=1, kv_len=KV)
    probe.submit(pr, max_new_tokens=3)
    needed = 0
    while not probe.done:
        probe.step()
        needed += 1
    eng = ServeEngine(model, mesh, params, n_slots=1, kv_len=KV)
    uid = eng.submit(pr, max_new_tokens=3)
    assert len(eng.run(max_steps=needed)[uid]) == 3


class _FakeClock:
    """Injectable engine clock: tests advance time explicitly."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_engine_request_deadline_timeout(served):
    """A request past its deadline retires with status 'timeout' and its
    KV slot returns to the pool; a request that expires while queued never
    takes a slot.  Requests without deadlines are untouched."""
    model, mesh, params = served
    clk = _FakeClock()
    eng = ServeEngine(model, mesh, params, n_slots=1, kv_len=KV, clock=clk)
    pr = _prompts(model.cfg, seed=6)[0]
    # greedy, no EOS: would decode until KV capacity if never timed out
    slow = eng.submit(pr, max_new_tokens=1000, deadline=5.0)
    queued = eng.submit(pr, max_new_tokens=4, deadline=5.0)
    ok = eng.submit(pr, max_new_tokens=4)          # no deadline
    eng.step()
    eng.step()
    assert eng.status[slow] == "active"
    assert eng.status[queued] == "queued"
    assert eng.pool.n_free == 0
    n_before = len(eng.results[slow])
    assert n_before >= 2                           # made progress first
    clk.t = 10.0                                   # past both deadlines
    eng.step()
    assert eng.status[slow] == "timeout"           # slot reclaimed...
    assert eng.status[queued] == "timeout"         # ...queue never admitted
    assert eng.status[ok] == "active"              # freed slot reused NOW
    assert len(eng.results[slow]) == n_before      # no tokens after timeout
    assert eng.slot_history[ok] == eng.slot_history[slow]
    res = eng.run(max_steps=50)
    assert eng.status[ok] == "done"
    assert len(res[ok]) == 4
    assert res[ok] == _reference_greedy(model, mesh, params, pr, 4)
    assert eng.pool.n_free == 1                    # everything released


def test_scheduler_queue_expiry():
    s = FIFOScheduler(kv_len=64)
    a = Request(prompt=np.zeros(4, np.int32), deadline=1.0)
    b = Request(prompt=np.zeros(4, np.int32))
    c = Request(prompt=np.zeros(4, np.int32), deadline=9.0)
    for r in (a, b, c):
        s.submit(r)
    assert s.expire(0.5) == []
    dropped = s.expire(2.0)
    assert [r.uid for r in dropped] == [a.uid]
    assert len(s) == 2                 # b (no deadline) and c survive
    adm = s.admit(4)
    assert [r.uid for r, _ in adm] == [b.uid, c.uid]


def test_engine_keeps_custom_scheduler(served):
    """An (empty, hence falsy) user-supplied scheduler must not be
    silently replaced by the default one."""
    model, mesh, params = served
    sched = FIFOScheduler(kv_len=KV, buckets=(16,))
    eng = ServeEngine(model, mesh, params, n_slots=1, kv_len=KV,
                      scheduler=sched)
    assert eng.scheduler is sched


def test_serve_shape_policy_validation():
    """The shape policy refuses unknown/non-serving shapes and bad meshes
    instead of silently falling through to the default layout."""
    pol = steps.serve_shape_policy
    assert pol("decode_32k", ("pod", "data", "model")) == \
        (("pod", "data"), ("model",))
    assert pol("long_500k", ("data", "model")) == ((), ("data", "model"))
    with pytest.raises(ValueError, match="unknown inference shape"):
        pol("decode_64k", ("data", "model"))
    with pytest.raises(ValueError, match="train shape"):
        pol("train_4k", ("data", "model"))
    with pytest.raises(ValueError, match="'model'"):
        pol("decode_32k", ("data", "mdl"))
    with pytest.raises(ValueError, match="duplicate"):
        pol("decode_32k", ("data", "data", "model"))


# ---------------------------------------------------------------------------
# multi-device engine smoke (subprocess; see testing/subproc.py)
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_engine_8dev_sharded_int8_boot():
    run_checks(["check_serve_engine_continuous_batching"], n_devices=8, timeout=900)
