"""Serving engine correctness: allocator properties, kernel equivalence,
continuous-batching oracle identity, sampling determinism.

The load-bearing guarantees (ISSUE 7 acceptance criteria):

* :class:`repro.serve.PageAllocator` never double-allocates or leaks pages
  across any alloc/free interleaving (hypothesis property tests);
* the Pallas paged-attention decode kernel matches dense attention to
  f32-ULP tolerance over a grid of shapes / shuffled block tables / ragged
  lengths (GQA and MLA fused-pool modes);
* continuous-batched greedy decoding is **token-identical** to the
  per-sequence static-batch oracle (``repro.launch.serve.generate``) across
  staggered admission/eviction schedules, ragged prompts, mid-stream EOS,
  single-token sequences, and both attention paths;
* seeded ``temperature>0`` streams depend only on (base key, request seed,
  step) — never on co-batched traffic — and equal the oracle's streams.

Serving under fire (ISSUE 9 acceptance criteria):

* KV preemption/restore is token-identical at page-boundary and
  ``max_new_tokens=1`` edges, for greedy and sampled streams, across
  arbitrary hypothesis-driven interleavings (with per-step allocator
  invariant checks);
* SLO deadlines shed queued requests and abort in-flight ones explicitly
  (never silently), and the aborted partial prefix is still the oracle's;
  head-of-line bypass is bounded; priorities preempt lower in-flight work;
* injected decode-step hangs (watchdog-classified) and crashes recover
  under supervision with streams bit-identical to the fault-free run, and
  fail loudly (with a state dump) without supervision.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from hypothesis import given, settings
from hypothesis import strategies as st

import repro.launch.serve as launch_serve
from repro.configs import get_config
from repro.kernels.paged_attention import (paged_decode_attention,
                                           paged_decode_attention_ref)
from repro.models import build_model
from repro.serve import (CRASH, HANG, OutOfPages, PageAllocator, Request,
                         ServeDrill, ServeEngine, ServeFault,
                         ServeFaultInjector, ServeFaultSpec, TRASH_PAGE,
                         check_servable, parse_chaos)

PAGE = 4          # one page size across tests -> shared decode-fn compiles
POOL = 32

_SETUPS: dict = {}    # plain cache: @given-wrapped tests can't take fixtures


def _get_setup(arch):
    if arch not in _SETUPS:
        cfg = get_config(arch, reduced=True)
        model = build_model(cfg)
        _SETUPS[arch] = (cfg, model, model.init(jax.random.PRNGKey(0)))
    return _SETUPS[arch]


@pytest.fixture(scope="module")
def dense_setup():
    return _get_setup("deepseek-7b")


@pytest.fixture(scope="module")
def mla_setup():
    return _get_setup("deepseek-v2-236b")


def _prompts(cfg, lens, seed=3):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, size=(p,)).astype(np.int32)
            for p in lens]


def _oracle(model, cfg, params, prompt, gen, temperature=0.0, seed=0):
    toks = launch_serve.generate(
        model, cfg, params, jnp.asarray(prompt)[None], gen,
        temperature=temperature, key=jax.random.PRNGKey(0), seeds=[seed])
    return [int(t) for t in np.asarray(toks)[0]]


def _engine(cfg, model, params, **kw):
    kw.setdefault("num_pages", POOL)
    kw.setdefault("page_size", PAGE)
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_len", 32)
    return ServeEngine(model, cfg, params, **kw)


# ===================================================== allocator properties

class TestPageAllocator:
    def test_trash_page_never_handed_out(self):
        alloc = PageAllocator(8, PAGE)
        pages = alloc.alloc(7)                    # the whole allocatable pool
        assert TRASH_PAGE not in pages
        assert sorted(pages) == list(range(1, 8))
        with pytest.raises(OutOfPages):
            alloc.alloc(1)

    def test_double_free_raises(self):
        alloc = PageAllocator(8, PAGE)
        pages = alloc.alloc(2)
        alloc.free(pages)
        with pytest.raises(KeyError):
            alloc.free(pages)

    def test_free_is_atomic_on_partial_double_free(self):
        """A bad batch (one live page + one stale) must raise *before* any
        refcount moves — the live page stays allocated, nothing leaks."""
        alloc = PageAllocator(8, PAGE)
        live = alloc.alloc(2)
        stale = alloc.alloc(1)
        alloc.free(stale)
        with pytest.raises(KeyError):
            alloc.free(live[:1] + stale)
        assert alloc.live_pages == 2              # untouched by the bad call
        alloc.free(live)
        assert alloc.free_pages == 7 and alloc.live_pages == 0

    def test_free_counts_duplicates_within_one_call(self):
        """``free([p, p])`` of a singly-referenced page is a double free —
        it must raise, not push ``p`` onto the free list twice."""
        alloc = PageAllocator(8, PAGE)
        [p] = alloc.alloc(1)
        with pytest.raises(KeyError):
            alloc.free([p, p])
        assert alloc.live_pages == 1
        alloc.free([p])
        assert alloc.free_pages == 7

    def test_share_unknown_page_is_atomic(self):
        alloc = PageAllocator(8, PAGE)
        pages = alloc.alloc(2)
        with pytest.raises(KeyError):
            alloc.share(pages + [7])              # 7 never allocated
        alloc.free(pages)                         # refcounts never bumped
        assert alloc.free_pages == 7 and alloc.live_pages == 0

    def test_refcounted_sharing(self):
        alloc = PageAllocator(8, PAGE)
        pages = alloc.alloc(3)
        alloc.share(pages)                        # refcount 2
        alloc.free(pages)                         # still live
        assert alloc.live_pages == 3 and alloc.free_pages == 4
        alloc.free(pages)                         # refcount 0 -> returned
        assert alloc.live_pages == 0 and alloc.free_pages == 7

    @given(ops=st.lists(st.tuples(st.booleans(), st.integers(1, 5)),
                        min_size=1, max_size=60))
    @settings(max_examples=40, deadline=None)
    def test_alloc_free_exactly_once_and_conserved(self, ops):
        """Any alloc/free interleaving: no page is ever handed out twice
        concurrently, block tables stay disjoint, and the free list is
        conserved (free + live == capacity) after every operation."""
        alloc = PageAllocator(16, PAGE)
        tables = []                               # outstanding allocations
        for is_alloc, n in ops:
            if is_alloc:
                try:
                    pages = alloc.alloc(n)
                except OutOfPages:
                    assert alloc.free_pages < n
                    continue
                live = {p for t in tables for p in t}
                assert len(set(pages)) == len(pages)
                assert not set(pages) & live      # disjoint block tables
                assert TRASH_PAGE not in pages
                tables.append(pages)
            elif tables:
                alloc.free(tables.pop(n % len(tables)))
            assert alloc.free_pages + alloc.live_pages == alloc.num_pages - 1
            assert alloc.live_pages == len({p for t in tables for p in t})
        for t in tables:
            alloc.free(t)
        assert alloc.free_pages == alloc.num_pages - 1
        assert alloc.live_pages == 0


# ============================================ paged kernel vs dense oracle

KERNEL_GRID = [
    # B, H, KV, d,  page, maxp
    (3, 4, 2, 16, 4, 4),          # GQA
    (2, 8, 8, 32, 8, 2),          # MHA
    (1, 4, 1, 64, 4, 3),          # MQA
    (4, 4, 4, 16, 4, 5),          # bigger batch
]


@pytest.mark.parametrize("B,H,KV,d,page,maxp", KERNEL_GRID)
def test_paged_kernel_matches_dense_ref(B, H, KV, d, page, maxp):
    rng = np.random.default_rng(B * 100 + H)
    P = B * maxp + 1
    kp = jnp.asarray(rng.normal(size=(P, KV, page, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, KV, page, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, d)), jnp.float32)
    # shuffled, non-contiguous block tables (page 0 kept as trash)
    bt = jnp.asarray(rng.permutation(np.arange(1, P))[:B * maxp]
                     .reshape(B, maxp), jnp.int32)
    # ragged lengths: 1, a page boundary, full, and something in between
    lens = np.ones((B,), np.int32)
    lens[1 % B] = page                            # exact page boundary
    lens[(2 % B)] = maxp * page                   # completely full
    if B > 3:
        lens[3] = page + 1
    lens = jnp.asarray(lens)
    out = paged_decode_attention(q, kp, vp, bt, lens, scale=d ** -0.5)
    ref = paged_decode_attention_ref(q, kp, vp, bt, lens, scale=d ** -0.5)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_paged_kernel_mla_fused_pool():
    """MLA mode: one fused c_kv‖k_rope pool, values = latent prefix."""
    rng = np.random.default_rng(7)
    B, H, lora, rope, page, maxp = 3, 4, 32, 16, 4, 4
    d = lora + rope
    P = B * maxp + 1
    kp = jnp.asarray(rng.normal(size=(P, 1, page, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, d)), jnp.float32)
    bt = jnp.asarray(rng.permutation(np.arange(1, P))[:B * maxp]
                     .reshape(B, maxp), jnp.int32)
    lens = jnp.asarray([1, page, maxp * page], jnp.int32)
    out = paged_decode_attention(q, kp, None, bt, lens, scale=d ** -0.5,
                                 v_width=lora)
    ref = paged_decode_attention_ref(q, kp, None, bt, lens, scale=d ** -0.5,
                                     v_width=lora)
    assert out.shape == (B, H, lora)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-6, rtol=2e-6)


def test_trash_page_contents_cannot_leak():
    """Garbage in page 0 (inactive-slot writes land there) must never move
    a live sequence's output: masked positions contribute exactly zero."""
    rng = np.random.default_rng(9)
    B, H, KV, d, page, maxp = 2, 4, 2, 16, 4, 3
    P = B * maxp + 1
    kp = jnp.asarray(rng.normal(size=(P, KV, page, d)), jnp.float32)
    vp = jnp.asarray(rng.normal(size=(P, KV, page, d)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, H, d)), jnp.float32)
    bt = np.arange(1, 1 + B * maxp, dtype=np.int32).reshape(B, maxp)
    bt[:, -1] = TRASH_PAGE                        # tail slots -> trash
    lens = jnp.asarray([3, 2 * page], jnp.int32)  # never reach the tail page
    base = paged_decode_attention(q, kp, vp, jnp.asarray(bt), lens,
                                  scale=d ** -0.5)
    kp2 = kp.at[TRASH_PAGE].set(1e6)              # poison the trash page
    vp2 = vp.at[TRASH_PAGE].set(-1e6)
    poisoned = paged_decode_attention(q, kp2, vp2, jnp.asarray(bt), lens,
                                      scale=d ** -0.5)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(poisoned))


# ================================== continuous batching == static oracle

SCHEDULES = {
    "all_at_once": [0, 0, 0, 0],
    "staggered": [0, 2, 3, 9],
    "serialized": [0, 40, 80, 120],
}


@pytest.mark.parametrize("attention", ["dense", "paged"])
@pytest.mark.parametrize("schedule", sorted(SCHEDULES))
def test_engine_greedy_token_identical(dense_setup, attention, schedule):
    """Ragged prompts (incl. single-token) under every admission schedule:
    the engine's greedy streams equal per-sequence static-batch decoding."""
    cfg, model, params = dense_setup
    prompts = _prompts(cfg, [5, 1, 9, 3])
    gens = [6, 4, 8, 3]
    eng = _engine(cfg, model, params, attention=attention)
    reqs = [Request(rid=i, prompt=prompts[i], max_new_tokens=gens[i])
            for i in range(4)]
    res = eng.serve(reqs, arrival_steps=SCHEDULES[schedule])
    for i in range(4):
        assert res[i].tokens == _oracle(model, cfg, params, prompts[i],
                                        gens[i]), (attention, schedule, i)
        assert res[i].finish_reason == "length"
    # no leaks: every page freed, every reservation released
    assert eng.alloc.live_pages == 0
    assert eng.alloc.free_pages == eng.alloc.num_pages - 1
    assert eng._reserved == 0


def test_engine_mla_arch_token_identical(mla_setup):
    """The MLA+MoE arch (fused latent pool, v_width kernel mode) through
    the full engine, staggered."""
    cfg, model, params = mla_setup
    prompts = _prompts(cfg, [5, 3, 8])
    gens = [5, 6, 4]
    eng = _engine(cfg, model, params, attention="paged")
    res = eng.serve([Request(rid=i, prompt=prompts[i], max_new_tokens=gens[i])
                     for i in range(3)], arrival_steps=[0, 1, 4])
    for i in range(3):
        assert res[i].tokens == _oracle(model, cfg, params, prompts[i],
                                        gens[i]), i


def test_engine_mid_stream_eos(dense_setup):
    """EOS mid-stream evicts the sequence and frees its pages; the emitted
    stream is the oracle's, truncated inclusively at the EOS token."""
    cfg, model, params = dense_setup
    [prompt] = _prompts(cfg, [5])
    full = _oracle(model, cfg, params, prompt, 8)
    eos = full[2]                        # a token the greedy stream emits
    cut = full.index(eos) + 1            # engine stops at first occurrence
    assert cut < len(full)
    eng = _engine(cfg, model, params)
    res = eng.serve([Request(rid=0, prompt=prompt, max_new_tokens=8,
                             eos_id=eos)])
    assert res[0].tokens == full[:cut]
    assert res[0].finish_reason == "eos"
    assert eng.alloc.live_pages == 0 and eng._reserved == 0


def test_engine_single_token_sequences(dense_setup):
    """max_new_tokens=1 finishes straight out of prefill (never enters the
    decode batch), co-scheduled with longer traffic."""
    cfg, model, params = dense_setup
    prompts = _prompts(cfg, [4, 6, 2])
    eng = _engine(cfg, model, params)
    res = eng.serve([
        Request(rid=0, prompt=prompts[0], max_new_tokens=1),
        Request(rid=1, prompt=prompts[1], max_new_tokens=5),
        Request(rid=2, prompt=prompts[2], max_new_tokens=1),
    ], arrival_steps=[0, 0, 2])
    assert res[0].tokens == _oracle(model, cfg, params, prompts[0], 1)
    assert res[1].tokens == _oracle(model, cfg, params, prompts[1], 5)
    assert res[2].tokens == _oracle(model, cfg, params, prompts[2], 1)
    assert res[0].finish_reason == "length" and len(res[0].tokens) == 1


def test_engine_capacity_backpressure(dense_setup):
    """A pool that fits ~one sequence serializes admissions (head-of-line
    waits for eviction) without corrupting any stream."""
    cfg, model, params = dense_setup
    prompts = _prompts(cfg, [5, 7, 3])
    gens = [6, 4, 5]
    # pages_for(max P+gen)=pages_for(11)=3 -> pool of 4 allocatable fits one
    # sequence plus slack but never two
    eng = _engine(cfg, model, params, num_pages=5, max_len=12)
    res = eng.serve([Request(rid=i, prompt=prompts[i], max_new_tokens=gens[i])
                     for i in range(3)])
    for i in range(3):
        assert res[i].tokens == _oracle(model, cfg, params, prompts[i],
                                        gens[i]), i
    assert eng.alloc.live_pages == 0 and eng._reserved == 0


def test_engine_rejects_impossible_requests(dense_setup):
    cfg, model, params = dense_setup
    eng = _engine(cfg, model, params, max_len=16)
    with pytest.raises(ValueError):
        eng.submit(Request(rid=0, prompt=np.zeros((10,), np.int32),
                           max_new_tokens=8))     # 18 > max_len
    with pytest.raises(ValueError):
        eng.submit(Request(rid=1, prompt=np.zeros((0,), np.int32),
                           max_new_tokens=2))


@given(plens=st.lists(st.integers(1, 9), min_size=1, max_size=4),
       arrivals=st.lists(st.integers(0, 12), min_size=4, max_size=4),
       gens=st.lists(st.integers(1, 6), min_size=4, max_size=4))
@settings(max_examples=5, deadline=None)
def test_engine_random_schedules_property(plens, arrivals, gens):
    """Hypothesis-driven admit/evict schedules: token identity + page
    conservation hold for arbitrary ragged traffic."""
    cfg, model, params = _get_setup("deepseek-7b")
    prompts = _prompts(cfg, plens, seed=sum(plens))
    n = len(prompts)
    eng = _engine(cfg, model, params)
    res = eng.serve([Request(rid=i, prompt=prompts[i],
                             max_new_tokens=gens[i]) for i in range(n)],
                    arrival_steps=arrivals[:n])
    for i in range(n):
        assert res[i].tokens == _oracle(model, cfg, params, prompts[i],
                                        gens[i]), i
    assert eng.alloc.live_pages == 0
    assert eng.alloc.free_pages == eng.alloc.num_pages - 1
    assert eng._reserved == 0


# ======================================== sampling determinism (temp > 0)

def test_sampled_stream_independent_of_cobatch(dense_setup):
    """A seeded temperature>0 request emits the same stream alone and
    co-batched with unrelated traffic (per-request RNG streams)."""
    cfg, model, params = dense_setup
    prompts = _prompts(cfg, [5, 9, 3])
    solo = _engine(cfg, model, params)
    a = solo.serve([Request(rid=0, prompt=prompts[0], max_new_tokens=6,
                            temperature=0.8, seed=7)])[0].tokens
    crowd = _engine(cfg, model, params)
    b = crowd.serve([
        Request(rid=0, prompt=prompts[0], max_new_tokens=6,
                temperature=0.8, seed=7),
        Request(rid=1, prompt=prompts[1], max_new_tokens=8,
                temperature=0.9, seed=11),
        Request(rid=2, prompt=prompts[2], max_new_tokens=4,
                temperature=0.0, seed=13),
    ], arrival_steps=[0, 0, 1])[0].tokens
    assert a == b
    assert len(a) == 6


def test_sampled_stream_matches_oracle(dense_setup):
    """Engine seeded stream == static-batch oracle seeded stream (same
    base key, same request seed, same fold_in(step) positions)."""
    cfg, model, params = dense_setup
    [prompt] = _prompts(cfg, [5])
    eng = _engine(cfg, model, params, seed=0)
    got = eng.serve([Request(rid=0, prompt=prompt, max_new_tokens=6,
                             temperature=0.8, seed=7)])[0].tokens
    assert got == _oracle(model, cfg, params, prompt, 6, temperature=0.8,
                          seed=7)


def test_generate_survives_temperature_without_key(dense_setup):
    """Seed-era bug: ``generate(..., temperature>0, key=None)`` crashed on
    ``jax.random.split(None)``.  It must sample with the default key now."""
    cfg, model, params = dense_setup
    prompts = jnp.asarray(_prompts(cfg, [4, 4]))
    toks = launch_serve.generate(model, cfg, params, prompts, 3,
                                 temperature=0.7)
    assert toks.shape == (2, 3)


def test_generate_does_not_rejit_per_call(dense_setup, monkeypatch):
    """Seed-era bug: the jitted serve step was rebuilt inside ``generate``
    on every call.  It must come from the per-config cache."""
    cfg, model, params = dense_setup
    calls = []
    orig = launch_serve.make_serve_step

    def counting(*a, **kw):
        calls.append(1)
        return orig(*a, **kw)

    monkeypatch.setattr(launch_serve, "make_serve_step", counting)
    launch_serve._STEP_CACHE.pop(cfg.name, None)
    prompts = jnp.asarray(_prompts(cfg, [4, 4]))
    launch_serve.generate(model, cfg, params, prompts, 2)
    launch_serve.generate(model, cfg, params, prompts, 2)
    launch_serve.generate(model, cfg, params, prompts, 3)
    assert len(calls) == 1


# =========================================================== servable gate

@pytest.mark.parametrize("arch,reason", [
    ("starcoder2-3b", "attention"),       # sliding-window ring cache
    ("mamba2-780m", "mixer"),             # ssm mixer
    ("qwen2-vl-72b", "mrope"),            # mrope positions
    ("seamless-m4t-medium", "encoder"),   # enc-dec
])
def test_unservable_archs_raise(arch, reason):
    cfg = get_config(arch, reduced=True)
    with pytest.raises(ValueError, match="not servable"):
        check_servable(cfg)
    with pytest.raises(ValueError, match=reason):
        check_servable(cfg)


def test_servable_archs_pass():
    for arch in ("deepseek-7b", "deepseek-v2-236b", "qwen2.5-32b"):
        check_servable(get_config(arch, reduced=True))


# ==================== serving under fire (ISSUE 9): preempt/SLO/faults

class FakeClock:
    """Manually-advanced engine clock for deterministic SLO tests."""

    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def test_submit_rejects_duplicate_rid(dense_setup):
    cfg, model, params = dense_setup
    eng = _engine(cfg, model, params)
    [p] = _prompts(cfg, [4])
    eng.submit(Request(rid=7, prompt=p, max_new_tokens=2))
    with pytest.raises(ValueError, match="duplicate rid"):
        eng.submit(Request(rid=7, prompt=p, max_new_tokens=2))


@pytest.mark.parametrize("attention", ["dense", "paged"])
@pytest.mark.parametrize("preempt_step", [1, 2, 3, 4])
def test_preempt_restore_token_identical(dense_setup, attention,
                                         preempt_step):
    """Forced KV eviction at every phase of a stream — right after the
    prefill token (re-prefill is the bare prompt), at an exact page
    boundary, and deep into decode — restores bit-identically: the
    re-prefilled prefix resumes the same RNG stream position."""
    cfg, model, params = dense_setup
    prompts = _prompts(cfg, [5, 3])
    gens = [6, 7]
    eng = _engine(cfg, model, params, attention=attention)
    res = eng.serve([Request(rid=i, prompt=prompts[i],
                             max_new_tokens=gens[i]) for i in range(2)],
                    preempt_at=[(preempt_step, 0)])
    for i in range(2):
        assert res[i].tokens == _oracle(model, cfg, params, prompts[i],
                                        gens[i]), (attention, i)
        assert res[i].finish_reason == "length"
    assert res[0].preemptions == 1 and res[1].preemptions == 0
    assert eng.n_preempted == 1 and eng.n_restored == 1
    assert eng.alloc.live_pages == 0 and eng._reserved == 0


def test_preempt_with_single_token_cobatch(dense_setup):
    """max_new_tokens=1 edge: a request that finishes straight out of
    prefill admits *while* another sequence sits evicted, and neither
    stream moves."""
    cfg, model, params = dense_setup
    prompts = _prompts(cfg, [5, 2])
    eng = _engine(cfg, model, params)
    res = eng.serve([Request(rid=0, prompt=prompts[0], max_new_tokens=6),
                     Request(rid=1, prompt=prompts[1], max_new_tokens=1)],
                    arrival_steps=[0, 2], preempt_at=[(2, 0)])
    assert res[0].tokens == _oracle(model, cfg, params, prompts[0], 6)
    assert res[1].tokens == _oracle(model, cfg, params, prompts[1], 1)
    assert res[0].preemptions == 1


def test_preempt_restore_preserves_sampled_stream(dense_setup):
    """Seeded temperature>0 stream across an eviction == the solo oracle
    stream: RNG position folds in (seed, step), never cache history."""
    cfg, model, params = dense_setup
    [prompt] = _prompts(cfg, [5])
    eng = _engine(cfg, model, params, seed=0)
    res = eng.serve([Request(rid=0, prompt=prompt, max_new_tokens=6,
                             temperature=0.8, seed=7)],
                    preempt_at=[(3, 0)])
    assert res[0].preemptions == 1
    assert res[0].tokens == _oracle(model, cfg, params, prompt, 6,
                                    temperature=0.8, seed=7)


@given(arrivals=st.lists(st.integers(0, 8), min_size=3, max_size=3),
       preempts=st.lists(st.tuples(st.integers(1, 12), st.integers(0, 2)),
                         min_size=0, max_size=4))
@settings(max_examples=5, deadline=None)
def test_preempt_interleavings_conserve_pages_property(arrivals, preempts):
    """Hypothesis: arbitrary admit/preempt/restore/evict interleavings
    keep the free list conserved, never double-map a page, and stay
    token-identical.  ``check_invariants`` runs after every step."""
    cfg, model, params = _get_setup("deepseek-7b")
    prompts = _prompts(cfg, [5, 1, 7], seed=11)
    gens = [6, 3, 5]
    eng = _engine(cfg, model, params)
    order = sorted(range(3), key=lambda i: arrivals[i])
    i = 0
    while i < len(order) or not eng.idle:
        while i < len(order) and eng.n_steps >= arrivals[order[i]]:
            eng.submit(Request(rid=order[i], prompt=prompts[order[i]],
                               max_new_tokens=gens[order[i]]))
            i += 1
        if eng.idle and i < len(order):
            eng.n_steps = arrivals[order[i]]
            continue
        for st_, rid in preempts:
            if st_ == eng.n_steps:
                eng.preempt(rid)
        eng.step()
        eng.check_invariants()
    for r in range(3):
        assert eng.results[r].tokens == _oracle(model, cfg, params,
                                                prompts[r], gens[r]), r
    assert eng.alloc.live_pages == 0
    assert eng.alloc.free_pages == eng.alloc.num_pages - 1


def test_overcommit_out_of_pages_preempts_victim(dense_setup):
    """Overcommit mode admits on prompt pages only, so lazy growth can hit
    ``OutOfPages`` mid-decode; the engine survives by evicting the
    youngest lowest-priority sequence, and every stream stays oracle."""
    cfg, model, params = dense_setup
    prompts = _prompts(cfg, [5, 5])
    eng = _engine(cfg, model, params, num_pages=5, max_len=12,
                  overcommit=True)
    res = eng.serve([Request(rid=i, prompt=prompts[i], max_new_tokens=6)
                     for i in range(2)])
    assert eng.n_preempted >= 1                   # growth ran out of pages
    for i in range(2):
        assert res[i].tokens == _oracle(model, cfg, params, prompts[i], 6), i
    assert eng.alloc.live_pages == 0 and eng._reserved == 0


# --------------------------------------------------- SLO / overload control

def test_deadline_aborts_inflight_with_partial_prefix(dense_setup):
    """A sequence past its deadline is aborted mid-stream: pages freed,
    result flagged partial, and the partial tokens are exactly the oracle
    prefix (an abort never corrupts what was already emitted)."""
    cfg, model, params = dense_setup
    [prompt] = _prompts(cfg, [5])
    clk = FakeClock()
    eng = _engine(cfg, model, params, clock=clk)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=8,
                       deadline=5.0))
    for _ in range(3):
        eng.step()
    emitted = len(eng.results[0].tokens)
    assert 0 < emitted < 8
    clk.t = 10.0                                  # blow the SLO
    eng.step()
    assert eng.idle
    r = eng.results[0]
    assert r.finish_reason == "deadline" and r.partial
    assert r.tokens == _oracle(model, cfg, params, prompt, 8)[:emitted]
    assert eng.n_deadline_aborts == 1 and 0 in eng.shed
    assert eng.alloc.live_pages == 0 and eng._reserved == 0


def test_queued_request_past_deadline_is_shed_explicitly(dense_setup):
    """Shedding is never silent: the refused request lands in ``results``
    with finish_reason='shed' and its rid in ``engine.shed``."""
    cfg, model, params = dense_setup
    prompts = _prompts(cfg, [5, 4])
    clk = FakeClock()
    eng = _engine(cfg, model, params, num_pages=5, max_len=16, clock=clk)
    eng.submit(Request(rid=0, prompt=prompts[0], max_new_tokens=8))
    eng.submit(Request(rid=1, prompt=prompts[1], max_new_tokens=4,
                       deadline=2.0))             # queued: pool fits one
    eng.step()
    assert len(eng.active) == 1 and len(eng.pending) == 1
    clk.t = 3.0                                   # rid 1 expires in queue
    res = eng.run()
    assert res[1].finish_reason == "shed" and res[1].tokens == []
    assert eng.shed == [1] and eng.n_shed == 1
    assert res[0].tokens == _oracle(model, cfg, params, prompts[0], 8)
    assert set(res) == {0, 1}                     # nobody silently dropped


def test_provably_unmeetable_slo_shed_at_admission(dense_setup):
    """Admission control sheds a request whose deadline cannot be met even
    with zero queue delay (max_new x rolling step clock overshoots)."""
    cfg, model, params = dense_setup
    prompts = _prompts(cfg, [5, 4])
    clk = FakeClock()
    eng = _engine(cfg, model, params, clock=clk)
    eng.submit(Request(rid=0, prompt=prompts[0], max_new_tokens=4))
    def _stepped():
        clk.t += 1.0                              # each engine step: 1s
        return None
    real_decode = eng._decode_step
    eng._decode_step = lambda: (real_decode(), _stepped())[0]
    eng.step(); eng.step()                        # step clock EMA warms up
    assert eng._step_ema and eng._step_ema > 0.5
    # 8 tokens x ~1s/step >> 3s of headroom: provably unmeetable
    eng.submit(Request(rid=1, prompt=prompts[1], max_new_tokens=8,
                       deadline=clk.t + 3.0))
    res = eng.run()
    assert res[1].finish_reason == "shed" and eng.n_shed == 1
    assert res[0].tokens == _oracle(model, cfg, params, prompts[0], 4)


def test_shedding_off_never_sheds(dense_setup):
    cfg, model, params = dense_setup
    [prompt] = _prompts(cfg, [5])
    clk = FakeClock()
    eng = _engine(cfg, model, params, clock=clk, shedding=False)
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=6,
                       deadline=0.0))             # already expired
    clk.t = 99.0
    res = eng.run()
    assert res[0].finish_reason == "length"
    assert res[0].tokens == _oracle(model, cfg, params, prompt, 6)


def test_small_request_bypasses_blocked_giant(dense_setup):
    """Head-of-line bypass: a giant blocked on pages does not starve a
    small request that fits *now*; with ``hol_bypass=0`` admission is
    strict FIFO and the small one waits."""
    cfg, model, params = dense_setup
    prompts = _prompts(cfg, [5, 7, 3])
    reqs = lambda: [  # noqa: E731
        Request(rid=0, prompt=prompts[0], max_new_tokens=6),   # holds pool
        Request(rid=1, prompt=prompts[1], max_new_tokens=4),   # giant: 3 pg
        Request(rid=2, prompt=prompts[2], max_new_tokens=1),   # small: 1 pg
    ]
    bypass = _engine(cfg, model, params, num_pages=5, max_len=12)
    res = bypass.serve(reqs())
    assert res[2].admitted < res[1].admitted      # small went around
    for i, g in ((0, 6), (1, 4), (2, 1)):
        assert res[i].tokens == _oracle(model, cfg, params, prompts[i], g), i

    fifo = _engine(cfg, model, params, num_pages=5, max_len=12,
                   hol_bypass=0)
    res = fifo.serve(reqs())
    assert res[2].admitted >= res[1].admitted     # strict FIFO: giant first
    for i, g in ((0, 6), (1, 4), (2, 1)):
        assert res[i].tokens == _oracle(model, cfg, params, prompts[i], g), i


def test_priority_preempts_lower_inflight(dense_setup):
    """A high-priority arrival evicts a lower-priority in-flight victim for
    its pages; the victim restores afterwards and both streams stay
    oracle-identical."""
    cfg, model, params = dense_setup
    prompts = _prompts(cfg, [5, 5])
    eng = _engine(cfg, model, params, num_pages=5, max_len=12)
    res = eng.serve([
        Request(rid=0, prompt=prompts[0], max_new_tokens=6, priority=0),
        Request(rid=1, prompt=prompts[1], max_new_tokens=6, priority=5),
    ], arrival_steps=[0, 2])
    assert res[0].preemptions == 1 and res[1].preemptions == 0
    assert res[1].admitted < res[0].token_times[-1]   # jumped the line
    for i in range(2):
        assert res[i].tokens == _oracle(model, cfg, params, prompts[i], 6), i
    assert eng.n_preempted == 1 and eng.n_restored == 1
    assert eng.alloc.live_pages == 0 and eng._reserved == 0


# ------------------------------------------------ fault-injected serving

def test_chaos_injector_is_order_independent():
    spec = ServeFaultSpec(crash_prob=0.2, hang_prob=0.3, seed=5)
    inj = ServeFaultInjector(spec)
    forward = [inj.decide(s) for s in range(40)]
    shuffled = {s: ServeFaultInjector(spec).decide(s)
                for s in np.random.default_rng(0).permutation(40)}
    assert forward == [shuffled[s] for s in range(40)]
    assert CRASH in forward and HANG in forward and None in forward


def test_parse_chaos():
    assert parse_chaos("hang:3,crash:6") == (ServeDrill(HANG, 3),
                                             ServeDrill(CRASH, 6))
    with pytest.raises(ValueError):
        parse_chaos("explode:3")
    with pytest.raises(ValueError):
        parse_chaos("hang:x")


@pytest.mark.parametrize("attention", ["dense", "paged"])
def test_crash_recovery_token_identical(dense_setup, attention):
    """An injected decode-step crash under supervision: the engine rebuilds
    pools+allocator from host truth, re-prefills every survivor, and all
    completed streams equal the fault-free oracle bit-for-bit."""
    cfg, model, params = dense_setup
    prompts = _prompts(cfg, [5, 1, 7])
    gens = [6, 4, 8]
    eng = _engine(cfg, model, params, attention=attention,
                  faults=ServeFaultSpec(drills=(ServeDrill(CRASH, 4),)))
    res = eng.serve([Request(rid=i, prompt=prompts[i],
                             max_new_tokens=gens[i]) for i in range(3)],
                    arrival_steps=[0, 1, 2])
    assert eng.n_rebuilds == 1
    [rep] = eng.recoveries
    assert rep.cause == CRASH and rep.step == 4 and rep.n_survivors >= 1
    assert rep.first_token_s >= 0.0
    for i in range(3):
        assert res[i].tokens == _oracle(model, cfg, params, prompts[i],
                                        gens[i]), (attention, i)
        assert res[i].finish_reason == "length"
    assert eng.alloc.live_pages == 0 and eng._reserved == 0


def test_hang_recovery_via_watchdog(dense_setup):
    """An injected decode hang is classified by the watchdog deadline, then
    recovered exactly like a crash — streams stay oracle-identical."""
    cfg, model, params = dense_setup
    prompts = _prompts(cfg, [5, 3])
    # warm the jit caches first so a cold compile can never be
    # misclassified as the injected hang
    warm = _engine(cfg, model, params)
    warm.serve([Request(rid=i, prompt=prompts[i], max_new_tokens=2)
                for i in range(2)])
    eng = _engine(cfg, model, params, watchdog_s=1.0,
                  faults=ServeFaultSpec(drills=(ServeDrill(HANG, 3),)))
    res = eng.serve([Request(rid=i, prompt=prompts[i], max_new_tokens=5)
                     for i in range(2)])
    assert eng.n_rebuilds == 1
    assert eng.recoveries[0].cause == HANG
    assert eng.recoveries[0].detect_s >= 1.0      # the watchdog deadline
    for i in range(2):
        assert res[i].tokens == _oracle(model, cfg, params, prompts[i], 5), i


def test_unsupervised_fault_raises_with_state_dump(dense_setup):
    """supervise=False: the fault propagates loudly (the CLI maps it to
    exit 2) carrying a full engine-state dump for postmortems."""
    cfg, model, params = dense_setup
    [prompt] = _prompts(cfg, [5])
    eng = _engine(cfg, model, params, supervise=False,
                  faults=ServeFaultSpec(drills=(ServeDrill(CRASH, 2),)))
    eng.submit(Request(rid=0, prompt=prompt, max_new_tokens=8))
    with pytest.raises(ServeFault, match="engine state at fault"):
        eng.run()


def test_hang_spec_requires_watchdog(dense_setup):
    cfg, model, params = dense_setup
    with pytest.raises(ValueError, match="watchdog"):
        _engine(cfg, model, params,
                faults=ServeFaultSpec(drills=(ServeDrill(HANG, 1),)))


def test_run_exhaustion_dumps_engine_state(dense_setup):
    """The stuck-engine diagnostic replaces the bare RuntimeError: it names
    queued/active rids, page occupancy, and reservation totals."""
    cfg, model, params = dense_setup
    prompts = _prompts(cfg, [5, 4])
    eng = _engine(cfg, model, params)
    eng.submit(Request(rid=3, prompt=prompts[0], max_new_tokens=8))
    eng.submit(Request(rid=9, prompt=prompts[1], max_new_tokens=8))
    with pytest.raises(RuntimeError) as ei:
        eng.run(max_steps=2)
    msg = str(ei.value)
    assert "not idle after 2 steps" in msg
    assert "3(len=" in msg and ("9(len=" in msg or "rids=[9]" in msg)
    assert "free=" in msg and "reserved=" in msg and "shed=" in msg


# ================================================================ CLI shim

def test_cli_continuous_smoke(capsys):
    res = launch_serve.main([
        "--arch", "deepseek-7b", "--engine", "continuous",
        "--attention", "paged", "--batch", "2", "--prompt-len", "4",
        "--gen", "3", "--page-size", "4", "--num-pages", "32"])
    assert len(res) == 2
    assert all(len(r.tokens) == 3 for r in res.values())
    assert "served 2 requests" in capsys.readouterr().out


def test_cli_static_smoke(capsys):
    toks = launch_serve.main([
        "--arch", "deepseek-7b", "--batch", "2", "--prompt-len", "4",
        "--gen", "3"])
    assert toks.shape == (2, 3)


def test_cli_full_leaves_the_reduced_preset(monkeypatch, capsys):
    """``--full`` asks for the published widths (``reduced=False``) and the
    default keeps the CPU preset.  The spy still hands back the reduced
    config so the drive stays CPU-sized; what is pinned is the flag."""
    asked = []
    real = launch_serve.get_config

    def spy(arch, reduced=False):
        asked.append(reduced)
        return real(arch, reduced=True)

    monkeypatch.setattr(launch_serve, "get_config", spy)
    argv = ["--arch", "deepseek-7b", "--engine", "continuous",
            "--batch", "1", "--prompt-len", "4", "--gen", "2",
            "--page-size", "4", "--num-pages", "16"]
    res = launch_serve.main(argv + ["--full"])
    assert [len(r.tokens) for r in res.values()] == [2]
    launch_serve.main(argv)
    assert asked == [False, True]
    assert "served 1 requests" in capsys.readouterr().out
