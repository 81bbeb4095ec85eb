"""Paged KV runtime + phase-disaggregated execution.

Covers the PR's serve-path invariants:

* page-allocator properties (hypothesis): no double free, no page ever
  shared between live requests, LIFO free-list reuse after retire,
* materialize/harvest round trips keep per-request state isolated,
* **determinism**: requests decoding interleaved through the phased
  executor produce exactly the tokens they produce when served alone
  (and the same at a small page size and at one page per request),
* tuple context keys (``(phase, bucket)``) survive spec_state.json
  save -> restore losslessly, and a warm restart resumes distinct
  per-phase configs with zero XLA recompiles,
* schedulers account for remaining *prefill* in job size.
"""
import os

import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.checkpoint import restore_spec_state, save_spec_state
from repro.core import IridescentRuntime
from repro.core.runtime import decode_context_key, encode_context_key
from repro.serve import (AdmissionQueue, ContinuousBatcher, DeadlineAware,
                         FCFS, PagedKV, PageError, PagePool, PhasedExecutor,
                         Request, ServeEngine, ServeMetrics,
                         ShortestJobFirst)
from repro.training import phase_context_fn

MAX_LEN = 16
VOCAB = 7


def _template(width: int = 3):
    return {"k": jnp.zeros((1, MAX_LEN, width), jnp.float32),
            "state": jnp.zeros((1, width), jnp.float32),
            "tick": jnp.zeros((), jnp.float32)}


AXES = {"k": ("batch", "seq_kv", "model"),
        "state": ("batch", "model"),
        "tick": ()}


def make_kv(page_size=4, capacity=8 * MAX_LEN, width=3):
    return PagedKV(_template(width), AXES, max_len=MAX_LEN,
                   capacity_tokens=capacity, page_size=page_size)


# -- page allocator properties --------------------------------------------------

@settings(max_examples=20)
@given(st.integers(1, 12), st.integers(1, 8))
def test_pool_allocs_are_unique_until_freed(num_pages, page_size):
    pool = PagePool(num_pages, page_size)
    got = [pool.alloc() for _ in range(num_pages)]
    assert sorted(got) == list(range(num_pages))   # each page handed out once
    with pytest.raises(PageError):
        pool.alloc()                               # exhausted
    for pid in got:
        pool.free(pid)
    assert pool.free_pages == num_pages


def test_pool_double_free_and_foreign_page_raise():
    pool = PagePool(4, 2)
    pid = pool.alloc()
    pool.free(pid)
    with pytest.raises(PageError):
        pool.free(pid)                             # double free
    with pytest.raises(PageError):
        pool.free(99)                              # never belonged here


def test_pool_free_list_reuse_is_lifo():
    pool = PagePool(8, 2)
    a, b = pool.alloc(), pool.alloc()
    pool.free(a)
    pool.free(b)
    assert pool.alloc() == b                       # most recently freed
    assert pool.alloc() == a


@settings(max_examples=15, deadline=None)
@given(st.lists(st.tuples(st.integers(0, 5), st.integers(0, 2)),
                min_size=1, max_size=40))
def test_no_page_shared_between_live_requests(ops):
    """Random join/harvest/retire interleavings: live requests' page sets
    stay disjoint, and retiring everything returns every page."""
    kv = make_kv(page_size=4)
    live: dict[str, int] = {}                      # rid -> tokens written
    for slot, action in ops:
        rid = f"r{slot}"
        if rid not in live:
            kv.join(rid)
            live[rid] = 0
        elif action == 0 and live[rid] + 2 <= MAX_LEN:
            cache, lengths = kv.materialize([rid], 1)
            assert int(lengths[0]) == live[rid]
            kv.harvest([rid], cache, [2])
            live[rid] += 2
        elif action == 1:
            kv.retire(rid)
            del live[rid]
        tables = {r: kv.table(r) for r in live}
        owned = [p for t in tables.values() for p in t.pages]
        assert len(owned) == len(set(owned)), "page shared across requests"
    for rid in list(live):
        kv.retire(rid)
    stats = kv.stats()
    assert stats["live_pages"] == 0
    assert stats["allocs"] == stats["frees"]


def test_retired_pages_are_reused_by_next_join():
    kv = make_kv(page_size=4)
    kv.join("a")
    cache, _ = kv.materialize(["a"], 1)
    kv.harvest(["a"], cache, [8])                  # 2 pages
    pages_a = list(kv.table("a").pages)
    kv.retire("a")
    kv.join("b")
    cache, _ = kv.materialize(["b"], 1)
    kv.harvest(["b"], cache, [8])
    assert set(kv.table("b").pages) == set(pages_a)   # free list reused


def test_harvest_roundtrip_isolates_rows():
    """Distinct values written for interleaved requests come back on the
    right rows at the right slots — at a small page size and at one page
    per request."""
    for page in (4, MAX_LEN):
        kv = make_kv(page_size=page)
        kv.join("a")
        kv.join("b")
        for step in range(3):
            cache, lengths = kv.materialize(["a", "b"], 4)   # padded batch
            k = np.array(cache["k"])
            st_ = np.array(cache["state"])
            for row, base in ((0, 100.0), (1, 200.0)):
                assert int(lengths[row]) == step
                # history written in earlier steps is visible
                np.testing.assert_array_equal(
                    k[row, :step, 0], [base + s for s in range(step)])
                k[row, step] = base + step
                st_[row] = base + step
            kv.harvest(["a", "b"], {"k": jnp.asarray(k),
                                    "state": jnp.asarray(st_),
                                    "tick": cache["tick"]}, [1, 1])
        # per-row recurrent state tracked independently of the pages
        cache, _ = kv.materialize(["b", "a"], 2)    # reversed order
        assert np.asarray(cache["state"])[0, 0] == 202.0
        assert np.asarray(cache["state"])[1, 0] == 102.0


def test_join_live_and_overflow_raise():
    kv = make_kv(page_size=4, capacity=MAX_LEN)    # one request's worth
    kv.join("a")
    with pytest.raises(PageError):
        kv.join("a")                               # already live
    cache, _ = kv.materialize(["a"], 1)
    with pytest.raises(PageError):
        kv.harvest(["a"], cache, [MAX_LEN + 1])    # past max_len
    kv.harvest(["a"], cache, [MAX_LEN])            # exactly full is fine
    kv.join("b")
    cache, _ = kv.materialize(["b"], 1)
    with pytest.raises(PageError):                 # pool exhausted
        kv.harvest(["b"], cache, [1])


# -- device pools against a host reference ---------------------------------------

LAYERS = 2


def _layered_template():
    """Leaves laid out as the models lay theirs: a stacked-layer bf16 KV
    leaf, one with its sequence axis ahead of its batch axis, row state
    whose template is not zero, and a shared leaf."""
    return {"kv": jnp.zeros((LAYERS, 1, 2, MAX_LEN, 4), jnp.bfloat16),
            "sk": jnp.zeros((MAX_LEN, 3, 1), jnp.float32),
            "state": jnp.full((LAYERS, 1, 5), 7.0, jnp.float32),
            "tick": jnp.arange(3, dtype=jnp.int32)}


LAYERED_AXES = {"kv": ("layers", "batch", "kv_heads", "seq_kv", "head_dim"),
                "sk": ("seq_kv", "model", "batch"),
                "state": ("layers", "batch", "model"),
                "tick": (None,)}


class _HostReference:
    """The dense cache as the host staging built it: zeros, the template
    row in every row-state row, then each request's written slots and its
    last harvested state copied in; the shared leaf as the template."""

    def __init__(self, template):
        self.template = {k: np.asarray(v) for k, v in template.items()}
        self.seqs: dict = {}                 # rid -> {"kv", "sk", "state"}

    def join(self, rid):
        t = self.template
        self.seqs[rid] = {"kv": np.zeros_like(t["kv"][:, 0]),
                          "sk": np.zeros_like(t["sk"][:, :, 0]),
                          "state": t["state"][:, 0].copy()}

    def dense(self, rids, batch, lengths):
        t = self.template
        kv = np.zeros(t["kv"].shape[:1] + (batch,) + t["kv"].shape[2:],
                      t["kv"].dtype)
        sk = np.zeros(t["sk"].shape[:2] + (batch,), t["sk"].dtype)
        state = np.repeat(t["state"], batch, axis=1)
        for r, rid in enumerate(rids):
            n, seq = lengths[rid], self.seqs[rid]
            kv[:, r, :, :n] = seq["kv"][:, :, :n]
            sk[:n, :, r] = seq["sk"][:n]
            state[:, r] = seq["state"]
        return {"kv": kv, "sk": sk, "state": state, "tick": t["tick"]}

    def harvest(self, rids, new, starts, n_new):
        for r, (rid, a, n) in enumerate(zip(rids, starts, n_new)):
            seq = self.seqs[rid]
            seq["kv"][:, :, a:a + n] = new["kv"][:, r, :, a:a + n]
            seq["sk"][a:a + n] = new["sk"][a:a + n, :, r]
            seq["state"] = new["state"][:, r].copy()


def _assert_same_bits(got, want):
    for name in want:
        g, w = np.asarray(got[name]), want[name]
        assert g.dtype == w.dtype and g.shape == w.shape, name
        np.testing.assert_array_equal(g.view(np.uint8), w.view(np.uint8),
                                      err_msg=name)


@pytest.mark.parametrize("page", [4, 8, MAX_LEN],
                         ids=["paged-4", "paged-8", "contig-16"])
def test_device_pools_match_host_staging_bit_for_bit(page):
    """Random join/harvest/retire interleavings: every dense cache equals
    the host reference bit for bit — padding rows, slots past each row's
    length after LIFO page reuse, rows in any order — and harvest takes
    only the written slots (the step fills every other slot with noise)."""
    rng = np.random.default_rng(page)
    template = _layered_template()
    kv = PagedKV(template, LAYERED_AXES, max_len=MAX_LEN,
                 capacity_tokens=4 * MAX_LEN, page_size=page)
    ref = _HostReference(template)
    live: dict[str, int] = {}
    for step in range(60):
        action = rng.integers(4)
        if action == 0 and len(live) < 4:
            rid = f"r{step}"
            kv.join(rid)
            ref.join(rid)
            live[rid] = 0
        elif action == 1 and live:
            rid = str(rng.choice(sorted(live)))
            kv.retire(rid)
            del live[rid]
        elif live:
            rids = [str(r) for r in rng.permutation(sorted(live))]
            rids = rids[:rng.integers(1, len(rids) + 1)]
            batch = len(rids) + int(rng.integers(0, 3))
            cache, lengths = kv.materialize(rids, batch)
            assert list(lengths[:len(rids)]) == [live[r] for r in rids]
            _assert_same_bits(cache, ref.dense(rids, batch, live))
            n_new = [int(rng.integers(0, min(5, MAX_LEN - live[r]) + 1))
                     for r in rids]
            noise = {name: jnp.asarray(rng.normal(size=np.shape(leaf)) * 9,
                                       leaf.dtype)
                     for name, leaf in cache.items() if name != "tick"}
            noise["tick"] = cache["tick"]
            kv.harvest(rids, noise, n_new)
            ref.harvest(rids, {k: np.asarray(v) for k, v in noise.items()},
                        [live[r] for r in rids], n_new)
            for r, n in zip(rids, n_new):
                live[r] += n


def _pool_arrays(kv):
    return ([np.asarray(a) for a in kv._pools],
            [np.asarray(a) for a in kv._rows])


def test_harvest_changes_only_the_written_slots_and_real_rows():
    kv = PagedKV(_layered_template(), LAYERED_AXES, max_len=MAX_LEN,
                 capacity_tokens=4 * MAX_LEN, page_size=4)
    for rid in ("a", "b", "c"):
        kv.join(rid)
    cache, _ = kv.materialize(["a", "b", "c"], 4)
    kv.harvest(["a", "b", "c"], cache, [6, 3, 0])
    before_pools, before_rows = _pool_arrays(kv)
    cache, _ = kv.materialize(["c", "a"], 4)
    step = {k: (v + 1 if k != "tick" else v) for k, v in cache.items()}
    kv.harvest(["c", "a"], step, [2, 1])
    after_pools, after_rows = _pool_arrays(kv)
    # token rows of the written slots: c's 0-1 (its first page) and a's 6
    pages = {r: kv.table(r).pages for r in ("a", "c")}
    written = {pages["c"][0] * 4, pages["c"][0] * 4 + 1, pages["a"][1] * 4 + 2}
    for before, after in zip(before_pools, after_pools):
        changed = {int(t) for t in np.nonzero(
            (before != after).reshape(len(before), -1).any(axis=1))[0]}
        assert changed == written
    # row state: the slots of c and a, and the sink the padding rows wrote
    slots = {kv.table("c").slot, kv.table("a").slot, kv._sink_slot}
    for before, after in zip(before_rows, after_rows):
        changed = {int(s) for s in np.nonzero(
            (before != after).swapaxes(0, 1).reshape(before.shape[1], -1)
            .any(axis=1))[0]}
        assert changed == slots
    assert kv.table("b").slot not in slots
    assert kv._template_slot not in slots


def test_page_error_leaves_the_device_pools_untouched():
    kv = PagedKV(_layered_template(), LAYERED_AXES, max_len=MAX_LEN,
                 capacity_tokens=MAX_LEN, page_size=4)   # one row slot
    kv.join("a")
    cache, _ = kv.materialize(["a"], 1)
    kv.harvest(["a"], cache, [10])                       # 3 of 4 pages
    kv.join("b")
    before_pools, before_rows = _pool_arrays(kv)
    cache, _ = kv.materialize(["a", "b"], 2)
    for n_new in ([6, 1], [2, 0], [MAX_LEN, 0]):
        # out of pages; out of row-state slots; past max_len
        with pytest.raises(PageError):
            kv.harvest(["a", "b"], cache, n_new)
    assert kv.length("a") == 10 and kv.length("b") == 0
    assert kv.table("b").slot is None and not kv.table("b").pages
    after_pools, after_rows = _pool_arrays(kv)
    for before, after in zip(before_pools + before_rows,
                             after_pools + after_rows):
        np.testing.assert_array_equal(before, after)


def test_pools_are_fixed_at_construction():
    """The pools are built once, before the first join, and joins,
    harvests and retires move pages without growing or dropping them."""
    kv = make_kv(page_size=4)
    # 128 token rows and the zero row, 3 float32 lanes each; 8 row-state
    # slots, the template row and the sink
    want = {"page_size": 4, "num_pages": 32,
            "pool_bytes": (8 * MAX_LEN + 1) * 3 * 4,
            "row_state_bytes": (8 + 2) * 3 * 4}
    shapes = [a.shape for a in kv._pools]

    def check():
        assert {k: kv.stats()[k] for k in want} == want
        assert [a.shape for a in kv._pools] == shapes

    check()
    kv.join("a")
    kv.join("b")
    check()
    cache, _ = kv.materialize(["a", "b"], 2)
    kv.harvest(["a", "b"], cache, [5, 1])
    check()
    assert kv.stats()["live_pages"] == 3
    kv.retire("a")
    check()
    kv.retire("b")
    check()
    assert kv.stats()["live_pages"] == 0 and kv.stats()["high_water"] == 3


def test_decode_step_uploads_index_tables_and_shared_leaves_only():
    import time

    from repro.core import telemetry

    rt = IridescentRuntime(async_compile=False)
    handler = rt.register("hist", _history_builder,
                          context_fn=phase_context_fn)
    kv = make_kv(page_size=4)
    executor = PhasedExecutor(handler, None, kv, prefill_chunk=2,
                              prompt_fn=_prompt_fn)
    engine = ServeEngine(handler, None,
                         ContinuousBatcher(2, scheme="single"), FCFS(),
                         executor=executor, queue=AdmissionQueue(),
                         metrics=ServeMetrics())
    try:
        for _ in range(2):
            assert engine.submit(Request(prompt_tokens=3, max_new_tokens=4))
        while not engine.active or any(r.prefilling for r in engine.active):
            engine.step()
        t0 = time.perf_counter()
        engine.step()                              # one decode step
    finally:
        rt.shutdown()
    spans = [sp for sp in telemetry.recent_spans() if sp[1] >= t0]
    assert [n for n, *_ in spans].count("serve.exec.decode") == 1
    assert not any(n == "kv.download" for n, *_ in spans)
    up = sum(a["bytes"] for n, _, _, a in spans if n == "kv.upload")
    # int32 tables of the bucket's 2 rows: materialize's 16 slots and
    # row-state slot; harvest's 1 slot, window start and row-state slot;
    # and the shared tick
    assert up == 2 * (MAX_LEN + 1) * 4 + 2 * (1 + 2) * 4 + 4


# -- phased executor determinism ------------------------------------------------

def _history_builder(spec):
    """Serve-contract handler whose next token is a deterministic function
    of the request's whole history: tokens+1 are written at their slots,
    and the logits peak at ``sum(written) mod VOCAB``.  Any cross-request
    page sharing, lost row state, or misplaced write changes the output
    stream."""

    def f(params, cache, tokens, pos, n_new):
        toks = tokens if tokens.ndim == 2 else tokens[:, None]
        c = toks.shape[1]
        k = cache["k"]
        slots = jnp.arange(k.shape[1])
        for t in range(c):
            at = (slots[None, :] == (pos + t)[:, None]) \
                & (t < n_new)[:, None]
            k = k.at[:, :, 0].set(
                jnp.where(at, (toks[:, t, None] + 1).astype(k.dtype),
                          k[:, :, 0]))
        total = k[:, :, 0].sum(axis=1)
        peak = jnp.mod(total, float(VOCAB))
        logits = -(jnp.arange(VOCAB)[None, :].astype(jnp.float32)
                   - peak[:, None]) ** 2
        return logits, {"k": k, "state": cache["state"] + 1.0,
                        "tick": cache["tick"]}

    return f


def _prompt_fn(req):
    return (np.arange(req.prompt_tokens, dtype=np.int32) * 3 + 1) % VOCAB


def _serve(reqs, page_size=4, bucket=2):
    rt = IridescentRuntime(async_compile=False)
    handler = rt.register("hist", _history_builder,
                          context_fn=phase_context_fn)
    kv = make_kv(page_size=page_size)
    executor = PhasedExecutor(handler, None, kv, prefill_chunk=2,
                              prompt_fn=_prompt_fn)
    engine = ServeEngine(handler, None,
                         ContinuousBatcher(bucket, scheme="single"),
                         FCFS(), executor=executor, queue=AdmissionQueue(),
                         metrics=ServeMetrics())
    for r in reqs:
        assert engine.submit(r)
    engine.run()
    rt.shutdown()
    return [list(r.payload) for r in reqs]


def test_interleaved_decode_matches_sequential():
    specs = [(5, 4), (3, 6), (7, 3)]               # (prompt, budget)
    together = _serve([Request(prompt_tokens=p, max_new_tokens=g)
                       for p, g in specs], bucket=2)
    alone = [_serve([Request(prompt_tokens=p, max_new_tokens=g)],
                    bucket=2)[0]
             for p, g in specs]
    assert together == alone
    for (p, g), out in zip(specs, together):
        assert len(out) == g
        assert all(0 <= t < VOCAB for t in out)


def test_paged_and_contig_geometries_decode_identically():
    """Page sizes 4 and MAX_LEN (one page per request) decode
    identically."""
    specs = [(5, 4), (3, 6)]
    small = _serve([Request(prompt_tokens=p, max_new_tokens=g)
                    for p, g in specs], page_size=4)
    whole = _serve([Request(prompt_tokens=p, max_new_tokens=g)
                    for p, g in specs], page_size=MAX_LEN)
    assert small == whole


def test_executor_rejects_requests_that_cannot_fit():
    rt = IridescentRuntime(async_compile=False)
    handler = rt.register("hist", _history_builder,
                          context_fn=phase_context_fn)
    executor = PhasedExecutor(handler, None, make_kv(), prefill_chunk=2,
                              prompt_fn=_prompt_fn)
    with pytest.raises(ValueError):
        executor.ensure_joined(Request(prompt_tokens=MAX_LEN,
                                       max_new_tokens=1))
    rt.shutdown()


# -- tuple context keys: lossless persistence ----------------------------------

@settings(max_examples=20)
@given(st.tuples(st.sampled_from(["prefill", "decode"]),
                 st.integers(1, 128)))
def test_phase_context_key_roundtrip(key):
    enc = encode_context_key(key)
    assert decode_context_key(enc) == key
    assert encode_context_key(decode_context_key(enc)) == enc


@pytest.mark.parametrize("key", [
    ("prefill", 8),
    ("decode", 1),
    (("nested", 2), "x"),
    ("mixed", 3, True, None),
    (),
])
def test_tuple_context_key_roundtrip_cases(key):
    enc = encode_context_key(key)
    assert decode_context_key(enc) == key
    assert encode_context_key(decode_context_key(enc)) == enc


def _phase_toy_builder(spec):
    scale = spec.enum("scale", 1, (1, 2), guarded=False)

    def f(params, cache, tokens, pos, n_new):
        toks = tokens if tokens.ndim == 2 else tokens[:, None]
        return toks.sum(axis=1).astype(jnp.float32) * float(scale), cache

    return f


def _phase_calls(handler):
    cache = jnp.zeros((2, 4), jnp.float32)
    pos = jnp.zeros((2,), jnp.int32)
    handler(None, cache, jnp.zeros((2, 4), jnp.int32), pos,
            jnp.full((2,), 4, jnp.int32))              # ('prefill', 2)
    handler(None, cache, jnp.zeros((2,), jnp.int32), pos,
            jnp.ones((2,), jnp.int32))                 # ('decode', 2)


def test_per_phase_configs_warm_restart_zero_recompiles(tmp_path):
    """ISSUE acceptance: distinct per-(phase, bucket) configs persist
    through spec_state.json v2 tuple keys and come back on a warm restart
    without a single XLA recompile."""
    cache_dir = str(tmp_path / "state")
    state_path = os.path.join(cache_dir, "spec_state.json")
    variants = os.path.join(cache_dir, "variants")

    rt = IridescentRuntime(async_compile=False, variant_cache=variants)
    handler = rt.register("phase_toy", _phase_toy_builder,
                          context_fn=phase_context_fn)
    _phase_calls(handler)                              # materialize contexts
    handler.specialize({"scale": 2}, context=("prefill", 2), wait=True)
    handler.specialize({"scale": 1}, context=("decode", 2), wait=True)
    _phase_calls(handler)
    assert rt.compile_stats()["xla_compiles"] > 0
    save_spec_state(state_path, rt)
    rt.shutdown()

    rt2 = IridescentRuntime(async_compile=False, variant_cache=variants)
    handler2 = rt2.register("phase_toy", _phase_toy_builder,
                            context_fn=phase_context_fn)
    assert restore_spec_state(state_path, rt2, wait=True)
    _phase_calls(handler2)                             # traffic re-seeds
    assert handler2.active_config(
        context=("prefill", 2))["scale"] == 2
    assert handler2.active_config(
        context=("decode", 2))["scale"] == 1
    stats = rt2.compile_stats()
    assert stats["xla_compiles"] == 0, f"warm restart recompiled: {stats}"
    assert stats["cache_hits"] > 0
    rt2.shutdown()


# -- schedulers: job size includes remaining prefill ---------------------------

def _mk(prompt, budget, consumed=0, generated=0, arrival=0.0, deadline=None):
    r = Request(prompt_tokens=prompt, max_new_tokens=budget,
                deadline_s=deadline)
    r.arrival_t = arrival
    r.prompt_consumed = consumed
    r.generated = generated
    return r


def test_sjf_counts_remaining_prefill_as_work():
    long_prompt = _mk(2048, 4)                     # huge prefill ahead
    short_prompt = _mk(16, 32)
    key = ShortestJobFirst().key(now=0.0)
    assert key(short_prompt) < key(long_prompt)    # 48 < 2052
    assert long_prompt.remaining_work == 2052
    assert short_prompt.remaining_work == 48


def test_sjf_mid_stream_prefill_progress_reorders():
    half_done = _mk(100, 10, consumed=90, generated=0)    # 20 left
    fresh = _mk(40, 10)                                   # 50 left
    key = ShortestJobFirst().key(now=0.0)
    assert key(half_done) < key(fresh)


def test_legacy_executor_requests_fall_back_to_decode_budget():
    # A legacy (non-phased) executor never advances prompt_consumed; once
    # decoding, the prompt must not be double-counted as pending work.
    legacy = _mk(100, 10, consumed=0, generated=4)
    assert legacy.remaining_prefill == 0
    assert legacy.remaining_work == 6


def test_deadline_aware_breaks_ties_by_remaining_work():
    urgent_big = _mk(200, 8, arrival=0.0, deadline=1.0)
    urgent_small = _mk(10, 8, arrival=0.0, deadline=1.0)
    relaxed = _mk(1, 1, arrival=0.0, deadline=9.0)
    key = DeadlineAware().key(now=0.0)
    order = sorted([relaxed, urgent_big, urgent_small], key=key)
    assert order == [urgent_small, urgent_big, relaxed]
