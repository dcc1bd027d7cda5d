"""Paged KV decode plane: allocator/prefix-cache invariants (hypothesis),
greedy byte-parity paged-vs-dense on the live engine (single device and
TP groups 1/2/4), PD handoff across unequal sharded groups, FT
snapshot/restore, admission-time rejection, and the ragged paged decode
kernel against its gathered-dense oracle.

The TP tests need >= 8 host devices; run with
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (set below when
this module is the first jax importer, e.g. a standalone pytest run).
"""
import os
import sys

if "jax" not in sys.modules:      # must precede the first jax import
    os.environ.setdefault(
        "XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:          # CI image without hypothesis: run the property
    from _hyp_compat import given, settings, st   # tests on deterministic
    # fallback examples instead of skipping the whole module

from repro.configs import get_config
from repro.core import build_pd_proxy
from repro.kernels import ref as R
from repro.kernels.decode_attention import ragged_paged_decode
from repro.launch.mesh import allocate_engine_devices, make_group_mesh
from repro.models import Model
from repro.rl.engine import GenRequest, InferenceEngine
from repro.rl.paged_kv import PagedKVAllocator, PageLeakError, PrefixCache

PAGE = 8

needs_8_devices = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs XLA_FLAGS=--xla_force_host_platform_device_count=8")


# ---------------------------------------------------------------------------
# allocator + prefix cache invariants
# ---------------------------------------------------------------------------
def test_alloc_is_all_or_nothing():
    a = PagedKVAllocator(4, PAGE)
    got = a.alloc(3)
    assert got is not None and len(got) == 3
    assert a.alloc(2) is None            # only 1 left: nothing handed out
    assert a.free_pages == 1
    a.decref(got)
    assert a.free_pages == 4
    a.check(external_refs={})


def test_cow_exclusive_shared_and_exhausted():
    a = PagedKVAllocator(2, PAGE)
    [p] = a.alloc(1)
    assert a.cow(p) == p                 # exclusive: same page back
    a.incref([p])                        # now shared (2 holders)
    q = a.cow(p)
    assert q is not None and q != p      # writer got a private copy
    assert a.refcount(p) == 1 and a.refcount(q) == 1
    a.incref([p])
    assert a.cow(p) is None              # shared + pool exhausted
    a.decref([p, p, q])
    a.check(external_refs={})


def test_refcount_misuse_raises():
    a = PagedKVAllocator(2, PAGE)
    [p] = a.alloc(1)
    a.decref([p])
    with pytest.raises(PageLeakError):
        a.decref([p])
    with pytest.raises(PageLeakError):
        a.incref([p])
    with pytest.raises(PageLeakError):
        a.cow(p)


def test_dirty_since_tracks_allocated_writes_only():
    a = PagedKVAllocator(4, PAGE)
    pids = a.alloc(2)
    base = a.clock()
    a.note_write(pids)
    assert sorted(a.dirty_since(base)) == sorted(pids)
    assert a.dirty_since(a.clock()) == []
    a.decref([pids[0]])                  # freed page: contents are dead
    assert a.dirty_since(base) == [pids[1]]
    a.decref([pids[1]])


@settings(deadline=None, max_examples=60)
@given(st.lists(st.tuples(st.integers(min_value=0, max_value=3),
                          st.integers(min_value=0, max_value=5)),
                min_size=1, max_size=40))
def test_allocator_invariants_under_random_ops(ops):
    """Random alloc/incref/decref/cow traffic against a shadow holder
    ledger: ``check(external_refs)`` must hold after every op."""
    a = PagedKVAllocator(6, PAGE)
    holders = {}                         # pid -> how many refs WE hold

    def live():
        return [p for p, n in holders.items() if n > 0]

    for op, arg in ops:
        if op == 0:                      # alloc(arg)
            pids = a.alloc(arg)
            if pids is not None:
                for p in pids:
                    holders[p] = holders.get(p, 0) + 1
        elif op == 1 and live():         # incref one live page
            p = live()[arg % len(live())]
            a.incref([p])
            holders[p] += 1
        elif op == 2 and live():         # decref one live page
            p = live()[arg % len(live())]
            a.decref([p])
            holders[p] -= 1
        elif op == 3 and live():         # cow one live page
            p = live()[arg % len(live())]
            q = a.cow(p)
            if q is not None and q != p:
                holders[p] -= 1
                holders[q] = holders.get(q, 0) + 1
        a.check(external_refs={p: n for p, n in holders.items() if n > 0})
    a.decref([p for p in holders for _ in range(holders[p])])
    a.check(external_refs={})


def test_prefix_cache_match_insert_evict():
    a = PagedKVAllocator(8, 2)
    c = PrefixCache(a, page_size=2)
    toks = [1, 2, 3, 4, 5]               # 2 full pages + 1-token tail
    pids = a.alloc(3)
    c.insert(toks, pids)
    assert c.cached_pages == 2           # tail page never cached
    assert c.match(toks) == pids[:2]
    assert c.match([1, 2, 9, 9]) == pids[:1]
    assert c.match([7, 7]) == []
    # cache + our table each hold a ref; dropping ours keeps pages alive
    a.decref(pids)
    a.check(external_refs={p: 1 for p in c.page_ids()})
    # LRU leaf eviction unwinds child-first and frees to the pool
    freed = c.evict(1)
    assert freed == 1 and c.cached_pages == 1
    c.clear()
    a.check(external_refs={})
    assert a.free_pages == a.num_pages


def test_prefix_cache_existing_nodes_win():
    a = PagedKVAllocator(8, 2)
    c = PrefixCache(a, page_size=2)
    first = a.alloc(2)
    c.insert([1, 2, 3, 4], first)
    second = a.alloc(2)
    c.insert([1, 2, 3, 4], second)       # same tokens, different pages
    assert c.match([1, 2, 3, 4]) == first
    a.decref(first)
    a.decref(second)
    c.clear()
    a.check(external_refs={})


# ---------------------------------------------------------------------------
# engine: paged vs dense byte-parity (single device)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def tiny():
    cfg = get_config("tiny")
    model = Model(cfg, remat=False)
    return model, model.init(jax.random.PRNGKey(0))


def _engine(model, params, paged, *, mesh=None, slots=4, max_len=64, k=4,
            seed=3, role="colocated"):
    return InferenceEngine(model, params, max_slots=slots, max_len=max_len,
                           seed=seed, steps_per_dispatch=k, role=role,
                           mesh=mesh, paged=paged, page_size=PAGE)


def _serve(eng, prompts, max_new=10, temperature=0.0):
    for j, p in enumerate(prompts):
        eng.add_request(GenRequest(request_id=f"r{j}", prompt=list(p),
                                   max_new_tokens=max_new,
                                   temperature=temperature))
    eng.run_until_idle()
    return [eng.pop_result(f"r{j}") for j in range(len(prompts))]


PROMPTS = [[1, 5, 7, 9], [2, 4, 6, 8, 10, 12, 3], [9, 9, 1], [3] * 17]


def test_greedy_parity_paged_vs_dense(tiny):
    model, params = tiny
    dense = _serve(_engine(model, params, False), PROMPTS)
    paged = _serve(_engine(model, params, True), PROMPTS)
    for d, p in zip(dense, paged):
        assert p.tokens == d.tokens
        assert p.logprobs == d.logprobs


def test_prefix_fork_parity_and_stats(tiny):
    model, params = tiny
    shared = list(range(1, 25))          # 3 full pages of 8 + 0-token tail
    eng = _engine(model, params, True)
    paged = _serve(eng, [shared, shared])
    dense = _serve(_engine(model, params, False), [shared, shared])
    assert [r.tokens for r in paged] == [r.tokens for r in dense]
    # the fork's TAIL prefill runs a different matmul shape (8 queries x
    # 24 keys) than the dense full prefill, so its logprob bits depend on
    # XLA:CPU reduction tiling (this module's 8-virtual-device flag
    # changes it); token streams must still match exactly
    for pr, dr in zip(paged, dense):
        np.testing.assert_allclose(pr.logprobs, dr.logprobs,
                                   rtol=1e-5, atol=1e-5)
    stt = eng.stats()
    assert stt["shared_prefix_tokens"] >= 2 * PAGE
    assert stt["prefix_hits"] >= 1
    # after drain only the prefix cache holds pages
    eng._alloc.check(external_refs={p: 1 for p in eng._prefix.page_ids()})


def test_too_long_rejected_at_submit(tiny):
    model, params = tiny
    eng = InferenceEngine(model, params, max_slots=2, max_len=32, seed=0,
                          paged=True, page_size=PAGE)
    eng.add_request(GenRequest(request_id="big", prompt=list(range(1, 31)),
                               max_new_tokens=20, temperature=0.0))
    r = eng.pop_result("big")
    assert r is not None and r.finish_reason == "aborted"
    assert eng.stats()["rejected_too_long"] == 1
    # an admissible request on the same engine still serves normally
    [ok] = _serve(eng, [[1, 2, 3]], max_new=4)
    assert len(ok.tokens) == 4


def test_dense_engine_also_rejects_too_long(tiny):
    model, params = tiny
    eng = InferenceEngine(model, params, max_slots=2, max_len=16, seed=0)
    eng.add_request(GenRequest(request_id="big", prompt=list(range(1, 15)),
                               max_new_tokens=10, temperature=0.0))
    r = eng.pop_result("big")
    assert r is not None and r.finish_reason == "aborted"
    assert eng.stats()["rejected_too_long"] == 1


def test_crash_resets_pool_bookkeeping(tiny):
    model, params = tiny
    eng = _engine(model, params, True)
    eng.add_request(GenRequest(request_id="c", prompt=[1, 2, 3, 4, 5],
                               max_new_tokens=20, temperature=0.0))
    eng.step()
    assert eng._alloc.used_pages > 0
    eng.crash()
    eng._alloc.check(external_refs={})
    assert eng.stats()["free_pages"] == eng.num_pages
    assert eng.stats()["prefix_cached_pages"] == 0


def test_midflight_weight_sync_parity(tiny):
    model, params = tiny

    def sync_run(paged):
        eng = _engine(model, params, paged, slots=2, seed=7, k=2)
        eng.add_request(GenRequest(request_id="r",
                                   prompt=list(range(1, 25)),
                                   max_new_tokens=16, temperature=0.0))
        for _ in range(3):
            eng.step()
        eng.update_params(jax.tree.map(lambda x: x * 1.01, params), 1)
        eng.run_until_idle()
        return eng.pop_result("r")

    d, p = sync_run(False), sync_run(True)
    assert p.tokens == d.tokens
    # one request in two slots: the dense decode dispatch runs batch 2
    # (max_slots) while the paged one compacts to batch 1, so the float32
    # matmul reductions differ in order and the logprobs differ in the
    # last bits (~1e-6 at |lp|~3) from the first decode step on, before
    # and after the sync alike; the token streams must still match
    np.testing.assert_allclose(p.logprobs, d.logprobs, rtol=1e-5, atol=1e-5)


def test_incremental_capture_shrinks_when_idle(tiny):
    model, params = tiny
    eng = _engine(model, params, True, slots=2, k=2, seed=11)
    eng.add_request(GenRequest(request_id="c", prompt=list(range(1, 10)),
                               max_new_tokens=40, temperature=0.0))
    eng.step()
    cap1 = eng.capture_kv_incremental()
    assert cap1["captured_bytes"] > 0 and cap1["slots"]
    eng.step()
    cap2 = eng.capture_kv_incremental()
    # a 2-token block dirties at most one fresh page per leaf: strictly
    # fewer bytes than the post-prefill capture
    assert 0 < cap2["captured_bytes"] < cap1["captured_bytes"]
    eng.run_until_idle()


def test_snapshot_restore_paged_engine(tiny):
    """Kill a paged engine mid-flight; the KVHandoff snapshot (dense
    portable format) re-injects into a fresh paged engine and finishes
    byte-identically."""
    model, params = tiny
    [ref] = _serve(_engine(model, params, False, slots=2, max_len=96,
                           seed=0), [PROMPTS[0]], max_new=24)
    eng = _engine(model, params, True, slots=2, max_len=96, seed=0, k=4)
    eng.add_request(GenRequest(request_id="r0", prompt=list(PROMPTS[0]),
                               max_new_tokens=24, temperature=0.0))
    eng.step()
    eng.step()                           # mid-flight
    [hf] = eng.snapshot_slots()
    assert isinstance(jax.tree.leaves(hf.cache)[0], np.ndarray)
    eng.crash()
    dst = _engine(model, params, True, slots=2, max_len=96, seed=0, k=4)
    dst.inject(hf)
    dst.run_until_idle()
    assert dst.pop_result("r0").tokens == ref.tokens


def test_pd_handoff_parity_single_device(tiny):
    model, params = tiny

    def pd(paged):
        pre = _engine(model, params, paged, slots=1, seed=3, role="prefill")
        dec = _engine(model, params, paged, slots=1, seed=4, k=2)
        pre.on_handoff = dec.inject
        pre.add_request(GenRequest(request_id="h", prompt=[4, 3, 2, 1, 5, 6],
                                   max_new_tokens=10, temperature=0.0))
        pre.run_until_idle()
        dec.run_until_idle()
        return dec.pop_result("h")

    d, p = pd(False), pd(True)
    assert p is not None and p.tokens == d.tokens
    assert p.logprobs == d.logprobs


# ---------------------------------------------------------------------------
# TP groups: paged parity at 1/2/4 and sharded PD handoff 2 -> 4
# ---------------------------------------------------------------------------
# tiny with num_kv_heads=4 so group 4 shards the KV heads too
TP_CFG = get_config("tiny").with_(name="tiny-paged-tp", num_kv_heads=4)


def _mesh(n):
    return make_group_mesh(allocate_engine_devices([n])[0])


@needs_8_devices
def test_paged_greedy_parity_across_group_sizes():
    model = Model(TP_CFG, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    prompt = [1, 5, 7, 9, 3]
    [ref] = _serve(_engine(model, params, False, slots=2, max_len=96),
                   [prompt], max_new=12)
    for n in (1, 2, 4):
        [got] = _serve(_engine(model, params, True, slots=2, max_len=96,
                               mesh=_mesh(n)), [prompt], max_new=12)
        assert got.tokens == ref.tokens, \
            f"paged group size {n} diverged from dense single-device"
        # sharded matmul reductions don't preserve logprob bits vs the
        # single-device ref (same contract as test_sharded_engine)
        np.testing.assert_allclose(got.logprobs, ref.logprobs,
                                   rtol=1e-5, atol=1e-5)


@needs_8_devices
def test_paged_pd_handoff_across_unequal_groups():
    model = Model(TP_CFG, remat=False)
    params = model.init(jax.random.PRNGKey(0))
    prompts = [[1, 5, 7, 9], [1, 2, 3]]
    refs = [_serve(_engine(model, params, False, slots=2, max_len=96),
                   [p], max_new=6)[0] for p in prompts]
    proxy = build_pd_proxy(model, params, max_slots=4, max_len=96, seed=7,
                           prefill_devices_per_engine=2,
                           decode_devices_per_engine=4,
                           paged=True, page_size=PAGE)
    assert all(h.engine.paged for h in proxy.handles)
    out = {}
    for i, p in enumerate(prompts):
        proxy.submit(GenRequest(request_id=f"r{i}", prompt=list(p),
                                max_new_tokens=6, temperature=0.0),
                     callback=lambda res: out.__setitem__(
                         res.request_id, res))
    pumps = 0
    while proxy.busy:
        proxy.pump()
        pumps += 1
        assert pumps < 2000, "proxy did not drain"
    for i, ref in enumerate(refs):
        assert out[f"r{i}"].tokens == ref.tokens
    assert proxy.stats()["handoffs"] == len(prompts)


# ---------------------------------------------------------------------------
# ragged paged decode kernel vs gathered-dense oracle
# ---------------------------------------------------------------------------
@settings(deadline=None, max_examples=8)
@given(st.integers(min_value=1, max_value=4),
       st.integers(min_value=0, max_value=3))
def test_ragged_paged_decode_matches_ref(batch, zero_rows):
    page, P, kvH, H, hd = 8, 4, 2, 4, 16
    key = jax.random.PRNGKey(batch * 7 + zero_rows)
    kq, kk, kv, kl = jax.random.split(key, 4)
    q = jax.random.normal(kq, (batch, H, hd), jnp.float32)
    pool_k = jax.random.normal(kk, (batch * P + 1, kvH, page, hd))
    pool_v = jax.random.normal(kv, (batch * P + 1, kvH, page, hd))
    tables = jnp.arange(batch * P, dtype=jnp.int32).reshape(batch, P)
    lens = jax.random.randint(kl, (batch,), 1, P * page + 1)
    lens = lens.at[:min(zero_rows, batch)].set(0)    # inactive rows
    out = ragged_paged_decode(q, pool_k, pool_v, tables, lens,
                              interpret=True)
    gk = jnp.moveaxis(pool_k[tables], 2, 1).reshape(batch, kvH, P * page, hd)
    gv = jnp.moveaxis(pool_v[tables], 2, 1).reshape(batch, kvH, P * page, hd)
    want = np.asarray(R.decode_ref(q, gk, gv, lens))
    got = np.asarray(out)
    for b in range(batch):
        if int(lens[b]) == 0:
            assert not got[b].any(), "inactive row must emit zeros"
        else:
            np.testing.assert_allclose(got[b], want[b], rtol=2e-5,
                                       atol=2e-5)
