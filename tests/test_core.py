"""SVFusion core behaviour tests: build/search recall, WAVP semantics,
updates, MVCC merge, engine consistency + hypothesis property tests."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings
    from hypothesis import strategies as st
except ImportError:   # no network route: replay fixed seeded examples
    from _hypothesis_shim import given, settings, st

from repro.core import cache as C
from repro.core import mvcc
from repro.core import update as U
from repro.core.build import build_graph, build_index, compute_e_in
from repro.core.engine import EngineConfig, SVFusionEngine
from repro.core.search import brute_force_topk, recall_at_k, search_batch
from repro.core.types import SearchParams

N, D, R = 3000, 24, 16
KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module")
def index():
    vecs = jax.random.normal(KEY, (N, D))
    return build_index(vecs, degree=R, cache_slots=384, n_max=8192)


@pytest.fixture(scope="module")
def sp():
    return SearchParams(k=10, pool=64, max_iters=96)


def test_build_graph_invariants(index):
    g = index.graph
    nb = np.asarray(g.nbrs[:N])
    assert (nb < N).all() and int(g.n) == N
    rows = np.arange(N)[:, None]
    assert not (nb == rows).any(), "self-loops"
    # e_in consistent with edges
    np.testing.assert_array_equal(
        np.asarray(compute_e_in(g.nbrs, g.capacity)), np.asarray(g.e_in))


def test_search_recall(index, sp):
    q = jax.random.normal(jax.random.PRNGKey(1), (64, D))
    res = search_batch(index, q, jax.random.PRNGKey(2), sp)
    truth, _ = brute_force_topk(index.graph, q, 10)
    assert float(recall_at_k(res.ids, truth)) > 0.8


def test_partitioned_build_recall():
    vecs = jax.random.normal(KEY, (2000, D))
    stp = build_index(vecs, degree=R, cache_slots=256, n_max=4096,
                      n_partitions=4, cross_samples=256)
    q = jax.random.normal(jax.random.PRNGKey(1), (32, D))
    res = search_batch(stp, q, jax.random.PRNGKey(2),
                       SearchParams(k=10, pool=64, max_iters=96))
    truth, _ = brute_force_topk(stp.graph, q, 10)
    assert float(recall_at_k(res.ids, truth)) > 0.7


def test_wavp_mapping_invariants(index, sp):
    q = jax.random.normal(jax.random.PRNGKey(3), (32, D))
    stt = index
    for i in range(3):
        res = search_batch(stt, q, jax.random.PRNGKey(4 + i), sp)
        stt = C.apply_wavp(stt, res.acc_ids, res.acc_hit, sp, now=i)
    cache = stt.cache
    slot_hid = np.asarray(cache.slot_hid)
    h2d = np.asarray(cache.h2d)
    occ = slot_hid >= 0
    # bijectivity: occupied slots' host ids map back to the slot
    np.testing.assert_array_equal(h2d[slot_hid[occ]], np.where(occ)[0])
    # every mapped host id is stored in that slot
    mapped = np.where(h2d >= 0)[0]
    np.testing.assert_array_equal(slot_hid[h2d[mapped]], mapped)
    # cached vectors hold the right contents
    vec = np.asarray(cache.vectors)[h2d[mapped]]
    np.testing.assert_allclose(vec, np.asarray(stt.graph.vectors)[mapped],
                               rtol=1e-6)
    assert int(stt.stats.hits) + int(stt.stats.misses) \
        == int(stt.stats.accesses)


def test_wavp_never_policy_keeps_cache(index, sp):
    spn = sp._replace(policy="never")
    q = jax.random.normal(jax.random.PRNGKey(5), (16, D))
    res = search_batch(index, q, jax.random.PRNGKey(6), spn)
    st2 = C.apply_wavp(index, res.acc_ids, res.acc_hit, spn)
    np.testing.assert_array_equal(np.asarray(st2.cache.slot_hid),
                                  np.asarray(index.cache.slot_hid))
    assert int(st2.stats.promotions) == 0


def test_theta_threshold_equivalence():
    """Paper §4.3 theory: gain(x) > 0  <=>  F_lambda(x) > theta."""
    t_cpu, t_gpu, t_xfer = 2e-6, 1e-7, 4e-6
    theta = t_xfer / (t_cpu - t_gpu)
    lam = np.linspace(0, 5, 101)
    gain = lam * (t_cpu - t_gpu) - t_xfer
    np.testing.assert_array_equal(gain > 0, lam > theta)


def test_insert_read_after_write(index, sp):
    newv = jax.random.normal(jax.random.PRNGKey(7), (64, D))
    st2, ids, rev = U.insert_batch(index, newv, jax.random.PRNGKey(8), sp)
    res = search_batch(st2, newv, jax.random.PRNGKey(9), sp)
    assert float((res.ids[:, 0] == ids).mean()) > 0.9
    assert rev.v.shape[0] == 64 * R
    # e_in stays consistent
    np.testing.assert_array_equal(
        np.asarray(compute_e_in(st2.graph.nbrs, st2.graph.capacity)),
        np.asarray(st2.graph.e_in))


def test_delete_then_search_excludes(index, sp):
    q = jax.random.normal(jax.random.PRNGKey(10), (16, D))
    truth, _ = brute_force_topk(index.graph, q, 1)
    st2 = U.delete_batch(index, truth[:, 0].astype(jnp.int32))
    res = search_batch(st2, q, jax.random.PRNGKey(11), sp)
    found = np.asarray(res.ids)
    assert not np.isin(np.asarray(truth[:, 0]), found).any()


def test_repair_improves_clustered_deletions(sp):
    vecs = jax.random.normal(KEY, (2000, D))
    stt = build_index(vecs, degree=R, cache_slots=256, n_max=4096)
    center = vecs[0]
    d = jnp.sum((vecs - center) ** 2, 1)
    dead = jnp.argsort(d)[:500].astype(jnp.int32)
    stt = U.delete_batch(stt, dead)
    frac_before = U.affected_fraction(stt.graph)
    n_affected = int((np.asarray(frac_before[:2000]) > 0.5)[
        np.asarray(stt.graph.alive[:2000])].sum())
    st2, nrep = U.repair_affected(stt, max_repair=512)
    assert int(nrep) > 0 and n_affected > 0
    frac_after = U.affected_fraction(st2.graph)
    alive = np.asarray(st2.graph.alive[:2000])
    assert float(np.asarray(frac_after[:2000])[alive].mean()) \
        < float(np.asarray(frac_before[:2000])[alive].mean())


def test_consolidate_removes_dead_edges(index):
    dead = jnp.arange(0, 600, dtype=jnp.int32)
    st2 = U.delete_batch(index, dead)
    st3 = U.consolidate(st2)
    nb = np.asarray(st3.graph.nbrs)
    alive = np.asarray(st3.graph.alive)
    bad = (nb >= 0) & ~alive[np.clip(nb, 0, None)]
    assert bad.sum() == 0


def test_mvcc_merge_preserves_new_vertices(index, sp):
    # snapshot, consolidate it, meanwhile insert into active, then merge
    snap = index
    snap_n = int(snap.graph.n)
    active = U.delete_batch(index, jnp.arange(0, 400, dtype=jnp.int32))
    newv = jax.random.normal(jax.random.PRNGKey(12), (32, D))
    active, ids, rev = U.insert_batch(active, newv, jax.random.PRNGKey(13), sp)
    consolidated = U.consolidate(snap)
    merged = mvcc.merge_consolidated(consolidated, active,
                                     jnp.asarray(snap_n, jnp.int32), rev)
    # new vertices searchable in merged state
    res = search_batch(merged, newv, jax.random.PRNGKey(14), sp)
    assert float((res.ids[:, 0] == ids).mean()) > 0.85
    # deletions from the window remain authoritative
    assert not bool(merged.graph.alive[:400].any())
    # reverse-edge log was applied: new ids appear in old rows
    nb = np.asarray(merged.graph.nbrs[:snap_n])
    assert np.isin(np.asarray(ids), nb).any()


def test_engine_consistency_modes():
    rng = np.random.default_rng(0)
    base = rng.normal(size=(800, D)).astype(np.float32)
    for sync, expect in ((True, 0.9), (False, 0.5)):
        eng = SVFusionEngine(base, EngineConfig(
            degree=R, cache_slots=256, capacity=4096,
            search=SearchParams(k=1, pool=48, max_iters=64),
            sync=sync, stale_refresh=64))
        hits = []
        for i in range(6):
            newv = rng.normal(size=(8, D)).astype(np.float32)
            ids = eng.insert(newv)
            found, _ = eng.search(newv)
            hits.append(float((found[:, 0] == ids).mean()))
        if sync:
            assert np.mean(hits) > expect
        else:
            assert np.mean(hits) < expect


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 2 ** 31 - 1), st.integers(2, 24), st.integers(1, 8))
def test_rank_reorder_properties(seed, C_, deg):
    """Rank-based reordering returns a permutation-subset of candidates and
    never invents ids."""
    from repro.core.build import rank_based_reorder
    rng = np.random.default_rng(seed)
    cand = rng.choice(200, size=(2, C_), replace=False).astype(np.int32)
    dists = np.sort(rng.random((2, C_)).astype(np.float32), axis=1)
    nbrs = rng.integers(-1, 200, size=(256, 8)).astype(np.int32)
    out = np.asarray(rank_based_reorder(jnp.asarray(cand),
                                        jnp.asarray(dists),
                                        jnp.asarray(nbrs), deg))
    assert out.shape == (2, deg)
    for b in range(2):
        valid = out[b][out[b] >= 0]
        assert set(valid).issubset(set(cand[b].tolist()))
        assert len(set(valid.tolist())) == len(valid)


def test_vectorized_clock_matches_sequential_semantics():
    """The batched clock (cache.py) must agree with the paper's sequential
    clock on the core invariants: (1) referenced slots survive the sweep,
    (2) among unreferenced slots, lowest-F_lambda occupants leave first."""
    from repro.core.clock_reference import SequentialClock
    rng = np.random.default_rng(0)
    n_slots, n_ids = 8, 64
    f_lam = rng.random(n_ids)

    seq = SequentialClock(n_slots)
    residents = rng.choice(n_ids, n_slots, replace=False)
    for s, rid in enumerate(residents):
        seq.occupant[s] = rid
    protected = [0, 3]
    for s in protected:
        seq.access(s)
    incoming = int(np.argmax(f_lam))          # high-value newcomer
    slot = seq.admit(incoming, f_lam)
    # sequential clock never evicts a referenced slot on the first sweep
    assert slot not in protected
    # and the victim had the minimal F_lambda among unreferenced slots
    unref = [s for s in range(n_slots) if s not in protected and s != slot]
    evicted_f = f_lam[residents[slot]]
    assert evicted_f <= min(f_lam[residents[s]] for s in unref) + 1e-12

    # vectorized clock: same invariants through apply_wavp's eviction rule
    # (empty-first, then ref==0 ascending F_lambda, ref==1 protected)
    empty = np.zeros(n_slots, bool)
    ref = np.zeros(n_slots, np.int8)
    ref[protected] = 1
    occ_score = f_lam[residents]
    evict_key = np.where(ref > 0, np.inf, occ_score)
    victim = int(np.argmin(evict_key))
    assert victim not in protected
    assert occ_score[victim] == evict_key.min()


def test_compile_cache_location(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins and nothing is set in code; without
    it the cache lands in the checkout's one fixed, git-ignored dir."""
    from repro.utils import CHECKOUT, use_compile_cache
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/jax")
        assert use_compile_cache() == "/elsewhere/jax"
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = use_compile_cache()
        assert path == str(CHECKOUT / ".jax_cache") == use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
        assert ".jax_cache/" in (CHECKOUT / ".gitignore").read_text()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def _distance_fns():
    from repro.core import quant
    from repro.core.build import pairwise_l2
    from repro.core.search import _batch_sqdist
    from repro.core.types import GraphState
    from repro.kernels.l2_gather.kernel import l2_gather
    from repro.kernels.pq_adc.kernel import pq_adc
    x, q = jnp.ones((2, 3, 4)), jnp.ones((2, 4))
    ids = jnp.zeros((2, 8), jnp.int32)
    g = GraphState(vectors=jnp.ones((5, 4)), nbrs=jnp.zeros((5, 2), jnp.int32),
                   alive=jnp.ones((5,), bool), e_in=jnp.zeros((5,), jnp.int32),
                   version=jnp.zeros((5,), jnp.int32), n=jnp.int32(5))
    return {
        "batch_sqdist": (_batch_sqdist, (x, q)),
        "pairwise_l2": (pairwise_l2, (q, q)),
        "brute_force_topk": (lambda g, q: brute_force_topk(g, q, 2), (g, q)),
        "pq_centroids": (quant._sqdist_to_centroids,
                         (jnp.ones((6, 2, 2)), jnp.ones((2, 8, 2)))),
        "pq_train": (lambda v: quant._train(v, KEY, 2, 4, 1),
                     (jnp.ones((16, 4)),)),
        "l2_gather_kernel": (lambda t, i, q: l2_gather(t, i, q, interpret=True),
                             (jnp.ones((5, 4)), ids, q)),
        "pq_adc_kernel": (lambda c, lut, i: pq_adc(c, lut, i, interpret=True),
                          (jnp.ones((5, 2), jnp.uint8), jnp.ones((2, 2, 4)),
                           ids)),
    }


@pytest.mark.parametrize("name", list(_distance_fns()))
def test_distance_contractions_are_fp32(name):
    """Every distance contraction asks for HIGHEST precision: the TPU's
    default rounds matmul operands to bf16, which neither the exact
    re-rank nor the recall reference may do. (On the CPU the precision
    changes nothing, so the lowered program is what is checked.)"""
    fn, args = _distance_fns()[name]
    text = jax.jit(fn).lower(*args).as_text()
    dots = [l for l in text.splitlines() if "dot_general" in l]
    assert dots, name
    assert all("precision = [HIGHEST, HIGHEST]" in l for l in dots), dots
