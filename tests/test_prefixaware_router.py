"""Prefix-aware routing: TokenTrie index, router hit/miss semantics,
engine-level prefix reuse (bit-identical streams, measured prefill-work
reduction), and the cluster-level gate that prefix-aware routing beats
round-robin on a shared-prefix workload without changing any stream."""
import numpy as np
import pytest

from repro.serve import (Engine, PrefixAwareRouter, ReplicaPool,
                         ReplicaView, RoundRobinRouter, ServeConfig,
                         TokenTrie, get_router)
from repro.serve.engine import REUSE_PREFILL_CHUNK


# ----------------------------------------------------------------------
# TokenTrie units
# ----------------------------------------------------------------------
def test_trie_insert_match_miss():
    t = TokenTrie()
    t.insert([1, 2, 3, 4])
    assert t.match([1, 2, 3, 4, 9]) == 4      # full indexed prefix
    assert t.match([1, 2, 7]) == 2            # partial
    assert t.match([5, 6]) == 0               # miss
    assert t.match([]) == 0


def test_trie_refcounted_removal():
    t = TokenTrie()
    t.insert([1, 2, 3])
    t.insert([1, 2, 9])
    t.remove([1, 2, 3])
    # the shared [1, 2] prefix is still pinned by the second sequence
    assert t.match([1, 2, 3]) == 2
    assert t.match([1, 2, 9]) == 3
    t.remove([1, 2, 9])
    assert t.match([1, 2, 9]) == 0
    # removing an unindexed sequence is a no-op
    t.remove([7, 7])


def test_trie_cap_evicts_oldest():
    t = TokenTrie(cap=2)
    t.insert([1, 1])
    t.insert([2, 2])
    t.insert([3, 3])                          # evicts [1, 1]
    assert t.match([1, 1]) == 0
    assert t.match([2, 2]) == 2
    assert t.match([3, 3]) == 2
    assert len(t) == 2


# ----------------------------------------------------------------------
# router units
# ----------------------------------------------------------------------
def _view(rid, outstanding=0, straggler=False):
    return ReplicaView(replica_id=rid, free_slots=1,
                       outstanding=outstanding, step_ewma=0.0,
                       straggler=straggler)


def test_prefix_router_hit_routes_to_matching_replica():
    r = PrefixAwareRouter()
    r.note_admitted(1, [5, 6, 7, 8])
    views = [_view(0), _view(1)]
    # longest match wins even though replica 0 has the lower id
    assert r.choose([5, 6, 7, 9], views) == 1
    assert r.match_len(1, [5, 6, 7, 9]) == 3


def test_prefix_router_miss_falls_back_to_load():
    r = PrefixAwareRouter()
    r.note_admitted(0, [1, 2, 3])
    views = [_view(0, outstanding=3), _view(1, outstanding=1)]
    # no replica has any prefix of this prompt -> least-loaded wins
    assert r.choose([9, 9, 9], views) == 1


def test_prefix_router_tie_breaks_to_less_loaded_then_lower_id():
    r = PrefixAwareRouter()
    r.note_admitted(0, [1, 2])
    r.note_admitted(2, [1, 2])
    views = [_view(0, outstanding=2), _view(1), _view(2, outstanding=1)]
    assert r.choose([1, 2, 3], views) == 2    # equal match, less loaded
    views = [_view(0, outstanding=1), _view(2, outstanding=1)]
    assert r.choose([1, 2, 3], views) == 0    # fully tied -> lower id


def test_get_router_registry():
    assert isinstance(get_router("round_robin"), RoundRobinRouter)
    assert get_router(PrefixAwareRouter()).name == "prefix_aware"
    with pytest.raises(ValueError):
        get_router("nope")


def test_round_robin_cycles_deterministically():
    r = RoundRobinRouter()
    views = [_view(0), _view(1), _view(2)]
    assert [r.choose([], views) for _ in range(5)] == [0, 1, 2, 0, 1]
    # a full replica is skipped without disturbing the cycle
    assert r.choose([], [_view(0), _view(1)]) == 0


# ----------------------------------------------------------------------
# engine-level prefix reuse
# ----------------------------------------------------------------------
def test_engine_prefix_reuse_bit_identical_and_cheaper(serve_model):
    bundle, params = serve_model
    V = bundle.cfg.vocab
    rng = np.random.default_rng(0)
    shared = rng.integers(0, V, 12)
    p1 = np.concatenate([shared, rng.integers(0, V, 4)])
    p2 = np.concatenate([shared, rng.integers(0, V, 6)])

    ref = Engine(bundle, params,
                 ServeConfig(max_seq=64, slots=3, prefill_chunk=2))
    r1, r2 = ref.generate(p1, 5), ref.generate(p2, 5)
    assert ref.prefix_hits == 0
    assert ref.prefill_tokens_computed == len(p1) + len(p2)

    eng = Engine(bundle, params,
                 ServeConfig(max_seq=64, slots=3, prefix_reuse=True,
                             prefill_chunk=2))
    assert eng.supports_prefix_reuse
    o1 = eng.generate(p1, 5)
    o2 = eng.generate(p2, 5)     # hits p1's retained 12-token prefix
    assert (o1, o2) == (r1, r2), "prefix reuse must not change streams"
    assert eng.prefix_hits == 1
    assert eng.prefix_tokens_reused == 12
    assert eng.prefill_tokens_computed == \
        ref.prefill_tokens_computed - 12


def test_engine_prefix_reuse_copies_whole_chunks(serve_model):
    """Reuse rounds down to whole prefill chunks: the copied rows and
    the chunks prefilled after them are the programs a fresh prefill
    runs, so the streams stay bit-identical on every backend."""
    bundle, params = serve_model
    V = bundle.cfg.vocab
    rng = np.random.default_rng(5)
    shared = rng.integers(0, V, 12)
    p1 = np.concatenate([shared, rng.integers(0, V, 9)])
    p2 = np.concatenate([shared, rng.integers(0, V, 7)])

    ref = Engine(bundle, params,
                 ServeConfig(max_seq=64, slots=2, prefill_chunk=8))
    r1, r2 = ref.generate(p1, 4), ref.generate(p2, 4)
    eng = Engine(bundle, params,
                 ServeConfig(max_seq=64, slots=2, prefix_reuse=True,
                             prefill_chunk=8))
    assert (eng.generate(p1, 4), eng.generate(p2, 4)) == (r1, r2)
    assert eng.prefix_hits == 1
    assert eng.prefix_tokens_reused == 8          # 12 shared -> 1 chunk
    assert eng.prefill_tokens_computed == len(p1) + len(p2) - 8
    with pytest.raises(ValueError):
        ServeConfig(prefill_chunk=0)


def test_engine_prefix_reuse_skips_ragged_and_decoded_rows(serve_model):
    """A second turn (turn 1's prompt + output + more tokens) copies
    only the rows whole prefill chunks wrote: turn 1's ragged last
    chunk and its decoded rows came from other call shapes."""
    bundle, params = serve_model
    V = bundle.cfg.vocab
    rng = np.random.default_rng(6)
    turn1 = rng.integers(0, V, 13)            # chunks of 4: 3 whole + 1
    scfg = ServeConfig(max_seq=64, slots=2, prefix_reuse=True,
                       prefill_chunk=4)
    ref = Engine(bundle, params,
                 ServeConfig(max_seq=64, slots=2, prefill_chunk=4))
    eng = Engine(bundle, params, scfg)
    out1 = eng.generate(turn1, 6)
    assert out1 == ref.generate(turn1, 6)
    assert list(eng.chunk_rows) == [12, 0]
    turn2 = np.concatenate([out1, rng.integers(0, V, 3)])
    assert eng.generate(turn2, 4) == ref.generate(turn2, 4)
    # 18 cached rows match turn 2 (13 prompt + 5 decoded), 12 are whole
    # chunks
    assert eng.prefix_hits == 1 and eng.prefix_tokens_reused == 12
    assert ref.prefix_hits == 0


def test_prefill_chunk_in_effect():
    """Chunks only where reuse needs them, or where asked for."""
    assert ServeConfig().chunk is None
    assert ServeConfig(prefix_reuse=True).chunk == REUSE_PREFILL_CHUNK
    assert ServeConfig(prefill_chunk=8).chunk == 8
    assert ServeConfig(prefix_reuse=True, prefill_chunk=8).chunk == 8


def test_engine_prefix_reuse_concurrent_slots(serve_model):
    """A live slot's rows serve as the prefix source too."""
    bundle, params = serve_model
    V = bundle.cfg.vocab
    rng = np.random.default_rng(1)
    shared = rng.integers(0, V, 10)
    pa = np.concatenate([shared, rng.integers(0, V, 3)])
    pb = np.concatenate([shared, rng.integers(0, V, 5)])

    ref = Engine(bundle, params,
                 ServeConfig(max_seq=64, slots=2, prefill_chunk=2))
    ra, rb = ref.generate(pa, 4), ref.generate(pb, 4)

    eng = Engine(bundle, params,
                 ServeConfig(max_seq=64, slots=2, prefix_reuse=True,
                             prefill_chunk=2))
    sa = eng.add_request(pa)
    sb = eng.add_request(pb)      # pa still live -> 10-token hit
    assert eng.prefix_hits == 1 and eng.prefix_tokens_reused == 10
    for _ in range(3):
        eng.step()
    assert eng.finish(sa) == ra
    assert eng.finish(sb) == rb


def test_engine_prefix_miss_no_reuse(serve_model):
    bundle, params = serve_model
    V = bundle.cfg.vocab
    rng = np.random.default_rng(2)
    eng = Engine(bundle, params,
                 ServeConfig(max_seq=64, slots=2, prefix_reuse=True,
                             prefill_chunk=2))
    p1 = rng.integers(1, V // 2, 6)
    p2 = rng.integers(V // 2, V, 6)           # disjoint token ranges
    eng.generate(p1, 3)
    eng.generate(p2, 3)
    assert eng.prefix_hits == 0
    assert eng.prefill_tokens_computed == 12


# ----------------------------------------------------------------------
# cluster-level: prefix-aware beats round-robin, streams identical
# ----------------------------------------------------------------------
def test_cluster_prefix_aware_reduces_prefill_work(serve_model):
    bundle, params = serve_model
    V = bundle.cfg.vocab
    rng = np.random.default_rng(3)
    scfg = ServeConfig(max_seq=64, slots=2, prefix_reuse=True,
                       prefill_chunk=2)
    # 3 prefix families over 2 replicas: round-robin necessarily
    # scatters each family across both replicas, prefix-aware pins
    # each family to the replica that already holds its prefix
    groups = [rng.integers(0, V, 10) for _ in range(3)]
    prompts = [np.concatenate([groups[i % 3], rng.integers(0, V, 3 + i % 2)])
               for i in range(9)]

    def run(policy):
        pool = ReplicaPool(bundle, params, scfg, replicas=2, instances=2,
                           policy=policy)
        rids = [pool.submit(p, max_new=3) for p in prompts]
        pool.run()
        stats = pool.replica_stats()
        return ([pool.result(r) for r in rids],
                sum(s["prefill_tokens_computed"] for s in stats.values()),
                sum(s["prefix_tokens_reused"] for s in stats.values()))

    rr_streams, rr_work, _rr_reused = run("round_robin")
    pa_streams, pa_work, pa_reused = run("prefix_aware")
    assert pa_streams == rr_streams, \
        "routing policy must never change a token stream"
    assert pa_reused > 0
    assert pa_work < rr_work, (
        f"prefix-aware prefill work {pa_work} should beat "
        f"round-robin {rr_work} on a shared-prefix workload")


def test_cluster_streams_identical_across_replica_counts(serve_model):
    bundle, params = serve_model
    V = bundle.cfg.vocab
    rng = np.random.default_rng(4)
    scfg = ServeConfig(max_seq=64, slots=2, prefix_reuse=True,
                       prefill_chunk=2)
    prompts = [rng.integers(0, V, 5 + i) for i in range(4)]

    def run(replicas, policy):
        pool = ReplicaPool(bundle, params, scfg, replicas=replicas,
                           instances=2, policy=policy)
        rids = [pool.submit(p, max_new=4) for p in prompts]
        pool.run()
        return [pool.result(r) for r in rids]

    ref = run(1, "round_robin")
    assert run(2, "prefix_aware") == ref
    assert run(4, "load_aware") == ref
