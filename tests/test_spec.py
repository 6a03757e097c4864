"""Peer-speculative decoding tests: the temperature-0 exactness invariant
(speculative streams bit-identical to plain decode, whatever the draft
proposes), KV rollback bit-identity across cache dtypes and mid-stream
churn, the k-token verify step vs sequential decode, chaos fallback, the
simulated-cost speedup, and the report/stats surface."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from dataclasses import replace

from repro.configs import get_reduced
from repro.models import build_model
from repro.runtime import FaultConfig
from repro.serve.fleet import (ChaosConfig, FleetConfig, FleetDefense,
                               FleetRouter, Request, SpecConfig, SpecEngine,
                               generate_workload)


def _tiny_cfg():
    return replace(get_reduced("qwen1.5-0.5b"), num_layers=2, d_model=64,
                   d_ff=128, vocab_size=64, num_heads=2, num_kv_heads=2,
                   head_dim=32)


def _requests(cfg, lens, max_new=6, gap_ms=1.0, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(i, i * gap_ms,
                    tuple(int(x) for x in rng.integers(0, cfg.padded_vocab,
                                                       size=l)),
                    max_new)
            for i, l in enumerate(lens)]


class _ListWorkload:
    def __init__(self, requests, scenario="custom", seed=0):
        self.requests = requests
        self.scenario = scenario
        self.seed = seed


def _noised(params, scale, seed=42):
    """Deterministically perturbed copy: a 'student' draft that agrees with
    the target on SOME argmaxes (partial accepts) but not all."""
    leaves, treedef = jax.tree.flatten(params)
    key = jax.random.key(seed)
    out = []
    for i, leaf in enumerate(leaves):
        k = jax.random.fold_in(key, i)
        out.append(leaf + scale * jax.random.normal(k, leaf.shape,
                                                    leaf.dtype))
    return jax.tree.unflatten(treedef, out)


_FC = dict(max_slots=2, block_size=4, num_blocks=32, max_blocks_per_slot=8,
           max_prefills_per_step=1)


# ----------------------------------------------------------------------------
# the exactness invariant: speculative == plain at temperature 0
# ----------------------------------------------------------------------------

def test_spec_bit_identical_identical_peers():
    """Ring-paired identical peers (the converged-codistillation limit):
    every draft accepted, stream digest identical to plain decode."""
    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    reqs = _requests(cfg, [5, 9, 12, 7, 5, 9, 12, 7])
    fc = FleetConfig(**_FC)
    plain = FleetRouter(model, [params, params], config=fc).run(
        _ListWorkload(list(reqs)))
    spec = FleetRouter(model, [params, params], config=fc,
                       policy="speculative", spec=SpecConfig(k=4)).run(
        _ListWorkload(list(reqs)))
    assert spec.completed == len(reqs)
    assert spec.stream_digest == plain.stream_digest
    assert spec.spec_accept_rate == 1.0
    assert spec.spec_rounds > 0
    assert spec.spec_fallback_ticks == 0
    assert spec.spec_accepted_tokens == spec.spec_drafted_tokens > 0


def test_spec_bit_identical_under_rejection():
    """A disagreeing draft changes NOTHING about the output: the target
    resamples every divergence from its own verify logits. Partial accepts
    (0 < rate < 1) prove both branches of accept/reject ran."""
    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    reqs = _requests(cfg, [5, 9, 12, 7, 5, 9, 12, 7])
    fc = FleetConfig(**_FC)
    plain = FleetRouter(model, [params], config=fc).run(
        _ListWorkload(list(reqs)))
    spec = FleetRouter(model, [params], config=fc, policy="speculative",
                       spec=SpecConfig(k=4), draft_model=model,
                       draft_params=_noised(params, 1e-3)).run(
        _ListWorkload(list(reqs)))
    assert spec.stream_digest == plain.stream_digest
    assert 0.0 < spec.spec_accept_rate < 1.0


def test_spec_seeded_determinism():
    """Two identical speculative runs produce byte-identical reports."""
    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    reqs = _requests(cfg, [5, 9, 12, 7])
    fc = FleetConfig(**_FC)

    def go():
        return FleetRouter(model, [params, params], config=fc,
                           policy="speculative", spec=SpecConfig(k=3)).run(
            _ListWorkload(list(reqs))).to_json()

    assert go() == go()


# ----------------------------------------------------------------------------
# KV rollback: pools bit-identical to a never-drafted run
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("cache_dtype", [jnp.float32, jnp.int8,
                                         jnp.float8_e4m3fn])
def test_spec_rollback_pool_bit_identity(cache_dtype):
    """After a run full of rejected drafts and mid-stream churn (two waves
    reusing the same blocks), the target pool — K/V bits, quantization
    scales, table, lengths, free list — matches a never-drafted run's
    exactly. Freed blocks keep residual rows from earlier occupants, so
    rollback must restore PRIOR CONTENT, not zeros; wave 2's rejections
    overwrite-and-restore wave 1's residue, which is what this pins."""
    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    # two waves far apart: both runs drain wave 1 (same allocate/free
    # sequence) before wave 2 reuses its freed blocks
    wave1 = _requests(cfg, [5, 9], gap_ms=0.0)
    wave2 = [Request(10 + i, 1000.0 + i * 0.0, r.prompt, r.max_new)
             for i, r in enumerate(_requests(cfg, [12, 7], seed=3))]
    reqs = wave1 + wave2
    fc = FleetConfig(**_FC)

    def pool_state(router):
        pool = router.engines[0].pool
        leaves = jax.tree.leaves(pool.kv)
        return (pool.table.copy(), pool.lengths.copy(),
                [list(b) for b in pool.slot_blocks], list(pool.free),
                [np.asarray(x) for x in leaves])

    plain = FleetRouter(model, [params], config=fc, cache_dtype=cache_dtype)
    rp = plain.run(_ListWorkload(list(reqs)))
    spec = FleetRouter(model, [params], config=fc, cache_dtype=cache_dtype,
                       policy="speculative", spec=SpecConfig(k=4),
                       draft_model=model, draft_params=_noised(params, 1e-2))
    rs = spec.run(_ListWorkload(list(reqs)))
    assert rs.stream_digest == rp.stream_digest
    assert rs.spec_accept_rate < 1.0      # rejections actually happened

    pt, pl, pb, pf, pleaves = pool_state(plain)
    st, slens, sb, sf, sleaves = pool_state(spec)
    np.testing.assert_array_equal(pt, st)
    np.testing.assert_array_equal(pl, slens)
    assert pb == sb and pf == sf
    for a, b in zip(pleaves, sleaves):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert a.tobytes() == b.tobytes()    # bit-identity, any dtype


def test_snapshot_restore_roundtrip():
    """Pool-level undo log: overwrite rows, restore a suffix, bits match."""
    from repro.serve.fleet.cache import PagedCachePool
    cfg = _tiny_cfg()
    model = build_model(cfg)
    pool = PagedCachePool(model, max_slots=2, block_size=4, num_blocks=16,
                          max_blocks_per_slot=4, cache_dtype=jnp.int8)
    pool.allocate(0, 10)
    pool.lengths[0] = 3
    before = [np.asarray(x).copy() for x in jax.tree.leaves(pool.kv)]
    snap = pool.snapshot_rows(0, 3, 4)
    # clobber the snapshot window via the writer maps
    wslots, woffs = pool.write_maps_k(np.array([True, False]), 4)
    for j in range(4):
        blk = int(np.nonzero(wslots[j] >= 0)[0][0])
        off = int(woffs[j][blk])
        for sub in pool.kv.values():
            for name in sub:
                sub[name] = sub[name].at[:, blk, off].set(1)
    changed = any(not np.array_equal(a, np.asarray(b)) for a, b in
                  zip(before, jax.tree.leaves(pool.kv)))
    assert changed
    pool.restore_rows(snap, start=0)
    for a, b in zip(before, jax.tree.leaves(pool.kv)):
        np.testing.assert_array_equal(a, np.asarray(b))


# ----------------------------------------------------------------------------
# the verify step: one batched k-token forward == k sequential decodes
# ----------------------------------------------------------------------------

@pytest.mark.parametrize("fused", [True, False])
def test_verify_step_matches_sequential_decode(fused):
    """build_verify_step's position-j logits equal the j'th plain decode's
    (argmax-identical; numerically tight), and it leaves the same pool."""
    from repro.serve.fleet.cache import PagedCachePool
    from repro.serve.fleet.model_exec import (build_decode_step,
                                              build_verify_step)
    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    k = 3
    rng = np.random.default_rng(0)
    prompts = [list(rng.integers(0, cfg.padded_vocab, size=n))
               for n in (5, 9)]
    toks = rng.integers(0, cfg.padded_vocab, size=(2, k)).astype(np.int32)

    def fresh_pool():
        pool = PagedCachePool(model, max_slots=2, block_size=4,
                              num_blocks=16, max_blocks_per_slot=4,
                              cache_dtype=jnp.float32)
        for s, p in enumerate(prompts):
            pool.allocate(s, len(p) + k + 1)
            t = jnp.asarray(p, jnp.int32)[None, :]
            _, cache = model.prefill(params, {"tokens": t}, len(p),
                                     cache_dtype=jnp.float32)
            pool.insert_prefill(s, cache, len(p))
        return pool

    # sequential reference: k plain decode steps
    pool = fresh_pool()
    decode = build_decode_step(model, fused_attention=fused)
    seq_logits = []
    for j in range(k):
        wslot, woff = pool.write_maps(np.ones(2, bool))
        lg, kv, st = decode(params, pool.kv, pool.states,
                            jnp.asarray(pool.table),
                            jnp.asarray(pool.lengths), jnp.asarray(wslot),
                            jnp.asarray(woff), jnp.asarray(toks[:, j:j + 1]))
        pool.kv, pool.states = kv, st
        # wait for the step before bumping the host lengths: on the CPU
        # jnp.asarray may alias that numpy buffer while the step still runs
        seq_logits.append(np.asarray(lg))
        pool.lengths += 1
    seq_leaves = [np.asarray(x) for x in jax.tree.leaves(pool.kv)]

    # one batched verify over the same k tokens
    pool2 = fresh_pool()
    verify = build_verify_step(model, k, fused_attention=fused)
    wslots, woffs = pool2.write_maps_k(np.ones(2, bool), k)
    vlg, kv, st = verify(params, pool2.kv, pool2.states,
                         jnp.asarray(pool2.table),
                         jnp.asarray(pool2.lengths), jnp.asarray(wslots),
                         jnp.asarray(woffs), jnp.asarray(toks))
    vlg = np.asarray(vlg)
    for j in range(k):
        np.testing.assert_array_equal(vlg[:, j].argmax(-1),
                                      seq_logits[j].argmax(-1))
        np.testing.assert_allclose(vlg[:, j], seq_logits[j], atol=2e-4)
    for a, b in zip(seq_leaves, jax.tree.leaves(kv)):
        np.testing.assert_allclose(a, np.asarray(b), atol=1e-5)


def test_verify_rejects_recurrent_models():
    from repro.serve.fleet.model_exec import build_verify_step
    cfg = get_reduced("rwkv6-1.6b")
    model = build_model(cfg)
    with pytest.raises(ValueError, match="attention-only"):
        build_verify_step(model, 4)


# ----------------------------------------------------------------------------
# chaos: health-aware pairing falls back to plain decode
# ----------------------------------------------------------------------------

def test_spec_fallback_when_draft_peer_offline():
    """Preempting the draft partner mid-run forces plain-decode fallback
    ticks; every request still completes with at-most-once emission."""
    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    wl = generate_workload("steady", 12, cfg.padded_vocab, seed=5,
                           max_prompt=12, max_new=6)
    fc = FleetConfig(**_FC)
    chaos = ChaosConfig(FaultConfig(n_peers=2, seed=5,
                                    preemptions=((1, 6, 120.0),)))
    rep = FleetRouter(model, [params, params], config=fc,
                      policy="speculative", spec=SpecConfig(k=4),
                      chaos=chaos, defense=FleetDefense()).run(wl)
    assert rep.preemptions >= 1
    assert rep.spec_fallback_ticks >= 1
    assert rep.spec_rounds >= 1           # speculation resumed after drains
    assert rep.completed == 12
    assert rep.lost_tokens == 0 and rep.duplicated_tokens == 0


def test_spec_dedicated_draft_peer_excluded_from_serving():
    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    reqs = _requests(cfg, [5, 9, 12, 7])
    fc = FleetConfig(**_FC)
    router = FleetRouter(model, [params, params, params], config=fc,
                         policy="speculative",
                         spec=SpecConfig(k=2, draft_peer=1))
    rep = router.run(_ListWorkload(list(reqs)))
    assert rep.completed == len(reqs)
    drafter = router.engines[1]
    assert not isinstance(drafter, SpecEngine)
    assert not drafter.records             # never served a request
    assert all(isinstance(router.engines[i], SpecEngine) for i in (0, 2))


def test_spec_requires_two_peers_for_ring():
    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    with pytest.raises(ValueError, match="2 peers"):
        FleetRouter(model, [params], policy="speculative")


# ----------------------------------------------------------------------------
# the point of it all: simulated speedup in the service-bound regime
# ----------------------------------------------------------------------------

def test_spec_simulated_speedup():
    """k=4 full-accept speculation beats plain decode by >1.5x simulated
    tokens/sec in the service-bound regime (the benchmarks/serving.py
    acceptance cell, pinned here at test scale)."""
    cfg = _tiny_cfg()
    model = build_model(cfg)
    params = model.init(jax.random.key(0))
    wl = generate_workload("steady", 16, cfg.padded_vocab, seed=7,
                           max_prompt=8, max_new=16)
    # compress arrivals + fix output lengths: decode-dominated saturation
    reqs = [Request(r.rid, r.arrival_ms * 0.02, r.prompt, 16)
            for r in wl.requests]
    fc = FleetConfig(max_slots=4, block_size=4, num_blocks=64,
                     max_blocks_per_slot=8)
    plain = FleetRouter(model, [params, params], config=fc).run(
        _ListWorkload(list(reqs), scenario="steady", seed=7))
    spec = FleetRouter(model, [params, params], config=fc,
                       policy="speculative", spec=SpecConfig(k=4)).run(
        _ListWorkload(list(reqs), scenario="steady", seed=7))
    assert spec.stream_digest == plain.stream_digest
    speedup = spec.sim_tokens_per_s / plain.sim_tokens_per_s
    assert speedup > 1.5, speedup
