"""DeepSeek-V2 on the serving path, at a tiny size on the CPU (Pallas
interpreted): YaRN's numbers, the latent pages, the absorbed paged kernel,
the softmax router and the shares of the experts, ``ServingEngine`` end to
end, what the fleet's features do with a latent pool, and the two readings
that must come out not correct."""

import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark.adapters import deepseek_v2 as adapter
from benchmark.reference import deepseek_v2 as ref
from hetu_tpu.layers.mla import YarnRope, rotate_pairs
from hetu_tpu.layers.moe import HeldExpertsMoE, SoftmaxRouter
from hetu_tpu.ops.pallas.paged_mla_decode import paged_mla_decode
from hetu_tpu.serve import ServingEngine
from hetu_tpu.serve import kv_cache
from hetu_tpu.layers import CacheSpec
from hetu_tpu.serve.kv_cache import (KVCachePool,
                                     UnsupportedCacheLayout)

TINY = {
    "family": "deepseek_v2", "dtype": "float32", "hidden_size": 64,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "intermediate_size": 96, "num_attention_heads": 4, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "n_routed_experts": 16, "num_experts_per_tok": 4,
    "moe_intermediate_size": 32, "n_shared_experts": 1,
    "routed_scaling_factor": 1.0, "rms_norm_eps": 1e-6,
    "max_position_embeddings": 512, "rope_theta": 10000,
    "rope_scaling": {"beta_fast": 32, "beta_slow": 1, "factor": 40,
                     "mscale": 0.707, "mscale_all_dim": 0.707,
                     "original_max_position_embeddings": 64,
                     "type": "yarn"},
    "vocab_size": 256, "initializer_range": 0.2}
SEED = 7
ENGINE = dict(num_slots=3, page_size=8, max_seq_len=64,
              prompt_buckets=(16, 32), sampling="greedy")
LIMIT = 5e-4            # the tiny cell's logit_gap_max


@pytest.fixture(scope="module")
def model():
    return adapter.build_model(TINY, SEED)


@pytest.fixture(scope="module")
def weights():
    return ref.to_float32(ref.init_weights(TINY, ref.C.seed_key(SEED)))


def prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, TINY["vocab_size"], n) for n in lengths]


# ------------------------------------------------------------ (a) YaRN

PUBLISHED = YarnRope(dim=64, theta=10000.0, factor=40.0,
                     original_max_position=4096, beta_fast=32, beta_slow=1,
                     mscale=0.707, mscale_all_dim=0.707)


def test_yarn_frequencies_at_the_published_keys():
    """By hand: low = floor(64 ln(4096 / (32 x 2 pi)) / (2 ln 1e4)) =
    floor(10.47) = 10 and high = ceil(64 ln(4096 / (2 pi)) / (2 ln 1e4)) =
    ceil(22.51) = 23, so pairs 0 to 10 keep f_i, pairs 23 to 31 turn 40
    times slower, and pair 16 (f = 1e4^(-1/2) = 0.01, ramp 6/13) reads
    0.01 / 40 x 6/13 + 0.01 x 7/13."""
    f = PUBLISHED.inv_freq()
    assert f.shape == (32,) and f[0] == pytest.approx(1.0)
    assert f[10] == pytest.approx(10000.0 ** (-20 / 64), rel=1e-6)
    assert f[16] == pytest.approx(0.01 / 40 * 6 / 13 + 0.01 * 7 / 13,
                                  rel=1e-5)
    for i in (23, 31):
        assert f[i] == pytest.approx(10000.0 ** (-2 * i / 64) / 40, rel=1e-5)
    both = ref.yarn({"qk_rope_head_dim": 64, "qk_nope_head_dim": 128,
                     "rope_theta": 10000,
                     "rope_scaling": {
                         "beta_fast": 32, "beta_slow": 1, "factor": 40,
                         "mscale": 0.707, "mscale_all_dim": 0.707,
                         "original_max_position_embeddings": 4096}})
    np.testing.assert_allclose(both["inv_freq"], f, rtol=1e-6)
    assert both["sigma"] == pytest.approx(PUBLISHED.softmax_scale(192))


def test_yarn_mscale_and_the_softmax_scale():
    m = 0.1 * 0.707 * math.log(40) + 1
    assert m == pytest.approx(1.2608, abs=1e-4)
    assert YarnRope.m(40.0, 0.707) == pytest.approx(m)
    assert PUBLISHED.amplitude() == pytest.approx(1.0)
    assert PUBLISHED.softmax_scale(192) == pytest.approx(0.1147, abs=1e-4)
    assert YarnRope(dim=8).softmax_scale(24) == pytest.approx(24 ** -0.5)


def test_rotation_turns_the_pairs_where_they_stand():
    rope = YarnRope(dim=4, theta=100.0)           # plain rotary
    x = jnp.asarray([[1.0, 0.0, 0.0, 2.0]])
    out = np.asarray(rotate_pairs(x, jnp.asarray([3]), rope))
    f = rope.inv_freq()
    want = [math.cos(3 * f[0]), math.sin(3 * f[0]),
            -2 * math.sin(3 * f[1]), 2 * math.cos(3 * f[1])]
    np.testing.assert_allclose(out[0], want, rtol=1e-5, atol=1e-6)


# ----------------------------------------------- (b) the latent pages

def served_logits(model, seqs, new, *, poison=False, page=8):
    """Prefill each sequence less its last ``new`` tokens, then decode
    those a step at a time, all rows in one batch: the logits of every
    decoded position, [row][step] -> (vocab,)."""
    spec = model.cache_spec()
    pool = KVCachePool(spec=spec, num_pages=1 + len(seqs) * 8,
                       page_size=page, max_seq_len=64)
    cache = pool.arrays
    if poison:
        cache = (cache[0].at[:, kv_cache.SCRATCH_PAGE].set(jnp.nan),)
    for i, seq in enumerate(seqs):
        n = len(seq) - new
        pool.alloc(i, n)
        bucket = 16 if n <= 16 else 32
        tok = np.zeros((1, bucket), np.int32)
        tok[0, :n] = seq[:n]
        _, cache, _ = model.prefill(
            cache, pool.gather_indices([i]), jnp.zeros((1,), jnp.int32),
            jnp.asarray(tok), jnp.asarray([n], jnp.int32))
    out = [[] for _ in seqs]
    for step in range(new):
        lengths = [len(s) - new + step for s in seqs]
        for i, n in enumerate(lengths):
            pool.ensure(i, n + 1)
        fed = np.asarray([[s[n]] for s, n in zip(seqs, lengths)], np.int32)
        x, cache, aux = model.decode(
            cache, pool.gather_indices(list(range(len(seqs)))),
            jnp.asarray(lengths, jnp.int32), jnp.asarray(fed))
        logits = np.asarray(x @ model.head()[0])
        for i in range(len(seqs)):
            out[i].append(logits[i])
    return out, aux


@pytest.mark.parametrize("poison", [False, True])
def test_prefill_then_decode_matches_the_full_forward(model, weights,
                                                      poison):
    """Ragged rows, one of which decodes across a page edge (15 -> 19) and
    one that starts on one (8): logits against the reference's full
    forward; a NaN-poisoned scratch page changes nothing."""
    seqs = prompts([19, 12, 29], seed=3)
    got, aux = served_logits(model, seqs, 4, poison=poison)
    for seq, rows in zip(seqs, got):
        pos = jnp.arange(len(seq) - 4, len(seq))
        want = np.asarray(ref.logits_at(weights, jnp.asarray(seq, jnp.int32),
                                        pos, cfg=TINY))
        assert np.isfinite(np.asarray(rows)).all()
        np.testing.assert_allclose(np.asarray(rows), want, atol=2e-4)
    assert int(aux["moe_held"]) == int(aux["moe_assignments"]) == 2 * 3 * 4


def test_the_whole_sequence_at_once_matches_too(model, weights):
    (seq,) = prompts([21], seed=4)
    got = np.asarray(model(jnp.asarray(seq)[None]))[0]
    want = np.asarray(ref.logits_at(weights, jnp.asarray(seq, jnp.int32),
                                    jnp.arange(21), cfg=TINY))
    np.testing.assert_allclose(got, want, atol=2e-4)


# -------------------------------------------------- (c) the paged kernel

@pytest.mark.parametrize("n_pages,per_step", [(1, None), (2, None), (11, 4),
                                              (11, None)])
def test_paged_mla_decode_against_plain_attention(n_pages, per_step):
    rng = np.random.default_rng(n_pages)
    B, H, W, VW, page = 3, 4, 40, 32, 8
    lengths = np.asarray([1, n_pages * page, max(1, n_pages * page - 3)])
    pool = rng.standard_normal((2, 1 + B * n_pages, W, page)).astype(
        np.float32)
    pool[:, 0] = np.nan                               # the scratch page
    tables = np.zeros((B, n_pages), np.int32)
    for b in range(B):
        live = -(-lengths[b] // page)
        tables[b, :live] = 1 + b * n_pages + rng.permutation(n_pages)[:live]
    q = rng.standard_normal((B, H, W)).astype(np.float32)
    got = np.asarray(paged_mla_decode(
        jnp.asarray(q), jnp.asarray(pool), jnp.asarray(tables),
        jnp.asarray(lengths, jnp.int32), value_width=VW, scale=0.3, layer=1,
        pages_per_step=per_step, interpret=True))
    for b in range(B):
        rows = np.concatenate([pool[1, p].T for p in tables[b]])[:lengths[b]]
        s = (q[b] @ rows.T) * 0.3
        p = np.exp(s - s.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows[:, :VW]
        np.testing.assert_allclose(got[b], want, rtol=2e-4, atol=2e-5)


# ------------------------------------------- (d) the router and the shares

def test_softmax_router_weights_stand_as_they_are():
    r = SoftmaxRouter(8, 16, 4, init_std=1.0)
    x = jnp.asarray(np.random.default_rng(0).standard_normal((5, 8)),
                    jnp.float32)
    chosen, weight = r(x)
    s = np.asarray(jax.nn.softmax(x @ r.w, axis=-1))
    order = np.argsort(-s, axis=-1)[:, :4]
    np.testing.assert_array_equal(np.sort(np.asarray(chosen)),
                                  np.sort(order))
    np.testing.assert_allclose(np.asarray(weight),
                               np.take_along_axis(s, np.asarray(chosen), -1),
                               rtol=1e-6)
    assert float(weight.sum(-1).max()) < 1.0      # not renormalised


def test_eight_shares_of_two_experts_add_up_to_the_uncut_layer(weights):
    """Eight chips' shares, two experts each, and the shared expert once,
    against the reference's layer with every expert held; and the layer
    that holds all sixteen, in one go."""
    p = "blocks.1.ffn."
    x = jnp.asarray(np.random.default_rng(1).standard_normal((10, 64)),
                    jnp.float32)
    want = np.asarray(ref.moe(x, weights, "blocks.1.", TINY, ref.C.mm))

    def layer(held, shared):
        moe = HeldExpertsMoE(64, 32, 16, held, top_k=4,
                             shared_hidden=32 if shared else 0,
                             router="softmax", interpret=True)
        moe.router.w = weights[p + "router.w"]
        for n in ("w_gate", "w_up", "w_down"):
            setattr(moe.experts, n,
                    weights[p + "experts." + n][jnp.asarray(held)])
            if shared:
                setattr(moe.shared, n, weights[p + "shared." + n])
        return moe

    total, pairs = 0.0, 0
    for chip in range(8):
        y, stats = layer((2 * chip, 2 * chip + 1), chip == 0).infer(x)
        total, pairs = total + y, pairs + int(stats["held"])
    assert pairs == 10 * 4                         # no pair dropped
    np.testing.assert_allclose(np.asarray(total), want, atol=1e-5)
    whole, stats = layer(tuple(range(16)), True).infer(x)
    np.testing.assert_allclose(np.asarray(whole), want, atol=1e-5)
    # the trained path computes the same layer
    trained, _ = layer(tuple(range(16)), True)(x)
    np.testing.assert_allclose(np.asarray(trained), want, atol=1e-5)


# ------------------------------------------------ (e) through the engine

def serve(model, lengths=(5, 16, 23), new=12, **kw):
    eng = ServingEngine(model, **{**ENGINE, **kw})
    ps = prompts(lengths)
    handles = [eng.submit(p, new) for p in ps]
    eng.run_until_idle()
    assert all(h.status == "completed" for h in handles)
    return eng, ps, handles


def test_streams_judged_as_the_runner_judges_them(model):
    from benchmark.runners.serve import served_gaps
    kv_cache.reset_gather_view_count()
    eng, ps, handles = serve(model)
    assert kv_cache.gather_view_count() == 0      # no program gathers
    sample = [(p, np.asarray(h.tokens)) for p, h in zip(ps, handles)]
    gaps = served_gaps(TINY, SEED, sample, pad_to=64, rank=1)
    assert max(g.max() for g in gaps) <= LIMIT
    cache = eng.stats()["cache"]
    assert cache["values_per_token_per_layer"] == 32 + 8
    assert cache["pool_bytes"] == eng.pool.num_pages * 8 * 3 * 40 * 4
    assert eng.pool.nbytes == cache["pool_bytes"]
    assert len(eng.pool.arrays) == 1               # held once


def test_same_seed_replay_is_bitwise(model):
    a = [h.tokens for h in serve(model, sampling="top_k", top_k=3)[2]]
    b = [h.tokens for h in serve(model, sampling="top_k", top_k=3)[2]]
    assert a == b


def test_the_loop_that_runs_itself_serves_the_same_streams(model):
    want = [h.tokens for h in serve(model)[2]]
    seen = []
    eng = ServingEngine(model, **ENGINE)
    eng.on_program = lambda kind, info: seen.append((kind, info))
    eng.start()
    try:
        handles = [eng.submit(p, 12) for p in prompts((5, 16, 23))]
        assert all(h.wait(120) for h in handles)
    finally:
        eng.stop()
    assert [h.tokens for h in handles] == want
    assert eng.stats()["lookahead"]["steps"]["ahead"] > 0
    kinds = [k for k, _ in seen]
    assert kinds.count("prefill") == 3 and "decode" in kinds
    assert all(info["routing"]["held"] > 0 for _, info in seen)


# ------------------------------- (f) the fleet's features on a latent pool

@pytest.mark.parametrize("kw,what", [
    (dict(prefix_sharing=True), "prefix sharing"),
    (dict(role="prefill"), "role"),
    (dict(role="decode"), "role"),
    (dict(paged_decode=False), "paged_decode=False"),
    (dict(draft_model=object()), "speculative"),
])
def test_what_reads_keys_and_values_is_refused_by_name(model, kw, what):
    with pytest.raises(UnsupportedCacheLayout, match=what):
        ServingEngine(model, **{**ENGINE, **kw})


def latent_pool():
    return KVCachePool(spec=CacheSpec.latent(2, 40), num_pages=9,
                       page_size=4, max_seq_len=16)


def test_a_latent_pool_has_no_k_and_no_v_and_exports_nothing():
    pool = latent_pool()
    assert pool.arrays[0].shape == (2, 9, 40, 4)   # token-minor pages
    pool.alloc(1, 6)
    for read in (lambda: pool.k, lambda: pool.v,
                 lambda: pool.export_pages(1),
                 lambda: pool.import_pages(None)):
        with pytest.raises(UnsupportedCacheLayout):
            read()
    assert isinstance(UnsupportedCacheLayout("x"), ValueError)


def test_copy_on_write_and_defrag_move_latent_pages():
    pool = latent_pool()
    a = pool.alloc(1, 8)
    pool.commit(pool.arrays[0].at[:, a.pages[0]].set(7.0))
    b = pool.alloc(2, 8, shared_pages=[a.pages[0]])
    assert pool.copy_on_write(2, 0) is True
    assert b.pages[0] != a.pages[0]
    assert np.all(np.asarray(pool.arrays[0][:, b.pages[0]]) == 7.0)
    pool.free(1)
    assert pool.defrag() > 0
    assert np.all(np.asarray(pool.arrays[0][:, pool.table(2).pages[0]])
                  == 7.0)
    pool.stats()                                    # invariants hold


def test_defrag_between_requests_changes_no_stream(model):
    want = [h.tokens for h in serve(model)[2]]
    assert [h.tokens for h in serve(model, defrag_every=1)[2]] == want


def test_a_hung_engine_evacuates_without_a_record(model):
    """The failover monitor re-homes by re-prefill what it cannot export."""
    eng = ServingEngine(model, **ENGINE)
    h = eng.submit(prompts((9,))[0], 6)
    eng.step()
    eng.hang(5)
    ((req, record, handle, _),) = eng.evacuate()
    assert record is None and handle is h
    assert eng.pool.stats()["sequences"] == 0
    other = ServingEngine(model, **ENGINE)
    assert other.accept_failover(req, handle, _) is None
    other.run_until_idle()
    assert h.status == "completed"
    assert h.tokens == serve(model, lengths=(9,), new=6)[2][0].tokens


def test_the_memory_ledger_and_the_donation_audit_read_the_spec(model):
    from hetu_tpu.exec.profiler import audit_serving_donation
    from hetu_tpu.obs import memledger
    eng = ServingEngine(model, **ENGINE)
    assert memledger._pool_page_bytes(eng.pool) * eng.pool.num_pages \
        == eng.pool.nbytes
    report = audit_serving_donation(eng)
    assert report["pool_bytes"] == eng.pool.nbytes
    for name, prog in report["programs"].items():
        assert prog["unusable"] == [], name
        assert prog["aliased_bytes"] >= eng.pool.nbytes, name


# ------------------------------------- (g) what must read not correct

def test_the_reference_without_rotary_reads_not_correct():
    from benchmark.tools.deepseek_v2_faults import fault_gaps
    gaps = fault_gaps(TINY, SEED, prompts((40, 33), seed=9), 8, rank=1,
                      fault="no_rope")
    assert max(g.max() for g in gaps) > 100 * LIMIT
    same = fault_gaps(TINY, SEED, prompts((40,), seed=9), 8, rank=1)
    assert max(g.max() for g in same) == 0.0


def test_the_reference_one_precision_lower_reads_not_correct():
    from benchmark.reference.common import LOWER
    from benchmark.tools.deepseek_v2_faults import fault_gaps
    gaps = fault_gaps(TINY, SEED, prompts((40, 33), seed=9), 8, rank=1,
                      control=LOWER[TINY["dtype"]])
    assert max(g.max() for g in gaps) > LIMIT
