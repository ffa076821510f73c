"""The K/V pool is donated to every serving program.

Each jitted program that returns the pool updated takes ``pool.k`` and
``pool.v`` donated and writes them in place, so an array handed to a step
is consumed and the only valid arrays are the ones last given to
``KVCachePool.commit``.  These tests hold that by compiling (the programs
alias the whole pool; XLA finds every donation usable) and by driving
(prefill, decode, copy-on-write, speculation and page migration between
steps end in the streams an engine without any of them gives, and no
array is used after it was consumed).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from hetu_tpu.core import set_random_seed
from hetu_tpu.exec import audit_serving_donation
from hetu_tpu.exec.profiler import _compile_fresh, _memory_stats
from hetu_tpu.layers import CacheSpec
from hetu_tpu.models.gpt import GPT, GPTConfig
from hetu_tpu.serve import KVCachePool, ServingEngine
from hetu_tpu.serve import kv_cache

pytestmark = pytest.mark.serve

CFG = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
                max_seq_len=64)
ENGINE = dict(num_slots=4, page_size=8, max_seq_len=64,
              prompt_buckets=(8, 16, 32), sampling="greedy", seed=11)
TEMPLATE = list(range(20, 36))                  # two full pages of 8
PROMPTS = (TEMPLATE + [5, 6, 7], TEMPLATE + [9, 9], [3, 4, 5, 6, 7])
BUDGET = 6


@pytest.fixture(scope="module")
def model():
    set_random_seed(0)
    return GPT(CFG)


@pytest.fixture(scope="module")
def draft():
    set_random_seed(1)
    return GPT(GPTConfig(vocab_size=97, hidden_size=16, num_layers=1,
                         num_heads=2, max_seq_len=64))


# ------------------------------------------------------- what is compiled

@pytest.fixture(scope="module")
def audits(model):
    """One fresh compile a program: the paged engine's prefill, decode
    and speculative verify shape, and the gather engine's decode."""
    return {
        "paged": audit_serving_donation(ServingEngine(model, **ENGINE),
                                        spec_k=3),
        "gather": audit_serving_donation(
            ServingEngine(model, paged_decode=False, **ENGINE)),
    }


@pytest.mark.parametrize("engine,program", [
    ("paged", "prefill"), ("paged", "decode"), ("gather", "decode"),
    ("paged", "verify")])
def test_serving_program_aliases_the_whole_pool(audits, engine, program):
    rep = audits[engine]
    prog = rep["programs"][program]
    assert rep["pool_bytes"] > 0
    assert prog["aliased_bytes"] >= rep["pool_bytes"], prog
    assert prog["unusable"] == []
    # the pool is argument and output at once, not a second buffer
    assert prog["output_bytes"] >= prog["aliased_bytes"]


def test_audit_lowers_on_shapes_and_consumes_nothing(model):
    eng = ServingEngine(model, **ENGINE)
    k, v = eng.pool.k, eng.pool.v
    audit_serving_donation(eng)
    assert not k.is_deleted() and not v.is_deleted()
    assert eng.pool.k is k and eng.pool.v is v


def test_compile_report_carries_aliased_bytes(model):
    """The engage counter: ``stats()["compile"]`` shows, beside temp,
    argument and output, the bytes each serving program writes in place.
    (Read from whatever executable the engine got, which a warm
    persistent cache may have deserialized: then it may read 0, so only
    a program compiled here is held to the pool's size.)"""
    eng = ServingEngine(model, **ENGINE)
    h = eng.submit(PROMPTS[2], 3)
    eng.run_until_idle()
    assert h.status == "completed"
    pool_bytes = int(eng.pool.k.nbytes) + int(eng.pool.v.nbytes)
    report = eng.stats()["compile"]
    for site in ("serve.prefill_step", "serve.paged_decode"):
        (prog,) = report[site]["by_signature"].values()
        assert prog["memory_bytes"]["alias"] in (0, pool_bytes)
    # the sampler returns no pool and aliases nothing
    (prog,) = report["serve.sample"]["by_signature"].values()
    assert prog["memory_bytes"].get("alias", 0) == 0


def small_pool():
    return KVCachePool(spec=CacheSpec.kv(2, 2, 4), num_pages=9,
                       page_size=4, max_seq_len=16)


def test_pool_page_writes_alias_the_whole_pool():
    """``copy_on_write`` and ``import_pages`` go through one small donated
    program, not through two eager whole-pool copies."""
    pool = small_pool()
    idx = jnp.asarray([3], jnp.int32)
    compiled, unusable = _compile_fresh(lambda: kv_cache._page_writer(2).lower(
        pool.k, pool.v, idx, pool.k[:, idx], pool.v[:, idx]))
    assert unusable == []
    assert _memory_stats(compiled)["aliased_bytes"] >= \
        int(pool.k.nbytes) + int(pool.v.nbytes)


# --------------------------------------------------------- what is driven

def fill(pool, page, value):
    pool.commit(pool.k.at[:, page].set(value), pool.v.at[:, page].set(-value))


def test_copy_on_write_consumes_the_arrays_and_keeps_the_bytes():
    pool = small_pool()
    a = pool.alloc(1, 4)
    pool.alloc(2, 4, shared_pages=a.pages)
    fill(pool, a.pages[0], 7.0)
    k, v = pool.k, pool.v
    assert pool.copy_on_write(2, 0) is True
    assert k.is_deleted() and v.is_deleted()
    new = pool.table(2).pages[0]
    assert new != a.pages[0]
    for page in (new, a.pages[0]):
        assert np.all(np.asarray(pool.k[:, page]) == 7.0)
        assert np.all(np.asarray(pool.v[:, page]) == -7.0)
    pool.stats()


def test_import_consumes_the_arrays_and_round_trips_bitwise():
    src, dst = small_pool(), small_pool()
    pt = src.alloc(5, 8)
    rng = np.random.default_rng(0)
    for p in pt.pages:
        fill(src, p, float(rng.standard_normal()))
    pt.length = 6
    record = src.export_pages(5)
    assert not src.k.is_deleted()          # an export only reads
    dst.alloc(1, 4)                        # so the pages land elsewhere
    k, v = dst.k, dst.v
    got = dst.import_pages(record)
    assert k.is_deleted() and v.is_deleted()
    assert got.length == 6
    for sp, dp in zip(pt.pages, got.pages):
        np.testing.assert_array_equal(np.asarray(src.k[:, sp]),
                                      np.asarray(dst.k[:, dp]))
        np.testing.assert_array_equal(np.asarray(src.v[:, sp]),
                                      np.asarray(dst.v[:, dp]))
    src.ack_export(5)
    src.stats(), dst.stats()


def alive(eng):
    return not eng.pool.k.is_deleted() and not eng.pool.v.is_deleted()


def oracle(model, prompt, n):
    """Greedy decoding by the whole forward pass, no cache at all."""
    toks = list(prompt)
    for _ in range(n):
        logits = model(jnp.asarray([toks], jnp.int32))
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_every_step_consumes_the_pool_it_was_given(model):
    eng = ServingEngine(model, **ENGINE)
    h = eng.submit(PROMPTS[2], BUDGET)
    seen = []
    while not eng.batcher.idle:
        k, v = eng.pool.k, eng.pool.v
        eng.step()
        seen.append((k.is_deleted(), v.is_deleted()))
        assert alive(eng)
    # the first tick prefills and decodes, the rest decode: each one ran
    # at least one program, and each program consumed what it was given
    assert seen and all(k and v for k, v in seen)
    assert h.tokens == oracle(model, PROMPTS[2], BUDGET)


@pytest.mark.parametrize("paged", [True, False])
def test_eager_edits_between_steps_leave_the_streams_alone(model, paged):
    """Prefill, decode, a prefix share with a forced copy-on-write and a
    page export/import round trip between steps: the same tokens as a
    plain engine and as decoding without a cache."""
    want = [oracle(model, p, BUDGET) for p in PROMPTS]

    plain = ServingEngine(model, paged_decode=paged, **ENGINE)
    hs = [plain.submit(p, BUDGET) for p in PROMPTS]
    plain.run_until_idle()
    assert [h.tokens for h in hs] == want

    eng = ServingEngine(model, paged_decode=paged, prefix_sharing=True,
                        **ENGINE)
    first = eng.submit(PROMPTS[0], BUDGET)
    eng.run_until_idle()                    # publishes the template's pages
    second, third = (eng.submit(p, BUDGET) for p in PROMPTS[1:])
    eng.step()                              # both prefilled, one decode
    assert alive(eng)
    pool = eng.pool
    with eng._lock:
        # the second request aliases the template's first page: un-share it
        shared = pool.table(second.request_id).pages[0]
        assert pool.refcount(shared) > 1
        k = pool.k
        assert pool.copy_on_write(second.request_id, 0) is True
        assert k.is_deleted() and alive(eng)
        assert pool.table(second.request_id).pages[0] != shared
        # the third request's pages leave and come back as a migration
        # would move them, and the request goes on from the imported ones
        table = pool.table(third.request_id)
        record = pool.export_pages(third.request_id)
        back = pool.import_pages(record, seq_id=10_000)
        assert alive(eng)
        table.pages, back.pages = back.pages, table.pages
        pool.cancel_export(third.request_id)
        pool.free(10_000)
        pool.stats()
    while not eng.batcher.idle:
        eng.step()
        assert alive(eng)
    assert [h.status for h in (first, second, third)] == ["completed"] * 3
    assert [first.tokens, second.tokens, third.tokens] == want


def test_speculative_engine_verifies_in_place(model, draft):
    eng = ServingEngine(model, draft_model=draft, spec_k=3, **ENGINE)
    hs = [eng.submit(p, BUDGET) for p in PROMPTS]
    while not eng.batcher.idle:
        k = eng.pool.k
        eng.step()
        assert k.is_deleted() and alive(eng)
    assert [h.tokens for h in hs] == [oracle(model, p, BUDGET)
                                      for p in PROMPTS]
    # the verify program ran at the chain's shape: slots x (k + 1) rows
    (sig,) = eng.stats()["compile"]["serve.paged_decode"]["by_signature"]
    assert sig.endswith("int32[16]")


@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_a_step_that_fails_after_consuming_the_pool_kills_the_engine(model):
    """No second copy is kept to fall back on: the scheduler dies by the
    path it has, waiting handles fail and later submits fail fast."""
    eng = ServingEngine(model, **ENGINE)
    warm = eng.submit(PROMPTS[2], 2)
    eng.run_until_idle()
    assert warm.status == "completed"
    real = eng.pool.step

    def broken(fn, model_, *args):
        if fn is not eng._paged_step_fn:
            return real(fn, model_, *args)
        fn(model_, eng.pool.k, eng.pool.v, *args)   # consumes the pool
        raise RuntimeError("device lost")

    eng.pool.step = broken
    eng.start()
    try:
        h = eng.submit(PROMPTS[2], 4)
        assert h.wait(60)
        assert h.status == "failed" and "device lost" in h.error
        late = eng.submit(PROMPTS[2], 4)
        assert late.status == "failed" and "device lost" in late.error
    finally:
        eng.stop()
    assert eng.pool.k.is_deleted() and eng.pool.v.is_deleted()
