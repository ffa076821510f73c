"""The plain references against the program at a small size, the control
that has to fail, and the faults that ``correct`` has to catch."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import compare, harness
from benchmark.reference import bert as ref_bert
from benchmark.reference import common, gpt2 as ref_gpt2
from benchmark.runners import serve, train
from conftest import TINY, run_tiny


def _cell(name):
    return harness.load_cell(TINY, name)


BATCHES = harness.resolve(_cell("tiny-bert.pretrain").workload["generator"])


# -- BERT: Trainer.step against the reference -------------------------------

@pytest.fixture(scope="module")
def bert_readings():
    cell = _cell("tiny-bert.pretrain")
    cfg, wl = cell.config, cell.workload
    out = {}

    def get(seed):
        if seed not in out:
            from benchmark.adapters import bert as adapter
            system = adapter.System(cfg, wl["trainer"], seed)
            prog = train.program_readings(system, wl["traffic"], seed,
                                          cfg["vocab_size"], BATCHES)
            system.free()
            read = functools.partial(
                train.reference_readings, cfg, wl["trainer"], wl["traffic"],
                seed, wl["reference_rows"],
                sites=wl["reference_dropout_sites"], batches=BATCHES)
            ref = {p: read(precision=p) for p in ("float32", "bfloat16")}
            half = read(half_batch=True)
            out[seed] = (prog, ref, half, wl["limits"])
        return out[seed]
    return get


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 3, 77])
def test_bert_step_agrees_with_the_reference(bert_readings, seed):
    prog, ref, _, limits = bert_readings(seed)
    got = train.compared(prog, ref["float32"], limits)
    assert set(got) == {"first_loss_gap", "loss_gap", "grad_norm_gap",
                        "change_norm_gap"}
    assert harness.correct(got), got
    assert prog["losses"][0] != prog["losses"][1]


@pytest.mark.parametrize("seed", [1, 2 ** 31 + 3, 77])
def test_bert_control_one_precision_lower_is_not_correct(bert_readings,
                                                         seed):
    _, ref, _, limits = bert_readings(seed)
    assert common.LOWER["float32"] == "bfloat16"
    got = train.compared(ref["bfloat16"], ref["float32"], limits)
    assert not harness.correct(got), got
    assert got["grad_norm_gap"]["value"] > 3 * limits["grad_norm_gap"]


@pytest.mark.parametrize("seed", [1, 77])
def test_bert_half_batch_reads_far_above_the_limits(bert_readings, seed):
    _, ref, half, limits = bert_readings(seed)
    got = train.compared(half, ref["float32"], limits)
    assert got["grad_norm_gap"]["value"] > 10 * limits["grad_norm_gap"]
    assert got["change_norm_gap"]["value"] > 10 * limits["change_norm_gap"]


def test_reference_rows_do_not_change_the_reference():
    cell = _cell("tiny-bert.pretrain")
    cfg, wl = cell.config, cell.workload
    a = train.reference_readings(cfg, wl["trainer"], wl["traffic"], 4, 2,
                                 sites="program", batches=BATCHES)
    b = train.reference_readings(cfg, wl["trainer"], wl["traffic"], 4, 4,
                                 sites="program", batches=BATCHES)
    assert compare.loss_gap(a["losses"], b["losses"]) < 1e-6
    assert compare.worst_norm_gap(a["grad"], b["grad"])[0] < 1e-5


@pytest.mark.parametrize("change,moves", [
    ({"sites": "published"}, True),      # dropout where the paper has it
    ({"layer_norm_eps": 1e-12}, True),
    ({"no_decay": ["layer_norm", "bias"]}, False),   # the loss is the same
])
def test_the_reference_follows_its_files(change, moves):
    """Every published value and the cell's recipe reach the reference."""
    cell = _cell("tiny-bert.pretrain")
    cfg, opt = dict(cell.config), dict(cell.workload["trainer"])
    mix = cell.workload["traffic"]
    base = train.reference_readings(cfg, opt, mix, 6, 2, sites="program",
                                    batches=BATCHES)
    sites = change.pop("sites", "program")
    cfg.update({k: v for k, v in change.items() if k in cfg})
    opt.update({k: v for k, v in change.items() if k in opt})
    other = train.reference_readings(cfg, opt, mix, 6, 2, sites=sites,
                                     batches=BATCHES)
    assert (other["losses"][0] != base["losses"][0]) == moves
    if not moves:
        # without decay a layer norm's gain moves by Adam's step alone
        gap = compare.worst_norm_gap(other["change"], base["change"])
        assert 1e-4 < gap[0] < 0.1 and gap[1].split("[")[0] in (
            "emb_ln_g", "ln1_g", "ln2_g", "tr_ln_g"), gap


@pytest.mark.parametrize("name,exact", [("gelu", True), ("gelu_new", False)])
def test_the_activation_is_the_one_the_published_key_names(name, exact):
    x = jnp.linspace(-4.0, 4.0, 101)
    got = common.gelu(x, common.GELU_FORMS[name])
    phi = 0.5 * (1.0 + jax.scipy.special.erf(x / np.sqrt(2.0)))
    assert (float(jnp.abs(got - x * phi).max()) < 1e-6) == exact
    # both references read it from the file, and know no other
    for cell, key in (("tiny-bert.pretrain", "hidden_act"),
                      ("tiny-gpt2.chat", "activation_function")):
        assert _cell(cell).config[key] in common.GELU_FORMS
    with pytest.raises(KeyError):
        ref_gpt2.logits_at({}, jnp.zeros(4, jnp.int32), jnp.arange(4),
                           cfg={**_cell("tiny-gpt2.chat").config,
                                "activation_function": "swish"})


def test_unknown_dropout_sites_are_an_error():
    cell = _cell("tiny-bert.pretrain")
    with pytest.raises(ValueError):
        train.reference_readings(cell.config, cell.workload["trainer"],
                                 cell.workload["traffic"], 6, 2,
                                 sites="nowhere", batches=BATCHES)


def test_dropout_bits_are_the_programs():
    from hetu_tpu.ops import nn
    key = jax.random.key(11)
    ours = ref_bert.dropout_bits(key, (3, 5, 7))
    assert (np.asarray(ours) == np.asarray(nn._hash_bits(key, (3, 5, 7)))
            ).all()


def test_norm_gap_arithmetic():
    ref = {"a": np.array([1.0, 2.0]), "b": 4.0, "tiny": 1e-9}
    prog = {"a": np.array([1.0, 2.2]), "b": 4.0, "tiny": 2e-9}
    gap, where = compare.worst_norm_gap(prog, ref)
    assert where == "a[1]" and gap == pytest.approx(0.1)
    # a leaf below the median is measured against the median leaf
    assert compare.worst_norm_gap({**prog, "a": ref["a"]}, ref)[0] < 1e-8
    assert compare.near_zero_leaves(ref) == {"tiny"}
    unmoved = {"a": np.array([0.0, 0.0]), "b": 0.0, "tiny": 0.0}
    assert compare.worst_norm_gap(unmoved, ref)[0] == pytest.approx(1.0)
    assert compare.loss_gap([1.0, float("nan")], [1.0, 1.0]) == np.inf


# -- GPT-2: prefill then decode through the engine --------------------------

@pytest.fixture(scope="module")
def gpt():
    cell = _cell("tiny-gpt2.chat")
    from benchmark.adapters import gpt2 as adapter
    system = adapter.System(cell.config, cell.workload["engine"], 21)
    yield cell.config, system
    system.free()


def test_gpt_forward_agrees_with_the_reference(gpt):
    cfg, system = gpt
    w = ref_gpt2.to_float32(ref_gpt2.init_weights(cfg, common.seed_key(21)))
    tokens = np.random.default_rng(0).integers(0, cfg["vocab_size"], 40)
    ours = ref_gpt2.logits_at(w, jnp.asarray(tokens), jnp.arange(40),
                              cfg=cfg)
    theirs = system.engine.model(jnp.asarray(tokens)[None])[0]
    assert np.abs(np.asarray(ours) - np.asarray(theirs)).max() < 2e-5


@pytest.mark.parametrize("plen,new", [(5, 12), (16, 9), (27, 20)])
def test_prefill_then_decode_serves_the_references_tokens(gpt, plen, new):
    cfg, system = gpt
    prompt = np.random.default_rng(plen).integers(0, cfg["vocab_size"],
                                                  plen)
    h = system.engine.submit(prompt, new)
    system.engine.run_until_idle()
    assert h.status == "completed" and len(h.tokens) == new
    gaps = serve.served_gaps(cfg, 21, [(prompt, np.asarray(h.tokens))],
                             pad_to=128)
    assert len(gaps[0]) == new and gaps[0].max() == 0.0


def test_sampled_tokens_are_held_to_the_rank_they_are_drawn_from():
    """Top-k 3 tokens lie within the reference's best 3 and, some of them,
    below its best; a token outside the best 3 reads a gap."""
    cell = _cell("tiny-gpt2.closed")
    assert serve.judged_rank(cell.workload["engine"]) == 3
    assert serve.judged_rank(_cell("tiny-gpt2.chat").workload["engine"]) == 1
    from benchmark.adapters import gpt2 as adapter
    system = adapter.System(cell.config, cell.workload["engine"], 21)
    prompt = np.random.default_rng(1).integers(0, 211, 10)
    h = system.engine.submit(prompt, 40)
    system.engine.run_until_idle()
    system.free()
    served = np.asarray(h.tokens)
    at = lambda rank, toks: serve.served_gaps(
        cell.config, 21, [(prompt, toks)], rank=rank, pad_to=128)[0]
    assert at(3, served).max() == 0.0
    assert at(1, served).max() > 0.0
    wrong = served.copy()
    wrong[5] = (wrong[5] + 1) % 211
    assert at(3, wrong)[5] > 0.0 and at(3, wrong)[:5].max() == 0.0


def test_the_served_weights_bytes_are_counted_from_the_shapes():
    from benchmark import counts
    cfg = _cell("tiny-gpt2.chat").config
    w = ref_gpt2.init_weights(cfg, common.seed_key(1))
    assert ref_gpt2.weight_bytes(cfg) == sum(a.nbytes for a in w.values())
    assert counts.kv_bytes_per_token(cfg) == 2 * 2 * 64 * 2


def test_gpt_control_one_precision_lower_is_not_correct(gpt):
    cfg, system = gpt
    limit = _cell("tiny-gpt2.chat").workload["limits"]["logit_gap_max"]
    rng = np.random.default_rng(3)
    sample = []
    for _ in range(6):
        prompt = rng.integers(0, cfg["vocab_size"], 20)
        h = system.engine.submit(prompt, 60)
        system.engine.run_until_idle()
        sample.append((prompt, np.asarray(h.tokens)))
    served = serve.served_gaps(cfg, 21, sample, pad_to=128)
    control = serve.served_gaps(cfg, 21, sample, pad_to=128,
                                control="bfloat16")
    assert max(g.max() for g in served) <= limit
    assert max(g.max() for g in control) > 3 * limit


# -- faults: the rest of a run with the timed path broken underneath --------

def _unchanged(monkeypatch):
    from benchmark.adapters import bert as adapter
    real = adapter.System.step

    def step(self, batch, key):
        saved = jax.tree_util.tree_map(jnp.copy, self.trainer.state)
        m = real(self, batch, key)
        self.trainer.state = saved
        return m
    monkeypatch.setattr(adapter.System, "step", step)


def _half(monkeypatch):
    from benchmark.adapters import bert as adapter
    real = adapter.System.step
    monkeypatch.setattr(adapter.System, "step", lambda self, b, k: real(
        self, {n: v[:len(v) // 2] for n, v in b.items()}, k))


def _token(monkeypatch):
    from hetu_tpu.serve import ServingEngine
    real = ServingEngine._append_token

    def append(self, req, tok, now, **kw):
        if len(req.tokens) == 2:
            tok = (tok + 1) % self.model.config.vocab_size
        return real(self, req, tok, now, **kw)
    monkeypatch.setattr(ServingEngine, "_append_token", append)


@pytest.mark.parametrize("fault,workload,caught_by", [
    (_unchanged, "tiny-bert.pretrain", "change_norm_gap"),
    (_half, "tiny-bert.pretrain", "grad_norm_gap"),
    (_token, "tiny-gpt2.chat", "logit_gap_max"),
    (_token, "tiny-gpt2.closed", "logit_gap_max"),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault, workload,
                                            caught_by):
    fault(monkeypatch)
    line = run_tiny(workload, seed=13, seconds=0.5)
    assert line["correct"] is False
    c = line["compared"][caught_by]
    assert c["value"] > c["limit"]
    assert set(line) == {"correct", "attempted", "failed", "metrics",
                         "device", "compared"}
