"""Three steps of ``Trainer.step`` on the tiny Kimi-Linear model against
the plain float32 reference, by the four numbers that decide a cell's
``correct``; the reference one precision lower and the reference without
KDA's decay gate, each in the program's place, read not correct.  Beside
``test_kimi_linear.py`` (the layers), in a file of its own so that the two
run on two workers."""

import functools
import json
import os

import jax
import pytest

from benchmark import harness, traffic_lm
from benchmark.adapters import kimi_linear as adapter
from benchmark.runners import train
from benchmark.tools import kimi_linear_faults

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                    "benchmark_harness", "data_kimi", "benchmark")
CFG = json.load(open(os.path.join(DATA, "configs", "tiny-kimi.json")))
CELL = json.load(open(os.path.join(DATA, "workloads",
                                   "tiny-kimi.pretrain.json")))


@pytest.fixture(autouse=True)
def _exact_matmuls():
    with jax.default_matmul_precision("highest"):
        yield


BATCHES = traffic_lm.next_token_batch


@pytest.fixture(scope="module")
def readings():
    out = {}

    def get(seed):
        if seed not in out:
            system = adapter.System(CFG, CELL["trainer"], seed)
            prog = train.program_readings(system, CELL["traffic"], seed,
                                          CFG["vocab_size"], BATCHES)
            system.free()
            read = functools.partial(
                train.reference_readings, CFG, CELL["trainer"],
                CELL["traffic"], seed, CELL["reference_rows"],
                sites=CELL["reference_dropout_sites"], batches=BATCHES)
            out[seed] = (prog, read(), read(precision="bfloat16"),
                         kimi_linear_faults.fault_readings(
                             CFG, CELL["trainer"], CELL["traffic"], seed,
                             CELL["reference_rows"], BATCHES,
                             "no_decay_gate"))
        return out[seed]
    return get


SEEDS = [1, 2 ** 31 + 3]


@pytest.mark.parametrize("seed", SEEDS)
def test_trainer_step_agrees_with_the_reference(readings, seed):
    prog, reference, _, _ = readings(seed)
    got = train.compared(prog, reference, CELL["limits"])
    assert set(got) == {"first_loss_gap", "loss_gap", "grad_norm_gap",
                        "change_norm_gap"}
    assert harness.correct(got), got
    assert prog["losses"][0] != prog["losses"][1]


@pytest.mark.parametrize("seed", SEEDS)
def test_one_precision_lower_is_not_correct(readings, seed):
    _, reference, lower, _ = readings(seed)
    got = train.compared(lower, reference, CELL["limits"])
    assert not harness.correct(got), got


@pytest.mark.parametrize("seed", SEEDS)
def test_without_the_decay_gate_is_not_correct(readings, seed):
    _, reference, _, gateless = readings(seed)
    got = train.compared(gateless, reference, CELL["limits"])
    assert got["grad_norm_gap"]["value"] > 100 * \
        got["grad_norm_gap"]["limit"], got
