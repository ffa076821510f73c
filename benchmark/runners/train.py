"""Training cells: ``Trainer.step`` on a new seeded batch each step."""

from __future__ import annotations

import collections
import functools
import gc
import importlib
import time

import jax

from benchmark import compare, counts, harness

CHECK_STEPS = 3


def step_key(seed: int, step: int):
    from benchmark.reference import common
    return jax.random.fold_in(common.seed_key(seed), step)


def program_readings(system, mix, seed, vocab, batches,
                     log=harness.say) -> dict:
    """Drive the system through its first steps, through the call and the
    feed that the window uses, and read what is compared: each step's
    loss, the first gradient's norms by leaf, the change's norms."""
    losses, grad = [], None
    for i in range(CHECK_STEPS):
        m = system.step(batches(mix, seed, i, vocab), step_key(seed, i))
        losses.append(float(m["loss"]))
        if i == 0:
            grad = jax.device_get(system.first_gradient_norms())
    change = jax.device_get(system.change_norms(seed))
    log(f"program: losses {losses}")
    return {"losses": losses, "grad": grad, "change": change}


def reference_readings(cell_config, opt, mix, seed, rows, *, sites,
                       batches, precision="float32", half_batch=False,
                       log=harness.say) -> dict:
    """The same three steps by the plain reference, in blocks of rows."""
    ref = importlib.import_module(
        f"benchmark.reference.{cell_config['family']}")
    vocab = cell_config["vocab_size"]
    weights = jax.jit(lambda k: ref.init_weights(cell_config, k))(
        ref.C.seed_key(seed))
    run = ref.Training(cell_config, opt, weights, rows=rows,
                       precision=precision, sites=sites)
    losses, grad = [], None
    for i in range(CHECK_STEPS):
        batch = batches(mix, seed, i, vocab)
        if half_batch:   # the fault: half of the rows left out
            batch = {k: v[:len(v) // 2] for k, v in batch.items()}
        loss, g = run.step(batch, step_key(seed, i))
        losses.append(loss)
        if g is not None:
            grad = jax.device_get(g)
    change = jax.device_get(run.change_norms())
    log(f"reference ({precision}, dropout as {sites}"
        f"{', half batch' if half_batch else ''}): losses {losses}")
    return {"losses": losses, "grad": grad, "change": change}


def compared(program: dict, reference: dict, limits: dict) -> dict:
    still = compare.near_zero_leaves(reference["grad"])
    g, g_leaf = compare.worst_norm_gap(program["grad"], reference["grad"],
                                       log=harness.say)
    c, c_leaf = compare.worst_norm_gap(program["change"],
                                       reference["change"], leave_out=still,
                                       log=harness.say)
    harness.say(f"compared: worst gradient leaf {g_leaf}, worst change leaf "
                f"{c_leaf}, {len(still)} leaves of no gradient left out")
    values = {"first_loss_gap": compare.loss_gap(program["losses"][:1],
                                                 reference["losses"][:1]),
              "loss_gap": compare.loss_gap(program["losses"],
                                           reference["losses"]),
              "grad_norm_gap": g, "change_norm_gap": c}
    return {n: {"value": v, "limit": limits[n]} for n, v in values.items()
            if n in limits}


def run(cell, *, seed, seconds, tracer, t0, devices, peaks, control=False):
    """One run of a training cell.  ``control`` (for the tool that reads
    the limits' two ends, never for a run of the benchmark) also puts the
    reference one precision lower, and the reference with half of the
    batch left out, in the program's place."""
    cfg, wl = cell.config, cell.workload
    mix, opt = wl["traffic"], wl["trainer"]
    vocab = cfg["vocab_size"]
    adapter = importlib.import_module(f"benchmark.adapters.{cfg['family']}")
    batches = harness.resolve(wl["generator"])
    system = adapter.System(cfg, opt, seed)
    program = program_readings(system, mix, seed, vocab, batches)
    lag = int(wl.get("steps_in_flight", 2))
    tokens_a_step = int(mix["batch"]) * int(mix["seq"])

    def drive(step, until=None, steps=None, spans=None):
        """Steps from ``step`` on until the clock passes ``until`` or
        ``steps`` are dispatched; at most ``lag`` steps wait on the device
        at any time.  Returns the next step's number."""
        pending = collections.deque()
        done = 0
        while True:
            batch = batches(mix, seed, step, vocab)
            a = time.perf_counter()
            with tracer.span("bench.train_step"):
                m = system.step(batch, step_key(seed, step))
            if spans is not None:
                spans.append((a, time.perf_counter()))
            pending.append(m["loss"])
            step += 1
            done += 1
            if len(pending) > lag:
                jax.block_until_ready(pending.popleft())
            if (until is not None and time.perf_counter() >= until) or \
                    (steps is not None and done >= steps):
                break
        jax.block_until_ready(system.state_leaves())
        return step

    begin = time.perf_counter()
    setup_s = begin - t0
    spans = []
    nxt = drive(CHECK_STEPS, until=begin + seconds, spans=spans)
    window_s = time.perf_counter() - begin
    steps = nxt - CHECK_STEPS
    rate = steps * tokens_a_step / window_s
    harness.say(f"window: {steps} steps in {window_s:.3f} s, "
                f"{rate:.1f} tokens/s, {1e3 * window_s / steps:.2f} ms a "
                f"step; set-up {setup_s:.2f} s")

    reduced = None
    traced_steps = int(wl.get("trace_steps", 6))
    if tracer.on:
        tracer.start()
        with tracer.span("bench.window"):
            drive(nxt, steps=traced_steps)
        tracer.stop()
        reduced = tracer.reduce()
    device = harness.device_dict(devices, cell.chips)
    system.free()
    del system
    gc.collect()

    t_ref = time.perf_counter()
    read = functools.partial(reference_readings, cfg, opt, mix, seed,
                             int(wl["reference_rows"]), batches=batches)
    sites = wl["reference_dropout_sites"]
    reference = read(sites=sites)
    harness.say(f"reference took {time.perf_counter() - t_ref:.1f} s")
    readings = {}
    if control:
        from benchmark.reference.common import LOWER
        # stated_precision is a witness, not a control: the reference at
        # the precision that the configuration states, which has to read
        # as the program does
        for name, kw in (
                ("control", {"precision": LOWER[cfg["dtype"]]}),
                ("half_batch", {"half_batch": True}),
                ("stated_precision", {"precision": cfg["dtype"]})):
            other = read(sites=sites, **kw)
            harness.say(f"-- {name} in the program's place")
            readings[name] = compared(other, reference, wl["limits"])
        if sites != "published":
            # a witness too: the program against the reference with
            # dropout where the publication has it
            harness.say("-- the program against dropout as published")
            readings["against_published_dropout"] = compared(
                program, read(sites="published"), wl["limits"])
    d = counts.dims(cfg)
    flash = [counts.flash_call(
        batch=int(mix["batch"]), heads=d["heads"], seq_q=int(mix["seq"]),
        seq_k=int(mix["seq"]), head_dim=d["hidden"] // d["heads"],
        causal=bool(mix.get("causal", False)), backward=b)
        for b in (False, True)]
    calls = traced_steps * d["layers"]
    facts = {
        "device": device, "chips": cell.chips, "window_s": window_s,
        "on_chip": devices[0].platform == "tpu",
        "model_flops": steps * tokens_a_step *
        counts.train_flops_per_token(cfg, mix),
        "spans": {"train_step": spans}, "control": readings,
        "kernel_work": {"flash": (calls * (flash[0][0] + flash[1][0]),
                                  calls * (flash[0][1] + flash[1][1]))},
    }
    return harness.Outcome(
        end_to_end={"train_tokens_per_s": rate, "setup_s": setup_s},
        facts=facts, attempted=steps, failed=0,
        compared=compared(program, reference, wl["limits"]),
        reduced=reduced, gap_spans=("bench.train_step",))
