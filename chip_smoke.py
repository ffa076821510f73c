#!/usr/bin/env python3
"""chip_smoke.py: the quickest proof that the system still starts on the chip.

    python chip_smoke.py

One process, no arguments.  Drives the three paths the benchmark is built
from through the entry points a user calls, at the published width of each
model and with random weights made from a seed:

- ``train``: ``exec.Trainer`` over BERT-large (24 x 1024, bf16, AdamW,
  dropout on, key threaded): ``Trainer.step`` at 96 x 128 on the default
  attention path, one ``Trainer.scan_steps`` call, then ``Trainer.step`` at
  24 x 512 with the flash kernels and the streamed MLM head (on one
  device, the LM-head CE kernel) on the path;
- ``kernels``: every Pallas kernel through Mosaic against its float32
  reference (``tests/tpu_checks.py``);
- ``serve``: ``serve_engine(ServingEngine(GPT 8 x 1024))`` answering
  ``POST /infer`` over HTTP, twice with the same seed, bitwise the same;
- ``ctr``: Wide&Deep on the host embedding engine (built here from
  ``native/embed``) over the bridge ``host_bridge="auto"`` picks;
- ``dp4``: the BERT-large step data-parallel over four chips, when the
  process sees four.

It refuses anything that is not a TPU (exit code 1, no result line) and any
phase that fails raises.  The last line of stdout is the result line, one
JSON object with exactly these keys:

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

(``"ok": false`` when a phase raised); the line before it, ``chip_smoke
summary: {...}``, carries the per-phase results.  Times in the output are
information about this run, not claims.
"""

from __future__ import annotations

import dataclasses
import gc
import json
import os
import sys
import time
import urllib.request
import warnings

import jax
import jax.numpy as jnp
import numpy as np

from hetu_tpu.core import set_random_seed
from hetu_tpu.core.runtime import compile_cache, device_info, require_tpu

# What main() runs on the chip, and the cut of it that tests/test_chip_smoke
# runs on the CPU, where "interpret" reaches the Pallas entries the phases
# call directly (the library paths pick it from the backend).
FULL = {
    "interpret": False,
    # lr: 1e-4 does for timing only.  Post-LN BERT-large without
    # warm-up spikes from 11.2 to 13-14 in its first three steps at 1e-4
    # (on every attention path alike) and is back under its first loss
    # only around step 6; at 2e-5 it falls steadily after step 2 (PR 21
    # chip runs).  Steps cost half a second, compiles a minute: take 16.
    "bert": {}, "lr": 2e-5, "train_steps": 16, "scan_k": 3,
    "batch_seq": (96, 128), "flash_batch_seq": (24, 512),
    "gpt": dict(vocab_size=32000, hidden_size=1024, num_layers=8,
                num_heads=16, max_seq_len=2048),
    "engine": dict(num_slots=8, page_size=64, max_seq_len=2048,
                   prompt_buckets=(128, 256, 512, 1024)),
    "requests": ((40, 8), (200, 12), (700, 16)),   # (prompt len, max new)
    "steady": (40, 96),       # every slot at once: (prompt len, max new)
    "ctr": dict(vocab=26000, cache_capacity=65536), "ctr_batch": 512,
    "ctr_steps": 8,
}
TINY = {
    "interpret": True,
    "bert": dict(hidden_size=64, num_layers=1, num_heads=2, vocab_size=512,
                 max_position_embeddings=128, dtype=jnp.float32),
    "lr": 1e-4, "train_steps": 3, "scan_k": 2,
    "batch_seq": (4, 32), "flash_batch_seq": (2, 128),
    "gpt": dict(vocab_size=97, hidden_size=32, num_layers=2, num_heads=2,
                max_seq_len=64),
    "engine": dict(num_slots=4, page_size=8, max_seq_len=64,
                   prompt_buckets=(8, 16)),
    "requests": ((3, 3), (12, 4)),
    "steady": (3, 40),
    "ctr": dict(vocab=2600, cache_capacity=2048), "ctr_batch": 64,
    "ctr_steps": 6,
}


def _check_losses(name: str, losses: list) -> None:
    if not all(np.isfinite(losses)):
        raise AssertionError(f"{name}: non-finite loss in {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"{name}: loss did not fall: {losses}")


def _bert_trainer(size, attn_fn=None, strategy=None, **cfg_kw):
    from hetu_tpu.exec import Trainer
    from hetu_tpu.models import BertForPreTraining, bert_large
    from hetu_tpu.optim import AdamWOptimizer

    set_random_seed(0)
    cfg = dataclasses.replace(bert_large(dtype=jnp.bfloat16),
                              **size["bert"], **cfg_kw)
    model = BertForPreTraining(cfg, attn_fn=attn_fn)

    def loss_fn(model, b, key):
        loss, _ = model.loss(b["input_ids"], b["token_type"], None,
                             b["mlm_labels"], b["nsp_labels"], key=key,
                             training=True)
        return loss, {}

    return cfg, Trainer(model, AdamWOptimizer(size["lr"], weight_decay=0.01),
                        loss_fn, strategy=strategy)


def _bert_batch(cfg, batch: int, seq: int) -> dict:
    rng = np.random.default_rng(0)
    ids = rng.integers(0, cfg.vocab_size, (batch, seq))
    return {
        "input_ids": jnp.asarray(ids, jnp.int32),
        "token_type": jnp.zeros((batch, seq), jnp.int32),
        "mlm_labels": jnp.asarray(
            np.where(rng.random((batch, seq)) < 0.15, ids, -1), jnp.int32),
        "nsp_labels": jnp.asarray(rng.integers(0, 2, (batch,)), jnp.int32),
    }


def _timed_steps(name: str, trainer, batch, n: int) -> dict:
    """``n`` ``Trainer.step`` calls on one fixed batch, each waited for:
    losses (finite, and lower at the last than at the first), the first
    step's seconds (compile included) and the median of the rest in
    milliseconds."""
    losses, secs = [], []
    for i in range(n):
        t0 = time.perf_counter()
        m = trainer.step(batch, key=jax.random.key(i))
        jax.block_until_ready(m["loss"])
        secs.append(time.perf_counter() - t0)
        losses.append(float(m["loss"]))
    out = {"losses": [round(x, 4) for x in losses],
           "first_step_s": round(secs[0], 2),
           "steady_step_ms": round(float(np.median(secs[1:])) * 1e3, 2)}
    print(f"  {name}: {json.dumps(out)}", flush=True)
    _check_losses(name, losses)
    return out


def phase_train(size) -> dict:
    from hetu_tpu.ops.pallas import flash_attn_fn

    cfg, trainer = _bert_trainer(size)
    batch = _bert_batch(cfg, *size["batch_seq"])
    out = {"default": _timed_steps("train default", trainer, batch,
                                   size["train_steps"])}

    # every training cell will be timed through scan_steps: one call
    t0 = time.perf_counter()
    trainer.state, last = trainer.scan_steps(size["scan_k"])(
        trainer.state, batch, jax.random.key(99))
    scan_loss = float(jax.block_until_ready(last["loss"]))
    out["scan"] = {"k": size["scan_k"], "loss": round(scan_loss, 4),
                   "seconds": round(time.perf_counter() - t0, 2)}
    print(f"  train scan: {json.dumps(out['scan'])}", flush=True)
    _check_losses("train scan", [out["default"]["losses"][0], scan_loss])
    del trainer
    gc.collect()

    # flash forward and backward, and (streamed head) the LM-head CE
    # kernel, which ops/losses.py picks on a one-device TPU
    cfg, trainer = _bert_trainer(
        size, attn_fn=flash_attn_fn(native_layout=True,
                                    interpret=size["interpret"]),
        streamed_head_chunk=8192)
    batch = _bert_batch(cfg, *size["flash_batch_seq"])
    out["flash"] = _timed_steps("train flash", trainer, batch,
                                size["train_steps"])
    jax.block_until_ready(trainer.state)
    return out


def phase_kernels(size) -> dict:
    sys.path.insert(0, os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "tests"))
    import tpu_checks
    rows = tpu_checks.run_checks(interpret=size["interpret"],
                                 tiny=size["interpret"])
    return {"compiled": [r["check"] for r in rows],
            "max_err": max(r["err"] for r in rows)}


def _post(url: str, body: dict) -> dict:
    req = urllib.request.Request(
        url, json.dumps(body).encode(), {"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=900) as resp:
        return json.loads(resp.read())


def phase_serve(size) -> dict:
    # XLA says so when a program cannot use a buffer it was given donated
    # (the K/V pool, since PR 25): nowhere in this phase may it
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        out = _serve(size)
    lost = [str(w.message) for w in caught
            if "donated buffers were not usable" in str(w.message)]
    if lost:
        raise AssertionError(f"serve: {lost[0]}")
    return out


def _serve(size) -> dict:
    from hetu_tpu.exec import audit_serving_donation
    from hetu_tpu.models import GPT, GPTConfig
    from hetu_tpu.serve import ServingEngine, serve_engine

    set_random_seed(0)
    cfg = GPTConfig(**size["gpt"], dtype=(jnp.float32 if size["interpret"]
                                          else jnp.bfloat16))
    model = GPT(cfg)
    rng = np.random.default_rng(17)
    prompts = [(rng.integers(0, cfg.vocab_size, n).tolist(), new)
               for n, new in size["requests"]]

    def run() -> tuple:
        engine = ServingEngine(model, sampling="top_k", top_k=5, seed=11,
                               **size["engine"])
        srv = serve_engine(engine, port=0)
        try:
            t0 = time.perf_counter()
            answers = [_post(f"{srv.url}/infer",
                             {"prompt": p, "max_new_tokens": new,
                              "timeout_s": 900}) for p, new in prompts]
            return answers, time.perf_counter() - t0
        finally:
            srv.stop()
            engine.stop()

    first, cold_s = run()
    for (_, new), a in zip(prompts, first):
        if a["status"] != "completed" or len(a["tokens"]) != new:
            raise AssertionError(f"serve: asked {new} tokens, got {a}")
        if not all(0 <= t < cfg.vocab_size for t in a["tokens"]):
            raise AssertionError(f"serve: token outside the vocabulary: {a}")
    second, warm_s = run()
    streams = [a["tokens"] for a in first]
    if streams != [a["tokens"] for a in second] or \
            [a["stream_fingerprint"] for a in first] != \
            [a["stream_fingerprint"] for a in second]:
        raise AssertionError(
            f"serve: same-seed runs differ: {streams} vs "
            f"{[a['tokens'] for a in second]}")
    # steady decode on an engine that runs its own loop: every slot full,
    # so nearly every decode step is dispatched while the one before it is
    # still in flight (the first after each drain is not)
    engine = ServingEngine(model, sampling="top_k", top_k=5, seed=11,
                           **size["engine"]).start()
    try:
        n, new = size["steady"]
        handles = [engine.submit(rng.integers(0, cfg.vocab_size, n), new)
                   for _ in range(size["engine"]["num_slots"])]
        for h in handles:
            if not h.wait(900) or h.status != "completed" \
                    or len(h.tokens) != new:
                raise AssertionError(f"serve: steady decode: {h.status} "
                                     f"{h.error} {len(h.tokens)} of {new}")
    finally:
        engine.stop()
    look = engine.stats()["lookahead"]
    if not look["ahead_share"] > 0.9 or look["discarded"]:
        raise AssertionError(f"serve: steady decode ran in turn: {look}")
    # every serving program takes the K/V pool donated: compiled fresh,
    # prefill, decode and the speculative verify shape must each alias
    # the whole pool, or they copy it on every call
    audit = audit_serving_donation(
        ServingEngine(model, sampling="top_k", top_k=5, seed=11,
                      **size["engine"]), spec_k=4)
    for name, prog in audit["programs"].items():
        if prog["aliased_bytes"] < audit["pool_bytes"] or prog["unusable"]:
            raise AssertionError(
                f"serve: program {name} does not write the pool's "
                f"{audit['pool_bytes']} bytes in place: {prog}")
    return {"requests": len(prompts),
            "tokens": [len(s) for s in streams],
            "pool_bytes": audit["pool_bytes"],
            "aliased_bytes": {n: int(p["aliased_bytes"])
                              for n, p in audit["programs"].items()},
            "fingerprints": [a["stream_fingerprint"] for a in first],
            "lookahead": {**look, "ahead_share": round(look["ahead_share"],
                                                       4)},
            "first_run_s": round(cold_s, 2), "second_run_s": round(warm_s, 2)}


def phase_ctr(size) -> dict:
    from hetu_tpu.data.datasets import synthetic_ctr
    from hetu_tpu.exec import Trainer
    from hetu_tpu.models import CTRConfig, WideDeep
    from hetu_tpu.optim import AdamOptimizer

    set_random_seed(0)
    cfg = CTRConfig(embed_dim=16, embedding="host", cache_policy="lfuopt",
                    host_optimizer="adagrad", host_lr=0.05,
                    host_bridge="auto", **size["ctr"])
    model = WideDeep(cfg)
    bridge = type(model.embed).__name__
    print(f'  host_bridge="auto" chose {bridge}')
    batch = size["ctr_batch"]
    data = synthetic_ctr(n=batch, vocab_per_field=cfg.vocab // 26)
    b = {k: jnp.asarray(v) for k, v in data.items()}
    trainer = Trainer(
        model, AdamOptimizer(1e-3),
        lambda m, b, k: m.loss(b["dense"], b["sparse"], b["label"]))
    losses, t0 = [], time.perf_counter()
    for _ in range(size["ctr_steps"]):
        for m_ in trainer.staged_modules():   # empty on the callback bridge
            m_.stage(b["sparse"])
        losses.append(float(trainer.step(b)["loss"]))
    _check_losses("ctr", losses)
    return {"bridge": bridge, "losses": [round(x, 4) for x in losses],
            "seconds": round(time.perf_counter() - t0, 2)}


def phase_dp4(size, devices) -> dict:
    """The BERT-large step data-parallel over four devices (optimizer
    state sharded over dp), with proof that no device is left out."""
    from hetu_tpu.parallel.mesh import make_mesh
    from hetu_tpu.parallel.strategies import ShardingStrategy

    mesh = make_mesh(dp=4, devices=devices)
    cfg, trainer = _bert_trainer(size, strategy=ShardingStrategy(
        mesh=mesh, zero_stage=1))
    out = _timed_steps("dp4", trainer, _bert_batch(cfg, *size["batch_seq"]),
                       size["train_steps"])
    out["placement"] = check_placement(trainer.state, devices)
    return out


def check_placement(state, devices) -> dict:
    """Every device holds an addressable shard of every parameter and
    optimizer leaf; counts the leaves that are really sharded."""
    leaves = [x for x in jax.tree_util.tree_leaves(state)
              if isinstance(x, jax.Array)]
    want = {d.id for d in devices}
    for x in leaves:
        have = {s.device.id for s in x.addressable_shards}
        if have != want:
            raise AssertionError(
                f"a {x.shape} leaf lives on devices {sorted(have)}, not on "
                f"all of {sorted(want)}")
    return {"leaves": len(leaves),
            "sharded": sum(not x.is_fully_replicated for x in leaves)}


def main() -> int:
    info = device_info()
    print(f"chip_smoke: platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    require_tpu()
    print(f"chip_smoke: compile cache at {compile_cache()}", flush=True)
    todo = [("train", phase_train), ("kernels", phase_kernels),
            ("serve", phase_serve), ("ctr", phase_ctr)]
    if info["count"] == 4:
        todo.append(("dp4", lambda size: phase_dp4(size, jax.devices())))
    phases = {}
    t_all = time.perf_counter()
    try:
        for name, fn in todo:
            print(f"[{name}]", flush=True)
            t0 = time.perf_counter()
            phases[name] = {"passed": True, **fn(FULL),
                            "phase_s": round(time.perf_counter() - t0, 1)}
            print(f"  {json.dumps(phases[name])}", flush=True)
    except BaseException:
        # not caught: the traceback and a non-zero exit code follow
        print(json.dumps({"ok": False, "device": info}), flush=True)
        raise
    if "dp4" not in phases:
        print(f"[dp4] {info['count']} device: skipped", flush=True)
        phases["dp4"] = {"skipped": f"{info['count']} device"}
    print("chip_smoke summary: " + json.dumps(
        {"phases": phases, "total_s": round(time.perf_counter() - t_all, 1),
         "claim": None}), flush=True)
    # the result line: these keys and no others (the driver parses it)
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
