"""What every run shares: the cell's files, the device, the trace, the line.

``BENCHMARK.json`` names the cells, the configurations and the metrics; the
files it points at hold them.  A cell's file names its runner (a module of
``benchmark.runners``) and the generator of its traffic
(``module:function``), a configuration's file its family (a module of
``benchmark.adapters`` and one of ``benchmark.reference``), a per-layer
metric's file its reader (``module:function``).  Nothing here asks for any
of them by name."""

from __future__ import annotations

import contextlib
import dataclasses
import importlib
import json
import os
import shutil
import sys

DATA = "benchmark"


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    root: str
    bench: dict          # BENCHMARK.json
    entry: dict          # its "workloads" entry
    config: dict         # the configuration's file
    workload: dict       # the cell's file
    end_to_end: list     # the end-to-end metric entries this cell reports
    per_layer: list      # (entry, metric file) pairs this cell reports

    @property
    def chips(self) -> int:
        return int(self.entry["chips"])


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: str, name: str) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    entries = {w["name"]: w for w in bench["workloads"]}
    if name not in entries:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json; it has "
                         f"{sorted(entries)}")
    entry = entries[name]
    configs = {c["name"]: c for c in bench["configs"]}
    config = load_json(os.path.join(root, configs[entry["config"]]["file"]))
    workload = load_json(os.path.join(root, DATA, "workloads",
                                      f"{name}.json"))
    e2e = [m for m in bench["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [(m, load_json(os.path.join(root, DATA, "metrics",
                                            f"{m['name']}.json")))
                 for m in bench["per_layer"]
                 if _applies(m, name) and m["moves"] in reported]
    return Cell(name, root, bench, entry, config, workload, e2e, per_layer)


def open_cell(root: str, name: str, need_chip: bool = True) -> tuple:
    """(cell, its devices, their peaks) with the compile cache set up: the
    start of a run and of each tool.  ``need_chip=False`` is for the
    rehearsal tests on the CPU: it skips the look for a chip and nothing
    else."""
    import jax
    from benchmark import peaks
    cell = load_cell(root, name)
    devices = jax.devices()
    row = (peaks.require_chips(devices, cell.chips) if need_chip
           else peaks.PEAKS["TPU v5 lite"])
    say(f"benchmark: {name} on {devices[0].device_kind} x {len(devices)}; "
        f"cache {setup_caches(root)}")
    return cell, devices[:cell.chips], row


def resolve(ref: str):
    """``module:function`` -> the function."""
    module, _, attr = ref.partition(":")
    return getattr(importlib.import_module(module), attr)


def device_dict(devices, chips: int) -> dict:
    used = devices[:chips]
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in used]
    return {"platform": used[0].platform, "kind": used[0].device_kind,
            "count": len(devices), "memory_peak_bytes": int(max(peaks))}


def setup_caches(root: str) -> str:
    """JAX's persistent compilation cache at the program's fixed place
    (``<checkout>/.jax_cache`` unless ``JAX_COMPILATION_CACHE_DIR`` is
    set), with every program kept, however quick its compile."""
    import jax
    from hetu_tpu.core.runtime import compile_cache
    path = compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


class Tracer:
    """The profiler around a stretch of a run, with host spans in it."""

    def __init__(self, root: str, cell: str, on: bool):
        self.on = bool(on)
        self.dir = os.path.join(root, ".bench_trace", cell)
        self.active = False

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.active = True

    def stop(self):
        import jax
        self.active = False
        jax.profiler.stop_trace()

    def span(self, name: str):
        """A host span on the profiler's clock while tracing, else nothing."""
        if not self.active:
            return contextlib.nullcontext()
        import jax
        return jax.profiler.TraceAnnotation(name)

    def reduce(self, **kw):
        from benchmark import trace
        red = trace.reduce_trace(self.dir, **kw)
        shutil.rmtree(self.dir, ignore_errors=True)
        return red


@dataclasses.dataclass
class Outcome:
    """What a runner hands back."""
    end_to_end: dict                 # name -> value, all that it measured
    facts: dict                      # what the per-layer readers read
    attempted: int
    failed: int
    compared: dict                   # name -> {"value", "limit"}
    reduced: object = None           # trace.Reduced of the traced stretch
    gap_spans: tuple = ()


def correct(compared: dict) -> bool:
    """Every number compared is a number and within its limit."""
    ok = bool(compared)
    for c in compared.values():
        v = c["value"]
        ok = ok and v is not None and v == v and v <= c["limit"]
    return ok


def result_line(cell: Cell, out: Outcome, device: dict, trace_on: bool,
                peaks: dict) -> dict:
    metrics = {}
    if not trace_on:
        for m in cell.end_to_end:
            if m["name"] not in out.end_to_end:
                raise RuntimeError(f"the runner measured no {m['name']}")
            metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    else:
        for m, spec in cell.per_layer:
            value = resolve(spec["reader"])(out.facts, out.reduced,
                                            spec.get("params", {}), peaks)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    line = {"correct": correct(out.compared), "attempted": out.attempted,
            "failed": out.failed, "metrics": metrics, "device": device}
    if trace_on and out.reduced is not None:
        device["busy_s"] = out.reduced.busy_s
        device["window_s"] = out.reduced.window_s
        line["breakdown"] = {
            "device_ops": out.reduced.top_ops(10),
            "idle_gaps": out.reduced.idle_gaps(out.gap_spans, 10)}
    line["compared"] = out.compared
    return line


def say(*a):
    print(*a, flush=True)


def say_compared(compared: dict):
    """Each number compared beside its limit, as the last lines of stderr."""
    for name, c in compared.items():
        print(f"compared {name}: value {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
