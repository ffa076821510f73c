"""chip_smoke.py and the helpers it stands on, at a tiny size on the CPU.

The chip run itself is the driver's; what runs here is every phase of the
smoke with the kernels interpreted, plus the strictness the smoke relies
on: no TPU is an error with the platform's name, the compile cache has one
fixed home, an unknown TPU kind has no peak, imports stay off the backend,
the embedding library is built from a hash of its sources, several local
workers on a TPU host are refused, and a scheduler that dies fails its
waiters instead of hanging them.
"""

import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import jax
import pytest

import chip_smoke
from hetu_tpu import launch
from hetu_tpu.core import runtime
from hetu_tpu.embed import engine as embed_engine
from hetu_tpu.obs.goodput import peak_flops
from hetu_tpu.serve import ServingEngine, serve_engine

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------- the phases

def test_phase_train_tiny():
    out = chip_smoke.phase_train(chip_smoke.TINY)
    assert set(out) == {"default", "scan", "flash"}
    assert len(out["flash"]["losses"]) == chip_smoke.TINY["train_steps"]


def test_phase_kernels_tiny():
    out = chip_smoke.phase_kernels(chip_smoke.TINY)
    kernels = {name.split()[0] for name in out["compiled"]}
    assert kernels == {"flash", "lm_head_ce", "lm_head_sample", "fused_ln",
                       "paged_decode"}


def test_phase_serve_tiny():
    out = chip_smoke.phase_serve(chip_smoke.TINY)
    assert out["tokens"] == [new for _, new in chip_smoke.TINY["requests"]]
    # the steady stretch ran ahead: every step but the first of 39
    assert out["lookahead"]["ahead_share"] > 0.9
    assert out["lookahead"]["steps"]["in_turn"] >= 1
    assert out["lookahead"]["discarded"] == 0


def test_phase_serve_refuses_a_donation_xla_could_not_use(monkeypatch):
    import warnings

    def serve(size):
        warnings.warn("Some donated buffers were not usable: "
                      "ShapedArray(bfloat16[24,513,64,16,128])")
        return {"stub": 1}

    monkeypatch.setattr(chip_smoke, "_serve", serve)
    with pytest.raises(AssertionError, match="donated buffers"):
        chip_smoke.phase_serve(chip_smoke.TINY)
    monkeypatch.setattr(chip_smoke, "_serve", lambda size: {"stub": 1})
    assert chip_smoke.phase_serve(chip_smoke.TINY) == {"stub": 1}


def test_phase_ctr_tiny():
    out = chip_smoke.phase_ctr(chip_smoke.TINY)
    # the CPU has host callbacks, so "auto" picks the io_callback bridge
    assert out["bridge"] == "HostEmbedding"


def test_phase_dp4_tiny():
    out = chip_smoke.phase_dp4(chip_smoke.TINY, jax.devices()[:4])
    assert out["placement"]["sharded"] > 0   # ZeRO-1 optimizer slots


def test_placement_check_names_a_leaf_left_on_one_device():
    devs = jax.devices()[:4]
    lonely = jax.device_put(jax.numpy.ones((4, 4)), devs[0])
    with pytest.raises(AssertionError, match="lives on devices"):
        chip_smoke.check_placement({"w": lonely}, devs)


# -------------------------------------------------------- the result line

def _main_on_a_pretend_tpu(monkeypatch, capsys, ctr):
    info = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "device_info", lambda: dict(info))
    monkeypatch.setattr(chip_smoke, "require_tpu", lambda: dict(info))
    monkeypatch.setattr(chip_smoke, "compile_cache", lambda: "unused")
    for name in ("phase_train", "phase_kernels", "phase_serve"):
        monkeypatch.setattr(chip_smoke, name, lambda size: {"stub": 1})
    monkeypatch.setattr(chip_smoke, "phase_ctr", ctr)
    try:
        return chip_smoke.main(), info, capsys.readouterr().out.splitlines()
    except RuntimeError:
        return None, info, capsys.readouterr().out.splitlines()


def test_result_line_has_the_contract_keys_and_no_others(monkeypatch, capsys):
    """The driver parses the last stdout line: ``ok`` and ``device``
    (``platform``, ``kind``, ``count``), nothing else.  Per-phase results
    go on the line before it."""
    rc, info, lines = _main_on_a_pretend_tpu(monkeypatch, capsys,
                                             lambda size: {"stub": 1})
    assert rc == 0
    assert json.loads(lines[-1]) == {"ok": True, "device": info}
    head, _, summary = lines[-2].partition(": ")
    assert head == "chip_smoke summary"
    summary = json.loads(summary)
    assert set(summary["phases"]) == {"train", "kernels", "serve", "ctr",
                                      "dp4"}
    assert list(summary)[-1] == "claim" and summary["claim"] is None


def test_failed_phase_says_ok_false_and_still_raises(monkeypatch, capsys):
    def boom(size):
        raise RuntimeError("ctr broke")

    rc, info, lines = _main_on_a_pretend_tpu(monkeypatch, capsys, boom)
    assert rc is None   # main() raised: the exit code is non-zero
    assert json.loads(lines[-1]) == {"ok": False, "device": info}


# ------------------------------------------------------ refusing the CPU

def test_main_refuses_the_cpu(capsys):
    with pytest.raises(runtime.NoTPUError, match="'cpu'"):
        chip_smoke.main()
    out = capsys.readouterr().out
    assert out.startswith("chip_smoke: platform=cpu kind=cpu count=8")
    assert "{" not in out   # no result line


def test_script_exits_nonzero_on_the_cpu():
    """``python chip_smoke.py`` on the CPU: non-zero, before any phase."""
    run = subprocess.run([sys.executable, "chip_smoke.py"], cwd=_REPO,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"),
                         capture_output=True, text=True, timeout=300)
    assert run.returncode != 0
    assert "platform=cpu" in run.stdout and "[train]" not in run.stdout
    assert "NoTPUError" in run.stderr


def test_pallas_interpret_refuses_other_backends(monkeypatch):
    assert runtime.pallas_interpret() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert runtime.pallas_interpret() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    with pytest.raises(runtime.NoTPUError, match="'gpu'"):
        runtime.pallas_interpret()


def test_unknown_tpu_kind_has_no_peak():
    with pytest.raises(KeyError, match="PEAK_BF16"):
        peak_flops("TPU v9000")
    assert peak_flops("TPU v5 lite") == 197e12   # what the v5e reports


# ------------------------------------------------------ the compile cache

def test_compile_cache_leaves_the_variable_alone(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert runtime.compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # untouched


def test_compile_cache_is_the_checkout_and_imports_stay_off_the_backend(
        tmp_path):
    """Two fresh interpreters in two working directories.  Each imports the
    package, the launcher, serve, models, exec and the Pallas ops and finds
    no backend initialised (so ``bin/heturun`` never holds a chip its
    children need); then ``compile_cache()`` resolves to the same
    ``<checkout>/.jax_cache`` from both."""
    probe = ("import sys; sys.path.insert(0, %r)\n"
             "import hetu_tpu, hetu_tpu.launch, hetu_tpu.serve, "
             "hetu_tpu.models, hetu_tpu.exec, hetu_tpu.ops.pallas\n"
             "import jax, jax._src.xla_bridge as xb\n"
             "assert not xb.backends_are_initialized()\n"
             "from hetu_tpu.core.runtime import compile_cache\n"
             "print(compile_cache(), jax.config.jax_compilation_cache_dir)"
             % _REPO)
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    seen = set()
    for cwd in (_REPO, str(tmp_path)):
        run = subprocess.run([sys.executable, "-c", probe], cwd=cwd, env=env,
                             capture_output=True, text=True, timeout=300)
        assert run.returncode == 0, run.stderr[-2000:]
        seen.add(run.stdout.strip())
    want = os.path.join(_REPO, ".jax_cache")
    assert seen == {f"{want} {want}"}


# ------------------------------------------------- the embedding library

def test_embed_library_rebuilds_on_hash_not_mtime(monkeypatch, tmp_path):
    src = tmp_path / "native"
    src.mkdir()
    (src / "a.cpp").write_text("// one\n")
    (src / "build.sh").write_text('echo built >> "$(dirname "$0")/log"\n'
                                  'touch "$1"\n')
    monkeypatch.setattr(embed_engine, "_SRC_DIR", src)
    monkeypatch.setattr(embed_engine, "_SO", tmp_path / "build" / "lib.so")
    (tmp_path / "build").mkdir()
    builds = lambda: (src / "log").read_text().count("built")  # noqa: E731

    embed_engine._build_if_stale()
    embed_engine._build_if_stale()
    assert builds() == 1
    os.utime(src / "a.cpp", (1, 1))          # mtime alone: no rebuild
    embed_engine._build_if_stale()
    assert builds() == 1
    (src / "a.cpp").write_text("// two\n")   # content: rebuild
    embed_engine._build_if_stale()
    assert builds() == 2


def test_embed_build_failure_carries_the_compiler_stderr(monkeypatch,
                                                         tmp_path):
    (tmp_path / "build.sh").write_text("echo 'a.cpp:1: error: boom' >&2\n"
                                       "exit 1\n")
    monkeypatch.setattr(embed_engine, "_SRC_DIR", tmp_path)
    monkeypatch.setattr(embed_engine, "_SO", tmp_path / "lib.so")
    with pytest.raises(RuntimeError, match="error: boom"):
        embed_engine._build_if_stale()
    assert not list(tmp_path.glob("lib.so*"))


# ------------------------------------------------------------ the launcher

def test_launch_refuses_several_workers_on_a_tpu_host(monkeypatch):
    cfg = launch.DistConfig(hosts=[launch.HostSpec("localhost", workers=4,
                                                   chief=True)])
    monkeypatch.setattr(launch, "_local_tpu_chips", lambda: 4)
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    with pytest.raises(launch.MultiWorkerTPUError, match="4 local workers"):
        launch.launch(cfg, [sys.executable, "-c", "pass"])
    # naming the CPU for the children, or only asking what would run, is fine
    assert len(launch.launch(cfg, ["true"], dry_run=True)) == 4
    procs = launch.launch(cfg, [sys.executable, "-c", "pass"],
                          extra_env={"JAX_PLATFORMS": "cpu"})
    assert [p.wait(60) for _, p in procs] == [0] * 4


# ----------------------------------------------------- the dead scheduler

@pytest.mark.filterwarnings(
    "ignore::pytest.PytestUnhandledThreadExceptionWarning")
def test_dead_scheduler_fails_the_waiter_and_infer_answers_500():
    from test_serve import tiny_gpt

    engine = ServingEngine(tiny_gpt(), num_slots=2, page_size=8,
                           max_seq_len=32, prompt_buckets=(8,))

    def refuse(*a, **k):
        raise RuntimeError("Mosaic refused the kernel")

    engine._step_locked = refuse
    srv = serve_engine(engine, port=0)
    try:
        req = urllib.request.Request(
            f"{srv.url}/infer",
            json.dumps({"prompt": [1, 2, 3], "max_new_tokens": 2}).encode())
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req, timeout=60)
        assert ei.value.code == 500
        body = json.loads(ei.value.read())
        assert body["status"] == "failed"
        assert "Mosaic refused the kernel" in body["error"]
        # and a later submit fails at once instead of queueing for nobody
        late = engine.submit([1, 2, 3], 2)
        assert late.done and late.status == "failed"
    finally:
        srv.stop()
        engine.stop()
