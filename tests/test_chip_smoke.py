"""chip_smoke.py on the CPU: its phases at a tiny size, and its refusal to
report success anywhere but on a TPU."""
import importlib.util
import json
import os
import pathlib
import shutil
import subprocess
import sys
import textwrap

ROOT = pathlib.Path(__file__).resolve().parent.parent
SCRIPT = ROOT / "chip_smoke.py"


def _load():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _tiny(cs):
    return dict(cs.FULL, users=24, links=48, features=32, count=8,
                fault_count=8,
                dataset=["--dataset", "random", "--vertices", "300",
                         "--edges", "900"],
                multihost=["--vertices", "2000", "--edges", "6000",
                           "--steps", "2"])


def _phase_lines(text):
    return [json.loads(line[len("phase "):]) for line in text.splitlines()
            if line.startswith("phase ")]


def test_phases_at_tiny_size(capsys, monkeypatch, tmp_path):
    # a cache directory from the environment keeps this process's config
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    cs = _load()
    tiny = _tiny(cs)
    cs.stream_phases(tiny, devices=4)
    cs.dataset_phase(tiny)
    lines = _phase_lines(capsys.readouterr().out)
    assert [ln["phase"] for ln in lines] == ["stream", "stream_faults",
                                             "dataset"]
    for ln in lines:
        assert ln["served"] == ln["submitted"] > 0, ln
        assert 0 <= ln["compile_s"] <= ln["wall_s"], ln
        assert ln["max_err"] < ln["bound"], ln
        assert ln["devices"] == 1, ln      # 4 servers fold onto 1 device
    for ln in lines[:2]:
        assert ln["plan_cache_misses"] >= 1, ln
    assert lines[0]["served"] == tiny["count"]


def test_four_device_phases_on_virtual_devices(tmp_path):
    """The ``--chips 4`` phases on 4 virtual CPU devices, in a child that
    owns its device count."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    code = textwrap.dedent(f"""
        import sys
        sys.path.insert(0, {str(ROOT / 'src')!r})
        sys.path.insert(0, {str(ROOT / 'tests')!r})
        from test_chip_smoke import _load, _tiny
        cs = _load()
        tiny = _tiny(cs)
        cs.stream_phases(tiny, devices=4, faults=False)
        cs.multihost_phase(tiny, devices=4)
    """)
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=600, env=env, cwd=tmp_path)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-3000:]
    lines = _phase_lines(out.stdout)
    assert [ln["phase"] for ln in lines] == ["stream", "multihost_engine",
                                             "multihost_resident"]
    assert all(ln["devices"] == 4 for ln in lines), lines
    assert lines[-1]["max_err"] == 0.0      # resident == engine, bitwise


def _run_script(args, cwd, script=SCRIPT):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run([sys.executable, str(script)] + args,
                          capture_output=True, text=True, timeout=300,
                          env=env, cwd=cwd)


def test_refuses_without_a_tpu(tmp_path):
    for args in ([], ["--chips", "4"]):
        out = _run_script(args, tmp_path)
        assert out.returncode != 0, out.stdout
        assert '"ok": true' not in out.stdout
        assert "needs a TPU" in out.stderr


def test_refuses_outside_the_repo(tmp_path):
    alone = tmp_path / "chip_smoke.py"
    shutil.copy(SCRIPT, alone)
    out = _run_script([], tmp_path, script=alone)
    assert out.returncode != 0, out.stdout
    assert '"ok": true' not in out.stdout


def test_enable_compile_cache_defers_to_the_environment(tmp_path):
    """Env var set: JAX's own setting stands. Unset: <repo>/.jax_cache.
    Run in children, so this process's JAX config is left alone."""
    code = ("import sys; sys.path.insert(0, {src!r}); import jax\n"
            "from repro.launch import enable_compile_cache\n"
            "print(enable_compile_cache(), "
            "jax.config.jax_compilation_cache_dir)").format(
                src=str(ROOT / "src"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    for cache_env, want in ((str(tmp_path), str(tmp_path)),
                            (None, str(ROOT / ".jax_cache"))):
        env.pop("JAX_COMPILATION_CACHE_DIR", None)
        if cache_env:
            env["JAX_COMPILATION_CACHE_DIR"] = cache_env
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        assert out.stdout.split() == [want, want]
