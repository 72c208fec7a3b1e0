"""CLI launcher smoke tests (subprocess — train/serve/dryrun drivers)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")


def _run(args, timeout=600, extra_env=None, drop_env=()):
    env = dict(os.environ)
    for name in drop_env:
        env.pop(name, None)
    env["PYTHONPATH"] = os.path.join(ROOT, "src")
    # Force the CPU platform: with libtpu installed but no TPU attached,
    # leaving the platform unset makes jax's TPU plugin stall ~8 min on
    # metadata queries before falling back.  Multi-device simulation comes
    # from XLA_FLAGS (the CLIs set it), not from the platform choice.
    env["JAX_PLATFORMS"] = "cpu"
    if extra_env:
        env.update(extra_env)
    res = subprocess.run([sys.executable] + args, env=env,
                         capture_output=True, text=True, timeout=timeout,
                         cwd=ROOT)
    assert res.returncode == 0, res.stdout[-2000:] + res.stderr[-2000:]
    return res.stdout


def test_train_cli_reduced():
    out = _run(["-m", "repro.launch.train", "--arch", "qwen1.5-0.5b",
                "--reduced", "--steps", "12", "--seq", "32",
                "--global-batch", "4"])
    assert "loss" in out


def test_train_cli_on_mesh():
    out = _run(["-m", "repro.launch.train", "--arch", "chatglm3-6b",
                "--reduced", "--steps", "6", "--seq", "32",
                "--global-batch", "4", "--host-devices", "4",
                "--data-axis", "2", "--model-axis", "2"])
    assert "mesh" in out and "loss" in out


def test_serve_cli_reduced():
    out = _run(["-m", "repro.launch.serve", "--arch", "mamba2-1.3b",
                "--reduced", "--batch", "2", "--prompt-len", "8",
                "--gen", "4"])
    assert "generated" in out


def test_dryrun_cli_single_cell():
    # tiny-arch cell; exercises the full lower+compile+analyze path
    out = _run(["-m", "repro.launch.dryrun", "--arch", "whisper-base",
                "--shape", "decode_32k", "--force"], timeout=900)
    assert "ok" in out


def _cache_dir_after(env_value):
    """Run use_compile_cache() in a fresh process; return (returned dir,
    the dir jax's config holds)."""
    code = ("import json, jax; "
            "from repro.launch.compile_cache import use_compile_cache; "
            "d = use_compile_cache(); "
            "print(json.dumps([d, jax.config.jax_compilation_cache_dir]))")
    env = {"JAX_COMPILATION_CACHE_DIR": env_value} if env_value else {}
    return json.loads(_run(["-c", code], extra_env=env,
                           drop_env=() if env_value else
                           ("JAX_COMPILATION_CACHE_DIR",)).strip())


def test_compile_cache_follows_the_environment(tmp_path):
    want = str(tmp_path / "jaxcache")
    assert _cache_dir_after(want) == [want, want]


def test_compile_cache_defaults_to_the_checkout():
    want = os.path.realpath(os.path.join(ROOT, ".jax_cache"))
    got, configured = _cache_dir_after(None)
    assert got == configured == want
