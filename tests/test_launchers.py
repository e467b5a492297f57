"""CLI launcher smoke tests (subprocess, reduced configs)."""
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, PYTHONPATH="src")


def _run(args, timeout=900):
    out = subprocess.run([sys.executable, "-m"] + args, capture_output=True,
                         text=True, env=ENV, cwd=REPO, timeout=timeout)
    assert out.returncode == 0, out.stderr[-3000:]
    return out.stdout


def test_train_launcher_reduced(tmp_path):
    ck = os.path.join(tmp_path, "ck.npz")
    out = _run(["repro.launch.train", "--arch", "gpt2_small", "--reduced",
                "--steps", "12", "--batch", "4", "--seq", "32",
                "--ckpt", ck])
    assert "done: loss" in out
    assert os.path.exists(ck)
    # loss must decrease
    import re
    m = re.search(r"loss (\d+\.\d+) -> (\d+\.\d+)", out)
    assert float(m.group(2)) < float(m.group(1))


def test_serve_launcher_reduced():
    out = _run(["repro.launch.serve", "--arch", "bert_base", "--requests",
                "16", "--batch", "8", "--seq", "48", "--calib-batches", "2",
                "--level", "aggressive"])
    assert "memo rate" in out
    assert "baseline" in out


def test_dryrun_cli_single_combo(tmp_path):
    out = _run(["repro.launch.dryrun", "--arch", "qwen2_1_5b", "--shape",
                "decode_32k", "--single-pod-only", "--no-correct",
                "--out", str(tmp_path)], timeout=1200)
    assert "-> ok" in out
    assert os.path.exists(
        os.path.join(tmp_path, "qwen2_1_5b_decode_32k_pod256.json"))


def test_compile_cache_dir_rule(monkeypatch):
    """One rule for the persistent compilation cache: the environment's
    directory when set (and then nothing is set in code), else a fixed
    directory inside the checkout."""
    import jax
    from repro.launch import compile_cache
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: calls.append((k, v)))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.enable_compile_cache() == want
    assert calls == [("jax_compilation_cache_dir", want)]
