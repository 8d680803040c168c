"""The train / serve launchers on the CPU at smoke widths: depth cut,
compile-cache placement, no silent resume, failures that surface."""
import dataclasses
import math

import jax
import numpy as np
import pytest

from repro.configs import cut_depth, get
from repro.launch import compile_cache, serve, train


@pytest.mark.parametrize("arch,ok,bad", [
    ("granite-34b", (1, 8, 88), (0, 89)),
    ("gemma2-27b", (2, 4, 46), (1, 3)),           # local/global period 2
    ("deepseek-moe-16b", (2, 5), (1,)),           # one dense prefix layer
    ("jamba-v0.1-52b", (8, 16, 32), (4, 12)),     # attn every 8
])
def test_cut_depth_keeps_whole_periods(arch, ok, bad):
    spec = get(arch).spec
    for n in ok:
        cut = cut_depth(spec, n)
        assert cut.n_layers == n
        assert dataclasses.replace(cut, n_layers=spec.n_layers) == spec
    for n in bad:
        with pytest.raises(ValueError, match="whole periods"):
            cut_depth(spec, n)


@pytest.mark.parametrize("launcher", [train, serve])
def test_launcher_rejects_partial_period(launcher, capsys):
    with pytest.raises(SystemExit) as e:
        launcher.main(["--arch", "jamba-v0.1-52b", "--smoke", "--layers",
                       "4"])
    assert e.value.code == 2
    assert "whole periods of 8" in capsys.readouterr().err


def test_compile_cache_env_wins_else_repo_dir(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    was = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        got = compile_cache.use_compile_cache()
        assert got == str(compile_cache.REPO_CACHE)
        assert compile_cache.REPO_CACHE.parent.joinpath(
            "pyproject.toml").exists()
        assert jax.config.jax_compilation_cache_dir == got
    finally:
        jax.config.update("jax_compilation_cache_dir", was)


@pytest.fixture
def no_repo_cache(monkeypatch, tmp_path):
    """Entry points leave the cache where the environment says."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    return tmp_path


def test_train_launcher_checkpoints_only_with_ckpt_dir(no_repo_cache,
                                                       monkeypatch):
    monkeypatch.chdir(no_repo_cache)
    argv = ["--arch", "rwkv6-7b", "--smoke", "--steps", "2", "--batch", "2",
            "--seq", "32"]
    res = train.main(argv)
    assert len(res["losses"]) == 2
    assert all(math.isfinite(v) for v in res["losses"])
    assert dict(res["mesh"].shape) == {"data": 1, "model": 1}
    ckpt = no_repo_cache / "ck"
    train.main(argv + ["--steps", "10", "--ckpt-dir", str(ckpt)])
    assert any(ckpt.iterdir())
    # a rerun with the same directory resumes at step 10: nothing to do
    assert train.main(argv + ["--steps", "10", "--ckpt-dir",
                              str(ckpt)])["losses"] == []
    # without --ckpt-dir it starts from scratch, whatever exists on disk
    again = train.main(argv)["losses"]
    np.testing.assert_allclose(again, res["losses"], rtol=1e-6)


def test_serve_launcher_answers_every_request(no_repo_cache):
    engine, done = serve.main(["--arch", "qwen3-14b", "--smoke",
                               "--requests", "3", "--max-new", "2",
                               "--kv-len", "64"])
    assert sorted(r.rid for r in done) == [0, 1, 2]
    assert all(len(r.out) == 2 for r in done)


def test_train_launcher_times_each_step_in_spans(no_repo_cache, monkeypatch):
    from repro.obs import profiled
    monkeypatch.chdir(no_repo_cache)
    with profiled() as prof:
        res = train.main(["--arch", "rwkv6-7b", "--smoke", "--steps", "2",
                          "--batch", "2", "--seq", "32"])
    assert len(res["losses"]) == 2
    steps = [e for e in prof.events if e.name == "train.step"]
    assert [e.args["step"] for e in steps] == [0, 1]
    ids = {e.id for e in steps}
    for name in ("train.data", "train.dispatch", "train.sync"):
        kids = [e for e in prof.events if e.name == name]
        assert len(kids) == 2 and {k.parent for k in kids} == ids
