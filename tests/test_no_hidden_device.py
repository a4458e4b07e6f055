"""No fallback hides the device.

A ``tpu`` place is served from the CPU only in a process that was PUT on
the CPU (``JAX_PLATFORMS=cpu`` — how this suite runs, see conftest); a
process that merely found no chip gets an error. An index past the
device count is an error, not the last device. Nothing in the program
defaults a platform for a child, and ``bench.py`` measures nothing
without a chip.
"""
import os
import sys

import numpy as np
import pytest

import jax

import paddle_tpu as paddle
from paddle_tpu.core import device as device_mod
from paddle_tpu.serving.fleet import launch

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import bench  # noqa: E402


@pytest.fixture
def found_no_chip(monkeypatch):
    """This process as one that was NOT put on the CPU and sees no chip
    (jax fell back by itself)."""
    monkeypatch.setattr(device_mod, "explicitly_on_cpu", lambda: False)
    prev = device_mod._STATE.place
    yield
    device_mod._STATE.place = prev


TPU_PLACE_USERS = {
    "set_device": lambda: paddle.set_device("tpu"),
    "set_device gpu alias": lambda: paddle.set_device("gpu:0"),
    "jax_device": lambda: device_mod.jax_device(paddle.TPUPlace(0)),
    "Tensor.cuda": lambda: paddle.to_tensor(np.ones(2, np.float32)).cuda(),
    "Tensor.to": lambda: paddle.to_tensor(np.ones(2, np.float32)).to("tpu"),
    "Layer.to": lambda: paddle.nn.Linear(2, 2).to("tpu"),
}


@pytest.mark.parametrize("user", sorted(TPU_PLACE_USERS))
def test_tpu_place_without_chip_raises(found_no_chip, user):
    with pytest.raises(RuntimeError, match="no accelerator"):
        TPU_PLACE_USERS[user]()


@pytest.mark.parametrize("user", sorted(TPU_PLACE_USERS))
def test_tpu_place_on_explicit_cpu_is_the_fake_backend(user):
    """The chosen fake backend stays: same calls, CPU devices."""
    prev = device_mod._STATE.place
    try:
        assert device_mod.explicitly_on_cpu()
        TPU_PLACE_USERS[user]()
        assert device_mod.jax_device(paddle.TPUPlace(3)).id == 3
    finally:
        jax.config.update("jax_default_device", None)
        device_mod._STATE.place = prev


@pytest.mark.parametrize("spec", ["tpu:9", "cpu:9", "gpu:64", "tpu:-1"])
def test_index_past_the_device_count_raises(spec):
    prev = device_mod._STATE.place
    try:
        with pytest.raises(ValueError, match="device"):
            paddle.set_device(spec)
        # the failed call left the current place alone
        assert device_mod._STATE.place == prev
    finally:
        device_mod._STATE.place = prev


def test_explicitly_on_cpu_reads_what_jax_was_told(monkeypatch):
    assert device_mod.explicitly_on_cpu()
    with monkeypatch.context() as m:
        m.setattr(type(jax.config), "jax_platforms",
                  property(lambda self: None), raising=False)
        assert not device_mod.explicitly_on_cpu()
        m.setattr(type(jax.config), "jax_platforms",
                  property(lambda self: "tpu,cpu"), raising=False)
        assert not device_mod.explicitly_on_cpu()


class _Stop(Exception):
    pass


def _main_until_args_are_parsed(monkeypatch):
    def stop(*a, **k):
        raise _Stop

    monkeypatch.setattr(launch.argparse.ArgumentParser, "parse_args", stop)
    with pytest.raises(_Stop):
        launch.main(["--role", "replica"])
    return dict(os.environ)


def _child_env(monkeypatch):
    seen = {}

    def popen(cmd, **kw):
        seen.update(kw["env"])
        raise _Stop

    monkeypatch.setattr(launch.subprocess, "Popen", popen)
    with pytest.raises(_Stop):
        launch.spawn("replica", ())
    return seen


@pytest.mark.parametrize("env_of", [_main_until_args_are_parsed, _child_env],
                         ids=["main", "spawned child"])
def test_fleet_launch_sets_no_platform(monkeypatch, env_of):
    """A replica runs on what its environment says; on a chip machine
    that is the chip, and N children that cannot all claim it fail —
    they do not serve from the CPU and print FLEET_READY."""
    monkeypatch.delenv("JAX_PLATFORMS")
    assert "JAX_PLATFORMS" not in env_of(monkeypatch)
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    assert env_of(monkeypatch)["JAX_PLATFORMS"] == "cpu"


def test_bench_refuses_to_run_without_a_chip(found_no_chip):
    with pytest.raises(SystemExit) as e:
        bench._require_backend()
    assert e.value.code not in (0, None)   # a message: exit status 1
    assert "no accelerator" in str(e.value.code)


def test_bench_cpu_smoke_needs_the_cpu_chosen():
    bench._require_backend()   # JAX_PLATFORMS=cpu: the smoke may run


def test_bench_unknown_device_kind_is_an_error_not_a_default():
    # this process's "cpu" kind is not in the peak table
    with pytest.raises(SystemExit, match="no peak FLOP/s known"):
        bench._peak()


def test_bench_all_lets_a_failing_config_end_the_run(monkeypatch):
    def boom():
        raise RuntimeError("config failed")

    monkeypatch.setattr(bench, "bench_lenet_fit", boom)
    with pytest.raises(RuntimeError, match="config failed"):
        bench.run_all()


@pytest.mark.parametrize("env_dir", [None, "/somewhere/else"])
def test_compile_cache_is_placed_from_outside(monkeypatch, env_dir):
    """``JAX_COMPILATION_CACHE_DIR`` set: jax reads it, nothing is set in
    code. Unset: one fixed path inside the checkout — never a temporary
    directory, which would never hit twice."""
    from paddle_tpu.jit import compile_cache

    seen = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda k, v: seen.__setitem__(k, v))
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_DIR, raising=False)
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        assert compile_cache.place_compile_cache() == os.path.join(
            repo, ".jax_cache")
        assert seen == {"jax_compilation_cache_dir": compile_cache.DEFAULT_DIR}
    else:
        monkeypatch.setenv(compile_cache.ENV_DIR, env_dir)
        assert compile_cache.place_compile_cache() == env_dir
        assert seen == {}


def test_removed_shims_are_gone():
    assert not hasattr(bench, "probe_backend")
    with pytest.raises(ImportError):
        import paddle_tpu.core.jax_compat  # noqa: F401
