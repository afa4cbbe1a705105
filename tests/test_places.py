"""Places name one platform and never resolve to another; the compile
cache directory is placed once, from outside or next to the package."""
import os

import jax
import pytest

import paddle_tpu as fluid
from paddle_tpu.core import compile_cache


def test_tpu_place_raises_without_tpu():
    # conftest pins the suite to the cpu platform
    # ... and the message carries JAX's own reason for having no TPU
    with pytest.raises(RuntimeError,
                       match=r"no 'tpu' platform.*cpu.*JAX said: "
                             r"Unknown backend tpu") as exc:
        fluid.TPUPlace(0).jax_device()
    assert isinstance(exc.value.__cause__, RuntimeError)
    assert fluid.tpu_places() == [] and fluid.cuda_places() == []
    assert not fluid.is_compiled_with_tpu()


def test_tpu_place_device_id_out_of_range(monkeypatch):
    from paddle_tpu.core import place
    chips = tuple(jax.devices("cpu")[:2])   # stand-ins for two chips
    monkeypatch.setattr(place, "_local_devices",
                        lambda platform: (chips, None))
    assert fluid.TPUPlace(1).jax_device() is chips[1]
    assert fluid.tpu_places() == [fluid.TPUPlace(0), fluid.TPUPlace(1)]
    with pytest.raises(RuntimeError, match="valid device_id is 0..1"):
        fluid.TPUPlace(2).jax_device()


def test_cpu_place_names_a_missing_cpu_backend(monkeypatch):
    from paddle_tpu.core import place
    why = RuntimeError("Unknown backend cpu")
    monkeypatch.setattr(place, "_local_devices",
                        lambda platform: ((), why))
    with pytest.raises(RuntimeError, match="no 'cpu' platform.*Unknown"):
        fluid.CPUPlace(0).jax_device()


def test_data_parallel_over_no_places_raises():
    """places=fluid.tpu_places() on a host without a TPU is an empty
    list: with_data_parallel must say so, not mesh over the CPU."""
    from paddle_tpu import layers
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = layers.data(name="x", shape=[4], dtype="float32")
        loss = layers.mean(layers.fc(x, 1))
        fluid.optimizer.SGD(learning_rate=0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    compiled = fluid.CompiledProgram(main).with_data_parallel(
        loss_name=loss.name, places=fluid.tpu_places())
    with pytest.raises(ValueError, match="places is empty"):
        exe.run(compiled, feed={"x": [[0.0] * 4] * 8},
                fetch_list=[loss])


def test_default_place_follows_default_backend(monkeypatch):
    assert fluid.default_place() == fluid.CPUPlace(0)
    assert fluid.Executor().place == fluid.CPUPlace(0)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert fluid.default_place() == fluid.TPUPlace(0)


@pytest.fixture
def config_updates(monkeypatch):
    """Record jax.config.update calls instead of applying them."""
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    return calls


def test_compile_cache_left_to_jax_when_env_places_it(
        monkeypatch, config_updates):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.configure_compile_cache() is None
    assert config_updates == []


def test_compile_cache_default_is_fixed_next_to_package(
        monkeypatch, tmp_path, config_updates):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    here = compile_cache.configure_compile_cache()
    monkeypatch.chdir(tmp_path)
    there = compile_cache.configure_compile_cache()
    checkout = os.path.dirname(os.path.dirname(
        os.path.abspath(fluid.__file__)))
    assert here == there == os.path.join(checkout, ".jax_cache")
    assert config_updates == [("jax_compilation_cache_dir", here)] * 2
