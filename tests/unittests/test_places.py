"""One decision about the device: an explicit place resolves to exactly that
device or raises; no place given means jax's default device, resolved once
and visible as ``exe.place``."""
import pytest

import paddle_tpu as fluid


def test_explicit_tpu_place_raises_without_a_tpu():
    with pytest.raises(RuntimeError, match="TPUPlace.*default backend is 'cpu'"):
        fluid.TPUPlace().jax_device()
    with pytest.raises(RuntimeError, match="TPUPlace"):
        fluid.Executor(fluid.TPUPlace())


def test_place_with_missing_device_id_raises():
    import jax

    n = len(jax.devices("cpu"))
    assert fluid.CPUPlace(n - 1).jax_device() == jax.devices("cpu")[n - 1]
    with pytest.raises(RuntimeError, match="CPUPlace"):
        fluid.CPUPlace(n).jax_device()


@pytest.mark.parametrize("make", [
    lambda: fluid.Executor(),
    lambda: fluid.Inferencer(lambda: fluid.layers.data(
        name="x", shape=[2], dtype="float32"), param_path=None),
], ids=["Executor", "Inferencer"])
def test_no_place_means_jax_default_device_and_says_so(make):
    assert make().place == fluid.CPUPlace(0)


def test_get_places_tpu_filters_on_tpu_only():
    assert fluid.layers.get_places(device_type="tpu") == []
    assert len(fluid.layers.get_places(device_type="cpu")) >= 1
