"""Context -> jax.Device resolution (`Context.jax_device`): the CPU-mode
emulation the tests run under, and the rule that holds on a chip."""
import jax
import pytest

import mxnet_tpu as mx
from mxnet_tpu import context
from mxnet_tpu.base import MXNetError


def test_cpu_mode_emulates_accelerators_on_host_devices():
    # default backend cpu (conftest: JAX_PLATFORMS=cpu, 8 devices)
    hosts = jax.local_devices(backend="cpu")
    assert mx.context.num_tpus() == 0
    assert [mx.tpu(i).jax_device() for i in range(8)] == hosts
    assert mx.gpu(3).jax_device() == hosts[3]
    assert mx.tpu(9).jax_device() == hosts[1]      # wraps in CPU mode
    assert mx.cpu(0).jax_device() == hosts[0]


class _Chip:
    platform, device_kind = "tpu", "TPU v5 lite"


def test_on_a_chip_a_context_names_one_device(monkeypatch):
    chips = [_Chip(), _Chip()]
    monkeypatch.setattr(context, "_accelerator_devices", lambda: chips)
    assert mx.tpu(0).jax_device() is chips[0]
    assert mx.gpu(1).jax_device() is chips[1]
    # beyond the local count: an error, never folded onto chip 0
    with pytest.raises(MXNetError, match=r"tpu\(2\).*2 accelerator"):
        mx.tpu(2).jax_device()
    # host contexts still resolve beside the chips
    assert mx.cpu(0).jax_device().platform == "cpu"
    assert mx.current_context() == mx.cpu(0)


def test_backend_failure_is_not_read_as_no_accelerator(monkeypatch):
    def broken(*a, **k):
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "local_devices", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        mx.context.num_tpus()
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        mx.tpu(0).jax_device()
    from mxnet_tpu.ops import pallas_kernels
    monkeypatch.setattr(jax, "default_backend", broken)
    with pytest.raises(RuntimeError, match="Unable to initialize"):
        pallas_kernels.is_tpu()


@pytest.mark.parametrize("make", [
    lambda: mx.cpu(0).jax_device(), lambda: mx.cpu_pinned(0).jax_device(),
    lambda: mx.Context("cpu_shared", 0).jax_device(),
    lambda: mx.nd.array([1.0]), lambda: mx.nd.zeros((2,))],
    ids=["cpu", "cpu_pinned", "cpu_shared", "array-no-ctx", "zeros-no-ctx"])
def test_platform_list_without_the_host_backend_is_named(monkeypatch, make):
    """JAX_PLATFORMS=tpu alone leaves no cpu backend; the default context
    is cpu(0), so the error says what to add to the list."""
    def local_devices(*a, backend=None, **k):
        assert backend == "cpu"
        raise RuntimeError("Unknown backend cpu. Available backends are "
                           "['tpu']")
    monkeypatch.setattr(jax, "local_devices", local_devices)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(MXNetError, match="JAX_PLATFORMS=tpu,cpu"):
        make()
