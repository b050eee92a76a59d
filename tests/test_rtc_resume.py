"""rtc (runtime Pallas kernels) + checkpoint auto-resume helpers.

Parity models: python/mxnet/rtc.py CudaModule/CudaKernel API shape,
SURVEY §5.3 (checkpoint-based resume, absent in the reference).
"""
import numpy as np
import pytest

import incubator_mxnet_tpu as mx
from incubator_mxnet_tpu import nd


def test_pallas_module_launch():
    def axpy_kernel(x_ref, y_ref, o_ref):
        o_ref[...] = x_ref[...] * 2.0 + y_ref[...]

    mod = mx.rtc.PallasModule({"axpy": axpy_kernel})
    k = mod.get_kernel("axpy")
    x = nd.array(np.arange(8, dtype=np.float32))
    # the CPU mesh has no Mosaic compiler: interpret mode, asked for by name
    out = k.launch([x, nd.ones(8)], interpret=True)
    np.testing.assert_allclose(out.asnumpy(), x.asnumpy() * 2 + 1)
    # compiled call is cached per signature
    out2 = k.launch([x, nd.ones(8)], interpret=True)
    np.testing.assert_allclose(out2.asnumpy(), out.asnumpy())
    with pytest.raises(mx.MXNetError):
        mod.get_kernel("nope")


def test_cuda_module_redirects():
    with pytest.raises(mx.MXNetError):
        mx.rtc.CudaModule("__global__ void f(){}")


def test_checkpoint_resume_cycle(tmp_path):
    prefix = str(tmp_path / "run")
    net = mx.sym.SoftmaxOutput(
        mx.sym.FullyConnected(mx.sym.var("data"), num_hidden=3, name="fc"),
        name="softmax")
    arg = {"fc_weight": nd.ones((3, 4)), "fc_bias": nd.zeros(3)}
    assert mx.model.latest_checkpoint(prefix) is None
    s, a, x, ep = mx.model.resume_from_checkpoint(prefix)
    assert s is None and ep == 0
    mx.model.save_checkpoint(prefix, 2, net, arg, {})
    mx.model.save_checkpoint(prefix, 5, net, arg, {})
    assert mx.model.latest_checkpoint(prefix) == 5
    s, a, x, ep = mx.model.resume_from_checkpoint(prefix)
    assert ep == 5 and set(a) == {"fc_weight", "fc_bias"}

    # resume actually continues training
    rng = np.random.RandomState(0)
    data = rng.randn(60, 4).astype(np.float32)
    label = (rng.rand(60) * 3).astype(np.float32)
    it = mx.io.NDArrayIter(data, label, batch_size=20)
    mod = mx.mod.Module(s, context=mx.cpu())
    mod.fit(it, num_epoch=7, begin_epoch=ep, arg_params=a, aux_params=x,
            optimizer="sgd", optimizer_params={"learning_rate": 0.1})
    assert mod.get_params()[0]["fc_weight"].shape == (3, 4)
