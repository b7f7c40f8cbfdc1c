"""paddle.nn.utils: clipping helpers, parameter vectorization, weight/
spectral norm hooks (ref:python/paddle/nn/utils/)."""
import numpy as np
import pytest
import torch

import paddle_tpu as paddle
from paddle_tpu import nn
from paddle_tpu.core.tensor import Tensor


def _net():
    paddle.seed(0)
    return nn.Linear(4, 3)


def test_clip_grad_norm_matches_torch():
    net = _net()
    x = np.random.randn(8, 4).astype(np.float32) * 10
    loss = (net(Tensor(x)) ** 2).sum()
    loss.backward()
    grads_before = [p.grad.numpy().copy() for p in net.parameters()]

    tp = [torch.nn.Parameter(torch.tensor(g)) for g in grads_before]
    for t, g in zip(tp, grads_before):
        t.grad = torch.tensor(g)
    tnorm = torch.nn.utils.clip_grad_norm_(tp, 1.0)

    total = nn.utils.clip_grad_norm_(net.parameters(), 1.0)
    assert float(total) == pytest.approx(float(tnorm), rel=1e-5)
    for p, t in zip(net.parameters(), tp):
        np.testing.assert_allclose(p.grad.numpy(), t.grad.numpy(), rtol=1e-4)


def test_clip_grad_norm_inf_and_value():
    net = _net()
    loss = (net(Tensor(np.ones((2, 4), np.float32))) ** 2).sum()
    loss.backward()
    total = nn.utils.clip_grad_norm_(net.parameters(), 0.5,
                                     norm_type=float("inf"))
    assert float(total) >= 0
    for p in net.parameters():
        assert float(np.abs(p.grad.numpy()).max()) <= 0.5 + 1e-6
    nn.utils.clip_grad_value_(net.parameters(), 0.1)
    for p in net.parameters():
        assert float(np.abs(p.grad.numpy()).max()) <= 0.1 + 1e-7


def test_parameters_vector_round_trip():
    net = _net()
    vec = nn.utils.parameters_to_vector(net.parameters())
    assert vec.shape == [4 * 3 + 3]
    new = Tensor(np.arange(15, dtype=np.float32))
    nn.utils.vector_to_parameters(new, net.parameters())
    np.testing.assert_allclose(
        nn.utils.parameters_to_vector(net.parameters()).numpy(),
        np.arange(15, dtype=np.float32))
    with pytest.raises(ValueError, match="elements"):
        nn.utils.vector_to_parameters(Tensor(np.zeros(7, np.float32)),
                                      net.parameters())


def test_weight_norm_forward_and_training():
    paddle.seed(1)
    lin = nn.Linear(4, 3)
    w0 = lin.weight.numpy().copy()
    out_ref = lin(Tensor(np.ones((2, 4), np.float32))).numpy()
    nn.utils.weight_norm(lin, "weight", dim=0)
    names = dict(lin.named_parameters())
    assert "weight_g" in names and "weight_v" in names and "weight" not in names
    # reparameterized forward equals the original at init
    out = lin(Tensor(np.ones((2, 4), np.float32))).numpy()
    np.testing.assert_allclose(out, out_ref, atol=1e-5)
    # trains: grads reach g and v
    loss = (lin(Tensor(np.random.randn(4, 4).astype(np.float32))) ** 2).mean()
    loss.backward()
    assert lin.weight_g.grad is not None and lin.weight_v.grad is not None
    # remove folds back to a single parameter with the same effective value
    nn.utils.remove_weight_norm(lin, "weight")
    assert "weight" in dict(lin.named_parameters())
    np.testing.assert_allclose(lin.weight.numpy(), w0, atol=1e-5)


def test_weight_norm_compiled_step():
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import SGD

    paddle.seed(2)
    lin = nn.Linear(4, 2)
    nn.utils.weight_norm(lin, "weight")
    opt = SGD(learning_rate=0.05, parameters=lin.parameters())
    # seeded: of data drawn from the global generator, one set in twenty
    # does not halve the loss in 20 steps (seed 0 ends at 0.31 of it)
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 4)).astype(np.float32)
    y = rng.standard_normal((8, 2)).astype(np.float32)
    step = TrainStep(lambda a, b: ((lin(a) - b) ** 2).mean(), opt, layers=lin)
    l0 = float(step(Tensor(x), Tensor(y))._data)
    for _ in range(20):
        l1 = float(step(Tensor(x), Tensor(y))._data)
    assert l1 < 0.5 * l0


def test_spectral_norm_hook():
    paddle.seed(3)
    lin = nn.Linear(6, 5)
    nn.utils.spectral_norm(lin, "weight", n_power_iterations=3)
    out = lin(Tensor(np.ones((2, 6), np.float32)))
    assert out.shape == [2, 5]
    # effective weight has unit spectral norm (power iteration converged)
    w = lin.weight.numpy()
    assert np.linalg.svd(w, compute_uv=False)[0] == pytest.approx(1.0,
                                                                  rel=1e-2)
    # trains through the reparameterization
    loss = (lin(Tensor(np.random.randn(3, 6).astype(np.float32))) ** 2).sum()
    loss.backward()
    assert lin.weight_orig.grad is not None


def test_vector_to_parameters_accepts_iterator():
    net = _net()
    vec = Tensor(np.arange(15, dtype=np.float32))
    nn.utils.vector_to_parameters(vec, iter(list(net.parameters())))
    np.testing.assert_allclose(
        nn.utils.parameters_to_vector(net.parameters()).numpy(),
        np.arange(15, dtype=np.float32))


def test_spectral_norm_dim_none_and_eval_stability():
    paddle.seed(4)
    lin = nn.Linear(6, 5)
    nn.utils.spectral_norm(lin)  # dim=None -> 1 for Linear (reference)
    lin.eval()
    x = Tensor(np.ones((2, 6), np.float32))
    a = lin(x).numpy()
    b = lin(x).numpy()
    np.testing.assert_array_equal(a, b)  # eval: no iteration, no drift
    u_before = lin.weight_u.numpy().copy()
    lin(x)
    np.testing.assert_array_equal(lin.weight_u.numpy(), u_before)


def test_clip_alias_routes_to_utils():
    from paddle_tpu.nn.clip import clip_grad_norm_ as alias

    net = _net()
    loss = (net(Tensor(np.ones((2, 4), np.float32))) ** 2).sum()
    loss.backward()
    t1 = float(alias(net.parameters(), 1.0))
    assert t1 > 0
