"""Property fuzz: random op chains executed EAGERLY must equal the same
chain captured into a static Program and replayed by the Executor — the
capture-the-eager-dispatch design's core invariant, probed across randomly
composed graphs rather than hand-picked ones."""
import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import static

OPS = ["add", "mul", "matmul", "relu", "tanh", "mean_keep", "transpose",
       "scale"]


def _apply_op(op, x, aux):
    import paddle_tpu.nn.functional as F

    if op == "add":
        return x + aux
    if op == "mul":
        return x * 0.5 + x * aux * 0.1
    if op == "matmul":
        return paddle.matmul(x, paddle.transpose(x, [1, 0]))
    if op == "relu":
        return F.relu(x - 0.2)
    if op == "tanh":
        return paddle.tanh(x)
    if op == "mean_keep":
        return x - x.mean(axis=-1, keepdim=True)
    if op == "transpose":
        # NOTE: no shape-dependent python branching here — under capture,
        # dim 0 is symbolic (None) and a `shape[0] != shape[1]` branch
        # would diverge from eager. (That is the documented static
        # contract, not a bug: data/shape-dependent control flow belongs
        # in static.nn.cond.)
        return paddle.transpose(x, [1, 0])
    if op == "scale":
        return paddle.scale(x, scale=1.3, bias=-0.05)
    raise AssertionError(op)


def _run_chain(ops, x, aux):
    for op in ops:
        x = _apply_op(op, x, aux)
    return x


@pytest.mark.parametrize("seed", range(8))
def test_random_chain_eager_equals_captured(seed):
    rng = np.random.RandomState(seed)
    n = 4  # square keeps every op shape-stable
    ops = [OPS[i] for i in rng.randint(0, len(OPS), size=6)]
    x_np = rng.randn(n, n).astype(np.float32)
    aux_np = rng.randn(n, n).astype(np.float32)

    eager = _run_chain(ops, paddle.to_tensor(x_np),
                       paddle.to_tensor(aux_np)).numpy()

    main, startup = static.Program(), static.Program()
    with static.program_guard(main, startup):
        xv = static.data("x", [None, n], "float32")
        av = static.data("aux", [None, n], "float32")
        out = _run_chain(ops, xv, av)
    exe = static.Executor()
    exe.run(startup)
    (got,) = exe.run(main, feed={"x": x_np, "aux": aux_np},
                     fetch_list=[out])
    np.testing.assert_allclose(np.asarray(got), eager, rtol=1e-5,
                               atol=1e-5, err_msg=f"ops={ops}")


@pytest.mark.parametrize("seed", range(8, 12))
def test_random_chain_eager_equals_to_static(seed):
    """Same property through the jit path: to_static(chain) == eager."""
    rng = np.random.RandomState(seed)
    n = 4
    ops = [OPS[i] for i in rng.randint(0, len(OPS), size=6)]
    x_np = rng.randn(n, n).astype(np.float32)
    aux_np = rng.randn(n, n).astype(np.float32)

    eager = _run_chain(ops, paddle.to_tensor(x_np),
                       paddle.to_tensor(aux_np)).numpy()

    @paddle.jit.to_static
    def fn(x, aux):
        return _run_chain(ops, x, aux)

    got = fn(paddle.to_tensor(x_np), paddle.to_tensor(aux_np)).numpy()
    np.testing.assert_allclose(got, eager, rtol=1e-5, atol=1e-5,
                               err_msg=f"ops={ops}")


@pytest.mark.parametrize("seed", range(12, 15))
def test_random_chain_gradients_eager_equals_to_static(seed):
    """And the BACKWARD of random chains: compiled grads == tape grads."""
    rng = np.random.RandomState(seed)
    n = 4
    ops = [OPS[i] for i in rng.randint(0, len(OPS), size=5)]
    x_np = rng.randn(n, n).astype(np.float32)
    aux_np = rng.randn(n, n).astype(np.float32)

    xe = paddle.to_tensor(x_np)
    xe.stop_gradient = False
    _run_chain(ops, xe, paddle.to_tensor(aux_np)).sum().backward()
    eager_grad = np.asarray(xe.grad._data)

    import jax

    def loss(xa):
        out = _run_chain(ops, paddle.to_tensor(xa),
                         paddle.to_tensor(aux_np))
        return out._data.sum()

    # same chain under jax.grad via the traced path
    from paddle_tpu.jit import to_static

    @to_static
    def fwd(x, aux):
        return _run_chain(ops, x, aux).sum()

    xs = paddle.to_tensor(x_np)
    xs.stop_gradient = False
    fwd(xs, paddle.to_tensor(aux_np)).backward()
    np.testing.assert_allclose(np.asarray(xs.grad._data), eager_grad,
                               rtol=1e-5, atol=1e-5, err_msg=f"ops={ops}")


def test_to_static_layer_trains_like_reference_pattern():
    """The reference's canonical dy2static flow: decorate the LAYER with
    @to_static, then train with eager loss.backward() + opt.step(). The
    compiled forward must join the tape so parameter grads flow."""
    from paddle_tpu import nn

    paddle.seed(0)
    net = paddle.jit.to_static(
        nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1)))
    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    rng = np.random.RandomState(0)
    X = paddle.to_tensor(rng.rand(16, 8).astype(np.float32))
    Y = paddle.to_tensor(rng.rand(16, 1).astype(np.float32))
    first = last = None
    for _ in range(25):
        loss = ((net(X) - Y) ** 2).mean()
        loss.backward()
        opt.step()
        opt.clear_grad()
        first = first if first is not None else float(loss.numpy())
        last = float(loss.numpy())
    assert last < first * 0.3, (first, last)


def test_to_static_inference_stays_fast_path_under_no_grad():
    """Inference under no_grad keeps the detached fast path: no tape node
    is attached to the output (nothing retained for a backward that can
    never come)."""
    from paddle_tpu import nn

    net = paddle.jit.to_static(nn.Linear(4, 2))
    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    with paddle.no_grad():
        out = net(x)
    assert out._node is None


def test_to_static_bn_buffers_update_through_taped_path():
    """Buffer mutations (BN running stats) must survive the taped
    training path exactly as they do on the fast path."""
    from paddle_tpu import nn

    paddle.seed(0)
    net = paddle.jit.to_static(nn.Sequential(nn.Linear(4, 6),
                                             nn.BatchNorm1D(6)))
    rng = np.random.RandomState(3)
    x = paddle.to_tensor(rng.rand(8, 4).astype(np.float32) + 2.0)
    before = np.asarray(net[1]._mean._data).copy()
    loss = net(x).sum()
    loss.backward()  # taped path (params live)
    after = np.asarray(net[1]._mean._data)
    assert not np.allclose(before, after), "running mean did not update"
    assert net[0].weight.grad is not None


def test_to_static_dict_output_trains():
    """Arbitrary output pytrees (dicts) must round-trip identically on the
    taped training path."""
    from paddle_tpu import nn

    paddle.seed(1)
    lin = nn.Linear(4, 2)

    @paddle.jit.to_static
    def fwd(x):
        h = lin(x)
        return {"logits": h, "sum": h.sum(), "tag": 7}

    x = paddle.to_tensor(np.ones((3, 4), np.float32))
    out = fwd(x)
    assert set(out) == {"logits", "sum", "tag"} and out["tag"] == 7
    out["sum"].backward()
    assert lin.weight.grad is not None


def test_to_static_unhashable_static_leaf_falls_back_to_eager():
    """A non-hashable STATIC leaf (e.g. a config object) must not leak a
    retrace per call — the eager tape handles it (correct, uncompiled)."""
    from paddle_tpu import nn
    from paddle_tpu.core import dispatch

    lin = nn.Linear(4, 2)

    class Cfg:  # deliberately unhashable config object
        __hash__ = None
        scale = 2.0

    @paddle.jit.to_static
    def fwd(x, cfg):
        return lin(x) * cfg.scale

    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    fwd(x, Cfg())
    before = len(dispatch._JIT_CACHE)
    for _ in range(4):
        out = fwd(x, Cfg())
    # per-op entries may exist from the eager ops, but no per-call growth
    grown = len(dispatch._JIT_CACHE) - before
    assert grown == 0, grown
    out.sum().backward()
    assert lin.weight.grad is not None


def test_to_static_global_model_weights_stay_live(tmp_path):
    """A module/global-scope model referenced by a free @to_static function
    must NOT bake its weights into the compiled program: updates made
    outside (optimizer steps, manual assignment, ckpt restore) must be
    visible to the next call."""
    import textwrap
    import subprocess
    import sys
    import os

    script = textwrap.dedent("""
        import numpy as np
        import jax
        jax.config.update("jax_platforms", "cpu")
        import paddle_tpu as paddle
        from paddle_tpu import nn

        m = nn.Linear(4, 1)       # module scope -> reached via __globals__
        @paddle.jit.to_static
        def infer(x):
            return m(x)
        x = paddle.to_tensor(np.ones((2, 4), np.float32))
        with paddle.no_grad():
            before = infer(x).numpy().copy()
        m.weight._data = m.weight._data * 2.0
        with paddle.no_grad():
            after = infer(x).numpy()
        assert not np.allclose(before, after), "stale baked weights"
        print("LIVE-OK")
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH="/root/repo")
    r = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0 and "LIVE-OK" in r.stdout, r.stderr[-500:]


def test_to_static_float_arg_does_not_retrace_per_value():
    """A per-step python float (lr, temperature) rides as a TRACED arg:
    distinct values must NOT mint new executables."""
    from paddle_tpu import nn
    from paddle_tpu.core import dispatch

    lin = nn.Linear(4, 2)

    @paddle.jit.to_static
    def fwd(x, scale):
        return lin(x) * scale

    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    x.stop_gradient = False
    out0 = fwd(x, 1.0)
    before = len(dispatch._JIT_CACHE)
    vals = [fwd(x, s).sum().numpy() for s in (2.0, 3.0, 4.5)]
    assert len(dispatch._JIT_CACHE) == before, "per-value retrace"
    np.testing.assert_allclose(
        np.asarray(vals) / float(out0.sum().numpy()), [2.0, 3.0, 4.5],
        rtol=1e-5)


def test_pylayer_custom_vjp_inside_to_static():
    """PyLayer custom backward composes with the taped compiled call: the
    custom 2x vjp must scale the input gradient exactly."""
    from paddle_tpu import nn
    from paddle_tpu.autograd import PyLayer

    class Double(PyLayer):
        @staticmethod
        def forward(ctx, x):
            ctx.save_for_backward(x)
            return x * 2.0

        @staticmethod
        def backward(ctx, dy):
            return dy * 2.0

    paddle.seed(2)
    lin = nn.Linear(4, 2)

    @paddle.jit.to_static
    def with_pylayer(x):
        return Double.apply(lin(x)).sum()

    @paddle.jit.to_static
    def plain(x):
        return lin(x).sum()

    x1 = paddle.to_tensor(np.ones((2, 4), np.float32))
    x1.stop_gradient = False
    with_pylayer(x1).backward()
    x2 = paddle.to_tensor(np.ones((2, 4), np.float32))
    x2.stop_gradient = False
    plain(x2).backward()
    np.testing.assert_allclose(np.asarray(x1.grad._data),
                               2 * np.asarray(x2.grad._data), rtol=1e-6)


def test_nested_to_static_grads_flow():
    """A @to_static function calling another @to_static function: the
    inner executes traced inside the outer's program; grads flow."""
    from paddle_tpu import nn

    paddle.seed(3)
    lin = nn.Linear(4, 2)

    @paddle.jit.to_static
    def inner(x):
        return lin(x)

    @paddle.jit.to_static
    def outer(x):
        return inner(x).sum()

    x = paddle.to_tensor(np.ones((2, 4), np.float32))
    x.stop_gradient = False
    outer(x).backward()
    assert x.grad is not None and lin.weight.grad is not None


def test_recursive_to_static_does_not_hang_discovery():
    """A @to_static function that REFERENCES itself (LOAD_GLOBAL of its own
    name) must not infinitely recurse in state discovery — the hazard is at
    build time, whether or not the recursive branch ever executes."""
    global _self_ref_fn

    @paddle.jit.to_static
    def _self_ref_fn(x, depth=0):
        if depth > 0:  # static python flag: branch never taken at trace
            return _self_ref_fn(x)
        return x * 2.0

    out = _self_ref_fn(paddle.to_tensor(np.ones(3, np.float32)))
    np.testing.assert_allclose(out.numpy(), 2.0)


def test_nested_to_static_bn_stats_reach_outer():
    """BN running stats mutated by an INNER @to_static must survive the
    outer program's state restore (the ambient-sink forwarding path)."""
    from paddle_tpu import nn

    paddle.seed(4)
    bn = nn.BatchNorm1D(3, momentum=0.5)

    @paddle.jit.to_static
    def inner(x):
        return bn(x)

    @paddle.jit.to_static
    def outer(x):
        return inner(x).sum()

    x = paddle.to_tensor(
        np.random.RandomState(0).rand(8, 3).astype(np.float32) + 2.0)
    x.stop_gradient = False
    before = np.asarray(bn._mean._data).copy()
    outer(x).backward()
    after = np.asarray(bn._mean._data)
    assert not np.allclose(before, after), \
        "inner BN stats silently dropped by the outer restore"
    assert np.isfinite(after).all()


def test_to_static_inside_trainstep_loss():
    """A @to_static function used INSIDE a TrainStep loss: the inner
    executes traced within the outer compiled program and training
    converges (the PRNG-key arg must not trip differentiability checks)."""
    from paddle_tpu import nn
    from paddle_tpu.jit import TrainStep

    paddle.seed(0)
    net = nn.Linear(4, 1)

    @paddle.jit.to_static
    def fwd(x):
        return net(x)

    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    step = TrainStep(lambda a, b: ((fwd(a) - b) ** 2).mean(), opt,
                     layers=net)
    X = paddle.to_tensor(np.random.RandomState(0).rand(8, 4)
                         .astype(np.float32))
    Y = paddle.to_tensor(np.random.RandomState(1).rand(8, 1)
                         .astype(np.float32))
    ls = [float(step(X, Y).numpy()) for _ in range(10)]
    assert ls[-1] < ls[0]


def test_double_grad_through_to_static():
    """create_graph double-grad composes with the taped compiled call:
    exact d/dx and d2/dx2 of x^3."""
    @paddle.jit.to_static
    def g(x):
        return (x ** 3).sum()

    x = paddle.to_tensor(np.array([2.0], np.float32))
    x.stop_gradient = False
    (dx,) = paddle.grad(g(x), [x], create_graph=True)
    (d2x,) = paddle.grad(dx.sum(), [x])
    np.testing.assert_allclose(float(dx.numpy()[0]), 12.0, rtol=1e-5)
    np.testing.assert_allclose(float(d2x.numpy()[0]), 12.0, rtol=1e-5)


def test_to_static_under_autocast_with_gradscaler():
    """AMP interplay: @to_static forward under auto_cast + GradScaler
    training. The autocast policy is SNAPSHOTTED into the taped call —
    backward re-executes after the context exits and must see the same
    casts (a policy change would make jax.vjp reject the ct dtype)."""
    from paddle_tpu import amp, nn

    paddle.seed(0)
    net = nn.Linear(4, 1)

    @paddle.jit.to_static
    def fwd(x):
        return net(x)

    opt = paddle.optimizer.Adam(learning_rate=1e-2,
                                parameters=net.parameters())
    scaler = amp.GradScaler(init_loss_scaling=1024.0)
    X = paddle.to_tensor(np.random.RandomState(0).rand(8, 4)
                         .astype(np.float32))
    Y = paddle.to_tensor(np.random.RandomState(1).rand(8, 1)
                         .astype(np.float32))
    first = last = None
    for _ in range(15):
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            loss = ((fwd(X) - Y) ** 2).mean()
        scaled = scaler.scale(loss)
        scaled.backward()
        scaler.step(opt)
        scaler.update()
        opt.clear_grad()
        first = first if first is not None else float(loss.numpy())
        last = float(loss.numpy())
    assert last < first, (first, last)


def test_to_static_inference_respects_policy_changes():
    """The no-grad fast path compiles PER autocast policy: a function first
    traced under bf16 autocast must NOT reuse that executable for a later
    call without autocast (and vice versa)."""
    from paddle_tpu import amp, nn

    paddle.seed(5)
    net = nn.Linear(4, 2)

    @paddle.jit.to_static
    def fwd(x):
        return net(x)

    x = paddle.to_tensor(np.random.RandomState(0).rand(2, 4)
                         .astype(np.float32))
    with paddle.no_grad():
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            out_amp = fwd(x)
        out_plain = fwd(x)
    assert "bfloat16" in str(out_amp._data.dtype)
    assert "float32" in str(out_plain._data.dtype), \
        "bf16 executable reused outside autocast"
    # and back again: the per-policy cache serves the right one
    with paddle.no_grad():
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            assert "bfloat16" in str(fwd(x)._data.dtype)
