"""core.compile_cache: persistent XLA cache, counters, donation, bucketing —
plus regression tests for the round-5 ADVICE.md findings (flash routing
threshold, NativePredictor empty options, recompute kwarg shadowing)."""
import functools
import os
import tempfile
import time

import numpy as np
import pytest

import paddle_tpu as pt
import paddle_tpu.jit as jit
from paddle_tpu.core import compile_cache as cc
from paddle_tpu.core.tensor import Tensor


@pytest.fixture
def tmp_cache(monkeypatch):
    """Point the persistent cache at a fresh tmp dir (persist-everything
    thresholds) for one test; restore the previous dir after. The
    explicit ``cache_dir=`` exists for exactly this and yields to
    JAX_COMPILATION_CACHE_DIR, so the variable is unset for the test."""
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    prev = cc.cache_dir()
    d = tempfile.mkdtemp(prefix="pt_cc_test_")
    cc.initialize(cache_dir=d, force=True, min_compile_secs=0.0)
    try:
        yield d
    finally:
        cc.initialize(cache_dir=prev, force=True)


@pytest.fixture
def restore_flags():
    keep = {k: pt.get_flags(k)[k] for k in
            ("trainstep_donate", "decode_donate", "shape_bucketing",
             "shape_bucket_min", "flash_attention_min_seqlen",
             "flash_block_q", "flash_block_k")}
    try:
        yield
    finally:
        pt.set_flags({k: v for k, v in keep.items()})


# ------------------------------------------------------- persistent cache


def test_persistent_cache_created_and_reused_across_to_static(tmp_cache):
    """Tier-1-safe smoke: the cache dir is created at initialize and a
    second in-process to_static of the same computation warm-starts from
    disk (cache-hit counter > 0, warm wall time below cold)."""
    assert os.path.isdir(tmp_cache)
    cc.reset_stats()

    def make():
        @jit.to_static
        def heavy(x):
            for _ in range(40):
                x = pt.tanh(pt.matmul(x, x))
            return x
        return heavy

    x = Tensor(np.eye(64, dtype=np.float32) * 0.1)
    t0 = time.perf_counter()
    r1 = make()(x)
    cold = time.perf_counter() - t0
    t0 = time.perf_counter()
    r2 = make()(x)
    warm = time.perf_counter() - t0

    s = cc.stats()
    assert s.get("persistent.hits", 0) > 0, s
    assert s.get("persistent.files", 0) > 0
    assert any(n.endswith("-cache") for n in os.listdir(tmp_cache))
    # the warm build skips the backend compile entirely; on CPU that is a
    # >10x gap, so a plain < comparison is stable
    assert warm < cold, (cold, warm)
    np.testing.assert_allclose(np.asarray(r1._data), np.asarray(r2._data))


def test_initialize_idempotent_and_clear(tmp_cache):
    assert cc.initialize() == tmp_cache  # already initialized: no-op
    # put at least one entry in, then clear only removes cache files
    @jit.to_static
    def f(x):
        return pt.matmul(x, x)

    f(Tensor(np.eye(16, dtype=np.float32)))
    removed = cc.clear(tmp_cache)
    assert removed >= 1
    assert os.path.isdir(tmp_cache)  # dir itself survives


@pytest.fixture
def restore_cache_dir(monkeypatch):
    """Re-point the cache where it was once the test is over. Depends on
    ``monkeypatch`` so that this teardown runs AFTER a test's own
    ``monkeypatch.setenv`` — but it must not trust the order: the variable
    is dropped here before the cache is re-pointed."""
    prev = cc.cache_dir()
    yield
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    cc.initialize(cache_dir=prev, force=True)
    assert cc.cache_dir() == prev


def test_env_var_places_the_cache_and_nothing_repoints_it(
        tmp_path, monkeypatch, restore_cache_dir):
    """JAX_COMPILATION_CACHE_DIR set -> the cache is there: neither the
    explicit argument nor a plain re-initialize moves it."""
    import jax

    env_dir, other = str(tmp_path / "from_env"), str(tmp_path / "other")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    assert cc.resolve_cache_dir() == env_dir
    assert cc.resolve_cache_dir(other) == env_dir
    assert cc.initialize(cache_dir=other, force=True) == env_dir
    assert cc.initialize(force=True) == env_dir
    assert cc.cache_dir() == env_dir
    assert jax.config.jax_compilation_cache_dir == env_dir
    assert os.path.isdir(env_dir) and not os.path.exists(other)


def test_default_cache_dir_is_fixed_inside_the_checkout(monkeypatch):
    """Unset -> one fixed directory in the checkout, the same from two
    fresh interpreters: never under the home directory, a temp name, a pid
    or a time (the path is part of the cache key)."""
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(cc.__file__)))
    root = os.path.dirname(root)
    want = os.path.join(root, ".jax_cache")
    assert cc.default_cache_dir() == want
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    env.update(JAX_PLATFORMS="cpu", PYTHONPATH=root)
    code = ("import jax, paddle_tpu;"
            "from paddle_tpu.core import compile_cache as cc;"
            "print(cc.cache_dir());"
            "print(jax.config.jax_compilation_cache_dir)")
    seen = [subprocess.run([sys.executable, "-c", code], env=env, cwd=cwd,
                           capture_output=True, text=True, timeout=300,
                           check=True).stdout.split()
            for cwd in (root, os.path.join(root, "tests"))]
    assert seen == [[want, want], [want, want]]
    assert not want.startswith(os.path.expanduser("~") + os.sep + ".cache")


def test_cache_stats_tool_reads_the_one_rule(tmp_path, monkeypatch):
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "cache_stats.py")
    spec = importlib.util.spec_from_file_location("cache_stats_tool", path)
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    args = type("A", (), {"dir": None})()
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert tool._resolve_dir(args) == cc.default_cache_dir()
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert tool._resolve_dir(args) == str(tmp_path)


def test_eager_jit_counters():
    cc.reset_stats()
    a = pt.to_tensor(np.full((3, 3), 2.0, np.float32))
    _ = a * a  # may miss or hit depending on what ran before
    _ = a * a  # same op+shapes again: must hit
    s = cc.stats()
    assert s.get("eager_jit.hits", 0) >= 1
    assert s.get("eager_jit.entries", 0) >= 0


def test_to_static_warm_counter_increments():
    cc.reset_stats()

    @jit.to_static
    def f(x):
        return x + 1.0

    x = Tensor(np.zeros((2, 4), np.float32))
    f(x)
    f(x)
    s = cc.stats()
    assert s.get("to_static.misses", 0) == 1
    assert s.get("to_static.hits", 0) == 1


def test_memory_stats_surfaces_compile_cache_providers():
    from paddle_tpu.core import memory_stats

    stats = memory_stats.memory_stats()
    assert "provider.compile_cache.persistent_hits" in stats
    assert "provider.compile_cache.eager_jit_hits" in stats


def test_profiler_snapshots_compile_cache_delta():
    from paddle_tpu import profiler

    prof = profiler.Profiler()
    prof.start()

    @jit.to_static
    def f(x):
        return x * 3.0

    f(Tensor(np.ones((2, 2), np.float32)))
    prof.stop()
    assert prof.compile_cache_stats.get("to_static.misses", 0) >= 1


# ---------------------------------------------------------- shape bucketing


def test_bucket_dim_policy():
    assert [cc.bucket_dim(n) for n in (1, 8, 9, 12, 13, 17, 25, 33)] == \
        [8, 8, 12, 12, 16, 24, 32, 48]
    for n in range(1, 300):
        b = cc.bucket_dim(n)
        assert b >= n
        # padding waste bounded: bucket < 1.5x for n above the floor
        if n > 8:
            assert b < 1.5 * n
    assert cc.bucket_shape((13, 7), axes=(0,)) == (16, 7)


def test_bucketing_two_batches_one_compile(restore_flags):
    cc.reset_stats()

    @jit.to_static(bucket_batch=True)
    def f(x):
        return x * 2.0 + 1.0

    x3 = np.random.default_rng(0).normal(size=(3, 5)).astype(np.float32)
    x7 = np.random.default_rng(1).normal(size=(7, 5)).astype(np.float32)
    o3 = f(Tensor(x3))
    o7 = f(Tensor(x7))
    s = cc.stats()
    # both batch sizes land in the 8-bucket: ONE cold signature, one hit
    assert s.get("to_static.misses", 0) == 1, s
    assert s.get("to_static.hits", 0) == 1, s
    assert s.get("bucket.padded", 0) == 2
    # outputs are sliced back to the true batch and numerically untouched
    assert tuple(o3.shape) == (3, 5) and tuple(o7.shape) == (7, 5)
    np.testing.assert_allclose(np.asarray(o3._data), x3 * 2.0 + 1.0,
                               rtol=1e-6)
    np.testing.assert_allclose(np.asarray(o7._data), x7 * 2.0 + 1.0,
                               rtol=1e-6)


def test_bucketing_global_flag_and_opt_out(restore_flags):
    pt.set_flags({"FLAGS_shape_bucketing": True})
    cc.reset_stats()

    @jit.to_static  # follows the global flag
    def f(x):
        return x - 1.0

    @jit.to_static(bucket_batch=False)  # explicit opt-out wins
    def g(x):
        return x - 1.0

    f(Tensor(np.ones((3, 2), np.float32)))
    f(Tensor(np.ones((5, 2), np.float32)))
    assert cc.stats().get("to_static.misses", 0) == 1
    cc.reset_stats()
    g(Tensor(np.ones((3, 2), np.float32)))
    g(Tensor(np.ones((5, 2), np.float32)))
    assert cc.stats().get("to_static.misses", 0) == 2  # no bucketing


def test_bucketing_never_applies_to_training_path(restore_flags):
    """Padded rows must not enter batch reductions: the live (taped) path
    ignores bucket_batch and gradients match the eager computation."""
    from paddle_tpu import nn

    lin = nn.Linear(4, 2)

    @jit.to_static(bucket_batch=True)
    def loss_fn(x):
        return (lin(x) ** 2).mean()

    x = Tensor(np.random.default_rng(0).normal(size=(3, 4)).astype(
        np.float32), stop_gradient=False)
    loss = loss_fn(x)
    loss.backward()
    g_static = np.asarray(lin.weight.grad._data).copy()

    lin.clear_gradients()
    loss_e = (lin(x) ** 2).mean()
    loss_e.backward()
    np.testing.assert_allclose(g_static, np.asarray(lin.weight.grad._data),
                               rtol=1e-5, atol=1e-6)


# ------------------------------------------------------------ buffer donation


def test_trainstep_donation_loss_identical_and_memory_no_worse(restore_flags):
    from paddle_tpu import nn
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import AdamW

    x = Tensor(np.random.default_rng(0).normal(size=(4, 8)).astype(
        np.float32))
    y = Tensor(np.zeros((4, 4), np.float32))

    def run(donate):
        pt.set_flags({"FLAGS_trainstep_donate": donate})
        pt.seed(0)
        m = nn.Linear(8, 4)
        opt = AdamW(learning_rate=1e-2, parameters=m.parameters())

        def loss_fn(xi, yi):
            return ((m(xi) - yi) ** 2).mean()

        step = TrainStep(loss_fn, opt, layers=m)
        losses = [float(step(x, y)) for _ in range(3)]
        return losses, [np.asarray(p._data).copy() for p in m.parameters()]

    from paddle_tpu.core import memory_stats

    l_on, p_on = run(True)
    peak_on = memory_stats.memory_stats().get("device.Allocated.peak")
    l_off, p_off = run(False)
    peak_off = memory_stats.memory_stats().get("device.Allocated.peak")
    assert l_on == l_off, (l_on, l_off)  # bit-identical trajectories
    for a, b in zip(p_on, p_off):
        assert (a == b).all()
    if peak_on is not None and peak_off is not None:
        # PJRT peak is a lifetime high-water mark; donation ran FIRST, so
        # its peak can only be <= the later copying run's
        assert peak_on <= peak_off


def test_generate_donation_output_identical(restore_flags):
    from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny

    cfg = gpt_tiny()
    pt.seed(0)
    model = GPTForCausalLM(cfg)
    model.eval()
    ids = Tensor((np.arange(2 * 8, dtype=np.int32).reshape(2, 8)
                  % cfg.vocab_size))

    pt.set_flags({"FLAGS_decode_donate": True})
    out_don = model.generate(ids, max_new_tokens=4)
    out_don2 = model.generate(ids, max_new_tokens=4)  # cached-runner path

    # toggling the flag is part of generate's executable cache key: the
    # copying build is constructed fresh, not served from the donating one
    pt.set_flags({"FLAGS_decode_donate": False})
    out_copy = model.generate(ids, max_new_tokens=4)

    a, b, c = (np.asarray(t._data) for t in (out_don, out_don2, out_copy))
    assert a.shape == (2, 12)
    assert (a == b).all() and (a == c).all()


def test_executor_state_dict_valid_after_donating_train_step():
    """The static Executor donates its optimizer state; the inner
    optimizer's accumulators must be re-pointed at the live slots or a
    post-restore state_dict would read donated (invalidated) arrays."""
    from paddle_tpu import nn, optimizer, static

    lin = nn.Linear(4, 1)
    main = static.Program()
    with static.program_guard(main):
        x = static.data("x", [None, 4], "float32")
        loss = (lin(x) ** 2).mean()
        opt = optimizer.Adam(learning_rate=0.01,
                             parameters=lin.parameters())
        opt.minimize(loss)
    exe = static.Executor()
    X = np.random.default_rng(0).normal(size=(8, 4)).astype(np.float32)
    exe.run(main, feed={"x": X}, fetch_list=[loss])
    exe.run(main, feed={"x": X}, fetch_list=[loss])
    # every accumulator must be a readable, live array
    sd = opt.state_dict()
    for k, v in sd.items():
        np.asarray(v._data if isinstance(v, Tensor) else v)


# ------------------------------------------- satellite regression: ADVICE.md


def test_flash_auto_threshold_gated_on_tuned_adoption(restore_flags,
                                                      monkeypatch):
    import jax

    from paddle_tpu.nn.functional import attention
    from paddle_tpu.ops import pallas_ops

    kind = jax.devices()[0].device_kind
    # tiles measured for this chip "exist"
    monkeypatch.setattr(pallas_ops, "_TUNED_BLOCKS",
                        {kind: {1024: (256, 512)}})
    pt.set_flags({"FLAGS_flash_attention_min_seqlen": -1,
                  "FLAGS_flash_block_q": 128,
                  "FLAGS_flash_block_k": 128})
    # they will be adopted -> aggressive 1024 threshold
    assert attention._effective_min_seqlen(2048) == 1024
    # custom blocks bypass adoption: the kernel that would run is the
    # 128-tile one (0.64-0.80x of XLA at 1k-4.6k)
    pt.set_flags({"FLAGS_flash_block_q": 256})
    assert attention._effective_min_seqlen(2048) == 4608
    # an explicit flag value always wins
    pt.set_flags({"FLAGS_flash_attention_min_seqlen": 2000,
                  "FLAGS_flash_block_q": 128})
    assert attention._effective_min_seqlen(2048) == 2000
    # nothing measured for this chip -> conservative threshold
    pt.set_flags({"FLAGS_flash_attention_min_seqlen": -1})
    monkeypatch.setattr(pallas_ops, "_TUNED_BLOCKS", {})
    assert attention._effective_min_seqlen(2048) == 4608


def test_native_predictor_empty_options_bypasses_env(monkeypatch):
    from paddle_tpu.native import pdnative

    class FakeLib:
        def __init__(self):
            self.calls = []

        def pt_infer_create_with_options(self, plugin, art, opts):
            self.calls.append(("with_options", bytes(opts)))
            return 1

        def pt_infer_create(self, plugin, art):
            self.calls.append(("plain", None))
            return 1

        def pt_infer_input_count(self, h):
            return 0

        def pt_infer_output_count(self, h):
            return 0

        def pt_infer_destroy(self, h):
            pass

        def pt_infer_last_error(self):
            return b""

    fake = FakeLib()
    monkeypatch.setattr(pdnative, "_lib", lambda: fake)
    monkeypatch.setenv("PADDLE_TPU_PJRT_CREATE_OPTIONS", "evil=s:injected")

    # explicit {} => with_options with an EMPTY string: zero NamedValues,
    # env fallback suppressed
    p = pdnative.NativePredictor("art.pdnative", plugin_path="fake.so",
                                 create_options={})
    assert fake.calls[-1] == ("with_options", b"")
    p.close()
    # None => legacy entry point (env fallback intentionally active)
    p = pdnative.NativePredictor("art.pdnative", plugin_path="fake.so",
                                 create_options=None)
    assert fake.calls[-1] == ("plain", None)
    p.close()
    # non-empty dict serializes type-tagged
    p = pdnative.NativePredictor("art.pdnative", plugin_path="fake.so",
                                 create_options={"a": 1, "b": "x"})
    kind, opts = fake.calls[-1]
    assert kind == "with_options"
    assert set(opts.split(b";")) == {b"a=i:1", b"b=s:x"}
    p.close()


def test_recompute_policy_is_keyword_only_not_swallowed():
    import inspect

    from paddle_tpu.distributed.fleet.recompute import recompute

    sig = inspect.signature(recompute)
    assert sig.parameters["policy"].kind is inspect.Parameter.KEYWORD_ONLY
    assert sig.parameters["policy"].default == "full"

    # a wrapped function's own `policy` kwarg travels via functools.partial
    # (the documented idiom); other kwargs are forwarded untouched
    seen = {}

    def fn(x, *, policy="inner-default", extra=0):
        seen["policy"] = policy
        seen["extra"] = extra
        return x * 2.0

    t = Tensor(np.ones((2, 2), np.float32))
    recompute(functools.partial(fn, policy="mine"), t, extra=7)
    assert seen == {"policy": "mine", "extra": 7}

    # recompute's own policy parameter still validates
    with pytest.raises(ValueError, match="unknown recompute policy"):
        recompute(fn, t, policy="not-a-policy")
    out = recompute(fn, t, policy="core_attn")  # valid name resolves
    assert np.asarray(out._data).shape == (2, 2)


# ---------------------------------------------------------------- tools CLI


def test_cache_stats_cli_inspect(tmp_cache, capsys):
    import importlib.util

    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "cache_stats.py")
    spec = importlib.util.spec_from_file_location("cache_stats", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)

    @jit.to_static
    def f(x):
        return pt.matmul(x, x) + x

    f(Tensor(np.eye(32, dtype=np.float32)))
    assert mod.main(["--dir", tmp_cache, "--json"]) == 0
    import json

    rep = json.loads(capsys.readouterr().out.strip())
    assert rep["exists"] is True
    assert rep["entries"] >= 1
    assert rep["bytes"] > 0
