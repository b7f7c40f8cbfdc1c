"""Compiled execution (paddle.jit equivalent).

The reference gets graphs from dygraph via AST rewriting (``@to_static``,
ref:python/paddle/jit/api.py:232 + dy2static transformers) and runs them on
StandaloneExecutor. TPU-native replacement: *trace* the same Python with JAX —
Tensor ops run on tracers, the whole function becomes one XLA program. Python
control flow is evaluated at trace time (use lax.cond/scan via paddle_tpu ops
for data-dependent flow); no AST surgery, no separate executor.

Key pieces:
  * ``functional_call(layer, state, args)`` — run a Layer with swapped
    parameter arrays (the lifting trick that makes Layers pure).
  * ``@to_static`` — jit a function/Layer forward; buffer mutations
    (BatchNorm stats) are captured via the mutation sink and applied after.
  * ``TrainStep`` — whole-training-step compilation: loss, grads, optimizer
    update in ONE XLA program (what the bench uses; ~KernelFusion of the
    reference's separate op launches).
"""
from __future__ import annotations

import contextlib
import functools
import threading
from typing import Callable, Dict, List, Optional

import jax
import jax.numpy as jnp

from ..core import compile_cache, flags, resilience, rng
from ..core.tensor import Tensor
from ..nn.layer import Layer, mutation_sink


# _swap_data mutates shared Tensor objects in place, so concurrent swapped
# regions over the SAME module corrupt each other: two gateway replicas
# cold-starting their prefill buckets in background threads would read the
# other trace's tracers out of the shared params (UnexpectedTracerError at
# best, silently-baked wrong constants at worst). One process-wide re-entrant
# lock serializes the whole swapped region; in the serving path it is only
# ever taken at trace time (compiled bodies run as XLA programs, not
# Python), so steady-state decode never contends on it.
_SWAP_LOCK = threading.RLock()


@contextlib.contextmanager
def _swap_data(tensors: List[Tensor], arrays):
    with _SWAP_LOCK:
        old = [t._data for t in tensors]
        for t, a in zip(tensors, arrays):
            t._data = a
        try:
            yield
        finally:
            for t, o in zip(tensors, old):
                t._data = o


def functional_call(layer: Layer, params_and_buffers: Dict[str, object], *args, **kwargs):
    """Run ``layer(*args)`` with parameter/buffer values taken from the dict
    (name -> Tensor/array). Pure w.r.t. the provided values; jit/grad-safe."""
    params, buffers = layer.functional_state()
    objs, vals = [], []
    for name, t in list(params.items()) + list(buffers.items()):
        if name in params_and_buffers:
            v = params_and_buffers[name]
            objs.append(t)
            vals.append(v._data if isinstance(v, Tensor) else v)
    with _swap_data(objs, vals):
        return layer(*args, **kwargs)



def scan_layers(layers, x: Tensor, *extra, remat=False) -> Tensor:
    """Apply a homogeneous LayerList as ``lax.scan(block, x, stacked_params)``.

    The block compiles once instead of ``len(layers)`` inlined copies, so
    XLA compile time stops growing with depth (the deep-model compile-time
    lever; see GPTConfig.use_scan_layers). Per-layer param tracers are
    stacked along a new leading axis inside the trace — gradients flow back
    through the stack to each layer's own parameters, leaving optimizers,
    checkpoints, and state_dict untouched. ``extra`` are closure constants
    shared by every block invocation (e.g. an attention mask). With
    ``remat`` the body is rematerialized — ``True`` for the save-nothing
    policy (matching fleet.recompute's default) or a policy name from
    fleet.recompute._POLICIES (e.g. ``"core_attn"`` saves weight-matmul
    outputs and recomputes only attention scores/softmax — far cheaper
    recompute at slightly more memory). Blocks must be structurally
    identical and buffer-free
    (a buffer mutated inside the scan body would be silently dropped)."""
    import jax
    import jax.numpy as jnp

    tmpl = layers[0]
    p0, b0 = tmpl.functional_state()
    if b0:
        raise NotImplementedError("scan_layers requires buffer-free blocks")
    names = list(p0.keys())
    cols = []
    for layer in layers:
        p, _ = layer.functional_state()
        cols.append([p[n]._data for n in names])
    stacked = [jnp.stack([c[i] for c in cols]) for i in range(len(names))]

    def body(carry, sl):
        out = functional_call(tmpl, dict(zip(names, sl)), Tensor(carry),
                              *extra)
        return out._data, None

    if remat:
        from ..distributed.fleet.recompute import resolve_policy

        body = jax.checkpoint(body, policy=resolve_policy(
            remat if isinstance(remat, str) else "full"))
    y, _ = jax.lax.scan(body, x._data, stacked)
    return Tensor(y)


def scan_layers_wanted(model, *, traced: bool, training: bool,
                       dropout_ps) -> bool:
    """Shared gate for the models' ``use_scan_layers`` flags: scan only
    under a trace, and never while training with live dropout — one traced
    block would reuse a single dropout mask for every layer. Warns once per
    model instance when it has to fall back (the caller asked for the
    compile-time lever and silently losing it would reproduce the exact
    compile-window timeout the flag exists to avoid)."""
    if not traced:
        return False
    if training and any(float(p) > 0.0 for p in dropout_ps):
        if not getattr(model, "_warned_scan_dropout", False):
            model._warned_scan_dropout = True
            import warnings

            warnings.warn(
                f"use_scan_layers is disabled while training with "
                f"dropout={tuple(dropout_ps)}: the scanned block would "
                "reuse one dropout mask for all layers. Falling back to "
                "the unrolled stack (compile time grows with depth).")
        return False
    return True


def _amp_key(st):
    """Hashable identity of an autocast policy (None = no autocast)."""
    if st is None:
        return None
    return (st["level"], str(st["dtype"]), frozenset(st["white"]),
            frozenset(st["black"]))


def _write_back_buffer(b, new_data):
    """Buffer writeback that survives NESTING: inside an enclosing trace
    (outer @to_static / TrainStep), the update goes to the ambient sink —
    the outer program carries it out. One shared rule (nn.layer
    sink_or_assign) for Layer.update_buffer and compiled-call writebacks."""
    from ..nn.layer import sink_or_assign

    sink_or_assign(b, new_data)


class StaticFunction:
    """Result of @to_static: a compile-cached callable (≈ ref StaticFunction,
    ref:python/paddle/jit/dy2static/program_translator.py).

    ``bucket_batch`` pads the shared leading (batch) dim of array inputs up
    to a power-of-two-ish bucket (core.compile_cache.bucket_dim) on the
    inference path and slices outputs back, so serving-style callers with
    variable batch sizes reuse one executable per bucket instead of one per
    size. None (default) follows FLAGS_shape_bucketing. Training (taped)
    calls are never bucketed — padded rows would enter batch reductions."""

    def __init__(self, function: Callable, layer: Optional[Layer] = None,
                 bucket_batch: Optional[bool] = None):
        self._fn = function
        self._layer = layer
        self._jit_fn = None
        self._jit_fns = {}
        self._param_objs: List[Tensor] = []
        self._buffer_objs: List[Tensor] = []
        self._bucket_batch = bucket_batch
        self._seen_sigs = set()
        functools.update_wrapper(self, function, updated=[])

    def _discover_state(self):
        if getattr(self, "_discovering", False):
            return  # self/mutual recursion: params are being collected by
            # the in-flight discovery already
        self._discovering = True
        try:
            self._discover_state_inner()
        finally:
            # exception-safe: a failure mid-discovery must not leave the
            # guard set, or every later call would silently skip discovery
            # and bake params as constants
            self._discovering = False

    def _discover_state_inner(self):
        layers = []
        inner_fns = []
        layer = self._layer
        if layer is None and hasattr(self._fn, "__self__") and isinstance(self._fn.__self__, Layer):
            layer = self._fn.__self__
        if layer is not None:
            layers = [layer]
        else:
            # free function closing over model objects (the common "build
            # the layers, decorate a train/eval fn" pattern): collect
            # Layers from the closure cells, else their parameters would
            # bake into the compiled program as constants — inference
            # would silently use stale weights after an update and
            # training grads would silently never reach them
            candidates = []
            for cell in getattr(self._fn, "__closure__", None) or ():
                try:
                    candidates.append(cell.cell_contents)
                except ValueError:  # empty cell
                    continue
            # module-scope models are reached through __globals__; ONLY
            # names loaded via LOAD_GLOBAL — co_names also lists attribute
            # names, and an unrelated global Layer colliding with an
            # attribute name would be silently captured (spurious zero
            # grads + buffer writebacks on the taped path)
            code = getattr(self._fn, "__code__", None)
            gl = getattr(self._fn, "__globals__", None)
            if code is not None and gl is not None:
                import dis

                gnames = {i.argval for i in dis.get_instructions(code)
                          if i.opname == "LOAD_GLOBAL"}
                for name in gnames:
                    if name in gl:
                        candidates.append(gl[name])
            for v in candidates:
                if isinstance(v, Layer):
                    layers.append(v)
                elif isinstance(v, StaticFunction):
                    # nested @to_static: the inner function's state must be
                    # OUR state too — otherwise its params bake into our
                    # trace as constants (stale weights, no grads)
                    inner_fns.append(v)
                elif isinstance(v, (list, tuple)):
                    layers.extend(x for x in v if isinstance(x, Layer))
                    inner_fns.extend(x for x in v
                                     if isinstance(x, StaticFunction))
        params, buffers, seen = [], [], set()

        def _take(ps, bs):
            for t in ps:
                if id(t) not in seen:
                    seen.add(id(t))
                    params.append(t)
            for t in bs:
                if id(t) not in seen:
                    seen.add(id(t))
                    buffers.append(t)

        for l in layers:
            p, b = l.functional_state()
            _take(p.values(), b.values())
        for f in inner_fns:
            if f is not self and not getattr(f, "_discovering", False):
                if not f._param_objs and not f._buffer_objs:
                    f._discover_state()
                _take(f._param_objs, f._buffer_objs)
        self._param_objs = params
        self._buffer_objs = buffers

    def _build(self):
        self._discover_state()
        fn = self._fn
        param_objs = self._param_objs
        buffer_objs = self._buffer_objs
        from .. import amp as _amp_mod

        # ONE compiled function PER autocast policy: jax.jit keys only on
        # shapes, so the policy active at first trace would otherwise be
        # silently baked in and reused under a different (or no) policy
        amp_st = _amp_mod.amp_state()
        amp_snap = None if amp_st is None else dict(amp_st)

        @jax.jit
        def _compiled(param_arrays, buffer_arrays, key, args, kwargs):
            sink = {}
            with _swap_data(param_objs + buffer_objs, list(param_arrays) + list(buffer_arrays)):
                with _amp_mod._with_state(amp_snap), \
                        rng.key_guard(key), mutation_sink(sink):
                    out = fn(*args, **kwargs)
            mutated = []
            for b in buffer_objs:
                hit = sink.get(id(b))
                mutated.append(hit[1] if hit is not None else None)
            return out, mutated

        self._jit_fns[_amp_key(amp_st)] = _compiled
        self._jit_fn = _compiled  # newest policy's executable (compat)

    def __call__(self, *args, **kwargs):
        if not _to_static_enabled:
            return self._fn(*args, **kwargs)  # eager fallback (debugging)
        from .. import amp as _amp_mod

        if not hasattr(self, "_jit_fns"):
            self._jit_fns = {}
        if _amp_key(_amp_mod.amp_state()) not in self._jit_fns:
            self._build()
        # TRAINING path: when gradients can flow (a live input arg or live
        # parameter, grads enabled), the compiled function must join the
        # autograd tape — the reference's core dy2static pattern is
        # `@to_static` forward + eager loss.backward(), and a silently
        # detached output would zero every gradient.
        from ..core.autograd import is_grad_enabled

        leaves, treedef = jax.tree_util.tree_flatten(
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
        live = is_grad_enabled() and (
            any(isinstance(l, Tensor) and not l.stop_gradient
                for l in leaves)
            or any(not p.stop_gradient for p in self._param_objs))
        if live:
            compile_cache.bump("to_static.taped_calls")
            return self._call_taped(args, kwargs)
        bucket = (self._bucket_batch if getattr(self, "_bucket_batch", None)
                  is not None else flags.flag("shape_bucketing"))
        orig_b = padded_b = None
        if bucket:
            leaves, orig_b, padded_b = self._pad_leaves(leaves)
            if orig_b is not None:
                args, kwargs = jax.tree_util.tree_unflatten(treedef, leaves)
        self._count_signature(leaves)
        param_arrays = tuple(p._data for p in self._param_objs)
        buffer_arrays = tuple(b._data for b in self._buffer_objs)
        jit_fn = self._jit_fns[_amp_key(_amp_mod.amp_state())]
        out, mutated = jit_fn(param_arrays, buffer_arrays, rng.next_key(), args, kwargs)
        for b, m in zip(self._buffer_objs, mutated):
            if m is not None:
                if orig_b is not None and not getattr(
                        self, "_warned_bucket_buffers", False):
                    self._warned_bucket_buffers = True
                    import warnings

                    warnings.warn(
                        "bucket_batch: a buffer mutation (e.g. BatchNorm "
                        "running stats) was computed over a zero-padded "
                        "batch — the written-back statistics include the "
                        "padding rows. Disable bucketing for functions "
                        "that update batch statistics.")
                _write_back_buffer(b, m)
        if orig_b is not None:
            out = _slice_batch(out, padded_b, orig_b)
        return out

    def _pad_leaves(self, leaves):
        """Pad the shared leading dim of array input leaves up to its
        bucket. Returns (leaves, orig_b, padded_b); orig_b None = no
        padding (no array leaves, ambiguous leading dims, or already
        on-bucket) — the caller only re-unflattens when padding happened."""
        import numpy as _np

        def _arr(l):
            return (isinstance(l, (Tensor, jax.Array, _np.ndarray))
                    and getattr(l._data if isinstance(l, Tensor) else l,
                                "ndim", 0) >= 1)

        dims = {(l._data if isinstance(l, Tensor) else l).shape[0]
                for l in leaves if _arr(l)}
        if len(dims) != 1:
            if dims:
                compile_cache.bump("bucket.skipped_ambiguous")
            return leaves, None, None
        b = dims.pop()
        pb = compile_cache.bucket_dim(b)
        if pb == b:
            return leaves, None, None
        leaves = [compile_cache.pad_to_bucket(l)[0] if _arr(l) else l
                  for l in leaves]
        return leaves, b, pb

    def _count_signature(self, leaves):
        """Cold/warm counters per (shapes, dtypes, amp) call signature —
        mirrors what jax.jit's executable cache keys on, so the second call
        with the same (post-bucketing) shapes records a hit. Works on the
        already-flattened leaves: no extra tree walk on the hot path."""
        import numpy as _np

        from .. import amp as _amp_mod

        parts = []
        for l in leaves:
            a = l._data if isinstance(l, Tensor) else l
            if isinstance(a, (jax.Array, _np.ndarray)):
                parts.append((a.shape, str(a.dtype)))
            else:
                parts.append((type(l).__name__,))
        try:
            sig = (tuple(parts), _amp_key(_amp_mod.amp_state()))
            hash(sig)
        except TypeError:
            return
        seen = getattr(self, "_seen_sigs", None)
        if seen is None:
            seen = self._seen_sigs = set()
        if sig in seen:
            compile_cache.bump("to_static.hits")
        else:
            seen.add(sig)
            compile_cache.bump("to_static.misses")

    def _call_taped(self, args, kwargs):
        """Record the whole compiled function as ONE tape op via
        dispatch.apply: jax.vjp differentiates through it, so loss
        .backward() after a @to_static call reaches input Tensors AND the
        layer's parameters. Buffers (BN stats) ride as extra outputs and
        are written back. The pure wrapper is cached per call-structure so
        the jit cache stays stable across training steps."""
        from ..core.dispatch import apply

        import numpy as _np

        leaves, treedef = jax.tree_util.tree_flatten(
            (args, kwargs), is_leaf=lambda x: isinstance(x, Tensor))
        n_leaves = len(leaves)
        # floats/arrays ride as TRACED args (a per-step python lr must not
        # mint a new executable per value — matching jax.jit's own leaf
        # handling on the fast path); ints/bools/strings stay static keys
        # (axis/flag arguments)
        def _traced(l):
            return isinstance(l, (Tensor, jax.Array, _np.ndarray)) or (
                isinstance(l, float) and not isinstance(l, bool))

        t_idx = tuple(i for i, l in enumerate(leaves) if _traced(l))
        raw_idx = frozenset(i for i in t_idx
                            if not isinstance(leaves[i], Tensor))
        others = tuple((i, l) for i, l in enumerate(leaves)
                       if not _traced(l))
        from .. import amp as _amp_mod

        amp_st = _amp_mod.amp_state()
        try:
            key = (treedef, t_idx, raw_idx, others, _amp_key(amp_st))
            hash(key)
        except TypeError:
            # an unhashable static leaf would defeat every cache below it
            # (a fresh wrapper per call retraces AND leaks one executable
            # per training step into the jit cache) — run the plain eager
            # tape instead: correct, uncompiled, leak-free
            return self._fn(*args, **kwargs)
        cache = getattr(self, "_taped_cache", None)
        if cache is None:
            cache = self._taped_cache = {}
        entry = cache.get(key)
        if entry is None:
            fn = self._fn
            amp_snap = None if amp_st is None else dict(amp_st)
            param_objs, buffer_objs = self._param_objs, self._buffer_objs
            n_args = len(t_idx)
            n_state = len(param_objs) + len(buffer_objs)
            out_spec = {}  # filled at first trace: output pytree structure

            def pure(rng_key, *arrs):
                rebuilt = [None] * n_leaves
                for i, v in others:
                    rebuilt[i] = v
                for j, i in enumerate(t_idx):
                    # raw numeric leaves come back as raw arrays, Tensor
                    # leaves as Tensors — what fn's body saw originally
                    rebuilt[i] = (arrs[j] if i in raw_idx
                                  else Tensor(arrs[j]))
                a2, k2 = jax.tree_util.tree_unflatten(treedef, rebuilt)
                sink = {}
                state = list(param_objs) + list(buffer_objs)
                with _swap_data(state, list(arrs[n_args:n_args + n_state])):
                    # the SNAPSHOTTED autocast policy, not the ambient one:
                    # backward re-executes this fn after the user's context
                    # exited, and a policy change would silently change the
                    # math (vjp rejects the resulting dtype mismatch)
                    with _amp_mod._with_state(amp_snap), \
                            rng.key_guard(rng_key), mutation_sink(sink):
                        out = fn(*a2, **k2)
                # preserve ARBITRARY output pytrees (dicts, nesting, bare
                # tensors) — the taped path must return exactly what the
                # fast path returns. Anything ARRAY-VALUED (Tensor, raw
                # jax array — a tracer during this trace!) must flow out
                # through the op outputs; snapshotting it into out_spec
                # would leak the tracer into later cache-hit calls.
                out_leaves, out_treedef = jax.tree_util.tree_flatten(
                    out, is_leaf=lambda x: isinstance(x, Tensor))
                oi = tuple(i for i, l in enumerate(out_leaves)
                           if isinstance(l, (Tensor, jax.Array)))
                out_spec["treedef"] = out_treedef
                out_spec["t_idx"] = oi
                out_spec["others"] = tuple(
                    (i, l) for i, l in enumerate(out_leaves)
                    if not isinstance(l, (Tensor, jax.Array)))
                out_arrs = tuple(
                    out_leaves[i]._data
                    if isinstance(out_leaves[i], Tensor)
                    else out_leaves[i] for i in oi)
                buf_arrs = []
                for b in buffer_objs:
                    hit = sink.get(id(b))
                    buf_arrs.append(hit[1] if hit is not None else b._data)
                return out_arrs + tuple(buf_arrs)

            entry = (pure, out_spec)
            cache[key] = entry
        pure, out_spec = entry

        tensor_args = tuple(leaves[i] for i in t_idx)
        res = apply(pure,
                    (Tensor(rng.next_key()),) + tensor_args
                    + tuple(self._param_objs) + tuple(self._buffer_objs),
                    {}, name=getattr(self._fn, "__name__", "to_static"),
                    # the snapshot policy applies PER-OP inside pure; a
                    # boundary cast (fn name colliding with the amp lists,
                    # or O2's cast-everything) would downcast params and
                    # buffers wholesale
                    cast_inputs=False)
        res = res if isinstance(res, tuple) else (res,)
        n_out = len(res) - len(self._buffer_objs)
        for b, nb in zip(self._buffer_objs, res[n_out:]):
            _write_back_buffer(b, nb._data)
        out_leaves = [None] * (len(out_spec["t_idx"])
                               + len(out_spec["others"]))
        for i, v in out_spec["others"]:
            out_leaves[i] = v
        for j, i in enumerate(out_spec["t_idx"]):
            out_leaves[i] = res[j]
        return jax.tree_util.tree_unflatten(out_spec["treedef"], out_leaves)

    @property
    def code(self):
        return "<XLA-compiled via jax.jit>"

    def concrete_program(self):
        return self._jit_fn


def _slice_batch(out, padded_b: int, orig_b: int):
    """Undo bucket padding: slice every array leaf whose leading dim is the
    padded bucket size back to the original batch."""

    def _cut(l):
        a = l._data if isinstance(l, Tensor) else l
        if (isinstance(a, jax.Array) and a.ndim >= 1
                and a.shape[0] == padded_b):
            s = a[:orig_b]
            return Tensor(s, stop_gradient=l.stop_gradient) \
                if isinstance(l, Tensor) else s
        return l

    return jax.tree_util.tree_map(
        _cut, out, is_leaf=lambda x: isinstance(x, Tensor))


def to_static(function=None, input_spec=None, build_strategy=None, backend=None, **kwargs):
    """@paddle.jit.to_static equivalent (trace+XLA instead of AST rewrite).

    ``bucket_batch=True`` opts this function into inference-path shape
    bucketing (see StaticFunction / FLAGS_shape_bucketing); ``False`` opts
    out even when the global flag is on."""
    bucket_batch = kwargs.pop("bucket_batch", None)

    def deco(fn):
        if isinstance(fn, Layer):
            layer = fn
            sf = StaticFunction(layer.forward, layer=layer,
                                bucket_batch=bucket_batch)
            layer.forward = sf
            return layer
        return StaticFunction(fn, bucket_batch=bucket_batch)

    if function is not None:
        return deco(function)
    return deco


class TrainStep:
    """One fully-compiled training step: forward + backward + optimizer.

    Replaces the reference's per-op dygraph hot loop (§3.1 of SURVEY.md) with
    a single XLA program; with sharded inputs this same class is the pjit
    training path.
    """

    def __init__(self, fn: Callable, optimizer, layers=None, extra_state: Optional[List[Tensor]] = None,
                 accumulate_steps: int = 1):
        self._fn = fn
        self._opt = optimizer
        # gradient merge (ref auto_parallel_gradient_merge pass): k>1 scans k
        # microbatches inside ONE compiled program — grads accumulate in f32
        # on-device, the optimizer applies once with the (averaged) total
        self._accumulate_steps = int(accumulate_steps)
        self._accumulate_avg = True
        if isinstance(layers, Layer):
            self._layers_for_amp = layers
        elif isinstance(layers, (list, tuple)):
            ls = [l for l in layers if isinstance(l, Layer)]
            self._layers_for_amp = ls or None
        else:
            self._layers_for_amp = None
        # a fleet gradient_merge wrapper (distributed.passes
        # .GradientMergeOptimizer) can't merge inside a compiled step — its
        # step() is never called. Adopt its k into the compiled scan and
        # drive the inner optimizer directly so the strategy still applies.
        inner = getattr(optimizer, "inner_opt", None)
        if inner is not None and hasattr(optimizer, "_k"):
            if self._accumulate_steps == 1:
                self._accumulate_steps = int(optimizer._k)
                self._accumulate_avg = bool(optimizer._avg)
            optimizer = inner
            self._opt = inner
        plist = optimizer._parameter_list or []
        self._train_params = [p for p in plist if not p.stop_gradient]
        frozen = [p for p in plist if p.stop_gradient]
        buffers: List[Tensor] = list(frozen)
        if layers is not None:
            if isinstance(layers, Layer):
                layers = [layers]
            seen = {id(p) for p in plist}
            for l in layers:
                for _, b in l.named_buffers():
                    if id(b) not in seen:
                        buffers.append(b)
                        seen.add(id(b))
                for _, p in l.named_parameters():
                    if id(p) not in seen:
                        buffers.append(p)
                        seen.add(id(p))
        self._buffers = buffers
        if extra_state:
            self._buffers.extend(extra_state)
        self._opt_state = None
        self._jit_fn = None
        self._sentinel = False  # set at build time from FLAGS_trainstep_sentinel
        self._bad_steps = 0  # consecutive nonfinite steps (sentinel rollback)

    def _loss_with_sink(self, pa, buf_arrays, key, args):
        """value_and_grad target shared by both build paths: swap state in,
        run the loss fn under the rng/mutation guards, return the f32 loss
        and the per-buffer mutation list (None = untouched)."""
        fn, train_params, buffers = self._fn, self._train_params, self._buffers
        sink = {}
        with _swap_data(train_params + buffers, list(pa) + list(buf_arrays)):
            with rng.key_guard(key), mutation_sink(sink):
                loss = fn(*args)
        loss_arr = loss._data if isinstance(loss, Tensor) else loss
        mutated = []
        for b in buffers:
            hit = sink.get(id(b))
            mutated.append(hit[1] if hit is not None else None)
        return loss_arr.astype(jnp.float32), mutated

    def _apply_optimizer(self, param_arrays, grads, opt_state, lr):
        """Clip + per-param update with master-weight dispatch (shared by
        both build paths; runs inside the jitted step)."""
        opt, train_params = self._opt, self._train_params
        if opt._grad_clip is not None:
            grads = opt._grad_clip._clip_arrays(grads)
        step = opt_state["step"] + 1
        new_params, new_slots = [], []
        for p_t, p_arr, g, slots in zip(train_params, param_arrays,
                                        grads, opt_state["slots"]):
            upd = opt._update_for(getattr(p_t, "name", None), p_t)
            np_, ns_ = opt._apply_with_master(upd, p_arr, g, slots, lr, step)
            new_params.append(np_)
            new_slots.append(ns_)
        return new_params, {"slots": new_slots, "step": step}

    @staticmethod
    def _donate_argnums():
        """Donate params + optimizer state (argnums 0 and 2): XLA updates
        them in place — halves the peak HBM of the update; old arrays are
        invalidated, but __call__ rebinds every Tensor._data to the new
        buffers. FLAGS_trainstep_donate=0 (read at build time) keeps the
        copying build for A/B verification."""
        return (0, 2) if flags.flag("trainstep_donate") else ()

    def _guarded_update(self, param_arrays, grads, loss, opt_state, lr):
        """NaN/Inf step sentinel: ONE fused finiteness reduction over
        loss+grads decides between the optimizer update and an identity step
        via ``lax.cond`` — both branches live in the same compiled program,
        so a bad step never recompiles. The skip branch returns params and
        optimizer state unchanged: a nonfinite step leaves training state
        bit-identical to pre-step (and the optimizer step counter does not
        advance); ``__call__`` additionally withholds the step's buffer
        mutations, so BN-style running stats stay clean too. Returns
        ``(new_params, new_state, finite)``."""
        finite = jnp.isfinite(loss)
        for g in grads:
            finite = jnp.logical_and(finite, jnp.all(jnp.isfinite(g)))

        def _apply(_):
            return self._apply_optimizer(param_arrays, grads, opt_state, lr)

        def _skip(_):
            return list(param_arrays), opt_state

        with jax.named_scope("optimizer_update"):
            new_params, new_state = jax.lax.cond(finite, _apply, _skip, None)
        return new_params, new_state, finite

    @staticmethod
    def _commit_to_mesh(tree):
        """Under an installed multi-device mesh, commit what no layer placed
        as replicated over it (``sharding_util.replicate_unplaced``):
        Tensors in place, arrays in the returned pytree. Left uncommitted
        on one device, the first call compiles for one-device inputs, its
        outputs come back on the mesh, and the second call compiles the
        whole step again for those."""
        from ..distributed import mesh as mesh_mod
        from ..distributed.sharding_util import replicate_unplaced

        m = mesh_mod.get_mesh()
        if m is None or m.devices.size <= 1:
            return tree
        return jax.tree_util.tree_map(
            lambda x: replicate_unplaced(x, m), tree,
            is_leaf=lambda x: isinstance(x, Tensor))

    def _build(self):
        compile_cache.bump("train_step.builds")
        self._commit_to_mesh(self._train_params + self._buffers)
        self._sentinel = bool(flags.flag("trainstep_sentinel"))
        if self._accumulate_steps > 1:
            self._build_accum(self._accumulate_steps, self._accumulate_avg)
            return

        if self._sentinel:
            @functools.partial(jax.jit, donate_argnums=self._donate_argnums())
            def _step_sentinel(param_arrays, buffer_arrays, opt_state, lr,
                               key, scale, args):
                def loss_f(pa):
                    loss, mutated = self._loss_with_sink(
                        pa, buffer_arrays, key, args)
                    # scale is 1.0 outside fault injection — a bit-exact
                    # identity; an injected NaN poisons the loss AND (chain
                    # rule) every grad, exercising the full sentinel path
                    return loss * scale, mutated

                (loss, mutated), grads = jax.value_and_grad(
                    loss_f, has_aux=True)(list(param_arrays))
                new_params, new_state, finite = self._guarded_update(
                    param_arrays, grads, loss, opt_state, lr)
                return loss, new_params, new_state, mutated, finite

            self._jit_fn = _step_sentinel
            return

        @functools.partial(jax.jit, donate_argnums=self._donate_argnums())
        def _step(param_arrays, buffer_arrays, opt_state, lr, key, args):
            def loss_f(pa):
                return self._loss_with_sink(pa, buffer_arrays, key, args)

            (loss, mutated), grads = jax.value_and_grad(loss_f, has_aux=True)(list(param_arrays))
            new_params, new_state = self._apply_optimizer(
                param_arrays, grads, opt_state, lr)
            return loss, new_params, new_state, mutated

        self._jit_fn = _step

    def _build_accum(self, k: int, avg: bool):
        """Gradient-merge variant: ONE compiled program scans k microbatches
        (grads evaluated at the step's initial params, accumulated in f32),
        then applies the optimizer once — the TPU-native rewrite of
        ref:python/paddle/distributed/passes/auto_parallel_gradient_merge.py:26
        (accumulate ops + conditional optimizer block become a lax.scan)."""

        def _core(param_arrays, buffer_arrays, key, sentinel_scale, args):
            micro = jax.tree_util.tree_map(
                lambda a: a.reshape((k, a.shape[0] // k) + a.shape[1:]), args)

            def body(carry, margs):
                bufs, acc, lsum, i = carry
                mkey = jax.random.fold_in(key, i)

                def loss_f(pa):
                    loss, mutated = self._loss_with_sink(pa, bufs, mkey, margs)
                    # sentinel_scale is 1.0 outside fault injection (the
                    # non-sentinel build bakes the constant in): bit-exact
                    # identity; an injected NaN poisons loss and grads
                    return loss * sentinel_scale, mutated

                (loss, mutated), grads = jax.value_and_grad(
                    loss_f, has_aux=True)(list(param_arrays))
                # chain buffer mutations (BN stats) across microbatches
                new_bufs = [m if m is not None else b
                            for b, m in zip(bufs, mutated)]
                acc = [a + g.astype(jnp.float32) for a, g in zip(acc, grads)]
                return (new_bufs, acc, lsum + loss, i + 1), None

            acc0 = [jnp.zeros(p.shape, jnp.float32) for p in param_arrays]
            carry0 = (list(buffer_arrays), acc0, jnp.zeros((), jnp.float32),
                      jnp.zeros((), jnp.int32))
            (new_bufs, acc, lsum, _), _ = jax.lax.scan(body, carry0, micro)

            # merged grads stay f32 into the update: _apply_with_master
            # casts per-path (master consumes f32; plain update casts to
            # param dtype) — never round the total through bf16 first
            scale = (1.0 / k) if avg else 1.0
            grads = [a * scale for a in acc]
            # every buffer passed through the scan carry: return them all
            # (loop-invariant ones come back value-equal; __call__ rebinds)
            # reported loss follows the configured semantics: the microbatch
            # MEAN under avg=True, the SUM under avg=False — matching what
            # the gradients were scaled by
            return lsum * scale, grads, new_bufs

        if self._sentinel:
            @functools.partial(jax.jit, donate_argnums=self._donate_argnums())
            def _step_sentinel(param_arrays, buffer_arrays, opt_state, lr,
                               key, scale, args):
                loss, grads, new_bufs = _core(
                    param_arrays, buffer_arrays, key, scale, args)
                new_params, new_state, finite = self._guarded_update(
                    param_arrays, grads, loss, opt_state, lr)
                return loss, new_params, new_state, new_bufs, finite

            self._jit_fn = _step_sentinel
            return

        @functools.partial(jax.jit, donate_argnums=self._donate_argnums())
        def _step(param_arrays, buffer_arrays, opt_state, lr, key, args):
            loss, grads, new_bufs = _core(
                param_arrays, buffer_arrays, key, 1.0, args)
            new_params, new_state = self._apply_optimizer(
                param_arrays, grads, opt_state, lr)
            return loss, new_params, new_state, new_bufs

        self._jit_fn = _step

    def _call_args(self, args, inject_fault: bool = True) -> tuple:
        """The compiled step's full argument tuple for user inputs
        ``args`` at the current training state (builds the step and seeds
        the optimizer state on first use)."""
        if self._jit_fn is None:
            self._build()
        if self._accumulate_steps > 1:
            k = self._accumulate_steps
            # every leaf is split along dim 0, so a non-batch arg whose dim0
            # "happens to divide k" would be silently chunked wrong — demand
            # ONE shared leading batch dim (constants: close over them or
            # tile to the batch)
            leading = set()
            for leaf in jax.tree_util.tree_leaves(args):
                shp = getattr(leaf, "shape", None)
                leading.add(shp[0] if shp else None)
            dim = next(iter(leading)) if len(leading) == 1 else None
            if dim is None or dim % k != 0:
                raise ValueError(
                    f"accumulate_steps={k}: all inputs must share one "
                    f"leading (batch) dim divisible by k; got leading dims "
                    f"{sorted((d if d is not None else -1) for d in leading)}")
        if (self._opt_state is not None
                and getattr(self._opt, "_state_version", 0)
                != getattr(self, "_opt_state_version", 0)):
            # opt.set_state_dict ran after we cached the compiled state
            # (mid-training restore/rollback): drop the stale cache and
            # re-seed from the restored accumulators below
            self._opt_state = None
        if self._opt_state is None:
            self._opt_state_version = getattr(self._opt, "_state_version", 0)
            # seed from the optimizer's accumulators when present (ckpt
            # resume via opt.set_state_dict) — shared overlay semantics
            # live in Optimizer._overlay_slot
            slots = [self._opt._overlay_slot(self._opt._init_slot(p._data), p)
                     for p in self._train_params]
            self._opt_state = self._commit_to_mesh({
                "slots": slots,
                "step": jnp.asarray(self._opt._step_count, jnp.int32),
            })
        param_arrays = tuple(p._data for p in self._train_params)
        buffer_arrays = tuple(b._data for b in self._buffers)
        lr = jnp.asarray(self._opt.get_lr(), jnp.float32)
        head = (param_arrays, buffer_arrays, self._opt_state, lr,
                rng.next_key())
        if not self._sentinel:
            return head + (args,)
        # nonfinite_grads injection rides a runtime scalar (no recompile)
        bad = inject_fault and resilience.maybe_fault("nonfinite_grads")
        return head + (jnp.asarray(float("nan") if bad else 1.0,
                                   jnp.float32), args)

    def lower(self, *args):
        """``jax.stages.Lowered`` of the compiled step for inputs ``args``
        at the current training state — for reading what the step contains
        (``.compile().as_text()``: a ``tpu_custom_call`` when flash
        attention is in it, collectives on a mesh) and what it needs
        (``.compile().memory_analysis()``). Runs nothing; like a call it
        builds the step on first use and draws one key from the framework
        rng."""
        call_args = self._call_args(args, inject_fault=False)
        return self._jit_fn.lower(*call_args)

    def __call__(self, *args):
        call_args = self._call_args(args)
        compile_cache.bump("train_step.steps")
        finite = None
        if self._sentinel:
            loss, new_params, self._opt_state, mutated, finite = \
                self._jit_fn(*call_args)
        else:
            loss, new_params, self._opt_state, mutated = \
                self._jit_fn(*call_args)
        # params/opt state MUST rebind even on a skipped step (donation
        # invalidated the old arrays; the skip branch returned them through)
        for p, np_ in zip(self._train_params, new_params):
            p._data = np_
        finite_b = True if finite is None else bool(finite)
        if finite_b:
            # buffer mutations (BN running stats) were computed during the
            # possibly-poisoned forward: commit them ONLY on finite steps,
            # or a skipped step would still contaminate persistent buffers
            # (buffers are not donated, so the old arrays stay valid)
            for b, m in zip(self._buffers, mutated):
                if m is not None:
                    b._data = m
        # keep the optimizer's own accumulators coherent with the compiled
        # state so opt.state_dict() after TrainStep training is truthful
        # (device arrays are shared by reference — no transfer)
        for p, ns in zip(self._train_params, self._opt_state["slots"]):
            self._opt._accumulators[id(p)] = ns
        self._opt._step_count = int(self._opt_state["step"])
        # a compiled step IS an optimizer step: advance the tensor checker's
        # debug_step window (Optimizer.step does the same on the eager path;
        # without this a TrainStep run would freeze the window at 0)
        mark = getattr(self._opt, "_mark_checker_step", None)
        if mark is not None:
            mark()
        if finite is not None:
            if finite_b:
                self._bad_steps = 0
            else:
                resilience.bump("sentinel.skipped")
                self._bad_steps += 1
                limit = int(flags.flag("max_bad_steps"))
                if limit > 0 and self._bad_steps >= limit:
                    self._bad_steps = 0
                    resilience.trigger_rollback(
                        f"TrainStep: {limit} consecutive nonfinite steps "
                        "(loss/grads)")
        return Tensor(loss)


def grad_and_value(fn: Callable, params: List[Tensor]):
    """Functional helper: returns jitted (loss, grads) over the given params."""

    @jax.jit
    def _gv(param_arrays, key, args):
        def loss_f(pa):
            with _swap_data(params, list(pa)):
                with rng.key_guard(key):
                    loss = fn(*args)
            return (loss._data if isinstance(loss, Tensor) else loss).astype(jnp.float32)

        return jax.value_and_grad(loss_f)(list(param_arrays))

    def run(*args):
        loss, grads = _gv(tuple(p._data for p in params), rng.next_key(), args)
        return Tensor(loss), [Tensor(g) for g in grads]

    return run


class InputSpec:
    """paddle.static.InputSpec parity. Dims of None/-1 are exported as
    jax.export symbolic dimensions, so the saved program stays callable at
    any size for those axes (the reference's dynamic-batch .pdmodel
    contract)."""

    def __init__(self, shape, dtype="float32", name=None):
        self.shape = list(shape)
        self.dtype = dtype
        self.name = name

    def to_sds(self, scope=None, prefix="d"):
        import jax

        from ..core.dtype import convert_dtype_arg

        dtype = jnp.dtype(convert_dtype_arg(self.dtype))
        if any(s is None or s < 0 for s in self.shape):
            from jax import export as jexport

            parts = [f"{prefix}{i}" if s is None or s < 0 else str(int(s))
                     for i, s in enumerate(self.shape)]
            shape = jexport.symbolic_shape(",".join(parts), scope=scope)
        else:
            shape = tuple(int(s) for s in self.shape)
        return jax.ShapeDtypeStruct(tuple(shape), dtype)


def save(layer, path, input_spec=None, **configs):
    """jit.save — deployable export (≈ ref jit.save -> TranslatedLayer,
    ref:python/paddle/jit/api.py).

    Writes:
      path.pdparams  — pickled numpy state dict (paddle contract)
      path.pdmodel   — serialized StableHLO program (jax.export), callable
                       after jit.load WITHOUT the Python model code — the
                       compiled-program deployment story (replaces the
                       reference's Program pbtxt + C++ executor).
    Program export happens when input_spec is given (or the layer was
    to_static-decorated with one).
    """
    import os
    import pickle

    import numpy as np

    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    state = {}
    if isinstance(layer, Layer):
        state = {k: np.asarray(v._data) for k, v in layer.state_dict().items()}
    with open(path + ".pdparams", "wb") as f:
        pickle.dump(state, f, protocol=4)

    if input_spec and isinstance(layer, Layer):
        from jax import export as jexport

        was_training = layer.training
        layer.eval()
        try:
            params, buffers = layer.functional_state()
            objs = list(params.values()) + list(buffers.values())
            arrays = [p._data for p in objs]

            def fwd(param_arrays, *inputs):
                with _swap_data(objs, list(param_arrays)):
                    with rng.key_guard(jax.random.key(0)):
                        out = layer(*[Tensor(i) for i in inputs])
                return out._data if isinstance(out, Tensor) else out

            # One shared scope; unnamed specs share per-axis symbols (d0, d1,
            # ...) so the common "all inputs share the dynamic batch/seq size"
            # case exports with the dims constrained equal. A spec with name=
            # gets its own symbols (name_0, ...) for genuinely independent
            # dynamic dims.
            scope = jexport.SymbolicScope()
            sds = [s.to_sds(scope=scope, prefix=(f"{s.name}_" if s.name else "d"))
                   if isinstance(s, InputSpec) else s
                   for s in input_spec]
            param_sds = [jax.ShapeDtypeStruct(a.shape, a.dtype) for a in arrays]
            exp = jexport.export(jax.jit(fwd))(param_sds, *sds)
            with open(path + ".pdmodel", "wb") as f:
                pickle.dump({
                    "stablehlo": exp.serialize(),
                    "param_keys": list(params.keys()) + list(buffers.keys()),
                }, f, protocol=4)

            # Native deploy artifact for the C++ PJRT runner (pjrt_runner.cc):
            # only for fully-static specs (C/C++ serving is static-shape;
            # dynamic batch stays on the python TranslatedLayer path). Lower
            # for TPU when possible so device custom-calls are baked for the
            # serving target.
            static = all(
                not isinstance(s, InputSpec)
                or all(d is not None and d != -1 for d in s.shape)
                for s in input_spec)
            if not static and configs.get("native") is True:
                raise ValueError(
                    "native=True requires a fully-static input_spec: the C++ "
                    "deploy artifact is static-shape (dynamic dims stay on "
                    "the python TranslatedLayer path)")
            if static and configs.get("native", True):
                try:
                    _write_pdnative(path, fwd, param_sds, sds, arrays,
                                    list(params.keys()) + list(buffers.keys()),
                                    exp)
                except Exception:
                    if configs.get("native") is True:  # explicit: surface
                        raise
        finally:
            if was_training:
                layer.train()


def _write_pdnative(path, fwd, param_sds, sds, arrays, param_keys, exp_host):
    """Emit ``path.pdnative`` — the self-contained C++ deploy artifact
    (StableHLO bytecode + compile options + weights + I/O specs) consumed by
    ``native/csrc/pjrt_runner.cc``. Prefers a TPU-platform lowering; falls
    back to the host export when cross-lowering fails."""
    import numpy as np
    from jax import export as jexport

    from paddle_tpu.native import pdnative

    exp = exp_host
    try:
        exp = jexport.export(jax.jit(fwd), platforms=["tpu"])(param_sds, *sds)
    except Exception:
        pass

    n_params = len(arrays)
    args = []
    for i in sorted(exp.module_kept_var_idx):
        if i < n_params:
            a = np.asarray(arrays[i])
            args.append(pdnative.ArgSpec(param_keys[i], a.dtype, a.shape,
                                         a.tobytes()))
        else:
            s = sds[i - n_params]
            args.append(pdnative.ArgSpec(f"input_{i - n_params}",
                                         np.dtype(s.dtype), s.shape))
    outs = [pdnative.ArgSpec(f"output_{j}", np.dtype(o.dtype), o.shape)
            for j, o in enumerate(exp.out_avals)]
    pdnative.write(path + ".pdnative",
                   platform=exp.platforms[0],
                   compile_options=pdnative.default_compile_options(),
                   stablehlo=exp.mlir_module_serialized,
                   args=args, outputs=outs)


class TranslatedLayer:
    """Result of jit.load on an exported program: a callable that runs the
    deserialized StableHLO with the saved parameters (no model code)."""

    def __init__(self, exported, param_arrays):
        self._exported = exported
        self._params = param_arrays

    def __call__(self, *inputs):
        arrs = [i._data if isinstance(i, Tensor) else jnp.asarray(i) for i in inputs]
        out = self._exported.call(self._params, *arrs)
        if isinstance(out, (tuple, list)):  # multi-fetch static exports
            return tuple(Tensor(o) for o in out)
        return Tensor(out)

    def forward(self, *inputs):
        return self(*inputs)

    def eval(self):
        return self

    def train(self):
        raise RuntimeError("TranslatedLayer is inference-only")


def load(path, **configs):
    """jit.load: returns a TranslatedLayer when a .pdmodel exists, else the
    raw state dict (legacy contract)."""
    import os
    import pickle

    if os.path.exists(path + ".pdmodel"):
        from jax import export as jexport

        with open(path + ".pdmodel", "rb") as f:
            meta = pickle.load(f)
        with open(path + ".pdparams", "rb") as f:
            state = pickle.load(f)
        exported = jexport.deserialize(meta["stablehlo"])
        arrays = [jnp.asarray(state[k]) for k in meta["param_keys"]]
        return TranslatedLayer(exported, arrays)
    with open(path + ".pdparams", "rb") as f:
        return pickle.load(f)


# --------------------------------------------------- dy2static config knobs
# (ref:python/paddle/jit/api.py enable_to_static, dy2static/logging_utils)

_to_static_enabled = True


def enable_to_static(enable: bool = True):
    """Globally toggle to_static compilation (when off, StaticFunction runs
    the original eager function)."""
    global _to_static_enabled
    _to_static_enabled = bool(enable)


def not_to_static(function):
    """Mark a function to stay eager inside to_static regions. Tracing-based
    to_static has no AST rewriting, so marked functions simply run as part of
    the trace; the marker is honored by returning the function unchanged."""
    function._paddle_not_to_static = True
    return function


_ignored_modules: list = []


def ignore_module(modules):
    """Register modules the dy2static transformer should skip. Trace-based
    compilation never rewrites module code, so registration is bookkeeping
    for API parity."""
    _ignored_modules.extend(modules if isinstance(modules, (list, tuple))
                            else [modules])


_code_level = 0
_verbosity = 0


def set_code_level(level=100, also_to_stdout=False):
    global _code_level
    _code_level = level


def set_verbosity(level=0, also_to_stdout=False):
    global _verbosity
    _verbosity = level
