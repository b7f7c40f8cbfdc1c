"""paddle.static.nn — static-graph layer builders.

Parity surface: ref:python/paddle/static/nn/__init__.py. The reference's
builders append OpDescs + parameters to the current Program's block; here
each builder instantiates the corresponding ``paddle_tpu.nn`` layer (fresh
parameters, shared only via an explicit ``name``) and applies it — under
``program_guard`` the application records onto the Program tape, in dygraph
it just runs. Running-stat side effects (batch_norm) are recorded as
buffer-update tape outputs (``Program.add_buffer_update``), mirroring the
extra stat-update ops the reference emits into the block.

LoD sequence ops (``sequence_*``, StaticRNN) are a deleted design on this
stack — variable-length data travels as padded batches + masks (SURVEY.md
§2.3) — and raise with that guidance.
"""
from __future__ import annotations

from typing import Optional

import numpy as np

from .program import default_main_program, is_symbolic

# explicit-name parameter sharing lives ON the current Program
# (``Program._static_layers``): scoped per program like the reference's
# per-Program parameter blocks, and freed with the program (no process-
# global cache, no id()-reuse hazard)


def _layer(name, factory):
    if name is None:
        return factory()
    cache = default_main_program()._static_layers
    if name not in cache:
        cache[name] = factory()
    return cache[name]


def get_layer(name):
    """The layer object behind a named builder call in the current Program
    scope (test/introspection hook)."""
    return default_main_program()._static_layers.get(name)


def clear_layer_cache():
    default_main_program()._static_layers.clear()


def _act(x, activation):
    if activation is None:
        return x
    from ..nn import functional as F

    return getattr(F, activation)(x)


def fc(x, size, num_flatten_dims=1, weight_attr=None, bias_attr=None,
       activation=None, name=None):
    """ref:python/paddle/static/nn/common.py fc: flatten trailing dims,
    affine, optional activation."""
    from .. import nn
    from ..ops import manipulation as M

    shape = list(x.shape)
    if len(shape) > num_flatten_dims + 1:
        # flatten dims [num_flatten_dims:] into one (fc's contract);
        # flatten derives lead dims from the runtime array, so a None
        # batch respecializes per feed shape
        x = M.flatten(x, start_axis=num_flatten_dims, stop_axis=-1)
    in_features = int(np.prod(shape[num_flatten_dims:]))
    lin = _layer(name, lambda: nn.Linear(
        in_features, size, weight_attr=weight_attr, bias_attr=bias_attr))
    return _act(lin(x), activation)


def embedding(input, size, is_sparse=False, is_distributed=False,
              padding_idx=None, param_attr=None, dtype="float32", name=None):
    from .. import nn

    emb = _layer(name, lambda: nn.Embedding(
        size[0], size[1], padding_idx=padding_idx, sparse=is_sparse,
        weight_attr=param_attr))
    return emb(input)


def sparse_embedding(input, size, padding_idx=None, is_test=False,
                     entry=None, table_class="MemorySparseTable",
                     param_attr=None, dtype="float32", slot=None, name=None):
    """PS-backed embedding when a sparse table is registered with the fleet
    (the reference routes this to the distributed lookup table,
    ref:python/paddle/static/nn/common.py sparse_embedding); plain sparse
    Embedding otherwise. ``slot`` selects the registered table id (first
    registered table when omitted)."""
    from ..distributed import fleet

    tables = getattr(fleet, "_registered_tables", None)
    if tables:
        if is_symbolic(input):
            raise NotImplementedError(
                "sparse_embedding over a parameter-server table is a host-"
                "side pull/push (RPC per batch) and cannot be recorded onto "
                "a compiled Program tape — drive PS training in dygraph "
                "(distributed.ps.PSEmbedding + TrainStep over the dense "
                "part)")
        from ..distributed.ps import PSEmbedding

        if slot is not None:
            client = tables.get(int(slot))
            if client is None:
                raise ValueError(
                    f"sparse_embedding: no sparse table registered under id "
                    f"{slot} (registered: {sorted(tables)})")
        else:
            client = next(iter(tables.values()))
        return _layer(name, lambda: PSEmbedding(client))(input)
    return embedding(input, size, is_sparse=True, padding_idx=padding_idx,
                     param_attr=param_attr, dtype=dtype, name=name)


def _in_channels(input, data_format):
    return input.shape[-1] if data_format.endswith("C") else input.shape[1]


def _conv(cls, name, *args, **kw):
    from .. import nn

    return _layer(name, lambda: getattr(nn, cls)(*args, **kw))


def conv2d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None, name=None,
           data_format="NCHW"):
    layer = _conv("Conv2D", name, _in_channels(input, data_format),
                  num_filters, filter_size,
                  stride=stride, padding=padding, dilation=dilation,
                  groups=groups, weight_attr=param_attr, bias_attr=bias_attr,
                  data_format=data_format)
    return _act(layer(input), act)


def conv3d(input, num_filters, filter_size, stride=1, padding=0, dilation=1,
           groups=1, param_attr=None, bias_attr=None, act=None, name=None,
           data_format="NCDHW"):
    layer = _conv("Conv3D", name, _in_channels(input, data_format),
                  num_filters, filter_size,
                  stride=stride, padding=padding, dilation=dilation,
                  groups=groups, weight_attr=param_attr, bias_attr=bias_attr,
                  data_format=data_format)
    return _act(layer(input), act)


def _conv_transpose(cls, fname, input, num_filters, filter_size, output_size,
                    stride, padding, dilation, groups, param_attr, bias_attr,
                    act, name, data_format):
    layer = _conv(cls, name, _in_channels(input, data_format), num_filters,
                  filter_size, stride=stride, padding=padding,
                  dilation=dilation, groups=groups, weight_attr=param_attr,
                  bias_attr=bias_attr, data_format=data_format)
    if output_size is None:
        return _act(layer(input), act)
    # output_size resolves the transpose shape ambiguity — route through the
    # functional form (the layer's forward has no output_size parameter)
    from ..nn import functional as F

    out = getattr(F, fname)(input, layer.weight, layer.bias, stride=stride,
                            padding=padding, dilation=dilation, groups=groups,
                            output_size=output_size, data_format=data_format)
    return _act(out, act)


def conv2d_transpose(input, num_filters, filter_size=None, output_size=None,
                     stride=1, padding=0, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, act=None, name=None,
                     data_format="NCHW"):
    return _conv_transpose("Conv2DTranspose", "conv2d_transpose", input,
                           num_filters, filter_size, output_size, stride,
                           padding, dilation, groups, param_attr, bias_attr,
                           act, name, data_format)


def conv3d_transpose(input, num_filters, filter_size=None, output_size=None,
                     stride=1, padding=0, dilation=1, groups=1,
                     param_attr=None, bias_attr=None, act=None, name=None,
                     data_format="NCDHW"):
    return _conv_transpose("Conv3DTranspose", "conv3d_transpose", input,
                           num_filters, filter_size, output_size, stride,
                           padding, dilation, groups, param_attr, bias_attr,
                           act, name, data_format)


def deform_conv2d(x, offset, mask, num_filters, filter_size, stride=1,
                  padding=0, dilation=1, groups=1, deformable_groups=1,
                  im2col_step=1, param_attr=None, bias_attr=None, name=None):
    from ..vision.ops import DeformConv2D

    layer = _layer(name, lambda: DeformConv2D(
        x.shape[1], num_filters, filter_size, stride=stride, padding=padding,
        dilation=dilation, groups=groups,
        deformable_groups=deformable_groups, weight_attr=param_attr,
        bias_attr=bias_attr))
    return layer(x, offset, mask)


def batch_norm(input, act=None, is_test=False, momentum=0.9, epsilon=1e-5,
               param_attr=None, bias_attr=None, data_layout="NCHW",
               in_place=False, name=None, moving_mean_name=None,
               moving_variance_name=None, do_model_average_for_mean_and_var=True,
               use_global_stats=False):
    """Batch norm with running-stat updates recorded onto the tape as
    buffer updates (the reference emits them as extra block ops)."""
    from .. import nn
    from ..nn import functional as F

    C = input.shape[1] if data_layout == "NCHW" else input.shape[-1]
    bn = _layer(name, lambda: nn.BatchNorm2D(
        C, momentum=momentum, epsilon=epsilon, weight_attr=param_attr,
        bias_attr=bias_attr) if len(input.shape) == 4 else nn.BatchNorm1D(
        C, momentum=momentum, epsilon=epsilon, weight_attr=param_attr,
        bias_attr=bias_attr))
    training = not is_test and not use_global_stats
    out = F.batch_norm(input, bn._mean, bn._variance, weight=bn.weight,
                       bias=bn.bias, training=training, momentum=momentum,
                       epsilon=epsilon, data_format=data_layout)
    if training and is_symbolic(out):
        # record running-stat maintenance on the program that owns the
        # captured output (NOT the current default — the op may be built
        # outside its guard). bn._mean/_variance enter the expression as
        # the LIVE buffer Tensors, recorded by reference, so each run
        # folds into the previous run's value.
        mean, var = F.batch_stats(input, data_format=data_layout)
        from .program import _sym_owner

        prog = _sym_owner[out._sym_id]
        # chain through any pending update of the same buffer (name-shared
        # layer applied twice in one program → sequential fold, like the
        # reference's in-block stat ops); the algebraic form keeps the
        # buffer inside ops whose OTHER operand is symbolic — a plain
        # `buffer * momentum` would execute eagerly and freeze into the
        # tape as a constant
        cur_mean = prog.pending_buffer_value(bn._mean)
        cur_var = prog.pending_buffer_value(bn._variance)
        new_mean = cur_mean + (mean - cur_mean) * (1 - momentum)
        new_var = cur_var + (var - cur_var) * (1 - momentum)
        prog.add_buffer_update(bn._mean, new_mean)
        prog.add_buffer_update(bn._variance, new_var)
    return _act(out, act)


def layer_norm(input, scale=True, shift=True, begin_norm_axis=1,
               epsilon=1e-5, param_attr=None, bias_attr=None, act=None,
               name=None):
    from .. import nn

    shape = list(input.shape[begin_norm_axis:])
    ln = _layer(name, lambda: nn.LayerNorm(
        shape, epsilon=epsilon, weight_attr=param_attr if scale else False,
        bias_attr=bias_attr if shift else False))
    return _act(ln(input), act)


def instance_norm(input, epsilon=1e-5, param_attr=None, bias_attr=None,
                  name=None):
    from .. import nn

    layer = _layer(name, lambda: nn.InstanceNorm2D(
        input.shape[1], epsilon=epsilon, weight_attr=param_attr,
        bias_attr=bias_attr))
    return layer(input)


def group_norm(input, groups, epsilon=1e-5, param_attr=None, bias_attr=None,
               act=None, data_layout="NCHW", name=None):
    from .. import nn

    layer = _layer(name, lambda: nn.GroupNorm(
        groups, input.shape[1], epsilon=epsilon, weight_attr=param_attr,
        bias_attr=bias_attr, data_format=data_layout))
    return _act(layer(input), act)


def data_norm(input, act=None, epsilon=1e-5, param_attr=None,
              data_layout="NCHW", in_place=False, name=None,
              moving_mean_name=None, moving_variance_name=None,
              do_model_average_for_mean_and_var=True, slot_dim=-1,
              sync_stats=False, summary_decay_rate=0.9999999,
              enable_scale_and_shift=False):
    """Normalization by accumulated batch statistics without affine params
    (the CTR data_norm op) — expressed as batch_norm minus scale/shift."""
    return batch_norm(input, act=act, epsilon=epsilon, param_attr=False,
                      bias_attr=False, data_layout=data_layout, name=name)


def continuous_value_model(input, cvm, use_cvm=True):
    """CTR show/click feature handling (ref continuous_value_model op):
    use_cvm keeps the leading 2 cvm columns, otherwise strips them."""
    if use_cvm:
        return input
    return input[:, 2:]


def prelu(x, mode="all", param_attr=None, data_format="NCHW", name=None):
    from .. import nn

    num = 1 if mode == "all" else (x.shape[1] if mode == "channel"
                                   else int(np.prod(x.shape[1:])))
    layer = _layer(name, lambda: nn.PReLU(
        num_parameters=num, weight_attr=param_attr, data_format=data_format))
    return layer(x)


def spectral_norm(weight, dim=0, power_iters=1, eps=1e-12, name=None):
    from .. import nn

    layer = _layer(name, lambda: nn.SpectralNorm(
        weight.shape, dim=dim, power_iters=power_iters, eps=eps))
    return layer(weight)


def bilinear_tensor_product(x, y, size, act=None, name=None,
                            param_attr=None, bias_attr=None):
    from .. import nn

    layer = _layer(name, lambda: nn.Bilinear(
        x.shape[-1], y.shape[-1], size, weight_attr=param_attr,
        bias_attr=bias_attr))
    return _act(layer(x, y), act)


def row_conv(input, future_context_size, param_attr=None, act=None):
    """Lookahead row convolution (ref row_conv op): causal-in-reverse 1-D
    conv mixing each step with its next ``future_context_size`` steps."""
    from ..core.dispatch import apply
    from ..core.tensor import Tensor

    import jax.numpy as jnp

    ctx = future_context_size + 1
    d = input.shape[-1]
    cache = default_main_program()._static_layers
    key = ("row_conv_w", d, ctx)
    if key not in cache:
        cache[key] = Tensor(jnp.zeros((ctx, d), jnp.float32) + 1.0 / ctx,
                            stop_gradient=False)
    w = cache[key]

    def _row(x, w):
        T = x.shape[1]
        out = jnp.zeros_like(x)
        for k in range(w.shape[0]):
            seg = x[:, k:T, :] * w[k]
            out = out.at[:, : T - k, :].add(seg)
        return out

    return _act(apply(_row, (input, w), {}), act)


def py_func(func, x, out, backward_func=None,
            skip_vars_in_backward_input=None):
    from . import py_func as _pf

    return _pf(func, x, out, backward_func=backward_func,
               skip_vars_in_backward_input=skip_vars_in_backward_input)


# ------------------------------------------------------------ control flow


def cond(pred, true_fn=None, false_fn=None, name=None, return_names=None):
    """Value-level conditional. Concrete pred executes the branch directly;
    a captured (symbolic) pred requires both branches traceable —
    jax.lax.cond through the tape."""
    if is_symbolic(pred):
        raise NotImplementedError(
            "cond on a captured predicate: express the branch with "
            "paddle_tpu.ops.where / lax.cond inside a to_static function — "
            "tape capture records straight-line ops")
    return true_fn() if bool(np.asarray(pred._data if hasattr(pred, "_data")
                                        else pred)) else (
        false_fn() if false_fn is not None else None)


def case(pred_fn_pairs, default=None, name=None):
    for pred, fn in pred_fn_pairs:
        arr = pred._data if hasattr(pred, "_data") else pred
        if is_symbolic(pred):
            raise NotImplementedError("case on captured predicates")
        if bool(np.asarray(arr)):
            return fn()
    return default() if default is not None else None


def switch_case(branch_index, branch_fns, default=None, name=None):
    idx = int(np.asarray(branch_index._data
                         if hasattr(branch_index, "_data") else branch_index))
    fns = dict(branch_fns) if not isinstance(branch_fns, dict) else branch_fns
    fn = fns.get(idx, default)
    if fn is None:
        raise ValueError(f"no branch for index {idx} and no default")
    return fn()


def while_loop(cond, body, loop_vars, is_test=False, name=None):
    """Value-level while. Concrete operands loop in python (the dygraph
    meaning); compiled loops belong to jax.lax.while_loop via to_static."""
    vars_ = list(loop_vars)
    while bool(np.asarray(cond(*vars_)._data)):
        out = body(*vars_)
        vars_ = list(out) if isinstance(out, (list, tuple)) else [out]
    return vars_


# -------------------------------------------------- deleted-design escapes


def _lod_gone(name):
    def fn(*a, **k):
        raise NotImplementedError(
            f"static.nn.{name} operates on LoD tensors, a deleted design on "
            "this stack — variable-length data travels as padded batches + "
            "masks (see text.viterbi_decode / nn.functional.sequence_mask)")

    fn.__name__ = name
    fn._intentional_redirect = True
    return fn


for _n in ("sequence_conv", "sequence_softmax", "sequence_pool",
           "sequence_concat", "sequence_first_step", "sequence_last_step",
           "sequence_slice", "sequence_expand", "sequence_expand_as",
           "sequence_pad", "sequence_unpad", "sequence_reshape",
           "sequence_scatter", "sequence_enumerate", "sequence_reverse"):
    globals()[_n] = _lod_gone(_n)

StaticRNN = _lod_gone("StaticRNN")
nce = _lod_gone("nce")


def create_parameter(shape, dtype, name=None, attr=None, is_bias=False,
                     default_initializer=None):
    from . import create_parameter as _cp

    return _cp(shape, dtype, name=name, attr=attr, is_bias=is_bias,
               default_initializer=default_initializer)
