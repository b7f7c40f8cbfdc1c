"""Quantization: QAT (fake-quant training) and PTQ (observer calibration).

Re-designs the reference's ref:python/paddle/quantization/ (QuantConfig,
qat.QAT, ptq.PTQ, observers/ and quanter/ factories) for the TPU stack:

* fake-quant is a straight-through-estimator PyLayer, so it trains in eager
  mode AND lowers to jax.custom_vjp inside a compiled TrainStep;
* PTQ observers watch activations during calibration and freeze per-tensor
  scales; convert() bakes weights to int8 + scale (dequantized to the
  compute dtype at apply time — weight-only int8, the standard TPU serving
  recipe) and activation quant-dequant with the calibrated scales;
* the converted model round-trips through jit.save/StableHLO export like
  any other model.

Simulated-quant math (symmetric, per-tensor or per-channel):
    q  = clip(round(x / scale), -128, 127)
    dq = q * scale
"""
from __future__ import annotations

import copy
from typing import Dict, Optional, Type

import jax.numpy as jnp
import numpy as np

from .. import nn
from ..core.autograd import PyLayer
from ..core.dispatch import apply
from ..core.tensor import Tensor

__all__ = [
    "QuantConfig", "QAT", "PTQ", "quanted_layers",
    "FakeQuanterWithAbsMaxObserver", "AbsmaxObserver",
    "MovingAverageMinMaxObserver", "quantize_weight", "dequantize_weight",
    "quantize_kv", "dequantize_kv",
]


# ------------------------------------------------------------- primitives


def _fake_quant_arrays(x, scale, qmin=-128, qmax=127):
    s = jnp.maximum(scale, 1e-9)
    q = jnp.clip(jnp.round(x / s), qmin, qmax)
    return q * s


class _FakeQuantSTE(PyLayer):
    """Quant-dequant with a straight-through gradient (QAT's core op,
    ref:python/paddle/nn/quant/format.py fake_quant behavior)."""

    @staticmethod
    def forward(ctx, x, scale):
        def f(xa, sa):
            return _fake_quant_arrays(xa, sa)

        return apply(f, (x, scale), {}, differentiable=False, name="fake_quant")

    @staticmethod
    def backward(ctx, dy):
        return dy, None  # straight-through to x; scale is observed, not learned


def fake_quant(x: Tensor, scale: Tensor) -> Tensor:
    return _FakeQuantSTE.apply(x, scale)


def quantize_weight(w: np.ndarray, channel_axis: Optional[int] = None):
    """float weight -> (int8 weight, float scale[, per-channel]).

    This is THE weight quantizer of the framework: both the PTQ/QAT
    ``convert()`` path and the serving engine's weight-only int8 mode
    (:func:`paddle_tpu.models.serving_seam.quantize_serving_weights`) call it, so
    the absmax math exists exactly once. ``channel_axis`` selects the
    per-channel axis (negative values count from the end, numpy-style);
    the returned scale keeps that axis (``keepdims``) so dequantization
    is a plain broadcast multiply."""
    w = np.asarray(w)
    if channel_axis is None:
        scale = np.maximum(np.abs(w).max(), 1e-9) / 127.0
        q = np.clip(np.round(w / scale), -128, 127).astype(np.int8)
        return q, np.float32(scale)
    channel_axis = channel_axis % w.ndim
    axes = tuple(i for i in range(w.ndim) if i != channel_axis)
    scale = (np.maximum(np.abs(w).max(axis=axes, keepdims=True), 1e-9) / 127.0)
    q = np.clip(np.round(w / scale), -128, 127).astype(np.int8)
    return q, scale.astype(np.float32)


def dequantize_weight(q: np.ndarray, scale) -> np.ndarray:
    return q.astype(np.float32) * scale


def quantize_kv(x):
    """Symmetric per-token int8 quantization of a K/V chunk — jax-traceable
    (runs INSIDE the serving engine's compiled prefill/decode programs:
    quantize-on-scatter). ``x`` is ``[..., heads, head_dim]``; one scale per
    leading (token/lane) index, reduced over the trailing ``(heads, dim)``
    axes. Returns ``(int8 payload, float32 scale[...])``. All-array math by
    construction: no host casts, no data-dependent shapes — the recompile
    lint's ``compiled_quant`` fixture pair documents the anti-patterns."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=(-2, -1))
    scale = jnp.maximum(amax, 1e-9) / 127.0
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None, None]),
                 -128, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_kv(q, scale, dtype):
    """Inverse of :func:`quantize_kv` (dequant-on-attend): int8 payload *
    per-token scale, cast to the attention compute ``dtype``. The f32
    multiply happens before the cast so a bf16 compute dtype rounds once,
    not twice.

    This is the home of the dequant math: the XLA gather path calls it
    over gathered context (per block when the compute dtype is narrower
    than f32 — ``cache_views.gather_ctx``). The Pallas paged kernels
    (:mod:`paddle_tpu.ops.paged_attention`) apply the same per-token
    scales to the score columns and the probabilities instead
    (``q.(k*s) == (q.k)*s``): a ``[1, bs]`` lane row of scales cannot be
    relaid across a VMEM block's leading axis on the chip. The two agree
    within the documented tolerance (docs/performance.md)."""
    return (q.astype(jnp.float32) * scale[..., None, None]).astype(dtype)


# -------------------------------------------------------------- observers


class AbsmaxObserver(nn.Layer):
    """Track max(|x|) over calibration batches -> symmetric scale."""

    def __init__(self, quant_bits: int = 8):
        super().__init__()
        self.quant_bits = quant_bits
        self._absmax = 0.0

    def forward(self, x):
        self._absmax = max(self._absmax, float(np.abs(np.asarray(x._data)).max()))
        return x

    def scale(self) -> float:
        return max(self._absmax, 1e-9) / 127.0


class MovingAverageMinMaxObserver(nn.Layer):
    """EMA of per-batch absmax (ref observer family)."""

    def __init__(self, quant_bits: int = 8, moving_rate: float = 0.9):
        super().__init__()
        self.quant_bits = quant_bits
        self.moving_rate = moving_rate
        self._stat = None

    def forward(self, x):
        cur = float(np.abs(np.asarray(x._data)).max())
        self._stat = cur if self._stat is None else (
            self.moving_rate * self._stat + (1 - self.moving_rate) * cur)
        return x

    def scale(self) -> float:
        return max(self._stat or 0.0, 1e-9) / 127.0


class FakeQuanterWithAbsMaxObserver(nn.Layer):
    """QAT quanter: observe absmax online AND fake-quantize (ref
    quanter/abs_max.py FakeQuanterWithAbsMaxObserverLayer)."""

    def __init__(self, quant_bits: int = 8, moving_rate: float = 0.9):
        super().__init__()
        self._observer = MovingAverageMinMaxObserver(quant_bits, moving_rate)

    def forward(self, x):
        if self.training:
            self._observer(x)
        elif self._observer._stat is None:
            # eval before any observation: identity, not a garbage 1e-9 scale
            return x
        return fake_quant(x, Tensor(jnp.float32(self._observer.scale())))

    def scale(self) -> float:
        return self._observer.scale()


# ----------------------------------------------------------------- config


class QuantConfig:
    """Which layers get which activation/weight quanters
    (ref:python/paddle/quantization/config.py QuantConfig)."""

    def __init__(self, activation=None, weight=None):
        self.activation = activation
        self.weight = weight
        self._type_configs: Dict[Type, dict] = {}
        self._layer_configs: Dict[int, dict] = {}

    def add_type_config(self, layer_type, activation=None, weight=None):
        for t in (layer_type if isinstance(layer_type, (list, tuple)) else [layer_type]):
            self._type_configs[t] = {"activation": activation, "weight": weight}

    def add_layer_config(self, layer, activation=None, weight=None):
        for l in (layer if isinstance(layer, (list, tuple)) else [layer]):
            self._layer_configs[id(l)] = {"activation": activation, "weight": weight}

    def _config_for(self, layer):
        if id(layer) in self._layer_configs:
            return self._layer_configs[id(layer)]
        for t, cfg in self._type_configs.items():
            if isinstance(layer, t):
                return cfg
        if self.activation is not None or self.weight is not None:
            return {"activation": self.activation, "weight": self.weight}
        return None


def _make(quanter):
    if quanter is None:
        return None
    if isinstance(quanter, type):
        return quanter()
    return copy.deepcopy(quanter)


# ------------------------------------------------------------ quanted nn


class QuantedLinear(nn.Layer):
    """Linear with fake-quant on weight and (optionally) activation."""

    def __init__(self, base: nn.Linear, a_quanter, w_quanter):
        super().__init__()
        self.base = base
        self.activation_quanter = a_quanter
        self.weight_quanter = w_quanter

    def forward(self, x):
        if self.activation_quanter is not None:
            x = self.activation_quanter(x)
        w = self.base.weight
        if self.weight_quanter is not None:
            w = self.weight_quanter(w)
        from ..nn import functional as F

        return F.linear(x, w, self.base.bias)


class QuantedConv2D(nn.Layer):
    def __init__(self, base, a_quanter, w_quanter):
        super().__init__()
        self.base = base
        self.activation_quanter = a_quanter
        self.weight_quanter = w_quanter

    def forward(self, x):
        if self.activation_quanter is not None:
            x = self.activation_quanter(x)
        w = self.base.weight
        if self.weight_quanter is not None:
            w = self.weight_quanter(w)
        from ..nn import functional as F

        return F.conv2d(x, w, self.base.bias, stride=self.base._stride,
                        padding=self.base._padding, dilation=self.base._dilation,
                        groups=self.base._groups)


quanted_layers = {nn.Linear: QuantedLinear, nn.Conv2D: QuantedConv2D}


# ---------------------------------------------------------- int8 frozen


class _QuantWeightLinear(nn.Layer):
    """Converted form: weight stored int8 + scale (weight-only int8)."""

    def __init__(self, qw: np.ndarray, scale, bias, act_scale: Optional[float]):
        super().__init__()
        self.qweight = self.create_parameter(list(qw.shape), dtype="float32")
        # int8 payload kept as the raw array; registered buffer for state_dict
        self.qweight._data = jnp.asarray(qw)
        self.qweight.stop_gradient = True
        self.scale = Tensor(jnp.asarray(np.asarray(scale, np.float32)))
        self.bias = bias
        self.act_scale = float(act_scale) if act_scale is not None else None

    def forward(self, x):
        def f(xa, qwa, sa, ba=None, *, act_scale):
            w = qwa.astype(jnp.float32) * sa
            if act_scale is not None:
                xa = _fake_quant_arrays(xa, jnp.float32(act_scale))
            y = xa @ w
            if ba is not None:
                y = y + ba
            return y

        args = (x, self.qweight, self.scale) + (
            () if self.bias is None else (self.bias,))
        return apply(f, args, {"act_scale": self.act_scale}, name="qlinear")


class _QuantWeightConv2D(nn.Layer):
    def __init__(self, base, qw, scale, act_scale):
        super().__init__()
        self.base = base
        self.qweight = self.create_parameter(list(qw.shape), dtype="float32")
        self.qweight._data = jnp.asarray(qw)
        self.qweight.stop_gradient = True
        self.scale = Tensor(jnp.asarray(np.asarray(scale, np.float32)))
        self.act_scale = float(act_scale) if act_scale is not None else None

    def forward(self, x):
        from ..nn import functional as F
        from ..ops import math as M

        w = M.multiply(self.qweight, self.scale)
        if self.act_scale is not None:
            x = fake_quant(x, Tensor(jnp.float32(self.act_scale)))
        return F.conv2d(x, w, self.base.bias, stride=self.base._stride,
                        padding=self.base._padding, dilation=self.base._dilation,
                        groups=self.base._groups)


# --------------------------------------------------------------- drivers


def _replace_layers(model: nn.Layer, config: QuantConfig, build):
    for name, child in list(model._sub_layers.items()):
        cfg = config._config_for(child)
        cls = type(child)
        if cfg is not None and cls in quanted_layers:
            setattr(model, name, build(child, cfg, quanted_layers[cls]))
        else:
            _replace_layers(child, config, build)
    return model


class QAT:
    """Quantization-aware training (ref:python/paddle/quantization/qat.py)."""

    def __init__(self, config: QuantConfig):
        self.config = config

    def quantize(self, model: nn.Layer, inplace: bool = False) -> nn.Layer:
        if not inplace:
            model = copy.deepcopy(model)

        def build(child, cfg, qcls):
            return qcls(child, _make(cfg["activation"]), _make(cfg["weight"]))

        return _replace_layers(model, self.config, build)

    def convert(self, model: nn.Layer, inplace: bool = False) -> nn.Layer:
        return _convert(model, inplace=inplace)


class PTQ:
    """Post-training quantization: insert observers, calibrate, convert
    (ref:python/paddle/quantization/ptq.py)."""

    def __init__(self, config: QuantConfig):
        self.config = config

    def quantize(self, model: nn.Layer, inplace: bool = False) -> nn.Layer:
        if not inplace:
            model = copy.deepcopy(model)

        def build(child, cfg, qcls):
            return qcls(child, _make(cfg["activation"]), _make(cfg["weight"]))

        return _replace_layers(model, self.config, build)

    def convert(self, model: nn.Layer, inplace: bool = False) -> nn.Layer:
        return _convert(model, inplace=inplace)


def _convert(model: nn.Layer, inplace: bool = False) -> nn.Layer:
    """Freeze observed scales: weights -> int8+scale, activations ->
    fixed-scale quant-dequant."""
    if not inplace:
        model = copy.deepcopy(model)
    for name, child in list(model._sub_layers.items()):
        if isinstance(child, QuantedLinear):
            w = np.asarray(child.base.weight._data)
            qw, scale = quantize_weight(w, channel_axis=1)
            act_scale = (child.activation_quanter.scale()
                         if child.activation_quanter is not None else None)
            setattr(model, name,
                    _QuantWeightLinear(qw, scale, child.base.bias, act_scale))
        elif isinstance(child, QuantedConv2D):
            w = np.asarray(child.base.weight._data)
            qw, scale = quantize_weight(w, channel_axis=0)
            act_scale = (child.activation_quanter.scale()
                         if child.activation_quanter is not None else None)
            setattr(model, name,
                    _QuantWeightConv2D(child.base, qw, scale, act_scale))
        else:
            _convert(child, inplace=True)
    return model
