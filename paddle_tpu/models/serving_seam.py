"""The engine<->model seam: what a model declares so that
``paddle_tpu.serving`` can run it, and the serving machinery every served
model shares (docs/serving_model_seam.md).

**What a model declares** (methods on the ``nn.Layer`` it hands the engine;
:class:`~paddle_tpu.models.gpt.GPTForCausalLM`,
:class:`~paddle_tpu.models.olmo_hybrid.OlmoHybridForCausalLM`,
:class:`~paddle_tpu.models.phi4flash.Phi4FlashForCausalLM`,
:class:`~paddle_tpu.models.xing4.Xing4ForCausalLM`,
:class:`~paddle_tpu.models.longcat_flash.LongcatFlashForCausalLM` and
:class:`~paddle_tpu.models.trinity.TrinityForCausalLM` do):

* ``serving_spec() -> ServingSpec``: vocabulary, longest context, one state
  declaration per layer, in order (the KIND of per-request state that layer
  keeps and its shape: :class:`KVLayerState`, :class:`RecurrentLayerState`,
  :class:`WindowLayerState`, :class:`SharedKVLayerState`,
  :class:`LatentKVLayerState`, :class:`SparseKVLayerState`,
  :class:`StatelessLayerState`), and
  ``prefill_tail``: the first layer that
  a prefill runs on each request's last valid token only (None: every layer
  runs on every token);
* ``serving_embed(ids, positions)``: ``[lanes, s]`` token ids at per-lane
  start positions -> hidden states, ``[lanes, s, hidden]`` or the model's
  own behind ``s`` (Xing4.0: ``[lanes, s, 4 hidden]``, its four residual
  streams side by side), which its layers carry and ``serving_final`` folds;
* ``serving_layers()``: the layers, each called
  ``layer(x, cache=view, start_pos=positions) -> (x, successor view)`` with a
  cache view of ITS kind, built by the engine; a layer whose class sets
  ``uses_step_carry = True`` is also handed ``carry=``, see below;
* ``serving_final(x)``: the final norm; ``serving_head(h_last)``:
  ``[b, hidden]`` arrays -> ``[b, vocab]`` logits;
* ``serving_linears()``: ``(site, linear)`` for every matmul the int8
  quantizer and the LoRA arena may touch, in model order;
* ``serving_embedding()``: the token table (never quantized: its dtype is
  the compute dtype);
* optionally ``serving_prepare()``: in place and idempotent, called once
  before the engine takes its snapshot of the weights: derive the buffers
  the served programs read from the parameters as they are now (Xing4.0:
  the mixers' matrices as its hyper-connection kernel reads them), so that
  no program derives them again at every call.

**The cache-view protocols.** A ``"kv"`` layer drives
``view.update_and_attend(q, k, v) -> (attention output, successor)`` with
``q`` ``[b, s, heads, head_dim]`` and ``k``, ``v`` ``[b, s, kv_heads,
head_dim]`` (``heads`` a multiple of ``kv_heads``: query head ``h`` reads
K/V head ``h // (heads // kv_heads)``); the view owns the paged layout. A
``"window"`` layer drives the same call; its view keeps the last ``window``
tokens' K/V of each lane (a ring per lane in the slot-indexed store; its
order is free: a model with positions turns each key at its own position
before it hands the row over, so a row carries its position in its values)
and query ``t`` attends keys ``t - window + 1 .. t``. A ``"shared"`` layer
owns no cache: its view reads the pool of the layer it names, as that
layer left it in this same call, through ``view.attend(q)``, and writes
nothing. A
``"recurrent"`` layer drives ``view.read() -> state arrays`` (the order of
its :class:`RecurrentLayerState`), ``view.valid_len`` (None, or the traced
true length of a padded prefill) and ``view.write(new arrays) -> successor``;
the view owns the per-lane store and which lanes a write reaches. A
``"none"`` layer is handed ``cache=None`` and returns ``(x, None)``.

A ``"latent"`` layer (:class:`LatentKVLayerState`) computes its token's one
row ``[c_kv | k_rope]`` itself (norm and rotary applied: the MODEL applies
rotary, to the queries and to the row's rotary part, at ``start_pos +`` the
token's index; the view never sees a position's angle) and reads
``view.absorbed``. False, a prefill: it expands the prompt's keys and
values from its rows and drives ``view.write_and_attend(q [1, s, heads,
192], rows [1, s, W], scale, kv=(k [1, s, heads, 192], v [1, s, heads,
128])) -> (attention output [1, s, heads, 128], successor)``: the view
keeps the rows for the engine to scatter and attends causally over the
expanded form. True, a decode step: it carries each head's query into the
latent space and drives ``view.write_and_attend(q [lanes, 1, heads, W],
rows [lanes, 1, W], scale) -> (the probabilities' sums of the rows' first
``latent_dim`` values [lanes, 1, heads, latent_dim], successor)``, which it
expands through the value half of its own up-projection. The view owns the
pool's layout (rows are packed two to a pool row at W = 576), the block
tables and the kernel.

A ``"sparse"`` layer (:class:`SparseKVLayerState`) keeps K and V rows as a
``"kv"`` layer's AND one index key of ``index_dim`` values a token, in the
same blocks under the same table, and attends, for every query, the
``topk`` earlier tokens of largest index score alone. The MODEL computes
everything a token brings (norms and rotary applied, to the index queries
and the index key too: the view never sees an angle) and drives
``view.select_and_attend(q [b, s, heads, head_dim], k, v [b, s, kv_heads,
head_dim], qi [b, s, index_heads, index_dim], ki [b, s, index_dim], w [b,
s, index_heads] float32) -> (attention output [b, s, heads, head_dim],
successor)``. The view owns the rest: it writes the three rows, scores
``I[t, s] = sum_j w[t, j] relu(qi[t, j] . ki[s])`` over the keys ``s <= t``
(float32 sums), keeps the ``topk`` largest (all while there are no more)
and runs the softmax over those; one kept set serves every head. In the
decode step it reads the lane's live index keys and at most ``topk`` rows
of K and of V through the block table; in a prefill no ``[positions,
positions]`` array is made (:mod:`paddle_tpu.ops.sparse_attention`).
Refused, by name: ``prefix_cache``, ``kv_tiering``, ``chunked_prefill``,
``spec_k``, ``quant_kv``, a ``mesh`` of more than one chip and the
disaggregated handoff (each would need the index keys carried, shared,
quantized or sharded beside K and V).

**Options a latent pool refuses** (a ``ValueError`` that names the option,
at construction): ``prefix_cache``, ``kv_tiering``, ``chunked_prefill``
(their programs attend a resident prefix through ``"kv"`` views),
``spec_k`` (the verify programs likewise), ``quant_kv`` (no int8 form of a
row), a device ``mesh`` of more than one chip (one row has no heads to
shard), and the disaggregated handoff (``DisaggReplicaPool``).

**The step carry.** ``forward_cached`` hands every layer that sets
``uses_step_carry`` one dict, the same for the whole call: a layer publishes
``carry[name] = value`` (``[b, s, ...]``) and a later layer of the same call
reads it (Xing4.0's layers hand the stream update behind their last
sublayer to the next layer's first kernel so: ``"hc.y"``, ``"hc.mix"``;
LongCat-Flash's decoder layer, which has two cache entries, is served as two
half-layers, and its expert layer's output crosses from the first to the end
of the second so: ``"scmoe.s"``). It is no cache: nothing of it outlives the
call. The decode step
seeds it with ``"lanes"`` (``[lanes, 1]`` bool: the lanes that hold a
request), and reads one entry back: ``carry["counters"]``, a dict of int32
scalars by counter name that layers add to (:func:`add_step_counters`);
the step returns their sums behind its tokens, and the host adds each to
the ``serving.metrics`` counter of its name when it reads the tokens: no
transfer of their own.

**The prefill tail.** With ``prefill_tail = n`` a prefill hands
``forward_cached`` each request's last valid index: from layer ``n`` on the
hidden states (and every carried value) are that one row. The view of a
``"kv"`` layer ``n - 1`` says so too (``view.last``), so that such a layer
can write K/V for every token and narrow everything else to the last row
itself. Exact, not an approximation: nothing else of those layers is read.

The engine never names a model class or a model's attribute beyond these.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor
from ..distributed.fleet.meta_parallel.mp_layers import (
    MODEL_AXIS,
    ColumnParallelLinear,
    RowParallelLinear,
)
from ..distributed.sharding_util import constraint


@dataclass(frozen=True)
class KVLayerState:
    """A layer whose per-request state is keys and values, one row a token:
    it lives in the paged arena's block pools. ``num_heads`` query heads
    read ``num_kv_heads`` K/V heads (None: as many)."""

    num_heads: int
    head_dim: int
    kind: str = "kv"
    num_kv_heads: Optional[int] = None

    @property
    def kv_heads(self) -> int:
        return int(self.num_kv_heads or self.num_heads)


@dataclass(frozen=True)
class LatentKVLayerState:
    """A layer whose per-request state is ONE row a token, shared by all
    ``num_heads`` query heads: ``latent_dim`` values of compressed keys and
    values (after their norm) and ``rope_dim`` of the one rotary key (after
    its rotation), nothing per head. It lives in the paged arena like a
    ``"kv"`` layer's, ``latent_dim + rope_dim`` values a token, with the
    same block accounting."""

    latent_dim: int
    rope_dim: int
    num_heads: int
    kind: str = "latent"

    @property
    def width(self) -> int:
        return int(self.latent_dim) + int(self.rope_dim)


@dataclass(frozen=True)
class SparseKVLayerState:
    """A layer whose per-request state is keys and values, one row a token
    as a ``"kv"`` layer's, AND one index key of ``index_dim`` values a
    token, by which ``index_heads`` index queries choose the ``topk``
    tokens a query attends. All three lie in the paged arena, in the same
    blocks under the same table."""

    num_heads: int
    head_dim: int
    num_kv_heads: int
    index_dim: int
    index_heads: int
    topk: int
    kind: str = "sparse"

    @property
    def kv_heads(self) -> int:
        return int(self.num_kv_heads)


@dataclass(frozen=True)
class WindowLayerState:
    """A layer that keeps the last ``window`` tokens' keys and values: a
    fixed size per lane, so it lives in the slot-indexed store (K and V
    ``[kv_heads, window, head_dim]`` a lane: the heads outside the tokens,
    as the attention over the ring reads them, so that neither the
    one-row write nor the read relays the ring out), reset at admission
    and freed with the lane, with no block accounting."""

    num_heads: int
    head_dim: int
    window: int
    num_kv_heads: Optional[int] = None
    kind: str = "window"

    @property
    def kv_heads(self) -> int:
        return int(self.num_kv_heads or self.num_heads)

    def arrays(self, dtype: str):
        """The store's arrays, in :class:`RecurrentLayerState`'s form."""
        shape = (self.kv_heads, int(self.window), int(self.head_dim))
        return (("k", shape, dtype), ("v", shape, dtype))


@dataclass(frozen=True)
class SharedKVLayerState:
    """A layer that owns no cache and attends the pool of layer ``source``
    (a ``"kv"`` layer before it) through the same block tables."""

    source: int
    num_heads: int
    head_dim: int
    kind: str = "shared"


@dataclass(frozen=True)
class StatelessLayerState:
    """A layer with no per-request state of its own."""

    kind: str = "none"


@dataclass(frozen=True)
class RecurrentLayerState:
    """A layer whose per-request state has a fixed size whatever the
    context: ``arrays`` is ``((name, per-lane shape, dtype), ...)``; the
    engine keeps one ``[lanes, *shape]`` array each, indexed by slot."""

    arrays: Tuple[Tuple[str, Tuple[int, ...], str], ...]
    kind: str = "recurrent"


@dataclass(frozen=True)
class ServingSpec:
    vocab_size: int
    max_positions: int
    layers: Tuple[object, ...]
    #: first layer a prefill runs on each request's last valid token only
    prefill_tail: Optional[int] = None
    #: Pallas kernels the model's own layers launch (the cache views'
    #: kernels are the engine's): the engine sets the gauge
    #: ``kernel.<name>`` for each, 1 on a TPU, 0 where the interpreter
    #: runs the kernel's body or the ``jax.numpy`` route stands in for it
    kernels: Tuple[str, ...] = ()

    def kv_layers(self):
        return [s for s in self.layers if s.kind == "kv"]

    def latent_layers(self):
        return [s for s in self.layers if s.kind == "latent"]


class SharedRef:
    """Stands in ``forward_cached``'s views for a ``"shared"`` layer: its
    real view is ``reader()`` of the SUCCESSOR view of layer ``source``,
    which exists only once that layer has run."""

    def __init__(self, source: int):
        self.source = int(source)


def add_step_counters(carry, values) -> None:
    """Add a layer's int32 scalars ``{name: value}`` to the step's
    counters (the carry's ``"counters"``)."""
    into = carry.setdefault("counters", {})
    for name, value in values.items():
        into[name] = into[name] + value if name in into else value


def last_row(a, last):
    """``a[:, last]`` kept as a length-1 axis (``last`` a traced scalar)."""
    return jax.lax.dynamic_slice_in_dim(a, last, 1, axis=1)


def forward_cached(model, ids, views, positions, last=None,
                   prefill_tail=None, carry=None):
    """Embed -> layers (each handed its cache view) -> final norm: the one
    way a compiled serving program runs a model. Returns ``(hidden
    [lanes, s, hidden] Tensor, successor views)``. With ``prefill_tail``
    and ``last`` (a prefill of a model that declares a tail) the layers
    from ``prefill_tail`` on see row ``last`` alone, and ``hidden`` is
    ``[lanes, 1, hidden]``. Between embed and final norm the hidden states
    are the model's own: ``[lanes, s, hidden]``, or wider or with further
    axes behind ``s`` (residual streams, ``[lanes, s, streams hidden]``),
    which ``serving_final`` folds away; nothing here reads past axis 1.
    ``carry``: the step carry to start from (the decode step seeds it, see
    the module's head); None starts an empty one."""
    x = model.serving_embed(ids, positions)
    new_views, carry = [], ({} if carry is None else carry)
    for i, (layer, view) in enumerate(zip(model.serving_layers(), views)):
        if isinstance(view, SharedRef):
            view = new_views[view.source].reader()
        if prefill_tail is not None and i == prefill_tail:
            if x.shape[1] != 1:  # layer i - 1 did not narrow it itself
                x = Tensor(last_row(x._data, last))
            for name, value in carry.items():
                if name != "counters" and value.shape[1] != 1:
                    carry[name] = last_row(value, last)
        if getattr(layer, "uses_step_carry", False):
            x, nv = layer(x, cache=view, start_pos=positions, carry=carry)
        else:
            x, nv = layer(x, cache=view, start_pos=positions)
        new_views.append(nv)
    return model.serving_final(x), new_views


def masked_attention(qa, ka, va, mask):
    """Core cached-decode attention: q against an (already updated) K/V
    buffer under an explicit boolean mask. ``qa`` is [b, s, heads, dim];
    ``ka``/``va`` are [b, kv_len, heads, dim]; ``mask`` broadcasts against
    [b, heads, s, kv_len]. Returns [b, s, heads, dim].

    This one function is the numerics contract shared by ``generate()``'s
    contiguous KV path and the serving engine's paged-arena path — both
    must produce token-for-token identical greedy decodes, so they must
    run the exact same ops (same dtypes, same -1e30 masking, same fp32
    softmax)."""
    if ka.shape[2] != qa.shape[2]:
        return _grouped_attention(qa, ka, va, mask)
    qt = jnp.swapaxes(qa, 1, 2)  # [b, h, s, d]
    kt = jnp.swapaxes(ka, 1, 2)
    vt = jnp.swapaxes(va, 1, 2)
    scale = 1.0 / math.sqrt(qa.shape[-1])
    logits = jnp.einsum("bhqd,bhkd->bhqk", qt, kt) * scale
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(qa.dtype)
    return jnp.swapaxes(jnp.einsum("bhqk,bhkd->bhqd", p, vt), 1, 2)


def _grouped_attention(qa, ka, va, mask):
    """:func:`masked_attention` where ``ka``/``va`` have fewer heads than
    ``qa``: query head ``h`` reads K/V head ``h // group``. The same ops on
    a ``[b, kv_heads, group, ...]`` view of the queries, so no K/V row is
    repeated in memory. ``mask`` broadcasts against ``[b, heads, s,
    kv_len]`` with a heads axis of 1."""
    b, s, h, d = qa.shape
    kvh = ka.shape[2]
    qg = qa.reshape(b, s, kvh, h // kvh, d)
    logits = jnp.einsum("bqkgd,btkd->bkgqt", qg, ka) / math.sqrt(d)
    logits = jnp.where(mask[:, :, None], logits, -1e30)
    p = jax.nn.softmax(logits.astype(jnp.float32), -1).astype(qa.dtype)
    return jnp.einsum("bkgqt,btkd->bqkgd", p, va).reshape(b, s, h, d)


#: multi-LoRA hook (serving.adapters): called as hook(layer, x, y) inside
#: serving_linear to add the per-lane low-rank update when an adapter
#: trace context is bound; inert (returns y) without one. Process-global
#: and None until an AdapterArena exists, so the training/generate paths
#: never pay for it.
_lora_hook = None


def set_lora_hook(fn) -> None:
    """Install the serving-adapter hook (``serving.adapters`` calls this
    once, at the first :class:`~paddle_tpu.serving.adapters.AdapterArena`
    construction). Idempotent."""
    global _lora_hook
    _lora_hook = fn


def quantize_serving_weights(model, mesh=None) -> int:
    """Per-channel int8 weight-only quantization of every matmul the model
    declares (``serving_linears()``), in place
    (``FLAGS_serving_quant_weights`` — the serving engine calls this at
    model load).

    Each targeted linear's weight payload becomes int8 (``[in, out]``,
    quantized per OUTPUT channel via
    :func:`paddle_tpu.quantization.quantize_weight` — the framework's one
    weight quantizer, no absmax math duplicated here) and the ``[1, out]``
    float32 scale is registered as a ``weight_scale`` buffer, so
    ``functional_state()`` carries both into every compiled program: the
    decode/prefill/verify programs then stream int8 weights from HBM and
    dequantize in-kernel (:func:`serving_linear`). Embeddings, the LM head
    and the norms stay in the compute dtype — they are a small fraction of
    decode traffic and the head's argmax is tolerance-critical.

    Idempotent (a gateway's replicas share one model instance): already
    quantized layers are skipped. Returns the number of layers quantized
    by THIS call. ``mesh`` pins the re-placement below to a specific mesh
    (the serving engine passes its captured one so an explicit
    ``ServingConfig.mesh`` stays coherent); None defers to the installed
    global. Training a quantized model is not supported — serving
    quantization is a load-time conversion, not QAT (see
    :mod:`paddle_tpu.quantization` for fake-quant training)."""
    from .. import quantization
    from ..distributed.sharding_util import shard_parameter

    n = 0
    for _, lin in model.serving_linears():
        if getattr(lin, "weight_scale", None) is not None:
            continue
        qw, scale = quantization.quantize_weight(
            np.asarray(lin.weight._data), channel_axis=1)
        lin.weight._data = jnp.asarray(qw)
        lin.weight.stop_gradient = True
        lin.register_buffer("weight_scale", Tensor(jnp.asarray(scale)))
        # re-place on the mesh: the payload swap above replaced the
        # committed (sharded) array with a default-placed one, and jit
        # infers in_shardings from committed arrays — without this a
        # TP mesh would hold the FULL int8 weight per chip. Column
        # linears shard out_features on the model axis (the
        # per-out-channel scale shards with them); row linears shard
        # in_features, their out-channel scale is replicated; a plain
        # linear is replicated. No-op off-mesh (single chip).
        if isinstance(lin, ColumnParallelLinear):
            shard_parameter(lin.weight, None, MODEL_AXIS, mesh=mesh)
            shard_parameter(lin.weight_scale, None, MODEL_AXIS, mesh=mesh)
        elif isinstance(lin, RowParallelLinear):
            shard_parameter(lin.weight, MODEL_AXIS, None, mesh=mesh)
            shard_parameter(lin.weight_scale, None, None, mesh=mesh)
        else:
            shard_parameter(lin.weight, None, None, mesh=mesh)
            shard_parameter(lin.weight_scale, None, None, mesh=mesh)
        n += 1
    if n:
        # generate()'s memoized runner is keyed per decode configuration;
        # the quant tag joins that key (like the donation flag) so a
        # pre-quantization runner is never reused on int8 weights
        model._serving_quant = getattr(model, "_serving_quant", 0) + 1
    return n


def serving_linear(layer, x):
    """The matmul entry point shared by the quantized and plain paths. An
    unquantized layer runs its normal forward (op-for-op identical to
    calling it directly — the flag-off serving path stays bit-identical).
    A layer carrying a ``weight_scale`` buffer (int8 payload from
    :func:`quantize_serving_weights`) dequantizes IN the kernel: the int8
    weight is read from HBM, multiplied by its per-channel scale and cast
    to the activation dtype right before the matmul, so XLA fuses the
    dequant into the matmul's operand pipeline — weight traffic is
    1 byte/param instead of 2-4.

    This is also the multi-LoRA attach point (``serving.adapters``): when
    an adapter trace context is bound, the per-lane low-rank update
    ``(x @ A[ids]) @ B[ids]`` is added to the base matmul's output —
    int8 base + f32 adapters compose here. No context ⇒ identical trace."""
    scale = getattr(layer, "weight_scale", None)
    if scale is None:
        y = layer(x)
        if _lora_hook is not None:
            y = _lora_hook(layer, x, y)
        return y
    from ..core.dispatch import apply

    if isinstance(layer, RowParallelLinear) and layer.input_is_parallel:
        # mirror RowParallelLinear.forward's input hint: the contraction
        # over the model-sharded in_features must stay a partial matmul +
        # psum, not an all-gather of the activations
        x = constraint(x, "data", None, MODEL_AXIS)

    def deq_matmul(xa, qwa, sa, ba=None):
        w = (qwa.astype(jnp.float32) * sa).astype(xa.dtype)
        y = xa @ w
        if ba is not None:
            y = y + ba.astype(y.dtype)
        return y

    args = (x, layer.weight, scale) + (
        () if layer.bias is None else (layer.bias,))
    y = apply(deq_matmul, args, {}, name="serving_qlinear")
    if _lora_hook is not None:
        y = _lora_hook(layer, x, y)
    # mirror the parallel linears' output shardings (the quantized matmul
    # must shard exactly like the one it replaces)
    if isinstance(layer, ColumnParallelLinear) and not layer.gather_output:
        return constraint(y, "data", None, MODEL_AXIS)
    return constraint(y, "data", None, None)


def serving_compute_dtype(model) -> str:
    """The model's activation/KV compute dtype. Normally its first declared
    linear's weight dtype; with int8-quantized serving weights those read
    "int8", so fall back to the (never-quantized) token embedding — KV
    caches and activation buffers must be allocated in the compute dtype,
    not the storage dtype. This is the ONE home of the fallback rule
    (``gen_kv_caches`` derives from it too), and the dict lookup keeps it
    branch-free — generate()'s compiled copying build traces through it."""
    d = str(model.serving_linears()[0][1].weight._data.dtype)
    return {"int8": str(model.serving_embedding().weight._data.dtype)}.get(
        d, d)
