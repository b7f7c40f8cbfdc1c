"""GPT family — the flagship transformer (benchmark config 4 of BASELINE.md:
GPT-3 1.3B, tensor+pipeline hybrid).

Functional parity with the reference's fleet GPT configs (TP layers from
ref:python/paddle/distributed/fleet/layers/mpu/mp_layers.py, fused attention
ref:python/paddle/incubate/nn/layer/fused_transformer.py), designed TPU-first:

* weights carry GSPMD shardings (model axis for TP; the "sharding" axis gives
  ZeRO-style param/optimizer partitioning when active),
* activations are constrained ("data", "sep", None) so long sequences can be
  context-parallel over the "sep" axis (the gap called out in SURVEY.md §5.7),
* attention runs through ``F.scaled_dot_product_attention`` which picks the
  Pallas flash kernel on TPU,
* recompute = ``jax.checkpoint`` per decoder block (policy: save nothing —
  trade FLOPs for HBM, SURVEY guidance).

All shapes static; whole model jits into one XLA program via TrainStep/pjit.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp

from .. import nn
from ..core.tensor import Tensor
from ..distributed.fleet.meta_parallel.mp_layers import (
    ColumnParallelLinear,
    RowParallelLinear,
    VocabParallelEmbedding,
)
from ..distributed.sharding_util import constraint
from ..nn import functional as F
from ..ops import creation, manipulation as M
from .serving_seam import (
    KVLayerState,
    ServingSpec,
    masked_attention,
    serving_compute_dtype,
    serving_linear as _serving_linear,
)


@dataclass
class GPTConfig:
    vocab_size: int = 50304
    hidden_size: int = 768
    num_layers: int = 12
    num_heads: int = 12
    max_position_embeddings: int = 1024
    intermediate_size: int = 0  # 0 -> 4*hidden
    dropout: float = 0.0
    layer_norm_epsilon: float = 1e-5
    use_recompute: bool = False
    # remat policy when use_recompute: "full" (save nothing) or "core_attn"
    # (save weight-matmul outputs, recompute only attention scores/softmax —
    # cheaper backward recompute for ~300 MB/layer more HBM at 1B scale)
    recompute_policy: str = "full"
    # lax.scan one decoder block over stacked per-layer params: XLA compiles
    # the block ONCE instead of inlining num_layers copies, so compile time
    # (and HLO size) stop growing with depth. Runtime cost is
    # one stack/unstack copy of the layer params per step (~2*P bytes of
    # HBM traffic, <1% of a training step). Training-path only (the KV-cache
    # decode path keeps per-layer buffers); requires dropout == 0 while
    # training (one trace would share a single mask across layers).
    use_scan_layers: bool = False
    tie_word_embeddings: bool = True
    # >0: fuse LM head + CE over sequence chunks of this many tokens (the
    # [tokens, vocab] logits tensor is never materialized)
    loss_chunk_size: int = 0

    def __post_init__(self):
        if self.intermediate_size == 0:
            self.intermediate_size = 4 * self.hidden_size


def _filter_logits(scaled, top_k: int, top_p: float, vocab: int):
    """Top-k and/or nucleus (top-p) logit filtering, jit-safe (static ks).

    Top-p keeps the smallest set of highest-probability tokens whose
    cumulative probability reaches ``top_p`` (a token survives when the
    cumulative probability BEFORE it is still < top_p, so the top token
    always survives)."""
    k_eff = min(int(top_k), vocab)
    if k_eff > 0:
        kth = jnp.sort(scaled, axis=-1)[:, -k_eff][:, None]
        scaled = jnp.where(scaled < kth, -jnp.inf, scaled)
    if 0.0 < float(top_p) < 1.0:
        desc = jnp.sort(scaled, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(desc.astype(jnp.float32), axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < top_p
        thresh = jnp.min(jnp.where(keep, desc, jnp.inf), axis=-1,
                         keepdims=True)
        scaled = jnp.where(scaled < thresh, -jnp.inf, scaled)
    return scaled


def gpt_tiny(**kw) -> "GPTConfig":
    return GPTConfig(vocab_size=1024, hidden_size=128, num_layers=2, num_heads=4,
                     max_position_embeddings=256, **kw)


def gpt_base(**kw) -> "GPTConfig":
    return GPTConfig(hidden_size=768, num_layers=12, num_heads=12, **kw)


def gpt_1p3b(**kw) -> "GPTConfig":
    return GPTConfig(hidden_size=2048, num_layers=24, num_heads=16,
                     max_position_embeddings=2048, **kw)


class GPTAttention(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        h = cfg.hidden_size
        self.num_heads = cfg.num_heads
        self.head_dim = h // cfg.num_heads
        self.qkv = ColumnParallelLinear(h, 3 * h, gather_output=False)
        self.proj = RowParallelLinear(h, h, input_is_parallel=True)
        self.dropout = cfg.dropout

    def forward(self, x, cache=None, start_pos=0):
        b, s, h = x.shape
        qkv = _serving_linear(self.qkv, x)  # [b, s, 3h] sharded on model axis
        qkv = M.reshape(qkv, [b, s, 3, self.num_heads, self.head_dim])
        qkv = constraint(qkv, "data", "sep", None, "model", None)
        qs = M.split(qkv, 3, axis=2)
        q, k, v = (M.squeeze(t, 2) for t in qs)
        if cache is not None and hasattr(cache, "update_and_attend"):
            # cache-protocol path: the cache object owns its storage layout
            # (the serving engine's paged KV arena) — it absorbs this
            # chunk's k/v, attends q against the stored history, and
            # returns (attn_out [b, s, heads, dim], successor cache)
            o, new_cache = cache.update_and_attend(q, k, v)
            oa = o._data if isinstance(o, Tensor) else o
            out = M.reshape(Tensor(oa), [b, s, h])
            return _serving_linear(self.proj, out), new_cache
        if cache is not None:
            # incremental decode: write this chunk's k/v into the
            # preallocated [b, max_len, heads, dim] buffers at start_pos and
            # attend over absolute positions <= the query's position
            k_buf, v_buf = cache
            kb = k_buf._data if isinstance(k_buf, Tensor) else k_buf
            vb = v_buf._data if isinstance(v_buf, Tensor) else v_buf

            def _cached_attn(qa, ka, va, kb, vb, pos):
                kb = jax.lax.dynamic_update_slice(kb, ka, (0, pos, 0, 0))
                vb = jax.lax.dynamic_update_slice(vb, va, (0, pos, 0, 0))
                max_len = kb.shape[1]
                j = jnp.arange(max_len)[None, :]
                i = pos + jnp.arange(qa.shape[1])[:, None]
                mask = (j <= i)[None, None]  # [1, 1, s, max_len]
                o = masked_attention(qa, kb, vb, mask)
                return o, kb, vb

            from ..core.dispatch import apply as _apply

            pos_arr = (start_pos._data if isinstance(start_pos, Tensor)
                       else start_pos)
            o, kb2, vb2 = _apply(
                _cached_attn, (q, k, v, Tensor(kb), Tensor(vb),
                               Tensor(jnp.asarray(pos_arr, jnp.int32))),
                {}, name="gpt_cached_attn")
            out = M.reshape(o, [b, s, h])
            return _serving_linear(self.proj, out), (kb2, vb2)
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             dropout_p=self.dropout if self.training else 0.0)
        out = M.reshape(out, [b, s, h])
        out = constraint(out, "data", "sep", "model")
        return _serving_linear(self.proj, out)


class GPTMLP(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.up = ColumnParallelLinear(cfg.hidden_size, cfg.intermediate_size, gather_output=False)
        self.down = RowParallelLinear(cfg.intermediate_size, cfg.hidden_size, input_is_parallel=True)

    def forward(self, x):
        return _serving_linear(
            self.down,
            F.gelu(_serving_linear(self.up, x), approximate=True))


class GPTDecoderLayer(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.ln1 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.attn = GPTAttention(cfg)
        self.ln2 = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        self.mlp = GPTMLP(cfg)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, x, cache=None, start_pos=0):
        if cache is not None:
            # serving's compiled steps: a scope is metadata on the device's
            # operations (a trace reader's handle) and changes no program
            with jax.named_scope("attention"):
                attn_out, new_cache = self.attn(self.ln1(x), cache=cache,
                                                start_pos=start_pos)
            x = x + self.drop(attn_out)
            with jax.named_scope("mlp"):
                x = x + self.drop(self.mlp(self.ln2(x)))
            return x, new_cache
        x = x + self.drop(self.attn(self.ln1(x)))
        x = x + self.drop(self.mlp(self.ln2(x)))
        return constraint(x, "data", "sep", None)


class GPTModel(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_position_embeddings, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)
        self.layers = nn.LayerList([GPTDecoderLayer(cfg) for _ in range(cfg.num_layers)])
        self.ln_f = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)

    def gen_kv_caches(self, batch, max_len, dtype=None):
        """Preallocated per-layer (k, v) buffers [b, max_len, heads, dim]
        for incremental decoding. dtype defaults to the model's own weight
        dtype — a bf16-cast serving model must not re-upcast its cache,
        and dynamic_update_slice requires exact dtype match with the
        produced k/v. Int8-quantized serving weights store int8 but
        COMPUTE in the embedding dtype — the cache follows
        :func:`serving_compute_dtype` (the one home of that fallback
        rule; weight-only quantization never quantizes this path's KV)."""
        if dtype is None:
            dtype = serving_compute_dtype(self)
        shape = [batch, max_len, self.cfg.num_heads,
                 self.cfg.hidden_size // self.cfg.num_heads]
        return [(creation.zeros(shape, dtype=dtype),
                 creation.zeros(shape, dtype=dtype))
                for _ in self.layers]

    def embed(self, input_ids, start_pos=None):
        """Token plus position embeddings; ``start_pos`` None is a whole
        sequence from position 0, a scalar or a per-sequence ``[b]`` vector
        the cached paths' offset."""
        s = input_ids.shape[1]
        if start_pos is not None:
            off = (start_pos._data if isinstance(start_pos, Tensor)
                   else start_pos)
            off = jnp.asarray(off)
            if off.ndim == 1:
                # per-sequence positions (the serving engine's slots each
                # sit at their own context length): [b] -> [b, s]
                pos = Tensor(off[:, None] + jnp.arange(s, dtype=jnp.int32))
            else:
                pos = Tensor(off + jnp.arange(s, dtype=jnp.int32))
        else:
            pos = creation.arange(0, s, dtype="int32")
        x = self.wte(input_ids) + self.wpe(pos)
        return constraint(self.drop(x), "data", "sep", None)

    def serving_linears(self):
        """``(site, linear)`` of every attention/MLP matmul, in model order:
        what the int8 quantizer and the LoRA arena target."""
        return [(f"{li}.{name}", lin)
                for li, blk in enumerate(self.layers)
                for name, lin in (("attn.qkv", blk.attn.qkv),
                                  ("attn.proj", blk.attn.proj),
                                  ("mlp.up", blk.mlp.up),
                                  ("mlp.down", blk.mlp.down))]

    def serving_embedding(self):
        return self.wte

    def forward(self, input_ids, caches=None, start_pos=0):
        x = self.embed(input_ids, start_pos if caches is not None else None)
        if caches is not None:
            new_caches = []
            for layer, cache in zip(self.layers, caches):
                x, nc = layer(x, cache=cache, start_pos=start_pos)
                new_caches.append(nc)
            return self.ln_f(x), new_caches
        from ..jit import scan_layers, scan_layers_wanted

        if self.cfg.use_scan_layers and scan_layers_wanted(
                self, traced=x._is_traced(), training=self.training,
                dropout_ps=(self.cfg.dropout,)):
            x = scan_layers(self.layers, x,
                            remat=(self.cfg.recompute_policy
                                   if self.cfg.use_recompute else False))
        elif self.cfg.use_recompute and x._is_traced():
            # fleet.recompute (NOT jax.checkpoint(layer) directly): remat's
            # jaxpr cache keys on the persistent layer and would replay
            # stale closure-captured param tracers on a re-trace
            from ..distributed.fleet.recompute import recompute

            for layer in self.layers:
                x = recompute(layer, x, policy=self.cfg.recompute_policy)
        else:
            for layer in self.layers:
                x = layer(x)
        return self.ln_f(x)


class GPTEmbeddingPipe(nn.Layer):
    """First pipeline section: token + position embeddings."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.wte = VocabParallelEmbedding(cfg.vocab_size, cfg.hidden_size)
        self.wpe = nn.Embedding(cfg.max_position_embeddings, cfg.hidden_size)
        self.drop = nn.Dropout(cfg.dropout)

    def forward(self, input_ids):
        b, s = input_ids.shape
        pos = creation.arange(0, s, dtype="int32")
        x = self.wte(input_ids) + self.wpe(pos)
        return constraint(self.drop(x), "data", "sep", None)


class GPTHeadPipe(nn.Layer):
    """Last pipeline section: final norm + (tied) LM head."""

    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.ln_f = nn.LayerNorm(cfg.hidden_size, epsilon=cfg.layer_norm_epsilon)
        if not cfg.tie_word_embeddings:
            self.head = ColumnParallelLinear(cfg.hidden_size, cfg.vocab_size, has_bias=False)

    def forward(self, x, shared_weight=None):
        h = self.ln_f(x)
        if self.cfg.tie_word_embeddings:
            logits = F.linear(h, M.transpose(shared_weight, [1, 0]))
        else:
            logits = self.head(h)
        return constraint(logits, "data", "sep", "model")


def gpt_pipe_loss(logits, labels):
    vocab = logits.shape[-1]
    return F.cross_entropy(
        M.reshape(logits, [-1, vocab]).astype("float32"),
        M.reshape(labels, [-1]),
        reduction="mean",
    )


def GPTForCausalLMPipe(cfg: GPTConfig, num_stages=None, num_microbatches: int = 1,
                       num_virtual_pipeline_stages=None):
    """Pipeline-parallel GPT (parity role: the reference's fleet
    GPTForPretrainingPipe built from LayerDesc lists). Decoder blocks form
    the stage-stacked homogeneous run; embedding/head run under GSPMD on
    every stage; tied embeddings share the wte Parameter object.
    ``num_virtual_pipeline_stages`` > 1 selects the interleaved schedule
    (ref:...pipeline_parallel.py:514)."""
    from ..distributed.fleet.meta_parallel import LayerDesc, PipelineLayer

    emb = GPTEmbeddingPipe(cfg)
    descs = [emb]
    descs += [LayerDesc(GPTDecoderLayer, cfg) for _ in range(cfg.num_layers)]
    head = GPTHeadPipe(cfg)
    if cfg.tie_word_embeddings:
        head_wrap = _TiedHead(head, emb)
        descs.append(head_wrap)
    else:
        descs.append(head)
    return PipelineLayer(
        descs,
        num_stages=num_stages,
        loss_fn=gpt_pipe_loss,
        num_microbatches=num_microbatches,
        recompute_interval=1 if cfg.use_recompute else 0,
        num_virtual_pipeline_stages=num_virtual_pipeline_stages,
    )


class _TiedHead(nn.Layer):
    """Binds the shared embedding weight into the head's forward (the
    SharedLayerDesc tie: same Parameter object, grads sum automatically)."""

    def __init__(self, head: GPTHeadPipe, emb: GPTEmbeddingPipe):
        super().__init__()
        self.head = head
        object.__setattr__(self, "_emb_ref", emb)  # not a sublayer: no double-count

    def forward(self, x):
        return self.head(x, shared_weight=self._emb_ref.wte.weight)


class GPTForCausalLM(nn.Layer):
    def __init__(self, cfg: GPTConfig):
        super().__init__()
        self.cfg = cfg
        self.gpt = GPTModel(cfg)
        if not cfg.tie_word_embeddings:
            self.lm_head = ColumnParallelLinear(cfg.hidden_size, cfg.vocab_size, has_bias=False)

    def forward(self, input_ids, labels=None):
        h = self.gpt(input_ids)
        if labels is not None and self.cfg.loss_chunk_size > 0:
            return self._chunked_loss(h, labels)
        if self.cfg.tie_word_embeddings:
            logits = F.linear(h, M.transpose(self.gpt.wte.weight, [1, 0]))
        else:
            logits = self.lm_head(h)
        logits = constraint(logits, "data", "sep", "model")
        if labels is None:
            return logits
        loss = F.cross_entropy(
            M.reshape(logits, [-1, self.cfg.vocab_size]).astype("float32"),
            M.reshape(labels, [-1]),
            reduction="mean",
        )
        return loss

    def _chunked_loss(self, h, labels):
        """Fused LM-head + cross-entropy scanned over sequence chunks: the
        full [tokens, vocab] logits tensor is never materialized — each
        chunk's logits live only inside its scan step, and jax.checkpoint
        recomputes them in backward. Trades ~1 extra head matmul per token
        for multi-GB of HBM traffic on large-vocab heads (the chunked-CE
        analog of the reference's fused softmax-CE CUDA kernel,
        ref:paddle/phi/kernels/fusion/)."""
        from ..core.dispatch import apply

        w = (self.gpt.wte.weight if self.cfg.tie_word_embeddings
             else self.lm_head.weight)
        chunk = int(self.cfg.loss_chunk_size)

        def _loss(ha, ya, wa):
            n_tok = ha.shape[0] * ha.shape[1]
            hf = ha.reshape(n_tok, ha.shape[-1])
            yf = ya.reshape(n_tok)
            pad = (-n_tok) % chunk
            if pad:
                hf = jnp.pad(hf, ((0, pad), (0, 0)))
                yf = jnp.pad(yf, (0, pad), constant_values=-100)
            hc = hf.reshape(-1, chunk, hf.shape[-1])
            yc = yf.reshape(-1, chunk)
            w_mat = (wa.T if self.cfg.tie_word_embeddings else wa)  # [H, V]

            @jax.checkpoint
            def body(carry, xs):
                h_i, y_i = xs
                # matmul in the ambient dtype (bf16 under AMP — this op is
                # on the autocast white list); fp32 only in the reduction
                logits = h_i @ w_mat  # [chunk, V]
                lse = jax.scipy.special.logsumexp(
                    logits.astype(jnp.float32), axis=-1)
                valid = y_i != -100  # F.cross_entropy's ignore_index
                safe = jnp.where(valid, jnp.clip(y_i, 0), 0)
                picked = jnp.take_along_axis(
                    logits, safe[:, None], axis=-1)[:, 0].astype(jnp.float32)
                vf = valid.astype(jnp.float32)
                tot, cnt = carry
                return (tot + ((lse - picked) * vf).sum(),
                        cnt + vf.sum()), None

            (total, count), _ = jax.lax.scan(
                body, (jnp.float32(0.0), jnp.float32(0.0)), (hc, yc))
            # normalize by VALID tokens — identical to F.cross_entropy's
            # weighted mean, so toggling chunking never rescales the loss
            return total / jnp.maximum(count, 1.0)

        return apply(_loss, (h, labels, w), {}, name="chunked_lm_loss")

    # ---- the engine<->model seam (models/serving_seam.py)

    def serving_spec(self) -> ServingSpec:
        c = self.cfg
        return ServingSpec(
            vocab_size=int(c.vocab_size),
            max_positions=int(c.max_position_embeddings),
            layers=(KVLayerState(int(c.num_heads),
                                 int(c.hidden_size // c.num_heads)),)
            * int(c.num_layers))

    def serving_embed(self, ids, positions):
        return self.gpt.embed(ids, positions)

    def serving_layers(self):
        return self.gpt.layers

    def serving_final(self, x):
        return self.gpt.ln_f(x)

    def serving_head(self, h_last):
        return self._head_logits(h_last)

    def serving_linears(self):
        return self.gpt.serving_linears()

    def serving_embedding(self):
        return self.gpt.wte

    def _head_logits(self, h_last):
        """Next-token logits [b, vocab] from last hidden states [b, hidden]
        through the (tied) LM head. Raw-array in, raw-array out — the one
        head computation shared by ``generate()`` and the serving engine's
        compiled slot step (parity depends on them running the same ops)."""
        from ..core import rng as prng

        with prng.key_guard(jax.random.key(0)):
            if self.cfg.tie_word_embeddings:
                w = self.gpt.wte.weight
                out = F.linear(Tensor(h_last[:, None]),
                               M.transpose(w, [1, 0]))
            else:
                out = self.lm_head(Tensor(h_last[:, None]))
        return out._data[:, 0]

    def verify_logits(self, h_seq):
        """Verify-k head: next-token logits ``[b, s, vocab]`` for a chunk
        of ``s`` hidden states ``[b, s, hidden]`` — the head computation of
        the serving engine's speculative verify step. Deliberately NOT one
        big ``[b*s, hidden]`` matmul: each position routes through
        :meth:`_head_logits` with the exact ``[b, hidden]`` shape the
        compiled decode step uses, so verifying k proposals is bit-identical
        to running k single-token decode steps (shape-dependent reduction
        order in the batched matmul would break the greedy-parity
        guarantee; see tests/test_spec_decode.py). ``s`` is static (the
        engine's ``k+1``), so the unroll costs nothing at runtime."""
        s = h_seq.shape[1]
        return jnp.stack([self._head_logits(h_seq[:, j]) for j in range(s)],
                         axis=1)

    def generate(self, input_ids, max_new_tokens: int = 32,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0, eos_token_id: int = -1,
                 seed: int = 0, use_cache: bool = True,
                 stop_token_id=None, sampling=None):
        """Compiled autoregressive decoding: ONE jitted program — prefill
        plus a ``lax.scan`` over decode steps — so the whole loop runs
        on-device with no host round trips (the XLA-native replacement for
        the reference's per-step executor decode).

        use_cache=True (default) decodes incrementally against preallocated
        per-layer KV buffers (O(1) model forward per step);
        use_cache=False re-runs the causal forward on a max-length padded
        buffer each step (more FLOPs, zero extra state — useful as a
        cross-check, and what the cache path is tested against).

        ``sampling`` (a :class:`paddle_tpu.serving.SamplingParams`) routes
        next-token selection through the serving engine's ONE sampling
        core (``serving.sampling.sample_tokens``) with *positional* PRNG
        keys — ``fold_in(PRNGKey(seed + row), context_index)`` — so a
        seeded ``generate(sampling=...)`` call is the bit-level parity
        anchor for a slot-engine request carrying the same params
        (``temperature=0`` reproduces greedy decode exactly). It overrides
        the legacy ``do_sample``/``temperature``/``top_k``/``top_p``/
        ``seed`` arguments, whose sequential-key behavior is kept
        bit-compatible for existing callers.

        ``stop_token_id`` enables per-sequence termination: each sequence
        carries a finished mask, finished rows stop mutating their KV
        cache and output buffer, and the decode loop (``lax.while_loop``
        instead of ``scan``) exits early once EVERY sequence has emitted
        the stop token — a batch of short answers no longer pays for
        ``max_new_tokens`` steps. Takes precedence over ``eos_token_id``
        (the legacy fill-only behavior, kept bit-compatible).

        Returns [batch, prompt_len + max_new_tokens] token ids; positions
        after a stop/eos hit are filled with that token.
        """
        was_training = self.training
        self.eval()
        try:
            ids = input_ids._data if isinstance(input_ids, Tensor) else jnp.asarray(input_ids)
            b, prompt_len = ids.shape
            total = prompt_len + max_new_tokens
            if total > self.cfg.max_position_embeddings:
                raise ValueError(
                    f"prompt+new tokens {total} exceeds "
                    f"max_position_embeddings {self.cfg.max_position_embeddings}")

            params, buffers = self.functional_state()
            objs = list(params.values()) + list(buffers.values())
            arrays = [p._data for p in objs]
            from ..jit import _swap_data

            from ..core import rng as prng

            def logits_at(param_arrays, buf, pos):
                with _swap_data(objs, list(param_arrays)):
                    with prng.key_guard(jax.random.key(0)):
                        full = self(Tensor(buf))._data  # [b, total, V]
                return jax.lax.dynamic_index_in_dim(full, pos, axis=1,
                                                    keepdims=False)

            # one compiled program per decode configuration: jit's cache is
            # keyed on function identity, so the closure is memoized here —
            # repeat generate() calls with the same shapes/flags reuse the
            # executable instead of retracing the whole scan
            from ..core import compile_cache, flags as _flags

            # the donation flag is part of the key: toggling it must build
            # a fresh executable, not reuse the old donation setting
            donate = bool(use_cache and _flags.flag("decode_donate"))
            stop = None if stop_token_id is None else int(stop_token_id)
            # the serving-quant tag joins the key like the donation flag:
            # quantizing the weights after a runner was memoized must build
            # a fresh executable over the int8 payload, never reuse one
            # traced against float weights
            # the seed is RUNTIME data on both sampling paths (threaded
            # through the `key` argument slot), so re-seeding never
            # rebuilds the program: the cache key carries the sampling
            # params with the seed stripped
            import dataclasses as _dc

            samp_key = (None if sampling is None
                        else _dc.replace(sampling, seed=0))
            # sampling.seed None falls back to the legacy `seed` argument
            # (generate() stays reproducible-by-default, unlike serving
            # submits which pin fresh entropy per request)
            key_arg = (jnp.int32(seed if sampling.seed is None
                                 else sampling.seed)
                       if sampling is not None else jax.random.key(seed))
            # the mesh fingerprint joins the key like the quant/donation
            # tags: installing (or changing) a device mesh between calls
            # must rebuild the runner over the newly committed shardings,
            # never replay one traced against the old placement
            from ..distributed.sharding_util import mesh_axes_key

            cache_key = (b, prompt_len, max_new_tokens, bool(do_sample),
                         float(temperature), int(top_k), float(top_p),
                         int(eos_token_id), bool(use_cache), donate, stop,
                         getattr(self, "_serving_quant", 0), samp_key,
                         mesh_axes_key())
            cached = getattr(self, "_gen_cache", None)
            if cached is not None and cached[0] == cache_key:
                compile_cache.bump("decode.cache_hits")
                return Tensor(cached[1](arrays, ids, key_arg))
            compile_cache.bump("decode.builds")

            def sample_next(logits, done, key, pos):
                if sampling is not None:
                    # the serving engine's sampling core with positional
                    # keys: row i's token at context index `pos` draws
                    # under fold_in(PRNGKey(seed+i), pos) — the engine
                    # parity anchor (see serving.sampling). On this path
                    # `key` carries the TRACED int32 base seed (runtime
                    # data: re-seeding reuses the compiled program).
                    from ..serving.sampling import sample_tokens

                    seeds = key + jnp.arange(b, dtype=jnp.int32)
                    nxt = sample_tokens(
                        logits,
                        jnp.full((b,), sampling.temperature, jnp.float32),
                        jnp.full((b,), sampling.top_k, jnp.int32),
                        jnp.full((b,), sampling.top_p, jnp.float32),
                        seeds, jnp.full((b,), pos, jnp.int32))
                elif do_sample:
                    key, sub = jax.random.split(key)
                    scaled = logits / jnp.maximum(temperature, 1e-6)
                    scaled = _filter_logits(scaled, top_k, top_p,
                                            self.cfg.vocab_size)
                    nxt = jax.random.categorical(sub, scaled)
                else:
                    nxt = jnp.argmax(logits, axis=-1)
                nxt = nxt.astype(jnp.int32)
                if stop is not None:
                    nxt = jnp.where(done, stop, nxt)
                    done = done | (nxt == stop)
                elif eos_token_id >= 0:
                    nxt = jnp.where(done, eos_token_id, nxt)
                    done = done | (nxt == eos_token_id)
                return nxt, done, key

            lm_head_logits = self._head_logits

            def fresh_out_buf(dtype):
                # with a stop token the loop can exit before writing every
                # position — pre-fill the tail so early exit reads as
                # "finished rows padded with stop"
                if stop is not None:
                    return jnp.full((b, total), stop, dtype)
                return jnp.zeros((b, total), dtype)

            def decode_cached(param_arrays, start_ids, key, caches0,
                              out_buf):
                # with FLAGS_decode_donate, caches0 / out_buf are allocated
                # by the caller and DONATED: XLA writes the KV cache and
                # the token buffer into the passed allocations instead of
                # double-buffering them — the KV cache is the dominant
                # per-call allocation of the serving loop. With the flag
                # off they are created inside the program (the copying
                # build, identical to the pre-donation behavior).
                with _swap_data(objs, list(param_arrays)):
                    with prng.key_guard(jax.random.key(0)):
                        # prefill the prompt in one pass
                        h, caches = self.gpt(
                            Tensor(start_ids),
                            caches=[(Tensor(k), Tensor(v))
                                    for k, v in caches0],
                            start_pos=0)
                        caches = [(k._data if isinstance(k, Tensor) else k,
                                   v._data if isinstance(v, Tensor) else v)
                                  for k, v in caches]
                        h_last = h._data[:, -1]

                def step(carry):
                    caches, h_last, pos, done, key, out_buf = carry
                    with _swap_data(objs, list(param_arrays)):
                        logits = lm_head_logits(h_last)
                        nxt, done, key = sample_next(logits, done, key, pos)
                        # finished rows: nxt is forced to the stop token and
                        # the buffer was pre-filled with it, so this write
                        # is value-preserving for them
                        out_buf = jax.lax.dynamic_update_slice(
                            out_buf, nxt[:, None], (0, pos))
                        with prng.key_guard(jax.random.key(0)):
                            h, new_caches = self.gpt(
                                Tensor(nxt[:, None]),
                                caches=[(Tensor(k), Tensor(v))
                                        for k, v in caches],
                                start_pos=pos)
                        new_caches = [
                            (k._data if isinstance(k, Tensor) else k,
                             v._data if isinstance(v, Tensor) else v)
                            for k, v in new_caches]
                        if stop is not None:
                            # finished rows freeze their KV state (their
                            # stop-token k/v is never attended to anyway —
                            # they only ever re-emit stop)
                            d4 = done[:, None, None, None]
                            new_caches = [
                                (jnp.where(d4, ko, kn), jnp.where(d4, vo, vn))
                                for (ko, vo), (kn, vn) in zip(caches,
                                                              new_caches)]
                    return (new_caches, h._data[:, 0], pos + 1, done, key,
                            out_buf)

                out_buf = jax.lax.dynamic_update_slice(out_buf, start_ids,
                                                       (0, 0))
                done0 = jnp.zeros((b,), jnp.bool_)
                carry0 = (caches, h_last, jnp.int32(prompt_len), done0, key,
                          out_buf)
                if stop is not None:
                    # early exit: stop decoding the moment every sequence
                    # finished (or the token budget ran out)
                    def cond(carry):
                        _, _, pos, done, _, _ = carry
                        return (pos < total) & ~jnp.all(done)

                    carry = jax.lax.while_loop(cond, step, carry0)
                else:
                    carry, _ = jax.lax.scan(lambda c, _: (step(c), None),
                                            carry0, None,
                                            length=max_new_tokens)
                return carry[5]

            def decode(param_arrays, start_ids, key):
                buf = fresh_out_buf(start_ids.dtype)
                buf = jax.lax.dynamic_update_slice(buf, start_ids, (0, 0))

                def step(carry):
                    buf, pos, done, key = carry
                    logits = logits_at(param_arrays, buf, pos - 1)
                    nxt, done, key = sample_next(logits, done, key, pos)
                    buf = jax.lax.dynamic_update_slice(
                        buf, nxt.astype(buf.dtype)[:, None], (0, pos))
                    return (buf, pos + 1, done, key)

                done0 = jnp.zeros((b,), jnp.bool_)
                carry0 = (buf, jnp.int32(prompt_len), done0, key)
                if stop is not None:
                    def cond(carry):
                        _, pos, done, _ = carry
                        return (pos < total) & ~jnp.all(done)

                    carry = jax.lax.while_loop(cond, step, carry0)
                else:
                    carry, _ = jax.lax.scan(lambda c, _: (step(c), None),
                                            carry0, None,
                                            length=max_new_tokens)
                return carry[0]

            if donate:
                jitted = jax.jit(decode_cached, donate_argnums=(3, 4))

                def runner(param_arrays, start_ids, key):
                    # fresh allocations per call: they are donated into the
                    # compiled loop (invalid afterwards), so they cannot be
                    # hoisted out of the runner
                    caches0 = [(c[0]._data, c[1]._data)
                               for c in self.gpt.gen_kv_caches(b, total)]
                    out_buf = fresh_out_buf(start_ids.dtype)
                    import warnings

                    with warnings.catch_warnings():
                        # donation is best-effort: XLA aliases the buffers
                        # it can (out_buf + part of the KV set) and warns
                        # about the rest — expected here, not actionable
                        warnings.filterwarnings(
                            "ignore",
                            message="Some donated buffers were not usable")
                        return jitted(param_arrays, start_ids, key, caches0,
                                      out_buf)
            elif use_cache:
                # copying build: the buffers materialize inside the
                # compiled program (no host-side allocation per call)
                def decode_alloc(param_arrays, start_ids, key):
                    caches0 = [(c[0]._data, c[1]._data)
                               for c in self.gpt.gen_kv_caches(b, total)]
                    out_buf = fresh_out_buf(start_ids.dtype)
                    return decode_cached(param_arrays, start_ids, key,
                                         caches0, out_buf)

                runner = jax.jit(decode_alloc)
            else:
                runner = jax.jit(decode)
            self._gen_cache = (cache_key, runner)
            return Tensor(runner(arrays, ids, key_arg))
        finally:
            if was_training:
                self.train()
