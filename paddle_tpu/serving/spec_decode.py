"""Speculative decoding over the paged slot arena (draft-propose, verify-k).

The slot engine's decode step emits ONE token per compiled call — the last
single-chip serving lever (ROADMAP item 3): per-iteration cost is dominated
by dispatch + per-op overhead, not model FLOPs, for small models, and by
memory-bound single-token forwards for big ones. Speculative decoding
amortizes both: a cheap **draft** proposes ``k`` tokens, the **target**
model verifies all ``k`` in ONE batched compiled call, and the longest
prefix of proposals matching the target's own greedy choices is accepted —
plus the target's correction token on the first mismatch. Greedy target
semantics are *bit-identical* to plain decode by construction:

* Every accepted token equals what sequential greedy decode would have
  emitted: the verify step's sub-step ``j`` computes the target's argmax
  after the true context extended by the already-matched proposals, so the
  emitted stream is exactly the target's greedy continuation regardless of
  how good (or garbage) the draft is. A bad draft costs speed, never
  correctness.
* The verify program is deliberately **unrolled into k+1 single-token
  sub-steps inside one jitted call**, each running the exact ops (same
  shapes, same :class:`~.cache_views.PagedCacheView`, same
  ``GPTForCausalLM._head_logits``) as the plain compiled decode step. A
  single ``[S, k+1]`` batched forward would be mathematically equal but
  NOT bitwise equal (shape-dependent matmul reduction order), which would
  silently break the parity harness — see ``tests/test_spec_decode.py``.

Two modes, selected by whether a draft model is configured:

* **Draft mode** (``ServingConfig.draft_model``): a small GPT proposes
  from its own KV cache — a second *namespace* of the shared
  :class:`~.kv_arena.KVArena` (same block ids, same free-list/refcount
  accounting, physically separate pools shaped for the draft's
  layers/heads) addressed through a second per-slot block table. Proposal
  + verification fuse into ONE compiled call per iteration. Rejected
  draft/target KV entries are never rolled back by copying: positions are
  host-side runtime data, the per-position attention mask hides stale
  entries, and the next iteration overwrites them — accept/reject NEVER
  recompiles (assertable via the ``serving.decode_compiles`` trace
  counter).
* **Lockstep self-draft** (no draft model): the target proposes for
  itself — ``k`` unrolled target sub-steps per dispatch, acceptance
  structurally 1.0. This is fused multi-token greedy decode: ~2x
  single-stream tokens/s on the CPU bench purely from dispatch/overhead
  amortization, still bit-identical.

Both are gated behind ``FLAGS_serving_spec_k`` (0 = off, exact PR 8/9
behavior). ``k`` is static per engine (part of the program key, like
donation); per-slot speculation depth is clamped at runtime (``allow``)
so token budgets and block reservations are never overrun — a slot one
token from its budget degenerates to plain decode via lane masking, with
zero recompiles.

Counters (``serving.metrics``): ``spec.proposed`` / ``spec.accepted`` /
``spec.rollback_tokens`` (proposed-but-rejected) / ``spec.emitted`` /
``spec.iterations``, plus the ``spec.acceptance_rate`` gauge.
"""
from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np

from ..core import compile_cache, flags
from ..core.tensor import Tensor
from . import metrics, telemetry
from .kv_arena import Reservation


def _ceil_div(a: int, b: int) -> int:
    return -(-a // b)


class SpecDecoder:
    """Speculative-decoding sidecar of one :class:`~.engine.ServingEngine`.

    Owns the draft half of the state: the arena's ``"draft"`` pool
    namespace, the second per-slot block table (+ its reservations), the
    fused propose+verify compiled program, and the acceptance accounting.
    The engine drives it: ``alloc_slot``/``prefill`` at admission,
    ``release_slot`` at retire, ``rebuild`` after a supervisor recovery,
    and ``step`` instead of ``decode_step`` when speculation is on.
    """

    NAMESPACE = "draft"

    def __init__(self, engine, draft_model=None, k: Optional[int] = None):
        self.engine = engine
        self.k = int(k if k is not None else flags.flag("serving_spec_k"))
        if self.k < 1:
            raise ValueError("SpecDecoder needs k >= 1 "
                             "(FLAGS_serving_spec_k)")
        self.draft = draft_model
        self._d_objs: List = []
        self._d_arrays: List = []
        s = engine.num_slots
        # the SECOND per-slot block-table namespace: draft KV lives in the
        # arena's "draft" pools at these (privately owned) block ids
        self._bt_host = np.zeros((s, engine.blocks_per_slot), np.int32)
        self._bt_dev = None
        self._filled = np.zeros(s, np.int32)
        self._res: List[Optional[Reservation]] = [None] * s
        # trace-time counters (the assertable no-recompile invariant) and
        # lifetime acceptance accounting for THIS engine stack
        self.spec_traces = 0
        self.draft_prefill_traces: Dict[int, int] = {}
        self.proposed = 0
        self.accepted = 0
        self.rollback_tokens = 0
        self.emitted = 0
        self.iterations = 0
        self._spec_jit = None
        self._prefill_jits: Dict[int, object] = {}
        self.quant_draft = bool(getattr(engine, "quant_draft", False))
        if self.draft is not None:
            self.draft.eval()
            dspec = self.draft.serving_spec()
            if any(st.kind != "kv" for st in dspec.layers):
                raise ValueError("speculative decoding needs a draft model "
                                 "whose layers all keep paged KV state")
            if dspec.vocab_size != engine.vocab:
                raise ValueError(
                    f"draft vocab {dspec.vocab_size} != target vocab "
                    f"{engine.vocab}: proposals would be meaningless ids")
            if self.quant_draft:
                # int8-quantize the draft's weights in place (idempotent)
                # BEFORE the functional-state snapshot: the fused
                # propose+verify program then streams the int8 payload.
                # Verification keeps target-greedy semantics, so this only
                # moves acceptance/speed — never the emitted tokens.
                from ..models.serving_seam import quantize_serving_weights

                n = quantize_serving_weights(self.draft)
                if n:
                    metrics.bump("quant.draft_layers", n)
            params, buffers = self.draft.functional_state()
            self._d_objs = list(params.values()) + list(buffers.values())
            self._d_arrays = [p._data for p in self._d_objs]
            self._bind_namespace()

    # ------------------------------------------------------------- arena

    @property
    def draft_mode(self) -> bool:
        return self.draft is not None

    def _bind_namespace(self) -> None:
        from ..models.serving_seam import serving_compute_dtype

        kv = self.draft.serving_spec().kv_layers()
        # compute dtype, not storage dtype: an int8-quantized draft still
        # produces (and attends over) float k/v; with FLAGS_serving_quant_kv
        # the namespace inherits the arena's int8+scale-pool layout
        kv_dtype = serving_compute_dtype(self.draft)
        self.engine.arena.add_namespace(
            self.NAMESPACE, len(kv), kv[0].kv_heads, kv[0].head_dim,
            kv_dtype)

    def rebuild(self) -> None:
        """Re-bind to the engine's freshly rebuilt arena (supervisor
        recovery): a new draft namespace over the new arena, all slot
        state cleared. Compiled programs depend only on shapes, so the
        rebuilt decoder re-serves with zero recompiles; journal replays
        re-prefill the draft cache per slot (admit runs the draft prefill
        over prompt+journal — the draft cache is *reconstructed*, not
        approximated)."""
        if self.draft is not None:
            self._bind_namespace()
        self._bt_host[:] = 0
        self._bt_dev = None
        self._filled[:] = 0
        self._res = [None] * self.engine.num_slots

    # ----------------------------------------------------- slot lifecycle

    def blocks_needed(self, prompt_len: int, max_new_tokens: int) -> int:
        """Extra blocks an admission must budget for the draft table
        (0 in lockstep mode — the target's own cache is the only one).
        The draft writes positions ``0..limit-2`` worst case; sized like
        the target's budget for simplicity (same ceil)."""
        if not self.draft_mode:
            return 0
        return _ceil_div(prompt_len + max_new_tokens,
                         self.engine.block_size)

    def alloc_slot(self, slot: int, prompt_len: int,
                   max_new_tokens: int) -> None:
        """Reserve the slot's draft-block budget (two-phase, same arena
        free list). Raises ArenaExhaustedError on pressure — the caller
        (``ServingEngine._admit_setup``) unwinds the whole admission."""
        if not self.draft_mode:
            return
        self._res[slot] = self.engine.arena.reserve(
            self.blocks_needed(prompt_len, max_new_tokens))

    def release_slot(self, slot: int) -> None:
        res = self._res[slot]
        self._res[slot] = None
        if res is not None:
            res.release()
        self._bt_host[slot, :] = 0
        self._bt_dev = None
        self._filled[slot] = 0

    def reserved_blocks(self, slot: int) -> int:
        res = self._res[slot]
        return res.total if res is not None else 0

    def slot_tables(self) -> List[List[int]]:
        """Per-slot draft block-id lists for occupied slots — the second
        namespace's contribution to the arena invariant audit (draft
        blocks are privately owned: refcount must be exactly 1 per table
        entry)."""
        out = []
        for slot in range(self.engine.num_slots):
            n = int(self._filled[slot])
            if n:
                out.append([int(b) for b in self._bt_host[slot, :n]])
        return out

    def _grow(self, slot: int, pos_max: int) -> None:
        """Take draft blocks until the table covers ``pos_max`` (runtime
        data; the reservation guarantees take() cannot fail)."""
        bs = self.engine.block_size
        need = pos_max // bs + 1
        res = self._res[slot]
        while int(self._filled[slot]) < need:
            bi = int(self._filled[slot])
            self._bt_host[slot, bi] = res.take()
            self._filled[slot] = bi + 1
            self._bt_dev = None

    # ----------------------------------------------------------- prefill

    def prefill(self, slot: int, ctx: np.ndarray) -> None:
        """Scatter the draft model's KV for the whole context into the
        slot's draft blocks (one bucketed compiled call — the draft
        mirror of the engine's full prefill). Runs at admission and at
        journal replay, so recovery reconstructs the draft cache exactly;
        no-op in lockstep mode."""
        if not self.draft_mode:
            return
        import jax.numpy as jnp

        engine = self.engine
        clen = int(ctx.shape[0])
        self._grow(slot, clen - 1)
        p_bucket = compile_cache.prefill_bucket(
            clen, engine.max_model_len, engine.prefill_bucket_min)
        ids = np.zeros((1, p_bucket), np.int32)
        ids[0, :clen] = ctx
        mbp = _ceil_div(p_bucket, engine.block_size)
        rows = np.zeros(mbp, np.int32)
        n = int(self._filled[slot])
        rows[:n] = self._bt_host[slot, :n]
        fn = self._get_prefill(p_bucket)
        new_pools = engine._call(
            fn, self._d_arrays, jnp.asarray(ids), jnp.int32(clen),
            engine.arena.ns_pools(self.NAMESPACE), jnp.asarray(rows),
            name="serving.draft_prefill")
        engine.arena.set_ns_pools(self.NAMESPACE, new_pools)

    def _get_prefill(self, p_bucket: int):
        fn = self._prefill_jits.get(p_bucket)
        if fn is not None:
            return fn
        import jax

        from ..core import rng as prng
        from ..jit import _swap_data
        from ..models.serving_seam import forward_cached
        from .cache_views import CapturePrefillView, scatter_blocks

        draft = self.draft
        n_layers = len(draft.serving_spec().layers)
        bs = self.engine.block_size

        # the draft prefill only needs the chunk k/v scattered — no head
        # logits (the target's prefill already emitted the first token)
        def draft_prefill(arrays, ids, true_len, pools, rows):
            self.draft_prefill_traces[p_bucket] = \
                self.draft_prefill_traces.get(p_bucket, 0) + 1
            compile_cache.bump("serving.prefill_compiles")
            views = [CapturePrefillView() for _ in range(n_layers)]
            with _swap_data(self._d_objs, list(arrays)):
                with prng.key_guard(jax.random.key(0)):
                    _, chunks = forward_cached(draft, Tensor(ids), views, 0)
            return [scatter_blocks(entry, rows, true_len, chunk.k[0],
                                   chunk.v[0], bs)
                    for chunk, entry in zip(chunks, pools)]

        fn = (jax.jit(draft_prefill, donate_argnums=(3,))
              if self.engine.donate else jax.jit(draft_prefill))
        self._prefill_jits[p_bucket] = fn
        return fn

    # -------------------------------------------------------------- step

    def _get_spec_step(self):
        """The fused per-iteration program: draft proposes k tokens
        (draft mode), then the target verifies k+1 positions — every
        sub-step an exact single-token replica of the plain decode step
        (bit-parity by construction). One compiled call per iteration;
        all per-slot state (positions, tables, activity, per-lane
        speculation depth ``allow``) is runtime data."""
        if self._spec_jit is not None:
            return self._spec_jit
        import jax
        import jax.numpy as jnp

        from ..core import rng as prng
        from ..jit import _swap_data
        from ..models.serving_seam import forward_cached
        from .cache_views import PagedCacheView

        engine = self.engine
        model = engine._model
        draft = self.draft
        k = self.k
        bs = engine.block_size

        use_kernel = engine.decode_kernel
        kmesh = engine._kernel_mesh

        def _fwd(m, objs, arrays, pools, bt, positions, toks, act):
            """One single-token model forward — same ops, shapes and view
            class as ``ServingEngine._get_step``'s body, head excluded
            (``kernel=`` rides along: on the engine's kernel route
            every draft/verify sub-step reads K/V through the block
            tables via the Pallas paged-decode kernel too, and ``mesh=``
            with it — on a multi-device mesh the sub-steps run the
            sharded kernel per model-shard like the main decode step).
            Returns (last hidden [S, H], new pools)."""
            views = [PagedCacheView(entry, bt, positions, act, bs,
                                    kernel=use_kernel, mesh=kmesh)
                     for entry in pools]
            with _swap_data(objs, list(arrays)):
                with prng.key_guard(jax.random.key(0)):
                    h, new_views = forward_cached(
                        m, Tensor(toks[:, None]), views, positions)
            return h._data[:, 0], [v.entry for v in new_views]

        def _sub_step(m, objs, arrays, pools, bt, positions, toks, act):
            """Forward + head + greedy pick — one full decode sub-step."""
            h, new_pools = _fwd(m, objs, arrays, pools, bt, positions,
                                toks, act)
            with _swap_data(objs, list(arrays)):
                logits = m.serving_head(h)
            nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            return nxt, new_pools

        if draft is not None:
            def spec_step(t_arrays, d_arrays, t_pools, d_pools, t_bt, d_bt,
                          positions, last_tok, active, allow):
                self.spec_traces += 1  # trace-time no-recompile counter
                compile_cache.bump("serving.decode_compiles")
                if use_kernel:
                    # trace-time: verify/draft sub-steps route through the
                    # paged-decode kernel; churn must never re-lower it
                    metrics.bump("kernel.verify_traces")
                # ---- draft proposes k tokens from its own namespace;
                # lanes past their allowed depth are masked (writes to
                # scratch, outputs ignored host-side)
                toks = last_tok
                props = []
                for j in range(k):
                    act_j = active & (j < allow)
                    toks, d_pools = _sub_step(
                        draft, self._d_objs, d_arrays, d_pools, d_bt,
                        positions + j, toks, act_j)
                    props.append(toks)
                proposals = jnp.stack(props, 1)  # [S, k]
                # ---- target verifies k+1 positions: sub-step j feeds the
                # j-th proposal (j=0: the real last token); the verify-k
                # head (the model's own head once per position: each runs
                # the exact [S, hidden] shape of the plain decode step, so
                # verifying is bit-identical to k single-token steps)
                # then scores every position
                toks = last_tok
                hs = []
                for j in range(k + 1):
                    act_j = active & (j <= allow)
                    h_j, t_pools = _fwd(
                        model, engine._objs, t_arrays, t_pools, t_bt,
                        positions + j, toks, act_j)
                    hs.append(h_j)
                    if j < k:
                        toks = proposals[:, j]
                with _swap_data(engine._objs, list(t_arrays)):
                    logits = jnp.stack(
                        [model.serving_head(h_j) for h_j in hs], 1)
                tgt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return tgt, proposals, t_pools, d_pools

            fn = (jax.jit(spec_step, donate_argnums=(2, 3))
                  if engine.donate else jax.jit(spec_step))
        else:
            def spec_step(t_arrays, t_pools, t_bt, positions, last_tok,
                          active, allow):
                self.spec_traces += 1  # trace-time no-recompile counter
                compile_cache.bump("serving.decode_compiles")
                if use_kernel:
                    # trace-time: verify/draft sub-steps route through the
                    # paged-decode kernel; churn must never re-lower it
                    metrics.bump("kernel.verify_traces")
                # lockstep self-draft: k fused target sub-steps, each
                # feeding the previous sub-step's own output — multi-token
                # greedy decode in one dispatch, acceptance structurally 1
                toks = last_tok
                outs = []
                for j in range(k):
                    act_j = active & (j <= allow)
                    toks, t_pools = _sub_step(
                        model, engine._objs, t_arrays, t_pools, t_bt,
                        positions + j, toks, act_j)
                    outs.append(toks)
                return jnp.stack(outs, 1), t_pools

            fn = (jax.jit(spec_step, donate_argnums=(1,))
                  if engine.donate else jax.jit(spec_step))
        self._spec_jit = fn
        return fn

    def step(self) -> Dict[int, List[int]]:
        """One speculative iteration over every active slot. Returns
        ``{slot: accepted_tokens}`` — 1 to k tokens per slot, every one
        exactly what sequential greedy decode would have emitted. Engine
        positions / last-token state advance here; rejected speculation
        rolls back as pure position bookkeeping (``spec.rollback_tokens``)
        — stale KV is masked by position and overwritten next iteration,
        so accept/reject never touches compiled code.

        **Sampling/constraint/adapter compose rule**: speculation's
        verify step is greedy-argmax over base weights with no vocab
        mask, so a slot carrying non-greedy sampling, a constraint mask,
        or a LoRA adapter (``engine.spec_ineligible()``) FALLS BACK to
        the plain per-slot decode step for this iteration — one token,
        sampled/masked/adapted exactly like a speculation-off engine,
        never an off-distribution token. The two compiled calls cover
        disjoint lane sets of the same arena; both are warm programs
        (zero recompiles). Verifying against the sampled distribution is
        follow-up work (docs/serving.md)."""
        engine = self.engine
        ineligible = engine.spec_ineligible()
        act_spec = engine._active & ~ineligible
        act_plain = engine._active & ineligible
        out: Dict[int, List[int]] = {}
        if act_spec.any():
            # the fused propose+verify dispatch alone (the plain-decode
            # fallback lanes below are latency.decode_step samples)
            with telemetry.phase("spec_verify", engine.hists):
                out.update(self._spec_step(act_spec))
        if act_plain.any():
            # per-slot fallback: sampled/constrained/adapter lanes decode
            # one plain (sampling-core) token through the classic step
            metrics.bump("sampling.spec_fallback_slots",
                         int(act_plain.sum()))
            from ..core import resilience

            resilience.bump("sampling.spec_fallbacks")
            toks = engine.decode_step(active=act_plain)
            for slot in np.flatnonzero(act_plain):
                out[slot] = [int(toks[slot])]
        return out

    def _spec_step(self, act_spec: np.ndarray) -> Dict[int, List[int]]:
        """The fused propose+verify dispatch over the speculation-eligible
        lanes (``act_spec`` — greedy, unconstrained, adapter-0)."""
        import jax.numpy as jnp

        engine = self.engine
        k = self.k
        active_slots = np.flatnonzero(act_spec)
        # per-lane speculation depth: writes this iteration reach position
        # pos+allow (target) / pos+allow-1 (draft), clamped so neither the
        # block reservation nor the model's position budget is overrun. A
        # lane at allow=0 degenerates to plain single-token decode.
        allow = np.zeros(engine.num_slots, np.int32)
        cap = k if self.draft_mode else k - 1
        for slot in active_slots:
            # tokens this slot may still emit: the pending last token (at
            # context index `pos`, not yet written) already counts toward
            # the budget, so remaining = limit - pos - 1; emission this
            # iteration is bounded by allow+1 <= remaining — the engine
            # never over-emits past the request budget
            remaining = (int(engine._slot_limit[slot])
                         - int(engine._positions[slot]) - 1)
            allow[slot] = max(0, min(cap, remaining - 1))
            engine._grow_slot_to(slot, int(engine._positions[slot])
                                 + int(allow[slot]))
            if self.draft_mode and allow[slot] > 0:
                self._grow(slot, int(engine._positions[slot])
                           + int(allow[slot]) - 1)
        if engine._bt_dev is None:
            engine._bt_dev = jnp.asarray(engine._bt_host)
        fn = self._get_spec_step()
        if self.draft_mode:
            if self._bt_dev is None:
                self._bt_dev = jnp.asarray(self._bt_host)
            tgt, props, t_pools, d_pools = engine._call(
                fn, engine._arrays, self._d_arrays, engine.arena.pools,
                engine.arena.ns_pools(self.NAMESPACE), engine._bt_dev,
                self._bt_dev, jnp.asarray(engine._positions),
                jnp.asarray(engine._last_tok), jnp.asarray(act_spec),
                jnp.asarray(allow), name="serving.spec_step", cause="sync")
            engine.arena.set_pools(t_pools)
            engine.arena.set_ns_pools(self.NAMESPACE, d_pools)
            tgt = np.asarray(tgt)      # [S, k+1] target greedy tokens
            props = np.asarray(props)  # [S, k]   draft proposals
        else:
            tgt, t_pools = engine._call(
                fn, engine._arrays, engine.arena.pools, engine._bt_dev,
                jnp.asarray(engine._positions),
                jnp.asarray(engine._last_tok), jnp.asarray(act_spec),
                jnp.asarray(allow), name="serving.spec_step", cause="sync")
            engine.arena.set_pools(t_pools)
            tgt = np.asarray(tgt)      # [S, k] fused greedy tokens
            props = tgt                # self-draft: proposals ARE outputs
        # the iteration's tokens are on the host: the device has nothing
        # left of this engine's (device.empty, ended as `sync`)
        engine._device_drained(engine._dispatched)

        out: Dict[int, List[int]] = {}
        n_emitted = n_proposed = n_accepted = n_rollback = 0
        for slot in active_slots:
            a = int(allow[slot])
            if self.draft_mode:
                n = 0
                while n < a and props[slot, n] == tgt[slot, n]:
                    n += 1
                if n == k:
                    # full acceptance: take the k matched proposals and
                    # skip the bonus token — the draft cache then covers
                    # exactly positions < pos', no catch-up step needed
                    accepted = [int(t) for t in tgt[slot, :k]]
                else:
                    # n matched proposals + the target's correction token
                    accepted = [int(t) for t in tgt[slot, :n + 1]]
                n_proposed += a
                n_accepted += n
                n_rollback += a - n
            else:
                accepted = [int(t) for t in tgt[slot, :a + 1]]
                n_proposed += a + 1
                n_accepted += a + 1
            engine._positions[slot] += len(accepted)
            engine._last_tok[slot] = accepted[-1]
            out[slot] = accepted
            n_emitted += len(accepted)
        # the plain step's device copy is stale (it runs the lanes
        # speculation must not cover, by an `active=` override)
        engine._touch_slot_state("override")
        self.iterations += 1
        self.proposed += n_proposed
        self.accepted += n_accepted
        self.rollback_tokens += n_rollback
        self.emitted += n_emitted
        metrics.bump("spec.iterations")
        metrics.bump("spec.emitted", n_emitted)
        metrics.bump("spec.proposed", n_proposed)
        metrics.bump("spec.accepted", n_accepted)
        if n_rollback:
            metrics.bump("spec.rollback_tokens", n_rollback)
        metrics.bump("engine.steps")
        metrics.bump("tokens.generated", n_emitted)
        engine._meter.tick(n_emitted)
        metrics.set_gauge("tokens_per_sec",
                          round(engine._meter.rate(), 1))
        metrics.set_gauge("spec.acceptance_rate",
                          round(self.acceptance_rate(), 4))
        if self.quant_draft and self.draft_mode:
            # per-mode acceptance telemetry: the tuning signal for a
            # quantized draft (speed knob — correctness is structural)
            metrics.set_gauge("quant.draft_acceptance",
                              round(self.acceptance_rate(), 4))
        return out

    # ------------------------------------------------------------- stats

    def acceptance_rate(self) -> float:
        return self.accepted / self.proposed if self.proposed else 0.0

    def mode(self) -> str:
        """The speculation mode label, quantization included —
        ``lockstep`` / ``draft`` / ``draft-int8``."""
        if not self.draft_mode:
            return "lockstep"
        return "draft-int8" if self.quant_draft else "draft"

    def stats(self) -> dict:
        return {
            "spec.k": self.k,
            "spec.mode": self.mode(),
            "spec.proposed": self.proposed,
            "spec.accepted": self.accepted,
            "spec.rollback_tokens": self.rollback_tokens,
            "spec.emitted": self.emitted,
            "spec.iterations": self.iterations,
            "spec.acceptance_rate": round(self.acceptance_rate(), 4),
            "spec.traces": self.spec_traces,
            "spec.draft_prefill_traces": dict(self.draft_prefill_traces),
        }
