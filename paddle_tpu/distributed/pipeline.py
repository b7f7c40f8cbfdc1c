"""Pipeline parallelism — compiled GPipe/1F1B over the "pipe" mesh axis.

The reference implements PP as a runtime: a hand-written 1F1B schedule
(ref:python/paddle/distributed/fleet/meta_parallel/pipeline_parallel.py:154,
271) driving per-microbatch send_partial/recv_partial p2p ops
(ref:.../pp_utils/p2p_communication.py:206) between rank processes, plus the
FleetExecutor actor runtime for static graphs.

TPU-native redesign: the pipeline is ONE differentiable program.

* Stage weights are stacked along a leading stage dimension and sharded over
  the "pipe" mesh axis.
* The schedule is a ``lax.scan`` over M + S - 1 clock ticks inside a
  partial-manual ``shard_map`` (manual only over "pipe"; data/model/sharding
  axes stay under GSPMD inside each stage).
* The per-tick hop between stages is ``lax.ppermute`` — the compiled form of
  the reference's p2p send/recv. Autodiff through scan+ppermute *derives*
  the backward pipeline (reverse ppermute), so there is no hand-written 1F1B
  backward pass to get wrong; XLA overlaps the forward of microbatch i+1
  with the backward of microbatch i exactly as 1F1B does.
* ``jax.checkpoint`` on the stage body keeps activation memory at
  O(microbatch) like the reference's recompute-in-pipeline mode.

Bubble fraction is the GPipe (S-1)/(M+S-1); choose M >= 4*S like the
reference's accumulate_steps guidance.

Interleaved virtual stages (ref:python/paddle/distributed/fleet/
meta_parallel/pipeline_parallel.py:514 PipelineParallelWithInterleave):
``pipeline_apply_interleaved`` splits the model into S*V chunks, chunk j
living on device j mod S, and runs a looped ring — each activation makes V
laps, hopping one device per tick, with a chunk 1/V the size of a GPipe
stage. Ticks = M·V + S - 1 at 1/V the per-tick cost, so the fill/drain
bubble shrinks from (S-1)/(M+S-1) to (S-1)/(M·V+S-1) — the reference's
interleaved-1F1B effect, paid for with V× the p2p hops (the same tradeoff
the reference documents). ``pipeline_tick_cost`` gives the closed-form
schedule cost both tests and the tuner use.
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from . import mesh as mesh_mod

PIPE_AXIS = "pipe"


def stack_stage_params(param_arrays, num_stages: int, mesh: Optional[Mesh] = None):
    """Stack per-stage pytrees (list of length S of identical-structure
    pytrees) into stage-major arrays sharded over the pipe axis."""
    mesh = mesh or mesh_mod.ensure_mesh()
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *param_arrays)

    def _place(x):
        spec = (PIPE_AXIS,) + (None,) * (x.ndim - 1)
        return jax.device_put(x, NamedSharding(mesh, PartitionSpec(*spec)))

    if mesh.shape.get(PIPE_AXIS, 1) > 1:
        stacked = jax.tree.map(_place, stacked)
    return stacked


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    x,
    *,
    num_microbatches: int,
    mesh: Optional[Mesh] = None,
    remat: bool = True,
):
    """Run ``x`` through S pipeline stages.

    ``stage_fn(local_params, h) -> h`` — one stage's computation. Its
    ``local_params`` pytree has the *leading stage dimension stripped*
    (each pipe rank sees its own stage's slice).

    ``stage_params`` — pytree with leading dim S on every leaf, sharded over
    the "pipe" axis (see :func:`stack_stage_params`).

    ``x`` — [B, ...] global batch; B must divide by num_microbatches.
    Returns [B, ...] outputs of the final stage (replicated over pipe).
    """
    mesh = mesh or mesh_mod.ensure_mesh()
    S = mesh.shape.get(PIPE_AXIS, 1)
    M = num_microbatches
    if x.shape[0] % M:
        raise ValueError(f"batch {x.shape[0]} not divisible by {M} microbatches")

    body = stage_fn
    if remat:
        body = jax.checkpoint(stage_fn, policy=jax.checkpoint_policies.nothing_saveable)

    if S <= 1:  # no pipe axis: plain microbatch loop (keeps semantics/shapes)
        local = jax.tree.map(lambda a: a[0], stage_params)
        mb = x.reshape((M, x.shape[0] // M) + x.shape[1:])
        ys = jax.lax.map(lambda h: body(local, h), mb)
        return ys.reshape(x.shape[:1] + ys.shape[2:])

    def _pipelined(params, xb):
        # params leaves: [S_local=1, ...] (manual over pipe) -> strip
        local = jax.tree.map(lambda a: a[0], params)
        rank = jax.lax.axis_index(PIPE_AXIS)
        mb_sz = xb.shape[0] // M
        x_mb = xb.reshape((M, mb_sz) + xb.shape[1:])

        # initial carries become stage-varying after the first tick; mark them
        state = jax.lax.pcast(jnp.zeros_like(x_mb[0]), (PIPE_AXIS,),
                              to="varying")
        outputs = jax.lax.pcast(jnp.zeros_like(x_mb), (PIPE_AXIS,),
                                to="varying")
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            state, outputs = carry
            # stage 0 injects microbatch t (clamped; masked by is-first-stage)
            inject = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
            )
            h = jnp.where(rank == 0, inject, state)
            h = body(local, h)
            # last stage owns microbatch t-(S-1) once t >= S-1
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            take = jnp.logical_and(rank == S - 1, t >= S - 1)
            cur = jax.lax.dynamic_index_in_dim(outputs, out_idx, 0, keepdims=False)
            new = jnp.where(take, h, cur)
            outputs = jax.lax.dynamic_update_index_in_dim(outputs, new, out_idx, 0)
            # rotate activations one stage forward (compiled p2p hop)
            state = jax.lax.ppermute(h, PIPE_AXIS, fwd_perm)
            return (state, outputs), None

        (state, outputs), _ = jax.lax.scan(tick, (state, outputs), jnp.arange(M + S - 1))
        # replicate the last stage's outputs to every pipe rank
        mask = (rank == S - 1).astype(outputs.dtype)
        outputs = jax.lax.psum(outputs * mask, PIPE_AXIS)
        return outputs.reshape(xb.shape[:1] + outputs.shape[2:])

    in_specs = (
        jax.tree.map(lambda _: PartitionSpec(PIPE_AXIS), stage_params),
        PartitionSpec(),
    )
    fn = jax.shard_map(
        _pipelined,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=PartitionSpec(),
        axis_names={PIPE_AXIS},
        check_vma=True,  # partial-manual mode requires vma tracking
    )
    return fn(stage_params, x)


def pipeline_tick_cost(num_microbatches: int, num_stages: int,
                       num_chunks: int = 1) -> float:
    """Schedule cost in full-stage units (1 unit = V chunk applications).

    GPipe (V=1): M + S - 1 ticks of one stage each. Interleaved: microbatch
    count pads to a multiple of S, then ceil(M/S)*S*V + S - 1 ticks of one
    chunk (1/V stage) each."""
    m, s, v = num_microbatches, num_stages, num_chunks
    if v <= 1:
        return float(m + s - 1)
    m_pad = -(-m // s) * s
    return (m_pad * v + s - 1) / v


def stack_chunk_params(param_arrays, num_stages: int, num_chunks: int,
                       mesh: Optional[Mesh] = None):
    """Stack S*V per-chunk pytrees (stage-major: chunk j = global stage j)
    into [V, S, ...] arrays with the S axis sharded over "pipe" — device d
    holds chunks d, d+S, ..., d+(V-1)S, the reference's interleaved
    placement."""
    mesh = mesh or mesh_mod.ensure_mesh()
    S, V = num_stages, num_chunks
    if len(param_arrays) != S * V:
        raise ValueError(f"expected {S * V} chunk pytrees, got "
                         f"{len(param_arrays)}")
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *param_arrays)
    stacked = jax.tree.map(
        lambda a: a.reshape((V, S) + a.shape[1:]), stacked)

    def _place(x):
        spec = (None, PIPE_AXIS) + (None,) * (x.ndim - 2)
        return jax.device_put(x, NamedSharding(mesh, PartitionSpec(*spec)))

    if mesh.shape.get(PIPE_AXIS, 1) > 1:
        stacked = jax.tree.map(_place, stacked)
    return stacked


def pipeline_apply_interleaved(
    chunk_fn: Callable,
    chunk_params,
    x,
    *,
    num_microbatches: int,
    num_chunks: int,
    mesh: Optional[Mesh] = None,
    remat: bool = True,
):
    """Interleaved virtual-stage schedule: a looped ring over the pipe axis.

    ``chunk_fn(local_params, h, chunk_idx) -> h`` — one chunk (1/V of a
    GPipe stage); ``chunk_idx`` is this device's local chunk slot (global
    stage = chunk_idx*S + rank), for RNG-key folding etc.

    ``chunk_params`` — pytree with leading dims [V, S_local=1, ...] under
    shard_map (see :func:`stack_chunk_params`).

    Schedule: microbatch m = g*S + i injects at device 0 on tick
    g*S*V + i and hops one device per tick for S*V ticks (V laps of the
    ring), finishing on device S-1. Per tick, the activation held by
    device d at tick t sits at global stage k where

        i = (t - d) mod S          injection phase
        k = (t - i) mod (S*V)      global stage (k ≡ d mod S)
        g = (t - i - k) / (S*V)    microbatch group

    Slots with g outside [0, ceil(M/S)) carry fill/drain garbage and are
    masked from injection/ejection.
    """
    mesh = mesh or mesh_mod.ensure_mesh()
    S = mesh.shape.get(PIPE_AXIS, 1)
    V = num_chunks
    M = num_microbatches
    if x.shape[0] % M:
        raise ValueError(f"batch {x.shape[0]} not divisible by {M} microbatches")
    body = chunk_fn
    if remat:
        body = jax.checkpoint(chunk_fn,
                              policy=jax.checkpoint_policies.nothing_saveable)

    if S <= 1:
        # no pipe axis: apply all V chunks sequentially per microbatch
        # (leaves are [V, S=1, ...]; global stage j = v)
        mb = x.reshape((M, x.shape[0] // M) + x.shape[1:])

        def one(h):
            for j in range(V):
                local = jax.tree.map(lambda a, j=j: a[j, 0], chunk_params)
                h = body(local, h, j)
            return h

        ys = jax.lax.map(one, mb)
        return ys.reshape(x.shape[:1] + ys.shape[2:])

    if V <= 1:
        # one chunk per device IS the GPipe schedule
        squeezed = jax.tree.map(lambda a: a[0], chunk_params)  # [S, ...]
        return pipeline_apply(
            lambda local, h: chunk_fn(local, h, 0), squeezed, x,
            num_microbatches=M, mesh=mesh, remat=remat)

    G = -(-M // S)          # microbatch groups (padded)
    M_pad = G * S
    T = M_pad * V + S - 1   # total clock ticks

    def _pipelined(params, xb):
        # params leaves: [V, S_local=1, ...] (manual over pipe) -> [V, ...]
        local = jax.tree.map(lambda a: a[:, 0], params)
        rank = jax.lax.axis_index(PIPE_AXIS)
        mb_sz = xb.shape[0] // M
        x_mb = xb.reshape((M, mb_sz) + xb.shape[1:])

        state = jax.lax.pcast(jnp.zeros_like(x_mb[0]), (PIPE_AXIS,),
                              to="varying")
        out_shape = (M_pad,) + x_mb.shape[1:]
        outputs = jax.lax.pcast(jnp.zeros(out_shape, x_mb.dtype),
                                (PIPE_AXIS,), to="varying")
        fwd_perm = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            state, outputs = carry
            i = jnp.mod(t - rank, S)
            k = jnp.mod(t - i, S * V)
            g = (t - i - k) // (S * V)
            m = g * S + i
            valid = jnp.logical_and(g >= 0, g < G)

            inject = jax.lax.dynamic_index_in_dim(
                x_mb, jnp.clip(m, 0, M - 1), axis=0, keepdims=False)
            h = jnp.where(jnp.logical_and(k == 0, valid), inject, state)

            v = k // S  # this device's local chunk slot
            chunk_local = jax.tree.map(
                lambda a: jax.lax.dynamic_index_in_dim(
                    a, v, axis=0, keepdims=False), local)
            h = body(chunk_local, h, v)

            eject = jnp.logical_and(
                jnp.logical_and(k == S * V - 1, valid), m < M)
            out_idx = jnp.clip(m, 0, M_pad - 1)
            cur = jax.lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                               keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(eject, h, cur), out_idx, 0)
            state = jax.lax.ppermute(h, PIPE_AXIS, fwd_perm)
            return (state, outputs), None

        (state, outputs), _ = jax.lax.scan(tick, (state, outputs),
                                           jnp.arange(T))
        mask = (rank == S - 1).astype(outputs.dtype)
        outputs = jax.lax.psum(outputs * mask, PIPE_AXIS)[:M]
        return outputs.reshape(xb.shape[:1] + outputs.shape[2:])

    in_specs = (
        jax.tree.map(lambda _: PartitionSpec(None, PIPE_AXIS), chunk_params),
        PartitionSpec(),
    )
    fn = jax.shard_map(
        _pipelined,
        mesh=mesh,
        in_specs=in_specs,
        out_specs=PartitionSpec(),
        axis_names={PIPE_AXIS},
        check_vma=True,
    )
    return fn(chunk_params, x)
