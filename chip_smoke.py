#!/usr/bin/env python
"""On-chip smoke: the two products at gpt_1p3b width on one TPU chip.

    python chip_smoke.py             # one chip: serve, serve-kernel, train
    python chip_smoke.py --chips 4   # four chips: the mesh paths only

Drives what a user calls — ``serving.gateway.gateway.serve`` over HTTP on
loopback, and ``jit.TrainStep`` — at the widths of ``models.gpt.gpt_1p3b()``
(hidden 2048, 16 heads of 128, vocab 50304), weights made from ``--seed``,
and checks what comes out by the repo's own means (token parity against
``GPTForCausalLM.generate()``, kernel output against the gather reference
at ``tests/test_paged_kernel.py``'s tolerance, frozen compile counters,
finite and falling loss). It is the quickest proof that the system still
starts on the chip; it measures nothing — every wall time it prints is a
smoke timing, not a metric.

ONE PROCESS HOLDS THE CHIP. Every phase runs in this process, in sequence,
and releases its arrays before the next; nothing here starts a child. With
no TPU attached the script exits non-zero before any phase and prints no
result. Each phase prints one JSON line; a failed check raises and ends the
run non-zero. The last line of a passing run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

The compile cache is the framework's (``core.compile_cache``): where
``JAX_COMPILATION_CACHE_DIR`` is set it is there, else ``.jax_cache`` in
this checkout. A second run sharing the directory shows
``persistent_hits`` for the step programs.

The native extension is not on this path: modules here import
``paddle_tpu.native`` but none calls ``native.load()``, so nothing is built
from ``native/csrc`` and nothing under the home directory is read (the
``done`` line says whether the library was loaded).
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import math
import os
import sys
import threading
import time
import traceback
import urllib.request
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

# ------------------------------------------------------------------ sizes


@dataclasses.dataclass(frozen=True)
class Plan:
    """Everything a run is sized by. ``FULL`` is what the chip runs; the
    CPU rehearsal (tests/test_chip_smoke.py) passes a tiny one to the same
    phase functions."""

    hidden: int
    heads: int
    layers: int            # served depth
    vocab: int
    max_len: int           # max_position_embeddings == max_model_len
    slots: int             # decode lanes of the one compiled step
    arena_blocks: int      # KV arena size, in blocks of `block` tokens
    block: int
    #: (prompt length, new tokens): two requests per prefill bucket — the
    #: first of each bucket is wave 1 (compiles), the second is wave 2
    #: (must compile nothing)
    requests: Tuple[Tuple[int, int], ...]
    parity: Tuple[int, ...]   # request indices compared with generate()
    train_layers: int
    train_batch: int
    train_seq: int
    kernel_sq: int         # query rows of the raw prefill-kernel parity


#: gpt_1p3b widths. Serving runs the full 24 layers in bf16 (2.45 GiB of
#: weights) over an 8 GiB arena: 192 KiB per token at these widths
#: (2 x 24 layers x 2048 x 2 bytes), 2730 blocks of 16 = 43,680 tokens —
#: what a deployment leaves for KV on a 16 GB chip after weights and
#: step temporaries. 32 lanes oversubscribe it as a deployment would
#: (32 x 2048 worst-case tokens > 43,680): admission is by blocks.
#:
#: Training depth is cut to 12 of 24 layers. Rehearsal compiles for the
#: described v5e (`memory_analysis()`, batch 4 x 1024, AMP O1 as the cell
#: `train-1chip` has it: f32 params, grads, Adam moments, 16 B/param): 24 are
#: refused by the compiler (19.85 G of 15.75 G HBM); 16 layers compile at
#: 14.81 GiB, 94% of HBM, which leaves under 1 GiB for the allocator's
#: fragmentation and whatever else the process holds; 12 layers take
#: 12.01 GiB (76%). The rule: the largest of 24/16/12/8 under 90% of HBM.
FULL = Plan(
    hidden=2048, heads=16, layers=24, vocab=50304, max_len=2048,
    slots=32, arena_blocks=2730, block=16,
    requests=((64, 32), (60, 48), (256, 32), (200, 64),
              (768, 48), (700, 96), (1024, 32), (1000, 128)),
    parity=(0, 2, 6),
    train_layers=12, train_batch=4, train_seq=1024,
    kernel_sq=512,
)

#: bf16 raw-output tolerance of tests/test_paged_kernel.py::_tol (online vs
#: full-width softmax association; bf16 rounds the operands)
KERNEL_TOL = dict(atol=2e-2, rtol=2e-2)

#: two correct bf16 paths may break a near-tie of the best logits either
#: way (see ``Parity``): at its first divergence each continuation's token
#: must be within this many bf16 steps of the best float32 reference logit
TIE_STEPS = 4

#: loss tolerance of tests/test_mesh_serving.py::
#: test_trainstep_data_parallel_on_mesh (mesh vs one device)
DP_LOSS_TOL = dict(rtol=2e-3, atol=2e-4)


def emit(rec: dict) -> None:
    print(json.dumps(rec), flush=True)


def check(cond, msg: str) -> None:
    """A failed check ends the run: no phase records an error and goes on."""
    if not cond:
        raise SystemExit(f"chip_smoke: CHECK FAILED: {msg}")


# ----------------------------------------------------------------- device


def device_record() -> dict:
    import jax

    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_record() -> dict:
    """Bytes on device 0 as the backend reports them (absent on backends
    that report none)."""
    import jax

    stats = jax.devices()[0].memory_stats() or {}
    return {k: int(stats[k]) for k in
            ("bytes_in_use", "peak_bytes_in_use", "bytes_limit")
            if k in stats}


def cache_record() -> dict:
    from paddle_tpu.core import compile_cache

    s = compile_cache.stats()
    return {"dir": s.get("persistent.dir"),
            "persistent_hits": int(s.get("persistent.hits", 0)),
            "persistent_misses": int(s.get("persistent.misses", 0)),
            "backend_compiles": int(s.get("compile.backend", 0)),
            "backend_compile_secs": round(
                float(s.get("compile.backend_secs", 0.0)), 1)}


def release() -> None:
    """Drop what the finished phase left on the device."""
    import jax

    gc.collect()
    jax.clear_caches()
    gc.collect()


# ------------------------------------------------------------------ models


def gpt_config(plan: Plan, layers: Optional[int] = None):
    from paddle_tpu.models.gpt import GPTConfig

    return GPTConfig(vocab_size=plan.vocab, hidden_size=plan.hidden,
                     num_layers=layers or plan.layers, num_heads=plan.heads,
                     max_position_embeddings=plan.max_len)


def serving_model(plan: Plan, seed: int):
    import paddle_tpu as pt
    from paddle_tpu.models.gpt import GPTForCausalLM

    pt.seed(seed)
    model = GPTForCausalLM(gpt_config(plan))
    model.eval()
    model.bfloat16()
    return model


def make_prompts(plan: Plan, seed: int) -> List[np.ndarray]:
    rng = np.random.default_rng(seed)
    return [rng.integers(0, plan.vocab, (n,), dtype=np.int32)
            for n, _ in plan.requests]


def param_bytes(model) -> int:
    return int(sum(p._data.nbytes for p in model.parameters()))


# ------------------------------------------------------------ HTTP client


def _json(url: str, body: Optional[dict] = None, timeout: float = 600.0):
    req = urllib.request.Request(
        url, data=None if body is None else json.dumps(body).encode(),
        method="GET" if body is None else "POST")
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.load(resp)


def _read_stream(base: str, rid: str) -> Tuple[List[int], dict]:
    """Read ``GET /v1/stream/<id>`` to its end: (tokens, done event)."""
    toks, done, event = [], None, None
    with urllib.request.urlopen(f"{base}/v1/stream/{rid}",
                                timeout=600.0) as resp:
        for line in resp:
            line = line.decode().strip()
            if line.startswith("event:"):
                event = line.split(":", 1)[1].strip()
            elif line.startswith("data:"):
                data = json.loads(line.split(":", 1)[1])
                check(event != "error", f"stream {rid} failed: {data}")
                if event == "done":
                    done = data
                else:
                    toks.append(int(data["token"]))
                event = None
    check(done is not None, f"stream {rid} ended without a done event")
    return toks, done


def _wave(base: str, prompts, plan: Plan, idxs: Sequence[int],
          out: Dict[int, List[int]]) -> None:
    """Submit requests ``idxs`` together, then read every stream to its
    end (one reader thread each, as concurrent clients would)."""
    rids = {i: _json(f"{base}/v1/submit",
                     {"prompt": prompts[i].tolist(),
                      "max_new_tokens": plan.requests[i][1]})["request_id"]
            for i in idxs}
    errors: List[BaseException] = []

    def read(i):
        try:
            toks, done = _read_stream(base, rids[i])
            check(done["state"] == "FINISHED",
                  f"request {i} ended {done['state']}")
            check(len(toks) == plan.requests[i][1],
                  f"request {i}: {len(toks)} tokens of "
                  f"{plan.requests[i][1]}")
            out[i] = toks
        except BaseException as e:  # re-raised below, in the main thread
            errors.append(e)

    threads = [threading.Thread(target=read, args=(i,)) for i in idxs]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=900.0)
        check(not t.is_alive(), "a stream reader did not finish in 900 s")
    if errors:
        raise errors[0]


# ------------------------------------------------------------ serve phases


def _spec(a) -> str:
    """An array's partition spec over all its dims, e.g. ``-,-,model,-``
    (a compiled step's outputs drop trailing unsharded dims); ``single``
    off a mesh."""
    spec = getattr(a.sharding, "spec", None)
    if spec is None:
        return "single"
    spec = tuple(spec) + (None,) * (a.ndim - len(spec))
    return ",".join("-" if d is None else str(d) for d in spec)


def serve_pass(name: str, model, plan: Plan, prompts, chip: bool,
               **engine_kw) -> Tuple[Dict[int, List[int]], dict]:
    """One engine behind ``gateway.serve``: two waves of requests over
    HTTP, then the engine's own account of what ran. Returns the generated
    tokens per request and the phase record. The gateway, its pool, the
    engine and its arena are closed and dropped before returning, so the
    next engine finds the chip's memory free."""
    from paddle_tpu.serving import ServingConfig
    from paddle_tpu.serving.gateway.gateway import serve

    t0 = time.perf_counter()
    gw = serve(model, replicas=1, port=0, guard=False,
               config=ServingConfig(
                   num_slots=plan.slots, num_blocks=plan.arena_blocks,
                   kv_block_size=plan.block, max_model_len=plan.max_len,
                   **engine_kw))
    base = f"http://127.0.0.1:{gw.port}"
    toks: Dict[int, List[int]] = {}
    try:
        engine = gw.pool.replicas()[0].api.engine
        before = _json(f"{base}/v1/stats")
        n = len(plan.requests)
        _wave(base, prompts, plan, range(0, n, 2), toks)
        warm = _json(f"{base}/v1/stats")
        t1 = time.perf_counter()
        _wave(base, prompts, plan, range(1, n, 2), toks)
        t2 = time.perf_counter()
        after = _json(f"{base}/v1/stats")

        def delta(section, key, a=before, b=after):
            return b[section].get(key, 0) - a[section].get(key, 0)

        # one compiled decode step, one prefill program per bucket, and
        # nothing compiled once the first request of each bucket is served
        check(delta("compile", "serving.decode_compiles") == 1,
              f"{name}: decode step traced "
              f"{delta('compile', 'serving.decode_compiles')} times")
        for key in ("serving.decode_compiles", "serving.prefill_compiles"):
            check(delta("compile", key, warm, after) == 0,
                  f"{name}: {key} rose after the first request of each "
                  f"bucket was served")
        check(delta("serving", "requests.finished") == n,
              f"{name}: {delta('serving', 'requests.finished')} of {n} "
              f"requests finished")
        check(delta("serving", "requests.failed") == 0,
              f"{name}: requests counted failed")
        stats = engine.stats()
        check(stats["kernel.paged"] == int(bool(
            engine_kw.get("paged_kernel"))),
            f"{name}: engine route is {stats['kernel.mesh']}")
        if engine_kw.get("paged_kernel") and chip:
            # the engine did not give way to the gather path, and the
            # kernel is compiled, not interpreted
            from paddle_tpu.ops.pallas_ops import _use_interpret

            check(not _use_interpret(), "Pallas interpret mode on the chip")
            check("tpu_custom_call" in engine.lower_decode_step().as_text(),
                  f"{name}: no tpu_custom_call in the lowered decode step")
        pools = [a for entry in engine.arena.pools for a in entry]
        rec = {
            "phase": name, "route": stats["kernel.mesh"],
            "quant_kv": bool(stats["quant.kv"]),
            "slots": plan.slots,
            "arena_tokens": plan.arena_blocks * plan.block,
            "arena_bytes": int(engine.arena.bytes_total()),
            "weight_bytes": param_bytes(model),
            "requests": n,
            "prompt_tokens": int(sum(len(p) for p in prompts)),
            "new_tokens": int(sum(len(t) for t in toks.values())),
            "prefill_programs": delta("compile", "serving.prefill_compiles"),
            "decode_programs": delta("compile", "serving.decode_compiles"),
            "smoke_secs_setup_and_wave1": round(t1 - t0, 1),
            "smoke_secs_wave2": round(t2 - t1, 1),
            "weight_devices": sorted({len(p._data.sharding.device_set)
                                      for p in model.parameters()}),
            "kv_pool_devices": sorted({len(a.sharding.device_set)
                                       for a in pools}),
            "kv_pool_specs": sorted({_spec(a) for a in pools}),
            "memory": memory_record(), "cache": cache_record(),
        }
    finally:
        gw.close()
    return toks, rec


class Parity:
    """Pairs of greedy continuations that should agree.

    In float32 on the CPU the repo's contract is exact token equality. In
    bf16 on the chip two correct paths round differently (bf16 scores vs
    f32 scores in the kernel, a [32, 1, h] matmul vs a [1, 1, h] one), and
    the logits themselves are bf16: the best two of 50k candidates sit
    within a couple of bf16 steps of each other at roughly one token in
    ten, and either path may pick either. So continuations are compared
    token for token up to their first divergence, and the divergence is
    judged against FLOAT32 reference logits of the same weights
    (:meth:`judge`): it must be a near-tie, or the run fails. After a
    divergence the continuations legitimately differ, so only the first is
    judged. A wrong kernel or a wrong cache derails at once with a token
    whose reference logit is nowhere near the best."""

    def __init__(self):
        self.cases: List[dict] = []

    def compare(self, what: str, prompts, a: Dict[int, List[int]],
                b: Dict[int, List[int]], gate: bool = True) -> dict:
        """Record every request whose continuations differ; returns the
        summary the phase prints. ``gate=False`` records the divergences
        for the report only (an int8 arena changes the numbers the model
        sees, so its tokens are not held to the bf16 pass)."""
        exact = 0
        for i in sorted(a):
            if a[i] == b[i]:
                exact += 1
                continue
            at = next(j for j, (x, y) in enumerate(zip(a[i], b[i]))
                      if x != y)
            self.cases.append({
                "what": what, "request": i, "at": at, "gate": gate,
                "tokens": [a[i][at], b[i][at]],
                "ctx": np.concatenate(
                    [prompts[i], np.asarray(a[i][:at], np.int32)])})
        return {"compared": len(a), "exact": exact,
                "diverged": len(a) - exact}

    def judge(self, model, plan: Plan) -> None:
        """Judge every recorded divergence; prints one ``parity`` line.
        Casts ``model`` to float32 in place (the served bf16 weights,
        upcast exactly) — call it when serving is over."""
        import jax

        import paddle_tpu as pt
        from paddle_tpu.core.tensor import Tensor

        rows = []
        rule = (f"at its first divergence each continuation's token is "
                f"within {TIE_STEPS} bf16 steps of the best float32 "
                f"reference logit")
        if not self.cases:
            emit({"phase": "parity", "rule": rule, "judged": rows})
            return
        model.float()
        # the plain XLA softmax attention at full f32 matmul precision: a
        # reference, not a third bf16 path
        keep = pt.get_flags("flash_attention_min_seqlen")
        pt.set_flags({"flash_attention_min_seqlen": 1 << 30})
        try:
            for c in self.cases:
                # one padded length, so every case shares its programs:
                # causal attention makes position len-1 independent of
                # the padding
                ids = np.zeros((1, plan.max_len), np.int32)
                ids[0, :len(c["ctx"])] = c["ctx"]
                with pt.no_grad(), jax.default_matmul_precision("highest"):
                    logits = np.asarray(
                        model(Tensor(ids))._data[0, len(c["ctx"]) - 1],
                        np.float32)
                best = float(logits.max())
                ulp = bf16_ulp(best)
                lo = min(float(logits[t]) for t in c["tokens"])
                rows.append({
                    "what": c["what"], "request": c["request"],
                    "at": c["at"], "tokens": c["tokens"],
                    "best_logit": round(best, 4),
                    "below_best_in_bf16_steps": round((best - lo) / ulp, 2),
                    "ok": bool(best - lo <= TIE_STEPS * ulp
                               or not c["gate"])})
        finally:
            pt.set_flags(keep)
        emit({"phase": "parity", "rule": rule, "judged": rows})
        bad = [r for r in rows if not r["ok"]]
        check(not bad, f"tokens diverge where it is no near-tie: {bad}")
        self.cases = []


def bf16_ulp(x: float) -> float:
    """Spacing of bfloat16 values (8 significant bits) at magnitude x."""
    return 2.0 ** (math.floor(math.log2(max(abs(x), 1e-30))) - 7)


def phase_serve(model, plan: Plan, prompts, parity: Parity, chip: bool):
    """Gather-path engine over HTTP; tokens against ``generate()``."""
    from paddle_tpu.core.tensor import Tensor

    # the XLA gather route, asked for: on the chip the default is the kernel
    toks, rec = serve_pass("serve", model, plan, prompts, chip,
                           paged_kernel=False)
    release()
    ref = {}
    for i in plan.parity:
        out = model.generate(Tensor(prompts[i][None]),
                             max_new_tokens=plan.requests[i][1])
        ref[i] = np.asarray(out._data)[0, len(prompts[i]):].tolist()
    rec["tokens_vs_generate"] = parity.compare(
        "serve vs generate()", prompts, {i: toks[i] for i in ref}, ref)
    emit(rec)
    return toks


def kernel_parity(plan: Plan, seed: int, quantized: bool) -> dict:
    """Raw kernel output against the gather reference (the construction of
    tests/test_paged_kernel.py: ``gather_ctx`` + ``masked_attention``) at
    the served widths, on random pools."""
    import jax.numpy as jnp

    from paddle_tpu.models.serving_seam import masked_attention
    from paddle_tpu.ops import paged_attention as pk
    from paddle_tpu.quantization import quantize_kv
    from paddle_tpu.serving.cache_views import gather_ctx

    rng = np.random.default_rng(seed)
    H, D, bs = plan.heads, plan.hidden // plan.heads, plan.block
    S, MB = plan.slots, plan.max_len // plan.block
    NB = S * MB // 4
    kf, vf = (jnp.asarray(rng.standard_normal((NB, bs, H, D), np.float32),
                          jnp.bfloat16) for _ in range(2))
    entry = (kf, vf)
    if quantized:
        (kq, ks), (vq, vs) = quantize_kv(kf), quantize_kv(vf)
        entry = (kq, vq, ks, vs)
    t_len = MB * bs

    q = jnp.asarray(rng.standard_normal((S, H, D)), jnp.bfloat16)
    bt = jnp.asarray(rng.integers(1, NB, (S, MB)), jnp.int32)
    pos = jnp.asarray(rng.integers(0, t_len, (S,)), jnp.int32)
    out = pk.paged_decode_attention(q, entry, bt, pos)
    k_all, v_all = gather_ctx(entry, bt, q.dtype)
    mask = (jnp.arange(t_len)[None, :] <= pos[:, None])[:, None, None, :]
    ref = masked_attention(q[:, None], k_all, v_all, mask)[:, 0]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **KERNEL_TOL)
    d_err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                  - ref.astype(jnp.float32))))

    sq = plan.kernel_sq
    prefix = t_len - sq - bs // 2
    q = jnp.asarray(rng.standard_normal((sq, H, D)), jnp.bfloat16)
    out = pk.paged_prefill_attention(q, entry, bt[0], jnp.int32(prefix))
    k_all, v_all = gather_ctx(entry, bt[0], q.dtype)
    gpos = prefix + jnp.arange(sq)
    mask = (jnp.arange(t_len)[None, :] <= gpos[:, None])[None, None]
    ref = masked_attention(q[None], k_all[None], v_all[None], mask)[0]
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32), **KERNEL_TOL)
    p_err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                                  - ref.astype(jnp.float32))))
    return {"decode_max_abs_err": round(d_err, 5),
            "prefill_max_abs_err": round(p_err, 5), "tol": KERNEL_TOL}


def phase_serve_kernel(model, plan: Plan, prompts, gather_toks, seed: int,
                       parity: Parity, chip: bool):
    """The same requests through the Pallas paged kernels: bf16 arena held
    to the gather pass's tokens, int8 arena to the kernel tolerance."""
    for name, quant in (("serve-kernel", False), ("serve-kernel-int8", True)):
        toks, rec = serve_pass(name, model, plan, prompts, chip,
                               paged_kernel=True, quant_kv=quant)
        release()
        rec["kernel_vs_gather_reference"] = kernel_parity(plan, seed, quant)
        rec["tokens_vs_gather_pass"] = parity.compare(
            f"{name} vs gather pass", prompts, gather_toks, toks,
            gate=not quant)
        emit(rec)
        release()


# ------------------------------------------------------------- train phase


def train_losses(plan: Plan, seed: int, steps: int, chip: bool,
                 shard: bool = False) -> Tuple[List[float], dict]:
    """``steps`` TrainStep updates of the depth-cut model on one fixed
    batch, AMP O1 as the benchmark's training cell uses it. Returns the
    losses and a record of what the compiled step is and holds."""
    import paddle_tpu as pt
    from paddle_tpu import amp
    from paddle_tpu.core import compile_cache
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.models.gpt import GPTForCausalLM
    from paddle_tpu.optimizer import AdamW

    t0 = time.perf_counter()
    pt.seed(seed)
    model = GPTForCausalLM(gpt_config(plan, plan.train_layers))
    opt = AdamW(learning_rate=1e-4, parameters=model.parameters(),
                weight_decay=0.01)

    def loss_fn(x, y):
        # bf16 compute on the MXU; f32 loss, params and moments
        with amp.auto_cast(level="O1", dtype="bfloat16"):
            return model(x, y)

    step = TrainStep(loss_fn, opt, layers=model)
    ids = np.random.default_rng(seed).integers(
        0, plan.vocab, (plan.train_batch, plan.train_seq), dtype=np.int32)
    x, y = Tensor(ids), Tensor(np.roll(ids, -1, axis=1))
    if shard:
        from paddle_tpu.distributed import shard_batch

        x, y = shard_batch(x), shard_batch(y)
    before = compile_cache.stats()
    losses = [float(step(x, y).numpy())]
    t1 = time.perf_counter()
    warm = compile_cache.stats()
    losses += [float(step(x, y).numpy()) for _ in range(steps - 1)]
    t2 = time.perf_counter()
    after = compile_cache.stats()
    check(all(np.isfinite(losses)), f"train: nonfinite loss in {losses}")
    check(after.get("train_step.builds", 0)
          - before.get("train_step.builds", 0) == 1
          and after.get("compile.backend", 0)
          == warm.get("compile.backend", 0),
          "train: the step compiled more than once")
    # the persistent cache serves this compile: the step program itself
    # was compiled once, above
    compiled = step.lower(x, y).compile()
    text = compiled.as_text()
    if chip:
        check("tpu_custom_call" in text,
              "train: no tpu_custom_call in the compiled step (flash "
              "attention is not in the program)")
    ma = compiled.memory_analysis()
    params = [p._data for p in model.parameters()]
    param_bytes_ = int(sum(p.nbytes for p in params))
    rec = {
        "layers": plan.train_layers, "batch": plan.train_batch,
        "seq": plan.train_seq,
        "params": int(sum(p.size for p in params)),
        # per device, by the compiler's account of the step: its arguments
        # are the params, the Adam state and the batch; its temporaries
        # hold the gradients and the activations
        "bytes": {"params": param_bytes_,
                  "optimizer_state_and_batch":
                      int(ma.argument_size_in_bytes) - param_bytes_,
                  "step_temporaries": int(ma.temp_size_in_bytes),
                  "step_total": int(ma.argument_size_in_bytes
                                    + ma.temp_size_in_bytes
                                    + ma.output_size_in_bytes
                                    - ma.alias_size_in_bytes)},
        "flash_kernel_calls": text.count("tpu_custom_call"),
        "all_reduces": text.count("all-reduce"),
        "losses": [round(v, 5) for v in losses],
        "smoke_secs_setup_and_step1": round(t1 - t0, 1),
        "smoke_secs_later_steps": round(t2 - t1, 1),
        "memory": memory_record(), "cache": cache_record(),
    }
    return losses, rec


def phase_train(plan: Plan, seed: int, chip: bool):
    losses, rec = train_losses(plan, seed, 5, chip)
    check(losses[-1] < losses[0],
          f"train: loss did not fall over 5 steps: {losses}")
    rec = {"phase": "train",
           "depth_rule": "largest of 24/16/12/8 layers whose compiled step "
                         "is under 90% of the chip's HBM "
                         "(see FULL in chip_smoke.py)", **rec}
    emit(rec)


# -------------------------------------------------------- four-chip phases


def phase_mesh_serve(plan: Plan, prompts, seed: int, chip: bool):
    """The serving engine under ``serving_mesh(4)`` — gather path and
    kernel path (``headwise_shard_map``) — against a one-device engine in
    this process: tokens agree, weights and KV pools span four devices
    with the head axis split as ``shard_kv_entry`` says."""
    from paddle_tpu.distributed import mesh as mesh_mod

    mesh_mod.clear_mesh()
    model = serving_model(plan, seed)
    ref, rec = serve_pass("mesh-serve/one-device", model, plan, prompts,
                          chip, paged_kernel=False)
    emit(rec)
    del model
    release()

    mesh_mod.serving_mesh(4)
    model = serving_model(plan, seed)
    parity = Parity()
    for name, kw in (("mesh-serve/gather", {"paged_kernel": False}),
                     ("mesh-serve/kernel", {"paged_kernel": True})):
        toks, rec = serve_pass(name, model, plan, prompts, chip, **kw)
        # every weight on all four devices; shard_kv_entry's rule for the
        # arena: every payload pool on all four, heads split over "model"
        heads_split = "-,-,model,-"
        check(rec["weight_devices"] == [4],
              f"{name}: weights live on {rec['weight_devices']} devices")
        check(rec["kv_pool_devices"] == [4]
              and rec["kv_pool_specs"] == [heads_split],
              f"{name}: KV pools on {rec['kv_pool_devices']} devices with "
              f"specs {rec['kv_pool_specs']}, want 4 and {heads_split}")
        rec["tokens_vs_one_device"] = parity.compare(
            f"{name} vs one device", prompts, ref, toks)
        emit(rec)
        release()
    parity.judge(model, plan)
    del model
    mesh_mod.clear_mesh()
    release()


def phase_mesh_train(plan: Plan, seed: int, chip: bool):
    """TrainStep under ``init_hybrid_mesh(dp=4)`` at the same global batch
    against the one-device loss for three steps."""
    from paddle_tpu.distributed import mesh as mesh_mod

    mesh_mod.clear_mesh()
    ref, rec = train_losses(plan, seed, 3, chip)
    emit({"phase": "mesh-train/one-device", **rec})
    release()
    import jax

    mesh_mod.init_hybrid_mesh(dp=4, devices=jax.devices()[:4])
    losses, rec = train_losses(plan, seed, 3, chip, shard=True)
    check(rec["all_reduces"] > 0,
          "mesh-train: no all-reduce in the compiled dp=4 step")
    np.testing.assert_allclose(losses, ref, **DP_LOSS_TOL)
    emit({"phase": "mesh-train/dp4", "one_device_losses":
          [round(v, 5) for v in ref], "tol": DP_LOSS_TOL, **rec})
    mesh_mod.clear_mesh()
    release()


# ------------------------------------------------------------------- main


def run(plan: Plan, chips: int, seed: int, chip: bool) -> None:
    prompts = make_prompts(plan, seed)
    if chips == 4:
        phase_mesh_serve(plan, prompts, seed, chip)
        phase_mesh_train(plan, seed, chip)
        return
    model = serving_model(plan, seed)
    parity = Parity()
    gather_toks = phase_serve(model, plan, prompts, parity, chip)
    release()
    phase_serve_kernel(model, plan, prompts, gather_toks, seed, parity, chip)
    parity.judge(model, plan)
    del model
    release()
    phase_train(plan, seed, chip)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the mesh phases and their one-device "
                         "comparisons, and no other phase")
    ap.add_argument("--seed", type=int, default=0,
                    help="weights, prompts and the training batch")
    args = ap.parse_args(argv)

    import jax

    dev = device_record()
    if dev["platform"] != "tpu":
        print(f"chip_smoke: needs a TPU; jax found {dev}", file=sys.stderr)
        return 2
    if dev["count"] < args.chips:
        print(f"chip_smoke: --chips {args.chips} but jax found {dev}",
              file=sys.stderr)
        return 2
    import paddle_tpu  # noqa: F401  (enables the one compile cache)
    from paddle_tpu.models.gpt import gpt_1p3b
    from paddle_tpu.nn.functional.attention import _effective_min_seqlen

    check(gpt_config(FULL) == gpt_1p3b(), "FULL is not models.gpt.gpt_1p3b()")
    emit({"phase": "start", "chips": args.chips, "seed": args.seed,
          "jax": jax.__version__, "device": dev, "cache": cache_record(),
          "flash_routed_from_seqlen": _effective_min_seqlen(FULL.train_seq),
          "plan": dataclasses.asdict(FULL)})
    t0 = time.perf_counter()
    run(FULL, args.chips, args.seed, chip=True)
    from paddle_tpu import native

    emit({"phase": "done", "smoke_secs_total":
          round(time.perf_counter() - t0, 1), "cache": cache_record(),
          "native_library_loaded": native._lib is not None})
    print(json.dumps({"ok": True, "device": dev}), flush=True)
    return 0


if __name__ == "__main__":
    # leave through os._exit: a serving thread that a failed phase left
    # behind must not keep the process (and the chip) after the verdict
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
        if not isinstance(e.code, int) and e.code is not None:
            print(e.code, file=sys.stderr)
    except BaseException:
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
