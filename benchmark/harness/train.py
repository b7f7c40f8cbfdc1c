"""The training product: one cell's run. ONE object (the compiled step
with its state) is built, driven from the seed through its first steps,
which the reference follows afterwards, and handed to the window.

    seeded model, AdamW, ``TrainStep`` (one chip) ->
    checked steps through the window's own call and feed (step 1 compiles)
    -> [window: steps back to back, a fresh seeded batch each] -> free the
    program -> the plain reference follows the checked steps.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from benchmark.harness import common, traffic


def _leaf_norm(a):
    import jax.numpy as jnp

    return jnp.sqrt(jnp.sum(jnp.square(a.astype(jnp.float32))))


def _worst_gap(prog: dict, ref: dict) -> float:
    """Worst leaf: |program's norm - reference's norm| over the larger of
    the reference's norm of that leaf and of the median leaf (some
    gradients are all but zero)."""
    names = sorted(ref, key=str)
    floor = float(np.median([ref[n] for n in names]))
    return max(abs(prog[n] - ref[n]) / max(ref[n], floor) for n in names)


def _flatten(tree: dict) -> dict:
    """The reference's norms, keyed as (group, layer, leaf)."""
    out = {}
    for group, leaves in tree.items():
        for leaf, v in leaves.items():
            v = np.asarray(v)
            if group == "layers":
                for i, x in enumerate(v):
                    out[(group, i, leaf)] = float(x)
            else:
                out[(group, None, leaf)] = float(v)
    return out


def compare(checks, lim: dict, losses, grad_norms: dict, change_norms: dict,
            ref: dict) -> None:
    """Hold what the program (or the control) gave to the reference's
    trajectory: each step's loss, the first gradient's norm and the
    norm of the parameters' change, the last two by the worst leaf."""
    for i, (a, b) in enumerate(zip(losses, ref["losses"])):
        checks.add(f"loss_gap_step{i + 1}", abs(a - b), lim["loss_gap"],
                   f"{a!r} against the reference's {b!r} (nats)")
    checks.add("grad_norm_gap",
               _worst_gap(grad_norms, _flatten(ref["grad_norms"])),
               lim["grad_norm_gap"],
               "first gradient as the optimizer got it, worst leaf")
    checks.add("change_norm_gap",
               _worst_gap(change_norms, _flatten(ref["change_norms"])),
               lim["change_norm_gap"],
               f"parameters' change after {len(losses)} steps, worst leaf")


def run(cell, seed: int, seconds: float, trace: bool, t_proc: float,
        keep_trace: str = None) -> dict:
    import jax

    from paddle_tpu import amp
    from paddle_tpu.core import compile_cache
    from paddle_tpu.core.tensor import Tensor
    from paddle_tpu.jit import TrainStep
    from paddle_tpu.optimizer import AdamW

    cfg, tr, lim = cell.config, cell.config["training"], cell.limits["check"]
    o = tr["optimizer"]
    chips = cell.chips
    if chips != 1:
        # a cell across chips brings its own product file, proven there
        raise SystemExit(f"benchmark: {cell.name} asks for {chips} chips; "
                         "harness/train.py drives one")
    hook = cell.hook("model")
    model = hook.build_model(cfg, seed, "float32", train=True)
    names = [hook.leaf_of(n) for n, _ in model.named_parameters()]
    params = [p for _, p in model.named_parameters()]
    opt = AdamW(learning_rate=o["learning_rate"], beta1=o["beta1"],
                beta2=o["beta2"], epsilon=o["epsilon"],
                weight_decay=o["weight_decay"],
                parameters=model.parameters())

    def loss_fn(x, y):
        with amp.auto_cast(level=tr["amp_level"], dtype=tr["amp_dtype"]):
            return model(x, y)

    step = TrainStep(loss_fn, opt, layers=model)
    batch, seq = int(tr["batch_per_chip"]) * chips, int(tr["seq_len"])
    feed = traffic.train_batches(cell.mix, seed, batch, seq,
                                 int(cfg["vocab_size"]))

    def one():
        """The window's own call and feed: a fresh batch from the seed, put
        on the device, one compiled step, waited for."""
        ids, labels = next(feed)
        loss = step(Tensor(ids), Tensor(labels))
        loss._data.block_until_ready()
        return loss, (ids, labels)

    # ---- the checked steps (set-up): what the program says of them
    n_check = int(lim["steps"])
    batches, prog_loss, prog_grad = [], [], None
    for i in range(n_check):
        loss, b = one()
        batches.append(b)
        prog_loss.append(float(np.asarray(loss._data)))
        if i == 0:
            # the first gradient as the optimizer got it: m1 = (1-b1) g
            prog_grad = {k: float(_leaf_norm(
                opt._accumulators[id(p)]["moment1"])) / (1.0 - o["beta1"])
                for k, p in zip(names, params)}
    weights = cell.hook("weights")
    start = {"embed": weights.embed(seed, cfg, "float32"),
             "final": weights.final(seed, cfg, "float32")}
    prog_change, made = {}, (None, None)
    for k, p in zip(names, params):
        group, index, leaf = k
        if group == "layers" and made[0] != index:
            made = (index, weights.layer(seed, index, cfg, "float32"))
        w0 = (made[1] if group == "layers" else start[group])[leaf]
        prog_change[k] = float(_leaf_norm(
            p._data.astype("float32") - jax.device_put(w0, p._data.sharding)))
    del start, made, w0
    stats_before = dict(compile_cache.stats())

    # ---- the window
    stretch = common.TracedStretch() if trace else None
    setup_s = time.monotonic() - t_proc
    t_start = time.perf_counter()
    ends, traced = [], None
    while True:
        if stretch and traced is None and \
                time.perf_counter() - t_start >= 0.4 * seconds:
            # a steady stretch of the window, traced: steps as above
            stretch.start()
            t_tr = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.window"):
                while time.perf_counter() - t_tr < min(5.0, seconds / 4.0):
                    with jax.profiler.TraceAnnotation("bench.step"):
                        one()
                    ends.append(time.perf_counter())
            stretch.stop()
            traced = True
            continue
        one()
        now = time.perf_counter()
        if now - t_start > seconds:
            break
        ends.append(now)
    stats_after = dict(compile_cache.stats())
    peak = common.memory_peak_bytes(chips)
    steps = len(ends)
    elapsed = (ends[-1] - t_start) if ends else float(seconds)
    e2e = {"setup_s": setup_s,
           "train_tokens_per_s": steps * batch * seq / elapsed}
    step_s = np.diff([t_start] + ends)
    common.note(window={"steps": steps, "elapsed_s": elapsed,
                        "tokens_per_step": batch * seq,
                        "step_s_median": float(np.median(step_s))
                        if steps else None,
                        "step_s_max": float(step_s.max()) if steps else None},
                e2e=e2e)

    # ---- free the program, then let the reference follow the checked steps
    del step, opt, model, params, loss
    gc.collect()
    jax.clear_caches()
    gc.collect()
    checks = common.Checks()
    for key in ("train_step.builds", "compile.backend"):
        checks.add(f"window_delta:{key}",
                   stats_after.get(key, 0) - stats_before.get(key, 0), 0,
                   "nothing compiles inside the window")
    t0 = time.perf_counter()
    ref = cell.hook("reference").train_trajectory(seed, cfg, o, batches)
    common.note(reference_s=time.perf_counter() - t0, steps=n_check)
    compare(checks, lim, prog_loss, prog_grad, prog_change, ref)
    tr_data = stretch.reduce(keep_trace) if stretch else None
    return {"cell": cell, "seconds": seconds, "checks": checks,
            "attempted": steps, "failed": 0, "e2e": e2e,
            "train": {"steps": steps, "tokens_per_step": batch * seq,
                      "chips": chips, "seq": seq, "batch": batch},
            "counters": {}, "hists": {}, "polls": [], "trace": tr_data,
            "program": {}, "memory_peak_bytes": peak}
