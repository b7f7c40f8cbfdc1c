"""What the window-and-experts model (Trinity) NEEDS on this chip, from THIS
configuration's keys: parameters a token is multiplied by (gated
grouped-query attention, the dense MLP or the router, the shared expert and
the HELD experts a token was routed to), the (query, key) pairs the banded
and the causal attention cannot avoid, the operations of a prefill, the
least seconds of a decode step and of its grouped matmuls. A key outside a
query's window or after it, an expert that is not held here or that no
token reached, a ring row that holds no key and anything recomputed are not
counted. A prefill counts at its bucket: the chip computes the padded
positions like the real ones (``roofline/hybrid_prefill.py`` says the
same). The trace's readers that know no configuration are
``roofline/latent_moe.py``'s."""
import re

from benchmark.roofline.latent_moe import (_ITEMSIZE, _inside,  # noqa: F401
                                           live, step_op_seconds)

KERNEL = "swa_prefill_flash"


def sizes(cfg: dict) -> dict:
    g = lambda k: int(cfg[k])
    layers, dense = g("num_hidden_layers"), g("num_dense_layers")
    types = cfg.get("layer_types") or [
        "full_attention" if (i + 1) % g("global_attn_every_n_layers") == 0
        else "sliding_attention" for i in range(layers)]
    sliding = sum(t == "sliding_attention" for t in types)
    held = g("num_experts")
    return {"h": g("hidden_size"), "heads": g("num_attention_heads"),
            "kv_heads": g("num_key_value_heads"), "d": g("head_dim"),
            "dense_w": g("intermediate_size"),
            "expert_w": g("moe_intermediate_size"), "held": held,
            "routed": int(cfg.get("published", {}).get("num_experts", held)),
            "k": g("num_experts_per_tok"), "layers": layers, "dense": dense,
            "expert": layers - dense, "sliding": sliding,
            "full": layers - sliding, "window": g("sliding_window"),
            "vocab": g("vocab_size")}


def params(cfg: dict) -> dict:
    """Matmul parameters: of one attention sublayer (q, k, v, gate, o), one
    dense MLP, one expert, the router."""
    s = sizes(cfg)
    h, wide, narrow = s["h"], s["heads"] * s["d"], s["kv_heads"] * s["d"]
    return {"attention": 3 * h * wide + 2 * h * narrow,
            "dense_mlp": 3 * h * s["dense_w"],
            "expert": 3 * h * s["expert_w"],
            "router": h * s["routed"]}


def steps_counted(run):
    """Decode steps the window's counters cover (``moe.layer_steps`` over
    the expert layers); None where the program counts none."""
    n = run["counters"].get("moe.layer_steps", 0)
    return n / sizes(run["cell"].config)["expert"] if n else None


def local_picks(run):
    """Held experts' picks a token a layer as the window's decode steps
    were routed, by the program's own counters; None where it counts
    none."""
    c = run["counters"]
    total = c.get("moe.assignments", 0)
    if not total:
        return None
    return sizes(run["cell"].config)["k"] \
        * c.get("moe.local_assignments", 0) / total


def active_params_per_token(cfg: dict, picks: float) -> float:
    """What one token is multiplied by here, the head not counted: every
    layer's attention, the dense layers' MLP, and in an expert layer the
    router, the shared expert and the held experts it was routed to
    (``picks`` a layer)."""
    s, p = sizes(cfg), params(cfg)
    return (s["layers"] * p["attention"] + s["dense"] * p["dense_mlp"]
            + s["expert"] * (p["router"] + (1 + picks) * p["expert"]))


def pairs(positions: float, window=None) -> float:
    """(query, key) pairs of one layer over a prompt of ``positions``: key
    ``j <= t`` and, with a window, ``t - j < window``."""
    if window is None or positions <= window:
        return positions * (positions + 1) / 2.0
    return window * (window + 1) / 2.0 + (positions - window) * window


def attention_flops(cfg: dict, positions: float) -> float:
    """Scores and values of every head over the pairs of every layer (4
    operations a pair a value of the head): the band in the sliding
    layers, the causal half in the full ones."""
    s = sizes(cfg)
    return 4.0 * s["heads"] * s["d"] * (
        s["sliding"] * pairs(positions, s["window"])
        + s["full"] * pairs(positions))


def attention_bytes(cfg: dict, positions: float) -> float:
    """Queries and keys and values read, the output written, once."""
    s = sizes(cfg)
    return s["layers"] * positions * 2 * (s["heads"] + s["kv_heads"]) \
        * s["d"] * _ITEMSIZE[cfg["dtype"]]


def prefill_flops(cfg: dict, positions: float, picks: float) -> float:
    """A prefill of ``positions``: every position through what
    :func:`active_params_per_token` counts, the attention's pairs, the
    head once."""
    s = sizes(cfg)
    return (2.0 * active_params_per_token(cfg, picks) * positions
            + attention_flops(cfg, positions) + 2.0 * s["h"] * s["vocab"])


def experts_touched_per_step(run):
    """Held experts that got a token, summed over a step's expert layers:
    the window's mean (None where the program counts none)."""
    c = run["counters"]
    if not c.get("moe.layer_steps"):
        return None
    return sizes(run["cell"].config)["expert"] \
        * c.get("moe.experts_touched", 0) / c["moe.layer_steps"]


def ring_rows_live_per_step(run):
    """Ring rows that hold a key, summed over a step's sliding layers and
    its lanes: the window's mean by ``window.rows_live`` (None where the
    program counts none)."""
    steps = steps_counted(run)
    rows = run["counters"].get("window.rows_live")
    return None if not steps or rows is None else rows / steps


def expert_ffn_least(cfg: dict, experts_touched: float,
                     peaks: dict) -> dict:
    """The grouped matmuls of one decode step: the weights of the held
    experts a token reached (counted over all layers) read once."""
    b = experts_touched * params(cfg)["expert"] * _ITEMSIZE[cfg["dtype"]]
    return {"seconds": b / peaks["hbm_bytes_per_s"], "bytes": b,
            "bound": "memory"}


def decode_step_least(cfg: dict, weight_bytes: float,
                      kv_bytes_per_token: float, live_tokens: float,
                      ring_rows: float, active_lanes: float,
                      experts_touched: float, picks: float,
                      peaks: dict) -> dict:
    """One decode step: every weight but the token table (a step gathers
    one row a lane of it) and the held experts no token reached, the LIVE
    ring rows (K and V of ``kv_heads`` heads a row) and the live rows of
    the paged pool, each once; or the active lanes' matmuls at the peak if
    that is longer."""
    s = sizes(cfg)
    item = _ITEMSIZE[cfg["dtype"]]
    parts = {"weights": weight_bytes - s["vocab"] * s["h"] * item
             - s["expert"] * s["held"] * params(cfg)["expert"] * item,
             "experts": experts_touched * params(cfg)["expert"] * item,
             "rings": ring_rows * 2 * s["kv_heads"] * s["d"] * item,
             "pool": kv_bytes_per_token * live_tokens}
    b = sum(parts.values())
    f = 2.0 * (active_params_per_token(cfg, picks) + s["h"] * s["vocab"]) \
        * active_lanes
    by_bytes = b / peaks["hbm_bytes_per_s"]
    by_flops = f / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops), "bytes": b, "flops": f,
            "parts": parts,
            "bound": "memory" if by_bytes >= by_flops else "compute"}


# ------------------------------------------------------ reading the trace

_RESULT = re.compile(r"\[\d+,(\d+),\d+\]")


def traced_prefills(run):
    """``[(positions, device seconds, the flash kernel's seconds)]`` of the
    traced stretch's ``jit_prefill`` executions that ran the kernel the
    program names ``swa_prefill_flash``, or None. Positions are the
    program's bucket, read off the kernel's own result ``[heads,
    positions, 128]``."""
    tr = run.get("trace")
    if tr is None:
        return None
    out = []
    for dev, _, start, end in _inside(tr, "jit_prefill"):
        positions, kernel_s = None, 0.0
        for name, s, d in dev["ops"]:
            head, _, rest = name.partition(" = ")
            if s >= start and s + d <= end and KERNEL in head:
                kernel_s += d
                m = _RESULT.search(rest)
                if m and positions is None:
                    positions = int(m.group(1))
        if positions:
            out.append((positions, end - start, kernel_s))
    return out or None
