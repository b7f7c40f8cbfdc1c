"""What the sparse-attention-and-experts model (Keye) NEEDS on this chip,
from THIS configuration's keys: parameters a token is multiplied by
(grouped-query attention, the indexer, the router and the HELD experts a
token was routed to), the (query, key) pairs the index scores cannot avoid
(the causal half) and the pairs the attention cannot avoid (``min(t + 1,
topk)`` keys a query), the operations of a prefill, the least bytes of a
decode step and of its indexer, selection and attention. An expert that is
not held here or that no token reached, a K/V row the selection did not
keep, a dead index key and anything recomputed are not counted. A prefill
counts at its bucket: the chip computes the padded positions like the real
ones (``roofline/hybrid_prefill.py`` says the same). The trace's readers
that know no configuration are ``roofline/latent_moe.py``'s.

**Which operations of ``jit_step`` are the indexer's, the selection's and
the attention's.** The program wraps them in the scopes ``indexer``,
``select`` and ``sparse_attn``, but a scope changes no event's name
(PERF.md section 3: it is a stat of the event's metadata, which the
harness's trace does not keep), so they are told by what their HLO text
shows: the kernel by the name the program gives it
(``paged_index_scores``), the selection by the ``[lanes, context]`` array
it searches, the attention by the gathered ``[lanes, topk, kv_heads,
head_dim]`` rows and the ``[lanes, kv_heads, group, 1, topk]`` scores
(:func:`scope_patterns`; checked against the scopes' own metadata in the
program compiled for a described v5e, PR 48)."""
import re

from benchmark.roofline.latent_moe import (_ITEMSIZE, _inside,  # noqa: F401
                                           live)

FLASH = "sparse_prefill_flash"
SCOPES = ("indexer", "select", "sparse_attn")


def sizes(cfg: dict) -> dict:
    g = lambda k: int(cfg[k])
    sa, held = cfg["sa_config"], g("num_experts")
    return {"h": g("hidden_size"), "heads": g("num_attention_heads"),
            "kv_heads": g("num_key_value_heads"), "d": g("head_dim"),
            "index_heads": int(sa["indexer_num_heads"]),
            "index_dim": int(sa["indexer_head_dim"]),
            "topk": int(sa["topk"]),
            "expert_w": g("moe_intermediate_size"), "held": held,
            "routed": int(cfg.get("published", {}).get("num_experts", held)),
            "k": g("num_experts_per_tok"), "layers": g("num_hidden_layers"),
            "vocab": g("vocab_size")}


def params(cfg: dict) -> dict:
    """Matmul parameters: of one attention sublayer (q, k, v, o), one
    indexer (its queries, its key, its head weights), the router, one
    expert."""
    s = sizes(cfg)
    h, wide, narrow = s["h"], s["heads"] * s["d"], s["kv_heads"] * s["d"]
    return {"attention": 2 * h * wide + 2 * h * narrow,
            "indexer": h * (s["index_heads"] * s["index_dim"]
                            + s["index_dim"] + s["index_heads"]),
            "router": h * s["routed"],
            "expert": 3 * h * s["expert_w"]}


def token_bytes(cfg: dict) -> dict:
    """Bytes one token keeps a layer: its K and V rows, its index key."""
    s, item = sizes(cfg), _ITEMSIZE[cfg["dtype"]]
    return {"kv": 2 * s["kv_heads"] * s["d"] * item,
            "index": s["index_dim"] * item}


def active_params_per_token(cfg: dict, picks: float) -> float:
    """What one token is multiplied by here, the head not counted: every
    layer's attention, indexer and router and the held experts it was
    routed to (``picks`` a layer)."""
    s, p = sizes(cfg), params(cfg)
    return s["layers"] * (p["attention"] + p["indexer"] + p["router"]
                          + picks * p["expert"])


def pairs(positions: float) -> float:
    """(query, key) pairs of one layer's index scores: key ``s <= t``."""
    return positions * (positions + 1) / 2.0


def kept_pairs(positions: float, topk: int) -> float:
    """(query, key) pairs of one layer's attention: ``min(t + 1, topk)``
    keys a query."""
    if positions <= topk:
        return pairs(positions)
    return pairs(topk) + (positions - topk) * topk


def index_flops(cfg: dict, positions: float) -> float:
    """Every index head's product over the causal pairs of every layer (2
    operations a pair a value of the index head)."""
    s = sizes(cfg)
    return 2.0 * s["index_heads"] * s["index_dim"] * s["layers"] \
        * pairs(positions)


def attention_flops(cfg: dict, positions: float) -> float:
    """Scores and values of every head over the KEPT pairs of every layer
    (4 operations a pair a value of the head)."""
    s = sizes(cfg)
    return 4.0 * s["heads"] * s["d"] * s["layers"] \
        * kept_pairs(positions, s["topk"])


def prefill_flops(cfg: dict, positions: float, picks: float) -> float:
    """A prefill of ``positions``: every position through what
    :func:`active_params_per_token` counts, the index scores' pairs, the
    attention's kept pairs, the head once."""
    s = sizes(cfg)
    return (2.0 * active_params_per_token(cfg, picks) * positions
            + index_flops(cfg, positions) + attention_flops(cfg, positions)
            + 2.0 * s["h"] * s["vocab"])


# ------------------------------------------------ the program's counters


def steps_counted(run):
    """Decode steps the window's counters cover (``sparse.layer_steps``
    over the layers); None where the program counts none."""
    n = run["counters"].get("sparse.layer_steps", 0)
    return n / sizes(run["cell"].config)["layers"] if n else None


def rows_per_step(run):
    """``{"live", "read", "index"}``: K/V rows a dense read would have
    touched, K/V rows the attention read and index keys scored, summed
    over a step's layers and its lanes: the window's means by the
    program's ``sparse.*`` counters (None where it counts none)."""
    steps, c = steps_counted(run), run["counters"]
    if not steps:
        return None
    return {"live": c.get("sparse.rows_live", 0) / steps,
            "read": c.get("sparse.rows_read", 0) / steps,
            "index": c.get("sparse.index_rows_scored", 0) / steps}


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


def experts_touched_per_step(run):
    """Held experts that got a token, summed over a step's layers: the
    window's mean (None where the program counts none)."""
    c = run["counters"]
    if not c.get("moe.layer_steps"):
        return None
    return sizes(run["cell"].config)["layers"] \
        * c.get("moe.experts_touched", 0) / c["moe.layer_steps"]


# ------------------------------------------------------------ least times


def sparse_decode_least(cfg: dict, rows: dict, active_lanes: float,
                        peaks: dict) -> dict:
    """One decode step's indexer, selection and attention: the live index
    keys and the K/V rows the attention read, each once, and every active
    lane's queries in (attention's and the indexer's, with its head
    weights) and output out."""
    s, item, t = sizes(cfg), _ITEMSIZE[cfg["dtype"]], token_bytes(cfg)
    lane = (2 * s["heads"] * s["d"] + s["index_heads"] * s["index_dim"]) \
        * item + 4 * s["index_heads"]
    parts = {"index_keys": rows["index"] * t["index"],
             "kv_rows": rows["read"] * t["kv"],
             "queries_and_outputs": s["layers"] * active_lanes * lane}
    b = sum(parts.values())
    return {"seconds": b / peaks["hbm_bytes_per_s"], "bytes": b,
            "parts": parts, "bound": "memory"}


def expert_ffn_least(cfg: dict, experts_touched: float,
                     peaks: dict) -> dict:
    """The grouped matmuls of one decode step: the weights of the held
    experts a token reached (counted over all layers) read once."""
    b = experts_touched * params(cfg)["expert"] * _ITEMSIZE[cfg["dtype"]]
    return {"seconds": b / peaks["hbm_bytes_per_s"], "bytes": b,
            "bound": "memory"}


def decode_step_least(cfg: dict, weight_bytes: float, rows: dict,
                      active_lanes: float, experts_touched: float,
                      picks: float, peaks: dict) -> dict:
    """One decode step: every weight but the token table (a step gathers
    one row a lane of it) and the held experts no token reached, the live
    index keys and the K/V rows the attention read, each once; or the
    active lanes' matmuls at the peak if that is longer."""
    s, item, t = sizes(cfg), _ITEMSIZE[cfg["dtype"]], token_bytes(cfg)
    parts = {"weights": weight_bytes - s["vocab"] * s["h"] * item
             - s["layers"] * s["held"] * params(cfg)["expert"] * item,
             "experts": experts_touched * params(cfg)["expert"] * item,
             "index_keys": rows["index"] * t["index"],
             "kv_rows": rows["read"] * t["kv"]}
    b = sum(parts.values())
    f = 2.0 * (active_params_per_token(cfg, picks) + s["h"] * s["vocab"]) \
        * active_lanes
    by_bytes = b / peaks["hbm_bytes_per_s"]
    by_flops = f / peaks["bf16_flops_per_s"]
    return {"seconds": max(by_bytes, by_flops), "bytes": b, "flops": f,
            "parts": parts,
            "bound": "memory" if by_bytes >= by_flops else "compute"}


# ------------------------------------------------------ reading the trace


def scope_patterns(cfg: dict, lanes: int) -> dict:
    """scope -> a pattern over an operation's HLO text (its instruction
    name and result): see the head of this file."""
    s, eng = sizes(cfg), cfg["serving"]["engine"]
    bs = int(eng["kv_block_size"])
    context = -(-int(eng["max_model_len"]) // bs) * bs
    k, kvh, d = min(s["topk"], context), s["kv_heads"], s["d"]
    group = s["heads"] // kvh
    attn = [f"[{lanes},{k},{kvh},{d}]", f"[{lanes * k},{kvh},{d}]",
            f"[{lanes},{kvh},{group},1,{k}]", f"[{lanes},1,{kvh},{group},{d}]"]
    return {"indexer": re.compile(r"^%paged_index_scores[.\d]* = "),
            "select": re.compile(re.escape(f"[{lanes},{context}]")),
            "sparse_attn": re.compile("|".join(map(re.escape, attn)))}


def _scope_of(text: str, patterns: dict):
    head = text[:400].split("), ", 1)[0]
    for scope in SCOPES:
        if patterns[scope].search(head):
            return scope
    return None


def step_scope_seconds(run):
    """``(decode steps in the traced stretch, {scope: device seconds inside
    them of the operations that scope's pattern accepts})``, or None where
    nothing was traced or no such operation ran (a program without the
    kernel: the parent's)."""
    tr = run.get("trace")
    if tr is None:
        return None
    patterns = scope_patterns(run["cell"].config,
                              int(run["program"]["num_slots"]))
    steps = _inside(tr, "jit_step")
    total = dict.fromkeys(SCOPES, 0.0)
    for dev in tr.devices.values():
        spans = sorted((s, e) for d, _, s, e in steps if d is dev)
        if not spans:
            continue
        i = 0
        for name, s, d in sorted(dev["ops"], key=lambda o: o[1]):
            while i < len(spans) and spans[i][1] < s:
                i += 1
            if i == len(spans):
                break
            if s >= spans[i][0] and s + d <= spans[i][1]:
                scope = _scope_of(name, patterns)
                if scope:
                    total[scope] += d
    if not steps or not total["indexer"]:
        return None
    return len(steps), total


_RESULT = re.compile(r"\[\d+,(\d+),\d+\]")


def traced_prefills(run):
    """``[(positions, device seconds)]`` of the traced stretch's
    ``jit_prefill`` executions that ran the kernel the program names
    ``sparse_prefill_flash``, or None. Positions are the program's bucket,
    read off the kernel's own result ``[heads, positions, 128]``."""
    tr = run.get("trace")
    if tr is None:
        return None
    out = []
    for dev, _, start, end in _inside(tr, "jit_prefill"):
        for name, s, d in dev["ops"]:
            head, _, rest = name.partition(" = ")
            if s >= start and s + d <= end and FLASH in head:
                m = _RESULT.search(rest)
                if m:
                    out.append((int(m.group(1)), end - start))
                    break
    return out or None
