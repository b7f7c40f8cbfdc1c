"""paddle_tpu.serving.telemetry (ISSUE 17): latency histograms (fixed
log buckets, merge/minus, percentile interpolation), the request-lifecycle
trace ring and its ``FLAGS_serving_telemetry`` gate, trace_id propagation
through a real ServingAPI run, Prometheus text rendering, Chrome
trace-event conversion, the windowed ``metrics.Meter`` decay regression,
and the profiler's per-run latency delta. ISSUE 24: ``telemetry.phase``
(histogram, ``time_us.*`` counter, profiler annotation), the phases of the
serving loop (closure, nesting, the profiler's clock), and first-token
time that holds the wait for the API lock. ISSUE 37: the admission's
tree under ``prefill``, padded positions, restarts by cause, the empty
device (``device.empty``) and ``decode.prepare`` in two."""
import glob
import json
import os
import re
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import RequestState, ServingAPI, telemetry
from paddle_tpu.serving import metrics as serving_metrics

pytestmark = pytest.mark.serving

MAX_LEN = 64
API_KW = dict(num_slots=4, kv_block_size=8, max_model_len=MAX_LEN)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


@pytest.fixture()
def spans_on():
    keep = paddle.get_flags(["serving_telemetry"])
    paddle.set_flags({"serving_telemetry": True})
    telemetry.reset_tracelog()
    yield
    telemetry.reset_tracelog()
    paddle.set_flags(keep)


# ------------------------------------------------------------- histograms


def test_histogram_percentile_within_one_bucket():
    h = telemetry.Histogram()
    for _ in range(90):
        h.record(1e-3)
    for _ in range(10):
        h.record(0.5)
    assert h.n == 100
    # each percentile lands inside the recorded sample's bucket
    # (log-bucket relative error is bounded by the 1.25x factor)
    assert 1e-3 / 1.25 <= h.percentile(50) <= 1e-3 * 1.25
    assert 0.5 / 1.25 <= h.percentile(99) <= 0.5 * 1.25
    assert h.percentile(5) <= h.percentile(50) <= h.percentile(99)
    assert abs(h.mean() - (90 * 1e-3 + 10 * 0.5) / 100) < 1e-9
    # negative skew clamps, never throws or corrupts counts
    h.record(-1.0)
    assert h.n == 101
    assert telemetry.Histogram().percentile(99) == 0.0


def test_histogram_merge_minus_and_buckets():
    a, b = telemetry.Histogram(), telemetry.Histogram()
    for _ in range(10):
        a.record(2e-3)
    for _ in range(30):
        b.record(8e-2)
    m = a.merge(b)
    assert m.n == 40 and abs(m.total - (a.total + b.total)) < 1e-12
    # merged percentiles see BOTH replicas' samples (p25 from a, p75 from b)
    assert m.percentile(20) <= 2e-3 * 1.25
    assert m.percentile(80) >= 8e-2 / 1.25
    d = m.minus(a)
    assert d.n == b.n and d.percentile(50) == b.percentile(50)
    # buckets(): cumulative, monotone, +Inf-free for in-range samples
    buckets = m.buckets()
    cums = [c for _, c in buckets]
    assert cums == sorted(cums) and cums[-1] == m.n
    assert all(bound > 0 for bound, _ in buckets)


def test_observe_records_global_and_extra_sets():
    telemetry.reset_histograms()
    extra = telemetry.HistogramSet()
    telemetry.observe("latency.ttft", 0.01, extra, None)
    telemetry.observe("latency.ttft", 0.02)
    assert telemetry.histogram("latency.ttft").n == 2
    assert extra.peek("latency.ttft").n == 1
    delta = telemetry.histograms_delta({})
    assert delta["latency.ttft"].n == 2
    table = telemetry.percentile_table()
    assert "latency.ttft" in table and "p99(ms)" in table


def test_meter_rate_decays_when_idle():
    """Satellite regression: tokens_per_sec is a sliding-window rate, not
    a lifetime average — 10s of idle tail must decay the gauge to 0."""
    t = [0.0]
    m = serving_metrics.Meter(window=10.0, now=lambda: t[0])
    for s in range(5):
        t[0] = float(s)
        m.tick(10)
    t[0] = 5.0
    assert m.rate() == pytest.approx(10.0, rel=0.25)
    assert m.tokens() == 50
    # the old lifetime-average bug: at t=16 it still reported ~3 tok/s
    t[0] = 16.0
    assert m.rate() == 0.0
    assert m.tokens() == 50  # lifetime count survives the window
    # traffic resumes: the rate reflects only the fresh window
    t[0] = 17.0
    m.tick(20)
    assert m.rate() == pytest.approx(2.0, rel=0.25)  # 20 tokens / 10s window
    m.reset()
    assert m.rate() == 0.0 and m.tokens() == 0


# ---------------------------------------------------------------- tracing


def test_span_gated_by_flag(spans_on):
    paddle.set_flags({"serving_telemetry": False})
    telemetry.span("tdeadbeef0001", telemetry.QUEUED, request_id="r1")
    assert telemetry.trace("tdeadbeef0001") == []
    paddle.set_flags({"serving_telemetry": True})
    telemetry.span("tdeadbeef0001", telemetry.QUEUED, request_id="r1")
    telemetry.span("", telemetry.QUEUED)  # no trace_id -> dropped silently
    evs = telemetry.trace("tdeadbeef0001")
    assert [e["event"] for e in evs] == [telemetry.QUEUED]
    assert evs[0]["request_id"] == "r1" and evs[0]["ts"] > 0


def test_tracelog_ring_drops_oldest_and_counts():
    log = telemetry.TraceLog(capacity=16)
    s0 = serving_metrics.stats().get("telemetry.spans_dropped", 0)
    for i in range(20):
        log.append("tring", telemetry.QUEUED, {"i": i})
    evs = log.trace("tring")
    assert len(evs) == 16
    assert [e["i"] for e in evs] == list(range(4, 20))  # oldest 4 dropped
    assert serving_metrics.stats()["telemetry.spans_dropped"] == s0 + 4
    # seq stays strictly increasing across the wrap
    seqs = [e["seq"] for e in evs]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)


def test_request_lifecycle_spans_and_histograms(model, spans_on):
    """One real request through ServingAPI: a single trace_id carries the
    SUBMITTED -> QUEUED -> ADMITTED -> FIRST_TOKEN -> FINISHED sequence in
    seq order, and the ttft/e2e/queue_wait histograms record it."""
    telemetry.reset_histograms()
    api = ServingAPI(model, **API_KW)
    try:
        rng = np.random.default_rng(7)
        prompt = rng.integers(0, 1024, (6,), dtype=np.int32)
        req = api.submit(prompt, max_new_tokens=4)
        assert req.trace_id.startswith("t") and len(req.trace_id) == 13
        api.run_until_idle()
        assert req.state == RequestState.FINISHED
        evs = telemetry.trace(req.trace_id)
        kinds = [e["event"] for e in evs]
        for k in (telemetry.SUBMITTED, telemetry.QUEUED, telemetry.ADMITTED,
                  telemetry.FIRST_TOKEN, telemetry.FINISHED):
            assert kinds.count(k) == 1, (k, kinds)
        assert kinds.index(telemetry.SUBMITTED) \
            < kinds.index(telemetry.QUEUED) \
            < kinds.index(telemetry.ADMITTED) \
            < kinds.index(telemetry.FIRST_TOKEN) \
            < kinds.index(telemetry.FINISHED)
        # every span of this trace names the same request
        assert {e["trace_id"] for e in evs} == {req.trace_id}
        hists = telemetry.histograms()
        for name in ("latency.ttft", "latency.e2e", "latency.queue_wait",
                     "latency.prefill", "latency.decode_step",
                     "latency.inter_token"):
            assert hists[name].n > 0, name
        assert hists["latency.ttft"].n == 1  # one request, one first token
        assert hists["latency.e2e"].n == 1
        # the engine's per-replica set saw the same request-scoped samples
        assert api.engine.hists.peek("latency.ttft").n == 1
    finally:
        api.close()


def test_preemption_keeps_trace_id_and_requeues(model, spans_on):
    """A preempted victim keeps its trace_id: the timeline shows
    PREEMPTED followed by a second QUEUED/ADMITTED, then FINISHED —
    one contiguous story, not two requests."""
    keep = paddle.get_flags(["serving_starvation_steps"])
    paddle.set_flags({"serving_starvation_steps": 1})
    # tiny arena: two long requests can't both hold blocks
    api = ServingAPI(model, num_slots=2, kv_block_size=8,
                     max_model_len=MAX_LEN, num_blocks=8)
    try:
        rng = np.random.default_rng(8)
        low = api.submit(rng.integers(0, 1024, (24,), dtype=np.int32),
                         max_new_tokens=24, priority=1)
        for _ in range(3):
            api.scheduler.step()
        high = api.submit(rng.integers(0, 1024, (24,), dtype=np.int32),
                          max_new_tokens=8, priority=0)
        api.run_until_idle()
        assert high.state == RequestState.FINISHED
        assert low.state == RequestState.FINISHED
        if low.preemptions:  # arena pressure actually bit
            kinds = [e["event"] for e in telemetry.trace(low.trace_id)]
            i = kinds.index(telemetry.PREEMPTED)
            assert telemetry.QUEUED in kinds[i:], kinds
            assert telemetry.ADMITTED in kinds[i:], kinds
            assert kinds[-1] == telemetry.FINISHED
            assert kinds.count(telemetry.SUBMITTED) == 1
    finally:
        api.close()
        paddle.set_flags(keep)


# ------------------------------------------------------------ export plane


_PROM_LINE = re.compile(
    r"^[a-zA-Z_][a-zA-Z0-9_]*(\{[^{}]*\})? -?\d+(\.\d+)?([eE][+-]?\d+)?$")


def test_prometheus_text_is_valid_and_complete(model):
    telemetry.reset_histograms()
    api = ServingAPI(model, **API_KW)
    try:
        rng = np.random.default_rng(9)
        api.submit(rng.integers(0, 1024, (5,), dtype=np.int32),
                   max_new_tokens=3)
        api.run_until_idle()
    finally:
        api.close()
    text = telemetry.prometheus_text()
    assert text.endswith("\n")
    families = set()
    for line in text.splitlines():
        if line.startswith("# TYPE"):
            _, _, fam, kind = line.split()
            assert kind in ("counter", "gauge", "histogram")
            families.add(fam)
        else:
            assert _PROM_LINE.match(line) or "+Inf" in line, line
    assert "paddle_serving_tokens_generated" in families
    assert "paddle_latency_ttft_seconds" in families
    # histogram contract: cumulative buckets end at +Inf == _count,
    # and the precomputed quantiles are present for the pool view
    assert 'paddle_latency_e2e_seconds_bucket{replica="pool",le="+Inf"}' \
        in text
    count = [ln for ln in text.splitlines()
             if ln.startswith("paddle_latency_e2e_seconds_count")]
    inf = [ln for ln in text.splitlines()
           if ln.startswith("paddle_latency_e2e_seconds_bucket")
           and 'le="+Inf"' in ln]
    assert count[0].rsplit(" ", 1)[1] == inf[0].rsplit(" ", 1)[1]
    assert 'quantile="0.99"' in text and 'quantile="0.50"' in text
    bucket_counts = [
        float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
        if ln.startswith("paddle_latency_e2e_seconds_bucket")]
    assert bucket_counts == sorted(bucket_counts)  # cumulative, monotone


def test_chrome_events_structure(spans_on):
    for i in range(3):
        telemetry.span("tchrome000001", telemetry.SPAN_KINDS[i], step=i)
    telemetry.span("tchrome000002", telemetry.FINISHED)
    evs = telemetry.chrome_events(telemetry.trace_events())
    json.dumps(evs)  # must be serializable as-is
    lanes = {e["args"]["name"] for e in evs if e["ph"] == "M"}
    assert lanes == {"tchrome000001", "tchrome000002"}
    slices = [e for e in evs if e["ph"] == "X"]
    instants = [e for e in evs if e["ph"] == "i"]
    assert len(slices) == 2 and len(instants) == 2  # terminal = instant
    assert all(e["dur"] >= 0 and e["ts"] > 0 for e in slices)
    assert all(e["args"]["trace_id"] for e in slices + instants)


def test_trace_dump_converts_input_file(tmp_path, spans_on):
    telemetry.span("tdump00000001", telemetry.SUBMITTED, request_id="d1")
    telemetry.span("tdump00000001", telemetry.FINISHED, request_id="d1")
    src = tmp_path / "spans.json"
    src.write_text(json.dumps({"events": telemetry.trace("tdump00000001")}))
    dst = tmp_path / "trace.json"
    from tools import trace_dump

    assert trace_dump.main(["--input", str(src), "-o", str(dst)]) == 0
    out = json.loads(dst.read_text())
    assert out["traceEvents"], out
    assert any(e.get("ph") == "i" and e["name"] == telemetry.FINISHED
               for e in out["traceEvents"])


def test_profiler_reports_latency_delta(model):
    from paddle_tpu import profiler

    telemetry.reset_histograms()
    telemetry.observe("latency.ttft", 0.5)  # pre-run noise: not in delta
    prof = profiler.Profiler(targets=[profiler.ProfilerTarget.CPU])
    prof.start()
    api = ServingAPI(model, **API_KW)
    try:
        rng = np.random.default_rng(11)
        api.submit(rng.integers(0, 1024, (5,), dtype=np.int32),
                   max_new_tokens=3)
        api.run_until_idle()
    finally:
        api.close()
    prof.stop()
    assert prof.latency_stats["latency.e2e.count"] == 1
    assert prof.latency_stats["latency.e2e.p99_ms"] > 0
    assert prof.latency_stats["latency.ttft.count"] == 1  # noise excluded


# ------------------------------------------------------- phases (ISSUE 24)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
#: parent phase -> the phases that lie inside it (docs/observability.md)
PHASE_TREE = {
    "sched.step": ("sched.admit", "decode_step", "sched.emit"),
    "sched.admit": ("prefill",),
    "prefill": ("prefill.setup", "prefill.upload", "prefill.dispatch",
                "prefill.wait", "prefill.finish"),
    "decode_step": ("decode.prepare", "decode.dispatch", "decode.wait"),
    "decode.prepare": ("decode.prepare.grow", "decode.prepare.upload"),
    "decode.wait": ("decode.release",),
}
#: every child an admission can have (``prefill.draft`` only where
#: speculation runs a draft model)
PREFILL_CHILDREN = PHASE_TREE["prefill"] + ("prefill.draft",)


def _time_us():
    return {k[len("time_us."):]: v for k, v in serving_metrics.stats().items()
            if k.startswith("time_us.")}


def _counters():
    return {k: v for k, v in serving_metrics.stats().items()
            if isinstance(v, int)}


def _moved(before):
    """What the counters moved by since the snapshot ``before``."""
    return serving_metrics.stats_delta(before, _counters(), drop_zero=True)


def _prompts(rng, n, lo=5, hi=12):
    return [rng.integers(0, 1024, (int(rng.integers(lo, hi)),),
                         dtype=np.int32) for _ in range(n)]


def test_phase_records_histogram_counter_and_self_time():
    telemetry.reset_histograms()
    before = _time_us()
    extra = telemetry.HistogramSet()
    with telemetry.phase("test.outer", extra):
        time.sleep(0.02)
        with telemetry.phase("test.inner", extra, turn=1):
            time.sleep(0.03)
    after = _time_us()
    outer = after["test.outer"] - before.get("test.outer", 0)
    inner = after["test.inner"] - before.get("test.inner", 0)
    assert isinstance(outer, int) and isinstance(inner, int)
    assert 30_000 <= inner <= outer and outer >= 50_000
    assert outer - inner >= 20_000  # the parent's self time
    for name, us in (("latency.test.outer", outer),
                     ("latency.test.inner", inner)):
        h = telemetry.histogram(name)
        assert h.n == 1 and extra.peek(name).n == 1
        assert abs(h.total * 1e6 - us) <= 1.0  # whole microseconds


def test_phase_stop_ends_early_and_discard_records_nothing():
    telemetry.reset_histograms()
    with telemetry.phase("test.stopped") as ph:
        time.sleep(0.01)
        ph.stop()
        time.sleep(0.03)  # after the stop: not the phase's time
    h = telemetry.histogram("latency.test.stopped")
    assert h.n == 1 and 0.01 <= h.total < 0.03
    with telemetry.phase("test.dropped") as ph:
        ph.discard()
    assert telemetry.histogram("latency.test.dropped").n == 0
    assert "time_us.test.dropped" not in serving_metrics.stats()


def test_pump_phases_cover_the_wall_time_and_children_fit_parents(model):
    """A background run of some tens of steps: ``pump.unlocked`` and
    ``sched.step`` partition the pump thread's time (within 5% of the wall
    time measured around the run), and no phase's children exceed it."""
    api = ServingAPI(model, background=True, **API_KW)
    try:
        rng = np.random.default_rng(24)
        api.result(api.submit(_prompts(rng, 1)[0], max_new_tokens=3),
                   timeout=300)  # compiled: the window below is steady
        time.sleep(0.05)
        uses = {k: h.n for k, h in telemetry.histograms().items()}
        c0, steps0 = _time_us(), serving_metrics.stats()["engine.steps"]
        t0 = time.perf_counter()
        reqs = [api.submit(p, max_new_tokens=40) for p in _prompts(rng, 6)]
        for r in reqs:
            api.result(r, timeout=300)
        time.sleep(0.05)
        wall_us = (time.perf_counter() - t0) * 1e6
        c1, steps1 = _time_us(), serving_metrics.stats()["engine.steps"]
    finally:
        api.close()
    assert all(r.state == RequestState.FINISHED for r in reqs)
    assert steps1 - steps0 >= 40
    d = {k: c1[k] - c0.get(k, 0) for k in c1}
    assert abs(d["pump.unlocked"] + d["sched.step"] - wall_us) \
        <= 0.05 * wall_us, (d, wall_us)
    n = {k: h.n - uses.get(k, 0) for k, h in telemetry.histograms().items()}
    for parent, children in PHASE_TREE.items():
        # each use rounds to a whole microsecond: half a one of slack
        slack = sum(n["latency." + c] for c in children) / 2 + 1
        assert sum(d[c] for c in children) <= d[parent] + slack, (parent, d)
    # what the three decode phases leave of the call is bookkeeping
    assert d["decode_step"] - sum(d[c] for c in PHASE_TREE["decode_step"]) \
        <= 0.25 * d["decode_step"]


def test_phases_nest_on_the_profilers_host_line(model, tmp_path):
    """Inside a ``jax.profiler`` session the phases are ``pt.*`` intervals
    on the pump thread's line of ``/host:CPU``, nested as the table in
    docs/observability.md says."""
    import jax
    from jax.profiler import ProfileData

    api = ServingAPI(model, background=True, **API_KW)
    try:
        rng = np.random.default_rng(25)
        api.result(api.submit(_prompts(rng, 1)[0], max_new_tokens=3),
                   timeout=300)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
        try:
            # 7 decode steps each: stop_trace may cut the last turn (its
            # pt.sched.step still open), and 5 whole ones must remain
            reqs = [api.submit(p, max_new_tokens=8)
                    for p in _prompts(rng, 3)]
            for r in reqs:
                api.result(r, timeout=300)
        finally:
            jax.profiler.stop_trace()
    finally:
        api.close()
    path = sorted(glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb")))[-1]
    host = next(p for p in ProfileData.from_file(path).planes
                if p.name == "/host:CPU")
    lines = [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
              for e in line.events if e.name.startswith("pt.")]
             for line in host.lines]
    pump = max(lines, key=lambda evs: sum(
        n == "pt.sched.step" for n, _, _ in evs))
    # a session cuts the turns at its edges: look at whole turns only
    whole = [(a, b) for n, a, b in pump if n == "pt.sched.step"]
    lo, hi = min(a for a, _ in whole), max(b for _, b in whole)
    by = {}
    for name, a, b in pump:
        if lo <= a and b <= hi:
            by.setdefault(name[len("pt."):], []).append((a, b))
    assert len(by["decode_step"]) >= 5 and len(by["prefill"]) >= 2
    # the empty device is one interval from the read that found it so to
    # the next compiled call: it starts in one phase and ends in another,
    # so it is exempt from containment (it is in no parent's list)
    assert by["device.empty"] and all(b > a for a, b in by["device.empty"])

    def inside(child, parents):
        return any(a <= child[0] and child[1] <= b for a, b in parents)

    for parent, children in PHASE_TREE.items():
        for c in children:
            assert by[c] and all(inside(ev, by[parent]) for ev in by[c]), c
    dispatched = 0
    for step in by["decode_step"]:
        mine = {k: [ev for ev in by["decode." + k] if inside(ev, [step])]
                for k in ("prepare", "dispatch", "wait")}
        # every turn reads one step; a turn that found the carried slot
        # state stale (a host write since the last dispatch) only reads
        assert mine["wait"] and len(mine["prepare"]) == len(mine["dispatch"])
        waits = iter(mine["wait"])
        for prep, disp in zip(mine["prepare"], mine["dispatch"]):
            # prepare, dispatch, then the wait around this step's release
            assert prep[1] <= disp[0] and disp[1] <= next(waits)[0]
            dispatched += 1
        # what is left is the read that ends the turn: of this turn's
        # step, or of the one dispatched a turn ago
        assert len(list(waits)) == 1
    assert dispatched >= 5
    # the two halves of the pump's time never overlap
    turns = sorted(by["pump.unlocked"] + by["sched.step"])
    assert all(a[1] <= b[0] for a, b in zip(turns, turns[1:]))
    # the handler threads' lock waits are on lines of their own
    assert any(n == "pt.submit.lock_wait" for evs in lines if evs is not pump
               for n, _, _ in evs)


# --------------------------------------------- an admission (ISSUE 37)


def _admission_api(model, kind):
    """An API whose admissions are of one kind, and the counter that says
    they were."""
    from paddle_tpu.serving import ServingConfig

    kw = dict(API_KW)
    if kind == "prefix_hit":
        kw["prefix_cache"] = True
    elif kind == "chunked":
        kw["chunked_prefill"] = 8
    elif kind == "draft":
        paddle.seed(77)
        draft = GPTForCausalLM(gpt_tiny())
        draft.eval()
        kw.update(spec_k=2, draft_model=draft)
    return ServingAPI(model, ServingConfig(**kw))


@pytest.mark.parametrize("kind", ["plain", "prefix_hit", "chunked", "draft"])
def test_prefill_children_close_on_their_parent(model, kind):
    """``prefill.setup`` + ``upload`` + ``dispatch`` + ``wait`` (+
    ``draft``) + ``finish`` are what a ``prefill`` phase holds: never more
    than it, and within 2% of it once each phase's own entry and exit (some
    15 us at this size, nothing beside the chip's 30-300 ms admissions) is
    allowed for. In the foreground, so no other thread holds the GIL
    between two children."""
    api = _admission_api(model, kind)
    try:
        rng = np.random.default_rng(37)
        shared = rng.integers(0, 1024, (16,), dtype=np.int32)
        prompts = [np.concatenate([shared, p]) for p in _prompts(rng, 7)]
        api.submit(prompts[0], max_new_tokens=3)
        api.run_until_idle()  # compiled, and the shared prefix is resident
        uses = {k: h.n for k, h in telemetry.histograms().items()}
        before = _counters()
        reqs = [api.submit(p, max_new_tokens=6) for p in prompts[1:]]
        api.run_until_idle()
        d = _moved(before)
    finally:
        api.close()
    assert all(r.state == RequestState.FINISHED for r in reqs)
    used = {k[len("latency."):]: h.n - uses.get(k, 0)
            for k, h in telemetry.histograms().items()}
    n = sum(used.get(c, 0) for c in PREFILL_CHILDREN)
    kids = sum(d.get("time_us." + c, 0) for c in PREFILL_CHILDREN)
    parent = d["time_us.prefill"]
    assert kids <= parent + n / 2 + 1, d
    assert kids >= 0.98 * parent - 25 * n, (kids, parent, n)
    assert d["engine.admits"] == 6
    # every compiled call counted, with the positions it computed
    assert d["prefill.positions_computed"] >= d["tokens.prefill"]
    assert d["prefill.upload_bytes"] > 4 * d["prefill.positions_computed"]
    if kind == "prefix_hit":
        assert d["tokens.prefill_avoided"] == 6 * 16
        assert d["prefill.calls"] == d["prefix.suffix_prefills"] == 6
    elif kind == "chunked":
        assert d["prefill.calls"] == d["chunk.chunks"] > 6
        # a chunk of 8 runs in the ladder's first bucket, 16
        assert d["prefill.positions_computed"] == 16 * d["chunk.chunks"]
    else:
        assert d["prefill.calls"] == 6
    assert ("time_us.prefill.draft" in d) == (kind == "draft")
    if kind == "draft":
        # speculation keeps every turn synchronous: what the device stood
        # empty for is `sync`, never a restart
        assert d["time_us.device.empty.sync"] > 0
        assert "time_us.device.empty.restart" not in d
        return  # the speculative step prepares for itself
    # decode.prepare in two, closed the same way
    parts = (d["time_us.decode.prepare.grow"]
             + d["time_us.decode.prepare.upload"])
    prepare, uses = d["time_us.decode.prepare"], used["decode.prepare"]
    assert 0.98 * prepare - 50 * uses <= parts <= prepare + uses + 1
    assert d["engine.step_upload_bytes"] > 0 and d["engine.lane_steps"] > 0


def test_padded_positions_are_counted_beside_the_real_ones(model):
    """Prompts of 5, 7, 9, 13, 17, 25 and 33 tokens on the ladder from 4
    (2^k and 3 * 2^(k-1)) prefill in buckets of 6, 8, 12, 16, 24, 32 and
    48: 146 positions computed for 109 real ones, and the benchmark's
    ``prefill_padding_pct`` reads 100 x (1 - 109 / 146)."""
    api = ServingAPI(model, prefill_bucket_min=4, **API_KW)
    try:
        rng = np.random.default_rng(38)
        before = _counters()
        for n in (5, 7, 9, 13, 17, 25, 33):
            api.submit(rng.integers(0, 1024, (n,), dtype=np.int32),
                       max_new_tokens=2)
            api.run_until_idle()
        d = _moved(before)
    finally:
        api.close()
    assert d["prefill.calls"] == 7
    assert d["tokens.prefill"] == d["prefill.body_tokens"] == 109
    assert d["prefill.positions_computed"] == 146
    padding = 100.0 * (1.0 - d["tokens.prefill"]
                       / d["prefill.positions_computed"])
    assert padding == pytest.approx(100.0 * 37 / 146, abs=1e-12)
    # nobody was decoding while these prefills ran: no lane-time was lost
    assert "prefill.lane_us_blocked" not in d
    # lanes a read step ran: one request alone, one lane a step
    assert d["engine.lane_steps"] == d["engine.steps"] == 7


def test_restarts_are_counted_by_who_made_the_state_stale(model):
    """Two requests admitted in one pass start the pump from the mirrors
    once (``admit``); one admitted in the middle of their run costs one
    more; the one that ends first, with nobody waiting for its slot, one
    ``retire``; and while prefills run beside decoding lanes the lane-time
    they take is counted."""
    api = ServingAPI(model, **API_KW)
    try:
        rng = np.random.default_rng(39)
        p = _prompts(rng, 4)
        api.submit(p[0], max_new_tokens=2)
        api.run_until_idle()  # compiled
        before = _counters()
        long_a = api.submit(p[1], max_new_tokens=40)
        short = api.submit(p[2], max_new_tokens=12)
        for _ in range(4):
            api._pump_once()
        first = _moved(before)
        before = _counters()
        api.submit(p[3], max_new_tokens=40)
        for _ in range(4):
            api._pump_once()
        middle = _moved(before)
        assert not short.finished
        before = _counters()
        while not short.finished:
            api._pump_once()
        for _ in range(3):
            api._pump_once()
        assert not long_a.finished
        retired = _moved(before)
        api.run_until_idle()
    finally:
        api.close()

    def restarts(d):
        return {k[len("engine.restarts."):]: v for k, v in d.items()
                if k.startswith("engine.restarts.")}

    assert restarts(first) == {"admit": 1} and first["engine.admits"] == 2
    assert restarts(middle) == {"admit": 1} and middle["engine.admits"] == 1
    assert restarts(retired) == {"retire": 1}
    assert retired["engine.retires"] == 1 and "engine.admits" not in retired
    # the admission in the middle stopped two decoding lanes
    assert middle["prefill.lane_us_blocked"] >= 2 * middle["time_us.prefill"]
    # each restart ended a stretch in which the device had nothing to do;
    # so did the second prefill of the pass that admitted two. The prefill
    # in the middle queued behind the step in flight: no gap before it
    assert middle["time_us.device.empty.restart"] > 0
    assert retired["time_us.device.empty.restart"] > 0
    assert first["time_us.device.empty.admit"] > 0
    assert "time_us.device.empty.admit" not in middle


def test_only_idle_grows_while_the_server_has_no_work(model):
    api = ServingAPI(model, background=True, **API_KW)
    try:
        rng = np.random.default_rng(40)
        p = _prompts(rng, 2)
        api.result(api.submit(p[0], max_new_tokens=3), timeout=300)
        time.sleep(0.05)  # the pump has seen that nothing is left
        gaps = telemetry.histogram("latency.device.empty").n
        before = _counters()
        time.sleep(0.3)
        assert _moved(before) == {} or set(_moved(before)) <= {
            "time_us.pump.unlocked"}  # an open phase counts at its end
        api.result(api.submit(p[1], max_new_tokens=3), timeout=300)
        time.sleep(0.05)
        d = _moved(before)
    finally:
        api.close()
    assert d["time_us.device.empty.idle"] >= 300_000
    others = sum(v for k, v in d.items()
                 if k.startswith("time_us.device.empty.")
                 and not k.endswith(".idle"))
    assert others < 100_000, d
    # one gap that the admission ended as `idle`, then the request's own
    assert telemetry.histogram("latency.device.empty").n > gaps


def test_the_run_report_gives_the_admissions_tree_and_the_restarts(model):
    """``tools/serving_stats.py --run`` reads the same counters: the
    ``prefill`` phase's children in ms a compiled call, the padding, the
    restarts by who made the state stale and the empty device by cause."""
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "serving_stats", os.path.join(REPO, "tools", "serving_stats.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    assert tool.admission_report({"tokens.generated": 5}) == {}
    api = ServingAPI(model, **API_KW)
    try:
        rng = np.random.default_rng(41)
        before = serving_metrics.stats()
        for p in _prompts(rng, 3):
            api.submit(p, max_new_tokens=6)
        api.run_until_idle()
        delta = serving_metrics.stats_delta(before, serving_metrics.stats(),
                                            drop_zero=True)
    finally:
        api.close()
    rep = tool.admission_report(delta)
    assert rep["prefill_calls"] == rep["admits"] == 3
    kids = rep["children_ms_per_call"]
    assert list(kids) == ["setup", "upload", "dispatch", "wait", "finish"]
    assert 0.9 * rep["prefill_ms_per_call"] - 0.2 <= sum(kids.values()) \
        <= rep["prefill_ms_per_call"] + 0.01
    # prompts of 5 to 11 tokens in the ladder's first bucket, 16
    assert rep["padding_pct"] == pytest.approx(
        100.0 * (1.0 - delta["tokens.prefill"] / 48), abs=0.01)
    assert rep["upload_kb_per_call"] > 0.0
    assert rep["restarts"] == {"admit": 1}  # one pass admitted all three
    assert set(rep["device_empty_ms"]) >= {"admit", "restart"}
    assert rep["restart_ms_per_admission"] > 0.0


def test_ttft_and_e2e_include_the_wait_for_the_api_lock(model):
    """A submit made while another thread holds ``ServingAPI._lock``: the
    wait is a ``submit.lock_wait`` sample AND inside that request's
    ``latency.ttft`` and ``latency.e2e`` (it used to start them after)."""
    telemetry.reset_histograms()
    api = ServingAPI(model, **API_KW)
    out, at_the_door = [], threading.Event()

    def client():
        at_the_door.set()
        out.append(api.submit(np.arange(6, dtype=np.int32),
                              max_new_tokens=3))

    try:
        t = threading.Thread(target=client)
        with api._lock:
            t.start()
            assert at_the_door.wait(10)
            time.sleep(0.06)
        t.join(30)
        assert not t.is_alive() and len(out) == 1
        api.run_until_idle()
    finally:
        api.close()
    assert out[0].state == RequestState.FINISHED
    waited = telemetry.histogram("latency.submit.lock_wait")
    assert waited.n == 1 and waited.total >= 0.05
    assert api.engine.hists.peek("latency.submit.lock_wait").n == 1
    for name in ("latency.ttft", "latency.e2e"):
        h = telemetry.histogram(name)
        assert h.n == 1 and h.total >= 0.05, name
    assert serving_metrics.stats()["time_us.submit.lock_wait"] >= 50_000


def test_a_request_built_directly_still_stamps_its_own_submit_time():
    from paddle_tpu.serving.scheduler import Request

    t0 = time.perf_counter()
    req = Request(np.arange(4), max_new_tokens=2)
    assert t0 <= req._submit_ts <= time.perf_counter()
    assert Request(np.arange(4), _submit_ts=12.5)._submit_ts == 12.5


def test_metric_key_lint_knows_every_phase_key(model):
    """``tools/analyze.py``'s ``unknown-metric-key`` rule over the files
    that emit phases: nothing unknown; and every key a run leaves in the
    registries sits in a documented namespace."""
    from paddle_tpu import analysis

    report = analysis.run_analysis(
        ["paddle_tpu/serving/telemetry.py", "paddle_tpu/serving/metrics.py",
         "paddle_tpu/serving/api.py", "paddle_tpu/serving/scheduler.py",
         "paddle_tpu/serving/engine.py", "paddle_tpu/serving/tiered.py",
         "paddle_tpu/serving/spec_decode.py"],
        root=REPO, rules=["unknown-metric-key"], full_corpus=False)
    assert not report.findings, report.findings
    api = ServingAPI(model, **API_KW)
    try:
        api.submit(np.arange(5, dtype=np.int32), max_new_tokens=2)
        api.run_until_idle()
    finally:
        api.close()
    keys = list(serving_metrics.stats())
    assert any(k.startswith("time_us.decode.") for k in keys)
    for key in ("time_us.prefill.setup", "time_us.prefill.upload",
                "time_us.prefill.dispatch", "time_us.prefill.wait",
                "time_us.prefill.finish", "time_us.decode.prepare.grow",
                "time_us.decode.prepare.upload", "prefill.calls",
                "prefill.positions_computed", "prefill.upload_bytes",
                "engine.restarts.admit", "engine.step_upload_bytes",
                "engine.lane_steps"):
        assert key in keys, key
    assert any(k.startswith("time_us.device.empty.") for k in keys)
    assert "latency.device.empty" in telemetry.histograms()
    for key in keys:
        assert key.split(".", 1)[0] in \
            serving_metrics.DOCUMENTED_NAMESPACES, key
    for key in telemetry.histograms():
        assert key.split(".", 1)[0] in telemetry.DOCUMENTED_NAMESPACES, key
