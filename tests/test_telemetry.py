"""paddle_tpu.serving.telemetry (ISSUE 17): latency histograms (fixed
log buckets, merge/minus, percentile interpolation), the request-lifecycle
trace ring and its ``FLAGS_serving_telemetry`` gate, trace_id propagation
through a real ServingAPI run, Prometheus text rendering, Chrome
trace-event conversion, the windowed ``metrics.Meter`` decay regression,
and the profiler's per-run latency delta. ISSUE 24: ``telemetry.phase``
(histogram, ``time_us.*`` counter, profiler annotation), the phases of the
serving loop (closure, nesting, the profiler's clock), and first-token
time that holds the wait for the API lock."""
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
    "decode_step": ("decode.prepare", "decode.dispatch", "decode.wait"),
    "decode.wait": ("decode.release",),
}


def _time_us():
    return {k[len("time_us."):]: v for k, v in serving_metrics.stats().items()
            if k.startswith("time_us.")}


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
    for key in keys:
        assert key.split(".", 1)[0] in \
            serving_metrics.DOCUMENTED_NAMESPACES, key
    for key in telemetry.histograms():
        assert key.split(".", 1)[0] in telemetry.DOCUMENTED_NAMESPACES, key
