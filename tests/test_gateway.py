"""paddle_tpu.serving.gateway: the multi-tenant front door (ISSUE 8) —
replica router (least-outstanding-work + bounded prefix-cache affinity,
crash-loop ejection with journaled re-route, respawn with backoff,
scale-down through drain), tenant quotas (token bucket / concurrency /
weighted fair share, retriable sheds with retry-after), and the HTTP/SSE
streaming gateway (endpoints, 429/503 error taxonomy, SIGTERM drain).

Pools that get ejected, drained, or scaled build their own instances —
like the drain tests in test_serving.py, a drained pool refuses admissions
forever. Tenancy gates are unit-tested without any engine (pure policy).
A miniature load/fairness run is here under the ``slow`` marker.
"""
import json
import re
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import compile_cache, resilience
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import (
    ReplicaPool,
    RequestState,
    ServingAPI,
    TenantConfig,
    TenantManager,
    telemetry,
)
from paddle_tpu.serving import metrics as serving_metrics
from paddle_tpu.serving.gateway import Gateway

pytestmark = [pytest.mark.serving, pytest.mark.gateway]

MAX_LEN = 64
POOL_KW = dict(num_slots=4, kv_block_size=8, max_model_len=MAX_LEN)


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


@pytest.fixture(scope="module")
def pool(model):
    """Shared 2-replica foreground pool for tests that neither drain nor
    eject (those build their own — a drained pool refuses admissions)."""
    p = ReplicaPool(model, replicas=2, **POOL_KW)
    yield p
    p.close()


def _prompt(rng, n):
    return rng.integers(0, 1024, (n,), dtype=np.int32)


def _ref(model, prompt, max_new, stop=None):
    out = model.generate(Tensor(np.asarray(prompt)[None]),
                         max_new_tokens=max_new, stop_token_id=stop)
    return np.asarray(out._data)[0]


def _kill_decode(replica):
    """Make one replica's engine die on every decode step: the supervisor
    rebuilds+replays until the crash-loop breaker opens, which is exactly
    the state the router's health policy keys on."""
    def dying():
        raise resilience.ServingDeviceError("injected: replica chip pulled")

    replica.api.engine.decode_step = dying


# ---------------------------------------------------------------- routing


def test_routing_least_outstanding(pool, model):
    """Without pumping, successive submissions alternate replicas (each
    submit raises the outstanding count the next routing decision sees),
    and everything completes with generate() parity."""
    rng = np.random.default_rng(1)
    prompts = [_prompt(rng, n) for n in (5, 7, 6, 9)]
    rrs = [pool.submit(p, max_new_tokens=4, tenant="route")
           for p in prompts]
    placed = [rr._replica_idx for rr in rrs]
    assert placed.count(0) == 2 and placed.count(1) == 2, placed
    pool.run_until_idle()
    for p, rr in zip(prompts, rrs):
        assert rr.state == RequestState.FINISHED
        np.testing.assert_array_equal(rr.output_ids(), _ref(model, p, 4))


def test_streaming_through_pool(pool, model):
    rng = np.random.default_rng(2)
    p = _prompt(rng, 6)
    rr = pool.submit(p, max_new_tokens=5, tenant="route")
    toks = list(pool.stream(rr))
    assert rr.state == RequestState.FINISHED
    np.testing.assert_array_equal(np.concatenate([p, toks]),
                                  _ref(model, p, 5))


def test_api_submit_journal_resumes_token_for_token(model):
    """The router's re-queue primitive: ``ServingAPI.submit(journal=...)``
    resumes a partial stream exactly where it left off — only NEW tokens
    are streamed, and the journal counts toward the budget."""
    api = ServingAPI(model, **POOL_KW)
    rng = np.random.default_rng(3)
    p = _prompt(rng, 7)
    ref = _ref(model, p, 8)
    journal = [int(t) for t in ref[7:10]]  # first 3 generated tokens
    req = api.submit(p, max_new_tokens=8, journal=journal)
    streamed = []
    for tok in api.stream(req):
        streamed.append(tok)
    np.testing.assert_array_equal(req.output_ids(), ref)
    np.testing.assert_array_equal(streamed, ref[10:])  # journal not re-sent
    with pytest.raises(ValueError):
        api.submit(p, max_new_tokens=3, journal=[1, 2, 3])  # exhausted
    api.close()


def test_cache_affinity_bounded(model):
    """A replica whose radix tree holds the prompt's prefix wins routing
    while its load is within the slack; past the slack the cold
    least-loaded replica wins — warm traffic cannot pile up unboundedly."""
    pool = ReplicaPool(model, replicas=2, prefix_cache=True,
                       affinity_slack=1, **POOL_KW)
    try:
        rng = np.random.default_rng(4)
        sysp = _prompt(rng, 16)  # two full 8-token blocks to share

        def with_tail(n):
            return np.concatenate([sysp, _prompt(rng, n)])

        warm = pool.submit(with_tail(3), max_new_tokens=2, tenant="warm")
        assert warm._replica_idx == 0  # empty pool: least-loaded is idx 0
        pool.run_until_idle()  # replica 0's tree now holds the prefix
        a0 = serving_metrics.stats().get("gateway.affinity_routes", 0)
        cold = pool.submit(_prompt(rng, 5), max_new_tokens=2, tenant="cold")
        assert cold._replica_idx == 0  # both idle: (load, idx) order
        # replica 0 is busier (1 outstanding) but warm and within slack=1
        w2 = pool.submit(with_tail(4), max_new_tokens=2, tenant="warm")
        assert w2._replica_idx == 0
        assert serving_metrics.stats()["gateway.affinity_routes"] == a0 + 1
        # now replica 0 holds 2 outstanding: past the slack, the warm
        # preference must NOT starve the cold replica's capacity
        w3 = pool.submit(with_tail(5), max_new_tokens=2, tenant="warm")
        assert w3._replica_idx == 1
        pool.run_until_idle()
        assert all(r.state == RequestState.FINISHED
                   for r in (warm, cold, w2, w3))
    finally:
        pool.close()


# ---------------------------------------------------------------- tenancy


def test_token_bucket_shed_is_retriable():
    tm = TenantManager()
    tm.configure(TenantConfig("t", rate=10.0, burst=20.0))
    tm.admit("t", 16)  # burst covers it
    with pytest.raises(resilience.QuotaExceededError) as ei:
        tm.admit("t", 16)  # bucket holds 4 < 16
    assert ei.value.retry_after > 0
    assert ei.value.tenant == "t"
    # refill at 10 tok/s: after the hinted wait the same request admits
    state = tm._tenants["t"]
    state.refilled_at -= ei.value.retry_after + 0.01
    cfg = tm.admit("t", 16)
    assert cfg.priority == 0
    stats = tm.stats()["t"]
    assert stats["admitted"] == 2 and stats["shed"] == 1


def test_concurrency_quota_and_release():
    tm = TenantManager()
    tm.configure(TenantConfig("c", max_concurrency=2))
    tm.admit("c", 4)
    tm.admit("c", 4)
    with pytest.raises(resilience.QuotaExceededError):
        tm.admit("c", 4)
    tm.release("c", tokens_out=4)
    tm.admit("c", 4)  # freed slot admits again
    assert tm.stats()["c"]["inflight"] == 2
    assert tm.stats()["c"]["tokens_out"] == 4


def test_fair_share_sheds_hog_not_compliant():
    """Under overload (outstanding >= 2x slot capacity — slots plus one
    capacity's worth of queued buffering) the tenant holding more than its
    weight-proportional share of that budget is shed; a compliant tenant
    with headroom still admits."""
    tm = TenantManager()
    tm.configure(TenantConfig("hog", weight=1.0))
    tm.configure(TenantConfig("nice", weight=1.0))
    for _ in range(4):
        tm.admit("hog", 4, outstanding=7, capacity=4)  # below 2x: inert
    tm.admit("nice", 4, outstanding=7, capacity=4)
    # overloaded now: hog holds 4 = its half of the 8-deep budget -> shed
    with pytest.raises(resilience.QuotaExceededError) as ei:
        tm.admit("hog", 4, outstanding=8, capacity=4)
    assert ei.value.retry_after > 0
    # nice holds 1 < its share of 4 -> admitted even under overload
    tm.admit("nice", 4, outstanding=8, capacity=4)
    assert tm.stats()["hog"]["shed"] == 1
    assert tm.stats()["nice"]["shed"] == 0


def test_unknown_tenant_materializes_from_flags():
    keep = paddle.get_flags(["gateway_tenant_rate",
                             "gateway_tenant_burst"])
    paddle.set_flags({"gateway_tenant_rate": 8.0,
                      "gateway_tenant_burst": 8.0})
    try:
        tm = TenantManager()
        tm.admit("anon", 8)
        with pytest.raises(resilience.QuotaExceededError):
            tm.admit("anon", 8)
    finally:
        paddle.set_flags(keep)


# ------------------------------------------------------- health / reroute


def test_crash_loop_ejects_and_reroutes_token_for_token(model):
    """A replica whose supervisor escalates to crash-loop is ejected; its
    in-flight stream re-queues onto the healthy replica from its token
    journal and finishes token-for-token identical (PR 5 replay parity,
    one level up)."""
    keep = paddle.get_flags(["serving_max_rebuilds"])
    paddle.set_flags({"serving_max_rebuilds": 1})
    pool = ReplicaPool(model, replicas=2, respawn_backoff=600, **POOL_KW)
    try:
        rng = np.random.default_rng(5)
        p = _prompt(rng, 8)
        ref = _ref(model, p, 8)
        rr = pool.submit(p, max_new_tokens=8, tenant="x")
        victim = pool._replica_at(rr._replica_idx)
        for _ in range(3):  # a few tokens decode before the chip dies
            pool.pump_once()
        assert not rr.finished
        e0 = serving_metrics.stats().get("gateway.ejected", 0)
        _kill_decode(victim)
        out = pool.result(rr, timeout=60)
        np.testing.assert_array_equal(out, ref)
        assert rr.reroutes == 1
        assert len(pool.healthy_replicas()) == 1
        assert not victim.healthy
        assert serving_metrics.stats()["gateway.ejected"] == e0 + 1
        # the ejected replica is out of rotation: new traffic still serves
        rr2 = pool.submit(_prompt(rng, 5), max_new_tokens=3, tenant="x")
        assert rr2._replica_idx != victim.idx
        pool.run_until_idle()
        assert rr2.state == RequestState.FINISHED
        # scale-down with a dead replica in the pool must retire the DEAD
        # one, never the last healthy survivor (regression: the
        # highest-index rule alone removed the survivor and stranded the
        # pool with zero routable replicas)
        pool.scale_to(1)
        assert victim.removed
        assert len(pool.healthy_replicas()) == 1
    finally:
        pool.close()
        paddle.set_flags(keep)


def test_reroute_replays_on_the_survivors_warm_programs(model):
    """The survivor absorbs a dead replica's streams on the programs it
    has: once its decode step and every prefill bucket a journal replay
    can land in are traced, a re-route traces nothing (prompt + journal
    is prefilled in a bucket of the ladder, not at its own length)."""
    keep = paddle.get_flags(["serving_max_rebuilds"])
    paddle.set_flags({"serving_max_rebuilds": 1})
    pool = ReplicaPool(model, replicas=2, respawn_backoff=600, **POOL_KW)
    try:
        rng = np.random.default_rng(9)
        for rep in pool.replicas():
            for plen in (10, 20, 28, 40, 60):
                rep.api.submit(_prompt(rng, plen), max_new_tokens=2)
            rep.api.run_until_idle()
        prompts = [_prompt(rng, n) for n in (8, 12, 10, 12)]
        rrs = [pool.submit(p, max_new_tokens=40) for p in prompts]
        for _ in range(12):  # journals of a dozen tokens before the kill
            pool.pump_once()
        assert not any(rr.finished for rr in rrs)
        victim = pool._replica_at(rrs[0]._replica_idx)
        survivor, = [r for r in pool.replicas() if r is not victim]
        eng = survivor.api.engine
        traced = (eng.decode_traces, dict(eng.prefill_traces))
        _kill_decode(victim)
        outs = [pool.result(rr, timeout=120) for rr in rrs]
        for p, out in zip(prompts, outs):
            np.testing.assert_array_equal(out, _ref(model, p, 40))
        assert sum(rr.reroutes for rr in rrs) >= 1
        assert survivor.api.engine is eng  # never rebuilt
        assert (eng.decode_traces, dict(eng.prefill_traces)) == traced
    finally:
        pool.close()
        paddle.set_flags(keep)


def test_ejected_replica_respawns_after_backoff(model):
    keep = paddle.get_flags(["serving_max_rebuilds"])
    paddle.set_flags({"serving_max_rebuilds": 1})
    pool = ReplicaPool(model, replicas=2, respawn_backoff=0.01, **POOL_KW)
    try:
        rng = np.random.default_rng(6)
        rr = pool.submit(_prompt(rng, 6), max_new_tokens=6, tenant="x")
        victim = pool._replica_at(rr._replica_idx)
        pool.pump_once()
        _kill_decode(victim)
        gen0 = victim.generation
        r0 = serving_metrics.stats().get("gateway.respawned", 0)
        pool.result(rr, timeout=60)
        assert victim.ejections == 1
        time.sleep(0.05)  # past the backoff
        pool.pump_once()  # respawn happens at the next pump/submit
        assert len(pool.healthy_replicas()) == 2
        assert victim.generation == gen0 + 1
        assert serving_metrics.stats()["gateway.respawned"] == r0 + 1
        # the respawned replica serves again
        rr2 = pool.submit(_prompt(rng, 5), max_new_tokens=3, tenant="x")
        pool.run_until_idle()
        assert rr2.state == RequestState.FINISHED
    finally:
        pool.close()
        paddle.set_flags(keep)


def test_cancel_sticks_across_reroute(model):
    """A cancel acknowledged before a crash must not be resurrected by the
    journaled re-route: the gateway handle carries the flag, so the stream
    ends CANCELLED instead of decoding to completion on a fresh replica."""
    keep = paddle.get_flags(["serving_max_rebuilds"])
    paddle.set_flags({"serving_max_rebuilds": 1})
    pool = ReplicaPool(model, replicas=2, respawn_backoff=600, **POOL_KW)
    try:
        rng = np.random.default_rng(11)
        rr = pool.submit(_prompt(rng, 7), max_new_tokens=12, tenant="c")
        pool.pump_once()
        victim = pool._replica_at(rr._replica_idx)
        rr.cancel()
        _kill_decode(victim)  # the cancel races the crash-loop ejection
        with pytest.raises(RuntimeError, match="cancelled"):
            pool.result(rr, timeout=60)
        assert rr.state == RequestState.CANCELLED
        assert rr.reroutes == 0  # never re-decoded on the survivor
    finally:
        pool.close()
        paddle.set_flags(keep)


# ----------------------------------------------------- drain / scale-down


def test_guard_drain_drains_every_replica(model):
    """A requested preemption (SIGTERM stand-in) drains the WHOLE pool:
    in-flight streams on both replicas finish inside the grace budget and
    new submissions shed with the retriable RequestDrainedError."""
    pool = ReplicaPool(model, replicas=2, **POOL_KW)
    guard = resilience.PreemptionGuard(install=False)
    pool.bind_preemption_guard(guard, grace=30.0)
    rng = np.random.default_rng(7)
    rrs = [pool.submit(_prompt(rng, n), max_new_tokens=4, tenant="g")
           for n in (5, 6)]
    assert {rr._replica_idx for rr in rrs} == {0, 1}
    guard.request("test preemption")
    pool.pump_once()  # the guard poll turns into a gateway-wide drain
    assert all(rr.state == RequestState.FINISHED for rr in rrs)
    for rep in pool.replicas():
        assert rep.api._draining
    with pytest.raises(resilience.RequestDrainedError):
        pool.submit(_prompt(rng, 5), max_new_tokens=2, tenant="g")
    pool.close()


def test_scale_down_routes_through_drain_and_reroutes(model):
    """scale_to(1) drains the retiring replica; with a zero grace budget
    its in-flight stream re-routes onto the survivor and finishes
    token-for-token — autoscaling never drops an accepted stream."""
    pool = ReplicaPool(model, replicas=2, **POOL_KW)
    try:
        rng = np.random.default_rng(8)
        prompts = [_prompt(rng, n) for n in (6, 7)]
        refs = [_ref(model, p, 6) for p in prompts]
        rrs = [pool.submit(p, max_new_tokens=6, tenant="s")
               for p in prompts]
        assert {rr._replica_idx for rr in rrs} == {0, 1}
        for _ in range(2):
            pool.pump_once()  # some tokens land on both replicas
        pool.scale_to(1, grace=0.0)
        st = pool.stats()
        assert st["replicas_total"] == 1
        moved = [rr for rr in rrs if rr.reroutes > 0]
        assert moved, "the retiring replica's stream must have re-routed"
        pool.run_until_idle()
        for rr, ref in zip(rrs, refs):
            assert rr.state == RequestState.FINISHED
            np.testing.assert_array_equal(rr.output_ids(), ref)
        with pytest.raises(ValueError):
            pool.scale_to(0)
    finally:
        pool.close()


def test_atexit_drain_hook_is_idempotent_with_close(model):
    """ISSUE 8 satellite: the atexit hook next to ``_live_apis`` drains
    every live API with zero grace, and an explicit close() before/after
    is a no-op — interpreter shutdown can never strand a pump thread."""
    from paddle_tpu.serving import api as api_mod

    api = ServingAPI(model, **POOL_KW)
    req = api.submit(np.arange(1, 6, dtype=np.int32), max_new_tokens=4)
    api_mod._drain_at_exit()  # what interpreter shutdown runs
    assert api._draining
    assert req.finished  # zero grace: failed retriably, done_event set
    assert isinstance(req.error, resilience.RequestDrainedError)
    api.close()   # idempotent after the hook
    api_mod._drain_at_exit()  # and the hook after close() is a no-op
    assert api._closed


# ------------------------------------------------------------------- HTTP


def test_http_sse_round_trip(model):
    """Loopback front door: submit + SSE stream returns generate()-parity
    tokens; health/stats/cancel endpoints respond; quota shed maps to 429
    with Retry-After; unknown ids 404."""
    tm = TenantManager()
    tm.configure(TenantConfig("metered", rate=6.0, burst=6.0))
    pool = ReplicaPool(model, replicas=2, tenants=tm, background=True,
                       **POOL_KW)
    gw = Gateway(pool, port=0).start()
    base = f"http://127.0.0.1:{gw.port}"
    try:
        health = json.load(urllib.request.urlopen(base + "/healthz",
                                                  timeout=30))
        assert health == {"status": "ok", "replicas_healthy": 2,
                          "replicas_total": 2}
        rng = np.random.default_rng(9)
        p = _prompt(rng, 6)
        ref = _ref(model, p, 6)
        body = json.dumps({"prompt": p.tolist(), "max_new_tokens": 6,
                           "tenant": "free"}).encode()
        toks, done = [], None
        req = urllib.request.Request(base + "/v1/stream", data=body,
                                     method="POST")
        with urllib.request.urlopen(req, timeout=120) as resp:
            assert resp.headers["Content-Type"] == "text/event-stream"
            event = None
            for line in resp:
                line = line.decode().strip()
                if line.startswith("event:"):
                    event = line.split(":", 1)[1].strip()
                elif line.startswith("data:"):
                    d = json.loads(line.split(":", 1)[1])
                    if event == "done":
                        done = d
                    else:
                        toks.append(d["token"])
                    event = None
        np.testing.assert_array_equal(np.concatenate([p, toks]), ref)
        assert done["state"] == "FINISHED" and done["tokens"] == 6

        # submit-then-stream by id (the async path)
        sub = json.load(urllib.request.urlopen(urllib.request.Request(
            base + "/v1/submit", data=body, method="POST"), timeout=60))
        res = json.load(urllib.request.urlopen(
            base + f"/v1/result/{sub['request_id']}?timeout=60",
            timeout=120))
        np.testing.assert_array_equal(res["output_ids"], ref)

        # tenant rate shed -> 429 + Retry-After (retriable taxonomy)
        mbody = json.dumps({"prompt": p.tolist(), "max_new_tokens": 6,
                            "tenant": "metered"}).encode()
        urllib.request.urlopen(urllib.request.Request(
            base + "/v1/submit", data=mbody, method="POST"), timeout=60)
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/v1/submit", data=mbody, method="POST"), timeout=60)
        assert ei.value.code == 429
        assert float(ei.value.headers["Retry-After"]) > 0
        assert json.load(ei.value)["retriable"] is True

        # 404 taxonomy + cancel endpoint + stats
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/v1/stream/nope", timeout=30)
        assert ei.value.code == 404
        c = json.load(urllib.request.urlopen(urllib.request.Request(
            base + f"/v1/cancel/{sub['request_id']}", method="POST"),
            timeout=30))
        assert c["cancelled"] is True
        stats = json.load(urllib.request.urlopen(base + "/v1/stats",
                                                 timeout=30))
        assert stats["pool"]["replicas_healthy"] == 2
        assert "metered" in stats["pool"]["tenants"]
        assert stats["serving"].get("gateway.routed", 0) >= 3
    finally:
        gw.close()
    # closed gateway reports unhealthy through the pool it drained
    assert pool._draining or pool._closed


def test_http_drain_maps_to_503(model):
    pool = ReplicaPool(model, replicas=1, background=True, **POOL_KW)
    gw = Gateway(pool, port=0).start()
    base = f"http://127.0.0.1:{gw.port}"
    try:
        pool.drain(grace=0.0)
        body = json.dumps({"prompt": [1, 2, 3],
                           "max_new_tokens": 2}).encode()
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(urllib.request.Request(
                base + "/v1/submit", data=body, method="POST"), timeout=30)
        assert ei.value.code == 503
        assert float(ei.value.headers["Retry-After"]) > 0
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/healthz", timeout=30)
        assert ei.value.code == 503
    finally:
        gw.close()


# ------------------------------------------------- observability (ISSUE 17)

_COMPILE_KEYS = ("serving.decode_compiles", "serving.prefill_compiles",
                 "serving.cow_compiles", "serving.restore_compiles")

_PROM_LINE = re.compile(
    r"^[a-zA-Z_][a-zA-Z0-9_]*(\{[^{}]*\})? -?\d+(\.\d+)?([eE][+-]?\d+)?$")


def test_http_metrics_scrape_concurrent_with_sse_under_churn(model):
    """``GET /v1/metrics`` scraped in a loop while SSE streams decode:
    every scrape is valid Prometheus text exposition, the scrapes cause
    ZERO serving compiles (the export plane reads host-side counters —
    it must never touch a traced region), and ``/v1/trace/<request_id>``
    serves the finished request's span timeline over HTTP."""
    keep = paddle.get_flags(["serving_telemetry"])
    paddle.set_flags({"serving_telemetry": True})
    telemetry.reset_tracelog()
    pool = ReplicaPool(model, replicas=2, background=True, **POOL_KW)
    gw = Gateway(pool, port=0).start()
    base = f"http://127.0.0.1:{gw.port}"
    try:
        rng = np.random.default_rng(21)
        # warm both replicas at the churn shape so the scraped window is
        # compile-free (same prompt length -> same prefill bucket)
        warm = [pool.submit(_prompt(rng, 6), max_new_tokens=4, tenant="m")
                for _ in range(4)]
        for rr in warm:
            pool.result(rr, timeout=60)
        cc0 = compile_cache.stats()

        scrapes, errors = [], []
        stop = threading.Event()

        def scraper():
            while not stop.is_set():
                try:
                    with urllib.request.urlopen(base + "/v1/metrics",
                                                timeout=30) as resp:
                        ctype = resp.headers["Content-Type"]
                        assert ctype.startswith(
                            "text/plain; version=0.0.4"), ctype
                        scrapes.append(resp.read().decode())
                except Exception as e:  # noqa: BLE001 — surfaced below
                    errors.append(e)
                    return
                time.sleep(0.002)

        th = threading.Thread(target=scraper, daemon=True)
        th.start()
        body = json.dumps({"prompt": _prompt(rng, 6).tolist(),
                           "max_new_tokens": 5, "tenant": "m"}).encode()
        for _ in range(4):  # churn: live SSE streams under the scraper
            req = urllib.request.Request(base + "/v1/stream", data=body,
                                         method="POST")
            with urllib.request.urlopen(req, timeout=120) as resp:
                for _line in resp:
                    pass
        # one request by id so /v1/trace has a finished timeline to serve
        sub = json.load(urllib.request.urlopen(urllib.request.Request(
            base + "/v1/submit", data=body, method="POST"), timeout=60))
        json.load(urllib.request.urlopen(
            base + f"/v1/result/{sub['request_id']}?timeout=60",
            timeout=120))
        stop.set()
        th.join(timeout=30)
        assert not errors, errors[0]
        assert scrapes  # the scraper did overlap the streams

        cc1 = compile_cache.stats()
        assert sum(cc1.get(k, 0) - cc0.get(k, 0)
                   for k in _COMPILE_KEYS) == 0

        last = scrapes[-1]
        for line in last.splitlines():
            if not line or line.startswith("#"):
                continue
            assert _PROM_LINE.match(line) or "+Inf" in line, line
        assert "paddle_serving_tokens_generated" in last
        assert "paddle_latency_ttft_seconds_bucket" in last
        assert "paddle_gateway_replica_outstanding" in last

        tr = json.load(urllib.request.urlopen(
            base + f"/v1/trace/{sub['request_id']}", timeout=30))
        assert tr["enabled"] is True and tr["trace_id"].startswith("t")
        kinds = [e["event"] for e in tr["events"]]
        assert kinds[0] == telemetry.SUBMITTED
        assert telemetry.FIRST_TOKEN in kinds
        assert kinds[-1] == telemetry.FINISHED
        # unknown ids stay a clean 404, not a crash in the export plane
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(base + "/v1/trace/nope", timeout=30)
        assert ei.value.code == 404
    finally:
        gw.close()
        paddle.set_flags(keep)
        telemetry.reset_tracelog()


def test_stats_snapshot_consistent_under_concurrent_eject(model):
    """Regression: the router's ``stats()`` snapshot is taken under ONE
    lock — scrapers hammering it while replicas are ejected and respawned
    must never observe a torn picture where the healthy/capacity headline
    disagrees with the per-replica rows it was (supposedly) derived from.
    (The old implementation read ``healthy_replicas()`` outside the rows
    pass; an eject between the two reads skewed ``capacity_slots``.)"""
    keep = paddle.get_flags(["serving_max_rebuilds"])
    paddle.set_flags({"serving_max_rebuilds": 1})
    pool = ReplicaPool(model, replicas=2, respawn_backoff=0.01, **POOL_KW)
    torn = []
    stop = threading.Event()

    def scraper():
        while not stop.is_set():
            st = pool.stats()
            routable = sum(1 for row in st["replicas"]
                           if row["healthy"] and not row["draining"]
                           and not row["removed"])
            if st["replicas_healthy"] != routable:
                torn.append(("replicas_healthy", st))
                return
            if st["capacity_slots"] != routable * POOL_KW["num_slots"]:
                torn.append(("capacity_slots", st))
                return
            time.sleep(0.0005)

    threads = [threading.Thread(target=scraper, daemon=True)
               for _ in range(3)]
    try:
        for th in threads:
            th.start()
        rng = np.random.default_rng(22)
        for _cycle in range(3):  # eject -> reroute -> respawn, repeatedly
            rr = pool.submit(_prompt(rng, 6), max_new_tokens=6, tenant="s")
            victim = pool._replica_at(rr._replica_idx)
            pool.pump_once()
            _kill_decode(victim)
            pool.result(rr, timeout=60)
            time.sleep(0.05)  # past the respawn backoff
            pool.pump_once()  # respawn happens at the next pump
        assert len(pool.healthy_replicas()) == 2
    finally:
        stop.set()
        for th in threads:
            th.join(timeout=30)
        pool.close()
        paddle.set_flags(keep)
    assert not torn, torn[0]


@pytest.mark.chaos
def test_chaos_trace_timeline_survives_eject_and_reroute(model):
    """ISSUE 17 chaos acceptance: a serving_device eject -> re-route ->
    journal replay keeps ONE trace_id whose span timeline is complete and
    ordered — exactly one SUBMITTED (the gateway mints, everyone
    downstream passes the id along), exactly one FIRST_TOKEN (the
    journal-seeded resubmit must not re-record it), a REROUTED span at
    the fail-over followed by QUEUED/ADMITTED on the survivor, FINISHED
    last, ``seq`` strictly increasing throughout."""
    keep = paddle.get_flags(["serving_max_rebuilds", "serving_telemetry"])
    paddle.set_flags({"serving_max_rebuilds": 1, "serving_telemetry": True})
    telemetry.reset_tracelog()
    pool = ReplicaPool(model, replicas=2, respawn_backoff=600, **POOL_KW)
    try:
        rng = np.random.default_rng(23)
        p = _prompt(rng, 8)
        ref = _ref(model, p, 8)
        rr = pool.submit(p, max_new_tokens=8, tenant="chaos")
        victim = pool._replica_at(rr._replica_idx)
        for _ in range(3):  # a few tokens land before the chip dies
            pool.pump_once()
        assert not rr.finished
        _kill_decode(victim)
        out = pool.result(rr, timeout=60)
        np.testing.assert_array_equal(out, ref)
        assert rr.reroutes == 1

        events = telemetry.trace(rr.trace_id)
        kinds = [e["event"] for e in events]
        assert kinds.count(telemetry.SUBMITTED) == 1
        assert kinds.count(telemetry.FIRST_TOKEN) == 1
        assert kinds.count(telemetry.REROUTED) == 1
        assert kinds.count(telemetry.FINISHED) == 1
        assert kinds[-1] == telemetry.FINISHED
        # the survivor re-admits from the journal AFTER the re-route
        after = kinds[kinds.index(telemetry.REROUTED):]
        assert telemetry.QUEUED in after and telemetry.ADMITTED in after
        # one contiguous, strictly ordered timeline — no interleaved or
        # duplicated sequence numbers across the replica hop
        seqs = [e["seq"] for e in events]
        assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
        assert all(e["trace_id"] == rr.trace_id for e in events)
        # wall clocks are monotone too (same host; ties allowed)
        ts = [e["ts"] for e in events]
        assert all(b >= a for a, b in zip(ts, ts[1:]))
    finally:
        pool.close()
        paddle.set_flags(keep)
        telemetry.reset_tracelog()


# ----------------------------------------------------------- load (slow)


@pytest.mark.slow
def test_tenant_mix_under_overload_completes_accepted(model):
    """Miniature of the gateway bench's acceptance: three tenants, one
    offering well past its rate quota — every ACCEPTED stream completes,
    the noisy tenant's excess is shed at its bucket, and the unmetered
    compliant tenants are never shed. (The weighted fair-share gate — which
    by design also binds compliant tenants once the pool is genuinely
    overloaded — is unit-tested separately; it is off here so the test is
    deterministic about WHO sheds.)"""
    keep = paddle.get_flags(["gateway_fair_share"])
    paddle.set_flags({"gateway_fair_share": False})
    tm = TenantManager()
    tm.configure(TenantConfig("noisy", rate=12.0, burst=12.0, weight=1.0))
    tm.configure(TenantConfig("calm1", weight=1.0))
    tm.configure(TenantConfig("calm2", weight=1.0))
    pool = ReplicaPool(model, replicas=2, tenants=tm, **POOL_KW)
    try:
        rng = np.random.default_rng(10)
        accepted, shed = [], 0
        for i in range(24):
            tenant = ("noisy", "calm1", "calm2")[i % 3]
            try:
                accepted.append(pool.submit(_prompt(rng, 5 + i % 4),
                                            max_new_tokens=6,
                                            tenant=tenant))
            except resilience.QuotaExceededError as e:
                assert e.tenant == "noisy"  # only the hog is shed
                shed += 1
            pool.pump_once()
        assert shed > 0
        pool.run_until_idle()
        assert all(rr.state == RequestState.FINISHED for rr in accepted)
        st = tm.stats()
        assert st["noisy"]["shed"] == shed
        assert st["calm1"]["shed"] == 0 and st["calm2"]["shed"] == 0
        assert st["calm1"]["tokens_out"] > 0
    finally:
        pool.close()
        paddle.set_flags(keep)
