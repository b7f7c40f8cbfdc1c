"""Stream consumers wait for their tokens instead of polling for them
(ISSUE 30): a consumer of a background pool (or of a ``ServingAPI`` with a
pump thread) blocks until its producer has something for it, one
housekeeping thread does what every consumer's wait loop used to do, and a
foreground pool's consumer is still the pump.

What is counted: ``gateway.stream_wakeups`` (a consumer came back from its
wait), ``gateway.stream_wait_timeouts`` (it came back by the backstop with
nothing new) and the gauge ``gateway.stream_consumers``.
"""
import json
import socket
import threading
import time
import types

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.core import resilience
from paddle_tpu.core.tensor import Tensor
from paddle_tpu.models.gpt import GPTForCausalLM, gpt_tiny
from paddle_tpu.serving import ReplicaPool, RequestState, ServingAPI
from paddle_tpu.serving import api as serving_api
from paddle_tpu.serving import metrics as serving_metrics
from paddle_tpu.serving.gateway import Gateway
from paddle_tpu.serving.gateway import procpool, router
from paddle_tpu.serving.scheduler import Scheduler, StreamSignal
from paddle_tpu.serving.supervisor import CrashLoopError

pytestmark = [pytest.mark.serving, pytest.mark.gateway]

MAX_LEN = 64
POOL_KW = dict(num_slots=4, kv_block_size=8, max_model_len=MAX_LEN)
WAKEUPS = "gateway.stream_wakeups"
TIMEOUTS = "gateway.stream_wait_timeouts"
CONSUMERS = "gateway.stream_consumers"


@pytest.fixture(scope="module")
def model():
    paddle.seed(0)
    m = GPTForCausalLM(gpt_tiny())
    m.eval()
    return m


def _prompt(rng, n):
    return rng.integers(0, 1024, (n,), dtype=np.int32)


def _ref(model, prompt, max_new):
    out = model.generate(Tensor(np.asarray(prompt)[None]),
                         max_new_tokens=max_new)
    return np.asarray(out._data)[0]


def _count(key):
    return serving_metrics.stats().get(key, 0)


def _wait_until(cond, timeout=30.0, step=0.01):
    deadline = time.monotonic() + timeout
    while not cond():
        if time.monotonic() > deadline:
            return False
        time.sleep(step)
    return True


def _consume(pool, rr, out, times=None):
    """Run ``pool.stream(rr)`` to its end on a thread of its own."""
    def body():
        try:
            for tok in pool.stream(rr):
                out.append(tok)
                if times is not None:
                    times.append(time.perf_counter())
        except Exception as e:  # the stream's own error, raised at its end
            out.append(e)

    t = threading.Thread(target=body, daemon=True)
    t.start()
    return t


def _slow_decode(api, seconds):
    """Make every decode step of one engine take ``seconds`` longer, so a
    test can act while a stream is still mid-decode."""
    real = api.engine.decode_step

    def slow(*a, **kw):
        time.sleep(seconds)
        return real(*a, **kw)

    api.engine.decode_step = slow


# ------------------------------------------------------------ the signal


def test_signal_fired_between_read_and_wait_is_not_lost():
    sig = StreamSignal()
    seen = sig.seq
    sig.fire()  # the producer got in after the consumer's read
    t0 = time.monotonic()
    assert sig.wait(seen, 5.0) is True
    assert time.monotonic() - t0 < 1.0
    # nothing new since: the wait runs into its timeout and says so
    t0 = time.monotonic()
    assert sig.wait(sig.seq, 0.05) is False
    assert time.monotonic() - t0 >= 0.04


def test_signal_wakes_every_consumer_blocked_on_it():
    sig = StreamSignal()
    woke = []

    def waiter():
        woke.append(sig.wait(0, 10.0))

    threads = [threading.Thread(target=waiter) for _ in range(3)]
    for t in threads:
        t.start()
    time.sleep(0.1)
    sig.fire()
    for t in threads:
        t.join(timeout=5)
    assert woke == [True, True, True]


def test_attach_hands_the_handles_signal_to_the_backend():
    """Whatever backend a routed handle rides fires the HANDLE's signal: a
    live ``scheduler.Request`` in ``_emit``/``_finish``, a process pool's
    ``RemoteRequest`` when a poll brings tokens or a terminal state."""
    rr = router.RoutedRequest(None, np.arange(4), 8, None, "t", 0,
                              resilience.Deadline.after(None), "sig-1")
    backend = procpool.RemoteRequest(None, "r0", "sig-1.0", rr.trace_id, None)
    replica = types.SimpleNamespace(idx=0, generation=0)
    s0 = rr.signal.seq
    rr._attach(backend, replica, 0)
    assert backend.signal is rr.signal
    assert rr.signal.seq > s0  # the attach itself wakes the consumer
    s1 = rr.signal.seq
    backend._apply({})  # a poll that brought nothing: no wake-up
    assert rr.signal.seq == s1
    backend._apply({"tokens": [5, 6]})
    assert rr.signal.seq == s1 + 1 and rr.tokens() == [5, 6]
    backend._apply({"state": RequestState.FINISHED})
    assert rr.signal.seq == s1 + 2
    other = procpool.RemoteRequest(None, "r1", "sig-1.1", rr.trace_id, None)
    other.signal = rr.signal
    other._fail(RuntimeError("worker died"))
    assert rr.signal.seq == s1 + 3
    s2 = rr.signal.seq
    rr.cancel()
    rr._finalize(RequestState.CANCELLED)
    assert rr.signal.seq == s2 + 2 and rr.done_event.is_set()


# ------------------------------------------------- a background pool waits


def test_idle_streams_wake_by_the_backstop_not_a_thousand_times_a_second(
        model, monkeypatch):
    """N consumers whose requests make no progress for a second come back
    from their wait seconds / backstop times each, where the polling loop
    woke each of them a thousand times."""
    n, backstop, seconds = 4, 0.25, 1.0
    monkeypatch.setattr(router, "STREAM_WAIT_S", backstop)
    pool = ReplicaPool(model, replicas=1, background=True, **POOL_KW)
    rng = np.random.default_rng(30)
    prompts = [_prompt(rng, 6) for _ in range(n)]
    rep, = pool.replicas()
    outs = [[] for _ in range(n)]
    try:
        # the pump thread needs the API lock for every turn: held here, no
        # request is admitted and every stream stands idle
        rep.api._lock.acquire()
        try:
            rrs = [pool.submit(p, max_new_tokens=5) for p in prompts]
            w0, t0 = _count(WAKEUPS), _count(TIMEOUTS)
            threads = [_consume(pool, rr, out)
                       for rr, out in zip(rrs, outs)]
            assert _wait_until(lambda: _count(CONSUMERS) == n)
            time.sleep(seconds)
            woke = _count(WAKEUPS) - w0
            timed_out = _count(TIMEOUTS) - t0
        finally:
            rep.api._lock.release()
        assert n <= woke <= 2 * n * seconds / backstop, woke
        assert timed_out == woke  # nothing fired: every return was a timeout
        for t in threads:
            t.join(timeout=60)
        for p, out in zip(prompts, outs):
            np.testing.assert_array_equal(np.concatenate([p, out]),
                                          _ref(model, p, 5))
        assert _count(CONSUMERS) == 0
    finally:
        pool.close()


def test_a_token_reaches_a_blocked_consumer_well_inside_the_backstop(
        model, monkeypatch):
    """Each token wakes its consumer: it arrives a few milliseconds after
    ``_emit``, about one wake-up a token, and no wait runs into the
    backstop while tokens flow."""
    emitted = []
    real_emit = Scheduler._emit

    def timed_emit(self, req, token):
        emitted.append(time.perf_counter())
        return real_emit(self, req, token)

    monkeypatch.setattr(Scheduler, "_emit", timed_emit)
    pool = ReplicaPool(model, replicas=1, background=True, **POOL_KW)
    try:
        rep, = pool.replicas()
        _slow_decode(rep.api, 0.02)
        rng = np.random.default_rng(31)
        p = _prompt(rng, 6)
        w0, t0 = _count(WAKEUPS), _count(TIMEOUTS)
        rr = pool.submit(p, max_new_tokens=12)
        out, got = [], []
        _consume(pool, rr, out, got).join(timeout=60)
        np.testing.assert_array_equal(np.concatenate([p, out]),
                                      _ref(model, p, 12))
        assert len(emitted) == len(got) == 12
        lag = max(g - e for g, e in zip(got, emitted))
        assert lag < 0.1 * serving_api.STREAM_WAIT_S, lag
        # a wake-up a token, a few more for the attach and the finish
        assert _count(WAKEUPS) - w0 <= 12 + 4
        assert _count(TIMEOUTS) - t0 == 0
    finally:
        pool.close()


def test_a_blocked_consumer_receives_the_rerouted_stream_token_for_token(
        model):
    """The consumer is blocked on its handle's signal while its replica
    crash-loops: the ejection re-routes the stream, the new backend fires
    the same signal, and the consumer reads on with no token lost or
    repeated."""
    keep = paddle.get_flags(["serving_max_rebuilds"])
    paddle.set_flags({"serving_max_rebuilds": 1})
    pool = ReplicaPool(model, replicas=2, background=True,
                       respawn_backoff=600, **POOL_KW)
    try:
        for rep in pool.replicas():
            _slow_decode(rep.api, 0.01)
        rng = np.random.default_rng(32)
        p = _prompt(rng, 8)
        rr = pool.submit(p, max_new_tokens=24)
        victim = pool._replica_at(rr._replica_idx)
        out = []
        t = _consume(pool, rr, out)
        assert _wait_until(lambda: len(out) >= 3)
        assert not rr.finished

        def dying():
            raise resilience.ServingDeviceError("injected: chip pulled")

        victim.api.engine.decode_step = dying
        t.join(timeout=120)
        assert not t.is_alive()
        np.testing.assert_array_equal(np.concatenate([p, out]),
                                      _ref(model, p, 24))
        assert rr.reroutes == 1 and rr.state == RequestState.FINISHED
        assert not victim.healthy
    finally:
        pool.close()
        paddle.set_flags(keep)


def test_a_failed_stream_still_raises_its_error_at_the_end(model):
    """A request that runs out of its deadline mid-decode: the consumer
    gets the tokens made so far, then the request's own error."""
    pool = ReplicaPool(model, replicas=1, background=True, **POOL_KW)
    try:
        rep, = pool.replicas()
        _slow_decode(rep.api, 0.05)
        rng = np.random.default_rng(33)
        rr = pool.submit(_prompt(rng, 6), max_new_tokens=40, timeout=0.4)
        out = []
        _consume(pool, rr, out).join(timeout=60)
        assert rr.state == RequestState.FAILED
        assert isinstance(out[-1], resilience.DeadlineExceededError)
        assert out[:-1] == rr.tokens() and len(out) - 1 < 40
    finally:
        pool.close()


def test_a_client_hangup_still_cancels_and_frees_the_lane(model):
    """An SSE client that leaves mid-stream: the handler's next write
    fails, the request is cancelled on its replica, its lane and its
    tenant slot come back."""
    pool = ReplicaPool(model, replicas=1, background=True, **POOL_KW)
    gw = Gateway(pool, port=0).start()
    try:
        rep, = pool.replicas()
        _slow_decode(rep.api, 0.03)
        rng = np.random.default_rng(34)
        body = json.dumps({"prompt": _prompt(rng, 6).tolist(),
                           "max_new_tokens": 50,
                           "request_id": "hangup-1"}).encode()
        d0 = _count("gateway.client_disconnects")
        sock = socket.create_connection(("127.0.0.1", gw.port), timeout=30)
        sock.sendall(b"POST /v1/stream HTTP/1.1\r\nHost: x\r\n"
                     b"Content-Type: application/json\r\n"
                     b"Content-Length: %d\r\n\r\n" % len(body) + body)
        seen = b""
        while seen.count(b"data:") < 2:  # two tokens in: mid-stream
            chunk = sock.recv(4096)
            assert chunk, seen
            seen += chunk
        sock.close()
        assert _wait_until(
            lambda: _count("gateway.client_disconnects") == d0 + 1)
        assert _wait_until(lambda: rep.api.outstanding() == 0)
        assert _wait_until(
            lambda: pool.tenants.stats()["default"]["inflight"] == 0)
        assert _wait_until(lambda: _count(CONSUMERS) == 0)
        rr = gw._requests["hangup-1"]
        assert rr.state == RequestState.CANCELLED
        assert 2 <= len(rr.tokens()) < 50  # decoding stopped early
    finally:
        gw.close()


# ------------------------------------ idle turns: a stream with nothing new


def _hold_every_stream_idle(pool):
    """No request is admitted while the API lock is held (the pump thread
    needs it for every turn): every stream stands where a QUEUED one
    stands. Returns the release."""
    rep, = pool.replicas()
    rep.api._lock.acquire()
    return rep.api._lock.release


def test_an_idle_stream_yields_none_by_the_backstop_only_when_asked(
        model, monkeypatch):
    """``stream(rr, idle_turns=True)`` hands its consumer a ``None`` for
    every wait that ended by the backstop; without the argument the same
    stream yields tokens alone, as every caller before it expects."""
    monkeypatch.setattr(router, "STREAM_WAIT_S", 0.1)
    pool = ReplicaPool(model, replicas=1, background=True, **POOL_KW)
    rng = np.random.default_rng(36)
    p = _prompt(rng, 6)
    try:
        release = _hold_every_stream_idle(pool)
        try:
            plain, asked = pool.submit(p, max_new_tokens=5), pool.submit(
                p, max_new_tokens=5)
            out = []
            t = _consume(pool, plain, out)
            turns = pool.stream(asked, idle_turns=True)
            assert [next(turns) for _ in range(3)] == [None] * 3
            assert out == []
        finally:
            release()
        rest = [tok for tok in turns if tok is not None]
        t.join(timeout=60)
        assert None not in out
        for got in (out, rest):
            np.testing.assert_array_equal(np.concatenate([p, got]),
                                          _ref(model, p, 5))
    finally:
        pool.close()


def _open_stream(gw, body):
    sock = socket.create_connection(("127.0.0.1", gw.port), timeout=30)
    sock.sendall(b"POST /v1/stream HTTP/1.1\r\nHost: x\r\n"
                 b"Content-Type: application/json\r\n"
                 b"Content-Length: %d\r\n\r\n" % len(body) + body)
    return sock


def _fill_the_lanes(pool, rng, step_s):
    """Every lane taken for 50 steps of ``step_s``: what a handler submits
    meanwhile waits in the queue. Returns the handles, to cancel."""
    rep, = pool.replicas()
    _slow_decode(rep.api, step_s)
    held = [pool.submit(_prompt(rng, 6), max_new_tokens=50)
            for _ in range(POOL_KW["num_slots"])]
    assert _wait_until(lambda: all(len(rr.tokens()) for rr in held))
    return held


def test_a_client_that_leaves_the_queue_is_found_before_its_first_token(
        model, monkeypatch):
    """A stream whose request waits is sent an SSE comment every backstop
    period; written to a client that has left, it fails as a token's write
    does: the request is cancelled with no token made, where it used to
    hold its place until its prefill had been spent on it."""
    monkeypatch.setattr(router, "STREAM_WAIT_S", 0.1)
    pool = ReplicaPool(model, replicas=1, background=True, **POOL_KW)
    gw = Gateway(pool, port=0).start()
    try:
        rng = np.random.default_rng(37)
        held = _fill_the_lanes(pool, rng, 0.1)
        d0 = _count("gateway.client_disconnects")
        sock = _open_stream(gw, json.dumps({
            "prompt": _prompt(rng, 6).tolist(), "max_new_tokens": 50,
            "request_id": "left-the-queue"}).encode())
        seen = b""
        while seen.count(b": waiting\n\n") < 2:
            chunk = sock.recv(4096)
            assert chunk, seen
            seen += chunk
        assert b"data:" not in seen
        sock.close()
        assert _wait_until(
            lambda: _count("gateway.client_disconnects") == d0 + 1)
        rr = gw._requests["left-the-queue"]
        assert _wait_until(lambda: rr.state == RequestState.CANCELLED)
        assert rr.tokens() == []
        assert not any(h.finished for h in held)  # found while it queued
        for h in held:
            h.cancel()
        assert _wait_until(lambda: _count(CONSUMERS) == 0)
        assert _wait_until(
            lambda: pool.tenants.stats()["default"]["inflight"] == 0)
    finally:
        gw.close()


def test_the_load_generator_cuts_streams_that_wait_in_the_queue(
        model, monkeypatch):
    """The benchmark's closed loop cuts its streams at the window's end.
    ``http.client`` hands a ``Connection: close`` response its socket, so
    ``cut()`` has nothing to shut and a client ends at its stream's next
    LINE: a queued stream's only lines before its first token are these
    comments. Without them each client hung until its request had been
    prefilled behind every one before it (``serve-longctx-sparse-moe``:
    32 queued prompts of 4-28k tokens, some 24 s, past the generator's
    ten-second join: ``left 1 threads``, exit 1, one run in twelve)."""
    from benchmark.harness import loadgen

    monkeypatch.setattr(router, "STREAM_WAIT_S", 0.2)
    pool = ReplicaPool(model, replicas=1, background=True, **POOL_KW)
    gw = Gateway(pool, port=0).start()
    clients, seconds = 6, 1.0
    sched = {"loop": "closed", "clients": clients, "seconds": seconds,
             "ramp_s": 0.5, "drain_s": 0, "seed": 38, "vocab": 1024,
             "shared_prefix": 0,
             "requests": [{"id": i, "due_s": None, "prompt_len": 6,
                           "max_new_tokens": 5}
                          for i in range(4 * clients)]}
    try:
        held = _fill_the_lanes(pool, np.random.default_rng(38), 0.15)
        t0 = time.monotonic() + 1.0
        res = loadgen.run(sched, f"http://127.0.0.1:{gw.port}", t0)
        took = time.monotonic() - (t0 + seconds)
        assert not any(h.finished for h in held)  # the queue never moved
        assert res["threads_left"] == 0
        assert took < 2.0, took  # the lanes are held for five seconds more
        assert len(res["requests"]) == clients
        assert all(r["error"] == "cut" and r["tokens"] == []
                   for r in res["requests"])
        for h in held:
            h.cancel()
    finally:
        gw.close()


# ------------------------------------------------------ ServingAPI.stream


class _CountingQueue:
    """A request's stream queue that counts how often it was asked."""

    def __init__(self, inner):
        self.inner = inner
        self.gets = 0

    def put(self, item):
        self.inner.put(item)

    def get(self, block=True, timeout=None):
        self.gets += 1
        return self.inner.get(block, timeout)


@pytest.mark.parametrize("background", [False, True],
                         ids=["consumer-pumps", "pump-thread"])
def test_api_stream_with_and_without_a_pump_thread(model, background):
    """Without a pump thread the consumer steps the scheduler itself; with
    one it blocks on the stream queue: one ``get`` a token and one for the
    finish sentinel, however long the tokens take."""
    api = ServingAPI(model, background=background, **POOL_KW)
    try:
        rng = np.random.default_rng(35)
        p = _prompt(rng, 6)
        if background:
            api._lock.acquire()  # hold the pump back: the stream stands idle
        try:
            req = api.submit(p, max_new_tokens=6)
            req.stream_queue = q = _CountingQueue(req.stream_queue)
            out = []
            t = threading.Thread(
                target=lambda: out.extend(api.stream(req)), daemon=True)
            t.start()
            if background:
                time.sleep(0.3)
                assert q.gets == 1  # blocked in its first get, not polling
        finally:
            if background:
                api._lock.release()
        t.join(timeout=60)
        np.testing.assert_array_equal(np.concatenate([p, out]),
                                      _ref(model, p, 6))
        if background:
            assert q.gets == 6 + 1
        else:
            assert q.gets > 6 + 1  # every empty read was a scheduler step
    finally:
        api.close()


def test_api_stream_with_a_pump_thread_raises_the_error_at_the_end(model):
    api = ServingAPI(model, background=True, **POOL_KW)
    try:
        _slow_decode(api, 0.05)
        rng = np.random.default_rng(36)
        req = api.submit(_prompt(rng, 6), max_new_tokens=40, timeout=0.4)
        got = []
        with pytest.raises(resilience.DeadlineExceededError):
            for tok in api.stream(req):
                got.append(tok)
        assert got == req.tokens and len(got) < 40
    finally:
        api.close()


# ----------------------------------------------------- a foreground pool


def test_a_foreground_pool_stream_still_pumps_itself(model):
    """No thread pumps a foreground pool: its consumer does, turn by turn,
    and waits for nothing (no wake-up is counted, no housekeeping thread
    runs)."""
    pool = ReplicaPool(model, replicas=2, **POOL_KW)
    try:
        assert pool._housekeeper is None
        assert all(rep.api._thread is None for rep in pool.replicas())
        rng = np.random.default_rng(37)
        p = _prompt(rng, 7)
        w0 = _count(WAKEUPS)
        rr = pool.submit(p, max_new_tokens=6)
        toks = list(pool.stream(rr))
        np.testing.assert_array_equal(np.concatenate([p, toks]),
                                      _ref(model, p, 6))
        assert rr.state == RequestState.FINISHED
        assert _count(WAKEUPS) == w0
    finally:
        pool.close()


# ------------------------------------------------ the housekeeping thread


@pytest.mark.parametrize("end", ["drain", "close"])
def test_housekeeping_respawns_with_no_consumer_and_ends_with_the_pool(
        model, end):
    """The one housekeeping thread of a background pool brings an ejected
    replica back with no stream open, finalizes a stream nobody consumes,
    and ends when the pool drains or closes."""
    pool = ReplicaPool(model, replicas=2, background=True,
                       respawn_backoff=0.05, **POOL_KW)
    try:
        hk = pool._housekeeper
        assert hk is not None and hk.is_alive()
        assert hk.name == "gateway-housekeeping"
        victim = pool.replicas()[1]
        gen0 = victim.generation
        r0 = _count("gateway.respawned")
        pool._eject(victim, CrashLoopError("injected: breaker open"))
        assert not victim.healthy
        assert _wait_until(lambda: victim.healthy, timeout=60)
        assert victim.generation == gen0 + 1
        assert _count("gateway.respawned") == r0 + 1
        # a submit nobody streams or waits for is still reconciled
        rng = np.random.default_rng(38)
        rr = pool.submit(_prompt(rng, 5), max_new_tokens=3)
        assert rr.done_event.wait(60)
        assert rr.state == RequestState.FINISHED
        if end == "drain":
            pool.drain(0.0)
        else:
            pool.close()
        hk.join(timeout=10)
        assert not hk.is_alive()
    finally:
        pool.close()
