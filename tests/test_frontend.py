"""Network front-end, fast tier: protocol parsing and SSE framing, the
byte tokenizer's incremental UTF-8 handling, admission-queue priority /
fairness / displacement / shedding, the serving loop's queue-not-reject
burst behaviour and graceful cancellation, the token pipeline (inline
AND real worker processes), the controller's admission-shed actuator,
and the full HTTP server on a loopback socket over a simulated
cluster."""
import json
import os
import socket
import struct
import threading
import time

import pytest

from repro.core.instance import HEALTH_DEAD
from repro.core.latency import SLO
from repro.engine.engine import SimExecutor
from repro.core.policies import Sliders
from repro.engine.request import Request, State
from repro.frontend import (AdmissionConfig, AdmissionQueue, ByteTokenizer,
                            FrontendConfig, FrontendServer,
                            IncrementalDetokenizer, TokenPipeline, protocol)
from repro.serving import (ControllerConfig, ServingLoop, SliderController,
                           WallClock)
from repro.sim.simulator import ServingConfig, build_cluster

BAL = SLO(ttft=1.5, tpot=0.030)
LOOSE = SLO(ttft=10.0, tpot=1.0)


def _mk_loop(slo=BAL, admission=None, sliders=Sliders(1, 1, 512, 256),
             blocks=4096, executor_factory=None, **kw):
    sc = ServingConfig(sliders=sliders, hbm_blocks=blocks)
    cluster = build_cluster(sc, slo, executor_factory=executor_factory)
    return ServingLoop(cluster, slo, admission=admission, **kw)


# ---------------------------------------------------------------------------
# protocol: request parsing
# ---------------------------------------------------------------------------

def test_parse_completion_and_chat():
    api = protocol.parse_request(
        protocol.COMPLETIONS,
        json.dumps({"model": "m", "prompt": "hi", "max_tokens": 7,
                    "stream": True}).encode())
    assert (api.kind, api.model, api.prompt_text) == ("completion", "m", "hi")
    assert api.max_tokens == 7 and api.stream
    assert api.priority == protocol.DEFAULT_PRIORITY

    api = protocol.parse_request(
        protocol.CHAT_COMPLETIONS,
        json.dumps({"messages": [
            {"role": "system", "content": "be brief"},
            {"role": "user", "content": "hi"}]}).encode())
    assert api.kind == "chat" and not api.stream
    assert api.prompt_text == "system: be brief\nuser: hi\nassistant:"


def test_parse_accepts_single_element_prompt_list():
    api = protocol.parse_request(
        protocol.COMPLETIONS, json.dumps({"prompt": ["one"]}).encode())
    assert api.prompt_text == "one"


@pytest.mark.parametrize("path,body", [
    (protocol.COMPLETIONS, b"{not json"),
    (protocol.COMPLETIONS, b"[1,2]"),
    (protocol.COMPLETIONS, b'{"prompt": "x", "n": 2}'),
    (protocol.COMPLETIONS, b'{"prompt": "x", "max_tokens": 0}'),
    (protocol.COMPLETIONS, b'{"prompt": ""}'),
    (protocol.COMPLETIONS, b'{"prompt": ["a", "b"]}'),
    (protocol.CHAT_COMPLETIONS, b'{"messages": []}'),
    (protocol.CHAT_COMPLETIONS, b'{"messages": [{"role": "user"}]}'),
    ("/v1/embeddings", b"{}"),
])
def test_parse_rejects_malformed(path, body):
    with pytest.raises(protocol.ProtocolError) as ei:
        protocol.parse_request(path, body)
    assert ei.value.status in (400, 404)
    err = json.loads(ei.value.body())
    assert err["error"]["message"]


def test_priority_from_body_and_header():
    api = protocol.parse_request(
        protocol.COMPLETIONS,
        json.dumps({"prompt": "x", "priority": "interactive"}).encode(),
        {"x-priority": "batch"})
    assert api.priority == "interactive"      # body wins
    api = protocol.parse_request(
        protocol.COMPLETIONS, json.dumps({"prompt": "x"}).encode(),
        {"x-priority": "batch"})
    assert api.priority == "batch"


# ---------------------------------------------------------------------------
# protocol: SSE framing + response bodies
# ---------------------------------------------------------------------------

def test_sse_framing():
    frame = protocol.stream_chunk("completion", "cmpl-1", "m", 123, "ab")
    assert frame.startswith(b"data: ") and frame.endswith(b"\n\n")
    obj = json.loads(frame[len(b"data: "):])
    assert obj["choices"][0]["text"] == "ab"
    assert obj["choices"][0]["finish_reason"] is None

    fin = protocol.stream_chunk("chat", "c-1", "m", 123, "", "length")
    obj = json.loads(fin[len(b"data: "):])
    assert obj["object"] == "chat.completion.chunk"
    assert obj["choices"][0]["delta"] == {}
    assert obj["choices"][0]["finish_reason"] == "length"
    assert protocol.SSE_DONE == b"data: [DONE]\n\n"


def test_final_response_usage_math():
    body = protocol.final_response("chat", "c-1", "m", 1, "out",
                                   "length", 11, 5)
    obj = json.loads(body)
    assert obj["choices"][0]["message"]["content"] == "out"
    assert obj["usage"] == {"prompt_tokens": 11, "completion_tokens": 5,
                            "total_tokens": 16}


# ---------------------------------------------------------------------------
# byte tokenizer + incremental detokenizer
# ---------------------------------------------------------------------------

def test_byte_tokenizer_roundtrip():
    for text in ("hello", "héllo wörld", "日本語テスト", "mixed: é日x"):
        ids = ByteTokenizer.encode(text)
        assert all(0 <= i < 256 for i in ids)
        assert ByteTokenizer.decode(ids) == text


def test_incremental_detok_handles_split_utf8():
    text = "a⚡é日"
    ids = ByteTokenizer.encode(text)
    detok = IncrementalDetokenizer()
    pieces = [detok.feed(i) for i in ids]     # one byte at a time
    # multi-byte sequences must be held, not emitted as replacement chars
    assert "".join(pieces) + detok.flush() == text
    assert "�" not in "".join(pieces)


def test_detok_out_of_range_id_renders_marker():
    detok = IncrementalDetokenizer()
    out = detok.feed(300)
    assert "⟨300⟩" in out


# ---------------------------------------------------------------------------
# admission queue
# ---------------------------------------------------------------------------

def _req():
    return Request(prompt_len=8, max_new_tokens=4)


def test_admission_priority_order_and_fifo():
    q = AdmissionQueue(AdmissionConfig(max_depth=16))
    batch = [_req() for _ in range(2)]
    inter = [_req() for _ in range(2)]
    for r in batch:
        q.push(r, "batch", 0.0)
    for r in inter:
        q.push(r, "interactive", 0.0)
    popped = [q.pop().req for _ in range(4)]
    assert popped == inter + batch            # strict priority, then FIFO


def test_admission_stride_fairness_within_rank():
    cfg = AdmissionConfig(max_depth=64, classes={
        "heavy": (0, 3.0), "light": (0, 1.0)}, default_class="heavy")
    q = AdmissionQueue(cfg)
    for _ in range(12):
        q.push(_req(), "heavy", 0.0)
        q.push(_req(), "light", 0.0)
    order = [q.pop().cls for _ in range(8)]
    # 3:1 weighted service, not starvation and not alternation
    assert order.count("heavy") == 6 and order.count("light") == 2


def test_admission_displacement_prefers_low_priority_newest():
    q = AdmissionQueue(AdmissionConfig(max_depth=2))
    q.push(_req(), "batch", 0.0)
    newest_batch = _req()
    q.push(newest_batch, "batch", 1.0)
    ok, displaced = q.push(_req(), "interactive", 2.0)
    assert ok and [e.req for e in displaced] == [newest_batch]
    # a full queue refuses an arrival no better than anything queued
    ok, displaced = q.push(_req(), "batch", 3.0)
    assert not ok and not displaced
    assert q.displaced == 1


def test_admission_shed_drops_back_of_lowest_classes():
    q = AdmissionQueue(AdmissionConfig(max_depth=32))
    inter = [_req() for _ in range(2)]
    batch = [_req() for _ in range(4)]
    for r in inter:
        q.push(r, "interactive", 0.0)
    for i, r in enumerate(batch):
        q.push(r, "batch", float(i))
    out = q.shed(0.5)                         # 3 of 6 queued
    assert len(out) == 3
    assert all(e.cls == "batch" for e in out)
    assert out[0].req is batch[-1]            # newest first
    assert q.shed_count == 3 and len(q) == 3


def test_admission_drain_and_gauges():
    q = AdmissionQueue(AdmissionConfig(max_depth=8))
    for i in range(3):
        q.push(_req(), "standard", float(i))
    g = q.gauges(5.0)
    assert g["depth"] == 3 and g["oldest_wait_s"] == 5.0
    assert g["depth_by_class"]["standard"] == 3
    assert "budget_deferrals_total" not in g   # budgets off: no gauges
    assert len(q.drain()) == 3 and len(q) == 0


def test_admission_token_budget_gates_class():
    # each request charges prompt(8) + max_new(4) = 12 tokens; a
    # 10-token/s budget admits one per window (the gate checks before
    # charging — one overshoot, then the class is ineligible)
    q = AdmissionQueue(AdmissionConfig(
        max_depth=16, token_budgets={"batch": 10.0}, budget_window=1.0))
    for _ in range(3):
        q.push(_req(), "batch", 0.0)
    assert q.pop(0.0) is not None              # 12 charged (overshoot)
    assert q.pop(0.1) is None                  # 12 >= 10: over budget
    assert q.budget_deferrals == 1
    assert len(q) == 2                         # deferred, not dropped
    assert q.pop(1.0) is not None              # window rolled: admits
    g = q.gauges(1.0)
    assert g["budget_deferrals_total"] == 1
    assert g["window_tokens_by_class"]["batch"] == 12.0  # fresh window


def test_admission_budget_skips_to_unbudgeted_class():
    # over-budget batch must not block standard (unlimited) — the gate
    # restricts eligibility, it does not stall the whole queue
    q = AdmissionQueue(AdmissionConfig(
        max_depth=16, token_budgets={"batch": 1.0}, budget_window=1.0))
    q.push(_req(), "batch", 0.0)
    q.push(_req(), "batch", 0.0)
    q.push(_req(), "standard", 0.0)
    assert q.pop(0.0).cls == "standard"        # higher rank serves first
    assert q.pop(0.0).cls == "batch"           # first charge always fits
    assert q.pop(0.0) is None                  # batch over budget: deferred
    # without a timestamp the gate is bypassed (legacy no-clock callers)
    assert q.pop().cls == "batch"


def test_admission_retry_after_tracks_drain_rate():
    q = AdmissionQueue(AdmissionConfig(max_depth=64, max_inflight=4))
    for i in range(20):
        q.push(_req(), "standard", 0.0)
    # no release history yet: falls back to cycle counting
    assert q.retry_after_hint() == int(1 + 20 / 4)
    # drain 10 at 2 per second -> observed rate 2/s, 10 left -> ~5 s
    for i in range(10):
        q.pop(i * 0.5)
    assert q.retry_after_hint() == 5
    assert 1 <= q.retry_after_hint(99.0) <= 60


# ---------------------------------------------------------------------------
# serving loop + admission: bursts queue instead of rejecting
# ---------------------------------------------------------------------------

def test_burst_queues_not_rejects():
    loop = _mk_loop(slo=LOOSE, admission=AdmissionConfig(
        max_depth=64, max_inflight=4))
    reqs = [Request(prompt_len=64, max_new_tokens=8, hidden_output_len=8)
            for _ in range(24)]
    handles = [loop.submit(r) for r in reqs]  # burst: all at t=0
    assert len(loop.admission) == 24 - 4      # excess queued, NOT dropped
    assert loop.shed_rejections == 0
    loop.run()
    assert all(h.done and not h.rejected and not h.cancelled
               for h in handles)
    snap = loop.snapshot()
    assert snap["admission"]["released_total"] == 24
    assert snap["queue_wait"]["releases"] > 0
    assert snap["queue_wait"]["max_s"] > 0.0


def test_admission_displacement_rejects_and_resolves():
    loop = _mk_loop(slo=LOOSE, admission=AdmissionConfig(
        max_depth=2, max_inflight=0))        # nothing ever releases
    low = [loop.submit(Request(prompt_len=8, max_new_tokens=2),
                       priority="batch") for _ in range(2)]
    hi = loop.submit(Request(prompt_len=8, max_new_tokens=2),
                     priority="interactive")
    assert low[-1].rejected and not hi.done  # newest batch displaced
    assert loop.shed_rejections == 1


def test_cancel_queued_resolves_cancelled():
    loop = _mk_loop(slo=LOOSE, admission=AdmissionConfig(
        max_depth=16, max_inflight=1))
    handles = [loop.submit(Request(prompt_len=32, max_new_tokens=4,
                                   hidden_output_len=4))
               for _ in range(5)]
    n = loop.cancel_queued()
    assert n == 4
    assert sum(h.cancelled for h in handles) == 4
    loop.run()                                # the released one finishes
    assert sum(h.done and not h.cancelled for h in handles) == 1
    assert loop.snapshot()["cancelled_total"] == 4


def test_submit_receipt_preserves_arrival():
    loop = _mk_loop(slo=LOOSE)
    loop.submit(Request(prompt_len=32, max_new_tokens=8,
                        hidden_output_len=8))
    loop.run()
    now = loop.cluster.now
    assert now > 0.05
    late = Request(prompt_len=32, max_new_tokens=4, hidden_output_len=4)
    loop.submit(late, receipt=0.01)           # received long before now
    assert late.arrival == 0.01               # receipt is arrival truth
    loop.run()
    assert late.state == State.FINISHED
    # TTFT includes the time the loop ran behind, it is not clamped away
    assert late.ttft() >= now - 0.01


# ---------------------------------------------------------------------------
# token pipeline (inline mode)
# ---------------------------------------------------------------------------

def _collect_sink(frames):
    def sink(rid, payload, done, t_event, pid):
        frames.append((payload, done, pid))
    return sink


def test_pipeline_inline_streaming():
    frames = []
    with TokenPipeline(n_workers=0) as pipe:
        ids = pipe.tokenize("hé!").result(timeout=5)
        assert ids == ByteTokenizer.encode("hé!")
        pipe.open_stream(7, "completion", "cmpl-7", "m", 1, True,
                         _collect_sink(frames))
        for i in ids:
            pipe.push_tokens(7, [i], 0.0)
        pipe.finish(7, "length", len(ids), 0.0)
    done_flags = [d for _, d, _ in frames]
    assert done_flags[-1] and not any(done_flags[:-1])
    text = ""
    for payload, _, _ in frames:
        for line in payload.split(b"\n\n"):
            if line.startswith(b"data: ") and line != b"data: [DONE]":
                obj = json.loads(line[len(b"data: "):])
                text += obj["choices"][0]["text"]
    assert text == "hé!"
    assert frames[-1][0].endswith(protocol.SSE_DONE)


def test_pipeline_inline_nonstream_accumulates():
    frames = []
    with TokenPipeline(n_workers=0) as pipe:
        ids = ByteTokenizer.encode("okay")
        pipe.open_stream(9, "chat", "c-9", "m", 1, False,
                         _collect_sink(frames))
        pipe.push_tokens(9, ids[:2], 0.0)
        pipe.push_tokens(9, ids[2:], 0.0)
        pipe.finish(9, "length", 4, 0.0)
    assert len(frames) == 1 and frames[0][1]  # single done payload
    obj = json.loads(frames[0][0])
    assert obj["choices"][0]["message"]["content"] == "okay"
    assert obj["usage"]["completion_tokens"] == 4


# ---------------------------------------------------------------------------
# token pipeline (real worker processes)
# ---------------------------------------------------------------------------

def test_pipeline_work_happens_in_worker_processes():
    frames = []
    got = threading.Event()

    def sink(rid, payload, done, t_event, pid):
        frames.append((payload, done, pid))
        if done:
            got.set()

    with TokenPipeline(n_workers=1) as pipe:
        ids = pipe.tokenize("worker").result(timeout=30)
        assert ids == ByteTokenizer.encode("worker")
        pipe.open_stream(3, "completion", "cmpl-3", "m", 1, True, sink)
        pipe.push_tokens(3, ids, time.monotonic())
        pipe.finish(3, "length", len(ids), time.monotonic())
        assert got.wait(timeout=30)
    # detokenization + formatting ran OUT of this process
    assert frames and all(pid != os.getpid() for _, _, pid in frames)


# ---------------------------------------------------------------------------
# controller: admission shed actuator
# ---------------------------------------------------------------------------

def _feed_bad_both(tw, now):
    for k in range(6):
        r = Request(prompt_len=10, max_new_tokens=4, arrival=now - 0.5)
        r.record_token(now + 10.0)            # ttft hopeless
        tw.on_token(r, now)
    for k in range(6):
        r = Request(prompt_len=10, max_new_tokens=3, arrival=0.0)
        gap = BAL.tpot * 3.0
        r.record_token(now - 2 * gap)
        r.record_token(now - gap)
        r.record_token(now)
        tw.on_finish(r, now)                  # tpot hopeless


def test_controller_sheds_admission_when_both_starved():
    ctl = SliderController(ControllerConfig(epoch=1.0, cooldown=0,
                                            shed_fraction=0.5))
    loop = _mk_loop(admission=AdmissionConfig(max_depth=32,
                                              max_inflight=0),
                    controller=ctl)
    handles = [loop.submit(Request(prompt_len=8, max_new_tokens=2),
                           priority="batch") for _ in range(8)]
    _feed_bad_both(loop.telemetry, 1.0)
    ctl.on_epoch(1.0)
    assert ctl.moves and ctl.moves[-1]["kind"] == "shed"
    assert ctl.moves[-1]["count"] == 4        # half the queue
    assert sum(h.rejected for h in handles) == 4
    assert loop.admission.shed_count == 4


def test_controller_queue_age_counts_as_ttft_starvation():
    ctl = SliderController(ControllerConfig(epoch=1.0, cooldown=0,
                                            queue_guard=0.5))
    loop = _mk_loop(admission=AdmissionConfig(max_depth=32,
                                              max_inflight=0),
                    controller=ctl)
    for _ in range(4):
        loop.submit(Request(prompt_len=8, max_new_tokens=2))
    # only-good TPOT evidence, nothing TTFT-bad in the window — but the
    # queue's oldest entry has burned > half the TTFT SLO
    for k in range(6):
        r = Request(prompt_len=10, max_new_tokens=3, arrival=0.0)
        gap = BAL.tpot * 0.5
        r.record_token(2.0 - 2 * gap)
        r.record_token(2.0 - gap)
        r.record_token(2.0)
        loop.telemetry.on_finish(r, 2.0)
    ctl.on_epoch(2.0)                         # oldest_wait=2.0 > 0.75
    assert any(m["kind"] in ("chunk", "flip") for m in ctl.moves), \
        "queue pressure must drive a prefill-capacity move"


# ---------------------------------------------------------------------------
# HTTP server end-to-end over the simulated cluster (loopback socket)
# ---------------------------------------------------------------------------

@pytest.fixture()
def server():
    loop = _mk_loop(slo=LOOSE, admission=AdmissionConfig(
        max_depth=64, max_inflight=2))
    srv = FrontendServer(loop, FrontendConfig(port=0, tok_workers=0))
    th = threading.Thread(target=srv.run, daemon=True)
    th.start()
    assert srv.started.wait(timeout=15)
    yield srv
    srv.shutdown()
    th.join(timeout=15)
    assert not th.is_alive()


def _http(port, method, path, body=b"", headers=""):
    s = socket.create_connection(("127.0.0.1", port), timeout=20)
    s.sendall((f"{method} {path} HTTP/1.1\r\nHost: t\r\n{headers}"
               f"Content-Length: {len(body)}\r\nConnection: close\r\n"
               "\r\n").encode() + body)
    data = b""
    while chunk := s.recv(65536):
        data += chunk
    s.close()
    head, _, payload = data.partition(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1])
    return status, head, payload


def _sse_events(payload):
    """De-chunk a Transfer-Encoding: chunked body, split SSE events."""
    body, rest = b"", payload
    while rest:
        size, _, rest = rest.partition(b"\r\n")
        n = int(size, 16)
        if n == 0:
            break
        body += rest[:n]
        rest = rest[n + 2:]
    return [e for e in body.split(b"\n\n") if e]


def test_http_completion_nonstream(server):
    status, _, payload = _http(
        server.port, "POST", "/v1/completions",
        json.dumps({"prompt": "hello", "max_tokens": 4}).encode())
    assert status == 200
    obj = json.loads(payload)
    assert obj["object"] == "text_completion"
    assert obj["choices"][0]["finish_reason"] == "length"
    assert obj["usage"]["prompt_tokens"] == 5


def test_http_chat_stream_sse(server):
    status, head, payload = _http(
        server.port, "POST", "/v1/chat/completions",
        json.dumps({"messages": [{"role": "user", "content": "hi"}],
                    "max_tokens": 4, "stream": True}).encode())
    assert status == 200
    assert b"text/event-stream" in head
    events = _sse_events(payload)
    assert events[-1] == b"data: [DONE]"
    fin = json.loads(events[-2][len(b"data: "):])
    assert fin["object"] == "chat.completion.chunk"
    assert fin["choices"][0]["finish_reason"] == "length"


def test_http_error_routes(server):
    status, _, payload = _http(server.port, "GET", "/v1/completions")
    assert status == 405
    status, _, payload = _http(server.port, "POST", "/v1/completions",
                               b"{broken")
    assert status == 400
    assert b"JSON" in payload
    status, _, _ = _http(server.port, "POST", "/v1/embeddings", b"{}")
    assert status == 404
    status, _, _ = _http(server.port, "PUT", "/healthz")
    assert status == 404


def test_http_healthz_and_metrics(server):
    status, _, payload = _http(server.port, "GET", "/healthz")
    assert status == 200 and json.loads(payload)["status"] == "ok"
    # push one request through so telemetry has content
    _http(server.port, "POST", "/v1/completions",
          json.dumps({"prompt": "m", "max_tokens": 2}).encode())
    status, _, payload = _http(server.port, "GET", "/metrics")
    assert status == 200
    snap = json.loads(payload)
    assert snap["finished_total"] >= 1
    assert "admission" in snap and snap["admission"]["released_total"] >= 1


def test_http_burst_queues_and_reports_wait(server):
    results = []

    def one(i):
        results.append(_http(
            server.port, "POST", "/v1/completions",
            json.dumps({"prompt": f"burst {i}",
                        "max_tokens": 2}).encode())[0])

    threads = [threading.Thread(target=one, args=(i,)) for i in range(12)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    # max_inflight=2: the burst queues and drains — every request served
    assert results == [200] * 12
    _, _, payload = _http(server.port, "GET", "/metrics")
    snap = json.loads(payload)
    assert snap["admission"]["enqueued_total"] >= 12
    assert snap["admission"]["displaced_total"] == 0
    assert "queue_wait" in snap


def test_http_priority_header_lands_in_admission(server):
    status, _, _ = _http(
        server.port, "POST", "/v1/completions",
        json.dumps({"prompt": "vip", "max_tokens": 2}).encode(),
        headers="x-priority: interactive\r\n")
    assert status == 200
    reqs = [r for r in server.loop.requests if r.priority is not None]
    assert any(r.priority == "interactive" for r in reqs)


# ---------------------------------------------------------------------------
# request-lifecycle hardening: finish reasons, overload headers,
# per-instance health, disconnect propagation
# ---------------------------------------------------------------------------

def test_protocol_renders_both_finish_reasons():
    for reason in ("stop", "length"):
        fin = protocol.stream_chunk("completion", "cmpl-1", "m", 1, "",
                                    reason)
        obj = json.loads(fin[len(b"data: "):])
        assert obj["choices"][0]["finish_reason"] == reason
        body = protocol.final_response("completion", "cmpl-1", "m", 1,
                                       "txt", reason, 3, 4)
        assert json.loads(body)["choices"][0]["finish_reason"] == reason


def test_eos_before_cap_finishes_stop_at_cap_finishes_length():
    loop = _mk_loop(slo=LOOSE)
    eos = Request(prompt_len=64, max_new_tokens=32, hidden_output_len=8)
    cap = Request(prompt_len=64, max_new_tokens=8, hidden_output_len=100)
    loop.submit(eos)
    loop.submit(cap)
    loop.run()
    assert eos.state == State.FINISHED and cap.state == State.FINISHED
    assert (eos.finish_reason, eos.output_len) == ("stop", 8)
    assert (cap.finish_reason, cap.output_len) == ("length", 8)


def test_http_reject_carries_retry_after():
    # a zero-depth queue refuses every arrival: the client must get a
    # 503 with a Retry-After hint, not a bare error
    loop = _mk_loop(slo=LOOSE, admission=AdmissionConfig(
        max_depth=0, max_inflight=0))
    srv = FrontendServer(loop, FrontendConfig(port=0, tok_workers=0))
    th = threading.Thread(target=srv.run, daemon=True)
    th.start()
    assert srv.started.wait(timeout=15)
    try:
        status, head, payload = _http(
            srv.port, "POST", "/v1/completions",
            json.dumps({"prompt": "nope", "max_tokens": 2}).encode())
        assert status == 503
        assert b"Retry-After:" in head
        assert b"overloaded" in payload
    finally:
        srv.shutdown()
        th.join(timeout=15)


def test_http_healthz_reports_per_instance_health(server):
    status, _, payload = _http(server.port, "GET", "/healthz")
    obj = json.loads(payload)
    assert status == 200 and obj["status"] == "ok"
    insts = obj["instances"]
    assert insts and all(i["health"] == "ok" for i in insts)
    assert {"iid", "itype", "health", "draining"} <= set(insts[0])
    # every instance down: healthz flips to 503 and names the cause
    for inst in server.loop.cluster.instances:
        inst.health = HEALTH_DEAD
    status, _, payload = _http(server.port, "GET", "/healthz")
    obj = json.loads(payload)
    assert status == 503 and obj["status"] == "no healthy instances"
    assert all(i["health"] == "dead" for i in obj["instances"])


class _TokenEchoExecutor(SimExecutor):
    """Sim oracle that also emits one byte token per generated token (a
    prefill's last chunk and each decode step, like the live engine), so
    the SSE path streams real mid-generation frames without any
    accelerator work."""

    def step_async(self, plan):
        for req, _, _, completes in plan.prefill_rows():
            if completes:
                req.output_tokens.append(65)  # "A"
        for req in plan.decode_reqs:
            req.output_tokens.append(65)
        return super().step_async(plan)


def test_sse_disconnect_aborts_engine_request():
    # paced wall-clock loop: 512 tokens take seconds of real time, so
    # the client can vanish mid-stream and the engine must notice, stop
    # generating into the dead socket, and free the KV blocks
    loop = _mk_loop(slo=LOOSE, clock=WallClock(), pace=True,
                    executor_factory=_TokenEchoExecutor,
                    admission=AdmissionConfig(max_depth=16, max_inflight=4))
    srv = FrontendServer(loop, FrontendConfig(port=0, tok_workers=0))
    th = threading.Thread(target=srv.run, daemon=True)
    th.start()
    assert srv.started.wait(timeout=15)
    try:
        body = json.dumps({"prompt": "never read", "max_tokens": 512,
                           "stream": True}).encode()
        s = socket.create_connection(("127.0.0.1", srv.port), timeout=20)
        s.sendall((f"POST /v1/completions HTTP/1.1\r\nHost: t\r\n"
                   f"Content-Length: {len(body)}\r\n\r\n").encode() + body)
        assert s.recv(1)                  # stream is live
        # RST on close so the server's next frame write fails at once
        s.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                     struct.pack("ii", 1, 0))
        s.close()
        deadline = time.monotonic() + 20
        aborted = None
        while aborted is None and time.monotonic() < deadline:
            aborted = next((r for r in loop.requests
                            if r.state == State.CANCELLED), None)
            time.sleep(0.05)
        assert aborted is not None, "disconnect never propagated"
        assert aborted.finish_reason == "abort"
        assert aborted.output_len < 512   # generation stopped early
        deadline = time.monotonic() + 10
        while (any(i.allocator.holds(aborted.rid)
                   for i in loop.cluster.instances)
               and time.monotonic() < deadline):
            time.sleep(0.05)
        for inst in loop.cluster.instances:
            assert not inst.allocator.holds(aborted.rid), "KV leaked"
        assert loop.aborted_count >= 1
    finally:
        srv.shutdown()
        th.join(timeout=15)


def test_graceful_shutdown_cancels_queued():
    # max_inflight=0: everything stays in the admission queue, so a
    # drain must answer the waiting client with a cancellation, not
    # hang or serve it
    loop = _mk_loop(slo=LOOSE, admission=AdmissionConfig(
        max_depth=16, max_inflight=0))
    srv = FrontendServer(loop, FrontendConfig(port=0, tok_workers=0))
    th = threading.Thread(target=srv.run, daemon=True)
    th.start()
    assert srv.started.wait(timeout=15)
    out = {}

    def client():
        out["resp"] = _http(
            srv.port, "POST", "/v1/completions",
            json.dumps({"prompt": "doomed", "max_tokens": 2}).encode())

    ct = threading.Thread(target=client, daemon=True)
    ct.start()
    deadline = time.monotonic() + 10
    while not loop.admission or len(loop.admission) == 0:
        assert time.monotonic() < deadline, "request never queued"
        time.sleep(0.02)
    srv.shutdown()
    ct.join(timeout=15)
    th.join(timeout=15)
    assert not th.is_alive()
    status, _, payload = out["resp"]
    assert status == 503 and b"cancelled" in payload
    assert loop.cancelled_count == 1
