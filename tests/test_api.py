"""OpenAI-compatible serving gateway tests (ISSUE 20).

Layers under test, cheapest first:

- wire/unit: SSE framing grammar, incremental stop matching, the byte
  tokenizer, chat templates, OpenAI error objects;
- translation: request-body edge cases against a fake backend (no
  engine, no HTTP);
- live worker: ``/v1/*`` on an api-enabled ``LLMWorker`` — parity with
  the native ``/worker_generate``, stream grammar + usage, shed → 429,
  client-disconnect abort freeing slot + KV pages, gate-off 404;
- live router: the SSE relay over the failover journal — bit-identical
  to ``model.generate`` through two workers, with the router's SLO
  sketches stamping every streamed token exactly once.
"""

import http.client
import io
import json
import socket
import struct
import time

import numpy as np
import pytest

from bigdl_tpu import observability as obs
from bigdl_tpu import reliability as rel
from bigdl_tpu.llm.api import (ByteTokenizer, InvalidRequestError,
                               OpenAIGateway, RateLimitError, StopMatcher,
                               UpstreamError, apply_chat_template,
                               build_tokenizer, parse_sse, sse_done,
                               sse_event)
from bigdl_tpu.llm.api.errors import error_for_status
from bigdl_tpu.llm.models.llama import LlamaConfig, LlamaForCausalLM
from bigdl_tpu.llm.serving import LLMServer
from bigdl_tpu.llm.worker import LLMRouter, LLMWorker

pytestmark = pytest.mark.api

MODEL_ID = "bigdl-tpu-llm"


@pytest.fixture(scope="module")
def model():
    return LlamaForCausalLM.from_config(LlamaConfig.tiny(), seed=0,
                                        max_cache_len=128)


def _generate(model, p, n):
    return [int(t) for t in
            model.generate(np.asarray(p)[None], max_new_tokens=n)
            [0, len(p):]]


def _req(addr, method, path, body=None, headers=None, timeout=120):
    conn = http.client.HTTPConnection(*addr, timeout=timeout)
    try:
        payload = json.dumps(body) if body is not None else None
        conn.request(method, path, payload,
                     dict(headers or {},
                          **({"Content-Type": "application/json"}
                             if body is not None else {})))
        r = conn.getresponse()
        data = json.loads(r.read().decode())
        return r.status, data, dict(r.getheaders())
    finally:
        conn.close()


def _stream(addr, path, body, timeout=120):
    """POST with ``stream=true`` → (status, [chunks], headers)."""
    conn = http.client.HTTPConnection(*addr, timeout=timeout)
    try:
        conn.request("POST", path, json.dumps(dict(body, stream=True)),
                     {"Content-Type": "application/json"})
        r = conn.getresponse()
        if r.status != 200:
            return r.status, json.loads(r.read().decode()), \
                dict(r.getheaders())
        return 200, list(parse_sse(r)), dict(r.getheaders())
    finally:
        conn.close()


# ---------------------------------------------------------------------------
# SSE framing
# ---------------------------------------------------------------------------

class TestSSEFraming:
    def test_event_grammar(self):
        assert sse_event({"a": 1}) == b'data: {"a": 1}\n\n'
        assert sse_done() == b"data: [DONE]\n\n"

    def test_parse_roundtrip_stops_at_done(self):
        wire = sse_event({"i": 0}) + sse_event({"i": 1}) + sse_done() \
            + b"data: after-done-is-ignored\n\n"
        got = list(parse_sse(io.BytesIO(wire)))
        assert got == [{"i": 0}, {"i": 1}]

    def test_parse_requires_terminal_done(self):
        with pytest.raises(ValueError, match="DONE"):
            list(parse_sse(io.BytesIO(sse_event({"i": 0}))))

    def test_parse_rejects_foreign_lines(self):
        with pytest.raises(ValueError, match="data line"):
            list(parse_sse(io.BytesIO(b"event: ping\n\n")))


# ---------------------------------------------------------------------------
# stop matching
# ---------------------------------------------------------------------------

class TestStopMatcher:
    def test_text_stop_split_across_chunks(self):
        m = StopMatcher(["XY"])
        assert m.feed("aX") == ("a", False)   # "X" held back
        assert m.feed("Yb") == ("", True)     # match cut exactly
        assert m.hit and m.flush() is None

    def test_text_no_match_flushes_tail(self):
        m = StopMatcher(["ZZ"])
        assert m.feed("aZ") == ("a", False)
        assert m.flush() == "Z"

    def test_earliest_stop_wins(self):
        m = StopMatcher(["cd", "b"])
        emit, done = m.feed("abcd")
        assert (emit, done) == ("a", True)

    def test_token_stop_sequences(self):
        m = StopMatcher([[5, 6]])
        emit, done = m.feed([1, 5])
        assert (list(emit), done) == ([1], False)
        emit, done = m.feed([6, 7])
        assert (list(emit), done) == ([], True)

    def test_no_stops_passthrough(self):
        m = StopMatcher([])
        assert m.feed("anything") == ("anything", False)


# ---------------------------------------------------------------------------
# tokenizer + chat templates
# ---------------------------------------------------------------------------

class TestTemplates:
    def test_byte_tokenizer_roundtrip(self):
        tok = ByteTokenizer()
        ids = tok.encode("héllo")
        assert all(0 <= t < 256 for t in ids)
        assert tok.decode(ids) == "héllo"

    def test_build_tokenizer_knob(self):
        assert build_tokenizer("") is None
        assert isinstance(build_tokenizer("byte"), ByteTokenizer)
        with pytest.raises(ValueError, match="byte"):
            build_tokenizer("sentencepiece")

    def test_families(self):
        msgs = [{"role": "system", "content": "be terse"},
                {"role": "user", "content": "hi"},
                {"role": "assistant", "content": "hello"},
                {"role": "user", "content": "bye"}]
        plain = apply_chat_template("plain", msgs)
        assert "### Human: hi" in plain and plain.endswith(
            "### Assistant:")
        llama = apply_chat_template("llama", msgs)
        assert "<<SYS>>" in llama and "[INST] bye [/INST]" in llama
        glm = apply_chat_template("chatglm", msgs)
        assert "[Round 0]\n问：hi" in glm and glm.endswith("答：")

    @pytest.mark.parametrize("messages", [
        [],
        [{"role": "user", "content": "hi"},
         {"role": "assistant", "content": "yo"}],   # must end on user
        [{"role": "tool", "content": "x"}],
        [{"role": "user", "content": 7}],
        "not a list",
    ])
    def test_bad_messages_rejected(self, messages):
        with pytest.raises(InvalidRequestError) as ei:
            apply_chat_template("plain", messages)
        assert ei.value.param == "messages"


# ---------------------------------------------------------------------------
# OpenAI error objects
# ---------------------------------------------------------------------------

class TestErrors:
    def test_shed_maps_to_429_rate_limit(self):
        e = error_for_status(503, "queue full", retry_after="7")
        assert isinstance(e, RateLimitError)
        assert e.status == 429
        assert dict(e.headers())["Retry-After"] == "7"
        err = e.body()["error"]
        assert err["type"] == "rate_limit_error"
        assert err["code"] == "rate_limit_exceeded"

    def test_4xx_keeps_status_as_invalid_request(self):
        e = error_for_status(422, "bad shape")
        assert isinstance(e, InvalidRequestError) and e.status == 422
        assert e.body()["error"]["type"] == "invalid_request_error"

    def test_5xx_is_api_error(self):
        e = error_for_status(504, "deadline")
        assert isinstance(e, UpstreamError) and e.status == 504
        assert e.body()["error"]["type"] == "api_error"


# ---------------------------------------------------------------------------
# translation edges (fake backend, no engine)
# ---------------------------------------------------------------------------

class _FakeBackend:
    model_name = MODEL_ID
    request_timeout = 5.0

    def sampling(self):
        return (0.0, 0)

    def generate(self, prompt_ids, max_new_tokens, priority, deadline,
                 on_delta):
        raise AssertionError("translation tests never dispatch")


class _ScriptedBackend(_FakeBackend):
    """Feeds scripted token groups through on_delta — the unit harness
    for stop matching + emission without an engine."""

    def __init__(self, groups):
        self.groups = [list(g) for g in groups]

    def generate(self, prompt_ids, max_new_tokens, priority, deadline,
                 on_delta):
        out = []
        for g in self.groups:
            out.extend(g)
            if on_delta is not None:
                on_delta(list(g))
        return out, "length"


class TestTranslation:
    def gw(self, tokenizer="byte"):
        return OpenAIGateway(_FakeBackend(),
                             tokenizer=build_tokenizer(tokenizer))

    def translate(self, body, headers=None, chat=False,
                  tokenizer="byte"):
        return self.gw(tokenizer)._translate(body, headers or {},
                                             chat=chat)

    def test_token_prompt_is_native(self):
        t = self.translate({"prompt": [1, 2, 3]}, tokenizer="")
        assert t.prompt_ids == [1, 2, 3] and t.max_tokens == 16
        assert t.n == 1 and not t.stream and t.priority is None

    def test_model_mismatch_404(self):
        with pytest.raises(InvalidRequestError) as ei:
            self.translate({"model": "gpt-4", "prompt": [1]})
        assert ei.value.status == 404
        assert ei.value.code == "model_not_found"

    @pytest.mark.parametrize("body,param", [
        ({"prompt": [1], "max_tokens": 0}, "max_tokens"),
        ({"prompt": [1], "max_tokens": "lots"}, "max_tokens"),
        ({"prompt": [1], "n": 0}, "n"),
        ({"prompt": [1], "n": 9}, "n"),
        ({"prompt": [1], "temperature": 0.7}, "temperature"),
        ({"prompt": [1], "top_k": 40}, "top_k"),
        ({"prompt": [1], "top_p": 0.9}, "top_p"),
        ({"prompt": [1], "stop": ["a", "b", "c", "d", "e"]}, "stop"),
        ({"prompt": [1], "stop": 7}, "stop"),
        ({"prompt": [1], "stop": [[1], "x"]}, "stop"),
        ({"prompt": []}, "prompt"),
        ({"prompt": [1, True, 3]}, "prompt"),
        ({}, "prompt"),
    ])
    def test_invalid_bodies(self, body, param):
        with pytest.raises(InvalidRequestError) as ei:
            self.translate(body)
        assert ei.value.param == param

    def test_matching_sampling_params_accepted(self):
        t = self.translate({"prompt": [1], "temperature": 0.0,
                            "top_k": 0, "top_p": 1.0})
        assert t.prompt_ids == [1]

    def test_stop_normalization(self):
        t = self.translate({"prompt": [1], "stop": "ab"})
        assert t.stops_text == ["ab"] and t.stops_tokens == []
        t = self.translate({"prompt": [1], "stop": [5, 6]})
        assert t.stops_tokens == [[5, 6]] and t.stops_text == []
        t = self.translate({"prompt": [1], "stop": [[5], [6, 7]]})
        assert t.stops_tokens == [[5], [6, 7]]

    def test_text_needs_tokenizer(self):
        with pytest.raises(InvalidRequestError) as ei:
            self.translate({"prompt": "hello"}, tokenizer="")
        assert ei.value.param == "prompt"
        with pytest.raises(InvalidRequestError) as ei:
            self.translate({"prompt": [1], "stop": "x"}, tokenizer="")
        assert ei.value.param == "stop"
        t = self.translate({"prompt": "hi"})
        assert t.prompt_ids == ByteTokenizer().encode("hi")

    def test_chat_templating_into_tokens(self):
        t = self.translate(
            {"messages": [{"role": "user", "content": "hi"}]},
            chat=True)
        want = ByteTokenizer().encode(apply_chat_template(
            "plain", [{"role": "user", "content": "hi"}]))
        assert t.prompt_ids == want and t.rid.startswith("chatcmpl-")

    def test_priority_header_and_user_passthrough(self):
        t = self.translate({"prompt": [1]},
                           headers={"X-BigDL-Priority": "batch"})
        assert t.priority == "batch"
        t = self.translate({"prompt": [1], "user": "interactive"})
        assert t.priority == "interactive"
        t = self.translate({"prompt": [1], "user": "alice"})
        assert t.priority is None    # opaque user ids are not classes

    def test_run_choice_text_stop_held_back(self):
        # "W" then "XY" arrives split across groups: the held-back "X"
        # never leaks and the stream cuts exactly at the match
        tok = ByteTokenizer()
        gw = OpenAIGateway(
            _ScriptedBackend([tok.encode("aX"), tok.encode("Yb")]),
            tokenizer=tok)
        treq = gw._translate({"prompt": "p", "stop": "XY"}, {},
                             chat=False)
        emitted = []
        generated, finish = gw._run_choice(
            treq, lambda ids, txt: emitted.append(txt))
        assert finish == "stop"
        assert "".join(emitted) == "a"

    def test_run_choice_token_stop(self):
        gw = OpenAIGateway(_ScriptedBackend([[1, 5], [6, 7]]),
                           tokenizer=None)
        treq = gw._translate({"prompt": [9], "stop": [5, 6]}, {},
                             chat=False)
        emitted = []
        _, finish = gw._run_choice(
            treq, lambda ids, txt: emitted.append(ids))
        assert finish == "stop"
        assert [t for g in emitted for t in g] == [1]


# ---------------------------------------------------------------------------
# live worker surface
# ---------------------------------------------------------------------------

class TestWorkerGateway:
    @pytest.fixture(scope="class")
    def served(self, model):
        srv = LLMServer(model, max_batch=2, max_seq_len=64,
                        page_size=8, kvcache=True).start()
        worker = LLMWorker(srv, api=True,
                           tokenizer=ByteTokenizer()).start()
        yield model, srv, worker
        worker.stop()
        srv.stop()

    def test_models_route(self, served):
        _, _, worker = served
        st, body, _ = _req(worker.address, "GET", "/v1/models")
        assert st == 200 and body["object"] == "list"
        assert [m["id"] for m in body["data"]] == [MODEL_ID]

    def test_blocking_parity_with_native(self, served):
        model, _, worker = served
        ids = [3, 1, 4, 1, 5]
        want = _generate(model, ids, 6)
        st, native, _ = _req(worker.address, "POST", "/worker_generate",
                             {"prompt_ids": ids, "max_new_tokens": 6})
        assert st == 200 and native["output_ids"] == want
        st, body, _ = _req(worker.address, "POST", "/v1/completions",
                           {"model": MODEL_ID, "prompt": ids,
                            "max_tokens": 6})
        assert st == 200, body
        choice = body["choices"][0]
        assert choice["token_ids"] == want
        assert choice["finish_reason"] == "length"
        assert body["usage"] == {"prompt_tokens": 5,
                                 "completion_tokens": 6,
                                 "total_tokens": 11}

    def test_stream_grammar_usage_and_parity(self, served):
        model, _, worker = served
        ids = [2, 7, 1, 8]
        want = _generate(model, ids, 6)
        st, chunks, hdrs = _stream(worker.address, "/v1/completions",
                                   {"model": MODEL_ID, "prompt": ids,
                                    "max_tokens": 6})
        assert st == 200
        assert hdrs["Content-Type"] == "text/event-stream"
        toks = [t for c in chunks
                for t in c["choices"][0].get("token_ids", [])]
        assert toks == want
        # exactly one terminal finish chunk, usage rides the last chunk
        finals = [c for c in chunks
                  if c["choices"][0]["finish_reason"] is not None]
        assert len(finals) == 1 and finals[0] is chunks[-1]
        assert chunks[-1]["usage"]["completion_tokens"] == 6
        rid = chunks[0]["id"]
        assert rid.startswith("cmpl-")
        assert all(c["id"] == rid for c in chunks)

    def test_stream_raw_wire_has_done_sentinel(self, served):
        _, _, worker = served
        conn = http.client.HTTPConnection(*worker.address, timeout=120)
        try:
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt": [1, 2, 3],
                                     "max_tokens": 2, "stream": True}),
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            raw = r.read()     # http.client undoes the chunking
        finally:
            conn.close()
        events = [ln for ln in raw.split(b"\n\n") if ln]
        assert all(e.startswith(b"data: ") for e in events)
        assert events[-1] == b"data: [DONE]"

    def test_token_stop_sequence_live(self, served):
        model, _, worker = served
        ids = [3, 1, 4, 1, 5]
        want = _generate(model, ids, 6)
        # the stop token must not occur before its own index, or the
        # stop fires earlier than the test expects
        fresh = [i for i in range(1, len(want)) if want[i] not in want[:i]]
        assert fresh, f"greedy run {want} never leaves its first token"
        stop_at = fresh[0]
        st, body, _ = _req(worker.address, "POST", "/v1/completions",
                           {"prompt": ids, "max_tokens": 6,
                            "stop": [want[stop_at]]})
        assert st == 200, body
        choice = body["choices"][0]
        assert choice["finish_reason"] == "stop"
        assert choice["token_ids"] == want[:stop_at]

    def test_n_two_choices_greedy_identical(self, served):
        model, _, worker = served
        ids = [5, 5, 2]
        want = _generate(model, ids, 4)
        st, body, _ = _req(worker.address, "POST", "/v1/completions",
                           {"prompt": ids, "max_tokens": 4, "n": 2})
        assert st == 200, body
        assert [c["index"] for c in body["choices"]] == [0, 1]
        for c in body["choices"]:
            assert c["token_ids"] == want
        assert body["usage"]["completion_tokens"] == 2 * len(want)

    def test_chat_completions_roundtrip(self, served):
        _, _, worker = served
        msgs = [{"role": "user", "content": "hi"}]
        st, body, _ = _req(worker.address, "POST",
                           "/v1/chat/completions",
                           {"model": MODEL_ID, "messages": msgs,
                            "max_tokens": 3})
        assert st == 200, body
        msg = body["choices"][0]["message"]
        assert msg["role"] == "assistant"
        assert isinstance(msg["content"], str)
        assert body["object"] == "chat.completion"
        want_prompt = ByteTokenizer().encode(
            apply_chat_template("plain", msgs))
        assert body["usage"]["prompt_tokens"] == len(want_prompt)

    def test_chat_stream_delta_grammar(self, served):
        _, _, worker = served
        st, chunks, _ = _stream(
            worker.address, "/v1/chat/completions",
            {"messages": [{"role": "user", "content": "go"}],
             "max_tokens": 3})
        assert st == 200
        assert chunks[0]["choices"][0]["delta"].get("role") \
            == "assistant"
        assert chunks[-1]["choices"][0]["delta"] == {}
        assert chunks[-1]["choices"][0]["finish_reason"] is not None
        assert chunks[0]["object"] == "chat.completion.chunk"

    def test_bad_bodies_answer_openai_error_objects(self, served):
        _, _, worker = served
        st, body, _ = _req(worker.address, "POST", "/v1/completions",
                           {"model": MODEL_ID})
        assert st == 400
        err = body["error"]
        assert err["type"] == "invalid_request_error"
        assert err["param"] == "prompt" and "message" in err
        st, body, _ = _req(worker.address, "POST", "/v1/completions",
                           {"model": "gpt-4o", "prompt": [1]})
        assert st == 404
        assert body["error"]["code"] == "model_not_found"

    def test_non_json_body_is_invalid(self, served):
        _, _, worker = served
        conn = http.client.HTTPConnection(*worker.address, timeout=60)
        try:
            conn.request("POST", "/v1/completions", b"not json{",
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            body = json.loads(r.read().decode())
        finally:
            conn.close()
        assert r.status == 400
        assert body["error"]["type"] == "invalid_request_error"

    def test_overload_sheds_as_429_with_retry_after(self, served,
                                                    monkeypatch):
        _, srv, worker = served

        def full(*a, **k):
            raise rel.OverloadError("queue full (max_queue=0)")
        monkeypatch.setattr(srv, "submit", full)
        st, body, hdrs = _req(worker.address, "POST",
                              "/v1/completions",
                              {"prompt": [1, 2], "max_tokens": 2})
        assert st == 429
        err = body["error"]
        assert err["type"] == "rate_limit_error"
        assert err["code"] == "rate_limit_exceeded"
        assert float(hdrs["Retry-After"]) >= 1.0

    def test_client_disconnect_aborts_and_frees_pages(self, served):
        model, srv, worker = served
        ids = [6, 2, 9, 4]
        st, _, _ = _req(worker.address, "POST", "/worker_generate",
                        {"prompt_ids": ids, "max_new_tokens": 12})
        assert st == 200
        kv = srv._kv
        pool = kv.pool
        # conservation baseline: every non-free page is indexed (the
        # radix legitimately keeps the aborted chain cached); a page
        # held by a dead slot would make the sum fall short
        page_sum = lambda: pool.free_pages() \
            + kv.index.indexed_pages()  # noqa: E731
        base_sum = page_sum()
        cancelled = lambda: obs.REGISTRY.sample_value(  # noqa: E731
            "bigdl_llm_requests_total", reason="cancelled") or 0.0
        before = cancelled()
        was = rel.enabled()
        if not was:
            rel.enable()
        plan = rel.FaultPlan(seed=0)
        plan.add("llm.step", "delay", times=None, delay=0.05)
        rel.set_plan(plan)
        try:
            conn = http.client.HTTPConnection(*worker.address,
                                              timeout=60)
            conn.request("POST", "/v1/completions",
                         json.dumps({"prompt": ids, "max_tokens": 12,
                                     "stream": True}),
                         {"Content-Type": "application/json"})
            r = conn.getresponse()
            first = r.readline()         # status is in; first event
            assert first.startswith(b"data: ")
            # a plain close() would keep the fd alive through the
            # response's makefile ref — no FIN ever reaches the server.
            # SO_LINGER(0) + closing both handles emits an RST, so the
            # next SSE write raises and the relay must abort.
            conn.sock.setsockopt(socket.SOL_SOCKET, socket.SO_LINGER,
                                 struct.pack("ii", 1, 0))
            r.close()
            conn.sock.close()
        finally:
            rel.set_plan(None)
            if not was:
                rel.disable()
        deadline = time.time() + 30
        while time.time() < deadline:
            if cancelled() > before and page_sum() >= base_sum:
                break
            time.sleep(0.05)
        assert cancelled() > before, \
            "disconnect never reached LLMServer.abort"
        assert page_sum() >= base_sum, \
            "aborted stream leaked KV pages"
        # the slot is reusable: a follow-up request answers correctly
        want = _generate(model, ids, 3)
        st, body, _ = _req(worker.address, "POST", "/v1/completions",
                           {"prompt": ids, "max_tokens": 3})
        assert st == 200 and body["choices"][0]["token_ids"] == want

    def test_api_counter_tracks_outcomes(self, served):
        _, _, worker = served
        if not obs.enabled():
            pytest.skip("observability disabled")
        val = lambda o: obs.REGISTRY.sample_value(  # noqa: E731
            "bigdl_api_requests_total", route="/v1/completions",
            outcome=o) or 0.0
        ok0, inv0 = val("ok"), val("invalid")
        _req(worker.address, "POST", "/v1/completions",
             {"prompt": [1, 2], "max_tokens": 2})
        _req(worker.address, "POST", "/v1/completions", {})
        assert val("ok") == ok0 + 1
        assert val("invalid") == inv0 + 1


class TestGateOff:
    def test_disabled_worker_404s_naming_the_gate(self, model):
        srv = LLMServer(model, max_batch=2, max_seq_len=32).start()
        worker = LLMWorker(srv).start()
        before = set(obs.render().splitlines()) if obs.enabled() \
            else set()
        try:
            assert worker._api is None
            for method, path in (("GET", "/v1/models"),
                                 ("POST", "/v1/completions"),
                                 ("POST", "/v1/chat/completions")):
                st, body, _ = _req(worker.address, method, path,
                                   {} if method == "POST" else None)
                assert st == 404, (path, st, body)
                assert "bigdl.llm.api.enabled" in body["error"]
            # the native surface still works and grew no api series
            st, out, _ = _req(worker.address, "POST",
                              "/worker_generate",
                              {"prompt_ids": [1, 2],
                               "max_new_tokens": 2})
            assert st == 200 and len(out["output_ids"]) == 2
            if obs.enabled():
                new = set(obs.render().splitlines()) - before
                assert not [ln for ln in new if "bigdl_api_" in ln], \
                    "gate-off serving grew bigdl_api_* series"
        finally:
            worker.stop()
            srv.stop()

    def test_router_gateway_requires_failover(self, model):
        with pytest.raises(ValueError, match="failover"):
            LLMRouter([], [("127.0.0.1", 1)], start_prober=False,
                      api=True)


# ---------------------------------------------------------------------------
# live router: SSE relay over the failover journal
# ---------------------------------------------------------------------------

class TestRouterGateway:
    @pytest.fixture(scope="class")
    def fleet(self, model):
        servers = [LLMServer(model, max_batch=2, max_seq_len=64,
                             page_size=8, kvcache=True,
                             slo=True).start() for _ in range(2)]
        workers = [LLMWorker(s, role="decode").start() for s in servers]
        router = LLMRouter([], [w.address for w in workers],
                           failover=True, start_prober=False,
                           slo=True, api=True).start()
        yield model, servers, workers, router
        router.stop()
        for w in workers:
            w.stop()
        for s in servers:
            s.stop()

    def _slo(self):
        if not obs.enabled():
            return None
        reg = obs.REGISTRY
        return {
            "ttft": reg.sample_value("bigdl_router_ttft_seconds")
            or 0.0,
            "itl": reg.sample_value("bigdl_router_itl_seconds") or 0.0}

    def test_streams_bit_identical_with_one_slo_accounting(self, fleet):
        model, _, _, router = fleet
        prompts = [[3, 1, 4, 1, 5], [9, 2, 6], [5, 3, 5, 8, 9, 7]]
        want = [_generate(model, p, 5) for p in prompts]
        before = self._slo()
        got = []
        for p in prompts:
            st, chunks, _ = _stream(router.address, "/v1/completions",
                                    {"model": MODEL_ID, "prompt": p,
                                     "max_tokens": 5})
            assert st == 200, chunks
            got.append([t for c in chunks
                        for t in c["choices"][0].get("token_ids", [])])
            assert chunks[-1]["usage"]["completion_tokens"] == 5
        assert got == want
        after = self._slo()
        if after is not None:
            # the SSE relay and the router SLO sketches fire from the
            # same journal drain: requests stamped exactly once
            assert after["ttft"] - before["ttft"] == len(prompts)
            assert after["itl"] - before["itl"] == \
                sum(len(w) - 1 for w in want)

    def test_blocking_matches_native_route(self, fleet):
        model, _, _, router = fleet
        ids = [7, 7, 2, 1]
        want = _generate(model, ids, 4)
        st, native, _ = _req(router.address, "POST",
                             "/worker_generate",
                             {"prompt_ids": ids, "max_new_tokens": 4})
        assert st == 200 and native["output_ids"] == want
        st, body, _ = _req(router.address, "POST", "/v1/completions",
                           {"prompt": ids, "max_tokens": 4})
        assert st == 200 and body["choices"][0]["token_ids"] == want

    def test_models_route_on_router(self, fleet):
        _, _, _, router = fleet
        st, body, _ = _req(router.address, "GET", "/v1/models")
        assert st == 200
        assert body["data"][0]["id"] == MODEL_ID


# ---------------------------------------------------------------------------
# langchain base_url client helper (satellite)
# ---------------------------------------------------------------------------

class TestLangchainClient:
    @pytest.fixture(scope="class")
    def served(self, model):
        srv = LLMServer(model, max_batch=2, max_seq_len=64,
                        page_size=8, kvcache=True).start()
        worker = LLMWorker(srv, api=True,
                           tokenizer=ByteTokenizer()).start()
        yield model, srv, worker
        worker.stop()
        srv.stop()

    def test_invoke_models_stream_and_chat(self, served):
        from bigdl_tpu.llm.langchain import BigdlTpuOpenAI
        _, _, worker = served
        host, port = worker.address
        llm = BigdlTpuOpenAI(f"http://{host}:{port}/v1",
                             max_tokens=4)
        assert llm.models() == [MODEL_ID]
        blocking = llm.invoke("hello")
        assert isinstance(blocking, str)
        streamed = "".join(llm.stream("hello"))
        assert streamed == blocking      # greedy: same text both ways
        answer = llm.chat([{"role": "user", "content": "hello"}])
        assert isinstance(answer, str)

    def test_base_url_parsing(self):
        from bigdl_tpu.llm.langchain import BigdlTpuOpenAI
        assert BigdlTpuOpenAI._parse("http://h:8000/v1") == ("h", 8000)
        assert BigdlTpuOpenAI._parse("h:8000") == ("h", 8000)
        with pytest.raises(ValueError):
            BigdlTpuOpenAI._parse("http://no-port/v1")
