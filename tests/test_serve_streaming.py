"""Serve streaming: SSE proxy responses, streaming handles, LLM tokens.

Reference models: python/ray/serve/tests/test_streaming_response.py and
the serve/llm OpenAI SSE surface.
"""

import json
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_instance(ray_start_shared):
    yield ray_start_shared
    serve.shutdown()


def test_streaming_handle(serve_instance):
    @serve.deployment
    class Streamer:
        def __call__(self, n):
            for i in range(n):
                yield {"i": i}

    handle = serve.run(Streamer.bind(), name="stream_app")
    out = list(handle.options(stream=True).remote(3))
    assert out == [{"i": 0}, {"i": 1}, {"i": 2}]


def test_streaming_handle_single_value(serve_instance):
    """Non-generator handlers still work through the streaming path."""
    @serve.deployment
    def plain(x):
        return x * 2

    handle = serve.run(plain.bind(), name="plain_stream_app")
    assert list(handle.options(stream=True).remote(21)) == [42]


def test_proxy_sse_response(serve_instance):
    @serve.deployment
    class SSE:
        def __call__(self, request):
            for i in range(3):
                yield f"data: {json.dumps({'n': i})}\n\n"
                time.sleep(0.05)

    serve.start(proxy=True, http_options=serve.HTTPOptions(port=0))
    from ray_tpu import serve as serve_mod
    port = serve_mod._proxy.port
    serve.run(SSE.bind(), name="sse_app", route_prefix="/sse")

    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/sse", timeout=60) as resp:
        assert resp.headers["Content-Type"] == "text/event-stream"
        raw = resp.read().decode()
    events = [json.loads(line[len("data: "):])
              for line in raw.splitlines() if line.startswith("data: ")]
    assert events == [{"n": 0}, {"n": 1}, {"n": 2}]


def test_proxy_plain_json_still_works(serve_instance):
    @serve.deployment
    def echo(request):
        return {"got": request.get("x")}

    serve.start(proxy=True, http_options=serve.HTTPOptions(port=0))
    from ray_tpu import serve as serve_mod
    port = serve_mod._proxy.port
    serve.run(echo.bind(), name="echo_app", route_prefix="/echo")

    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/echo?x=1", timeout=60) as resp:
        payload = json.loads(resp.read())
    assert payload == {"got": "1"}


def test_llm_sse_token_streaming(serve_instance):
    """/v1/completions with stream=true emits per-token SSE chunks and a
    [DONE] terminator (VERDICT round-1 item 4 done-criterion)."""
    from ray_tpu.llm.engine import EngineConfig
    from ray_tpu.models.llama import LlamaConfig
    from ray_tpu.serve.llm import LLMConfig, build_openai_app

    config = LLMConfig(
        model_id="llama-stream-test",
        engine=EngineConfig(
            model=LlamaConfig.tiny(vocab_size=258, max_seq_len=64,
                                   attention="reference", remat=False),
            max_batch=2, max_seq=64),
        max_tokens=8)
    serve.start(proxy=True, http_options=serve.HTTPOptions(port=0))
    from ray_tpu import serve as serve_mod
    port = serve_mod._proxy.port
    serve.run(build_openai_app(config=config), name="llm_stream_app",
              route_prefix="/v1")

    body = json.dumps({"prompt": "hi", "max_tokens": 4,
                       "stream": True}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/completions", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        assert resp.headers["Content-Type"] == "text/event-stream"
        raw = resp.read().decode()
    lines = [ln for ln in raw.splitlines() if ln.startswith("data: ")]
    assert lines[-1] == "data: [DONE]"
    chunks = [json.loads(ln[len("data: "):]) for ln in lines[:-1]]
    # 4 token chunks + 1 finish chunk
    assert len(chunks) == 5
    assert all(c["object"] == "text_completion" for c in chunks)
    assert chunks[-1]["choices"][0]["finish_reason"] == "stop"

    # chat streaming too
    body = json.dumps({"messages": [{"role": "user", "content": "hey"}],
                       "max_tokens": 3, "stream": True}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/v1/chat/completions", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=120) as resp:
        raw = resp.read().decode()
    lines = [ln for ln in raw.splitlines() if ln.startswith("data: ")]
    assert lines[-1] == "data: [DONE]"
    chunks = [json.loads(ln[len("data: "):]) for ln in lines[:-1]]
    assert chunks[0]["choices"][0]["delta"].get("role") == "assistant"
    assert chunks[-1]["choices"][0]["finish_reason"] == "stop"


# -- stream_token_deltas alone: a queue, a stub request, no engine -----

def _stub_request(stop_ids=(257,)):
    import queue
    import types
    return types.SimpleNamespace(stream_queue=queue.Queue(),
                                 stop_ids=tuple(stop_ids), error=None)


def _next_within(gen, timeout_s=10.0):
    """One step of ``gen`` on a thread of its own: ("value", delta),
    ("stop", None) or ("error", exc); fails where the step blocks, as
    a generator does that waits for a token nobody has sampled yet."""
    import threading
    box = []

    def step():
        try:
            box.append(("value", next(gen)))
        except StopIteration:
            box.append(("stop", None))
        except Exception as exc:  # noqa: BLE001 - handed to the test
            box.append(("error", exc))

    thread = threading.Thread(target=step, daemon=True)
    thread.start()
    thread.join(timeout_s)
    assert not thread.is_alive(), (
        "stream_token_deltas waits for a token that is not on the queue")
    return box[0]


_E_ACUTE, _EURO, _GRIN = "é".encode(), "€".encode(), "😀".encode()

# name -> (token ids as put, the chunks due after each put before any
# later put, (immediate, held))
_DELTA_CASES = {
    # the regression: the first token's chunk needs no second token
    "first_token_alone": ([104], [["h"]], (1, 0)),
    "plain_ascii": (list(b"hi!"), [["h"], ["i"], ["!"]], (3, 0)),
    "two_byte_character": (
        [97, *_E_ACUTE, 98], [["a"], [], ["", "é"], ["b"]], (3, 1)),
    "three_byte_character": (
        [97, *_EURO, 98], [["a"], [], [""], ["", "€"], ["b"]], (3, 2)),
    "four_byte_character": (
        [*_GRIN, 98], [[], [""], [""], ["", "😀"], ["b"]], (2, 3)),
    # the sentinel releases the held token with the U+FFFD tail
    "ends_inside_a_character": (
        [97, *_EURO[:2]], [["a"], [], [""]], (1, 2)),
    "stop_ids_yield_no_chunk": (
        [104, 257, 105, 257], [["h"], [], ["i"], []], (2, 0)),
    # ids past the tokenizer's bytes add no text: "" at once
    "token_without_text": ([104, 300, 105], [["h"], [""], ["i"]], (3, 0)),
    # a byte that never becomes a character is a real U+FFFD: it and
    # the textless tokens after it stay one token behind, as before
    "invalid_byte_then_text": (
        [0x80, 300, 97], [[], [""], ["", "\ufffda"]], (1, 2)),
}


@pytest.mark.parametrize("case", list(_DELTA_CASES))
def test_stream_token_deltas_leave_when_sampled(case):
    """One chunk per non-stop token, yielded as soon as that token is
    on the queue unless the decoded text ends inside a character; the
    chunks joined are the non-streamed decode."""
    from ray_tpu.llm.tokenizer import ByteTokenizer
    from ray_tpu.serve.llm import stream_token_deltas

    tokens, due, counts = _DELTA_CASES[case]
    tok, request = ByteTokenizer(), _stub_request()
    tallies = []
    gen = stream_token_deltas(tok, request,
                              lambda *n: tallies.append(n))
    chunks = []
    for token, expected in zip(tokens, due):
        request.stream_queue.put(token)
        got = [_next_within(gen) for _ in expected]
        assert got == [("value", d) for d in expected], (token, got)
        chunks += expected
    assert tallies == []  # counted once, and only at the end
    request.stream_queue.put(None)
    tail = []
    while True:
        kind, value = _next_within(gen)
        if kind == "stop":
            break
        assert kind == "value"
        tail.append(value)
    chunks += tail
    kept = [t for t in tokens if t not in request.stop_ids]
    assert len(tail) <= 1
    assert len(chunks) == len(kept)
    assert "".join(chunks) == tok.decode(kept)
    assert tallies == [counts] and sum(counts) == len(kept)
    if case == "ends_inside_a_character":
        assert tail == ["\ufffd"]


def test_stream_token_deltas_error_raises_after_the_drain():
    from ray_tpu.llm.tokenizer import ByteTokenizer
    from ray_tpu.serve.llm import stream_token_deltas

    request = _stub_request()
    tallies = []
    gen = stream_token_deltas(ByteTokenizer(), request,
                              lambda *n: tallies.append(n))
    request.stream_queue.put(104)
    assert _next_within(gen) == ("value", "h")
    request.error = "engine fell over"
    request.stream_queue.put(None)
    kind, exc = _next_within(gen)
    assert kind == "error" and isinstance(exc, RuntimeError)
    assert "engine fell over" in str(exc)
    assert tallies == [(1, 0)]


def _stub_server(request):
    """An LLMServer with no engine behind it: _make_request hands out
    ``request``, and the engine is a metrics buffer and a cancel."""
    import types
    from ray_tpu.llm.tokenizer import ByteTokenizer
    from ray_tpu.serve.llm import LLMServer
    from ray_tpu.util import metrics

    server = LLMServer.__new__(LLMServer)
    server.tokenizer = ByteTokenizer()
    server._make_request = lambda prompt, **kw: ([256], request)
    server.cancelled = []
    server.engine = types.SimpleNamespace(
        _mbuf=metrics.LocalBuffer(),
        cancel=lambda req, why: server.cancelled.append(why))
    return server


def _streamed_counts(server):
    """{kind: tokens} of what the stub's buffer would ship."""
    return {dict(tags)["kind"]: value
            for kind, name, tags, value, _ in server.engine._mbuf.flush()
            if (kind, name) == ("counter",
                                "ray_tpu_serve_llm_stream_tokens_total")}


@pytest.mark.parametrize("text,stop,expected,counts", [
    ("héllo wörld", None, "héllo wörld", (11, 2)),
    ("plain ascii", None, "plain ascii", (11, 0)),
    # the stop string is never streamed, and neither is its prefix
    ("héllo wörld", ["wö"], "héllo ", None),
    ("plain ascii", ["zz", "asc"], "plain ", None),
    ("no stop here", ["zz"], "no stop here", (12, 0)),
], ids=["split_characters", "ascii", "stop_after_split_characters",
        "stop_in_ascii", "stop_never_hit"])
def test_generate_stream_text_and_stream_token_counter(
        text, stop, expected, counts):
    """_generate_stream over the deltas: the text before a stop string
    is what it was, and each stream adds its tokens once to
    ray_tpu_serve_llm_stream_tokens_total{kind} through the engine's
    buffer: both kinds together the non-stop tokens it streamed, no
    ``held`` for plain ASCII."""
    request = _stub_request()
    tokens = list(text.encode()) + [257]
    for token in tokens + [None]:
        request.stream_queue.put(token)
    server = _stub_server(request)
    out = list(server._generate_stream("p", stop=stop))
    assert "".join(out) == expected
    hit = expected != text
    assert server.cancelled == (["stop"] if hit else [])
    got = _streamed_counts(server)
    assert set(got) == {"immediate", "held"}
    if counts is not None:
        assert (got["immediate"], got["held"]) == counts
        assert sum(counts) == len(text.encode())
        # without stop strings: one chunk a token
        if not stop:
            assert len(out) == len(text.encode())
    else:
        # cancelled at the hit: the tokens read until then, no more
        assert 0 < got["immediate"] + got["held"] <= len(text.encode())
    if text.isascii():
        assert got["held"] == 0
