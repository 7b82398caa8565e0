"""Serve: deployments, routing, composition, batching, autoscaling,
replica recovery, HTTP proxy.

Mirrors the reference's serve test strategy (reference:
python/ray/serve/tests/ — test_deploy.py, test_autoscaling_policy.py,
test_batching.py, test_multiplex.py) at unit scale.
"""

import json
import threading
import time
import urllib.request

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_instance(ray_start_shared):
    yield ray_start_shared
    serve.shutdown()


def test_function_deployment(serve_instance):
    @serve.deployment
    def double(req):
        return req["x"] * 2

    handle = serve.run(double.bind(), name="fn_app")
    assert handle.remote({"x": 21}).result() == 42


def test_class_deployment_and_methods(serve_instance):
    @serve.deployment
    class Counter:
        def __init__(self, start):
            self.count = start

        def __call__(self, req):
            return self.count

        def incr(self, by):
            self.count += by
            return self.count

    handle = serve.run(Counter.bind(10), name="cls_app")
    assert handle.remote({}).result() == 10
    assert handle.incr.remote(5).result() == 15
    assert handle.options(method_name="incr").remote(1).result() == 16


def test_num_replicas_spread(serve_instance):
    @serve.deployment(num_replicas=3, ray_actor_options={"num_cpus": 0})
    class WhoAmI:
        def __init__(self):
            import os
            self.pid = os.getpid()

        def __call__(self, req):
            return self.pid

    handle = serve.run(WhoAmI.bind(), name="spread_app")
    pids = {handle.remote({}).result() for _ in range(30)}
    assert len(pids) >= 2  # pow-2 routing spreads across replicas


def test_composition(serve_instance):
    @serve.deployment
    class Adder:
        def __init__(self, amount):
            self.amount = amount

        def __call__(self, x):
            return x + self.amount

    @serve.deployment
    class Pipeline:
        def __init__(self, a, b):
            self.a = a  # DeploymentHandles
            self.b = b

        def __call__(self, req):
            x = self.a.remote(req["x"]).result()
            return self.b.remote(x).result()

    app = Pipeline.bind(Adder.options(name="add1").bind(1),
                        Adder.options(name="add10").bind(10))
    # 3 deployments × worker spawn can exceed the 60s default readiness
    # budget on a loaded shared box; total must stay under the 150s
    # per-test watchdog
    handle = serve.run(app, name="comp_app", timeout_s=110.0)
    assert handle.remote({"x": 0}).result(timeout_s=30) == 11


def test_batching(serve_instance):
    @serve.deployment(max_ongoing_requests=32)
    class Batcher:
        def __init__(self):
            self.batch_sizes = []

        @serve.batch(max_batch_size=8, batch_wait_timeout_s=0.2)
        def handle_batch(self, items):
            self.batch_sizes.append(len(items))
            return [i * 2 for i in items]

        def __call__(self, req):
            return self.handle_batch(req["x"])

        def sizes(self, req):
            return self.batch_sizes

    handle = serve.run(Batcher.bind(), name="batch_app")
    results = [None] * 16

    def call(i):
        results[i] = handle.remote({"x": i}).result()

    threads = [threading.Thread(target=call, args=(i,)) for i in range(16)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert results == [i * 2 for i in range(16)]
    sizes = handle.sizes.remote({}).result()
    assert max(sizes) > 1  # batching actually batched


def test_user_config_reconfigure(serve_instance):
    @serve.deployment(user_config={"threshold": 1})
    class Thresh:
        def __init__(self):
            self.threshold = None

        def reconfigure(self, config):
            self.threshold = config["threshold"]

        def __call__(self, req):
            return self.threshold

    handle = serve.run(Thresh.bind(), name="cfg_app")
    assert handle.remote({}).result() == 1
    serve.run(Thresh.options(user_config={"threshold": 5}).bind(),
              name="cfg_app")
    deadline = time.monotonic() + 10
    while time.monotonic() < deadline:
        if handle.remote({}).result() == 5:
            break
        time.sleep(0.1)
    assert handle.remote({}).result() == 5


def test_autoscaling_up_and_down(serve_instance):
    @serve.deployment(
        autoscaling_config={"min_replicas": 1, "max_replicas": 3,
                            "target_ongoing_requests": 1.0,
                            "upscale_delay_s": 0.0,
                            "downscale_delay_s": 0.5,
                            "look_back_period_s": 1.0},
        max_ongoing_requests=100,
        ray_actor_options={"num_cpus": 0})
    class Slow:
        def __call__(self, req):
            time.sleep(0.3)
            return 1

    handle = serve.run(Slow.bind(), name="auto_app")

    stop = time.monotonic() + 6.0
    def hammer():
        while time.monotonic() < stop:
            try:
                handle.remote({}).result()
            except Exception:
                pass

    threads = [threading.Thread(target=hammer) for _ in range(8)]
    for t in threads:
        t.start()
    saw_upscale = False
    while time.monotonic() < stop:
        st = serve.status()["Slow"]
        if st["running_replicas"] >= 2:
            saw_upscale = True
            break
        time.sleep(0.2)
    for t in threads:
        t.join()
    assert saw_upscale, serve.status()
    deadline = time.monotonic() + 15
    while time.monotonic() < deadline:
        if serve.status()["Slow"]["running_replicas"] == 1:
            break
        time.sleep(0.3)
    assert serve.status()["Slow"]["running_replicas"] == 1


def test_replica_crash_recovery(serve_instance):
    @serve.deployment(ray_actor_options={"num_cpus": 0})
    class Fragile:
        def __call__(self, req):
            if req.get("die"):
                import os
                os._exit(1)
            return "alive"

    handle = serve.run(Fragile.bind(), name="crash_app")
    assert handle.remote({}).result() == "alive"
    try:
        handle.remote({"die": True}).result(timeout_s=5)
    except Exception:
        pass
    deadline = time.monotonic() + 20
    ok = False
    while time.monotonic() < deadline:
        try:
            if handle.remote({}).result(timeout_s=5) == "alive":
                ok = True
                break
        except Exception:
            time.sleep(0.2)
    assert ok, "controller did not replace the dead replica"


def test_multiplexed_models(serve_instance):
    @serve.deployment
    class MultiModel:
        def __init__(self):
            self.loads = []

        @serve.multiplexed(max_num_models_per_replica=2)
        def get_model(self, model_id):
            self.loads.append(model_id)
            return {"id": model_id, "scale": int(model_id[-1])}

        def __call__(self, req):
            model = self.get_model(req["model"])
            return req["x"] * model["scale"]

        def load_count(self, req):
            return len(self.loads)

    handle = serve.run(MultiModel.bind(), name="mux_app")
    assert handle.remote({"model": "m2", "x": 10}).result() == 20
    assert handle.remote({"model": "m3", "x": 10}).result() == 30
    assert handle.remote({"model": "m2", "x": 5}).result() == 10
    assert handle.load_count.remote({}).result() == 2  # m2 cached


def test_http_proxy(serve_instance):
    @serve.deployment
    def echo(req):
        return {"got": req}

    serve.start(proxy=True,
                http_options=serve.HTTPOptions(port=0))
    from ray_tpu import serve as serve_mod
    port = serve_mod._proxy.port
    serve.run(echo.bind(), name="http_app", route_prefix="/echo")
    body = json.dumps({"a": 1}).encode()
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/echo", data=body,
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=30) as resp:
        payload = json.loads(resp.read())
    assert payload == {"got": {"a": 1}}
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/echo?b=2", timeout=30) as resp:
        payload = json.loads(resp.read())
    assert payload == {"got": {"b": "2"}}


def test_grpc_ingress(ray_start_shared):
    """gRPC proxy (generic handlers, no codegen): unary + server
    streaming against deployed apps, routed like the HTTP proxy."""
    grpc = pytest.importorskip("grpc")
    import json

    from ray_tpu import serve

    @serve.deployment
    class Echo:
        def __call__(self, request):
            if request.get("__method__") == "Ping":
                return {"pong": True, "path": request.get("__path__")}
            if request.get("__method__") == "TokensStream":
                def gen():
                    for i in range(int(request.get("n", 3))):
                        yield {"tok": i}
                return gen()
            return {"echo": {k: v for k, v in request.items()
                             if not k.startswith("__")}}

    try:
        serve.start(grpc_port=0)
        from ray_tpu import serve as serve_mod
        port = serve_mod._grpc_proxy.port
        serve.run(Echo.bind(), name="g", route_prefix="/g",
                  blocking_ready=True)

        channel = grpc.insecure_channel(f"127.0.0.1:{port}")
        unary = channel.unary_unary("/ray.serve.UserService/Ping")
        reply = unary(json.dumps({}).encode(),
                      metadata=(("route", "/g"), ("path", "/health")))
        out = json.loads(reply)
        assert out == {"pong": True, "path": "/health"}

        echo = channel.unary_unary("/ray.serve.UserService/Echo")
        out = json.loads(echo(json.dumps({"x": 1}).encode(),
                              metadata=(("route", "/g"),)))
        assert out == {"echo": {"x": 1}}

        stream = channel.unary_stream("/ray.serve.UserService/TokensStream")
        chunks = [json.loads(c) for c in
                  stream(json.dumps({"n": 4}).encode(),
                         metadata=(("route", "/g"),))]
        assert chunks == [{"tok": i} for i in range(4)]

        # unknown route → NOT_FOUND
        with pytest.raises(grpc.RpcError) as err:
            unary(b"{}", metadata=(("route", "/nope"),))
        assert err.value.code() == grpc.StatusCode.NOT_FOUND

        # malformed payload → INVALID_ARGUMENT
        with pytest.raises(grpc.RpcError) as err:
            unary(b"[1,2]", metadata=(("route", "/g"),))
        assert err.value.code() == grpc.StatusCode.INVALID_ARGUMENT
        channel.close()
    finally:
        serve.shutdown()


# --- declarative config deploy (round 3; reference: serve/schema.py:431
#     + `serve deploy` scripts.py) --------------------------------------

def _write_app_module(tmp_path):
    mod = tmp_path / "myserveapp.py"
    mod.write_text(
        "from ray_tpu import serve\n"
        "\n"
        "@serve.deployment(num_replicas=1, max_ongoing_requests=8)\n"
        "class Doubler:\n"
        "    def __init__(self, bias=0):\n"
        "        self.bias = bias\n"
        "    def __call__(self, x):\n"
        "        return 2 * x + self.bias\n"
        "\n"
        "app = Doubler.bind()\n"
        "\n"
        "def build(bias=0):\n"
        "    return Doubler.bind(bias)\n")
    return str(tmp_path)


def test_declarative_deploy_with_overrides(ray_start_shared, tmp_path,
                                           monkeypatch):
    import sys as _sys
    monkeypatch.syspath_prepend(_write_app_module(tmp_path))
    _sys.modules.pop("myserveapp", None)
    try:
        deployed = serve.deploy_config({
            "applications": [{
                "name": "decl",
                "route_prefix": "/decl",
                "import_path": "myserveapp:build",
                "args": {"bias": 5},
                "deployments": [{"name": "Doubler", "num_replicas": 2,
                                 "max_ongoing_requests": 4}],
            }],
        })
        assert deployed == ["decl"]
        handle = serve.get_app_handle("decl")
        assert handle.remote(10).result(timeout_s=60) == 25  # bias applied
        info = serve.status()["Doubler"]
        assert info["target_replicas"] == 2  # override applied
    finally:
        serve.shutdown()
        _sys.modules.pop("myserveapp", None)


def test_declarative_deploy_validation_errors():
    from ray_tpu.serve.schema import ServeDeploySchema
    with pytest.raises(ValueError):
        ServeDeploySchema.from_dict({"applications": []})
    with pytest.raises(ValueError):
        ServeDeploySchema.from_dict({"applications": [
            {"name": "a", "import_path": "no_colon_here"}]})
    with pytest.raises(ValueError):
        ServeDeploySchema.from_dict({"applications": [
            {"name": "a", "import_path": "m:x", "bogus": 1}]})
    with pytest.raises(ValueError):  # duplicate names
        ServeDeploySchema.from_dict({"applications": [
            {"name": "a", "import_path": "m:x"},
            {"name": "a", "import_path": "m:y"}]})


def test_declarative_deploy_over_rest(ray_start_shared, tmp_path,
                                      monkeypatch):
    """POST /api/serve/deploy on the dashboard applies the config —
    the CLI's `serve deploy` path (reference: dashboard REST deploy)."""
    import json as _json
    import sys as _sys
    import urllib.request

    from ray_tpu.dashboard import DashboardServer

    monkeypatch.syspath_prepend(_write_app_module(tmp_path))
    _sys.modules.pop("myserveapp", None)
    rt = ray_start_shared
    dash = DashboardServer(rt, port=0)
    try:
        body = _json.dumps({
            "applications": [{"name": "restapp",
                              "route_prefix": "/rest",
                              "import_path": "myserveapp:app"}],
        }).encode()
        req = urllib.request.Request(
            dash.url + "/api/serve/deploy", data=body,
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            out = _json.load(resp)
        assert out == {"deployed": ["restapp"]}
        assert serve.get_app_handle("restapp").remote(3).result(
            timeout_s=60) == 6
    finally:
        dash.stop()
        serve.shutdown()
        _sys.modules.pop("myserveapp", None)


# --- prefix-aware routing (reference: routing_policies/prefix_aware)


def test_prefix_tree_match_insert_evict():
    from ray_tpu.serve.prefix_router import PrefixTree

    tree = PrefixTree(eviction_threshold_chars=10_000)
    tree.insert("You are a helpful assistant. Question one", "r1")
    tree.insert("You are a helpful assistant. Question two", "r2")
    m = tree.match("You are a helpful assistant. Question three")
    assert set(m) == {"r1", "r2"}
    assert m["r1"] >= 32  # shared prefix matched deep
    # unrelated text matches nothing
    assert tree.match("completely different") == {}
    # dead replicas are forgotten
    tree.drop_replica("r1")
    assert "r1" not in tree.match("You are a helpful assistant.")
    # eviction bound: overflow resets instead of growing forever
    small = PrefixTree(eviction_threshold_chars=100)
    for i in range(50):
        small.insert(f"prompt number {i} with padding text", "r")
    assert small._chars <= 100 + 64


def test_prefix_aware_routing_affinity(ray_start_shared):
    """Balanced load + shared prompt prefix -> same replica every time
    (cache locality); the tree records routed prompts (reference:
    prefix_aware_router.py PrefixCacheAffinityRouter)."""
    from ray_tpu import serve

    @serve.deployment(num_replicas=2, request_router="prefix_aware")
    class Echo:
        def __call__(self, request):
            import os
            return {"pid": os.getpid(),
                    "prompt": request.get("prompt", "")}

    try:
        serve.run(Echo.bind(), name="prefixapp", route_prefix="/pfx")
        handle = serve.get_deployment_handle("Echo",
                                             app_name="prefixapp")
        base = "System: you are terse. Document: " + "x" * 200
        pids = {handle.remote({"prompt": base + f" q{i}"}
                              ).result(timeout_s=60)["pid"]
                for i in range(6)}
        # after the first routing decision lands in the tree, every
        # later shared-prefix request sticks to that replica
        assert len(pids) <= 2
        sticky = {handle.remote({"prompt": base + f" late{i}"}
                                ).result(timeout_s=60)["pid"]
                  for i in range(4)}
        assert len(sticky) == 1
        # unrelated prompts still spread by pow-2 (no crash, any pid)
        handle.remote({"prompt": "zzz different"}).result(timeout_s=60)
    finally:
        serve.shutdown()


def test_proxy_listens_with_room_for_a_burst_of_connections():
    """64 clients connect at once while nobody accepts (the accept
    loop is not even started): every handshake completes out of the
    listen backlog. socketserver's default of 5 let the kernel drop the
    rest, and a dropped SYN is retried after 1, 3, 7, 15, 31 s."""
    import socket
    from http.server import BaseHTTPRequestHandler
    from ray_tpu.serve.proxy import _ProxyServer
    server = _ProxyServer(("127.0.0.1", 0), BaseHTTPRequestHandler)
    clients = []
    try:
        for _ in range(64):
            client = socket.socket()
            client.settimeout(0.5)
            client.connect(server.server_address)
            clients.append(client)
    finally:
        for client in clients:
            client.close()
        server.server_close()
    assert len(clients) == 64
