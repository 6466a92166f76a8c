"""Serve tests (reference model: python/ray/serve/tests/test_standalone.py,
test_deployment_graph.py, test_batching.py, test_autoscaling_policy.py)."""

import time

import pytest

import ray_tpu
from ray_tpu import serve


@pytest.fixture
def serve_cluster():
    ray_tpu.init(num_cpus=16, ignore_reinit_error=True)
    yield
    serve.shutdown()
    ray_tpu.shutdown()


def test_function_deployment(serve_cluster):
    @serve.deployment
    def echo(x):
        return {"echo": x}

    handle = serve.run(echo.bind(), name="echo_app")
    assert handle.remote("hi").result() == {"echo": "hi"}


def test_class_deployment_replicas(serve_cluster):
    @serve.deployment(num_replicas=2)
    class Doubler:
        def __call__(self, x):
            return x * 2

        def triple(self, x):
            return x * 3

    handle = serve.run(Doubler.bind(), name="doubler")
    out = [handle.remote(i).result() for i in range(6)]
    assert out == [0, 2, 4, 6, 8, 10]
    # named method routing
    assert handle.triple.remote(3).result() == 9
    st = serve.status()
    assert st["Doubler"]["live"] == 2


def test_composition_graph(serve_cluster):
    @serve.deployment
    class Preprocess:
        def __call__(self, x):
            return x + 1

    @serve.deployment
    class Model:
        def __init__(self, pre):
            self.pre = pre

        def __call__(self, x):
            y = self.pre.remote(x).result()
            return y * 10

    app = Model.bind(Preprocess.bind())
    handle = serve.run(app, name="graph")
    assert handle.remote(4).result() == 50


def test_batching(serve_cluster):
    @serve.deployment
    class Batched:
        @serve.batch(max_batch_size=4, batch_wait_timeout_s=0.2)
        def __call__(self, items):
            # whole batch processed at once
            return [{"v": i, "batch_size": len(items)} for i in items]

    handle = serve.run(Batched.bind(), name="batched")
    responses = [handle.remote(i) for i in range(4)]
    results = [r.result(timeout_s=10) for r in responses]
    assert [r["v"] for r in results] == [0, 1, 2, 3]
    assert max(r["batch_size"] for r in results) > 1  # actually batched


def test_autoscaling_policy_math():
    from ray_tpu.serve.autoscaling import calculate_desired_num_replicas
    from ray_tpu.serve.deployment import AutoscalingConfig

    ac = AutoscalingConfig(min_replicas=1, max_replicas=10, target_ongoing_requests=2)
    assert calculate_desired_num_replicas(ac, 0, 1) == 1
    assert calculate_desired_num_replicas(ac, 9, 1) == 5
    assert calculate_desired_num_replicas(ac, 100, 4) == 10  # clamped
    assert calculate_desired_num_replicas(ac, 0, 0) == 1


def test_autoscaling_batch_occupancy_signal():
    """Decode-aware scaling: a generation-bound replica whose batcher slots
    are saturated upscales even while the queued-call count alone would not
    (ROADMAP serving remainder: scale on batch saturation, not just queue)."""
    from ray_tpu.serve.autoscaling import calculate_desired_num_replicas
    from ray_tpu.serve.deployment import AutoscalingConfig

    ac = AutoscalingConfig(
        min_replicas=1, max_replicas=10, target_ongoing_requests=100,
        target_batch_occupancy=0.8,
    )
    # queue depth says 1 replica (8 << 100), but all 8 slots are running:
    # occupancy 1.0 > 0.8 target -> 2 replicas
    assert calculate_desired_num_replicas(
        ac, 8, 1, batch_slots=8, batch_load=8) == 2
    # half-busy slots: occupancy 0.5 <= 0.8 -> stay
    assert calculate_desired_num_replicas(
        ac, 4, 1, batch_slots=8, batch_load=4) == 1
    # queued generations count toward load: 8 active + 8 waiting on 8 slots
    # needs 2x capacity at full occupancy, 3 replicas at 0.8 target
    assert calculate_desired_num_replicas(
        ac, 16, 1, batch_slots=8, batch_load=16) == 3
    # no batcher -> pure queue-depth policy, unchanged
    assert calculate_desired_num_replicas(ac, 16, 1) == 1
    # idle batcher never pins replicas up (downscale still possible)
    assert calculate_desired_num_replicas(
        ac, 0, 4, batch_slots=32, batch_load=0) == 1


def test_replica_stats_surface_batcher_occupancy():
    """Replica.stats() aggregates ContinuousBatcher-shaped drainable
    attributes into batch_slots/active/queued for the controller's
    autoscale loop."""
    from ray_tpu.serve.replica import Replica

    class FakeBatcher:
        _serve_drainable = True

        def __init__(self, slots, active, queued):
            self._s = {"max_batch_size": slots, "active": active,
                       "queued": queued}

        def stats(self):
            return dict(self._s)

        def drain(self, deadline_s=None):
            pass

    class Deployment:
        def __init__(self):
            self.batcher = FakeBatcher(8, 5, 3)
            self.other = FakeBatcher(4, 1, 0)

        def __call__(self):
            return "ok"

    r = Replica("gen", Deployment, (), {})
    s = r.stats()
    assert s["batch_slots"] == 12
    assert s["batch_active"] == 6
    assert s["batch_queued"] == 3
    # a plain replica reports zeros (queue-depth-only policy)
    r2 = Replica("plain", lambda: "ok", (), {})
    s2 = r2.stats()
    assert (s2["batch_slots"], s2["batch_active"], s2["batch_queued"]) == (0, 0, 0)


def test_autoscaling_e2e_upscale(serve_cluster):
    @serve.deployment(
        autoscaling_config={
            "min_replicas": 1,
            "max_replicas": 3,
            "target_ongoing_requests": 1.0,
            "upscale_delay_s": 0.1,
            "downscale_delay_s": 60,
        }
    )
    class Slow:
        def __call__(self, x):
            time.sleep(1.0)
            return x

    handle = serve.run(Slow.bind(), name="slow")
    # flood with concurrent requests to build queue depth
    responses = [handle.remote(i) for i in range(12)]
    deadline = time.time() + 15
    scaled = False
    while time.time() < deadline:
        if serve.status()["Slow"]["live"] >= 2:
            scaled = True
            break
        time.sleep(0.25)
    [r.result(timeout_s=30) for r in responses]
    assert scaled, f"never scaled up: {serve.status()}"


def test_redeploy_updates_code(serve_cluster):
    @serve.deployment(name="V")
    def v1(x):
        return "v1"

    @serve.deployment(name="V")
    def v2(x):
        return "v2"

    h = serve.run(v1.bind(), name="app_v")
    assert h.remote(0).result() == "v1"
    h = serve.run(v2.bind(), name="app_v")
    assert h.remote(0).result() == "v2"


def test_http_proxy(serve_cluster):
    @serve.deployment
    def classify(body):
        return {"label": "cat", "input": body}

    serve.run(classify.bind(), name="http_app", route_prefix="/classify")
    addr = serve.proxy_address()
    assert addr is not None

    import json
    import urllib.request

    req = urllib.request.Request(
        f"http://{addr}/classify",
        data=json.dumps({"pixels": [1, 2]}).encode(),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=30) as resp:
        out = json.loads(resp.read())
    assert out["result"]["label"] == "cat"
    assert out["result"]["input"] == {"pixels": [1, 2]}


def test_delete_application(serve_cluster):
    @serve.deployment
    def f(x):
        return x

    serve.run(f.bind(), name="todelete")
    assert "f" in serve.status()
    serve.delete("todelete")
    assert "f" not in serve.status()


def test_slow_constructor_is_not_replaced(serve_cluster):
    """A replica whose __init__ outlasts the health check's 10 s deadline
    (a model-sized constructor: weights, engine, compiles) is still
    STARTING, not dead. The control loop used to drop it unreaped and
    spawn a replacement every pass — on a one-chip host the replacement
    then waited forever for the TPU the first one held."""
    from ray_tpu.experimental.state.api import list_actors
    from ray_tpu.serve.handle import CONTROLLER_NAME

    @serve.deployment
    class Slow:
        def __init__(self):
            time.sleep(12)

        def __call__(self, _):
            import os

            return os.getpid()

    h = serve.run(Slow.bind(), name="slow")
    pid = h.remote(None).result(timeout_s=30)
    assert h.remote(None).result(timeout_s=30) == pid
    ctl = ray_tpu.get_actor(CONTROLLER_NAME)
    assert len(ray_tpu.get(ctl.get_replicas.remote("Slow"), timeout=10)) == 1
    replicas = [a for a in list_actors() if a.get("class_name") == "Replica"]
    assert len(replicas) == 1, replicas
