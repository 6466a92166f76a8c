"""ServeController: the reconciliation control plane, as a named actor.

Reference parity: serve/controller.py:79 (ServeController detached actor),
deployment_state.py:2073 (DeploymentStateManager reconciling target vs live
replicas), autoscaling decision loop (_private/autoscaling_policy.py:69-141),
and the graceful-drain sequencing of deployment_state.py's
stop_replicas(graceful_shutdown) path: replicas leaving the set (redeploy,
downscale, delete, shutdown) are DRAINED — new traffic routed away first,
in-flight requests given a deadline to finish — and only then reaped.
"""

from __future__ import annotations

import threading
import time
from collections import OrderedDict
from typing import Any, Dict, List, Optional

from .autoscaling import calculate_desired_num_replicas
from .deployment import AutoscalingConfig, DeploymentConfig
from .replica import Replica


class _DeploymentState:
    def __init__(self, name: str, func_or_class, init_args, init_kwargs, config):
        self.name = name
        self.func_or_class = func_or_class
        self.init_args = init_args
        self.init_kwargs = init_kwargs
        self.config: DeploymentConfig = config
        self.replicas: List[Any] = []  # ActorHandles
        # actor ids of replicas whose constructor has not answered ready()
        # yet: a model-sized __init__ outlasts the health check's deadline,
        # and a starting replica is not a dead one
        self.starting: set = set()
        self.draining = False  # whole deployment slated for removal
        # prefix-affinity digest: hint -> (replica actor_id, cached chain
        # depth in blocks). Bounded LRU, harvested from replica stats on
        # the heartbeat and published over serve:prefix:<name>.
        self.prefix_digest: "OrderedDict[str, tuple]" = OrderedDict()
        self.target: int = (
            config.autoscaling_config.min_replicas
            if config.autoscaling_config
            else config.num_replicas
        )
        self.last_scale_ts = 0.0


class ServeController:
    def __init__(self):
        self._deployments: Dict[str, _DeploymentState] = {}
        self._apps: Dict[str, Dict[str, Any]] = {}
        self._lock = threading.Lock()
        self._stop = threading.Event()
        # per-node proxy fleet (reference: _private/http_state.py
        # HTTPProxyStateManager — one proxy actor per alive node, shared
        # routing table). Disabled until start_proxies().
        self._proxy_fleet = False
        self._proxy_port = 0
        self._proxies: Dict[str, Any] = {}  # node_id -> handle
        self._proxy_addrs: Dict[str, str] = {}
        self._routes: Dict[str, tuple] = {}  # prefix -> (deployment, pass_req)
        self._drainers: List[threading.Thread] = []
        self._loop_thread = threading.Thread(target=self._reconcile_loop, daemon=True)
        self._loop_thread.start()

    def ready(self):
        return True

    # ------------------------------------------------------- proxy fleet

    def set_route(self, route_prefix: str, deployment_name: str,
                  pass_request: bool = False):
        """Record a route and push it to every fleet proxy. Routes set
        before start_proxies() apply when the fleet comes up."""
        prefix = route_prefix.rstrip("/") or "/"
        with self._lock:
            self._routes[prefix] = (deployment_name, pass_request)
            proxies = list(self._proxies.values())
        self._broadcast(
            [h.set_route.remote(prefix, deployment_name, pass_request)
             for h in proxies]
        )
        return True

    def remove_route(self, route_prefix: str):
        prefix = route_prefix.rstrip("/") or "/"
        with self._lock:
            self._routes.pop(prefix, None)
            proxies = list(self._proxies.values())
        self._broadcast([h.remove_route.remote(prefix) for h in proxies])
        return True

    @staticmethod
    def _broadcast(refs):
        """Push to all proxies with ONE shared deadline — a wedged member
        costs one bounded wait, never N serial timeouts on serve.run's
        critical path (the reconcile loop replaces stragglers)."""
        import ray_tpu

        if refs:
            ray_tpu.wait(refs, num_returns=len(refs), timeout=10)

    def start_proxies(self, port: int = 0) -> Dict[str, str]:
        """Enable the per-node fleet; returns {node_id: host:port}."""
        self._proxy_fleet = True
        self._proxy_port = port
        self._ensure_proxies()
        return self.proxy_addresses()

    def proxy_addresses(self) -> Dict[str, str]:
        with self._lock:
            return dict(self._proxy_addrs)

    def _spawn_proxy(self, node_id: str):
        import ray_tpu
        from ray_tpu.util.scheduling_strategies import NodeAffinitySchedulingStrategy

        from .http_proxy import HTTPProxyActor

        Proxy = ray_tpu.remote(HTTPProxyActor)
        h = Proxy.options(
            name=f"SERVE_PROXY:{node_id}",
            lifetime="detached",
            max_concurrency=32,
            scheduling_strategy=NodeAffinitySchedulingStrategy(
                node_id=node_id, soft=False
            ),
        ).remote("0.0.0.0", self._proxy_port)
        info = ray_tpu.get(h.ready.remote(), timeout=30)
        with self._lock:
            routes = dict(self._routes)
        for prefix, (dep, pr) in routes.items():
            ray_tpu.get(h.set_route.remote(prefix, dep, pr), timeout=10)
        with self._lock:
            if not self._proxy_fleet:
                # shutdown raced this spawn: don't leak a detached proxy
                # that would block the name for every future fleet
                abort = True
            else:
                abort = False
                self._proxies[node_id] = h
                self._proxy_addrs[node_id] = f"{info['host']}:{info['port']}"
        if abort:
            try:
                ray_tpu.kill(h)
            except Exception:
                pass

    def _ensure_proxies(self):
        """One healthy proxy per alive node: spawn on new nodes, drop on
        dead ones, replace unresponsive ones (reference: http_state.py
        reconciliation)."""
        if not self._proxy_fleet:
            return
        import ray_tpu

        alive = {n["node_id"] for n in ray_tpu.nodes() if n.get("alive")}
        with self._lock:
            current = dict(self._proxies)
        for node_id in set(current) - alive:
            try:
                ray_tpu.kill(current[node_id])
            except Exception:
                pass
            with self._lock:
                self._proxies.pop(node_id, None)
                self._proxy_addrs.pop(node_id, None)
            current.pop(node_id)
        # health: ping every proxy CONCURRENTLY with one shared deadline, so
        # wedged members cost one bounded wait, not a serial stall each
        if current:
            nodes_order = list(current)
            refs = [current[n].ready.remote() for n in nodes_order]
            ready, _ = ray_tpu.wait(refs, num_returns=len(refs), timeout=10)
            ready_ids = {r.id for r in ready}
            for node_id, ref in zip(nodes_order, refs):
                healthy = ref.id in ready_ids
                if healthy:
                    try:
                        ray_tpu.get(ref)
                    except Exception:
                        healthy = False
                if not healthy:
                    # KILL before respawn: the detached name must free up,
                    # and a wedged-but-listening proxy must not keep
                    # serving stale routes
                    try:
                        ray_tpu.kill(current[node_id])
                    except Exception:
                        pass
                    with self._lock:
                        self._proxies.pop(node_id, None)
                        self._proxy_addrs.pop(node_id, None)
        with self._lock:
            have = set(self._proxies)
        for node_id in alive - have:
            try:
                self._spawn_proxy(node_id)
            except Exception:
                pass  # node may have just died; next tick retries

    # ---------------------------------------------------------- deploy API

    def deploy_application(self, app_name: str, specs: List[dict], ingress: str):
        """specs: [{name, func_or_class, init_args, init_kwargs, config}],
        dependencies first (so handles in init args resolve to live replicas)."""
        with self._lock:
            prev = self._apps.get(app_name, {}).get("deployments", [])
            new_names = [s["name"] for s in specs]
            self._apps[app_name] = {"deployments": new_names, "ingress": ingress}
            # reap deployments the redeploy dropped (e.g. a fresh uniquely-
            # named DAGDriver per bind) — otherwise their replicas leak
            # until full shutdown
            orphaned = [
                n for n in prev
                if n not in new_names
                and not any(
                    n in a["deployments"]
                    for an, a in self._apps.items() if an != app_name
                )
            ]
        for n in orphaned:
            self._retire_deployment(n)
        for s in specs:
            with self._lock:
                state = self._deployments.get(s["name"])
                old: List[Any] = []
                if state is None:
                    state = _DeploymentState(
                        s["name"], s["func_or_class"], s["init_args"], s["init_kwargs"], s["config"]
                    )
                    self._deployments[s["name"]] = state
                else:  # redeploy: replace code/config, then swap replicas
                    state.func_or_class = s["func_or_class"]
                    state.init_args = s["init_args"]
                    state.init_kwargs = s["init_kwargs"]
                    state.config = s["config"]
                    state.draining = False
                    ac = state.config.autoscaling_config
                    state.target = ac.min_replicas if ac else state.config.num_replicas
                    # the OLD replica set keeps serving until the new one is
                    # ready — get_replicas()/the push channel never expose an
                    # empty set mid-redeploy
                    old = state.replicas
            if old:
                import ray_tpu

                new = []
                try:
                    new = [
                        self._spawn_replica(state)
                        for _ in range(state.target)
                    ]
                    ray_tpu.get([r.ready.remote() for r in new])
                except Exception:
                    # failed redeploy must not leak half-built replicas
                    # (each pins num_cpus) — reap them and keep the OLD set
                    # serving; the caller sees the deploy error
                    self._kill_replicas(new)
                    raise
                state.replicas = new
                self._publish_replicas(state)
                # drain -> reap: old replicas finish their in-flight
                # requests (up to the deadline) before being killed
                self._drain_then_stop(old, state.config)
            else:
                self._reconcile(state)
        return True

    def get_replicas(self, deployment_name: str):
        state = self._deployments.get(deployment_name)
        if state is None:
            raise ValueError(f"no deployment named {deployment_name!r}")
        return list(state.replicas)

    def get_ingress(self, app_name: str) -> str:
        return self._apps[app_name]["ingress"]

    def flush_telemetry(self) -> int:
        """Fan-out: every live replica and every proxy (the fleet's, and
        the one `serve.run` starts) force-pushes its flight recorder +
        metrics to the head (serve.telemetry.dump_timeline's first step).
        One shared deadline — a wedged process costs one bounded wait.
        Returns the number of processes reached."""
        import ray_tpu

        from . import _PROXY_NAME

        with self._lock:
            replicas = [
                r for s in self._deployments.values() for r in s.replicas
            ] + list(self._proxies.values())
        try:
            replicas.append(ray_tpu.get_actor(_PROXY_NAME))
        except Exception:
            pass  # no single proxy running
        refs = []
        for r in replicas:
            try:
                refs.append(r.flush_telemetry.remote())
            except Exception:
                pass
        if not refs:
            return 0
        ready, _ = ray_tpu.wait(refs, num_returns=len(refs), timeout=10)
        return len(ready)

    def list_deployments(self) -> Dict[str, dict]:
        return {
            name: {
                "target": s.target,
                "live": len(s.replicas),
                "draining": s.draining,
                "autoscaling": s.config.autoscaling_config is not None,
            }
            for name, s in self._deployments.items()
        }

    def _retire_deployment(self, name: str, wait: bool = False):
        """Drain a whole deployment out of existence: broadcast the drain
        state (handles fail fast with DeploymentUnavailableError -> proxies
        emit 503), then drain -> reap the replicas."""
        state = self._deployments.pop(name, None)
        if state is None:
            return
        state.draining = True
        victims = state.replicas
        state.replicas = []
        self._publish_replicas(state)
        self._drain_then_stop(victims, state.config, wait=wait)

    def delete_application(self, app_name: str):
        app = self._apps.pop(app_name, None)
        if not app:
            return False
        for name in app["deployments"]:
            self._retire_deployment(name)
        return True

    def graceful_shutdown(self):
        self._stop.set()
        for name in list(self._deployments):
            # wait=True: the controller actor dies right after this call
            # returns, so background drainers would be killed mid-drain
            self._retire_deployment(name, wait=True)
        self._deployments.clear()
        self._apps.clear()
        import ray_tpu

        with self._lock:
            self._proxy_fleet = False  # in-flight spawns self-abort
            self._routes.clear()
            proxies = list(self._proxies.values())
            self._proxies.clear()
            self._proxy_addrs.clear()
        for h in proxies:
            try:
                ray_tpu.get(h.stop.remote(), timeout=5)
                ray_tpu.kill(h)
            except Exception:
                pass
        return True

    # ------------------------------------------------------- reconciliation

    def _kill_replicas(self, replicas):
        import ray_tpu

        for r in replicas:
            try:
                ray_tpu.kill(r)
            except Exception:
                pass

    def _drain_then_stop(self, replicas, config: DeploymentConfig,
                         wait: bool = False):
        """Drain -> reap: close each victim's request gate, then kill it as
        soon as it reports idle — or at the drain deadline, whichever comes
        first. The caller must already have published a replica set that
        excludes the victims (no new traffic routes to them)."""
        if not replicas:
            return
        import ray_tpu

        drain_s = float(getattr(config, "graceful_shutdown_timeout_s", 10.0))
        poll_s = max(
            0.02, float(getattr(config, "graceful_shutdown_wait_loop_s", 0.1))
        )
        # 1) close the gates (best-effort, one shared deadline: a dead
        # victim must neither stall nor abort the others' drain)
        refs = []
        for r in replicas:
            try:
                # the deadline rides along so replica-side batchers
                # (@serve.batch queues, ContinuousBatchers) can bounce
                # queued work for retry and cut running generations in time
                refs.append(r.prepare_to_drain.remote(drain_s))
            except Exception:
                pass  # already dead: the drain worker reaps it
        try:
            if refs:
                ray_tpu.wait(refs, num_returns=len(refs), timeout=5)
        except Exception:
            pass

        def _drain_worker():
            from ray_tpu.exceptions import GetTimeoutError

            deadline = time.time() + drain_s
            pending = list(replicas)
            while pending and time.time() < deadline:
                still = []
                for r in pending:
                    try:
                        busy = ray_tpu.get(r.num_ongoing.remote(), timeout=2) > 0
                    except GetTimeoutError:
                        busy = True  # all actor slots occupied -> in flight
                    except Exception:
                        busy = False  # already dead: just reap
                    if busy:
                        still.append(r)
                    else:
                        self._kill_replicas([r])
                pending = still
                if pending:
                    time.sleep(poll_s)
            # deadline: force-reap stragglers (bounded drain, never hung)
            self._kill_replicas(pending)

        t = threading.Thread(target=_drain_worker, daemon=True,
                             name="serve-drain")
        t.start()
        with self._lock:
            self._drainers = [d for d in self._drainers if d.is_alive()]
            self._drainers.append(t)
        if wait:
            t.join(timeout=drain_s + 10)

    def _spawn_replica(self, state: _DeploymentState):
        import ray_tpu

        opts = dict(state.config.ray_actor_options)
        opts.setdefault("num_cpus", 1)
        # the placement rule for accelerator deployments, decided here and
        # nowhere else: a deployment class that builds a PagedDecodeEngine
        # (it says so with `runs_paged_engine`) takes one TPU chip per
        # replica when the cluster has chips, so the engine is never
        # built in a CPU-pinned worker beside an idle chip
        wants = opts.get("num_tpus") or (opts.get("resources") or {}).get("TPU")
        if (
            not wants
            and getattr(state.func_or_class, "runs_paged_engine", False)
            and ray_tpu.cluster_resources().get("TPU", 0) >= 1
        ):
            wants = opts["num_tpus"] = 1
        if wants:
            from ..util.accelerators import require_tpus

            require_tpus(wants, f"serve deployment {state.name!r}")
        ReplicaCls = ray_tpu.remote(Replica)
        return ReplicaCls.options(max_concurrency=8, **opts).remote(
            state.name, state.func_or_class, state.init_args, state.init_kwargs
        )

    def _reconcile(self, state: _DeploymentState):
        import ray_tpu

        spawned = []
        while len(state.replicas) < state.target:
            r = self._spawn_replica(state)
            state.starting.add(r._actor_id)
            spawned.append(r)
            state.replicas.append(r)
        if len(state.replicas) > state.target:
            victims = state.replicas[state.target :]
            state.replicas = state.replicas[: state.target]
            # publish the shrunken set FIRST so no new request routes to a
            # victim, then drain -> reap in the background (downscale must
            # not drop in-flight requests)
            self._publish_replicas(state)
            self._drain_then_stop(victims, state.config)
        # block until new replicas constructed
        try:
            ray_tpu.get([r.ready.remote() for r in state.replicas])
        finally:
            state.starting.difference_update(r._actor_id for r in spawned)
        self._publish_replicas(state)

    def _publish_replicas(self, state: _DeploymentState):
        """Push the live replica set + drain state to handles/proxies over
        the long-poll channel (reference: long_poll.py:68 — controller-side
        broadcast)."""
        from .long_poll import replica_channel
        from ..util import pubsub

        try:
            pubsub.publish(
                replica_channel(state.name),
                {"replicas": list(state.replicas), "draining": state.draining},
            )
        except Exception:
            pass  # handles fall back to their polling refresh

    def _autoscale(self, state: _DeploymentState):
        import ray_tpu

        ac: AutoscalingConfig = state.config.autoscaling_config
        try:
            stats = ray_tpu.get(
                [r.stats.remote() for r in state.replicas], timeout=5
            )
        except Exception:
            return
        total_ongoing = sum(s["ongoing"] for s in stats)
        # decode-aware signal: generation slots + their load, when replicas
        # host ContinuousBatchers (0 otherwise -> pure queue-depth policy)
        batch_slots = sum(s.get("batch_slots", 0) for s in stats)
        batch_load = sum(
            s.get("batch_active", 0) + s.get("batch_queued", 0) for s in stats
        )
        # paged-KV signal: block-pool saturation (0 total -> signal off)
        kv_total = sum(s.get("kv_blocks_total", 0) for s in stats)
        kv_free = sum(s.get("kv_blocks_free", 0) for s in stats)
        desired = calculate_desired_num_replicas(
            ac, total_ongoing, len(state.replicas),
            batch_slots=batch_slots, batch_load=batch_load,
            kv_blocks_total=kv_total, kv_blocks_free=kv_free,
        )
        now = time.time()
        delay = ac.upscale_delay_s if desired > state.target else ac.downscale_delay_s
        if desired != state.target and now - state.last_scale_ts >= delay:
            state.target = desired
            state.last_scale_ts = now
            self._reconcile(state)

    def _harvest_prefix_digest(self, state: _DeploymentState):
        """Fold every replica's advertised prefix digest (hint -> cached
        chain depth, from KVTransferManager via Replica.stats) into one
        bounded per-deployment LRU and publish it on serve:prefix:<name>.
        Longest advertised chain wins a hint; entries from replicas that
        left the set are dropped — the digest only ever names routable
        replicas. Runs on the ~5s heartbeat, gated on
        serve_prefix_affinity (one stats fan-out per beat)."""
        import ray_tpu

        from ray_tpu._private.config import GLOBAL_CONFIG as cfg

        from ..util import pubsub
        from .long_poll import prefix_channel

        replicas = list(state.replicas)
        if not replicas:
            return
        try:
            stats = ray_tpu.get(
                [r.stats.remote() for r in replicas], timeout=5
            )
        except Exception:
            return
        merged = state.prefix_digest
        live = {getattr(r, "_actor_id", None) for r in replicas}
        for r, s in zip(replicas, stats):
            aid = getattr(r, "_actor_id", None)
            for hint, depth in (s.get("prefix_digest") or {}).items():
                cur = merged.get(hint)
                if cur is None or cur[0] not in live or int(depth) >= cur[1]:
                    merged[hint] = (aid, int(depth))
                    merged.move_to_end(hint)
        for hint in [h for h, (aid, _) in merged.items() if aid not in live]:
            del merged[hint]
        cap = max(1, int(cfg.serve_prefix_digest_size))
        while len(merged) > cap:
            merged.popitem(last=False)
        try:
            pubsub.publish(
                prefix_channel(state.name),
                {"digest": {h: [a, d] for h, (a, d) in merged.items()}},
            )
        except Exception:
            pass  # handles just keep their last snapshot

    def get_prefix_digest(self, deployment_name: str) -> Dict[str, tuple]:
        """Pull-path mirror of the serve:prefix push (tests/debugging)."""
        state = self._deployments.get(deployment_name)
        return dict(state.prefix_digest) if state is not None else {}

    def _health_check(self, state: _DeploymentState):
        import ray_tpu

        alive, dead = [], []
        for r in state.replicas:
            if r._actor_id in state.starting:
                alive.append(r)  # still constructing: _reconcile is waiting
                continue
            try:
                ray_tpu.get(r.check_health.remote(), timeout=10)
                alive.append(r)
            except Exception:
                dead.append(r)
        if dead:
            state.replicas = alive
            # a replica that fails its check may still hold its resources
            # (a TPU replica holds the chip): reap it before replacing it
            self._kill_replicas(dead)
            self._reconcile(state)  # replace dead replicas

    def _reconcile_loop(self):
        last_heartbeat = 0.0
        while not self._stop.is_set():
            time.sleep(0.25)
            # heartbeat republish: watchers gauge push-pipeline health by
            # data recency, so a periodic re-publish both self-heals a
            # dropped publish and keeps healthy() honest (long_poll.py)
            heartbeat = time.time() - last_heartbeat >= 5.0
            if heartbeat:
                last_heartbeat = time.time()
            for state in list(self._deployments.values()):
                try:
                    if state.config.autoscaling_config is not None:
                        self._autoscale(state)
                    self._health_check(state)
                    if heartbeat:
                        self._publish_replicas(state)
                        from ray_tpu._private.config import (
                            GLOBAL_CONFIG as _cfg,
                        )

                        if _cfg.serve_prefix_affinity:
                            self._harvest_prefix_digest(state)
                except Exception:
                    pass
            if heartbeat:
                try:
                    self._ensure_proxies()
                except Exception:
                    pass
