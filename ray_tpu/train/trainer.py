"""JaxTrainer: the DataParallelTrainer equivalent.

Reference parity: train/data_parallel_trainer.py:58 + BackendExecutor
(train/_internal/backend_executor.py:104) + WorkerGroup (worker_group.py:193).
Differences, by TPU design:
  - one worker actor per HOST (not per device); the worker's train loop
    builds a Mesh over the host's chips (or the whole slice when
    jax.distributed is enabled) and compiles ONE SPMD program.
  - the backend seam that runs dist.init_process_group in the reference
    (train/torch/config.py:113) here passes coordinator info for
    jax.distributed.initialize — after which GSPMD owns every collective.
"""

from __future__ import annotations

import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional

logger = logging.getLogger(__name__)

import ray_tpu
from ray_tpu.util import placement_group, PlacementGroupSchedulingStrategy

from .config import RunConfig, ScalingConfig
from .session import TrainContext, _set_context


@dataclass
class Result:
    metrics: Dict[str, Any] = field(default_factory=dict)
    metrics_history: List[Dict[str, Any]] = field(default_factory=list)
    checkpoint: Optional[Any] = None
    error: Optional[Exception] = None


class TrainWorker:
    """Actor hosting one training process (one host's SPMD shard)."""

    def __init__(self, rank: int, world_size: int):
        self.rank = rank
        self.world_size = world_size
        self.ctx: Optional[TrainContext] = None
        self._done = threading.Event()
        self._ret = None
        self._err: Optional[Exception] = None

    def ready(self):
        return True

    def get_coordinator_address(self) -> str:
        """Rank-0 upcall: a `host:port` the REST of the gang can dial for
        jax.distributed rendezvous. Resolved AFTER placement, on the worker
        itself — the reference does exactly this for the torch rendezvous
        (train/torch/config.py:113-170 master addr/port queried from worker
        0; backend_executor.py:342) — a driver-picked loopback address
        cannot form a mesh across hosts."""
        import socket

        from ray_tpu._private.head import _advertise_host

        host = _advertise_host("0.0.0.0")  # this node's outbound/routable IP
        s = socket.socket()
        s.bind(("0.0.0.0", 0))
        port = s.getsockname()[1]
        s.close()  # jax.distributed binds it next; standard rendezvous race
        return f"{host}:{port}"

    def run(
        self,
        train_fn: Callable,
        config: Dict[str, Any],
        datasets=None,
        checkpoint=None,
        coordinator: Optional[str] = None,
        num_slices: int = 1,
        virtual_stages_per_device: int = 1,
    ):
        dist_inited = False
        if self.world_size > 1 and coordinator:
            import jax

            from ray_tpu._private.config import GLOBAL_CONFIG as gcfg

            kwargs = dict(
                coordinator_address=coordinator,
                num_processes=self.world_size,
                process_id=self.rank,
            )
            hb_s = int(gcfg.train_dist_heartbeat_timeout_s)
            if hb_s > 0:
                # bound gang peer-death detection: jax's default budget
                # (100s) parks every SURVIVING rank that long at the
                # shutdown barrier when a gang member dies hard — the
                # latency floor of the whole gang-restart path. Heartbeats
                # run on a C++ thread, so a long jit compile cannot miss
                # them.
                kwargs["heartbeat_timeout_seconds"] = hb_s
            jax.distributed.initialize(**kwargs)
            dist_inited = True
        self.ctx = TrainContext(
            world_rank=self.rank,
            world_size=self.world_size,
            local_rank=0,
            config=config or {},
            dataset_shards=datasets or {},
            checkpoint=checkpoint,
            num_slices=num_slices,
            virtual_stages_per_device=virtual_stages_per_device,
        )
        _set_context(self.ctx)
        try:
            import inspect

            sig = inspect.signature(train_fn)
            self._ret = train_fn(config) if len(sig.parameters) >= 1 else train_fn()
            return self._ret
        except BaseException as e:
            self._err = e
            raise
        finally:
            self.ctx.done.set()
            if dist_inited:
                import jax

                try:  # leave the process reusable for a gang-restart attempt
                    jax.distributed.shutdown()
                except Exception:
                    pass

    def next_results(self, max_items: int = 100):
        """Drain queued session.report() payloads (non-blocking)."""
        out = []
        if self.ctx is None:
            return out, False
        while len(out) < max_items:
            try:
                out.append(self.ctx.results.get_nowait())
            except Exception:
                break
        return out, self.ctx.done.is_set()


class JaxTrainer:
    def __init__(
        self,
        train_loop_per_worker: Callable,
        *,
        train_loop_config: Optional[Dict[str, Any]] = None,
        scaling_config: Optional[ScalingConfig] = None,
        run_config: Optional[RunConfig] = None,
        datasets: Optional[Dict[str, Any]] = None,
        resume_from_checkpoint=None,
    ):
        self._train_fn = train_loop_per_worker
        self._config = train_loop_config or {}
        self.scaling_config = scaling_config or ScalingConfig()
        self.run_config = run_config or RunConfig()
        self._datasets = datasets or {}
        self._resume_checkpoint = resume_from_checkpoint

    def fit(self) -> Result:
        """Run the training gang; on worker failure, restart the WHOLE gang
        from the last reported checkpoint up to
        run_config.failure_config.max_failures times (SURVEY §7.2: pjit
        programs are SPMD gangs — all-or-nothing restart from checkpoint is
        the tractable elastic-training v1; reference analogue: Tune
        restarting a trial from its checkpoint under FailureConfig)."""
        fc = self.run_config.failure_config
        resume = self._resume_checkpoint
        history: List[Dict[str, Any]] = []
        failures = 0
        while True:
            try:
                result = self._fit_attempt(resume)
            except Exception as e:  # setup-phase failure (spawn/pg/ready)
                result = Result(error=e)
            # keep the full metric history across restarts
            history.extend(result.metrics_history)
            result.metrics_history = list(history)
            if result.error is None or failures >= fc.max_failures:
                return result
            failures += 1
            resume = result.checkpoint if result.checkpoint is not None else resume
            logger.warning(
                "training gang failed (%r); restart %d/%d from %s",
                result.error, failures, fc.max_failures,
                "last checkpoint" if resume is not None else "scratch",
            )

    def _fit_attempt(self, resume_checkpoint) -> Result:
        """One gang attempt. Setup failures raise (fit() settles them into
        a Result); workers and the placement group are ALWAYS torn down —
        a leaked half-built gang would starve the restart attempt."""
        pg_box: List[Any] = []
        workers: List[Any] = []
        try:
            return self._fit_attempt_inner(resume_checkpoint, pg_box.append, workers)
        finally:
            for w in workers:
                try:
                    ray_tpu.kill(w)
                except Exception:
                    pass
            if pg_box:
                from ray_tpu.util import remove_placement_group

                try:
                    remove_placement_group(pg_box[0])
                except Exception:
                    pass

    def _fit_attempt_inner(self, resume_checkpoint, set_pg, workers) -> Result:
        sc = self.scaling_config
        n = sc.num_workers
        res = sc.worker_resources()
        if res.get("TPU"):
            from ray_tpu.util.accelerators import require_tpus

            require_tpus(res["TPU"], "JaxTrainer's ScalingConfig")
        strategy = None
        if n > 1:
            pg = placement_group([dict(res) for _ in range(n)], strategy=sc.placement_strategy)
            set_pg(pg)
            if not pg.wait(120):
                raise RuntimeError(
                    f"placement group for {n} training workers not placeable "
                    f"within 120s (bundles: {res})"
                )
            strategy = PlacementGroupSchedulingStrategy(placement_group=pg)

        WorkerCls = ray_tpu.remote(TrainWorker)
        opts: Dict[str, Any] = {
            "num_cpus": res.get("CPU", 1),
            "max_concurrency": 2,  # run + next_results pump
        }
        if res.get("TPU"):
            opts["num_tpus"] = res["TPU"]
        if strategy is not None:
            opts["scheduling_strategy"] = strategy
        extra = {k: v for k, v in res.items() if k not in ("CPU", "TPU")}
        if extra:
            opts["resources"] = extra
        env_vars = dict(sc.env_vars)
        if sc.dcn_grad_compression is not None:
            # pin the gang-wide compression mode: every host must compile
            # the same step (the int8 path changes the opt_state pytree)
            env_vars.setdefault(
                "RAY_TPU_TRAIN_DCN_GRAD_COMPRESSION", sc.dcn_grad_compression
            )
        if env_vars:
            opts["runtime_env"] = {"env_vars": env_vars}

        workers.extend(
            WorkerCls.options(**opts).remote(rank, n) for rank in range(n)
        )
        # timeout: unschedulable/crashing workers must raise into the
        # restart loop, not block setup forever
        ray_tpu.get([w.ready.remote() for w in workers], timeout=180)

        # rendezvous: rank-0 worker (placed!) picks the coordinator address
        # on ITS node and the driver broadcasts it to the gang
        coordinator = None
        if n > 1:
            coordinator = ray_tpu.get(
                workers[0].get_coordinator_address.remote(), timeout=60
            )

        # shard datasets across workers (streaming split)
        def shard_for(rank):
            out = {}
            for name, ds in self._datasets.items():
                if hasattr(ds, "split_at"):
                    out[name] = ds.split_at(rank, n)
                else:
                    out[name] = ds
            return out

        run_refs = [
            w.run.remote(
                self._train_fn, self._config, shard_for(i), resume_checkpoint,
                coordinator, sc.num_slices, sc.virtual_stages_per_device,
            )
            for i, w in enumerate(workers)
        ]

        result = Result()
        done = False
        try:
            while not done:
                reports, rank0_done = ray_tpu.get(workers[0].next_results.remote())
                for rep in reports:
                    result.metrics_history.append(rep["metrics"])
                    result.metrics = rep["metrics"]
                    if rep.get("checkpoint") is not None:
                        result.checkpoint = rep["checkpoint"]
                if rank0_done:
                    done = True
                else:
                    ready, _ = ray_tpu.wait(run_refs, num_returns=len(run_refs), timeout=0.2)
                    if len(ready) == len(run_refs):
                        done = True
        except Exception as e:  # a worker died mid-run: settle the error so
            result.error = e  # fit()'s gang-restart loop can act on it
        # surface worker errors (rank 0 first)
        if result.error is None:
            try:
                ray_tpu.get(run_refs)
            except Exception as e:  # noqa: BLE001
                result.error = e
        # final drain (best-effort: the pump actor may be gone)
        try:
            reports, _ = ray_tpu.get(workers[0].next_results.remote())
            for rep in reports:
                result.metrics_history.append(rep["metrics"])
                result.metrics = rep["metrics"]
                if rep.get("checkpoint") is not None:
                    result.checkpoint = rep["checkpoint"]
        except Exception:
            pass
        # worker + placement-group teardown happens in _fit_attempt's
        # finally (covers setup failures too)
        return result
