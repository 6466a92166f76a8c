"""TD3: twin-delayed deterministic policy gradients for continuous control.

Reference parity: rllib/algorithms/td3/td3.py (TD3 = DDPG + twin critics +
target-policy smoothing + delayed actor updates; rllib implements it as a
DDPG config preset). Shares SAC's networks (the pi mean head acts as the
deterministic policy; the log_std head is simply unused), replay buffer,
and continuous rollout worker; the num_sgd_iter gradient steps run as one
jitted lax.scan with the delayed-actor mask computed inside the scan.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np
import optax

from .algorithm import Algorithm
from .config import AlgorithmConfig
from .learner import Learner, LearnerGroup, TrainState
from .models import init_sac_params, sac_pi_apply, sac_q_apply
from .replay_buffer import ReplayBuffer
from .rollout_worker import _make_env
from .sac import _ContinuousWorker
from .sample_batch import ACTIONS, DONES, NEXT_OBS, OBS, REWARDS


class TD3Config(AlgorithmConfig):
    def __init__(self):
        super().__init__(algo_class=TD3)
        self.buffer_size: int = 100_000
        self.learning_starts: int = 1_000
        self.tau: float = 0.005
        self.num_sgd_iter: int = 32
        self.policy_delay: int = 2  # actor/target update every N critic steps
        self.target_noise: float = 0.2  # smoothing noise std on target actions
        self.target_noise_clip: float = 0.5
        self.exploration_noise: float = 0.1  # behavior-policy Gaussian std
        self.lr = 1e-3
        self.minibatch_size = 256
        self.train_batch_size = 256
        self.model = {"hidden": (256, 256)}


class _TD3Worker(_ContinuousWorker):
    """Deterministic actor + fixed exploration noise (vs SAC's learned-std
    sampling); actions live squashed in [-1, 1] like SAC's."""

    def __init__(self, *args, exploration_noise: float = 0.1, **kwargs):
        super().__init__(*args, **kwargs)
        self.exploration_noise = exploration_noise

    def _action(self, mean: np.ndarray, log_std: np.ndarray) -> np.ndarray:
        noise = self._rng.standard_normal(mean.shape).astype(np.float32)
        return np.clip(np.tanh(mean) + self.exploration_noise * noise, -1.0, 1.0)


class TD3Learner(Learner):
    def __init__(
        self,
        obs_dim: int,
        act_dim: int,
        hidden=(256, 256),
        lr: float = 1e-3,
        gamma: float = 0.99,
        tau: float = 0.005,
        policy_delay: int = 2,
        target_noise: float = 0.2,
        target_noise_clip: float = 0.5,
        num_sgd_iter: int = 32,
        minibatch_size: int = 256,
        seed: int = 0,
    ):
        super().__init__(config=None)
        self.gamma = gamma
        self.tau = tau
        self.policy_delay = policy_delay
        self.target_noise = target_noise
        self.target_noise_clip = target_noise_clip
        self.num_sgd_iter = num_sgd_iter
        self.minibatch_size = minibatch_size
        self.optimizer = optax.adam(lr)
        nets = init_sac_params(jax.random.PRNGKey(seed), obs_dim, act_dim, hidden)
        params = {
            "nets": nets,
            "target": jax.tree_util.tree_map(jnp.copy, nets),
            "it": jnp.zeros((), jnp.int32),
        }
        self.state = TrainState(
            params=params,
            opt_state=self.optimizer.init(nets),
            rng=jax.random.PRNGKey(seed + 1),
        )
        self._update_fn = None

    def _losses(self, nets, target, mb, rng, actor_mask):
        # -- critic: target-policy smoothing --
        mean_t, _ = sac_pi_apply(target, mb[NEXT_OBS])
        noise = jnp.clip(
            self.target_noise * jax.random.normal(rng, mean_t.shape),
            -self.target_noise_clip,
            self.target_noise_clip,
        )
        a_next = jnp.clip(jnp.tanh(mean_t) + noise, -1.0, 1.0)
        q1t, q2t = sac_q_apply(target, mb[NEXT_OBS], a_next)
        y = mb[REWARDS] + self.gamma * (1.0 - mb[DONES]) * jax.lax.stop_gradient(
            jnp.minimum(q1t, q2t)
        )
        q1, q2 = sac_q_apply(nets, mb[OBS], mb[ACTIONS])
        critic_loss = 0.5 * (jnp.mean((q1 - y) ** 2) + jnp.mean((q2 - y) ** 2))

        # -- delayed deterministic actor: maximize Q1(s, pi(s)) --
        mean, _ = sac_pi_apply(nets, mb[OBS])
        a_pi = jnp.tanh(mean)
        q1p, _ = sac_q_apply(jax.lax.stop_gradient(nets), mb[OBS], a_pi)
        actor_loss = -jnp.mean(q1p)

        total = critic_loss + actor_mask * actor_loss
        return total, {
            "critic_loss": critic_loss,
            "actor_loss": actor_loss,
            "mean_q": jnp.mean(q1),
        }

    def _build_update(self):
        optimizer = self.optimizer
        tau = self.tau
        delay = self.policy_delay
        losses = self._losses

        def step(carry, inp):
            nets, target, opt_state, it = carry
            mb, rng = inp
            actor_mask = (it % delay == 0).astype(jnp.float32)
            (_, metrics), grads = jax.value_and_grad(losses, has_aux=True)(
                nets, target, mb, rng, actor_mask
            )
            updates, opt_state = optimizer.update(grads, opt_state, nets)
            nets = optax.apply_updates(nets, updates)
            # polyak targets on the same delayed schedule as the actor
            # (Fujimoto et al. 2018, alg. 1)
            step_tau = tau * actor_mask
            target = jax.tree_util.tree_map(
                lambda t, o: (1.0 - step_tau) * t + step_tau * o, target, nets
            )
            return (nets, target, opt_state, it + 1), metrics

        def update(state: TrainState, minibatches):
            p = state.params
            rng, sub = jax.random.split(state.rng)
            n = jax.tree_util.tree_leaves(minibatches)[0].shape[0]
            rngs = jax.random.split(sub, n)
            (nets, target, opt_state, it), metrics = jax.lax.scan(
                step, (p["nets"], p["target"], state.opt_state, p["it"]), (minibatches, rngs)
            )
            metrics = jax.tree_util.tree_map(lambda m: m.mean(), metrics)
            params = {"nets": nets, "target": target, "it": it}
            return TrainState(params, opt_state, rng), metrics

        # NOT donated: on the CPU backend THIS executable
        # comes back from the persistent compilation cache (tests/conftest.py)
        # with its donated-input aliasing broken — nets/target outputs return
        # the unmodified inputs (targets never move) while `it` and the
        # metrics are correct. A fresh compile is right; only the
        # deserialized executable is wrong, so the failure appeared only on
        # cache-hit runs. The fix stays LOCAL because the corruption is:
        # every other donated jit (other learners, the paged-decode pools)
        # is exercised with token/numeric-exactness assertions on warm-cache
        # runs and none reproduces it — dropping donation fleet-wide would
        # trade real decode HBM for a failure only ever observed here. The
        # signature to watch for elsewhere: a cache-hit-only failure where a
        # donated output equals its unmodified input. The nets here are
        # tiny — donation bought nothing.
        return jax.jit(update)

    def update(self, buffer: ReplayBuffer) -> Dict[str, float]:
        samples = [buffer.sample(self.minibatch_size) for _ in range(self.num_sgd_iter)]
        minibatches = {
            k: jnp.asarray(np.stack([s[k] for s in samples])) for k in samples[0].keys()
        }
        if self._update_fn is None:
            self._update_fn = self._build_update()
        self.state, metrics = self._update_fn(self.state, minibatches)
        return {k: float(v) for k, v in metrics.items()}

    def get_weights(self):
        return jax.device_get(self.state.params["nets"])

    def set_weights(self, weights):
        p = dict(self.state.params)
        p["nets"] = jax.device_put(weights)
        self.state = self.state._replace(params=p)


class TD3(Algorithm):
    _config_class = TD3Config
    _learner_class = TD3Learner  # hook: DDPG swaps in its single-critic losses

    def _worker_cls(self):
        return _TD3Worker

    def _worker_kwargs(self):
        cfg = self.algo_config
        return dict(
            env_spec=cfg.env,
            num_envs=cfg.num_envs_per_worker,
            rollout_fragment_length=cfg.rollout_fragment_length,
            policy_hidden=tuple(cfg.model.get("hidden", (256, 256))),
            exploration_noise=cfg.exploration_noise,
        )

    def _build_learner(self) -> LearnerGroup:
        cfg = self.algo_config
        env = _make_env(cfg.env)
        obs_dim = int(np.prod(env.observation_space.shape))
        act_dim = int(np.prod(env.action_space.shape))
        env.close()
        self.replay = ReplayBuffer(cfg.buffer_size, seed=cfg.seed)

        learner_cls = self._learner_class

        def factory():
            return learner_cls(
                obs_dim=obs_dim,
                act_dim=act_dim,
                hidden=tuple(cfg.model.get("hidden", (256, 256))),
                lr=cfg.lr,
                gamma=cfg.gamma,
                tau=cfg.tau,
                policy_delay=cfg.policy_delay,
                target_noise=cfg.target_noise,
                target_noise_clip=cfg.target_noise_clip,
                num_sgd_iter=cfg.num_sgd_iter,
                minibatch_size=cfg.minibatch_size,
                seed=cfg.seed,
            )

        return LearnerGroup(factory, remote=False)

    def training_step(self) -> Dict[str, Any]:
        cfg = self.algo_config
        collected = 0
        while collected < cfg.train_batch_size:
            batch = self.workers.sample()
            self.replay.add(batch)
            collected += len(batch)
            self._timesteps_total += len(batch)
        metrics: Dict[str, Any] = {"replay_size": len(self.replay)}
        if len(self.replay) >= cfg.learning_starts:
            metrics.update(self.learner_group._learner.update(self.replay))
            self.workers.set_weights(self.learner_group.get_weights())
        metrics["num_env_steps_sampled_this_iter"] = collected
        return metrics
