"""Anakin: the whole PPO actor-learner loop as one jitted program.

The first Podracer shape (PAPERS.md "Podracer architectures for scalable
Reinforcement Learning"): environments live ON the accelerator next to
the learner, so an entire training iteration — act, step thousands of
envs, GAE, minibatched multi-epoch PPO update — is a single XLA program
with no host round-trips:

    pmap over devices
      └─ scan over train iterations (cfg.iters_per_step fused per call)
           └─ scan over unroll steps
                └─ vmap over envs (vec_env protocol)
           └─ scan over epochs x minibatches (grads pmean'd across devices)

Per-env episode returns are tracked inside the program (an accumulator
carried through the rollout scan; completed-episode sums emitted per
iteration), so metrics cost no extra device<->host traffic.

This is the ``PPOConfig(vectorized=True)`` fast path; the Python
``EnvRunnerGroup`` remains the fallback for envs only the Python registry
knows (rl/ppo.py dispatches). The distributed sibling is rl/sebulba.py.
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.rl.ppo import compute_gae, init_policy, mlp_apply
from ray_tpu.rl.vec_env import make_jax_env

_AXIS = "anakin_devices"


def pick_num_devices(num_envs: int, requested: int = 0) -> int:
    """Largest usable device count: envs shard evenly across devices."""
    avail = requested or jax.local_device_count()
    d = min(avail, jax.local_device_count())
    while d > 1 and num_envs % d:
        d -= 1
    return max(d, 1)


def _update(optimizer, cfg_static, params, opt_state, batch, key):
    """Minibatched multi-epoch clipped-PPO update with cross-device grad
    averaging — rl/ppo.py's ``ppo_update`` body plus ``lax.pmean`` (it
    runs inside the pmap, so the jit wrapper there does not apply)."""
    clip, vf_coef, ent_coef, num_mb, epochs = cfg_static
    B = batch["obs"].shape[0]
    mb = B // num_mb

    def loss_fn(p, mb_batch):
        logits = mlp_apply(p["pi"], mb_batch["obs"])
        values = mlp_apply(p["vf"], mb_batch["obs"])[..., 0]
        logp_all = jax.nn.log_softmax(logits)
        logp = jnp.take_along_axis(
            logp_all, mb_batch["actions"][..., None], axis=-1)[..., 0]
        ratio = jnp.exp(logp - mb_batch["logp"])
        adv = mb_batch["advantages"]
        adv = (adv - adv.mean()) / (adv.std() + 1e-8)
        pg = -jnp.minimum(ratio * adv,
                          jnp.clip(ratio, 1 - clip, 1 + clip) * adv).mean()
        vf = 0.5 * ((values - mb_batch["returns"]) ** 2).mean()
        ent = -(jnp.exp(logp_all) * logp_all).sum(-1).mean()
        return pg + vf_coef * vf - ent_coef * ent, (pg, vf, ent)

    def mb_step(carry, idx):
        p, os_ = carry
        mb_batch = jax.tree.map(lambda x: x[idx], batch)
        (_, aux), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            p, mb_batch)
        grads = jax.lax.pmean(grads, _AXIS)
        updates, os_ = optimizer.update(grads, os_, p)
        p = optax.apply_updates(p, updates)
        return (p, os_), aux

    def epoch(carry, ekey):
        # Strided minibatch assignment with a random rotation instead of
        # jax.random.permutation: the full-batch sort behind permutation
        # costs more than the grad steps themselves at these batch sizes
        # (and sorts are no friendlier on TPU). Striding spreads each
        # minibatch evenly across the [T, N] samples; the roll varies the
        # partition across epochs and iterations.
        shift = jax.random.randint(ekey, (), 0, B)
        idxs = jnp.roll(jnp.arange(num_mb * mb), shift)
        idxs = idxs.reshape(mb, num_mb).T
        return jax.lax.scan(mb_step, carry, idxs)

    keys = jax.random.split(key, epochs)
    (params, opt_state), aux = jax.lax.scan(epoch, (params, opt_state),
                                            keys)
    pg, vf, ent = jax.tree.map(lambda a: a[-1, -1], aux)
    return params, opt_state, {"policy_loss": pg, "vf_loss": vf,
                               "entropy": ent}


def make_rollout_fn(env, params_apply_pi, params_apply_vf, unroll_len: int):
    """scan(unroll) x vmap(envs) trajectory collection; shared by Anakin
    (inside pmap) and Sebulba runners (jitted on the actor's host).

    carry: (env_states, obs, ep_ret, key) with [N]-batched leaves.
    Returns the new carry, a [T, N, ...] trajectory dict, and per-rollout
    episode stats (sum of completed-episode returns, completion count).
    """

    def rollout(params, env_states, obs, ep_ret, key):
        def rollout_step(rc, _):
            env_states, obs, ep_ret, key = rc
            key, ka = jax.random.split(key)
            logits = params_apply_pi(params, obs)
            value = params_apply_vf(params, obs)
            action = jax.random.categorical(ka, logits)
            logp_all = jax.nn.log_softmax(logits)
            logp = jnp.take_along_axis(
                logp_all, action[..., None], axis=-1)[..., 0]
            env_states, next_obs, reward, done = jax.vmap(env.step)(
                env_states, action)
            ep_ret = ep_ret + reward
            done_f = done.astype(jnp.float32)
            trans = {"obs": obs, "actions": action, "logp": logp,
                     "values": value, "rewards": reward, "dones": done,
                     "ep_ret_done": ep_ret * done_f, "ep_done": done_f}
            ep_ret = jnp.where(done, 0.0, ep_ret)
            return (env_states, next_obs, ep_ret, key), trans

        (env_states, obs, ep_ret, key), traj = jax.lax.scan(
            rollout_step, (env_states, obs, ep_ret, key), None, unroll_len)
        ep_stats = {"ret_sum": traj.pop("ep_ret_done").sum(),
                    "count": traj.pop("ep_done").sum()}
        return (env_states, obs, ep_ret, key), traj, ep_stats

    return rollout


class AnakinPPO:
    """Drives the fused program; rl/ppo.py's PPO delegates here when
    ``vectorized=True`` and the env has a JAX implementation."""

    def __init__(self, cfg):
        self.cfg = cfg
        env = make_jax_env(cfg.env)
        self.env = env
        self.unroll_len = cfg.unroll_len or cfg.rollout_len
        self.num_envs = cfg.num_envs or (
            max(1, cfg.num_env_runners) * cfg.num_envs_per_runner)
        self.num_devices = pick_num_devices(
            self.num_envs, int(cfg.extra.get("anakin_devices", 0)))
        self.n_local = self.num_envs // self.num_devices
        local_batch = self.n_local * self.unroll_len
        if local_batch % cfg.num_minibatches:
            raise ValueError(
                f"per-device batch {local_batch} (= {self.n_local} envs x "
                f"{self.unroll_len} unroll) must divide num_minibatches="
                f"{cfg.num_minibatches}")
        self.iters_per_step = int(cfg.extra.get("iters_per_step", 1))

        self.optimizer = optax.adam(cfg.lr)
        params = init_policy(jax.random.PRNGKey(cfg.seed),
                             env.observation_size, env.num_actions,
                             cfg.hidden)
        opt_state = self.optimizer.init(params)
        self.params = self._replicate(params)
        self.opt_state = self._replicate(opt_state)

        static = (cfg.clip, cfg.vf_coef, cfg.ent_coef, cfg.num_minibatches,
                  cfg.num_epochs)
        apply_pi = lambda p, o: mlp_apply(p["pi"], o)
        apply_vf = lambda p, o: mlp_apply(p["vf"], o)[..., 0]
        rollout = make_rollout_fn(env, apply_pi, apply_vf, self.unroll_len)
        gamma, lam = cfg.gamma, cfg.gae_lambda
        n_local = self.n_local

        def one_iter(carry, _):
            params, opt_state, env_states, obs, ep_ret, key = carry
            (env_states, obs, ep_ret, key), traj, ep_stats = rollout(
                params, env_states, obs, ep_ret, key)
            last_values = apply_vf(params, obs)
            adv, ret = compute_gae(traj["rewards"], traj["values"],
                                   traj["dones"], last_values, gamma, lam)
            flat = lambda x: x.reshape((x.shape[0] * x.shape[1],)
                                       + x.shape[2:])
            batch = {"obs": flat(traj["obs"]),
                     "actions": flat(traj["actions"]),
                     "logp": flat(traj["logp"]),
                     "advantages": adv.reshape(-1),
                     "returns": ret.reshape(-1)}
            key, ku = jax.random.split(key)
            params, opt_state, stats = _update(self.optimizer, static,
                                               params, opt_state, batch, ku)
            stats.update(ep_stats)
            return (params, opt_state, env_states, obs, ep_ret, key), stats

        def train(params, opt_state, env_states, obs, ep_ret, key,
                  num_iters):
            (params, opt_state, env_states, obs, ep_ret, key), stats = (
                jax.lax.scan(one_iter,
                             (params, opt_state, env_states, obs, ep_ret,
                              key), None, num_iters))
            return params, opt_state, env_states, obs, ep_ret, key, stats

        def init_envs(key):
            states, obs = jax.vmap(env.reset)(jax.random.split(key, n_local))
            return states, obs

        self._train = jax.pmap(
            partial(train, num_iters=self.iters_per_step), axis_name=_AXIS)
        dev_keys = jax.random.split(jax.random.PRNGKey(cfg.seed + 1),
                                    self.num_devices)
        self.env_states, self.obs = jax.pmap(init_envs)(dev_keys)
        self.ep_ret = jnp.zeros((self.num_devices, self.n_local))
        self.key = jax.random.split(jax.random.PRNGKey(cfg.seed + 2),
                                    self.num_devices)
        self._return_window: list[float] = []

    def step(self) -> dict:
        (self.params, self.opt_state, self.env_states, self.obs,
         self.ep_ret, self.key, stats) = self._train(
            self.params, self.opt_state, self.env_states, self.obs,
            self.ep_ret, self.key)
        stats = jax.tree.map(np.asarray, stats)  # [devices, iters]
        count = float(stats["count"].sum())
        if count:
            # One aggregate per fused call keeps the same smoothed-window
            # metric shape as the EnvRunner path's per-episode list.
            self._return_window.append(float(stats["ret_sum"].sum()) / count)
            self._return_window = self._return_window[-100:]
        mean_ret = (float(np.mean(self._return_window))
                    if self._return_window else 0.0)
        steps = self.iters_per_step * self.num_envs * self.unroll_len
        return {
            "episode_return_mean": mean_ret,
            "episodes_completed": int(count),
            "num_env_steps_sampled": steps,
            "policy_loss": float(stats["policy_loss"].mean()),
            "vf_loss": float(stats["vf_loss"].mean()),
            "entropy": float(stats["entropy"].mean()),
        }

    # -- checkpoint plumbing (PPO.save/load_checkpoint delegate) ----------
    def host_params(self):
        return jax.tree.map(lambda x: np.asarray(x[0]), self.params)

    def set_params(self, params) -> None:
        self.params = self._replicate(jax.tree.map(jnp.asarray, params))

    def _replicate(self, tree):
        """One copy per device on a leading device axis, the layout pmap
        takes without a transfer."""
        devices = jax.local_devices()[: self.num_devices]
        per_device = NamedSharding(Mesh(np.array(devices), (_AXIS,)),
                                   P(_AXIS))
        return jax.tree.map(
            lambda x: jax.device_put(
                jnp.broadcast_to(x, (len(devices),) + x.shape), per_device),
            tree)
