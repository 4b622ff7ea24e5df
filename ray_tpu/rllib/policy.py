"""Policy: parameters + jitted action/update computations.

Parity: reference ``rllib/policy/policy.py`` (:166) and
``torch_policy_v2.py`` — ``compute_actions``, ``learn_on_batch``,
``postprocess_trajectory``, weight get/set.  jax-native design: the
model forward, action sampling and the SGD update are each ONE jitted
XLA program with static shapes (fixed env-batch and minibatch sizes), so
on TPU the learner is a single compiled step and the sampler does one
small H2D/D2H pair per env tick.  Multi-chip learners shard the same
update via pjit over a mesh (see ``algorithms/`` configs).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from ray_tpu.core import device_telemetry as _dt
from ray_tpu.rllib.env import Box, Discrete
from ray_tpu.rllib.models import Categorical, DiagGaussian, FCNet
from ray_tpu.rllib.postprocessing import compute_gae
from ray_tpu.rllib.sample_batch import SampleBatch


def rescale_actions(act: np.ndarray, low: np.ndarray, high: np.ndarray
                    ) -> np.ndarray:
    """tanh-scale [-1, 1] -> env scale (no-op for unbounded spaces)."""
    if np.all(np.isfinite(low)) and np.all(np.isfinite(high)):
        return (low + (act + 1.0) * 0.5 * (high - low)).astype(np.float32)
    return act


def normalize_actions(act: np.ndarray, low: np.ndarray, high: np.ndarray
                      ) -> np.ndarray:
    """Env scale -> tanh-scale [-1, 1]: actors/critics operate entirely
    in [-1, 1]; replay stores what the env consumed."""
    if np.all(np.isfinite(low)) and np.all(np.isfinite(high)):
        return (2.0 * (act - low) / (high - low) - 1.0).astype(np.float32)
    return act


class JaxPolicy:
    """Base class; algorithms override :meth:`loss` (and optionally
    :meth:`learn_on_batch` for multi-epoch schemes)."""

    def __init__(self, observation_space, action_space,
                 config: Dict[str, Any]):
        self.observation_space = observation_space
        self.action_space = action_space
        self.config = config
        if isinstance(action_space, Discrete):
            self.dist = Categorical
            num_outputs = action_space.n
        elif isinstance(action_space, Box):
            self.dist = DiagGaussian
            num_outputs = 2 * int(np.prod(action_space.shape))
        else:
            raise ValueError(f"unsupported action space {action_space!r}")
        model_cfg = config.get("model", {})
        self.recurrent = bool(model_cfg.get("use_lstm", False)
                              or model_cfg.get("use_attention", False))
        if model_cfg.get("use_attention", False):
            from ray_tpu.rllib.models import AttentionNet

            self.model = AttentionNet(
                num_outputs=num_outputs,
                dim=int(model_cfg.get("attention_dim", 64)),
                num_layers=int(model_cfg.get(
                    "attention_num_transformer_units", 2)),
                memory_len=int(model_cfg.get("attention_memory_inference",
                                             16)),
                heads=int(model_cfg.get("attention_num_heads", 4)),
            )
        elif self.recurrent:
            from ray_tpu.rllib.models import LSTMNet

            self.model = LSTMNet(
                num_outputs=num_outputs,
                cell_size=int(model_cfg.get("lstm_cell_size", 64)),
                embed_size=int(model_cfg.get("fcnet_hiddens",
                                             (64,))[-1]),
                activation=model_cfg.get("fcnet_activation", "tanh"),
            )
        else:
            self.model = FCNet(
                num_outputs=num_outputs,
                hiddens=tuple(model_cfg.get("fcnet_hiddens", (64, 64))),
                activation=model_cfg.get("fcnet_activation", "tanh"),
                vf_share_layers=bool(model_cfg.get("vf_share_layers",
                                                   False)),
            )
        # samplers pin to host CPU (config "_device": "cpu") so rollout
        # actor fleets never contend for the TPU; the
        # learner keeps the default (accelerator) backend
        if config.get("_device") == "cpu":
            self._device = jax.devices("cpu")[0]
        else:
            self._device = None
        with self._on_device():
            self._rng = jax.random.PRNGKey(int(config.get("seed", 0) or 0))
            self._rng, init_rng = jax.random.split(self._rng)
            obs_dim = int(np.prod(observation_space.shape))
            if self.recurrent:
                dummy = jnp.zeros((1, 1, obs_dim), jnp.float32)
                self.params = self.model.init(
                    init_rng, dummy, self.model.initial_carry(1))
            else:
                dummy = jnp.zeros((1, obs_dim), jnp.float32)
                self.params = self.model.init(init_rng, dummy)
            self.opt = self._make_optimizer()
            self.opt_state = self.opt.init(self.params)
        self._np_rng = np.random.default_rng(int(config.get("seed", 0) or 0))

        model = self.model
        dist = self.dist

        if self.recurrent:
            @jax.jit
            def _act_rnn(params, obs, c, h, rng):
                # key split lives INSIDE the jit (a separate host-side
                # threefry call per env tick dominated tiny-model
                # sampling); the next key returns as a device array
                rng, next_rng = jax.random.split(rng)
                logits, vf, (c2, h2) = model.apply(params, obs[:, None],
                                                   (c, h))
                dist_inputs = logits[:, 0]
                actions = dist.sample(dist_inputs, rng)
                logp = dist.logp(dist_inputs, actions)
                return actions, logp, vf[:, 0], dist_inputs, c2, h2, \
                    next_rng

            @jax.jit
            def _act_rnn_greedy(params, obs, c, h):
                logits, vf, (c2, h2) = model.apply(params, obs[:, None],
                                                   (c, h))
                dist_inputs = logits[:, 0]
                if dist is Categorical:
                    actions = jnp.argmax(dist_inputs, axis=-1)
                else:
                    actions, _ = jnp.split(dist_inputs, 2, axis=-1)
                return actions, vf[:, 0], c2, h2

            @jax.jit
            def _values_rnn(params, obs, c, h):
                _, vf, _ = model.apply(params, obs[:, None], (c, h))
                return vf[:, 0]

            self._act_rnn = _dt.instrument_step(
                _act_rnn, name="jax_policy.act_rnn")
            self._act_rnn_greedy = _dt.instrument_step(
                _act_rnn_greedy, name="jax_policy.act_rnn_greedy")
            self._values_rnn = _dt.instrument_step(
                _values_rnn, name="jax_policy.values_rnn")
            #: set by the sampler before postprocess_trajectory so the
            #: truncation bootstrap evaluates V(s_last | carry)
            self._bootstrap_state: Optional[Tuple] = None
        else:
            @jax.jit
            def _act(params, obs, rng):
                # split inside the jit; next key stays on device
                rng, next_rng = jax.random.split(rng)
                dist_inputs, vf = model.apply(params, obs)
                actions = dist.sample(dist_inputs, rng)
                logp = dist.logp(dist_inputs, actions)
                return actions, logp, vf, dist_inputs, next_rng

            @jax.jit
            def _act_greedy(params, obs):
                dist_inputs, vf = model.apply(params, obs)
                if dist is Categorical:
                    actions = jnp.argmax(dist_inputs, axis=-1)
                else:
                    actions, _ = jnp.split(dist_inputs, 2, axis=-1)
                return actions, vf

            @jax.jit
            def _values(params, obs):
                _, vf = model.apply(params, obs)
                return vf

            self._act = _dt.instrument_step(_act, name="jax_policy.act")
            self._act_greedy = _dt.instrument_step(
                _act_greedy, name="jax_policy.act_greedy")
            self._values = _dt.instrument_step(
                _values, name="jax_policy.values")
        self._update = _dt.instrument_step(
            jax.jit(self._update_impl), name="jax_policy.update")
        self._grads = _dt.instrument_step(
            jax.jit(self._grads_impl), name="jax_policy.grads")
        self._apply = _dt.instrument_step(
            jax.jit(self._apply_impl), name="jax_policy.apply")

    def _on_device(self):
        if self._device is None:
            return contextlib.nullcontext()
        return jax.default_device(self._device)

    # -- overridables ---------------------------------------------------
    def _make_optimizer(self) -> optax.GradientTransformation:
        lr = float(self.config.get("lr", 5e-4))
        clip = float(self.config.get("grad_clip", 0) or 0)
        tx = optax.adam(lr)
        if clip:
            tx = optax.chain(optax.clip_by_global_norm(clip), tx)
        return tx

    def loss(self, params, batch: Dict[str, jnp.ndarray]
             ) -> Tuple[jnp.ndarray, Dict[str, jnp.ndarray]]:
        raise NotImplementedError

    # -- recurrent surface ----------------------------------------------
    def get_initial_state(self, batch: int) -> Tuple[np.ndarray, ...]:
        """Zero recurrent carry for ``batch`` parallel envs (reference
        ``Policy.get_initial_state``) — LSTM (c, h) or attention
        (memory, count); both are pairs of per-env arrays."""
        return tuple(np.asarray(c) for c in
                     self.model.initial_carry(batch))

    def compute_actions_rnn(self, obs: np.ndarray, state: Tuple,
                            explore: bool = True):
        """One env tick with carry: returns (actions, state_out, extras);
        extras carry the *input* state columns for sequence training."""
        with self._on_device():
            obs_j = jnp.asarray(obs, jnp.float32)
            c, h = (jnp.asarray(state[0]), jnp.asarray(state[1]))
            if explore:
                actions, logp, vf, _, c2, h2, self._rng = self._act_rnn(
                    self.params, obs_j, c, h, self._rng)
                extras = {SampleBatch.ACTION_LOGP: np.asarray(logp),
                          SampleBatch.VF_PREDS: np.asarray(vf),
                          "state_in_c": np.asarray(state[0]),
                          "state_in_h": np.asarray(state[1])}
            else:
                actions, vf, c2, h2 = self._act_rnn_greedy(
                    self.params, obs_j, c, h)
                extras = {SampleBatch.VF_PREDS: np.asarray(vf),
                          "state_in_c": np.asarray(state[0]),
                          "state_in_h": np.asarray(state[1])}
            # writable copies: the sampler zeroes per-env rows on resets
            return (np.asarray(actions), (np.array(c2), np.array(h2)),
                    extras)

    # -- acting ---------------------------------------------------------
    def compute_actions(self, obs: np.ndarray, explore: bool = True
                        ) -> Tuple[np.ndarray, Dict[str, np.ndarray]]:
        with self._on_device():
            obs = jnp.asarray(obs, jnp.float32)
            if explore:
                actions, logp, vf, dist_inputs, self._rng = self._act(
                    self.params, obs, self._rng)
                extras = {SampleBatch.ACTION_LOGP: np.asarray(logp),
                          SampleBatch.VF_PREDS: np.asarray(vf)}
            else:
                actions, vf = self._act_greedy(self.params, obs)
                extras = {SampleBatch.VF_PREDS: np.asarray(vf)}
            return np.asarray(actions), extras

    def compute_values(self, obs: np.ndarray) -> np.ndarray:
        with self._on_device():
            return np.asarray(self._values(self.params,
                                           jnp.asarray(obs, jnp.float32)))

    # -- learning -------------------------------------------------------
    def _update_impl(self, params, opt_state, batch):
        (loss, stats), grads = jax.value_and_grad(
            self.loss, has_aux=True)(params, batch)
        updates, opt_state = self.opt.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        stats = dict(stats)
        stats["total_loss"] = loss
        stats["grad_gnorm"] = optax.global_norm(grads)
        return params, opt_state, stats

    def _grads_impl(self, params, batch):
        (loss, stats), grads = jax.value_and_grad(
            self.loss, has_aux=True)(params, batch)
        stats = dict(stats)
        stats["total_loss"] = loss
        return grads, stats

    def _apply_impl(self, params, opt_state, grads):
        updates, opt_state = self.opt.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state

    def compute_gradients(self, batch: SampleBatch):
        """Gradients without applying them (reference
        ``Policy.compute_gradients`` — the A3C path where workers compute
        grads and the driver applies them asynchronously)."""
        with self._on_device():
            grads, stats = self._grads(self.params,
                                       self._device_batch(batch))
            grads = jax.tree_util.tree_map(np.asarray, grads)
        return grads, {k: float(v) for k, v in stats.items()}

    def apply_gradients(self, grads) -> None:
        with self._on_device():
            grads = jax.tree_util.tree_map(jnp.asarray, grads)
            self.params, self.opt_state = self._apply(
                self.params, self.opt_state, grads)

    def _device_batch(self, batch: SampleBatch) -> Dict[str, jnp.ndarray]:
        return {k: jnp.asarray(v) for k, v in batch.items()
                if v.dtype != object}

    def learn_on_batch(self, batch: SampleBatch) -> Dict[str, float]:
        """One SGD step on the whole batch; PPO-style algorithms override
        with epoch/minibatch schedules."""
        with self._on_device():
            self.params, self.opt_state, stats = self._update(
                self.params, self.opt_state, self._device_batch(batch))
        return {k: float(v) for k, v in stats.items()}

    # -- trajectory postprocessing -------------------------------------
    def postprocess_trajectory(self, batch: SampleBatch,
                               last_obs: Optional[np.ndarray] = None,
                               truncated: bool = False) -> SampleBatch:
        """Default: GAE advantages (reference ``postprocessing.py``)."""
        if truncated and last_obs is not None:
            if self.recurrent:
                state = self._bootstrap_state or self.get_initial_state(1)
                with self._on_device():
                    last_value = float(self._values_rnn(
                        self.params, jnp.asarray(last_obs[None],
                                                 jnp.float32),
                        jnp.asarray(state[0]), jnp.asarray(state[1]))[0])
            else:
                last_value = float(self.compute_values(last_obs[None])[0])
        else:
            last_value = 0.0
        return compute_gae(
            batch, last_value,
            gamma=float(self.config.get("gamma", 0.99)),
            lambda_=float(self.config.get("lambda_", 0.95)),
            use_gae=bool(self.config.get("use_gae", True)))

    # -- weights --------------------------------------------------------
    def get_weights(self):
        return jax.tree_util.tree_map(np.asarray, self.params)

    def set_weights(self, weights) -> None:
        with self._on_device():
            self.params = jax.tree_util.tree_map(jnp.asarray, weights)

    def get_state(self) -> Dict[str, Any]:
        return {"weights": self.get_weights(),
                "opt_state": jax.tree_util.tree_map(np.asarray,
                                                    self.opt_state)}

    def set_state(self, state: Dict[str, Any]) -> None:
        self.set_weights(state["weights"])
        self.opt_state = jax.tree_util.tree_map(
            jnp.asarray, state["opt_state"],
            is_leaf=lambda x: isinstance(x, np.ndarray))
