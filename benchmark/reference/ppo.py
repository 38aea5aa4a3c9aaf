"""Plain float32 reference of one PPO iteration's LEARNING half: behaviour
log-probs and values from the plain forward, numpy GAE, then the
epochs x minibatches of clipped-surrogate updates with global-norm clipping
and Adam, written out by hand (no optax, no flax, no program import).

The trajectory (observations, masks, the actions taken, rewards, dones) is
data; everything computed FROM it is computed here. Minibatch membership
follows the semantics the configuration states: per epoch one split of the
step's key, one whole-batch permutation, contiguous blocks of it.
"""
from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from . import gae as gae_ref
from .forward import entropy, forward, forward_blocks, log_prob


class Hyper(NamedTuple):
    """What the configuration states about PPO (read from the program's
    resolved config by the driver, as numbers)."""
    gamma: float
    gae_lambda: float
    clip_eps: float
    vf_coef: float
    ent_coef: float
    lr: float
    max_grad_norm: float
    n_epochs: int
    n_minibatches: int
    adam_b1: float = 0.9
    adam_b2: float = 0.999
    adam_eps: float = 1e-5


# Faults a control can plant in the reference put in the program's place
# (``benchmark/control.py``): the update at half the stated learning rate;
# the update over the first half of the batch only, minibatch size kept
# (half the optimizer steps); every minibatch thinned to its first half (a
# change of sampling only, read to show that no number sees it at size).
FAULTS = ("half_lr", "half_batch", "half_rows")


class AdamState(NamedTuple):
    count: jax.Array
    mu: dict
    nu: dict


def adam_init(params) -> AdamState:
    z = lambda: jax.tree.map(jnp.zeros_like, params)
    return AdamState(jnp.zeros((), jnp.int32), z(), z())


def _loss_sum(ref, params, blk, hp: Hyper, quant):
    """Sums (not means) over one block of rows, so blocks add up."""
    obs, mask, action, logp_old, v_old, adv, ret = blk
    logits, value = forward(ref, params, obs, mask, quant)
    logp = log_prob(logits, action)
    ratio = jnp.exp(logp - logp_old)
    pg = -jnp.minimum(ratio * adv,
                      jnp.clip(ratio, 1 - hp.clip_eps, 1 + hp.clip_eps) * adv)
    v_clip = v_old + jnp.clip(value - v_old, -hp.clip_eps, hp.clip_eps)
    vl = 0.5 * jnp.maximum((value - ret) ** 2, (v_clip - ret) ** 2)
    ent = entropy(logits)
    return jnp.sum(pg + hp.vf_coef * vl - hp.ent_coef * ent)


def make_update(ref, hp: Hyper, block: int, quant=None, fault=None):
    """``ref``: the configuration's reference as
    ``benchmark.common.Reference`` resolved it. Jittable ``(params, adam, data[B,...], key) -> (params', adam',
    losses[n_epochs, n_minibatches])``; gradients of a minibatch are
    accumulated over blocks of ``block`` rows. ``fault``: one of
    ``FAULTS``, planted for a control."""
    if fault == "half_lr":
        hp = hp._replace(lr=hp.lr / 2)

    def minibatch(carry, mb):
        params, adam = carry
        if fault == "half_rows":
            mb = jax.tree.map(lambda x: x[:x.shape[0] // 2], mb)
        n = mb[2].shape[0]
        blk = max(d for d in range(1, min(block, n) + 1) if n % d == 0)
        blocks = jax.tree.map(lambda x: x.reshape(n // blk, blk,
                                                  *x.shape[1:]), mb)

        def acc(c, blk):
            loss, grads = jax.value_and_grad(
                lambda p: _loss_sum(ref, p, blk, hp, quant))(params)
            return (c[0] + loss, jax.tree.map(jnp.add, c[1], grads)), None

        zero = (jnp.zeros((), jnp.float32),
                jax.tree.map(jnp.zeros_like, params))
        (loss, grads), _ = jax.lax.scan(acc, zero, blocks)
        loss = loss / n
        grads = jax.tree.map(lambda g: g / n, grads)
        gnorm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        clip = jnp.where(gnorm < hp.max_grad_norm, 1.0,
                         hp.max_grad_norm / gnorm)
        grads = jax.tree.map(lambda g: g * clip, grads)
        count = adam.count + 1
        mu = jax.tree.map(lambda m, g: hp.adam_b1 * m + (1 - hp.adam_b1) * g,
                          adam.mu, grads)
        nu = jax.tree.map(
            lambda v, g: hp.adam_b2 * v + (1 - hp.adam_b2) * g * g,
            adam.nu, grads)
        c1 = 1 - hp.adam_b1 ** count.astype(jnp.float32)
        c2 = 1 - hp.adam_b2 ** count.astype(jnp.float32)
        params = jax.tree.map(
            lambda p, m, v: p - hp.lr * (m / c1)
            / (jnp.sqrt(v / c2) + hp.adam_eps), params, mu, nu)
        return (params, AdamState(count, mu, nu)), loss

    def update(params, adam, data, key):
        n_mb = hp.n_minibatches
        if fault == "half_batch":
            data = jax.tree.map(lambda x: x[:x.shape[0] // 2], data)
            n_mb = n_mb // 2
        B = data[2].shape[0]
        mbs = B // n_mb

        def epoch(carry, _):
            params, adam, key = carry
            key, sub = jax.random.split(key)
            perm = jax.random.permutation(sub, B)
            mbd = jax.tree.map(
                lambda x: x[perm].reshape(n_mb, mbs, *x.shape[1:]), data)
            (params, adam), losses = jax.lax.scan(minibatch, (params, adam),
                                                  mbd)
            return (params, adam, key), losses

        (params, adam, _), losses = jax.lax.scan(
            epoch, (params, adam, key), None, length=hp.n_epochs)
        return params, adam, losses

    return update


def make_behaviour(ref, block: int, quant=None):
    """Jittable ``(params, obs[B,...], mask[B,A], action[B], last_obs[E,...],
    last_mask[E,A]) -> (log_prob[B], value[B], last_value[E])``."""

    def behaviour(params, obs, mask, action, last_obs, last_mask):
        logits, value = forward_blocks(ref, params, obs, mask, block, quant)
        _, last_value = forward_blocks(ref, params, last_obs, last_mask,
                                       block, quant)
        return log_prob(logits, action), value, last_value

    return behaviour


class Follower:
    """Follows the program's iterations: holds its OWN params and Adam
    state from the seeded start (a copy: the caller's stay the caller's) and
    advances them on each trajectory. The update takes them DONATED, so
    that the scans' carries alias the arguments and the follower's peak is
    its state once, not twice; ``release`` frees them."""

    def __init__(self, ref, hp: Hyper, params, block: int, quant=None,
                 fault=None):
        self.hp = hp
        self.params = jax.tree.map(jnp.array, params)
        self.adam = adam_init(self.params)
        self.memory: dict | None = None
        self._behaviour = jax.jit(make_behaviour(ref, block, quant))
        self._update = jax.jit(make_update(ref, hp, block, quant, fault),
                               donate_argnums=(0, 1))
        self._compiled = None

    def step(self, traj: dict, key) -> dict:
        """``traj``: obs[T,E,...], mask[T,E,A], action[T,E], reward[T,E],
        done[T,E], last_obs[E,...], last_mask[E,A] (device or host).
        Returns this iteration's readings."""
        T, E = traj["action"].shape
        flat = lambda x: x.reshape(T * E, *x.shape[2:])
        obs, mask, action = (flat(traj[k]) for k in ("obs", "mask", "action"))
        logp, value, last_value = self._behaviour(
            self.params, obs, mask, action, traj["last_obs"],
            traj["last_mask"])
        value_te = np.asarray(value).reshape(T, E)
        adv, ret = gae_ref.gae(np.asarray(traj["reward"]), value_te,
                               np.asarray(traj["done"]),
                               np.asarray(last_value), self.hp.gamma,
                               self.hp.gae_lambda)
        adv_n = gae_ref.normalize(adv)
        data = (obs, mask, action, logp, value,
                jnp.asarray(adv_n.reshape(-1)), jnp.asarray(ret.reshape(-1)))
        if self._compiled is None:
            self._compiled = self._update.lower(self.params, self.adam, data,
                                                key).compile()
            self.memory = self._memory(data)
        self.params, self.adam, losses = self._compiled(
            self.params, self.adam, data, key)
        return {"loss": float(jnp.mean(losses)),
                "log_prob": np.asarray(logp).reshape(T, E),
                "value": value_te}

    def _memory(self, data) -> dict:
        """What the first update is about to cost, read as it starts: what
        the device holds, and the compiler's account of the update at the
        cell's own shape (``peak`` = arguments + outputs + temporaries -
        aliased), split in two. ``param_shaped_bytes``: what arguments and
        outputs hold of the follower's state (once where it is aliased,
        12 B a parameter) and the gradient accumulator (4 B): it grows with
        the policy by construction. ``remainder_bytes``: the rest of the
        peak, i.e. the data and its shuffled copy, a block's activations,
        and a block's gradients and the optimizer's temporaries, which
        grow with the policy too (PERF.md section 4 has the slope measured
        on the chip, ``benchmark/follower_memory.py``)."""
        n_params = sum(x.size for x in jax.tree.leaves(self.params))
        m = self._compiled.memory_analysis()
        update = {"argument": m.argument_size_in_bytes,
                  "output": m.output_size_in_bytes,
                  "temp": m.temp_size_in_bytes,
                  "alias": m.alias_size_in_bytes}
        update["peak"] = (update["argument"] + update["output"]
                          + update["temp"] - update["alias"])
        nbytes = lambda tree: sum(x.nbytes for x in jax.tree.leaves(tree))
        state = nbytes((self.params, self.adam))
        shaped = 2 * state - update["alias"] + nbytes(self.params)
        stats = jax.local_devices()[0].memory_stats() or {}
        return {"param_count": n_params,
                "bytes_in_use": stats.get("bytes_in_use"),
                "update": update, "data_bytes": nbytes(data),
                "alias_bytes_per_param": update["alias"] / n_params,
                "param_shaped_bytes": shaped,
                "remainder_bytes": update["peak"] - shaped}

    @property
    def released(self) -> bool:
        return all(x.is_deleted()
                   for x in jax.tree.leaves((self.params, self.adam)))

    def release(self) -> None:
        """Free the follower's device state (its readings are the
        caller's, on the host)."""
        for x in jax.tree.leaves((self.params, self.adam)):
            x.delete()


def leaf_gaps(program_norms, reference_norms) -> np.ndarray:
    """|program - reference| leaf by leaf, each measured against the
    reference's norm of that leaf or of the median leaf, whichever is
    larger (some leaves' gradients are all but zero). The driver reads the
    worst and the median of them; it holds neither to a limit."""
    p = np.asarray(program_norms, np.float64)
    r = np.asarray(reference_norms, np.float64)
    floor = max(float(np.median(r)), 1e-30)
    return np.abs(p - r) / np.maximum(r, floor)
