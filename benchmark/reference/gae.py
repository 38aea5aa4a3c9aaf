"""Generalised advantage estimation as the textbook numpy loop.

``dones[t]`` means the episode ended AT step t (auto-reset envs: no
bootstrap across it). float32 throughout, like the system under test."""
from __future__ import annotations

import numpy as np


def gae(rewards, values, dones, last_value, gamma: float, lam: float):
    """``[T, E]`` inputs, ``last_value [E]`` -> ``(advantages, returns)``."""
    rewards = np.asarray(rewards, np.float32)
    values = np.asarray(values, np.float32)
    nonterm = np.float32(1.0) - np.asarray(dones, np.float32)
    g, gl = np.float32(gamma), np.float32(gamma * lam)
    adv = np.zeros_like(rewards)
    next_adv = np.zeros_like(np.asarray(last_value, np.float32))
    next_v = np.asarray(last_value, np.float32)
    for t in range(rewards.shape[0] - 1, -1, -1):
        delta = rewards[t] + g * next_v * nonterm[t] - values[t]
        next_adv = delta + gl * nonterm[t] * next_adv
        adv[t] = next_adv
        next_v = values[t]
    return adv, adv + values


def normalize(adv):
    """Whole-batch advantage normalisation, E[x^2]-E[x]^2 form."""
    adv = np.asarray(adv, np.float32)
    mean = np.mean(adv, dtype=np.float32)
    var = np.mean(adv * adv, dtype=np.float32) - mean * mean
    return (adv - mean) / np.sqrt(var + np.float32(1e-8))
