"""rlgpuschedule_tpu — a TPU-native RL GPU-cluster scheduler framework.

A from-scratch rebuild of the capabilities of ``matthewygf/RLGPUSchedule``
(see SURVEY.md; the reference mount was empty, so parity targets come from the
driver's capability spec, provenance tag ``[B]`` in SURVEY.md):

- L0 traces:      Microsoft Philly / Alibaba PAI loaders + synthetic Poisson.
- L1 simulator:   a discrete-event GPU-cluster simulator, twice —
                  * ``sim.oracle``: an exact event-driven Python oracle
                    (executable spec, hosts the baseline schedulers), and
                  * ``sim.core``:   a pure-functional, jit/vmap-able JAX sim
                    with fixed-shape state (the TPU-native hot path).
- L2 env:         gym-style pure-functional env with grid / flat / graph
                  observations, JCT + fairness rewards, action masking.
- L3 models:      Flax MLP / CNN / GNN actor-critic encoders.
- L4 algorithms:  PPO / A2C with fused lax.scan rollouts and reverse-scan GAE.
- L5 parallel:    data-parallel shard_map + psum over a device mesh,
                  hierarchical multi-agent, population-based training.
- L6 driver:      named configs, train/evaluate CLIs, metrics, checkpoints.
"""

import time as _time

# the start-up account's origin (``obs.startup``): the first statement any
# import of the package runs, on the event bus's clock
T0 = _time.monotonic()
# (name, start, end) of what a process pays before the account can be
# imported, stamped by the modules that pay: ``import`` (the bodies that
# import jax, flax and optax: ``utils``, ``configs``, ``experiment``,
# whichever an entry point reaches first) and ``backend``
# (``utils.platform.device_record``: the TPU client's start)
EARLY_SPANS: list = []


def stamp(name: str, since: float) -> None:
    """What began at ``since`` under ``name`` ends now."""
    EARLY_SPANS.append((name, since, _time.monotonic()))


__version__ = "0.1.0"
