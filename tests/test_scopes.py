"""The layers named inside the timed program (ISSUE 27): every scope of
``obs.scopes.TREE`` is on the lowered train step of PPO and of A2C; the
scopes are metadata only (the compiled step is the same code without
them); the compile cache tells a scoped program from an unscoped one;
the obs tracer's spans reach the profiler's trace with NO telemetry
attached, nested in one step annotation per iteration; and with
telemetry attached the bus stream is event for event what it was before
the spans were folded into ``Tracer.phase``.
"""
import contextlib
import dataclasses
import functools
import glob
import re

import jax
import jax.numpy as jnp
import pytest

from rlgpuschedule_tpu.algos import A2CConfig, PPOConfig
from rlgpuschedule_tpu.configs import CONFIGS
from rlgpuschedule_tpu.experiment import Experiment
from rlgpuschedule_tpu.obs import RunTelemetry, read_events, scopes
from rlgpuschedule_tpu.obs.telemetry import OverlapMeter
from rlgpuschedule_tpu.obs.trace import (NULL_TRACER, SPAN_BEGIN, SPAN_END,
                                         Tracer)
from rlgpuschedule_tpu.parallel.groups import split_devices
from rlgpuschedule_tpu.utils.profiling import SectionTimer

# two minibatches in both, so that the update's shuffle is in the program
# (A2C's default 1 x 1 geometry takes no permutation at all)
SMALL = {
    "ppo": dataclasses.replace(
        CONFIGS["ppo-mlp-synth64"], n_envs=2, window_jobs=16, horizon=64,
        ppo=PPOConfig(n_steps=8, n_epochs=1, n_minibatches=2)),
    "a2c": dataclasses.replace(
        CONFIGS["a2c-pai-fair"], n_envs=2, window_jobs=16, horizon=64,
        a2c=A2CConfig(n_steps=8, n_epochs=1, n_minibatches=2)),
}
# the token policy (ISSUE 30) at its tiny trunk: the same step, with the
# trunk's own scopes under both places its forward is traced
SMALL["tokens"] = dataclasses.replace(
    CONFIGS["ppo-trinity-philly512"], trunk="tiny", n_envs=2, n_nodes=2,
    gpus_per_node=4, window_jobs=16, queue_len=4, horizon=64,
    ppo=PPOConfig(n_steps=8, n_epochs=1, n_minibatches=2))
# the second family of token blocks (ISSUE 39), likewise
SMALL["ling"] = dataclasses.replace(SMALL["tokens"],
                                    name="ppo-ling-philly512",
                                    trunk="ling-tiny")
# the third, whose layers hang under a loop over steps (ISSUE 41)
SMALL["ouro"] = dataclasses.replace(SMALL["tokens"],
                                    name="ppo-ouro-philly512",
                                    trunk="ouro-tiny")
SCOPE_NAMES = tuple(path[-1] for path in scopes.TREE)
TRUNK_SCOPE_NAMES = tuple(path[-1] for path in scopes.TRUNK_TREE)
# each token policy's step with the tree of its family
TRUNK_TREES = {"tokens": scopes.TRUNK_TREE, "ling": scopes.LING_TRUNK_TREE,
               "ouro": scopes.OURO_TRUNK_TREE}


def lower_step(algo: str):
    exp = Experiment.build(SMALL[algo])
    return exp.train_step.lower(exp.train_state, exp.carry, exp.traces,
                                jax.random.PRNGKey(0), exp.faults)


def bare(component: str) -> str:
    """``vmap(sim_step)`` -> ``sim_step``: the name a transformation
    wrapped."""
    return re.findall(r"[\w.]+", component)[-1] if component else ""


def scope_names_in(text: str, pattern: str) -> set:
    return {bare(c) for path in re.findall(pattern, text)
            for c in path.split("/")}


@pytest.fixture(scope="module")
def lowered_names():
    cache: dict = {}

    def names(algo: str) -> set:
        if algo not in cache:
            cache[algo] = scope_names_in(
                lower_step(algo).as_text(debug_info=True),
                r'loc\("([^"]+)"')
        return cache[algo]

    return names


@pytest.mark.parametrize("scope", SCOPE_NAMES)
@pytest.mark.parametrize("algo", ["ppo", "a2c"])
def test_scope_is_on_the_lowered_train_step(lowered_names, algo, scope):
    assert scope in lowered_names(algo)


@pytest.mark.parametrize("scope", SCOPE_NAMES + TRUNK_SCOPE_NAMES)
def test_scope_is_on_the_token_policys_train_step(lowered_names, scope):
    assert scope in lowered_names("tokens")


@pytest.mark.parametrize("scope", SCOPE_NAMES + tuple(
    path[-1] for path in scopes.LING_TRUNK_TREE))
def test_scope_is_on_the_ling_policys_train_step(lowered_names, scope):
    assert scope in lowered_names("ling")


@pytest.mark.parametrize("scope", SCOPE_NAMES + tuple(
    path[-1] for path in scopes.OURO_TRUNK_TREE))
def test_scope_is_on_the_ouro_policys_train_step(lowered_names, scope):
    assert scope in lowered_names("ouro")


@pytest.mark.parametrize("parent", scopes.TRUNK_PARENTS,
                         ids=lambda p: "/".join(p))
@pytest.mark.parametrize("policy,path", [
    (policy, path) for policy, tree in TRUNK_TREES.items() for path in tree],
    ids=lambda v: v if isinstance(v, str) else "/".join(v))
def test_trunk_scope_hangs_under_both_forward_passes(policy, path, parent):
    """Some operation of the COMPILED step carries ``parent`` and then
    the trunk scope's own path, in order, in its ``op_name`` (what the
    benchmark's readers match): the rollout's forward and the update's
    loss forward (and its transpose) both name the trunk's layers."""
    want = [*parent, *path]

    def holds(components):
        it = iter(components)
        return all(any(c == w for c in it) for w in want)

    assert any(holds([bare(c) for c in name.split("/")])
               for name in _tokens_op_names(policy))


@functools.lru_cache(maxsize=None)
def _tokens_op_names(policy: str) -> frozenset:
    return frozenset(re.findall(
        r'op_name="([^"]+)"', lower_step(policy).compile().as_text()))


def test_tree_is_parents_first_and_names_are_unique():
    assert len(set(SCOPE_NAMES)) == len(SCOPE_NAMES)
    assert len(set(SCOPE_NAMES + TRUNK_SCOPE_NAMES)) == len(
        SCOPE_NAMES + TRUNK_SCOPE_NAMES)
    ling = tuple(path[-1] for path in scopes.LING_TRUNK_TREE)
    assert len(set(SCOPE_NAMES + ling)) == len(SCOPE_NAMES + ling)
    ouro = tuple(path[-1] for path in scopes.OURO_TRUNK_TREE)
    assert len(set(SCOPE_NAMES + ouro)) == len(SCOPE_NAMES + ouro)
    for tree in (scopes.TREE, *TRUNK_TREES.values()):
        seen = set()
        for path in tree:
            assert path[:-1] == () or path[:-1] in seen
            seen.add(path)
    assert all(p in scopes.TREE for p in scopes.TRUNK_PARENTS)


def strip_metadata(hlo: str) -> str:
    """The HLO text without what says where an operation came from: each
    instruction's ``metadata={...}`` and the module's source tables
    (FileNames ... StackFrames, up to the blank line after the last)."""
    hlo = re.sub(r",? ?metadata=\{[^}]*\}", "", hlo)
    return re.sub(r"\nFileNames\n.*?\nStackFrames\n.*?\n\n", "\n", hlo,
                  count=1, flags=re.DOTALL)


@pytest.mark.parametrize("algo", ["ppo", "a2c", "tokens"])
def test_scopes_change_no_code(monkeypatch, algo):
    """The compiled step's HLO, metadata stripped, is the same text with
    this repo's scopes patched to no-ops (the token policy's: the
    trunk's scopes too)."""
    names = SCOPE_NAMES + (TRUNK_SCOPE_NAMES if algo == "tokens" else ())
    scoped = lower_step(algo).compile().as_text()
    real = jax.named_scope          # flax names its modules with it too
    monkeypatch.setattr(
        jax, "named_scope",
        lambda name: (contextlib.nullcontext() if name in names
                      else real(name)))
    plain = lower_step(algo).compile().as_text()
    op_names = r'op_name="([^"]+)"'
    assert set(names) <= scope_names_in(scoped, op_names)
    assert not set(names) & scope_names_in(plain, op_names)
    assert strip_metadata(scoped) == strip_metadata(plain)


def test_compile_cache_tells_scoped_from_unscoped(tmp_path, monkeypatch):
    """jax's default cache key strips metadata: a program that differs
    from a cached one only in its scopes would come back with the cached
    program's op_names. ``enable_compile_cache`` keys on metadata."""
    from rlgpuschedule_tpu.utils.platform import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    try:
        assert enable_compile_cache() == str(tmp_path)

        def program(scope):
            def f(x):
                with scope:
                    return jnp.cumsum(jnp.sin(x) * 2.0)
            return jax.jit(f).lower(jnp.ones((128,))).compile().as_text()

        assert "observe" not in program(contextlib.nullcontext())
        assert "observe" in program(jax.named_scope(scopes.OBSERVE))
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def host_events(trace_dir: str) -> list:
    """``(name, start_ns, end_ns, stats)`` of every host-plane event the
    profiler recorded under this repo's prefix."""
    from jax.profiler import ProfileData
    (path,) = glob.glob(f"{trace_dir}/plugins/profile/*/*.xplane.pb")
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(scopes.ANNOTATION_PREFIX):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns,
                                dict(ev.stats)))
    return sorted(out, key=lambda e: e[1])


def test_profile_without_telemetry_shows_the_host_spans(tmp_path):
    exp = Experiment.build(SMALL["ppo"])
    exp.run(iterations=1, log_every=1)            # compile outside
    with jax.profiler.trace(str(tmp_path)):
        exp.run(iterations=2, log_every=1)
    events = host_events(str(tmp_path))
    steps = [e for e in events if e[0] == scopes.TRAIN_ITERATION]
    assert [e[3]["step_num"] for e in steps] == [0, 1]
    for _, t0, t1, _ in steps:
        inside = [e[0] for e in events
                  if e[0] != scopes.TRAIN_ITERATION
                  and t0 <= e[1] and e[2] <= t1]
        assert inside == ["rlsched:step", "rlsched:sync"]
    assert len(events) == 6                       # nothing outside a step


# recorded from the parent commit (d354aa1) with the same two calls: the
# stream before the sites' stacks were folded into Tracer.phase.
# (kind, span, depth, attrs) per thread; phases of each iteration event
SYNC_STREAM = [
    ("run_start",),
    (SPAN_BEGIN, "iteration", 0, {"iteration": 0}),
    (SPAN_BEGIN, "step", 1, None), (SPAN_END, "step", 1, None),
    (SPAN_BEGIN, "sync", 1, None), (SPAN_END, "sync", 1, None),
    (SPAN_END, "iteration", 0, None),
    ("iteration", 0, ["step", "sync"]),
    (SPAN_BEGIN, "iteration", 0, {"iteration": 1}),
    (SPAN_BEGIN, "step", 1, None), (SPAN_END, "step", 1, None),
    (SPAN_BEGIN, "sync", 1, None), (SPAN_END, "sync", 1, None),
    (SPAN_BEGIN, "eval", 1, None), (SPAN_END, "eval", 1, None),
    (SPAN_END, "iteration", 0, None),
    ("iteration", 1, ["eval", "step", "sync"]),
    ("run_end",),
]


def _pairs(*spans):
    return [row for name, depth, attrs in spans
            for row in ((SPAN_BEGIN, name, depth, attrs),
                        (SPAN_END, name, depth, None))]


ASYNC_ACTOR = [row for i in (0, 1) for row in _pairs(
    ("actor_barrier_wait", 0, None), ("actor_gate_wait", 0, None),
    ("actor", 0, {"iteration": i}), ("queue_push_wait", 0, None))]
ASYNC_LEARNER = [row for i in (0, 1) for row in (
    [(SPAN_BEGIN, "iteration", 0, {"iteration": i})]
    + _pairs(("queue_pop_wait", 1, None),
             ("learner", 1, {"iteration": i}), ("sync", 1, None))
    + [(SPAN_END, "iteration", 0, None)])]
ASYNC_PHASES = ["actor", "learner", "queue_wait", "sync"]


def normalised(events, tid=None):
    out = []
    for e in events:
        kind = e["kind"]
        if kind in (SPAN_BEGIN, SPAN_END):
            if tid is None or e["tid"] == tid:
                out.append((kind, e["span"], e["depth"], e.get("attrs")))
        elif tid is not None:
            continue
        elif kind == "iteration":
            out.append((kind, e["iteration"], sorted(e["phases"])))
        else:
            out.append((kind,))
    return out


def test_bus_stream_with_telemetry_is_what_it_was(tmp_path):
    exp = Experiment.build(SMALL["ppo"])
    with RunTelemetry(str(tmp_path), rank=0, trace=True) as tel:
        exp.run(iterations=2, log_every=1, telemetry=tel, eval_every=2,
                eval_fn=lambda i: {"x": 1.0})
    assert normalised(read_events(tel.bus.path)) == SYNC_STREAM


def test_async_bus_stream_with_telemetry_is_what_it_was(tmp_path):
    exp = Experiment.build(SMALL["ppo"])
    with RunTelemetry(str(tmp_path), rank=0, trace=True) as tel:
        out = exp.run_async(
            iterations=2, log_every=1, telemetry=tel, staleness_bound=0,
            groups=split_devices(devices=jax.devices()[:1]))
    events = read_events(tel.bus.path)
    tids = {e["thread"]: e["tid"] for e in events
            if e["kind"] == SPAN_BEGIN}
    assert normalised(events, tids["async-actor"]) == ASYNC_ACTOR
    assert normalised(events, tids["MainThread"]) == ASYNC_LEARNER
    assert [sorted(e["phases"]) for e in events
            if e["kind"] == "iteration"] == [ASYNC_PHASES] * 2
    assert sorted(out["phase_seconds"]) == sorted(ASYNC_PHASES)
    assert out["async"]["actor_busy_s"] > 0


@pytest.mark.parametrize("enabled", [False, True])
def test_phase_names_one_boundary_for_all_its_readers(tmp_path, enabled):
    """One call: the section's time, the span (under the stream's own
    name for it), and the overlap meter's lane."""
    from rlgpuschedule_tpu.obs import EventBus
    sections, meter = SectionTimer(), OverlapMeter()
    with EventBus(str(tmp_path), rank=0) as bus:
        tracer = Tracer(bus, enabled=enabled)
        with tracer.phase(sections, "queue_wait", span="queue_pop_wait"):
            pass
        with tracer.phase(sections, "actor", meter=meter, iteration=3):
            pass
    assert sorted(sections.report()) == ["actor", "queue_wait"]
    assert list(meter.busy_s) == ["actor"]
    spans = [(e["kind"], e["span"], e.get("attrs"))
             for e in read_events(bus.path)]
    assert spans == ([] if not enabled else [
        (SPAN_BEGIN, "queue_pop_wait", None),
        (SPAN_END, "queue_pop_wait", None),
        (SPAN_BEGIN, "actor", {"iteration": 3}),
        (SPAN_END, "actor", None)])


def test_null_tracer_phase_keeps_the_sections(tmp_path):
    sections = SectionTimer()
    with NULL_TRACER.phase(sections, "step"):
        pass
    assert list(sections.report()) == ["step"]
