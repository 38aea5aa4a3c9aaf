"""The control of ``correct``, at a size a test run can hold: the plain
reference computed in fp8 (the precision below the configuration's bf16)
and put in the program's place must read FARTHER from the float32
reference than the program does, by the factor the limits rely on (three),
in the number the cell's limit leans on. On the chip, at the cell's own
size, ``benchmark/control.py`` takes the same readings (PERF.md keeps
them); here the tiny ``--rehearse-cpu`` shape stands in.
"""
from benchmark import common
from benchmark.run import context_for

SEEDS = (3, 4, 2 ** 31 + 5)


def _reads(workload: str, seed: int, variants, seconds: float = 0.5) -> dict:
    from rlgpuschedule_tpu.utils.platform import (device_record,
                                                  enable_compile_cache)
    enable_compile_cache()
    ctx = context_for(workload, seed, seconds, True, device_record())
    driver = common.load_module("drivers", ctx.traffic["driver"])
    return driver.control(ctx, variants)


def test_fp8_control_reads_farther_than_the_program():
    prog, ctl = [], []
    for seed in SEEDS:
        r = _reads("philly512-cnn.train", seed, ["none", "fp8"])
        prog.append(r["none"]["log_prob_gap"])
        ctl.append(r["fp8"]["log_prob_gap"])
        # what no precision may move: the simulator against the oracle
        assert r["none"]["sim_state"] == 0 and r["none"]["untied_envs"] == 0
    assert min(ctl) >= 3 * max(prog), (prog, ctl)


def test_planted_faults_read_farther_than_the_program():
    """The reference with a fault planted, in the program's place: half
    the learning rate moves the whole-tree parameter change, half the
    batch left out the first loss, each at least three times as far as the
    program reads."""
    r = _reads("philly512-cnn.train", SEEDS[0],
               ["none", "half_lr", "half_batch"])
    assert (r["half_lr"]["param_change_tree_gap"]
            >= 3 * r["none"]["param_change_tree_gap"]), r
    assert (r["half_batch"]["loss_gap_first"]
            >= 3 * r["none"]["loss_gap_first"]), r
