"""CLI + utils tests (SURVEY.md §2 "Config/flags" / "Metrics/logging",
§3.1 cli main, §5 tracing)."""
import csv
import json
import os

import numpy as np
import pytest

from rlgpuschedule_tpu import evaluate as evaluate_cli
from rlgpuschedule_tpu import train as train_cli
from rlgpuschedule_tpu.utils import (MetricsLogger, SectionTimer,
                                     ThroughputMeter)

FAST = ["--iterations", "2", "--n-envs", "4", "--n-nodes", "2",
        "--gpus-per-node", "4", "--window-jobs", "16", "--log-every", "1",
        # suite-speed: the CLI tests exercise mechanics (flags, logging,
        # checkpoint/resume), not learning — shrink the compiled programs
        # (preset n_steps=128/epochs=4 cost multi-second XLA compiles per
        # distinct shape on the 1-core CI host)
        "--horizon", "64", "--queue-len", "4", "--n-steps", "8",
        "--n-epochs", "1", "--n-minibatches", "2"]


class TestMetricsLogger:
    def test_csv_rows_and_echo(self, tmp_path, capsys):
        path = str(tmp_path / "m.csv")
        with MetricsLogger(path, echo=False) as log:
            log(0, {"loss": 1.5, "reward": -2.0})
            log(10, {"loss": 1.0, "reward": -1.0})
        rows = list(csv.DictReader(open(path)))
        assert len(rows) == 2
        assert float(rows[1]["loss"]) == 1.0
        assert rows[1]["iteration"] == "10"

    def test_throughput_meter(self):
        m = ThroughputMeter()
        m.tick(100)
        m.tick(100)
        assert m.steps_per_sec > 0

    def test_tensorboard_writer_roundtrip(self, tmp_path):
        # the hand-encoded Event/TFRecord bytes must read back through
        # stock TensorBoard's own loader (crc framing + proto layout)
        tb_mod = pytest.importorskip(
            "tensorboard.backend.event_processing.event_file_loader")
        from rlgpuschedule_tpu.utils import TensorBoardWriter
        with TensorBoardWriter(str(tmp_path)) as tb:
            tb(3, {"mean_reward": -0.5, "note": "skipped-non-float"})
            tb(7, {"mean_reward": 1.25})
            path = tb.path
        from tensorboard.compat.proto import event_pb2
        events = [event_pb2.Event.FromString(raw) for raw in
                  tb_mod.RawEventFileLoader(path).Load()]
        assert events[0].file_version == "brain.Event:2"
        scalars = {(e.step, v.tag): v.simple_value
                   for e in events[1:] for v in e.summary.value}
        assert scalars[(3, "mean_reward")] == -0.5
        assert scalars[(7, "mean_reward")] == 1.25

    def test_section_timer(self):
        t = SectionTimer()
        with t("a"):
            pass
        with t("a"):
            pass
        assert "a" in t.report() and t.report()["a"] >= 0


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_script(*argv, cwd=REPO, timeout=300):
    """``python <argv>`` as a user runs it, on the CPU: the scripts that
    need a chip must fail here, visibly."""
    import subprocess
    import sys
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO}
    return subprocess.run([sys.executable, *argv], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


class TestCompileCache:
    @pytest.fixture(autouse=True)
    def _restore_jax_cache_dir(self):
        import jax
        prev = jax.config.jax_compilation_cache_dir
        yield
        jax.config.update("jax_compilation_cache_dir", prev)

    def test_env_var_places_the_cache_and_nothing_else_does(self, tmp_path,
                                                            monkeypatch):
        # whoever runs the program places the cache: with the env var set
        # the helper uses exactly that directory, for jax and for the
        # native oracle's .so alike, and sets no other in code
        import jax
        from rlgpuschedule_tpu.utils.platform import (cache_dir,
                                                      enable_compile_cache)
        target = str(tmp_path / "cache")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", target)
        cap = jax.config.jax_compilation_cache_max_size
        assert enable_compile_cache() == target == cache_dir()
        assert jax.config.jax_compilation_cache_dir == target
        assert os.environ["JAX_COMPILATION_CACHE_DIR"] == target
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
        # the helper sets no size cap of its own: one would switch the
        # directory to jax's LRU layout, unwritable for every process
        # that did not set the same cap. Whoever places it bounds it.
        assert jax.config.jax_compilation_cache_max_size == cap

    def test_default_is_one_fixed_path_inside_the_checkout(self,
                                                           monkeypatch):
        # unset: <repo>/.jax_cache — resolved from the package's own
        # location, so two calls and two processes agree and nothing of
        # $HOME, a temp dir, a pid or the clock is in it
        import jax
        from rlgpuschedule_tpu.utils.platform import enable_compile_cache
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(REPO, ".jax_cache")
        assert enable_compile_cache() == want == enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == want
        assert "JAX_COMPILATION_CACHE_DIR" not in os.environ
        for cwd in (REPO, "/"):
            out = _run_script(
                "-c", "from rlgpuschedule_tpu.utils.platform import "
                      "enable_compile_cache as e; print(e())", cwd=cwd)
            assert out.stdout.strip() == want, out.stderr


class TestNoHiddenFallback:
    """The chip entry points fail without a TPU: the one yardstick of
    speed (``BENCHMARK.json``'s command) and the bring-up smoke. Their
    rehearsals (--rehearse-cpu, --tiny) are the only ways onto the CPU
    and both label their output."""

    @pytest.mark.parametrize("argv", [
        ("benchmark/run.py", "--workload", "philly512-cnn.train",
         "--seed", "1", "--seconds", "1", "--trace", "0"),
        ("chip_smoke.py",)], ids=["benchmark/run.py", "chip_smoke.py"])
    def test_chip_script_without_a_tpu_fails_and_prints_no_result(
            self, argv):
        r = _run_script(*argv)
        assert r.returncode != 0
        assert r.stdout == "", r.stdout       # no metric, no result line
        assert "TPU" in r.stderr.strip().splitlines()[-1]

    def test_chip_smoke_tiny_rehearsal_ends_ok_false_on_cpu(self):
        r = _run_script("chip_smoke.py", "--tiny")
        assert r.returncode == 0, r.stderr[-4000:]   # the phases passed
        lines = [json.loads(ln) for ln in r.stdout.strip().splitlines()]
        assert lines[-1] == {"ok": False,
                             "device": {"platform": "cpu", "kind": "cpu",
                                        "count": 1}}
        assert lines[-2]["phases_ok"] is True
        assert lines[-2]["ran"] == ["train", "serve", "evaluate"]
        phases = {ln["phase"]: ln for ln in lines[:-1]}
        assert phases["serve"]["post_warmup_recompiles"] == 0
        assert phases["evaluate"]["policy_completion"] == 1.0
        # the CLIs, the benchmark and this script share one cache
        # directory and one on-disk format
        assert "Error writing persistent compilation cache" not in r.stderr


def _readme_commands():
    """Every ``python -m rlgpuschedule_tpu.<cli> ...`` command in
    README.md's fenced blocks, continuation lines joined, as
    ``(cli, arguments, README line)``."""
    import re
    cli = re.compile(r"python3? -m rlgpuschedule_tpu\."
                     r"(train|evaluate|serve|select_checkpoint)\b(.*)")
    out, fenced, joined, start = [], False, "", 0
    with open(os.path.join(REPO, "README.md")) as f:
        for n, line in enumerate(f, 1):
            line = line.rstrip("\n")
            if line.lstrip().startswith("```"):
                fenced = not fenced
                continue
            if not fenced:
                continue
            if not joined:
                start = n
            joined += line
            if joined.endswith("\\"):
                joined = joined[:-1] + " "
                continue
            m = cli.search(joined)
            if m:
                out.append((m.group(1), m.group(2), start))
            joined = ""
    return out


README_COMMANDS = _readme_commands()


class TestReadmeCommands:
    """README's commands are held to the parsers they name: a flag that
    is renamed or deleted takes its README line with it."""

    @pytest.mark.parametrize(
        "cli,arguments", [c[:2] for c in README_COMMANDS],
        ids=[f"{c[0]}-L{c[2]}" for c in README_COMMANDS])
    def test_command_parses(self, cli, arguments):
        import importlib
        import shlex
        name = "serve.__main__" if cli == "serve" else cli
        parser = importlib.import_module(
            f"rlgpuschedule_tpu.{name}").build_parser()
        try:
            parser.parse_args(shlex.split(arguments, comments=True))
        except SystemExit as e:
            pytest.fail(f"README: python -m rlgpuschedule_tpu.{cli}"
                        f"{arguments} does not parse (exit {e.code})")

    def test_the_readme_still_has_its_commands(self):
        # a README whose fences moved would leave the test above with
        # no cases and no failure
        assert {"train", "evaluate", "serve"} <= {
            c[0] for c in README_COMMANDS}
        assert len(README_COMMANDS) >= 30


class TestTrainCLI:
    def test_list_configs(self, capsys):
        train_cli.main(["--list-configs"])
        out = capsys.readouterr().out
        for name in ("ppo-mlp-synth64", "ppo-cnn-philly512", "a2c-pai-fair",
                     "gnn-gang-place", "hier-pbt-member"):
            assert name in out

    def test_unknown_config_exits(self):
        with pytest.raises(SystemExit):
            train_cli.main(["--config", "nope"])

    def test_train_logs_and_checkpoints(self, tmp_path, capsys):
        csv_path = str(tmp_path / "metrics.csv")
        ckpt_dir = str(tmp_path / "ckpt")
        summary = train_cli.main(
            ["--config", "ppo-mlp-synth64", *FAST,
             "--log-csv", csv_path, "--ckpt-dir", ckpt_dir,
             "--ckpt-every", "1"])
        assert summary["iterations"] == 2
        assert np.isfinite(summary["env_steps_per_sec"])
        rows = list(csv.DictReader(open(csv_path)))
        assert len(rows) == 2
        assert os.path.isdir(ckpt_dir) and os.listdir(ckpt_dir)
        # stdout's last line is the summary JSON
        line = capsys.readouterr().out.strip().splitlines()[-1]
        assert json.loads(line)["iterations"] == 2

    def test_resume_roundtrip(self, tmp_path):
        ckpt_dir = str(tmp_path / "ckpt")
        args = ["--config", "ppo-mlp-synth64", *FAST,
                "--ckpt-dir", ckpt_dir, "--ckpt-every", "2"]
        train_cli.main(args)
        out = train_cli.main(args + ["--resume"])
        assert out["iterations"] == 2

    def test_pbt_training(self, tmp_path):
        summary = train_cli.main(
            ["--config", "hier-pbt-member", "--pbt", "--n-pop", "2",
             "--pbt-ready", "1", "--iterations", "2", "--n-envs", "4",
             "--n-nodes", "4", "--gpus-per-node", "4",
             "--window-jobs", "16", "--log-every", "1",
             "--horizon", "48", "--queue-len", "4", "--n-steps", "8",
             "--n-epochs", "1", "--n-minibatches", "2"])
        assert summary["pbt_events"] >= 1
        assert all(np.isfinite(summary["final_fitness"]))

    def test_select_checkpoint_ranks_retained_series(self, tmp_path):
        # --ckpt-keep retains a checkpoint SERIES; select_checkpoint ranks
        # it by full-trace JCT on a held-out validation stream and emits
        # the argmin step (round-5 finding: per-window probes do not rank
        # full-trace quality, so selection must use the deliverable's own
        # metric on a third stream)
        from rlgpuschedule_tpu import select_checkpoint
        ckpt_dir = str(tmp_path / "ckpt")
        train_cli.main(["--config", "ppo-mlp-synth64", *FAST,
                        "--ckpt-dir", ckpt_dir, "--ckpt-every", "1",
                        "--ckpt-keep", "2"])
        out = select_checkpoint.main(
            ["--config", "ppo-mlp-synth64", "--ckpt-dir", ckpt_dir,
             "--n-envs", "4", "--n-nodes", "2", "--gpus-per-node", "4",
             "--window-jobs", "16", "--queue-len", "4", "--horizon", "64",
             "--val-jobs", "48", "--val-seed", "77"])
        assert len(out["ranking"]) == 2
        assert out["step"] in [s for _, s in out["ranking"]]
        assert out["val_ratio"] == out["ranking"][0][0]
        with pytest.raises(SystemExit, match="training seed"):
            select_checkpoint.main(
                ["--config", "ppo-mlp-synth64", "--ckpt-dir", ckpt_dir,
                 "--val-seed", "0"])

    def test_source_jobs_override(self):
        # --source-jobs pins the generated source trace size explicitly
        # (the north-star run trains on a 100k+-job trace by contract,
        # not as a side effect of n_envs * window_jobs)
        from rlgpuschedule_tpu.configs import CONFIGS
        from rlgpuschedule_tpu.experiment import load_source_trace
        args = train_cli.build_parser().parse_args(
            ["--config", "ppo-mlp-synth64", "--source-jobs", "2048"])
        cfg = train_cli.apply_overrides(CONFIGS["ppo-mlp-synth64"], args)
        assert cfg.source_jobs == 2048
        assert load_source_trace(cfg).num_jobs == 2048

    def test_algo_hparam_overrides(self):
        # --lr/--ent-coef/--n-steps/--n-epochs/--n-minibatches land in the
        # active algo's config; PPO-only knobs are rejected for A2C
        args = train_cli.build_parser().parse_args(
            ["--config", "ppo-mlp-synth64", "--lr", "1e-3",
             "--n-steps", "32", "--n-epochs", "2", "--n-minibatches", "2",
             "--ent-coef", "0.02"])
        from rlgpuschedule_tpu.configs import CONFIGS
        cfg = train_cli.apply_overrides(CONFIGS["ppo-mlp-synth64"], args)
        assert (cfg.ppo.lr, cfg.ppo.n_steps, cfg.ppo.n_epochs,
                cfg.ppo.n_minibatches, cfg.ppo.ent_coef) == \
            (1e-3, 32, 2, 2, 0.02)
        a2c_args = train_cli.build_parser().parse_args(
            ["--config", "a2c-pai-fair", "--lr", "1e-3", "--n-steps", "8"])
        cfg = train_cli.apply_overrides(CONFIGS["a2c-pai-fair"], a2c_args)
        assert (cfg.a2c.lr, cfg.a2c.n_steps) == (1e-3, 8)
        # A2C runs the shared minibatch-geometry engine too (its preset
        # 1x1 geometry is the classic full-batch update), so geometry
        # overrides now land in cfg.a2c instead of being refused
        a2c_geom = train_cli.build_parser().parse_args(
            ["--config", "a2c-pai-fair", "--n-epochs", "2",
             "--n-minibatches", "4"])
        cfg = train_cli.apply_overrides(CONFIGS["a2c-pai-fair"], a2c_geom)
        assert (cfg.a2c.n_epochs, cfg.a2c.n_minibatches) == (2, 4)

    def test_minibatch_geometry_and_bf16_overrides(self):
        # the ISSUE-2 lever flags: --minibatch-size (overrides
        # --n-minibatches, algos.update contract) and --bf16-update
        from rlgpuschedule_tpu.configs import CONFIGS
        args = train_cli.build_parser().parse_args(
            ["--config", "ppo-mlp-synth64", "--minibatch-size", "64",
             "--bf16-update"])
        cfg = train_cli.apply_overrides(CONFIGS["ppo-mlp-synth64"], args)
        assert cfg.ppo.minibatch_size == 64
        assert cfg.ppo.bf16_update is True
        # untouched flags keep preset values
        assert cfg.ppo.n_epochs == 4 and cfg.ppo.bf16_update is True
        base = train_cli.build_parser().parse_args(
            ["--config", "ppo-mlp-synth64"])
        cfg = train_cli.apply_overrides(CONFIGS["ppo-mlp-synth64"], base)
        assert cfg.ppo.minibatch_size is None
        assert cfg.ppo.bf16_update is False

    def test_obs_kind_override(self):
        # --obs-kind swaps the preset's encoder family (e.g. config 2's
        # grid CNN down to the flat MLP for a CPU-host training run)
        args = train_cli.build_parser().parse_args(
            ["--config", "ppo-cnn-philly512", "--obs-kind", "flat"])
        from rlgpuschedule_tpu.configs import CONFIGS
        cfg = train_cli.apply_overrides(CONFIGS["ppo-cnn-philly512"], args)
        assert cfg.obs_kind == "flat"
        # no override keeps the preset encoder
        args = train_cli.build_parser().parse_args(
            ["--config", "ppo-cnn-philly512"])
        cfg = train_cli.apply_overrides(CONFIGS["ppo-cnn-philly512"], args)
        assert cfg.obs_kind == "grid"

    def test_eval_every_probe(self, tmp_path):
        # --eval-every: held-out greedy replay scored vs cached baselines,
        # logged to a separate .eval.csv stream (schemas differ from the
        # train rows) and returned as eval_history
        csv_path = str(tmp_path / "m.csv")
        summary = train_cli.main(
            ["--config", "ppo-mlp-synth64", *FAST, "--eval-every", "1",
             "--eval-windows", "2", "--log-csv", csv_path])
        hist = summary["eval_history"]
        assert len(hist) == 2        # iterations=2, probe each iteration
        for row in hist:
            assert np.isfinite(row["eval_avg_jct"])
            assert np.isfinite(row["eval_vs_tiresias"])
            assert 0 < row["eval_completion"] <= 1.0
        rows = list(csv.DictReader(open(csv_path + ".eval.csv")))
        assert len(rows) == 2 and "eval_vs_tiresias" in rows[0]

    @pytest.mark.timing_flake(retries=2)
    def test_keep_best_checkpoint(self, tmp_path):
        # --keep-best: the best-by-held-out-probe params survive under
        # <ckpt-dir>/best even if later iterations regress (the GNN
        # late-collapse lesson); the eval rows carry an eval_is_best flag
        #
        # timing_flake TRACKING NOTE (carried 1F since the seed, ~1 in
        # N full-suite runs; always passes standalone): the --resume
        # half fails with FileNotFoundError("no checkpoint found under
        # .../ckpt") — the FIRST run's final periodic save (experiment
        # .run's b == iterations-1 save into <ckpt-dir>) is missing
        # from disk when the second run restores, while the best/
        # sidecar store written moments earlier IS present (its
        # assertions above pass in the failing runs). Orbax
        # CheckpointManager is synchronous on CPU here, so the step
        # was handed to orbax but its directory did not survive to
        # the re-open — pointing at tmp/step-dir lifecycle, not our
        # save logic. Until the orbax-side race is pinned, the retry
        # marker reruns with a FRESH tmp_path so tier-1 stays clean
        # and the flake stays visible as a PytestWarning.
        ckpt_dir = str(tmp_path / "ckpt")
        summary = train_cli.main(
            ["--config", "ppo-mlp-synth64", *FAST, "--eval-every", "1",
             "--eval-windows", "2", "--ckpt-dir", ckpt_dir,
             "--keep-best"])
        hist = summary["eval_history"]
        assert hist[0]["eval_is_best"] == 1.0   # first probe always best
        from rlgpuschedule_tpu.checkpoint import Checkpointer
        with Checkpointer(os.path.join(ckpt_dir, "best")) as best:
            assert len(best.all_steps()) == 1
        best_jcts = [r["eval_avg_jct"] for r in hist
                     if r["eval_is_best"] == 1.0]
        # keep-best only tracks full-completion probes (its contract)
        assert min(r["eval_avg_jct"] for r in hist
                   if r["eval_completion"] >= 1.0) == best_jcts[-1]
        # a resumed run recovers the bar from the best meta instead of
        # resetting to +inf (which would rotate out the prior best)
        with Checkpointer(os.path.join(ckpt_dir, "best")) as best:
            prior = best.read_meta()["eval_avg_jct"]
        summary2 = train_cli.main(
            ["--config", "ppo-mlp-synth64", *FAST, "--eval-every", "1",
             "--eval-windows", "2", "--ckpt-dir", ckpt_dir,
             "--keep-best", "--resume"])
        for row in summary2["eval_history"]:
            if row["eval_is_best"] == 1.0:
                assert row["eval_avg_jct"] < prior

    def test_report_flag(self, capsys):
        summary = train_cli.main(
            ["--config", "ppo-mlp-synth64", *FAST, "--report"])
        assert "tiresias" in summary["jct_report"]


class TestEvaluateCLI:
    def test_baselines_only(self, capsys):
        report = evaluate_cli.main(
            ["--config", "ppo-mlp-synth64", "--baselines-only"])
        assert set(report) >= {"fifo", "sjf", "srtf", "tiresias"}

    def test_policy_eval_untrained(self):
        report = evaluate_cli.main(
            ["--config", "ppo-mlp-synth64", "--n-envs", "4", "--no-random",
             "--n-nodes", "2", "--gpus-per-node", "4", "--window-jobs", "16",
             "--horizon", "64", "--max-steps", "64"])
        assert "policy" in report and "vs_tiresias" in report

    def test_drain_frac_eval(self):
        # --drain-frac 1.0 evaluates on backlog-drain copies of the
        # windows: every valid job submits at t=0, so the baseline FIFO
        # JCT must differ from the streaming-windows evaluation of the
        # same config (reproduces the BASELINE.md drain tables)
        common = ["--config", "ppo-mlp-synth64", "--n-envs", "4",
                  "--no-random", "--n-nodes", "2", "--gpus-per-node", "4",
                  "--window-jobs", "16", "--horizon", "64",
                  "--max-steps", "64"]
        stream = evaluate_cli.main(common)
        drain = evaluate_cli.main(common + ["--drain-frac", "1.0"])
        assert np.isfinite(drain["policy"])
        assert drain["fifo"] != stream["fifo"]

    def test_eval_windows_decoupled_from_training_batch(self, tmp_path):
        # a checkpoint trained at n_envs=4 must evaluate on a 2-window
        # batch: --n-envs stays 4 (the carry restore template), while
        # --eval-windows re-cuts the replay batch (the big-batch-TPU-
        # checkpoint-on-CPU-host case)
        ckpt_dir = str(tmp_path / "ckpt")
        train_cli.main(["--config", "ppo-mlp-synth64", *FAST,
                        "--ckpt-dir", ckpt_dir, "--ckpt-every", "2"])
        report = evaluate_cli.main(
            ["--config", "ppo-mlp-synth64", "--n-envs", "4",
             "--n-nodes", "2", "--gpus-per-node", "4", "--queue-len", "4",
             "--window-jobs", "16", "--horizon", "64", "--max-steps", "64",
             "--no-random", "--ckpt-dir", ckpt_dir, "--eval-windows", "2"])
        assert np.isfinite(report["policy"])
        with pytest.raises(SystemExit):
            evaluate_cli.main(["--config", "hier-pbt-member", "--pbt",
                               "--eval-windows", "2"])

    def test_pbt_population_eval(self, tmp_path):
        # config-5 eval path: train a tiny PBT population, checkpoint it,
        # then restore + replay the fittest member against the baselines
        ckpt_dir = str(tmp_path / "pop")
        small = ["--n-envs", "4", "--n-nodes", "4", "--gpus-per-node", "4",
                 "--window-jobs", "16", "--horizon", "48",
                 "--queue-len", "4"]
        train_small = [*small, "--n-steps", "8", "--n-epochs", "1",
                       "--n-minibatches", "2"]   # train-CLI-only knobs
        train_cli.main(
            ["--config", "hier-pbt-member", "--pbt", "--n-pop", "2",
             "--pbt-ready", "1", "--iterations", "2", *train_small,
             "--log-every", "0", "--ckpt-dir", ckpt_dir,
             "--ckpt-every", "2"])
        report = evaluate_cli.main(
            ["--config", "hier-pbt-member", "--pbt", "--n-pop", "2",
             *small, "--max-steps", "48", "--no-random",
             "--ckpt-dir", ckpt_dir])
        assert "policy" in report and "tiresias" in report
        assert np.isfinite(report["policy"])

    def test_stall_guard_flag_and_report_marker(self):
        # VERDICT r4 weak #6: guarded and unguarded preemptive runs must
        # be distinguishable from the emitted report, and the guard must
        # be A/B-able from the CLI
        common = ["--config", "ppo-mlp-preempt", "--n-envs", "4",
                  "--no-random", "--n-nodes", "2", "--gpus-per-node", "4",
                  "--window-jobs", "16", "--horizon", "64",
                  "--queue-len", "4", "--max-steps", "64"]
        guarded = evaluate_cli.main(common)
        assert guarded["stall_guard"] is True
        raw = evaluate_cli.main(common + ["--no-stall-guard"])
        assert raw["stall_guard"] is False
        # non-preemptive configs: the guard is structurally a no-op, so
        # disabling it is refused rather than silently ignored
        with pytest.raises(SystemExit):
            evaluate_cli.main(["--config", "ppo-mlp-synth64",
                               "--no-stall-guard"])

    def test_hier_policy_eval(self):
        report = evaluate_cli.main(
            ["--config", "hier-pbt-member", "--n-envs", "2", "--no-random",
             "--n-nodes", "4", "--gpus-per-node", "4", "--window-jobs", "16",
             "--horizon", "48", "--max-steps", "48"])
        assert "policy" in report and "tiresias" in report
        assert np.isfinite(report["policy"])

    def test_repro_tuple_in_json_output(self, capsys):
        # ISSUE 6 satellite: every evaluate JSON carries the full
        # reproducibility tuple (seed, scenario params, checkpoint step)
        evaluate_cli.main(
            ["--config", "ppo-mlp-synth64", "--n-envs", "2", "--no-random",
             "--n-nodes", "2", "--gpus-per-node", "4", "--window-jobs",
             "16", "--horizon", "64", "--max-steps", "64", "--seed", "5"])
        out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
        repro = out["repro"]
        assert repro["seed"] == 5 and repro["config"] == "ppo-mlp-synth64"
        assert {"trace", "n_nodes", "gpus_per_node", "window_jobs",
                "faults", "ckpt_dir", "ckpt_step"} <= set(repro)
        assert repro["ckpt_step"] is None   # untrained init weights

    def test_chaos_matrix_cli(self, capsys):
        # the ISSUE 6 acceptance shape: regime x scheduler degradation
        # matrix on CPU, conservation holding, repro tuple attached
        report = evaluate_cli.main(
            ["--config", "ppo-mlp-synth64", "--chaos",
             "--chaos-regimes", "sporadic", "--chaos-baselines", "sjf",
             "--n-envs", "2", "--n-nodes", "2", "--gpus-per-node", "4",
             "--window-jobs", "16", "--queue-len", "4",
             "--horizon", "256", "--max-steps", "256"])
        assert set(report["regimes"]) == {"none", "sporadic"}
        assert report["jobs_lost"] == 0
        row = report["regimes"]["sporadic"]["policy"]
        assert np.isfinite(row["avg_jct"]) and row["degradation"] >= 0
        assert report["repro"]["chaos_seed"] == 0
        err = capsys.readouterr().err
        assert "chaos matrix" in err and "degradation" in err

    def test_chaos_flag_refusals(self):
        with pytest.raises(SystemExit):   # chaos sub-flag without --chaos
            evaluate_cli.main(["--config", "ppo-mlp-synth64",
                               "--chaos-regimes", "storm"])
        with pytest.raises(SystemExit):   # incompatible mode
            evaluate_cli.main(["--config", "ppo-mlp-synth64", "--chaos",
                               "--baselines-only"])
        with pytest.raises(SystemExit):   # unknown regime, named early
            evaluate_cli.main(["--config", "ppo-mlp-synth64", "--chaos",
                               "--chaos-regimes", "meteor"])

    def test_train_faults_refusals(self):
        with pytest.raises(SystemExit):   # unknown fault regime
            train_cli.main(["--config", "ppo-mlp-synth64", *FAST,
                            "--faults", "meteor"])
        with pytest.raises(SystemExit):   # unknown domain regime
            train_cli.main(["--config", "ppo-mlp-synth64", *FAST,
                            "--domains", "meteor"])
        # --faults x --pbt is SUPPORTED since the domain PR (per-member
        # (seed, member, env) schedules); --domains x --pbt is not
        with pytest.raises(SystemExit):
            train_cli.main(["--config", "ppo-mlp-synth64", *FAST,
                            "--domains", "mixed", "--pbt"])


class TestStallGuardEngage:
    def test_guard_engage_path_decides_completion_from_cli(self, tmp_path):
        """ISSUE-2 satellite (VERDICT r5 weak #4): a REAL place<->preempt
        deadlock driven from the evaluate CLI — guard-off must read <100%
        completion (the completion guard flags it), guard-on must
        complete. The cycler is the synthetic form of the measured
        config-1p staller (BASELINE.md 'Learned preemption'): a constant-
        logit policy that prefers preempting the most-attained running
        job over placing, so greedy replay ping-pongs place<->preempt at
        clock 0.0 forever."""
        import dataclasses
        import flax
        import jax.numpy as jnp
        from rlgpuschedule_tpu.checkpoint import Checkpointer
        from rlgpuschedule_tpu.configs import CONFIGS
        from rlgpuschedule_tpu.experiment import Experiment

        over = dict(n_nodes=2, gpus_per_node=4, n_envs=2, window_jobs=16,
                    queue_len=4, horizon=1024, drain_frac=1.0)
        cfg = dataclasses.replace(CONFIGS["ppo-mlp-preempt"], **over)
        exp = Experiment.build(cfg)
        sim = exp.env_params.sim
        K, P = sim.queue_len, sim.n_placements
        flat = flax.traverse_util.flatten_dict(exp.train_state.params)
        bias = np.zeros(sim.n_actions, np.float32)
        bias[:K * P] = 1.0       # placements: preferred over no-op
        bias[K * P] = 2.0        # preempt slot 0: preferred over all
        bias[-1] = -1.0          # no-op: last resort (advances time)
        flat[("params", "policy", "kernel")] = jnp.zeros_like(
            flat[("params", "policy", "kernel")])
        flat[("params", "policy", "bias")] = jnp.asarray(bias)
        exp.train_state = exp.train_state.replace(
            params=flax.traverse_util.unflatten_dict(flat))
        with Checkpointer(str(tmp_path / "ck")) as ck:
            exp.save_checkpoint(ck)

        common = ["--config", "ppo-mlp-preempt", "--n-nodes", "2",
                  "--gpus-per-node", "4", "--n-envs", "2",
                  "--window-jobs", "16", "--queue-len", "4",
                  "--horizon", "1024", "--drain-frac", "1.0",
                  "--ckpt-dir", str(tmp_path / "ck"), "--no-random"]
        raw = evaluate_cli.main(common + ["--no-stall-guard"])
        assert raw["stall_guard"] is False
        assert raw["policy_completion"] < 1.0   # deadlocked, flagged
        guarded = evaluate_cli.main(common)
        assert guarded["stall_guard"] is True
        assert guarded["policy_completion"] == 1.0
        assert np.isfinite(guarded["policy"])


class TestPBTKeepBest:
    def test_pbt_eval_probe_and_best_population_retention(self, tmp_path):
        """ISSUE-2 satellite (VERDICT r5 weak #2): the PBT path honors
        --ckpt-keep (series rotation) and retains a probe-selected best/
        population on the eval cadence."""
        ck = str(tmp_path / "ck")
        summary = train_cli.main(
            ["--config", "ppo-mlp-synth64", "--pbt", "--n-pop", "2",
             "--pbt-ready", "1", "--iterations", "2", "--n-envs", "4",
             "--n-nodes", "2", "--gpus-per-node", "4",
             "--window-jobs", "16", "--horizon", "64", "--queue-len", "4",
             "--n-steps", "8", "--n-epochs", "1", "--n-minibatches", "2",
             "--log-every", "1", "--eval-every", "1", "--eval-windows",
             "2", "--keep-best", "--ckpt-dir", ck, "--ckpt-every", "1",
             "--ckpt-keep", "1"])
        assert summary["pbt_events"] >= 1
        # the probe ran on the eval cadence and its rows are in the summary
        assert [row["iteration"] for row in summary["eval_history"]] \
            == [0, 1]
        assert all("eval_avg_jct" in row for row in summary["eval_history"])
        from rlgpuschedule_tpu.checkpoint import Checkpointer
        # --ckpt-keep 1 honored in the PBT path: one retained series step
        with Checkpointer(ck) as series:
            assert len(series.all_steps()) == 1
        # best/ holds a full population checkpoint + the probe bar in meta
        with Checkpointer(os.path.join(ck, "best")) as best:
            steps = best.all_steps()
            assert len(steps) == 1
            meta = best.read_meta()
            assert "eval_avg_jct" in meta
        # and it restores as a population (evaluate --pbt's path)
        report = evaluate_cli.main(
            ["--config", "ppo-mlp-synth64", "--pbt", "--n-pop", "2",
             "--n-envs", "4", "--n-nodes", "2", "--gpus-per-node", "4",
             "--window-jobs", "16", "--horizon", "64", "--queue-len", "4",
             "--ckpt-dir", os.path.join(ck, "best"), "--no-random",
             "--max-steps", "32"])
        assert np.isfinite(report["policy"])
