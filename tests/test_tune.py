"""Tune: searchers, ASHA early stopping, PBT, failure retry, experiment
restore, and JaxTrainer integration.

Mirrors the reference's tune test strategy (python/ray/tune/tests/) on the
in-process runtime fixture.
"""

import os

import pytest

import ray_tpu
from ray_tpu import tune
from ray_tpu.air.checkpoint import Checkpoint
from ray_tpu.air.config import FailureConfig, RunConfig


@pytest.fixture
def tune_cluster(tmp_path):
    ray_tpu.init(num_cpus=8, ignore_reinit_error=True)
    yield str(tmp_path)
    ray_tpu.shutdown()


def test_grid_and_random_search(tune_cluster):
    def trainable(config):
        tune.report({"score": config["a"] * 10 + config["b"]})

    tuner = tune.Tuner(
        trainable,
        param_space={"a": tune.grid_search([1, 2, 3]), "b": tune.uniform(0, 1)},
        tune_config=tune.TuneConfig(metric="score", mode="max", seed=7),
        run_config=RunConfig(name="grid", storage_path=tune_cluster),
    )
    results = tuner.fit()
    assert len(results) == 3
    best = results.get_best_result()
    assert best.metrics["config"]["a"] == 3
    df_scores = sorted(r["score"] // 10 for r in [t.last_result for t in results.trials])
    assert df_scores == [1, 2, 3]


def test_asha_stops_bad_trials(tune_cluster):
    def trainable(config):
        import time

        for step in range(1, 21):
            # lr quality is baked into the score slope
            tune.report({"score": config["lr"] * step, "training_iteration": step})
            # Reports drained after the trainable returned never reach the
            # scheduler: a step must outlast a poll round to be stoppable.
            time.sleep(0.05)

    # Serial execution, best-first order: ASHA's rungs retain completed
    # trials' scores, so the later bad trials deterministically fall below
    # the recorded cutoffs — no reliance on wall-clock overlap (the old
    # sleep-paced concurrent version flaked under CI load when trials
    # serialized worst-first and the single bad-first trial had no peers).
    tuner = tune.Tuner(
        trainable,
        param_space={"lr": tune.grid_search([10.0, 1.0, 0.1, 0.01])},
        tune_config=tune.TuneConfig(
            metric="score",
            mode="max",
            max_concurrent_trials=1,
            scheduler=tune.ASHAScheduler(grace_period=2, reduction_factor=2, max_t=20),
        ),
        run_config=RunConfig(name="asha", storage_path=tune_cluster),
    )
    results = tuner.fit()
    trials = results.trials
    assert len(trials) == 4
    stopped = [t for t in trials if t.stopped_early and t.training_iteration < 20]
    assert stopped, "ASHA should stop at least one underperforming trial early"
    best = results.get_best_result()
    assert best.metrics["config"]["lr"] == 10.0


def test_stop_criteria_and_checkpoint(tune_cluster):
    def trainable(config):
        import time

        ckpt = tune.get_checkpoint()
        start = ckpt.to_dict()["step"] + 1 if ckpt else 0
        for step in range(start, 100):
            tune.report(
                {"step": step}, checkpoint=Checkpoint.from_dict({"step": step})
            )
            # A step takes time: without a pause all 100 reports can land
            # between one poll and the completion check of the same round,
            # and reports drained after the trainable returned are recorded
            # without consulting the stop criterion.
            time.sleep(0.05)

    tuner = tune.Tuner(
        trainable,
        param_space={},
        tune_config=tune.TuneConfig(metric="step", mode="max"),
        run_config=RunConfig(name="stopper", storage_path=tune_cluster, stop={"step": 5}),
    )
    results = tuner.fit()
    best = results.get_best_result()
    assert best.metrics["step"] >= 5
    assert best.metrics["step"] < 99  # stopped early, not run out
    assert best.checkpoint is not None


def test_failure_retry_resumes_from_checkpoint(tune_cluster, tmp_path):
    marker = str(tmp_path / "crashed_once")

    def trainable(config):
        ckpt = tune.get_checkpoint()
        start = ckpt.to_dict()["step"] + 1 if ckpt else 0
        for step in range(start, 6):
            tune.report({"step": step}, checkpoint=Checkpoint.from_dict({"step": step}))
            if step == 3 and not os.path.exists(config["marker"]):
                open(config["marker"], "w").close()
                raise RuntimeError("boom")

    tuner = tune.Tuner(
        trainable,
        param_space={"marker": marker},
        tune_config=tune.TuneConfig(metric="step", mode="max"),
        run_config=RunConfig(
            name="retry",
            storage_path=tune_cluster,
            failure_config=FailureConfig(max_failures=1),
        ),
    )
    results = tuner.fit()
    best = results.get_best_result()
    assert best.error is None
    assert best.metrics["step"] == 5  # finished after the retry


def test_experiment_restore_restarts_errored(tune_cluster, tmp_path):
    """Driver-restart flow: first run leaves an ERROR trial; Tuner.restore
    re-runs it from the experiment checkpoint on disk."""
    marker = str(tmp_path / "fixed")

    def trainable(config):
        if config["kind"] == "bad" and not os.path.exists(config["marker"]):
            raise RuntimeError("deliberate failure")
        tune.report({"score": 1.0 if config["kind"] == "bad" else 0.5})

    exp_dir = os.path.join(tune_cluster, "restore_exp")
    tuner = tune.Tuner(
        trainable,
        param_space={"kind": tune.grid_search(["good", "bad"]), "marker": marker},
        tune_config=tune.TuneConfig(metric="score", mode="max"),
        run_config=RunConfig(name="restore_exp", storage_path=tune_cluster),
    )
    results = tuner.fit()
    assert len(results.errors) == 1

    # "fix the bug", then restore from disk — only the errored trial re-runs
    open(marker, "w").close()
    restored = tune.Tuner.restore(exp_dir, trainable, restart_errored=True)
    results2 = restored.fit()
    assert len(results2.errors) == 0
    assert len(results2) == 2
    assert results2.get_best_result().metrics["score"] == 1.0


def test_pbt_perturbs_and_improves(tune_cluster):
    def trainable(config):
        import time

        ckpt = tune.get_checkpoint()
        state = ckpt.to_dict() if ckpt else {"value": 0.0, "step": 0}
        value, start = state["value"], state["step"] + 1
        for step in range(start, 31):
            value += config["lr"]  # higher lr -> faster growth
            tune.report(
                {"score": value, "training_iteration": step},
                checkpoint=Checkpoint.from_dict({"value": value, "step": step}),
            )
            # pace reports so driver polls interleave trials (PBT compares
            # populations at matching wall-clock progress)
            time.sleep(0.05)

    scheduler = tune.PopulationBasedTraining(
        perturbation_interval=5,
        hyperparam_mutations={"lr": [0.1, 0.5, 1.0, 2.0]},
        quantile_fraction=0.5,
        seed=3,
    )
    tuner = tune.Tuner(
        trainable,
        param_space={"lr": tune.grid_search([0.1, 2.0])},
        tune_config=tune.TuneConfig(metric="score", mode="max", scheduler=scheduler),
        run_config=RunConfig(name="pbt", storage_path=tune_cluster),
    )
    results = tuner.fit()
    assert scheduler.num_perturbations >= 1, "PBT never exploited"
    # The exploited trial inherits the fast trial's checkpoint, so both end high.
    scores = sorted(t.last_result["score"] for t in results.trials)
    assert scores[0] > 0.1 * 30  # the slow config alone would reach ~3.0


def test_tuner_over_jax_trainer(tune_cluster):
    import jax.numpy as jnp

    from ray_tpu.train import JaxTrainer
    from ray_tpu.air.config import ScalingConfig

    def train_fn(config):
        import numpy as np

        from ray_tpu.train.session import report

        # toy quadratic: loss = (w - 1)^2 after config["lr"]-sized steps
        w = 0.0
        for step in range(5):
            w = w + config["lr"] * (1.0 - w)
            report({"loss": float((1.0 - w) ** 2), "training_iteration": step + 1})

    trainer = JaxTrainer(
        train_fn,
        scaling_config=ScalingConfig(num_workers=1),
    )
    tuner = tune.Tuner(
        trainer,
        param_space={"lr": tune.grid_search([0.01, 0.9])},
        tune_config=tune.TuneConfig(
            metric="loss", mode="min", resources_per_trial={"CPU": 2}
        ),
        run_config=RunConfig(name="trainer_tune", storage_path=tune_cluster),
    )
    results = tuner.fit()
    assert len(results) == 2
    best = results.get_best_result()
    assert best.metrics["config"]["lr"] == 0.9
    assert best.metrics["loss"] < 1e-3


def test_tpe_searcher_concentrates_near_optimum(tune_cluster):
    """Model-based search (native TPE — the optuna/hyperopt algorithm):
    after the random warmup, suggestions must concentrate near the optimum
    of a smooth objective and beat pure random search's mean."""
    import random as _random

    from ray_tpu.tune.search import TPESearcher

    def objective(cfg):
        return -((cfg["x"] - 0.7) ** 2) - 0.5 * (cfg["lr"] - 1e-2) ** 2

    space = {"x": tune.uniform(0.0, 1.0), "lr": tune.loguniform(1e-4, 1.0)}
    tpe = TPESearcher(space, num_samples=48, n_initial=8, seed=5)
    tpe.set_search_properties("score", "max")
    late = []
    for i in range(48):
        cfg = tpe.suggest(f"t{i}")
        score = objective(cfg)
        tpe.on_trial_complete(
            f"t{i}", {"score": score, "config": cfg}, error=False
        )
        if i >= 32:
            late.append(cfg["x"])
    assert tpe.suggest("t_done") is None  # budget exhausted
    # Late suggestions cluster near x*=0.7 much tighter than uniform draws.
    rng = _random.Random(5)
    uniform_dist = sum(abs(rng.uniform(0, 1) - 0.7) for _ in range(16)) / 16
    tpe_dist = sum(abs(x - 0.7) for x in late) / len(late)
    assert tpe_dist < uniform_dist * 0.6, (tpe_dist, uniform_dist)


def test_tpe_drives_tuner(tune_cluster):
    """TPE as the Tuner's search_alg end-to-end.  The runner must query
    the searcher INCREMENTALLY (refill after completions) — an upfront
    drain would leave every suggestion on the random-warmup path."""
    from ray_tpu.tune.search import TPESearcher

    def trainable(config):
        tune.report({"score": -((config["x"] - 0.3) ** 2)})

    class SpyTPE(TPESearcher):
        def __init__(self, *a, **kw):
            super().__init__(*a, **kw)
            self.obs_seen = []

        def suggest(self, trial_id):
            self.obs_seen.append(len(self._obs))
            return super().suggest(trial_id)

    space = {"x": tune.uniform(0.0, 1.0)}
    spy = SpyTPE(space, num_samples=20, n_initial=6, seed=2)
    tuner = tune.Tuner(
        trainable,
        param_space=space,
        tune_config=tune.TuneConfig(
            metric="score", mode="max", search_alg=spy,
        ),
        run_config=RunConfig(name="tpe", storage_path=tune_cluster),
    )
    results = tuner.fit()
    assert len(results) == 20
    # Later suggestions actually SAW completed observations (model path),
    # not just the warmup RNG.
    assert max(spy.obs_seen) >= spy.n_initial, spy.obs_seen
    best = results.get_best_result()
    assert abs(best.metrics["config"]["x"] - 0.3) < 0.15


@pytest.mark.slow  # pbt test is the fast population-based twin
def test_pb2_gp_explore_within_bounds(tune_cluster):
    """PB2: exploit inherits PBT's checkpoint copy; explore picks bounded
    hyperparams via the GP-UCB model, always inside the declared bounds."""
    def trainable(config):
        import time

        ckpt = tune.get_checkpoint()
        state = ckpt.to_dict() if ckpt else {"value": 0.0, "step": 0}
        value, start = state["value"], state["step"] + 1
        for step in range(start, 31):
            value += config["lr"]
            tune.report(
                {"score": value, "training_iteration": step},
                checkpoint=Checkpoint.from_dict({"value": value, "step": step}),
            )
            time.sleep(0.05)

    scheduler = tune.PB2(
        perturbation_interval=5,
        hyperparam_bounds={"lr": (0.05, 2.0)},
        quantile_fraction=0.5,
        seed=4,
    )
    tuner = tune.Tuner(
        trainable,
        param_space={"lr": tune.grid_search([0.05, 2.0])},
        tune_config=tune.TuneConfig(metric="score", mode="max", scheduler=scheduler),
        run_config=RunConfig(name="pb2", storage_path=tune_cluster),
    )
    results = tuner.fit()
    assert scheduler.num_perturbations >= 1, "PB2 never exploited"
    for t in results.trials:
        assert 0.05 <= t.config["lr"] <= 2.0
    scores = sorted(t.last_result["score"] for t in results.trials)
    assert scores[0] > 0.05 * 30  # the slow config alone reaches ~1.5


def test_pb2_gp_targets_known_optimum(tune_cluster):
    """Regression for the GP-bandit explore itself: given observations of
    a deterministic improvement landscape peaking at lr*=0.5, PB2's UCB
    choices must concentrate near the optimum far tighter than uniform
    exploration — a silent regression to random picks fails this."""
    import numpy as np

    pb2 = tune.PB2(hyperparam_bounds={"lr": (0.0, 1.0)}, seed=7)
    for x in np.linspace(0.0, 1.0, 40):
        pb2._gp_data.append(([float(x)], float(-((x - 0.5) ** 2))))

    picks = []
    for _ in range(12):
        choice = pb2._gp_choose()
        assert choice is not None and 0.0 <= choice["lr"] <= 1.0
        picks.append(choice["lr"])
    gp_dist = float(np.mean([abs(p - 0.5) for p in picks]))
    rng = np.random.default_rng(7)
    uniform_dist = float(np.mean(np.abs(rng.uniform(0, 1, 200) - 0.5)))
    assert gp_dist < uniform_dist * 0.5, (gp_dist, uniform_dist)
