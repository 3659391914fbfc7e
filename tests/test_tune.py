"""Tune layer tests (reference test model: python/ray/tune/tests/ —
test_tune_basic, searcher/scheduler unit tests)."""

import random
import threading

import pytest

import ray_tpu
from ray_tpu import tune
from ray_tpu.tune.schedulers import AsyncHyperBandScheduler, PopulationBasedTraining
from ray_tpu.tune.search import BasicVariantGenerator
from ray_tpu.tune.trial import Trial


@pytest.fixture(autouse=True)
def _rt():
    ray_tpu.init()
    yield
    ray_tpu.shutdown()


def test_grid_search_cross_product():
    gen = BasicVariantGenerator(seed=0)
    gen.set_search_properties("m", "max", {
        "a": tune.grid_search([1, 2, 3]),
        "b": tune.grid_search(["x", "y"]),
        "c": 7,
    })
    gen._materialize(num_samples=1)
    cfgs = [gen.suggest(f"t{i}") for i in range(6)]
    assert all(c is not None for c in cfgs)
    assert gen.suggest("t6") is None
    assert {(c["a"], c["b"]) for c in cfgs} == {(a, b) for a in (1, 2, 3)
                                               for b in ("x", "y")}
    assert all(c["c"] == 7 for c in cfgs)


def test_random_domains_and_sample_from():
    gen = BasicVariantGenerator(seed=42)
    gen.set_search_properties("m", "max", {
        "lr": tune.loguniform(1e-5, 1e-1),
        "bs": tune.choice([16, 32]),
        "n": tune.randint(0, 10),
        "double_n": tune.sample_from(lambda cfg: cfg["n"] * 2),
    })
    gen._materialize(num_samples=5)
    for i in range(5):
        c = gen.suggest(f"t{i}")
        assert 1e-5 <= c["lr"] <= 1e-1
        assert c["bs"] in (16, 32)
        assert 0 <= c["n"] < 10
        assert c["double_n"] == c["n"] * 2


def test_function_trainable_end_to_end():
    def objective(config):
        acc = 0.0
        for i in range(5):
            acc += config["lr"]
            tune.report({"acc": acc})

    tuner = tune.Tuner(
        objective,
        param_space={"lr": tune.grid_search([0.1, 0.2, 0.3])},
        tune_config=tune.TuneConfig(metric="acc", mode="max"),
    )
    grid = tuner.fit()
    assert len(grid) == 3
    best = grid.get_best_result()
    assert best.config["lr"] == 0.3
    assert best.metrics["acc"] == pytest.approx(1.5)


def test_class_trainable_and_stop_criteria():
    class MyTrainable(tune.Trainable):
        def setup(self, config):
            self.x = config["start"]

        def step(self):
            self.x += 1
            return {"x": self.x}

        def save_checkpoint(self):
            return {"x": self.x}

        def load_checkpoint(self, ckpt):
            self.x = ckpt["x"]

    tuner = tune.Tuner(
        MyTrainable,
        param_space={"start": tune.grid_search([0, 100])},
        tune_config=tune.TuneConfig(metric="x", mode="max"),
        stop={"training_iteration": 3},
    )
    grid = tuner.fit()
    assert len(grid) == 2
    best = grid.get_best_result()
    assert best.metrics["x"] == 103
    assert best.checkpoint == {"x": 103}


def test_asha_stops_bad_trials():
    sched = AsyncHyperBandScheduler(grace_period=1, reduction_factor=2,
                                    max_t=16)
    sched.set_search_properties("score", "max")
    good, bad = Trial({"q": 1}), Trial({"q": 0})
    decisions = []
    for it in range(1, 6):
        d_good = sched.on_trial_result(good, {"training_iteration": it,
                                              "score": 10.0 * it})
        d_bad = sched.on_trial_result(bad, {"training_iteration": it,
                                            "score": 0.1 * it})
        decisions.append((d_good, d_bad))
    assert all(dg == "CONTINUE" for dg, _ in decisions)
    assert any(db == "STOP" for _, db in decisions)


def test_tune_errors_surface_in_results():
    def broken(config):
        if config["i"] == 1:
            raise ValueError("boom")
        tune.report({"ok": 1})

    grid = tune.Tuner(
        broken,
        param_space={"i": tune.grid_search([0, 1])},
        tune_config=tune.TuneConfig(metric="ok", mode="max"),
    ).fit()
    assert len(grid.errors) == 1
    assert "boom" in grid.errors[0]
    assert grid.get_best_result().config["i"] == 0


# At module level because a trial's class travels to its actor by name, and
# the trials and the test share this event: set when a trial is rebuilt from
# a donor's checkpoint.
_cloned = threading.Event()


class _Rate(tune.Trainable):
    """Its improvement rate IS its hyperparameter. A trial ends after eight
    steps once some trial has been cloned, not before: with a fixed horizon
    the strong trial could end all its steps before the weak one reached a
    perturbation with a donor known to the scheduler, and whether it did
    was the box's load. So both stay in the population until PBT has acted
    (or the cap says it never will)."""

    def setup(self, config):
        self.w = 0.0

    def step(self):
        self.w += self.config["rate"]
        steps = self.iteration + 1
        return {"score": self.w,
                "done": (steps >= 8 and _cloned.is_set()) or steps >= 20_000}

    def save_checkpoint(self):
        return {"w": self.w}

    def load_checkpoint(self, ckpt):
        self.w = ckpt["w"]
        _cloned.set()


def test_pbt_exploits_and_explores():
    # PBT should propagate high-rate configs/weights to low-rate trials.
    _cloned.clear()
    rng = random.Random(0)
    sched = PopulationBasedTraining(
        perturbation_interval=2,
        hyperparam_mutations={"rate": lambda: rng.uniform(0.5, 1.0)},
        quantile_fraction=0.5, seed=0)
    grid = tune.Tuner(
        _Rate,
        param_space={"rate": tune.grid_search([0.01, 1.0])},
        tune_config=tune.TuneConfig(metric="score", mode="max",
                                    scheduler=sched),
    ).fit()
    assert not any(r.error for r in grid.results)
    # Exploit: a trial was rebuilt from a donor's weights, and the weak one
    # was boosted (its score would be ~0.08 after eight steps without PBT).
    assert _cloned.is_set()
    scores = sorted(r.metrics["score"] for r in grid.results)
    assert scores[0] > 0.5
    # Explore: the clone runs a perturbed copy of the donor's config.
    assert {r.config["rate"] for r in grid.results} - {0.01, 1.0}


def test_trainer_under_tune():
    from ray_tpu.train import DataParallelTrainer, ScalingConfig

    def train_fn(config):
        from ray_tpu.train.session import report
        report({"loss": 1.0 / config["lr"]})

    trainer = DataParallelTrainer(
        train_fn, train_loop_config={"lr": 1.0},
        scaling_config=ScalingConfig(num_workers=1))
    grid = tune.Tuner(
        trainer,
        param_space={"train_loop_config": {"lr": tune.grid_search([1.0, 2.0])}},
        tune_config=tune.TuneConfig(metric="loss", mode="min"),
    ).fit()
    assert len(grid) == 2
    assert grid.get_best_result().config["train_loop_config"]["lr"] == 2.0


def test_tpe_beats_random_on_deterministic_objective():
    """Model-based search: with the same trial budget, TPE's best objective
    beats pure random search on a smooth deterministic function (averaged
    over seeds — both samplers fully seeded, so this is deterministic)."""
    from ray_tpu.tune.search import TPESearcher

    space = {
        "x": tune.uniform(-2.0, 2.0),
        "y": tune.uniform(-2.0, 2.0),
        "lr": tune.loguniform(1e-5, 1e-1),
    }

    def objective(cfg):
        # Minimum 0 at (0.7, -0.3, 1e-3).
        import math as _m

        return ((cfg["x"] - 0.7) ** 2 + (cfg["y"] + 0.3) ** 2
                + (_m.log10(cfg["lr"]) + 3.0) ** 2)

    def run(searcher, n):
        searcher.set_search_properties("loss", "min", space)
        best = float("inf")
        for i in range(n):
            cfg = searcher.suggest(f"t{i}")
            score = objective(cfg)
            searcher.on_trial_complete(f"t{i}", {"loss": score})
            best = min(best, score)
        return best

    n_trials, seeds = 60, [0, 1, 2, 3, 4]
    tpe_best, rand_best = [], []
    for s in seeds:
        tpe_best.append(run(TPESearcher(n_startup=12, seed=s), n_trials))

        class _Random(tune.Searcher):
            def __init__(self, seed):
                self._rng = random.Random(seed)

            def suggest(self, trial_id):
                from ray_tpu.tune.search import Domain, _deepcopy_plain, \
                    _set_path, _walk

                cfg = _deepcopy_plain(self.space)
                for p, v in _walk(self.space):
                    if isinstance(v, Domain):
                        _set_path(cfg, p, v.sample(self._rng))
                return cfg

        rand_best.append(run(_Random(s), n_trials))
    tpe_mean = sum(tpe_best) / len(tpe_best)
    rand_mean = sum(rand_best) / len(rand_best)
    assert tpe_mean < rand_mean, (tpe_best, rand_best)


def test_tpe_in_tuner_end_to_end():
    """TPESearcher drops into the Tuner loop (suggest/on_trial_complete
    protocol) and converges toward the known optimum."""
    from ray_tpu.tune.search import TPESearcher

    def train_fn(config):
        tune.report({"loss": (config["x"] - 1.0) ** 2,
                     "done": True})

    tuner = tune.Tuner(
        train_fn,
        param_space={"x": tune.uniform(-4.0, 4.0),
                     "opt": tune.choice(["sgd", "adam"])},
        tune_config=tune.TuneConfig(metric="loss", mode="min",
                                    num_samples=20,
                                    search_alg=TPESearcher(n_startup=6,
                                                           seed=3)),
    )
    grid = tuner.fit()
    best = grid.get_best_result()
    assert best.metrics["loss"] < 1.0
