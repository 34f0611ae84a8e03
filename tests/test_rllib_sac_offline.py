"""SAC (continuous control), offline IO + BC, and evaluation workers.

ref: rllib/algorithms/sac/sac.py (twin-Q + entropy auto-tune),
rllib/offline/json_reader.py + json_writer.py (sample shards),
rllib/evaluation/worker_set.py:82 (separate deterministic eval workers).
"""
import numpy as np
import pytest

from ray_tpu.rllib import BC, BCConfig, PPOConfig, SACConfig
from ray_tpu.rllib.env import PendulumVecEnv
from ray_tpu.rllib.offline import (
    SampleWriter,
    read_samples,
    record_rollouts,
)


@pytest.fixture(autouse=True)
def _shut_down_what_a_test_started():
    """`record_rollouts` and the offline algorithms call `ray_tpu.init`
    themselves and nothing here shut it down, so this module handed an
    initialised runtime to whichever module its worker ran next; one whose
    fixture calls `init` without `ignore_reinit_error`
    (test_job_submission, test_lineage) then failed at set-up."""
    import ray_tpu

    yield
    ray_tpu.shutdown()


def test_pendulum_vec_env_contract():
    env = PendulumVecEnv(num_envs=3, seed=0)
    obs = env.reset()
    assert obs.shape == (3, 3)
    assert env.continuous and env.act_dim == 1 and env.act_limit == 2.0
    total = np.zeros(3)
    for _ in range(200):
        obs, rew, dones, ep = env.step(np.zeros((3, 1), np.float32))
        assert rew.shape == (3,) and (rew <= 0).all()
        total += rew
    # 200-step time limit: every env truncates on the same step.
    assert dones.all() and env.truncateds.all()
    finished = ~np.isnan(ep)
    assert finished.all()
    np.testing.assert_allclose(ep, total, rtol=1e-6)


def test_sac_learner_update_shapes():
    from ray_tpu.rllib.sac import SACHyperparams, SACLearner

    learner = SACLearner(obs_dim=3, act_dim=1,
                         hp=SACHyperparams(act_limit=2.0,
                                           target_entropy=-1.0),
                         seed=0, hidden=(32, 32))
    batch = {
        "obs": np.random.randn(64, 3).astype(np.float32),
        "actions": np.random.uniform(-2, 2, (64, 1)).astype(np.float32),
        "rewards": np.random.randn(64).astype(np.float32),
        "next_obs": np.random.randn(64, 3).astype(np.float32),
        "terminals": np.zeros(64, np.float32),
    }
    m1 = learner.update(batch)
    m2 = learner.update(batch)
    for k in ("critic_loss", "actor_loss", "alpha", "entropy"):
        assert np.isfinite(m1[k]) and np.isfinite(m2[k])
    # Target network must have moved (polyak) but stayed close.
    import jax

    diffs = jax.tree_util.tree_map(
        lambda a, b: float(np.abs(np.asarray(a) - np.asarray(b)).max()),
        learner.critic, learner.target_critic)
    assert max(jax.tree_util.tree_leaves(diffs)) > 0


def test_sac_improves_pendulum():
    """The VERDICT CI criterion: SAC improves Pendulum — late-phase
    episode returns must clearly beat the random-policy warmup phase."""
    algo = (SACConfig()
            .environment("Pendulum-v1")
            .env_runners(num_envs_per_env_runner=4,
                         rollout_fragment_length=32)
            # SAC wants ~1 update per env step (ref sac.py defaults);
            # with these settings the swing-up goes from ~-1250 to
            # better than -300 in 45 iterations (over seeds 0-9 the gain
            # asserted below reads 944 to 1,190 at 45 and 627 to 1,117
            # at 40, against its bound of 400).
            .training(train_batch_size=128,
                      num_updates_per_iteration=128,
                      learning_starts=256,
                      actor_lr=1e-3, critic_lr=1e-3, alpha_lr=1e-3)
            .debugging(seed=0)
            .rl_module(model_hidden=(64, 64))
            .build())
    early, late = [], []
    for it in range(45):
        m = algo.train()
        r = m.get("episode_return_mean")
        if r is not None:
            (early if it < 15 else late).append(r)
    algo.stop()
    assert early and late
    early_mean = float(np.mean(early))
    late_mean = float(np.mean(late[-3:]))
    # Random policy on Pendulum ~= -1200..-1500; learning must show.
    assert late_mean > early_mean + 400, (early_mean, late_mean)


def test_sample_writer_roundtrip(tmp_path):
    w = SampleWriter(str(tmp_path / "off"), fmt="parquet",
                     rows_per_shard=50)
    for _ in range(3):
        w.write({"obs": np.random.randn(40, 4).astype(np.float32),
                 "actions": np.random.randint(0, 2, 40),
                 "rewards": np.ones(40, np.float32)})
    w.close()
    ds = read_samples(str(tmp_path / "off"))
    rows = ds.take_all()
    assert len(rows) == 120
    assert len(rows[0]["obs"]) == 4
    assert set(rows[0]) == {"obs", "actions", "rewards"}


def test_bc_trains_from_recorded_data(tmp_path, local_ray):
    """The VERDICT criterion: a BC run trains PURELY from recorded
    offline data. Record a few PPO rollouts, clone them, and check the
    cloned policy is meaningfully better than random on CartPole."""
    ppo = (PPOConfig().environment("CartPole-v1")
           .env_runners(num_envs_per_env_runner=8,
                        rollout_fragment_length=64)
           .debugging(seed=0).build())
    for _ in range(8):  # competent-ish demonstrator (not expert)
        ppo.train()
    path = record_rollouts(ppo, str(tmp_path / "demos"),
                           num_iterations=6)
    ppo.stop()

    bc = (BCConfig().environment("CartPole-v1")
          .offline_data(input_path=path)
          .training(num_updates_per_iteration=64)
          .evaluation(evaluation_interval=4, evaluation_duration=5)
          .debugging(seed=1).build())
    first = bc.train()["bc_loss"]
    last = None
    for _ in range(3):
        last = bc.train()
    bc.stop()
    assert last["bc_loss"] < first          # NLL decreases
    # Eval ran on the separate worker set this iteration (4 % 4 == 0).
    assert "evaluation/episode_return_mean" in last
    assert last["evaluation/episode_return_mean"] > 40  # random ~ 20


def test_evaluation_workers_separate_and_deterministic(local_ray):
    """evaluation() metrics come from a separate deterministic worker
    set at the configured interval."""
    algo = (PPOConfig().environment("CartPole-v1")
            .env_runners(num_envs_per_env_runner=4,
                         rollout_fragment_length=32)
            .evaluation(evaluation_interval=2, evaluation_duration=4)
            .debugging(seed=0).build())
    m1 = algo.train()
    assert "evaluation/episode_return_mean" not in m1  # iter 1: no eval
    m2 = algo.train()
    assert m2["evaluation/num_episodes"] >= 4.0
    assert np.isfinite(m2["evaluation/episode_return_mean"])
    # Eval workers exist and are distinct from training workers.
    assert algo._eval_workers and (algo._eval_workers[0]
                                   is not algo.workers[0])
    algo.stop()


def test_cql_conservative_term_lowers_q_off_the_data():
    """What makes CQL conservative, without a training run: on one batch
    whose recorded action is always +1 (a terminal step, reward 0, so the
    TD target is 0 everywhere), the penalty pushes Q up on the recorded
    action and down on an action the data never took.  Same seed, same
    batch, 40 updates, with the term and without: Q(s, 1) - Q(s, -1.5)
    reads 0.60 against -0.32 here; over seeds 0-9 the difference is
    0.69-1.40 and the last penalty 0.54-0.86 lower."""
    from ray_tpu.rllib.cql import CQLLearner
    from ray_tpu.rllib.models import apply_twin_q
    from ray_tpu.rllib.sac import SACHyperparams

    n = 128
    obs = np.random.default_rng(0).normal(size=(n, 3)).astype(np.float32)
    batch = {"obs": obs, "actions": np.full((n, 1), 1.0, np.float32),
             "rewards": np.zeros(n, np.float32), "next_obs": obs,
             "terminals": np.ones(n, np.float32)}

    def after_40_updates(cql_alpha):
        learner = CQLLearner(
            3, 1, SACHyperparams(act_limit=2.0, target_entropy=-1.0),
            cql_alpha=cql_alpha, seed=0, hidden=(32, 32))
        for _ in range(40):
            metrics = learner.update(batch)

        def q(action):
            return float(np.minimum(*apply_twin_q(
                learner.critic, obs, np.full((n, 1), action, np.float32))
            ).mean())

        return q(1.0) - q(-1.5), float(metrics["cql_penalty"])

    plain, plain_penalty = after_40_updates(0.0)
    conservative, penalty = after_40_updates(5.0)
    assert conservative > plain + 0.3, (plain, conservative)
    assert penalty < plain_penalty - 0.25, (plain_penalty, penalty)


@pytest.mark.slow
def test_cql_trains_offline_and_beats_random(tmp_path):
    """CQL (ref: rllib/algorithms/cql) trains PURELY from a recorded
    replay dataset (diverse, D4RL-replay-style) and its deterministic
    policy clearly beats random on Pendulum — measured runs reach ~-100,
    i.e. better than the behavior policy itself.

    Slow (56 s under the driver's six workers) and not to be shrunk: at
    30 SAC and 20 CQL iterations the evaluation read -228 to -900 over
    seeds 0-9 against this bound of -700.  The case above holds the
    conservative term in seconds."""
    from ray_tpu.rllib import CQLConfig, SACConfig
    from ray_tpu.rllib.cql import record_replay

    sac = (SACConfig().environment("Pendulum-v1")
           .env_runners(num_envs_per_env_runner=4,
                        rollout_fragment_length=32)
           .training(train_batch_size=128, num_updates_per_iteration=128,
                     learning_starts=256, actor_lr=1e-3, critic_lr=1e-3,
                     alpha_lr=1e-3)
           .debugging(seed=0).build())
    for _ in range(45):
        sac.train()
    path = record_replay(sac, str(tmp_path / "pendulum_replay"))
    sac.stop()

    cql = (CQLConfig().environment("Pendulum-v1")
           .offline_data(input_path=path)
           .env_runners(num_envs_per_env_runner=4)
           .training(train_batch_size=128, num_updates_per_iteration=128,
                     actor_lr=1e-3, critic_lr=1e-3, alpha_lr=1e-3,
                     cql_alpha=1.0)
           .evaluation(evaluation_interval=40, evaluation_duration=4)
           .debugging(seed=1).build())
    last = None
    for _ in range(40):
        last = cql.train()
    cql.stop()
    assert np.isfinite(last["critic_loss"])
    assert np.isfinite(last["cql_penalty"])
    assert last["num_offline_rows"] >= 5000
    # Purely-offline policy clearly better than random (~-1250);
    # measured ~-100..-300 across seeds, asserted with slack.
    assert last["evaluation/episode_return_mean"] > -700, last


def test_marwil_weights_good_behavior_over_bad(tmp_path):
    """MARWIL on mixed-quality data: recorded action 1 always earns
    return 1.0, action 0 earns 0 — a 50/50 behavior policy. BC imitates
    the 50/50 split; MARWIL's exp(beta*advantage) weights tilt the
    learned policy hard toward the rewarded action (beta=0 == BC, ref:
    rllib/algorithms/marwil/marwil.py identity)."""
    import numpy as np

    import ray_tpu
    from ray_tpu.rllib import BCConfig, MARWILConfig
    from ray_tpu.rllib.offline import SampleWriter, discounted_returns

    rng = np.random.default_rng(0)
    n = 2000
    obs = rng.normal(size=(n, 4)).astype(np.float32)
    actions = rng.integers(0, 2, size=n).astype(np.int64)
    rewards = actions.astype(np.float32)          # a=1 pays, a=0 doesn't
    dones = np.ones(n, bool)                      # 1-step episodes
    path = str(tmp_path / "mixed")
    w = SampleWriter(path)
    w.write({"obs": obs, "actions": actions, "rewards": rewards,
             "dones": dones.astype(np.float32)})
    w.close()

    # returns helper: per-episode discounting resets at dones
    r = discounted_returns(np.array([1.0, 2.0, 3.0], np.float32),
                           np.array([False, False, True]), 0.5)
    np.testing.assert_allclose(r, [2.75, 3.5, 3.0])

    def action1_prob(algo):
        import jax

        from ray_tpu.rllib.models import apply_mlp_policy

        logits, _ = apply_mlp_policy(
            jax.device_put(algo.get_weights()), obs[:256])
        p = np.asarray(jax.nn.softmax(logits, axis=1))[:, 1]
        return float(p.mean())

    marwil = (MARWILConfig().environment("CartPole-v1")
              .offline_data(input_path=path)
              .training(beta=3.0, lr=3e-3).debugging(seed=0)).build()
    for _ in range(6):
        m = marwil.train()
    assert np.isfinite(m["marwil_loss"])
    p_marwil = action1_prob(marwil)

    bc = (BCConfig().environment("CartPole-v1")
          .offline_data(input_path=path)
          .training(lr=3e-3).debugging(seed=0)).build()
    for _ in range(6):
        bc.train()
    p_bc = action1_prob(bc)

    assert p_marwil > 0.75, p_marwil       # tilted to rewarded action
    assert abs(p_bc - 0.5) < 0.15, p_bc    # BC copies the 50/50 data
    assert p_marwil > p_bc + 0.2
    marwil.stop(), bc.stop()

