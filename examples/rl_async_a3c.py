"""Async RL: A3C worker threads on a gridworld (reference analog:
rl4j-examples A3CCartPole / the async-learning family).

Shows the reference's headline async design on this framework:
- A3CDiscreteDense spawns worker threads that each own an env, roll
  out n steps against a lock-free snapshot of the shared params,
  compute the jitted gradient OUTSIDE the lock, and apply serialized.
- The same MDP is then solved with the second async learner,
  AsyncNStepQLearningDiscrete (n-step TD vs a synced target net).

Runs in ~20s on CPU; no gym/downloads — the in-repo GridWorldMDP
stands in for the gym envs the reference wraps (zero-egress env).
"""

from __future__ import annotations

from deeplearning4j_tpu.rl import (
    A3CConfiguration, A3CDiscreteDense, AsyncNStepQLConfiguration,
    AsyncNStepQLearningDiscrete, GridWorldMDP,
)


def main(updates: int = 800):
    factory = lambda: GridWorldMDP(n=3)

    a3c = A3CDiscreteDense(factory, A3CConfiguration(
        seed=7, n_step=8, n_workers=3, learning_rate=3e-3, hidden=(32,)))
    a3c_ret = -1.0
    for _ in range(3):  # async training is nondeterministic; bounded retrain
        a3c.train(updates=updates)
        a3c_ret = a3c.getPolicy(greedy=True).play(GridWorldMDP(n=3))
        if a3c_ret > 0.9:
            break
    print(f"A3C greedy return: {a3c_ret:.3f} "
          f"({len(a3c.episode_rewards)} episodes)")

    ql = AsyncNStepQLearningDiscrete(factory, AsyncNStepQLConfiguration(
        seed=7, n_step=5, n_workers=3, learning_rate=3e-3,
        target_update=25, anneal_updates=max(updates * 2 // 3, 1),
        hidden=(32,)))
    q_ret = -1.0
    for _ in range(3):
        ql.train(updates=updates)
        q_ret = ql.getPolicy().play(GridWorldMDP(n=3))
        if q_ret > 0.9:
            break
    print(f"async n-step Q greedy return: {q_ret:.3f}")
    return min(a3c_ret, q_ret)


if __name__ == "__main__":
    from deeplearning4j_tpu.common.environment import (
        configure_compile_cache,
    )

    configure_compile_cache()
    main()
