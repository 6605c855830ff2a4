"""Device milliseconds per launch per chip (one update per launch in the
benchmark's Ocean cells) of the rollout: the ops under the named scopes
``ppo_rollout`` and ``ppo_env_step`` (``rl/rollout.py``: the rollout scan,
the policy step, the env step and the bootstrap value). Source: the profiler
trace, by the program's instruction map (``bench/phases.py``). Silent where
the program marks no scopes."""
from bench import phases


def read(ctx):
    return phases.ms_per_launch("ppo_rollout", "ppo_env_step")
