"""Device milliseconds per launch per chip (one update per launch in the
benchmark's Ocean cells) of the env step inside the rollout: the ops under
the named scope ``ppo_env_step`` (``rl/rollout.py``, the ``step_fn`` call:
the Ocean env and its emulation layer). Source: the profiler trace, by the
program's instruction map (``bench/phases.py``). Silent where the program
marks no scopes."""
from bench import phases


def read(ctx):
    return phases.ms_per_launch("ppo_env_step")
