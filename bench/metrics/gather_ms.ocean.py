"""Device milliseconds per launch per chip (one update per launch in the
benchmark's Ocean cells) of the learner's minibatch row reads: the ops under
the named scope ``ppo_minibatch_rows`` (``rl/learner.py``, the random-index
gathers of each minibatch's observations, actions, log-probabilities,
advantages, values and returns). Source: the profiler trace, by the
program's instruction map (``bench/phases.py``). Silent where the program
marks no scopes."""
from bench import phases


def read(ctx):
    return phases.ms_per_launch("ppo_minibatch_rows")
