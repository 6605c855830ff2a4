"""Device milliseconds per launch per chip (one update per launch in the
benchmark's Ocean cells) of the learner: the ops under the named scopes
``ppo_learn`` and ``ppo_minibatch_rows`` (``rl/learner.py``
``make_ocean_learn``: GAE, the minibatch scan of forward, backward and AdamW
steps with its row reads, and the episode stats). Source: the profiler
trace, by the program's instruction map (``bench/phases.py``). Silent where
the program marks no scopes."""
from bench import phases


def read(ctx):
    return phases.ms_per_launch("ppo_learn", "ppo_minibatch_rows")
