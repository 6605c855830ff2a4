"""Paper Table 1: emulation overhead.

Times raw env steps vs emulated (flattened) env steps, single instance,
jitted, on this machine. The paper's claim: overhead is a few tens of µs and
negligible for envs slower than a few thousand SPS.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.core import spaces as sp
from repro.core.emulation import Emulated, flat_spec, emulate, unemulate
from repro.envs.ocean import OCEAN


class MockStructured:
    """NetHack-shaped mock: dict of mixed-dtype arrays (paper §3.1)."""
    num_agents = 1

    def __init__(self):
        self.observation_space = sp.Dict({
            "glyphs": sp.Box((21, 79), jnp.int32),
            "chars": sp.Box((21, 79), jnp.uint8),
            "blstats": sp.Box((27,), jnp.float32),
            "message": sp.Box((256,), jnp.uint8),
        })
        self.action_space = sp.Discrete(23)

    def init(self, key):
        return {"t": jnp.zeros((), jnp.int32)}

    def reset(self, state, key):
        return state, self._obs(state)

    def _obs(self, s):
        t = s["t"].astype(jnp.float32)
        return {"glyphs": jnp.full((21, 79), s["t"], jnp.int32),
                "chars": jnp.full((21, 79), 32, jnp.uint8),
                "blstats": jnp.full((27,), t),
                "message": jnp.zeros((256,), jnp.uint8)}

    def step(self, state, action, key):
        s = {"t": state["t"] + 1}
        from repro.envs.base import empty_info
        return s, self._obs(s), jnp.float32(0), s["t"] >= 1000, empty_info()


def _time_step(env, steps=3000):
    key = jax.random.PRNGKey(0)
    state = env.init(key)
    state, obs = env.reset(state, key)
    if isinstance(env, Emulated):
        if env.act_spec.kind == "discrete":
            act = jnp.zeros((len(env.action_space.nvec),), jnp.int32)
        else:
            act = jnp.zeros((env.act_spec.cont_dim,), jnp.float32)
    else:
        act = sp.zeros(env.action_space)
    step = jax.jit(env.step)
    state, obs, *_ = step(state, act, key)      # compile
    jax.block_until_ready(jax.tree.leaves(obs)[0])
    t0 = time.perf_counter()
    for i in range(steps):
        state, obs, *_ = step(state, act, key)
    jax.block_until_ready(jax.tree.leaves(obs)[0])
    return (time.perf_counter() - t0) / steps


def run():
    rows = []
    envs = {name: cls() for name, cls in OCEAN.items()}
    envs["mock_nethack"] = MockStructured()
    for name, env in envs.items():
        t_raw = _time_step(env)
        t_emu = _time_step(Emulated(env))
        overhead = (t_emu - t_raw) / max(t_raw, 1e-12) * 100
        rows.append({"env": name, "raw_us": t_raw * 1e6,
                     "emulated_us": t_emu * 1e6,
                     "sps_emulated": 1.0 / t_emu,
                     "overhead_pct": overhead})
    return rows


def main():
    from repro.telemetry import benchwatch
    rows = run()
    for r in rows:
        print(f"bench_emulation/{r['env']},{r['emulated_us']:.1f},"
              f"raw_us={r['raw_us']:.1f};overhead_pct={r['overhead_pct']:.1f};"
              f"sps={r['sps_emulated']:.0f}")
    benchwatch.record(
        "emulation", {f"{r['env']}_sps": r["sps_emulated"] for r in rows})


if __name__ == "__main__":
    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    main()
