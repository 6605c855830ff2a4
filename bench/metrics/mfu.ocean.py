"""Model FLOP/s utilization of the Ocean PPO update step while the device
runs it, in %: the policy operations the traced window's agent steps
need (``flops.ocean_flops_per_agent_step``: the rollout forward and the
learner's forward and backward passes), over the device's busy time in
the profiler trace times chips times the bf16 peak. Idle time is left
out (``device_idle.ocean`` has it); recomputation does not count."""


def read(ctx):
    busy = ctx["trace"]["busy_s"] * ctx["chips"]
    if busy <= 0:
        return None
    return 100.0 * ctx["work"] * ctx["info"]["flops_per_unit"] / (
        busy * ctx["peak"]["bf16_flops"])
