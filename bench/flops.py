"""Operations and bytes each unit of work needs, computed from shapes, and
the chip peaks they are measured against. These are the benchmark's own
counts, kept apart from the program, so every PR divides by the same
numbers.

Counts are of what the algorithm requires, not of what a program happens to
execute: recomputation under remat, padding and masked-out blocks are not
counted. Only matrix multiplications count as operations (2 per
multiply-add); elementwise work is left out.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"


def peaks(device_kind: str) -> dict:
    """Peak figures of one chip of ``device_kind``; an unknown kind is an
    error, never a default."""
    table = json.loads(PEAKS.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def mlp_flops(widths) -> int:
    """Forward matmul operations of one row through dense layers
    ``widths[0] -> widths[1] -> ...``."""
    return sum(2 * a * b for a, b in zip(widths, widths[1:]))


def ocean_flops_per_agent_step(obs_dim: int, hidden: int, num_actions: int,
                               update_epochs: int, unroll: int) -> float:
    """OceanPolicy (two tanh layers, action and value heads) per trained
    agent step: one rollout forward, ``1/unroll`` of a bootstrap forward,
    and ``update_epochs`` forward+backward passes in the learner. The
    backward pass costs two forwards per layer (input and weight
    gradients), except the first layer, whose input gradient no one
    needs."""
    trunk = [(obs_dim, hidden), (hidden, hidden)]
    heads = [(hidden, num_actions), (hidden, 1)]
    layers = trunk + heads
    fwd = sum(2 * a * b for a, b in layers)
    bwd = 2 * fwd - 2 * obs_dim * hidden
    return fwd * (1 + 1 / unroll) + update_epochs * (fwd + bwd)


def lm_matmul_params(cfg: dict) -> int:
    """Parameters that enter a matmul per token of a dense GQA transformer
    with a gated MLP (``cfg`` uses the Hugging Face config keys): the
    projections, the MLP, the output head (tied or not) and the value
    head. The embedding lookup is a gather and does not count."""
    d, L = cfg["hidden_size"], cfg["num_hidden_layers"]
    H, K = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, ff, V = cfg["head_dim"], cfg["intermediate_size"], cfg["vocab_size"]
    attn = d * H * hd * 2 + d * K * hd * 2          # q, o and k, v
    mlp = 3 * d * ff                                # gate, up, down
    return L * (attn + mlp) + d * V + d


def lm_train_flops_per_token(cfg: dict, seq: int) -> float:
    """Training operations per token: ``6 N`` for the matmul parameters,
    plus causal attention, ``6 L H hd (seq + 1)`` (scores and values,
    forward and backward, averaged over the causal triangle)."""
    L, H, hd = (cfg["num_hidden_layers"], cfg["num_attention_heads"],
                cfg["head_dim"])
    return 6 * lm_matmul_params(cfg) + 6 * L * H * hd * (seq + 1)


def flash_attention_cost(batch: int, seq: int, heads: int, kv_heads: int,
                         head_dim: int, itemsize: int = 2):
    """(operations, bytes) of one causal forward call: ``QK^T`` and ``PV``
    over the lower triangle, and q, k, v read and the output written
    once."""
    ops = 2 * 2 * batch * heads * head_dim * seq * (seq + 1) / 2
    elems = batch * seq * head_dim * (2 * heads + 2 * kv_heads)
    return ops, elems * itemsize


def gae_cost(unroll: int, envs: int):
    """(operations, bytes) of one GAE call on a time-major ``(T, B)``
    batch: rewards, values and non-terminal flags read and advantages
    written as float32, plus the bootstrap row; about six operations per
    element."""
    ops = 6 * unroll * envs
    return ops, 4 * (4 * unroll * envs + envs)


def least_time(ops: float, nbytes: float, peak: dict) -> tuple:
    """Least time the chip can take and what bounds it."""
    t_ops = ops / peak["bf16_flops"]
    t_mem = nbytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_mem else (t_mem, "memory")
