"""Serving launcher: batched autoregressive decoding with a KV/SSM cache
(the serve_step the decode dry-run shapes lower).

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-0.6b --smoke \
      --batch 4 --prompt-len 32 --tokens 16
"""
import argparse


def serve(arch: str, *, smoke: bool = False, batch: int = 4,
          prompt_len: int = 32, tokens: int = 16, temperature: float = 1.0,
          seed: int = 0):
    """Random-weight ``arch`` decodes ``tokens`` tokens for ``batch`` random
    prompts of ``prompt_len`` through ``actor.generate``. Returns
    ``(cfg, out, seconds)``; the time includes compilation."""
    import time
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, get_smoke_config
    from repro.models.policy import BackbonePolicy
    from repro.rl import actor

    cfg = get_smoke_config(arch) if smoke else get_config(arch)
    policy = BackbonePolicy(cfg, tp=1, kernel="auto")
    key = jax.random.PRNGKey(seed)
    params = policy.init(key)
    prompt = jax.random.randint(jax.random.fold_in(key, 1),
                                (batch, prompt_len), 0, cfg.vocab_size,
                                jnp.int32)
    t0 = time.perf_counter()
    out = actor.generate(policy, params, prompt, tokens,
                         jax.random.fold_in(key, 2),
                         max_len=prompt_len + tokens, temperature=temperature)
    jax.block_until_ready(out)
    return cfg, out, time.perf_counter() - t0


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    from repro.utils.compile_cache import setup_compile_cache
    setup_compile_cache()
    cfg, out, dt = serve(args.arch, smoke=args.smoke, batch=args.batch,
                         prompt_len=args.prompt_len, tokens=args.tokens,
                         temperature=args.temperature, seed=args.seed)
    print(f"arch={cfg.name} generated {out.shape} in {dt:.2f}s "
          f"({args.batch * args.tokens / dt:.1f} tok/s incl. compile)")
    print("first sequence:", out[0].tolist())


if __name__ == "__main__":
    main()
