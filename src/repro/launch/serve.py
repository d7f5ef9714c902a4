"""Serving driver: batched prefill + decode with the merged global model.

FedOptima is a *training* system; serving uses the merged (device+server)
model — ``transformer.merge_params`` — behind the standard prefill/decode
steps.  This driver demonstrates batched request serving end-to-end on
CPU with a smoke-scale arch::

    python -m repro.launch.serve --arch smollm-135m --batch 4 --new-tokens 16
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.models import transformer as tfm


def generate(params, arch, prompts, *, new_tokens: int, max_len: int,
             frontend=None, greedy: bool = True, rng=None):
    """prompts: (B, S0) int32.  Returns (B, S0 + new_tokens)."""
    B, S0 = prompts.shape
    logits, caches = jax.jit(
        lambda p, t, f: tfm.prefill(p, arch, t, max_len=max_len, frontend=f)
    )(params, prompts, frontend)

    decode = jax.jit(
        lambda p, c, t, pos: tfm.serve_decode_step(p, arch, c, t, pos))
    out = [prompts]
    token = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
    for i in range(new_tokens):
        out.append(token)
        if i == new_tokens - 1:
            break
        logits, caches = decode(params, caches, token, jnp.int32(S0 + i))
        if greedy:
            token = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        else:
            rng, k = jax.random.split(rng)
            token = jax.random.categorical(k, logits)[:, None].astype(jnp.int32)
    return jnp.concatenate(out, axis=1)


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--arch", default="smollm-135m")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=32)
    p.add_argument("--new-tokens", type=int, default=16)
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args()

    arch = registry.smoke_config(args.arch)
    rng = jax.random.PRNGKey(args.seed)
    params = tfm.init_params(rng, arch)
    prompts = jax.random.randint(rng, (args.batch, args.prompt_len), 0,
                                 arch.vocab, jnp.int32)
    frontend = None
    if arch.frontend_len:
        frontend = jax.random.normal(
            rng, (args.batch, arch.frontend_len, arch.d_model))

    max_len = args.prompt_len + args.new_tokens
    t0 = time.time()
    out = generate(params, arch, prompts, new_tokens=args.new_tokens,
                   max_len=max_len, frontend=frontend)
    out.block_until_ready()
    dt = time.time() - t0
    tok_s = args.batch * args.new_tokens / dt
    assert out.shape == (args.batch, args.prompt_len + args.new_tokens)
    assert bool(jnp.isfinite(out).all())
    print(f"served {args.batch} requests × {args.new_tokens} new tokens "
          f"in {dt:.2f}s ({tok_s:.1f} tok/s, CPU smoke config '{arch.name}')")
    print("first request tokens:", out[0, -args.new_tokens:].tolist())


if __name__ == "__main__":
    main()
