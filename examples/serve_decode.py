"""Scenario: batched serving with the merged global model.

After FedOptima training, device + server halves merge into one model
(``merge_params``); serving is standard prefill + KV-cache decode
(``repro.launch.serve``).  Demonstrates a hybrid arch (jamba: mamba states + attention KV +
MoE routing in one cache pytree).

Run:  PYTHONPATH=src python examples/serve_decode.py [--arch jamba-1.5-large-398b]
"""
import argparse
import time

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.core import fedopt_step as F
from repro.launch.serve import generate
from repro.models import transformer as tfm


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="jamba-1.5-large-398b")
    p.add_argument("--batch", type=int, default=4)
    p.add_argument("--new-tokens", type=int, default=12)
    args = p.parse_args()

    arch = registry.smoke_config(args.arch)
    rng = jax.random.PRNGKey(0)

    # train one hybrid round, then merge the halves for serving
    mesh_cfg = F.FedStepConfig(arch=arch, l_split=1, n_groups=2, seq_len=32,
                               per_group_batch=2, H=2)
    from repro.launch.mesh import make_debug_mesh
    step, _, s_spec, _ = F.jit_train_step(mesh_cfg, make_debug_mesh(1, 1))
    state = jax.jit(lambda: F.init_train_state(rng, mesh_cfg),
                    out_shardings=s_spec)()
    state, _ = step(state, F.concrete_train_batch(rng, mesh_cfg))
    dev0 = jax.tree.map(lambda x: x[0], state["dev"])   # any group (merged)
    params = tfm.merge_params(dev0, state["srv"], arch)

    prompts = jax.random.randint(rng, (args.batch, 16), 0, arch.vocab,
                                 jnp.int32)
    frontend = None
    if arch.frontend_len:
        frontend = jax.random.normal(
            rng, (args.batch, arch.frontend_len, arch.d_model))
    t0 = time.time()
    out = generate(params, arch, prompts, new_tokens=args.new_tokens,
                   max_len=16 + args.new_tokens, frontend=frontend)
    dt = time.time() - t0
    assert bool(jnp.isfinite(out).all())
    print(f"[{arch.name}] served {args.batch} requests x "
          f"{args.new_tokens} tokens in {dt:.2f}s "
          f"({args.batch * args.new_tokens / dt:.1f} tok/s on CPU smoke)")
    print("sample:", out[0].tolist())


if __name__ == "__main__":
    main()
