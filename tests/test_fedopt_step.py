"""Hybrid pjit step semantics: round structure, pipelining, aggregation,
multi-device SPMD equivalence."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.core import fedopt_step as F
from repro.launch.mesh import make_debug_mesh


def _setup(arch="smollm-135m", H=2, per_group_batch=4):
    a = registry.smoke_config(arch)
    cfg = F.FedStepConfig(arch=a, l_split=1, n_groups=2, seq_len=16,
                          per_group_batch=per_group_batch, H=H)
    mesh = make_debug_mesh(1, 1)
    jitted, _, s_spec, _ = F.jit_train_step(cfg, mesh)
    state = jax.jit(lambda: F.init_train_state(jax.random.PRNGKey(0), cfg),
                    out_shardings=s_spec)()
    batch = F.concrete_train_batch(jax.random.PRNGKey(1), cfg)
    return cfg, jitted, state, batch


def test_round_advances_version_once():
    cfg, step, state, batch = _setup()
    state, _ = step(state, batch)
    assert int(state["version"]) == 1 and int(state["step"]) == 1


def test_groups_identical_after_aggregation_uniform_weights():
    cfg, step, state, batch = _setup()
    state, _ = step(state, batch)
    for leaf in jax.tree.leaves(state["dev"]):
        np.testing.assert_allclose(np.asarray(leaf[0]), np.asarray(leaf[1]),
                                   atol=1e-6)


def test_groups_diverge_within_round():
    """Different local shards -> different pre-aggregation trajectories:
    verify by zeroing one group's aggregation weight and comparing."""
    cfg, step, state, batch = _setup()
    batch["agg_weight"] = jnp.asarray([1.0, 0.0])
    state, _ = step(state, batch)
    # global model equals group-0's trained block; group-1's contribution
    # was dropped, so rerunning with swapped weights must differ
    cfg2, step2, state2, batch2 = _setup()
    batch2["tokens"] = batch["tokens"]
    batch2["labels"] = batch["labels"]
    batch2["agg_weight"] = jnp.asarray([0.0, 1.0])
    state2, _ = step2(state2, batch2)
    w1 = np.asarray(jax.tree.leaves(state["dev"])[1][0])
    w2 = np.asarray(jax.tree.leaves(state2["dev"])[1][0])
    assert np.abs(w1 - w2).max() > 1e-7


def test_pipelined_server_uses_previous_buffer():
    """The server reads its ring slot before this iteration writes it
    (H = 1, ω = 1): round 0 reads a never-written slot, so it has no
    server loss and leaves the server half as it was, while the slot
    fills with every group's rows; round 1 trains on round 0's rows."""
    cfg, step, state, batch = _setup(H=1, per_group_batch=2)
    srv0 = jax.tree.map(np.asarray, state["srv"])
    state, m0 = step(state, batch)
    assert float(m0["s_loss"]) == 0.0
    for a, b in zip(jax.tree.leaves(srv0), jax.tree.leaves(state["srv"])):
        np.testing.assert_array_equal(a, np.asarray(b))
    assert int(state["act_buf"]["valid"].sum()) == \
        cfg.n_groups * cfg.micro_batch
    srv1 = jax.tree.map(np.asarray, state["srv"])
    state, m1 = step(state, batch)
    assert float(m1["s_loss"]) > 0.0
    assert any(not np.array_equal(a, np.asarray(b)) for a, b in
               zip(jax.tree.leaves(srv1), jax.tree.leaves(state["srv"])))


def test_server_loss_decreases_over_rounds():
    cfg, step, state, _ = _setup()
    losses = []
    for r in range(10):
        batch = F.concrete_train_batch(jax.random.PRNGKey(2), cfg)  # fixed
        state, m = step(state, batch)
        losses.append(float(m["s_loss"]))
    assert losses[-1] < losses[1], losses


def test_agg_weights_reweight_contributions():
    cfg, step, state, batch = _setup()
    batch["agg_weight"] = jnp.asarray([3.0, 1.0])
    state, _ = step(state, batch)   # must run + normalize (no nan)
    assert bool(jnp.isfinite(jax.tree.leaves(state["dev"])[0]).all())


BATCH_DIGEST_SNIPPET = r"""
import zlib
import jax, numpy as np
from repro.configs import registry
from repro.core import fedopt_step as F

arch = registry.smoke_config("smollm-135m")
cfg = F.FedStepConfig(arch=arch, l_split=1, n_groups=2, seq_len=16,
                      per_group_batch=4, H=2)
batch = F.concrete_train_batch(jax.random.PRNGKey(0), cfg)
digest = 0
for k in sorted(batch):
    digest = zlib.crc32(np.ascontiguousarray(batch[k]).tobytes(), digest)
print("DIGEST", digest)
"""


def test_concrete_batch_deterministic_across_processes():
    """Regression: seeding with builtin hash() made synthetic batches vary
    per process via PYTHONHASHSEED, breaking benchmark reproducibility."""
    import os
    digests = []
    for hashseed in ("0", "12345"):
        env = dict(os.environ, PYTHONPATH="src", PYTHONHASHSEED=hashseed)
        out = subprocess.run(
            [sys.executable, "-c", BATCH_DIGEST_SNIPPET],
            capture_output=True, text=True, timeout=300, env=env)
        assert "DIGEST" in out.stdout, out.stderr[-2000:]
        digests.append(out.stdout.strip())
    assert digests[0] == digests[1], digests


MULTIDEV_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from repro.configs import registry
from repro.core import fedopt_step as F
from repro.launch.mesh import make_debug_mesh

arch = registry.smoke_config("qwen3-moe-235b-a22b")
cfg = F.FedStepConfig(arch=arch, l_split=1, n_groups=4, seq_len=32,
                      per_group_batch=2, H=2)
mesh = make_debug_mesh(2, 2, pod=2)     # (pod=2, data=2, model=2)
jitted, state_sds, s_spec, _ = F.jit_train_step(cfg, mesh)
compiled = jitted.lower(state_sds, F.train_input_specs(cfg)).compile()
state = jax.jit(lambda: F.init_train_state(jax.random.PRNGKey(0), cfg),
                out_shardings=s_spec)()
batch = F.concrete_train_batch(jax.random.PRNGKey(1), cfg)
state, metrics = jitted(state, batch)
assert np.isfinite(float(metrics["d_loss"]))
assert np.isfinite(float(metrics["s_loss"]))
print("MULTIDEV_OK", float(metrics["d_loss"]), float(metrics["s_loss"]))
"""


@pytest.mark.slow
def test_multipod_spmd_runs_in_subprocess():
    """The multi-pod mesh path executes (not just compiles) on 8 forced
    host devices — MoE arch to exercise expert sharding + all collectives."""
    import os
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)     # the snippet sets its own device count
    out = subprocess.run([sys.executable, "-c", MULTIDEV_SNIPPET],
                         capture_output=True, text=True, timeout=900,
                         env=env)
    assert "MULTIDEV_OK" in out.stdout, out.stderr[-3000:]
