"""A run whose timed path is broken underneath comes out not correct.

Each test replaces the program's ``jit_train_step`` for the duration of
one harness run (at smoke widths on the CPU, past the harness's look for
a chip) with a step that has one fault a program could have.
"""
import jax.numpy as jnp
import pytest

from bench.tests import smoke
from repro.core import fedopt_step as F

ORIGINAL = F.jit_train_step


def _broken(fault):
    def jit_train_step(cfg, mesh, *, donate=True):
        jitted, state, s_spec, b_spec = ORIGINAL(cfg, mesh, donate=False)

        def step(st, batch):
            if fault == "unchanged":
                _, metrics = jitted(st, batch)
                return st, metrics
            batch = dict(batch)
            if fault == "half_batch":
                # the first half of each micro-batch's rows stands in for
                # the rest: the mean is over half the rows
                for k in ("tokens", "labels"):
                    x = batch[k]
                    half = x.shape[2] // 2
                    batch[k] = jnp.concatenate([x[:, :, :half]] * 2, axis=2)
            elif fault == "no_exchange":
                # every group keeps its own weights: no aggregation
                batch["agg_weight"] = jnp.zeros_like(batch["agg_weight"])
            return jitted(st, batch)

        return step, state, s_spec, b_spec
    return jit_train_step


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "no_exchange"])
def test_broken_step_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(F, "jit_train_step", _broken(fault))
    out = smoke.run(smoke.cell("smollm-135m"))
    checks = {k: v["value"] for k, v in out["checks"].items()}
    assert not out["correct"], checks
