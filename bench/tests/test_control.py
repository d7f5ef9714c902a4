"""The controls: the plain reference one precision step down (bfloat16
for the configurations' float32: held in it, or only computed in it over
float32 weights), put in the program's place, fail the check; so do the
planted faults.  CPU, smoke widths."""
import jax.numpy as jnp
import pytest

from bench.reference.check import (ReferenceTrack, compare,
                                   reference_readings)
from bench.reference.fedround import Reference
from bench.tests import smoke


@pytest.fixture(scope="module", params=smoke.ARCHS)
def base(request):
    c = smoke.cell(request.param)
    return c, reference_readings(c, 3)


@pytest.mark.parametrize("variant", [
    {"dtype": jnp.bfloat16}, {"compute_dtype": jnp.bfloat16},
    {"half_batch": True}, {"aggregate": False}])
def test_variant_fails_a_limit(base, variant):
    c, ref = base
    hist, norms = reference_readings(c, 3, **variant)
    nums = compare(hist, norms, *ref, int(c.traffic["check_rounds"]))
    assert any(nums[k] > lim for k, lim in c.limits.items()), nums


def test_reference_against_itself_reads_zero(base):
    c, ref = base
    nums = compare(*ref, *ref, int(c.traffic["check_rounds"]))
    assert nums == {"loss_gap": 0.0, "update_gap": 0.0, "change_gap": 0.0}


def test_mixed_precision_trains_float32_weights(base):
    """bfloat16 compute keeps float32 weights, and they move by about
    the reference's update (a bfloat16 state would barely move at all)."""
    c, (_, ref_norms) = base
    ref = Reference(c.config, c.traffic, compute_dtype=jnp.bfloat16)
    track = ReferenceTrack(ref, int(c.traffic["check_rounds"]))
    seen = []
    ref.run(3, 1, on_round=lambda r, g, s: (
        seen.append(ref.leaves(g, s)), track(r, g, s)))
    assert all(x.dtype == jnp.float32
               for xs in seen[-1].values() for x in xs)
    gaps = [abs(track.norms[1][k] - v) / v
            for k, v in ref_norms[1].items() if v > 0]
    assert max(gaps) < 0.5, max(gaps)
