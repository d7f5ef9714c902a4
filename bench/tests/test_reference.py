"""The plain reference against the program's pod round, on the CPU at
smoke widths, through the whole harness (set-up, window, check)."""
import pytest

from bench.tests import smoke


@pytest.mark.parametrize("arch", smoke.ARCHS)
def test_run_is_correct_at_smoke_widths(arch):
    out = smoke.run(smoke.cell(arch), seed=2 ** 31 + 11)
    checks = {k: v["value"] for k, v in out["checks"].items()}
    assert out["correct"], checks
    # the CPU computes both sides in float32: they agree to rounding
    for k in ("loss_gap", "update_gap", "change_gap"):
        assert checks[k] < 1e-5, (k, checks[k])
    assert out["attempted"] >= 1 and out["failed"] == 0
    m = out["metrics"]
    assert m["tokens_per_s"]["value"] > 0 and m["setup_s"]["value"] > 0
    assert list(out)[-1] == "checks"
