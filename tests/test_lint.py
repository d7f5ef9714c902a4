"""Repo lint: the tree is clean, each rule fires on a minimal violating
fixture (and stays quiet on the corrected form), and scope/allowlist/
waiver mechanics behave."""
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

from repro.analysis.lint import RULES, lint_file, lint_paths, main

SRC = Path(__file__).resolve().parent.parent / "src"


def _write(tmp_path, rel, body):
    p = tmp_path / rel
    p.parent.mkdir(parents=True, exist_ok=True)
    p.write_text(textwrap.dedent(body))
    return p


def _rules_of(errors):
    return sorted({e.rule for e in errors})


# ---------------------------------------------------------------------------
# the real tree
# ---------------------------------------------------------------------------

def test_src_tree_is_clean():
    errors = lint_paths([SRC])
    assert errors == [], "\n".join(str(e) for e in errors)


def test_rule_table_is_complete():
    assert set(RULES) == {f"RP00{i}" for i in range(1, 7)}
    assert len(set(RULES.values())) == len(RULES)


# ---------------------------------------------------------------------------
# per-rule fixtures: bad fires, good is quiet
# ---------------------------------------------------------------------------

def test_rp001_unseeded_random(tmp_path):
    bad = _write(tmp_path, "core/sim.py", """
        import numpy as np
        def draw():
            return np.random.uniform(0, 1)
    """)
    assert _rules_of(lint_file(bad)) == ["RP001"]
    bad2 = _write(tmp_path, "core/sim2.py", """
        import numpy as np
        def draw():
            return np.random.default_rng().uniform(0, 1)
    """)
    assert _rules_of(lint_file(bad2)) == ["RP001"]
    good = _write(tmp_path, "core/sim3.py", """
        import numpy as np
        def draw(seed):
            return np.random.default_rng(seed).uniform(0, 1)
    """)
    assert lint_file(good) == []


def test_rp002_wallclock(tmp_path):
    bad = _write(tmp_path, "fleet/traces.py", """
        import time
        def now():
            return time.time()
    """)
    assert _rules_of(lint_file(bad)) == ["RP002"]
    # perf_counter is a wall clock too: intervals read the obs clock
    assert _rules_of(lint_file(_write(tmp_path, "fleet/traces3.py", """
        import time
        def tick():
            return time.perf_counter()
    """))) == ["RP002"]
    good = _write(tmp_path, "fleet/traces2.py", """
        from repro.obs.clock import now
        def tick():
            return now()
    """)
    assert lint_file(good) == []


def test_rp003_hash_seed(tmp_path):
    bad = _write(tmp_path, "util/keys.py", """
        def seed_of(name):
            return hash(name) % 2**32
    """)
    assert _rules_of(lint_file(bad)) == ["RP003"]
    good = _write(tmp_path, "util/keys2.py", """
        import zlib
        def seed_of(name):
            return zlib.crc32(name.encode())
    """)
    assert lint_file(good) == []


def test_rp004_bare_assert_in_core(tmp_path):
    bad = _write(tmp_path, "core/flow.py", """
        def check(n, cap):
            assert n <= cap
    """)
    assert _rules_of(lint_file(bad)) == ["RP004"]
    # the same assert OUTSIDE core/ is fine
    ok = _write(tmp_path, "kernels/flow.py", """
        def check(n, cap):
            assert n <= cap
    """)
    assert lint_file(ok) == []
    good = _write(tmp_path, "core/flow2.py", """
        def check(n, cap):
            if n > cap:
                raise RuntimeError(f"cap violated: {n} > {cap}")
    """)
    assert lint_file(good) == []


def test_rp005_blockspec_divisibility(tmp_path):
    bad = _write(tmp_path, "kernels/attn.py", """
        import jax.experimental.pallas as pl
        def fwd(S, block_q):
            spec = pl.BlockSpec((block_q, 64), lambda i: (i, 0))
            return S // block_q, spec
    """)
    assert _rules_of(lint_file(bad)) == ["RP005"]
    good = _write(tmp_path, "kernels/attn2.py", """
        import jax.experimental.pallas as pl
        def fwd(S, block_q):
            if S % block_q:
                raise ValueError(f"{S} not divisible by {block_q}")
            spec = pl.BlockSpec((block_q, 64), lambda i: (i, 0))
            return S // block_q, spec
    """)
    assert lint_file(good) == []
    # full-dimension names (not block_*/chunk*) tile trivially: no finding
    triv = _write(tmp_path, "kernels/attn3.py", """
        import jax.experimental.pallas as pl
        def fwd(hd):
            return pl.BlockSpec((hd,), lambda i: (0,))
    """)
    assert lint_file(triv) == []


def test_rp006_statedict_version(tmp_path):
    bad = _write(tmp_path, "runtime/ckpt.py", """
        class Thing:
            def state_dict(self):
                return {"weights": self.w}
    """)
    assert _rules_of(lint_file(bad)) == ["RP006"]
    good = _write(tmp_path, "runtime/ckpt2.py", """
        class Thing:
            def state_dict(self):
                return {"version_tag": 3, "weights": self.w}
    """)
    assert lint_file(good) == []


# ---------------------------------------------------------------------------
# scope, allowlist, waiver
# ---------------------------------------------------------------------------

def test_hot_path_rules_exempt_data_and_launch(tmp_path):
    for seg in ("data", "launch"):
        f = _write(tmp_path, f"{seg}/loader.py", """
            import time
            import numpy as np
            def jitter():
                return np.random.uniform() + time.time()
        """)
        assert lint_file(f) == [], seg
    # ...but the identical code in core/ fires both hot-path rules
    f = _write(tmp_path, "core/loader.py", """
        import time
        import numpy as np
        def jitter():
            return np.random.uniform() + time.time()
    """)
    assert _rules_of(lint_file(f)) == ["RP001", "RP002"]


def test_waiver_comment_suppresses_one_line(tmp_path):
    f = _write(tmp_path, "core/sim.py", """
        import numpy as np
        def draw():
            a = np.random.uniform()  # lint: allow-unseeded-random
            b = np.random.uniform()
            return a + b
    """)
    errors = lint_file(f)
    assert len(errors) == 1 and errors[0].rule == "RP001"
    assert errors[0].line == 5


def test_syntax_error_reported_not_raised(tmp_path):
    f = _write(tmp_path, "core/broken.py", "def nope(:\n")
    errors = lint_file(f)
    assert len(errors) == 1 and errors[0].rule == "RP000"


def test_error_format_is_clickable(tmp_path):
    f = _write(tmp_path, "core/sim.py", """
        import numpy as np
        def draw():
            return np.random.uniform()
    """)
    msg = str(lint_file(f)[0])
    assert msg.startswith(f"{f}:4: RP001[unseeded-random] ")


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

def test_main_exit_codes(tmp_path, capsys):
    clean = _write(tmp_path, "ok/mod.py", "X = 1\n")
    assert main([str(clean)]) == 0
    bad = _write(tmp_path, "core/bad.py", "def f():\n    assert True\n")
    assert main([str(bad)]) == 1
    assert main([]) == 2                     # usage
    capsys.readouterr()


@pytest.mark.slow
def test_cli_subprocess_on_real_tree():
    out = subprocess.run(
        [sys.executable, "-m", "repro.analysis.lint", str(SRC)],
        capture_output=True, text=True,
        env={"PYTHONPATH": str(SRC), "PATH": "/usr/bin:/bin"})
    assert out.returncode == 0, out.stdout + out.stderr
    assert "clean" in out.stderr
