"""Named scopes of the round program: the op→scope table parsed from an
optimized HLO module, the scopes ``make_train_step`` puts on the round's
parts, and the executor's ``op_table`` span (only when tracing)."""
import contextlib
import re

import jax
import numpy as np
import pytest

from repro.configs import registry
from repro.core import fedopt_step as F
from repro.launch.mesh import make_debug_mesh
from repro.obs.scopes import (_INSTRUCTION, _OP_NAME, SCOPES, op_scopes,
                               scope_of)
from repro.obs.trace import Tracer, traced

HLO = """\
HloModule jit_step, is_scheduled=true

%fused_computation.1 (param_0: f32[8,64], param_1: f32[64,64]) -> f32[8,64] {
  %param_0 = f32[8,64]{1,0} parameter(0)
  %param_1 = f32[64,64]{1,0} parameter(1)
  ROOT %dot.3 = f32[8,64]{1,0} dot(%param_0, %param_1), lhs_contracting_dims={1}, rhs_contracting_dims={0}, metadata={op_name="jit(step)/while/body/server_half/transpose(jvp(ring))/dot_general" stack_frame_id=3}
}

ENTRY %main (p0: f32[8,64], p1: f32[64,64]) -> f32[8,64] {
  %p0 = f32[8,64]{1,0} parameter(0), metadata={op_name="state[\\'srv\\']"}
  %p1 = f32[64,64]{1,0} parameter(1)
  %fusion.1 = f32[8,64]{1,0} fusion(%p0, %p1), kind=kOutput, calls=%fused_computation.1, metadata={op_type="dot_general" op_name="jit(step)/while/body/closed_call/server_half/jvp()/dot_general" stack_frame_id=3}
  %copy-start.2 = (f32[8,64]{1,0}, f32[8,64]{1,0}, u32[]) copy-start(%fusion.1)
  %bitcast_dynamic-update-slice_fusion.4 = f32[8,64]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/while/body/closed_call/vmap(device_half)/transpose(jvp())/dynamic_update_slice"}
  %add.5 = f32[8,64]{1,0} add(%p0, %p0), metadata={op_name="jit(step)/while/body/add"}
  ROOT %multiply_reduce_fusion = f32[8,64]{1,0} fusion(%add.5), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(step)/aggregate/jit(_where)/select_n"}
}
"""


def test_op_scopes_innermost_scope_wins():
    table = op_scopes(HLO)
    # server_half/.../transpose(jvp(ring)): ring is the innermost scope
    assert table["dot.3"] == "ring"
    assert table["fusion.1"] == "server_half"
    assert table["bitcast_dynamic-update-slice_fusion.4"] == "device_half"
    assert table["multiply_reduce_fusion"] == "aggregate"


def test_op_scopes_names_unscoped_instructions_none():
    table = op_scopes(HLO)
    # no metadata; an op_name with none of the scopes; no op_name
    for name in ("copy-start.2", "add.5", "p0", "p1"):
        assert table[name] is None
    # every instruction, fused ones too, and nothing else
    assert set(table) == {"param_0", "param_1", "dot.3", "p0", "p1",
                          "fusion.1", "copy-start.2",
                          "bitcast_dynamic-update-slice_fusion.4", "add.5",
                          "multiply_reduce_fusion"}
    assert set(table.values()) <= set(SCOPES) | {None}


@pytest.mark.parametrize("op_name, want", [
    ("jit(step)/while/body/closed_call/vmap(device_half)/jvp()/dot", "device_half"),
    ("jit(step)/ring/server_half/add", "server_half"),
    ("jit(step)/while/body/closed_call/ring_buffer/add", None),
    ("jit(step)/aggregate", "aggregate"),
    ("", None),
])
def test_scope_of_matches_whole_path_parts(op_name, want):
    assert scope_of(op_name) == want


# ---------------------------------------------------------------------------
# the compiled round program at smoke size
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _no_persistent_cache():
    """Compile anew: the persistent cache keys a program without its
    metadata, so with it on (another test may have turned it on in this
    process) the bare build would load the scoped one's executable."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


def _smoke_round_text(arch="smollm-135m"):
    cfg = F.FedStepConfig(arch=registry.smoke_config(arch),
                          l_split=1, n_groups=2, seq_len=16,
                          per_group_batch=4, H=2)
    jitted, state, _, _ = F.jit_train_step(cfg, make_debug_mesh(1, 1))
    batch = F.concrete_train_batch(jax.random.PRNGKey(1), cfg)
    with _no_persistent_cache():
        return jitted.lower(state, batch).compile().as_text()


def _strip_debug_info(text):
    """The module without metadata and without its stack-frame tables."""
    out, skip = [], False
    for line in text.splitlines():
        if line in ("FileNames", "FunctionNames", "FileLocations",
                    "StackFrames"):
            skip = True
        elif not line.strip():
            skip = False
        if not skip:
            out.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    return "\n".join(out)


def _matmul_bearing(text):
    """Names of the instructions that are a dot or convolution, or a
    fusion whose fused computation holds one."""
    comps, called, cur = {}, {}, None
    for line in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) .*\{$", line)
        if head:
            cur = head.group(1)
            comps[cur] = set()
            continue
        ins = re.match(r"\s*(?:ROOT )?%([\w.\-]+) = .*? "
                       r"(dot|convolution|fusion)\(", line)
        if ins and cur is not None:
            comps[cur].add(ins.group(2))
            calls = re.search(r"calls=%([\w.\-]+)", line)
            called[ins.group(1)] = (ins.group(2),
                                    calls.group(1) if calls else None)
    return [n for n, (op, comp) in called.items()
            if op in ("dot", "convolution")
            or (op == "fusion" and comps.get(comp, set())
                & {"dot", "convolution"})]


@pytest.fixture(scope="module")
def smoke_text():
    return _smoke_round_text()


@pytest.fixture(scope="module")
def mamba_text():
    return _smoke_round_text("mamba2-780m")


def _op_names(text):
    """{instruction name: op_name} of the instructions that have one."""
    out = {}
    for m in _INSTRUCTION.finditer(text):
        op = _OP_NAME.search(m.group(0))
        if op:
            out[m.group(1)] = op.group(1)
    return out


@pytest.mark.parametrize("family, want", [
    # attention: every scope but the SSD scan's
    ("smoke_text", set(SCOPES) - {"ssd"}),
    # Mamba-2 (no attention): all of them, the scan among them
    ("mamba_text", set(SCOPES)),
])
def test_compiled_round_places_every_scope(family, want, request):
    text = request.getfixturevalue(family)
    table = op_scopes(text)
    placed = set(table.values()) - {None}
    assert placed == want, placed
    bearing = _matmul_bearing(text)
    assert bearing, "no dot-bearing instruction found"
    assert [n for n in bearing if table.get(n) is None] == []


def test_ssd_scope_runs_in_both_halves(mamba_text):
    """The scan's ops are counted under ``ssd`` in the device half (6 of
    48 layers at full size) and in the server half alike: the innermost
    scope wins, forward and transposed."""
    table = op_scopes(mamba_text)
    names = _op_names(mamba_text)
    ssd = [names[n] for n, s in table.items() if s == "ssd"]
    halves = {h for path in ssd for h in ("device_half", "server_half")
              if h in re.split(r"[/()]", path)}
    assert halves == {"device_half", "server_half"}, halves
    assert any("transpose" in path for path in ssd)


def test_scopes_are_metadata_only(smoke_text, monkeypatch):
    """Without the scopes the optimized module is the same text, once
    metadata and the stack-frame tables are stripped."""
    class NoScope(contextlib.ContextDecorator):
        def __init__(self, name):
            pass

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax, "named_scope", NoScope)
    bare = _smoke_round_text()
    assert set(op_scopes(bare).values()) == {None}
    assert _strip_debug_info(bare) == _strip_debug_info(smoke_text)


# ---------------------------------------------------------------------------
# the executor's op_table span
# ---------------------------------------------------------------------------

class _LowerableStep:
    """A synchronous step with the ``lower(...).compile().as_text()``
    chain of a jitted function; counts the lowerings."""

    def __init__(self):
        self.lowered = 0

    def __call__(self, state, batch):
        return state, {"d_loss": 1.0, "s_loss": 1.0}

    def lower(self, state, batch):
        self.lowered += 1

        class _Compiled:
            def as_text(self):
                return HLO

        class _Lowered:
            def compile(self):
                return _Compiled()

        return _Lowered()


def _run(step, rounds=3, window=2, state=None):
    from repro.core.control_plane import ControlPlane
    from repro.core.executor import RoundExecutor
    G = 2
    ex = RoundExecutor(step, ControlPlane(G, 1, 2), window=window)
    ex.run(state, 0, rounds, active_fn=lambda r: np.ones(G, bool),
           batch_fn=lambda r, plan: {})
    return ex


def test_no_op_table_and_no_lowering_when_tracing_is_off():
    step = _LowerableStep()
    _run(step)
    assert step.lowered == 0


def test_op_table_span_once_at_the_first_dispatch():
    step = _LowerableStep()
    with traced(Tracer(domain="wall")) as tr:
        _run(step)
    assert step.lowered == 1
    tables = [s for s in tr.spans if s[0] == "host/compile"]
    assert len(tables) == 1
    lane, name, t0, t1, args = tables[0]
    assert name == "op_table" and t1 >= t0
    assert args["round"] == 0
    assert args["op_scope"] == op_scopes(HLO)


@pytest.fixture
def fresh_compile_cache(tmp_path):
    """A persistent compilation cache of its own that keeps every entry;
    the process-wide settings come back after the test."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    before = {k: getattr(jax.config, k) for k in keys}
    cc.reset_cache()
    jax.config.update("jax_compilation_cache_dir", str(tmp_path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    yield
    for k, v in before.items():
        jax.config.update(k, v)
    cc.reset_cache()


def _toy_step(scoped):
    import jax.numpy as jnp

    def step(state, batch):
        with (jax.named_scope("ring") if scoped
              else contextlib.nullcontext()):
            y = jnp.sin(state["x"]) * 2.0
        return {"x": y + 1.0}, {"d_loss": jnp.sum(y), "s_loss": jnp.sum(y)}
    return jax.jit(step)


def test_op_table_names_the_scopes_of_the_code_that_runs(
        fresh_compile_cache):
    """The persistent cache keys a program without its metadata: after
    an unscoped build of the same program filled it, the traced run's
    table still holds the scopes of the code it runs."""
    import jax.numpy as jnp
    state = {"x": jnp.ones((64, 128), jnp.float32)}
    _toy_step(False)(state, {})[1]["d_loss"].block_until_ready()
    step = _toy_step(True)
    with traced(Tracer(domain="wall")) as tr:
        _run(step, state=state)
    (table,) = [a["op_scope"] for ln, _, _, _, a in tr.spans
                if ln == "host/compile"]
    assert "ring" in table.values()
    assert jax.config.jax_compilation_cache_include_metadata_in_key is False
