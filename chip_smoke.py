#!/usr/bin/env python3
"""Chip smoke test: the FedOptima pod round on a TPU, in one process.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips: the multi-chip pod phase only

One chip runs three phases, each of which must pass:

1. the pod path through ``repro.launch.train`` (``run_pod``, the function
   behind ``--mode pod``) for smollm-135m at its published widths: the
   equivalent of ``--mode pod --arch smollm-135m --full --seq-len 2048
   --batch 8 --H 4 --groups-per-shard 4 --window 2`` for six rounds
   (G = 4 groups, split after 3 of 30 layers).  Every round's losses are
   finite, and round 1's are near ln(vocab), as a random init gives;
2. the Pallas kernels, forward and gradient, compiled for the chip (the
   compiled program must hold ``tpu_custom_call``: nothing interpreted)
   and compared with the plain references in ``kernels/ref.py``;
3. two rounds of the same pod configuration with ``use_kernel=True``,
   whose round-1 losses must match phase 1's.

``--chips 4`` instead runs the pod path on a (data=4, model=1) mesh and a
(2, 2) mesh, each against the same G, seed and configuration on one
chip, and checks that the train state really spans the four chips.

Compile seconds, round times and peak memory are printed as smoke
readings, not benchmark metrics.  The last line of standard output is
the JSON contract line, printed only when every phase passed; a failed
phase exits non-zero before it.  Without a TPU the script exits non-zero
at once.
"""
from __future__ import annotations

import argparse
import json
import math
import statistics
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "smollm-135m"
SEQ_LEN = 2048
BATCH = 8              # sequences per group per round
H = 4                  # local iterations per round
WINDOW = 2
SEED = 0
# Round-1 losses of a random init sit near ln(vocab): the logits start
# near zero.  One nat of slack covers the init's logit spread and the
# SGD steps taken within round 1 (lr 0.05, H=4).
INIT_LOSS_SLACK = 1.0
# Two runs of the same round that differ only in how attention is
# computed (Pallas kernel vs the chunked jnp path), or in how the state is
# laid out over chips, agree to reduction order and to the chip's f32
# matmul passes: a relative 5e-3 of a ~10.8-nat loss is ~0.05 nats.
LOSS_RTOL = 5e-3
# Kernel vs kernels/ref.py, as a relative L2 error of the whole output or
# gradient.  The reference runs at "highest" matmul precision; 2e-2 still
# admits a kernel whose f32 matmuls ran as single bf16 passes (~4e-3 in
# the norm; on a v5e the SSD gradient, whose reverse scan compounds that
# rounding over chunks, reads up to 1.7e-2).  A wrong mask, block index or
# transposition is O(1).
KERNEL_REL_L2 = 2e-2
KERNEL = 'custom_call_target="tpu_custom_call"'


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def log(msg: str) -> None:
    print(msg, flush=True)


class CompileClock:
    """Sums XLA backend compile seconds, read per phase."""

    def __init__(self):
        import jax
        from jax._src import dispatch
        self.total = 0.0
        self._event = dispatch.BACKEND_COMPILE_EVENT
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == self._event:
            self.total += duration

    def since(self, mark: float) -> float:
        return self.total - mark


# ---------------------------------------------------------------------------
# pod path
# ---------------------------------------------------------------------------

def run_pod_phase(*, rounds: int, mesh=(1, 1), groups_per_shard: int = 4,
                  use_kernel: bool = False, full: bool = True,
                  arch: str = ARCH, seq_len: int = SEQ_LEN,
                  batch: int = BATCH, h: int = H) -> dict:
    """``launch.train``'s pod mode, from its own argument parser."""
    from repro.launch import train
    argv = ["--mode", "pod", "--arch", arch, "--seq-len", str(seq_len),
            "--batch", str(batch), "--H", str(h),
            "--groups-per-shard", str(groups_per_shard),
            "--window", str(WINDOW), "--rounds", str(rounds),
            "--mesh-data", str(mesh[0]), "--mesh-model", str(mesh[1]),
            "--seed", str(SEED)]
    if full:
        argv.append("--full")
    if use_kernel:
        argv.append("--use-kernel")
    args = train.build_parser().parse_args(argv)
    t0 = time.perf_counter()
    out = train.run_pod(args)
    out["wall_s"] = time.perf_counter() - t0
    return out


def check_losses(out: dict, rounds: int, vocab: int, tag: str) -> None:
    hist = out["history"]
    if len(hist) != rounds:
        fail(f"{tag}: {len(hist)} rounds reported, {rounds} run")
    for r, m in enumerate(hist, 1):
        for k in ("d_loss", "s_loss"):
            if not math.isfinite(m[k]):
                fail(f"{tag}: round {r} {k} = {m[k]}")
    ln_v = math.log(vocab)
    for k in ("d_loss", "s_loss"):
        if abs(hist[0][k] - ln_v) > INIT_LOSS_SLACK:
            fail(f"{tag}: round 1 {k} = {hist[0][k]:.4f}, expected "
                 f"ln({vocab}) = {ln_v:.4f} +- {INIT_LOSS_SLACK}")


def compare_round1(a: dict, b: dict, tag: str) -> dict:
    out = {}
    for k in ("d_loss", "s_loss"):
        x, y = a["history"][0][k], b["history"][0][k]
        rel = abs(x - y) / abs(y)
        out[k] = (x, y, rel)
        if not rel <= LOSS_RTOL:
            fail(f"{tag}: round 1 {k} {x!r} vs {y!r} (rel {rel:.2e} > "
                 f"{LOSS_RTOL})")
    return out


def steady_round_s(out: dict) -> float:
    """Median round wall after the first ``WINDOW`` rounds (compile and
    pipeline fill).  Each round is timed when the executor's blocking
    fetch of its metrics returns: they are outputs of the same program
    as the state, so the fetch waits for the round to finish."""
    walls = out["round_wall_s"][WINDOW:]
    return statistics.median(walls) if walls else float("nan")


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------

def _rel_l2(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    return float(np.linalg.norm(got - want) / max(np.linalg.norm(want),
                                                  1e-30))


def _check_kernel(name: str, kern, refr, args) -> None:
    """Forward and gradient of ``kern`` against ``refr``."""
    import jax
    import jax.numpy as jnp
    argnums = tuple(range(len(args)))

    def grads(fn):
        return jax.grad(lambda *a: jnp.sum(jnp.sin(
            fn(*a).astype(jnp.float32))), argnums=argnums)

    for what, k_fn, r_fn in (("fwd", kern, refr),
                             ("grad", grads(kern), grads(refr))):
        compiled = jax.jit(k_fn).lower(*args).compile()
        n = compiled.as_text().count(KERNEL)
        if n == 0:
            fail(f"{name} {what}: no tpu_custom_call in the compiled "
                 "program — the kernel did not compile for the chip")
        got = jax.block_until_ready(compiled(*args))
        with jax.default_matmul_precision("highest"):
            want = jax.block_until_ready(jax.jit(r_fn)(*args))
        got_l, want_l = jax.tree.leaves(got), jax.tree.leaves(want)
        errs = [_rel_l2(g, w) for g, w in zip(got_l, want_l)]
        finite = all(bool(jnp.all(jnp.isfinite(g))) for g in got_l)
        log(f"kernel {name} {what}: {n} tpu_custom_call, rel L2 err "
            f"{', '.join(f'{e:.2e}' for e in errs)} (limit {KERNEL_REL_L2})")
        if not finite or max(errs) > KERNEL_REL_L2:
            fail(f"{name} {what}: finite={finite}, rel L2 errors {errs}")


def check_kernels() -> None:
    """smollm-135m attention widths (B=2, S=2048, H=9, Hkv=3, hd=64) and
    mamba2-780m SSD widths (H=48, P=64, N=128, chunk 256).  The SSD
    check uses B=1, T=1024: the sequential reference's gradient keeps
    every step's (H, N, P) state."""
    import jax
    import jax.numpy as jnp
    from repro.configs import registry
    from repro.kernels import ops, ref

    ks = jax.random.split(jax.random.PRNGKey(SEED), 8)
    B, S, Hq, Hkv, hd = 2, 2048, 9, 3, 64
    q = jax.random.normal(ks[0], (B, S, Hq, hd), jnp.float32)
    k = jax.random.normal(ks[1], (B, S, Hkv, hd), jnp.float32)
    v = jax.random.normal(ks[2], (B, S, Hkv, hd), jnp.float32)
    _check_kernel(
        "flash_attention",
        lambda q, k, v: ops.flash_attention(q, k, v, causal=True,
                                            interpret=False),
        lambda q, k, v: ref.flash_attention_reference(q, k, v, causal=True),
        (q, k, v))

    m = registry.get("mamba2-780m").mamba_cfg()
    Bs, T, Hs, P, G, N = 1, 1024, m.n_heads, m.head_dim, m.n_groups, m.d_state
    x = jax.random.normal(ks[3], (Bs, T, Hs, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[4], (Bs, T, Hs)) - 1.0)
    A = -jnp.exp(jax.random.normal(ks[5], (Hs,)) * 0.5)
    Bm = jax.random.normal(ks[6], (Bs, T, G, N)) * 0.5
    Cm = jax.random.normal(ks[7], (Bs, T, G, N)) * 0.5
    _check_kernel(
        "ssd",
        lambda *a: ops.ssd(*a, chunk=m.chunk, interpret=False),
        lambda *a: ref.ssd_reference(*a)[0],
        (x, dt, A, Bm, Cm))


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------

def one_chip(clock: CompileClock, device) -> None:
    from repro.configs import registry
    vocab = registry.get(ARCH).vocab
    rounds = 6

    mark = clock.total
    base = run_pod_phase(rounds=rounds)
    check_losses(base, rounds, vocab, "pod")
    log(f"smoke reading: pod {ARCH} G=4 seq {SEQ_LEN} batch {BATCH} H={H}: "
        f"compile {clock.since(mark):.1f} s, median round after warmup "
        f"{steady_round_s(base):.4f} s, rounds "
        f"{[round(w, 4) for w in base['round_wall_s']]}, wall "
        f"{base['wall_s']:.1f} s")
    log(f"smoke reading: round-1 d_loss {base['history'][0]['d_loss']:.4f} "
        f"s_loss {base['history'][0]['s_loss']:.4f} (ln vocab "
        f"{math.log(vocab):.4f}); round-{rounds} d_loss "
        f"{base['history'][-1]['d_loss']:.4f} s_loss "
        f"{base['history'][-1]['s_loss']:.4f}")
    peak = device.memory_stats().get("peak_bytes_in_use")
    log(f"smoke reading: peak_bytes_in_use after the pod phase {peak}")
    base.pop("state")

    mark = clock.total
    check_kernels()
    log(f"smoke reading: kernel phase compile {clock.since(mark):.1f} s")

    mark = clock.total
    kern = run_pod_phase(rounds=2, use_kernel=True)
    check_losses(kern, 2, vocab, "pod use_kernel")
    d = compare_round1(kern, base, "use_kernel vs fallback")
    log(f"use_kernel round 1 vs fallback: "
        + ", ".join(f"{k} {x:.6f} vs {y:.6f} (rel {r:.2e})"
                    for k, (x, y, r) in d.items())
        + f"; compile {clock.since(mark):.1f} s, rounds "
        f"{[round(w, 4) for w in kern['round_wall_s']]}")


def four_chips(clock: CompileClock, devices, *, rounds: int = 3,
               full: bool = True, arch: str = ARCH, seq_len: int = SEQ_LEN,
               batch: int = BATCH, h: int = H) -> None:
    """G = 4 on (4, 1), (2, 2) and one chip; state spread over 4 chips."""
    import jax
    from repro.configs import registry
    cfg = registry.get(arch) if full else registry.smoke_config(arch)
    kw = dict(rounds=rounds, full=full, arch=arch, seq_len=seq_len,
              batch=batch, h=h)

    mark = clock.total
    ref = run_pod_phase(mesh=(1, 1), groups_per_shard=4, **kw)
    check_losses(ref, rounds, cfg.vocab, "one chip")
    ref.pop("state")
    log(f"smoke reading: one chip: compile {clock.since(mark):.1f} s, "
        f"rounds {[round(w, 4) for w in ref['round_wall_s']]}")
    for mesh, gps in (((4, 1), 1), ((2, 2), 2)):
        tag = f"mesh {mesh}"
        mark = clock.total
        out = run_pod_phase(mesh=mesh, groups_per_shard=gps, **kw)
        check_losses(out, rounds, cfg.vocab, tag)
        d = compare_round1(out, ref, f"{tag} vs one chip")
        state = out.pop("state")
        spans = {k: sorted({len(x.sharding.device_set)
                            for x in jax.tree.leaves(state[k])})
                 for k in ("dev", "srv")}
        if spans["dev"] != [4] or 4 not in spans["srv"]:
            fail(f"{tag}: state does not span 4 chips: device-set sizes "
                 f"{spans}")
        del state
        peaks = [(dv.memory_stats() or {}).get("peak_bytes_in_use", 0)
                 for dv in devices[:4]]
        if min(peaks) <= 0:
            fail(f"{tag}: a chip shows no memory use: {peaks}")
        log(f"{tag}: round 1 vs one chip: "
            + ", ".join(f"{k} {x:.6f} vs {y:.6f} (rel {r:.2e})"
                        for k, (x, y, r) in d.items())
            + f"; device-set sizes dev {spans['dev']} srv {spans['srv']}; "
            f"peak_bytes_in_use per chip {peaks}")
        log(f"smoke reading: {tag}: compile {clock.since(mark):.1f} s, "
            f"rounds {[round(w, 4) for w in out['round_wall_s']]}")


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    p.add_argument("--chips", type=int, choices=(1, 4), default=1,
                   help="4 runs only the four-chip pod phase")
    a = p.parse_args()
    if not (SRC / "repro").is_dir():
        fail(f"no repro package under {SRC}: run from a checkout")
    sys.path.insert(0, str(SRC))

    import jax
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        fail(f"no TPU: JAX's first device is {dev.platform} ({dev})")
    log(f"device_kind {dev.device_kind}  count {len(devices)}  "
        f"jax {jax.__version__}")
    if len(devices) < a.chips:
        fail(f"--chips {a.chips} needs {a.chips} devices, found "
             f"{len(devices)}")
    log(f"compile cache: {enable_compile_cache()}")
    clock = CompileClock()
    if a.chips == 4:
        four_chips(clock, devices)
    else:
        one_chip(clock, dev)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
