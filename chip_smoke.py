#!/usr/bin/env python3
"""Smoke run of the DiOMP-JAX main path on a TPU, in one process.

    python chip_smoke.py              # one chip: serving, training, Minimod
    python chip_smoke.py --chips 4    # four chips: the cross-chip kernels only

One chip, phases in order, each failing loudly:

1. device check — the first device must be a TPU; never falls back to CPU;
2. serving — stablelm-3b at its published widths (random weights, seed 0)
   through ``repro.launch.serve.main``; one prompt's first-token
   logits are checked against a cache-free forward on the chip;
3. training — stablelm-3b widths, depth cut to 4 layers, through
   ``build_train_step`` as ``repro.launch.train`` drives it, on one repeated
   batch; the loss must be finite and end below its first value;
4. Minimod — a 512³ f32 grid, ``mode="fused"``, checked against the
   ``wave_step_ref`` oracle looped on the chip.

``--chips 4`` runs only what exists across chips: Minimod on a 4-way Z
decomposition (fused one-sided vs the two-sided listing vs the one-device
oracle) and the fused ring all-gather matmul at the stablelm MLP widths vs
its all-gather reference.

Every phase prints its compile and wall seconds (smoke timings, not
benchmark numbers) and how many Pallas kernels (``tpu_custom_call``) its
programs hold.  The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""

from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent

# bf16 model: the engine's chunked-prefill logits vs a whole-prompt forward
LOGIT_TOL = 5e-2          # max |diff| over max |reference logit|
STENCIL_TOL = 1e-4        # f32: max |diff| over max |oracle|
RING_TOL = 1e-2           # bf16 outputs, f32 accumulation


class SmokeFailure(Exception):
    """A phase did not pass; the process exits non-zero."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def log(*a) -> None:
    print(*a, flush=True)


# -- compile accounting ------------------------------------------------------

class CompileClock:
    """Backend-compile seconds and persistent-cache hits, from JAX's own
    monitoring events, so a phase can report what it spent compiling."""

    def __init__(self):
        from jax import monitoring

        self.seconds = 0.0
        self.hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def mark(self):
        return self.seconds, self.hits


def kernels_in(text: str) -> int:
    return text.count("tpu_custom_call")


def peak_bytes(dev) -> int:
    stats = dev.memory_stats() or {}
    return int(stats.get("peak_bytes_in_use", -1))


# -- phase 1 -----------------------------------------------------------------

def device_check(n_expected: int):
    import jax
    import jaxlib

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SmokeFailure(
            f"no TPU found: jax.devices()[0].platform is "
            f"{devs[0].platform!r}; this smoke run needs a TPU and does "
            f"not fall back to {devs[0].platform!r}")
    check(len(devs) >= n_expected,
          f"need {n_expected} TPU chips, found {len(devs)}")
    try:
        from importlib.metadata import version
        libtpu = version("libtpu")
    except Exception:
        libtpu = "not installed as a package"
    log(f"[device] kind={devs[0].device_kind} count={len(devs)} "
        f"jax={jax.__version__} jaxlib={jaxlib.__version__} "
        f"libtpu={libtpu}")
    return devs


# -- phase 2: serving --------------------------------------------------------

def serve_phase(clock, *, arch="stablelm-3b", reduced=False, slots=4,
                max_len=1024, requests=8, min_prompt=128, max_prompt=512,
                max_new=32, chunk=128):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.core.compat import shard_map
    from repro.distributed.sharding import rules_for_ctx
    from repro.launch import serve
    from repro.models import schema as sch
    from repro.models.transformer import transformer_forward

    c0, h0 = clock.mark()
    t0 = time.perf_counter()
    eng, reqs = serve.main([
        "--arch", arch, "--reduced" if reduced else "--no-reduced",
        "--slots", str(slots), "--max-len", str(max_len),
        "--requests", str(requests), "--min-prompt", str(min_prompt),
        "--max-prompt", str(max_prompt), "--max-new", str(max_new),
        "--prefill-chunk", str(chunk)])
    wall = time.perf_counter() - t0
    c1, h1 = clock.mark()
    check(all(r.done and len(r.out) == max_new for r in reqs),
          "serving: not every request finished")

    # which implementation ran: Pallas kernels in the engine's programs
    cfg, mesh, params = eng.cfg, eng.mesh, eng.params
    n_dec = kernels_in(eng.decode_step.lower(
        params, jnp.asarray(eng.pending), eng.cache).as_text())
    n_pre = kernels_in(eng.chunk_step.lower(
        params, jnp.zeros((1, eng.chunk), jnp.int32), eng._slot_cache(0),
        jnp.asarray(1, jnp.int32)).as_text())

    # first-token logits of one prompt vs a cache-free forward on the chip
    ctx = eng.ctx
    pspecs = sch.partition_specs(cfg, mesh, rules_for_ctx(ctx))

    def forward(p, tokens):
        h, _ = transformer_forward(p, tokens, cfg, ctx)
        return jnp.dot(h[:, -1].astype(jnp.float32),
                       p["lm_head"].astype(jnp.float32))

    ref_fn = jax.jit(shard_map(forward, mesh=mesh, in_specs=(pspecs, P()),
                               out_specs=P()))
    stats = eng.latency_stats()
    # serve the first prompt once more, keeping its last prefill chunk's
    # logits: the row that chooses the first token
    req = reqs[0]
    chunk_step, rows = eng.chunk_step, []

    def keep(*a):
        out = chunk_step(*a)
        rows.append(out[0])
        return out

    eng.chunk_step = keep
    eng.submit(req.prompt, max_new=1)
    eng.run()
    eng.chunk_step = chunk_step
    want = np.asarray(ref_fn(params, jnp.asarray(req.prompt[None])))[0]
    got = np.asarray(rows[-1], np.float32)[0, 0]
    err = float(np.abs(got - want).max() / np.abs(want).max())
    log(f"[serve] {cfg.name}: {len(reqs)} requests, prompts "
        f"{min(len(r.prompt) for r in reqs)}-"
        f"{max(len(r.prompt) for r in reqs)} tokens, max_new={max_new}, "
        f"slots={slots}, max_len={max_len}, "
        f"params={cfg.param_count() / 1e9:.3f}B")
    log(f"[serve] compile_s={c1 - c0:.2f} (cache hits {h1 - h0}) "
        f"wall_s={wall:.2f} (incl. compile) engine_steps={stats['engine_steps']} "
        f"device_calls={stats['device_calls']} "
        f"decode_steps={sum(r.decode_steps for r in reqs)} "
        f"preemptions={stats['preemptions']} "
        f"peak_bytes_in_use={peak_bytes(jax.devices()[0])}")
    log(f"[serve] tpu_custom_call: decode={n_dec} chunk_prefill={n_pre}")
    log(f"[serve] first-token logits vs cache-free forward: max|diff|/max|ref|"
        f"={err:.3e} (tol {LOGIT_TOL}) argmax {int(got.argmax())} vs "
        f"{int(want.argmax())}")
    check(np.isfinite(got).all() and err <= LOGIT_TOL,
          f"serving: first-token logits off by {err:.3e} of the reference")
    log("[serve] PASS")


# -- phase 3: training -------------------------------------------------------

def train_phase(clock, *, arch="stablelm-3b", layers=4, reduced=False,
                batch=8, seq=1024, steps=5, lr=1e-3):
    import jax
    import jax.numpy as jnp

    from repro import configs
    from repro.data.pipeline import SyntheticLM
    from repro.launch.mesh import make_smoke_mesh
    from repro.models import schema as sch
    from repro.models.config import ParallelCtx
    from repro.train.optim import adamw, cosine_schedule
    from repro.train.step import build_train_step

    base = configs.get_reduced(arch) if reduced else configs.get(arch)
    cfg = dataclasses.replace(base, num_layers=layers)
    log(f"[train] {arch} widths, depth cut {base.num_layers} -> {layers} "
        f"layers: AdamW state for all {base.param_count() / 1e9:.2f}B "
        f"parameters does not fit one chip's 16 GB")
    mesh = make_smoke_mesh(len(jax.devices()))
    ctx = ParallelCtx.from_mesh(mesh, remat=True, microbatch=1,
                                grad_codec="none", dp_backend="hierarchical")
    opt = adamw(cosine_schedule(lr, warmup=max(steps // 10, 1), total=steps))
    step_fn = build_train_step(cfg, mesh, ctx, opt, optimizer_name="adamw",
                               donate=True, global_batch=batch)
    params = sch.init_params(cfg, jax.random.PRNGKey(0))
    opt_state = jax.jit(opt.init)(params)
    # one fixed batch, every step: uniform random tokens carry nothing to
    # learn, so only memorizing a batch shows the gradients flow
    tokens = SyntheticLM(cfg, batch, seq, seed=17).batch_at(0)

    c0, h0 = clock.mark()
    t0 = time.perf_counter()
    compiled = step_fn.lower(params, opt_state, tokens,
                             jnp.asarray(0)).compile()
    t_compile = time.perf_counter() - t0
    c1, h1 = clock.mark()
    losses = []
    t1 = time.perf_counter()
    for i in range(steps):
        params, opt_state, metrics = compiled(
            params, opt_state, tokens, jnp.asarray(i))
        losses.append(float(metrics["loss"]))
        log(f"[train] step {i} loss {losses[-1]:.4f} "
            f"gnorm {float(metrics['grad_norm']):.3f}")
    wall = time.perf_counter() - t1
    log(f"[train] {cfg.name} x{layers} layers, one batch {batch} x seq "
        f"{seq} repeated, params={cfg.param_count() / 1e9:.3f}B")
    log(f"[train] compile_s={t_compile:.2f} (backend {c1 - c0:.2f}, cache "
        f"hits {h1 - h0}) wall_s={wall:.2f} for {steps} steps "
        f"peak_bytes_in_use={peak_bytes(jax.devices()[0])}")
    log(f"[train] tpu_custom_call: step={kernels_in(compiled.as_text())}")
    check(all(np.isfinite(losses)), f"training: non-finite loss {losses}")
    check(losses[-1] < losses[0],
          f"training: loss did not fall ({losses[0]:.4f} -> {losses[-1]:.4f})")
    log(f"[train] PASS loss {losses[0]:.4f} -> {losses[-1]:.4f}")


# -- phase 4: Minimod --------------------------------------------------------

def stencil_oracle(u0, steps: int, c2dt2: float = 0.1):
    """``wave_step_ref`` looped on one device (the oracle of every mode)."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from repro.kernels.stencil.ref import wave_step_ref

    @jax.jit
    def run(u):
        def body(carry, _):
            u, up = carry
            return (wave_step_ref(u, up, c2dt2), u), None
        (u, _), _ = lax.scan(body, (u, jnp.zeros_like(u)), None,
                             length=steps)
        return u

    return np.asarray(run(jnp.asarray(u0)))


def point_source(grid):
    u0 = np.zeros(grid, np.float32)
    u0[grid[0] // 2, grid[1] // 2, grid[2] // 2] = 1.0
    return u0


def stencil_err(got, want) -> float:
    return float(np.abs(got - want).max() / np.abs(want).max())


def minimod_phase(clock, *, grid=(512, 512, 512), steps=20, nz=1,
                  interpret=False):
    import jax

    from repro.apps.minimod import run_minimod

    r = run_minimod(grid=grid, nz=nz, mode="fused", steps=steps,
                    interpret=interpret)
    want = stencil_oracle(point_source(grid), steps)
    err = stencil_err(r.field, want)
    path = ("the Pallas stencil kernel" if r.kernel_calls
            else "the XLA emulation, not a Pallas kernel")
    log(f"[minimod] grid {'x'.join(map(str, grid))} f32, {steps} steps, "
        f"nz={nz}, mode=fused: compile_s={r.compile_s:.2f} "
        f"wall_s={r.wall_s:.3f} "
        f"peak_bytes_in_use={peak_bytes(jax.devices()[0])}")
    log(f"[minimod] tpu_custom_call={r.kernel_calls}: Minimod ran {path}")
    log(f"[minimod] vs wave_step_ref oracle: max|diff|/max|ref|={err:.3e} "
        f"(tol {STENCIL_TOL})")
    check(np.isfinite(r.field).all() and err <= STENCIL_TOL,
          f"minimod: fused field off by {err:.3e} of the oracle")
    log("[minimod] PASS")


# -- --chips 4: the cross-chip path ------------------------------------------

def minimod_cross_chip(*, grid=(4 * 512, 512, 512), steps=20, nz=4,
                       interpret=False):
    from repro.apps.minimod import run_minimod

    want = stencil_oracle(point_source(grid), steps)
    fields = {}
    for mode in ("fused", "none"):
        r = run_minimod(grid=grid, nz=nz, mode=mode, steps=steps,
                        interpret=interpret)
        err = stencil_err(r.field, want)
        fields[mode] = r.field
        log(f"[minimod x{nz}] mode={mode}: compile_s={r.compile_s:.2f} "
            f"wall_s={r.wall_s:.3f} tpu_custom_call={r.kernel_calls} "
            f"(make_async_remote_copy kernel ran: "
            f"{'yes' if r.kernel_calls else 'no, XLA collectives'}) "
            f"vs oracle {err:.3e}")
        check(np.isfinite(r.field).all() and err <= STENCIL_TOL,
              f"minimod x{nz} {mode}: off by {err:.3e} of the oracle")
    d = stencil_err(fields["fused"], fields["none"])
    log(f"[minimod x{nz}] fused vs two-sided: {d:.3e}")
    check(d <= STENCIL_TOL, f"minimod x{nz}: fused != two-sided ({d:.3e})")
    log(f"[minimod x{nz}] PASS fused == two-sided == oracle")


def ring_matmul_cross_chip(*, tokens=4096, k=2560, n=6912,
                           interpret=False):
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.compat import make_mesh, shard_map
    from repro.core.context import DiompContext, use_default
    from repro.core.groups import DiompGroup
    from repro.kernels.ring_matmul.fused import fused_ring_allgather_matmul
    from repro.kernels.ring_matmul.ref import ring_allgather_matmul_ref

    nd = len(jax.devices())
    mesh = make_mesh((nd,), ("x",))
    group = DiompGroup(("x",), name="ring")
    kx, kw = jax.random.split(jax.random.PRNGKey(0))
    x = jax.device_put(
        (jax.random.normal(kx, (tokens, k), jnp.float32) * 0.5)
        .astype(jnp.bfloat16), NamedSharding(mesh, P("x")))
    w = jax.device_put(
        (jax.random.normal(kw, (k, n), jnp.float32) * k ** -0.5)
        .astype(jnp.bfloat16), NamedSharding(mesh, P(None, "x")))

    def build(body):
        return jax.jit(shard_map(body, mesh=mesh,
                                 in_specs=(P("x"), P(None, "x")),
                                 out_specs=P(None, "x")))

    with use_default(DiompContext(mesh=mesh)):
        fused = build(lambda a, b: fused_ring_allgather_matmul(
            a, b, group, interpret=interpret))
        ref = build(lambda a, b: ring_allgather_matmul_ref(a, b, group))
        out = {}
        for name, fn in (("fused", fused), ("reference", ref)):
            t0 = time.perf_counter()
            compiled = fn.lower(x, w).compile()
            t1 = time.perf_counter()
            y = jax.block_until_ready(compiled(x, w))
            t2 = time.perf_counter()
            out[name] = np.asarray(y, np.float32)
            n_k = kernels_in(compiled.as_text())
            log(f"[ring matmul x{nd}] {name}: tokens {tokens} K {k} N {n} "
                f"bf16: compile_s={t1 - t0:.2f} wall_s={t2 - t1:.4f} "
                f"tpu_custom_call={n_k}")
            if name == "fused":
                log(f"[ring matmul x{nd}] make_async_remote_copy kernel ran: "
                    f"{'yes' if n_k else 'no, the ompx_put emulation'}")
    err = float(np.abs(out["fused"] - out["reference"]).max()
                / np.abs(out["reference"]).max())
    log(f"[ring matmul x{nd}] fused vs ring_allgather_matmul_ref: "
        f"max|diff|/max|ref|={err:.3e} (tol {RING_TOL})")
    check(np.isfinite(out["fused"]).all() and err <= RING_TOL,
          f"ring matmul: fused off by {err:.3e} of the reference")
    log(f"[ring matmul x{nd}] PASS")


# -- driver ------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serving, training, Minimod on one chip; "
                         "4: only the cross-chip kernels on four")
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: FAIL: no program next to this script "
              f"(expected {src / 'repro'})", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    try:
        devs = device_check(args.chips)
        from repro.launch.compile_cache import enable_compile_cache

        log(f"[cache] persistent compilation cache: {enable_compile_cache()}")
        clock = CompileClock()
        t0 = time.perf_counter()
        if args.chips == 1:
            serve_phase(clock)
            gc.collect()
            train_phase(clock)
            gc.collect()
            minimod_phase(clock)
        else:
            minimod_cross_chip()
            gc.collect()
            ring_matmul_cross_chip()
        log(f"[done] all phases passed in {time.perf_counter() - t0:.1f}s; "
            f"backend compile {clock.seconds:.1f}s, cache hits {clock.hits}")
    except SmokeFailure as e:
        print(f"chip_smoke: FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
