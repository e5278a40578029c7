"""Smoke run of the training path on TPU chips, in one process.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four chips of one host

One chip: ``repro.api.fit`` trains gpt-2b at its published widths (depth
cut to fit one 16 GB v5e chip) for a few steps with donated state, and the
step-1 loss is checked against the same model's ``model.loss`` at full f32
matmul precision.  Then the Pallas flash-attention kernel, compiled by
Mosaic, runs forward and backward at gpt-2b's attention widths and is
checked against ``attention_ref``.

Four chips: only the pipeline step (``make_pipeline_train_step``, two
stages over a ("pod", "data", "model") = (2, 1, 2) mesh) takes a few steps
at gpt-2b widths, and its step-1 loss and gradient norm are checked against
the single-program ``model.loss`` on the same params and batch.

Progress and every checked number go to stdout.  The last line is one JSON
object, ``{"ok": true, "device": {"platform", "kind", "count"}}``, printed
only when every check passed.  Without a TPU, or without the repo's
``src/`` next to this file, the script exits non-zero and prints no result.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import math
import statistics
import sys
import tempfile
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"

ARCH = "gpt-2b"
SEED = 0
# one chip: 4 of gpt-2b's 32 layers at batch 8 x 1024, f32 params + AdamW,
# compile to ~14 GB of the chip's 16 GB with donated state (6 layers: ~17.8)
TRAIN_LAYERS, TRAIN_BATCH, TRAIN_STEPS = 4, 8, 8
FLASH_BATCH = 4
# the pipeline takes the one-chip run's steps, batches and schedule, so the
# two loss curves can be read side by side
PIPE_LAYERS, PIPE_MICROBATCHES = 4, 4
PIPE_MESH = (2, 1, 2)    # (pod, data, model): 2 stages x 2-way tensor parallel
PIPE_ACT_DTYPE = "bfloat16"   # the chip's compute dtype for activations

# Tolerances, set before the first chip run.
# - The training step runs f32 matmuls at the chip's default precision, which
#   rounds operands to bf16 (8 significant bits); the reference runs them at
#   "highest".  At random init the loss is a mean over 8192 tokens of
#   unbiased rounding noise, expected near 1e-3: 2e-2 keeps a 10x margin and
#   still catches a wrong batch, parameter set or mask (> 0.1).
STEP1_LOSS_ATOL = 2e-2
# - Random init puts the logits at a std near 1.6 (unit-RMS hidden states
#   times a fan-in scaled head), which lifts the expected loss about 1.3
#   nats above ln(vocab); a broken model lands far outside 2 nats.
INIT_LOSS_NATS = 2.0
# - The flash kernel may also run its f32 dots as bf16 MXU passes: errors up
#   to 2e-2 of the largest reference entry, as the interpret-mode tests allow
#   for bf16 inputs.
FLASH_REL_TOL = 2e-2
# - The pipeline step casts activations to bf16 (the chip's compute dtype);
#   the reference stays f32 at "highest".  bf16 rounding of the residual
#   stream over 4 layers moves the loss by ~1e-2 and the gradient norm by a
#   few percent.
PIPE_LOSS_ATOL = 5e-2
PIPE_GRAD_NORM_RTOL = 5e-2
# - After the steps every device holds its shards of the placed state and
#   little else: on a v5e 2x2 the sound run read a least/most bytes_in_use
#   ratio of 0.979, a run that left the unplaced parameters on the first
#   device 0.519 (that device held 4.91 GB, the others 2.55 GB).
MEM_BALANCE_MIN = 0.9
MEM_EXCESS_MAX = 0.1


def log(msg: str) -> None:
    print(msg, flush=True)


class Checks:
    """Named pass/fail records; every check is printed with its numbers."""

    def __init__(self):
        self.failed = []

    def __call__(self, name: str, ok: bool, detail: str) -> None:
        log(f"check {name}: {'ok' if ok else 'FAILED'} ({detail})")
        if not ok:
            self.failed.append(name)


def compile_s() -> float:
    """Seconds JAX has spent building executables so far, from the
    ``jax.backend_compile_s`` histogram that ``watch_compiles`` feeds."""
    from repro.obs.metrics import DEFAULT_REGISTRY
    hist = DEFAULT_REGISTRY.snapshot()["histograms"]
    return hist.get("jax.backend_compile_s", {"sum": 0.0})["sum"]


def _gib(n: float) -> str:
    return f"{n / 2 ** 30:.3f} GiB"


def _max_rel_err(a, b) -> float:
    import numpy as np
    a, b = np.asarray(a, np.float64), np.asarray(b, np.float64)
    return float(np.max(np.abs(a - b)) / np.max(np.abs(b)))


def train_phase(checks: Checks, cfg, *, batch: int, steps: int) -> None:
    """``api.fit`` for ``steps`` steps from a fresh state; checks the loss
    curve against the same model's reference loss."""
    import jax

    from repro import api
    from repro.data.pipeline import DataConfig, make_batch
    from repro.models import build_model
    from repro.train.trainer import TrainerConfig

    seq = cfg.max_position
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                      global_batch=batch, seed=SEED)
    log(f"[train] {cfg.arch_id}: d_model {cfg.d_model}, heads {cfg.n_heads}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}; n_layers {cfg.n_layers} "
        f"(cut); batch {batch} x {seq}; {steps} steps; f32 params, AdamW, "
        "donated state")

    # the reference: fit's own initial params (same model, same seed) and
    # first batch, at full f32 matmul precision; freed before fit allocates
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    with jax.default_matmul_precision("highest"):
        ref = float(jax.jit(model.loss)(params, make_batch(data, 0))[0])
    for leaf in jax.tree.leaves(params):
        leaf.delete()
    del params

    c0 = compile_s()
    with tempfile.TemporaryDirectory() as ckpt_dir:
        harp = api.HarpConfig(seq_len=seq, global_batch=batch,
                              trainer=TrainerConfig(
                                  total_steps=steps, ckpt_dir=ckpt_dir,
                                  ckpt_every=steps + 1, log_every=1))
        res = api.fit(cfg, harp, data_cfg=data, seed=SEED, start_step=0,
                      log_fn=log)
    hist = res["history"]
    times = [h["time_s"] for h in hist]
    log(f"[train] compile s: {compile_s() - c0:.3f}")
    log(f"[train] step s: first (with compile) {times[0]:.4f}; then "
        + ", ".join(f"{t:.4f}" for t in times[1:])
        + (f"; median {statistics.median(times[1:]):.4f}" if len(times) > 1
           else ""))
    stats = jax.devices()[0].memory_stats() or {}
    if "peak_bytes_in_use" in stats:
        log(f"[train] peak_bytes_in_use {stats['peak_bytes_in_use']} "
            f"({_gib(stats['peak_bytes_in_use'])})")

    losses = [h["loss"] for h in hist]
    gnorms = [h["grad_norm"] for h in hist]
    checks("train.steps", [h["step"] for h in hist] == list(range(1, steps + 1)),
           f"{len(hist)} of {steps} steps logged")
    checks("train.finite", all(map(math.isfinite, losses + gnorms)),
           f"losses {losses}; grad norms {gnorms}")
    ln_v = math.log(cfg.vocab_size)
    checks("train.init_loss", abs(losses[0] - ln_v) < INIT_LOSS_NATS,
           f"step-1 loss {losses[0]!r} vs ln(vocab) {ln_v!r}, "
           f"limit {INIT_LOSS_NATS} nats")
    checks("train.matches_reference", abs(losses[0] - ref) <= STEP1_LOSS_ATOL,
           f"step-1 loss {losses[0]!r} vs model.loss at highest precision "
           f"{ref!r}: |diff| {abs(losses[0] - ref)!r}, limit {STEP1_LOSS_ATOL}")
    checks("train.loss_falls", losses[-1] < losses[0],
           f"first {losses[0]!r}, last {losses[-1]!r}")


def flash_phase(checks: Checks, cfg, *, batch: int) -> None:
    """Flash attention forward + backward through ``ops.flash_attention``
    vs ``attention_ref`` at highest precision, at ``cfg``'s head widths."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import ops
    from repro.models.attention import attention_ref

    T, H, D = cfg.max_position, cfg.n_heads, cfg.d_model // cfg.n_heads
    log(f"[flash] causal, q/k/v ({batch}, {T}, {H}, {D}) f32")
    kq, kk, kv, kg = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q, k, v, g = (jax.random.normal(key, (batch, T, H, D), jnp.float32)
                  for key in (kq, kk, kv, kg))

    def fwd_bwd(attn):
        def run(q, k, v):
            out, vjp = jax.vjp(lambda *a: attn(*a, causal=True), q, k, v)
            return (out, *vjp(g))
        return jax.jit(run)

    kernel = fwd_bwd(lambda *a, causal: ops.flash_attention(
        *a, causal=causal, interpret=False))
    lowered = kernel.lower(q, k, v).as_text()
    checks("flash.mosaic", "tpu_custom_call" in lowered,
           "the jitted program calls the compiled Mosaic kernel")
    c0 = compile_s()
    got = jax.block_until_ready(kernel(q, k, v))
    log(f"[flash] compile s: {compile_s() - c0:.3f}")
    t0 = time.perf_counter()
    jax.block_until_ready(kernel(q, k, v))
    log(f"[flash] fwd+bwd s (second call): {time.perf_counter() - t0:.6f}")
    with jax.default_matmul_precision("highest"):
        want = fwd_bwd(attention_ref)(q, k, v)
    for name, a, b in zip(("out", "dq", "dk", "dv"), got, want):
        err = _max_rel_err(a, b)
        checks(f"flash.{name}", err <= FLASH_REL_TOL,
               f"max |kernel - ref| / max |ref| = {err!r}, "
               f"limit {FLASH_REL_TOL}")


def pipeline_phase(checks: Checks, cfg, devices, *,
                   batch: int, seq: int, n_microbatches: int,
                   steps: int) -> None:
    """The 2-stage pipeline train step over ``devices`` vs the single-program
    model on the same params and batch."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data.pipeline import DataConfig, make_batch
    from repro.launch.mesh import make_mesh
    from repro.models import build_model
    from repro.train.optimizer import OptimizerConfig, global_norm
    from repro.train.step import (
        make_pipeline_train_step, pipeline_state_shardings,
    )

    mesh = make_mesh(PIPE_MESH, ("pod", "data", "model"), devices=devices)
    data = DataConfig(vocab_size=cfg.vocab_size, seq_len=seq,
                      global_batch=batch, seed=SEED)
    log(f"[pipeline] {cfg.arch_id}: d_model {cfg.d_model}, n_layers "
        f"{cfg.n_layers} (cut); mesh {dict(mesh.shape)} on "
        f"{len(devices)} devices; batch {batch} x {seq} in {n_microbatches} "
        f"microbatches; activations {PIPE_ACT_DTYPE}")

    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(SEED))
    first = make_batch(data, 0)

    def ref_fn(p, b):
        loss, grads = jax.value_and_grad(lambda p: model.loss(p, b)[0])(p)
        return loss, global_norm(grads)

    with jax.default_matmul_precision("highest"):
        ref_loss, ref_gn = map(float, jax.jit(ref_fn)(params, first))

    # api.fit's default schedule for a run of this length
    opt_cfg = OptimizerConfig(warmup_steps=min(20, steps), total_steps=steps)
    step, staging, opt_init, sh = make_pipeline_train_step(
        cfg, opt_cfg, mesh=mesh, n_stages=PIPE_MESH[0],
        n_microbatches=n_microbatches, params=params,
        act_dtype=jnp.dtype(PIPE_ACT_DTYPE))

    staged_sh, shared_sh, opt_sh = pipeline_state_shardings(
        mesh, sh, staging, jax.eval_shape(
            opt_init, {"staged": staging.staged, "shared": staging.shared}))
    staged = jax.device_put(staging.staged, staged_sh)
    shared = jax.device_put(staging.shared, shared_sh)
    consts = jax.device_put(staging.consts, sh["consts"])
    # the unplaced parameters all sit on the first device; the step keeps
    # no reference to them
    del staging, params
    opt_state = jax.jit(opt_init, out_shardings=opt_sh)(
        {"staged": staged, "shared": shared})
    batch_sh = NamedSharding(mesh, P("data", None))
    batches = [jax.device_put(make_batch(data, i), batch_sh)
               for i in range(steps)]

    with jax.set_mesh(mesh):
        c0 = compile_s()
        # the state leaves the step placed as it entered, so the next step
        # takes it as is and the donated buffers can be reused
        compiled = jax.jit(
            step, in_shardings=(staged_sh, shared_sh, sh["consts"], opt_sh,
                                batch_sh),
            out_shardings=(staged_sh, shared_sh, opt_sh,
                           NamedSharding(mesh, P())),
            donate_argnums=(0, 1, 3)).lower(
                staged, shared, consts, opt_state, batches[0]).compile()
        log(f"[pipeline] compile s: {compile_s() - c0:.3f}")
        hlo = compiled.as_text()
        log("[pipeline] collectives in the step: " + ", ".join(
            f"{op} x{hlo.count(op + '(') + hlo.count(op + '-start(')}"
            for op in ("collective-permute", "all-reduce", "all-gather",
                       "reduce-scatter")))
        hist = []
        for i in range(steps):
            t0 = time.perf_counter()
            staged, shared, opt_state, metrics = compiled(
                staged, shared, consts, opt_state, batches[i])
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            hist.append({"loss": loss,
                         "grad_norm": float(metrics["grad_norm"]),
                         "time_s": dt})
            log(f"[pipeline] step {i + 1}: loss {loss!r} grad_norm "
                f"{hist[-1]['grad_norm']!r} ({dt:.4f} s)")

    # what each device should hold: its shards of the live placed arrays
    placed = {d: 0 for d in devices}
    for leaf in jax.tree.leaves((staged, shared, consts, opt_state, batches)):
        for s in leaf.addressable_shards:
            placed[s.device] += s.data.nbytes
    in_use = []
    for d in devices:
        stats = d.memory_stats() or {}
        in_use.append(stats.get("bytes_in_use"))
        log(f"[pipeline] device {d.id} {d.device_kind}: bytes_in_use "
            f"{stats.get('bytes_in_use')} peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use')} placed state {placed[d]}")
    if all(b is not None for b in in_use):
        checks("pipeline.all_devices_hold_state",
               min(in_use) >= MEM_BALANCE_MIN * max(in_use),
               f"bytes_in_use per device {in_use}: least / most "
               f"{min(in_use) / max(in_use)!r}, limit {MEM_BALANCE_MIN}")
        excess = max((b - placed[d]) / placed[d]
                     for d, b in zip(devices, in_use))
        checks("pipeline.no_unplaced_copy", excess <= MEM_EXCESS_MAX,
               f"largest (bytes_in_use - placed state) / placed state "
               f"{excess!r}, limit {MEM_EXCESS_MAX}")
    losses = [h["loss"] for h in hist]
    gnorms = [h["grad_norm"] for h in hist]
    checks("pipeline.finite", all(map(math.isfinite, losses + gnorms)),
           f"losses {losses}; grad norms {gnorms}")
    checks("pipeline.loss_matches_reference",
           abs(losses[0] - ref_loss) <= PIPE_LOSS_ATOL,
           f"step-1 loss {losses[0]!r} vs single-program model.loss "
           f"{ref_loss!r}: |diff| {abs(losses[0] - ref_loss)!r}, "
           f"limit {PIPE_LOSS_ATOL}")
    rel = abs(gnorms[0] - ref_gn) / ref_gn
    checks("pipeline.grad_norm_matches_reference",
           rel <= PIPE_GRAD_NORM_RTOL,
           f"step-1 grad norm {gnorms[0]!r} vs reference {ref_gn!r}: "
           f"relative diff {rel!r}, limit {PIPE_GRAD_NORM_RTOL}")
    checks("pipeline.loss_falls", losses[-1] < losses[0],
           f"first {losses[0]!r}, last {losses[-1]!r}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        description="Smoke run of the training path on TPU chips.")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: api.fit + flash kernel on one chip; 4: only the "
                         "pipeline step across four chips")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir():
        print(f"chip_smoke.py: no repro package under {SRC}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))

    import jax

    from repro.compile_cache import enable_compile_cache
    from repro.configs import get_config
    from repro.obs.metrics import watch_compiles

    cache_dir = enable_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke.py: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke.py: --chips {args.chips} but JAX sees "
              f"{len(devices)} devices", file=sys.stderr)
        return 1
    log(f"device: {dev.device_kind} ({dev.platform}), count {len(devices)}; "
        f"jax {jax.__version__}; compile cache {cache_dir}")

    watch_compiles()
    checks = Checks()
    full = get_config(ARCH)
    if args.chips == 4:
        pipeline_phase(checks,
                       dataclasses.replace(full, n_layers=PIPE_LAYERS),
                       devices[:4], batch=TRAIN_BATCH, seq=full.max_position,
                       n_microbatches=PIPE_MICROBATCHES, steps=TRAIN_STEPS)
    else:
        train_phase(checks,
                    dataclasses.replace(full, n_layers=TRAIN_LAYERS),
                    batch=TRAIN_BATCH, steps=TRAIN_STEPS)
        flash_phase(checks, full, batch=FLASH_BATCH)
    if checks.failed:
        print(f"chip_smoke.py: failed checks: {checks.failed}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
