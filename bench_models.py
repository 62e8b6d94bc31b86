"""Flagship-model benchmarks: step time, throughput, and MFU on the live
backend.

Measures what the AutoML bench can't:
ViT-B/16 (the BASELINE.json north-star config) and the progressive GAN (the
reference fork's marquee model, reference pg_gans.py).

MFU accounting (VERDICT r2 item 1): FLOPs are counted *analytically* —
matmul/conv multiply-adds at 2 FLOPs each, backward = 2x forward — the
PaLM-style model-FLOPs convention. XLA's ``cost_analysis()`` is NOT used
for MFU: it counts a ``lax.scan`` body once regardless of trip count, which
under-reported the ViT step ~6x in round 2 (0.59 vs ~6.7 TFLOP at bs=64).
It is still reported as ``xla_cost_analysis_tflops`` for cross-checking.

Timing: each measured call runs ``steps_per_call`` train steps inside one
jitted ``lax.scan`` with params/opt_state donated, and synchronizes by
fetching the final loss to the host — the scan keeps the device busy
between steps (no host round trip per step), and a fetched value is a
fence on any backend. What a dispatch and a ``block_until_ready`` cost on
the chip is measured by ``chip_smoke.py`` (its kernel phase), not assumed.

Run standalone (`python bench_models.py`) for a JSON report, or let
bench.py embed the numbers in its one-line summary (RAFIKI_BENCH_MODELS=0
skips).
"""

from __future__ import annotations

import json
import os
import time
from typing import Any, Dict, Optional

import numpy as np

# Peak dense bf16 TFLOP/s of ONE chip, keyed by ``device_kind`` as JAX
# reports it. Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
# bf16, 16 GB HBM at 819 GB/s). A device that is not here is an error, not
# a default: an MFU against somebody else's peak is not a number.
PEAK_BF16_TFLOPS = {
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
}


def peak_tflops(device_kind: Optional[str] = None) -> float:
    """The peak for ``device_kind`` (default: this process's device 0)."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return PEAK_BF16_TFLOPS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device kind {device_kind!r}: add it to "
            "bench_models.PEAK_BF16_TFLOPS with its source") from None


def mfu(flops: float, step_s: float) -> Optional[float]:
    """Model-FLOPs utilisation against this device's published peak; None
    on the CPU rehearsal (a CPU run has no device utilisation to report)."""
    import jax

    if jax.default_backend() == "cpu":
        return None
    return round(flops / (step_s * peak_tflops() * 1e12), 4)


def vit_train_flops(cfg, batch_size: int) -> float:
    """Analytic model-FLOPs of one ViT train step (fwd + bwd + no optimizer
    matmuls), counting each multiply-add as 2 FLOPs and backward as 2x
    forward. Matmul/conv terms only — elementwise/softmax/LN are noise next
    to the MXU work and inflating MFU with them would flatter the number."""
    S, D = cfg.seq_len, cfg.encoder.dim
    mlp_hidden = cfg.encoder.mlp_ratio * D
    per_block = (
        8 * S * D * D          # qkv + output projections
        + 4 * S * S * D        # scores (q@k) + weighted values (p@v)
        + 4 * S * D * mlp_hidden  # mlp in + out
    )
    patch = 2 * S * D * (cfg.patch_size ** 2 * cfg.channels)
    head = 2 * D * cfg.num_classes
    fwd = cfg.encoder.depth * per_block + patch + head
    return 3.0 * fwd * batch_size


def _xla_flops(jitted, *args) -> Optional[float]:
    """XLA's own FLOP estimate (cross-check only — undercounts scan)."""
    try:
        compiled = jitted.lower(*args).compile()
        analysis = compiled.cost_analysis()
        if isinstance(analysis, list):  # per-device list on some backends
            analysis = analysis[0]
        flops = float(analysis.get("flops", 0.0))
        return flops if flops > 0 else None
    except Exception:
        return None


def bench_vit(batch_size: int = 192, image_size: int = 224,
              n_steps: int = 32, steps_per_call: int = 8,
              remat: Optional[str] = "dots",
              scan_unroll: int = 1,
              use_flash: Optional[bool] = None,
              mu_bf16: bool = False,
              fused_qkv: bool = False) -> Dict[str, Any]:
    """ViT-B/16 fused train step (fwd+bwd+adamw), bf16 activations, donated
    buffers, multi-step scan per dispatch, dots-saveable remat (batches
    this size do not fit 16 GB HBM with full activation stashing).
    Batch 192 is the measured single-chip optimum (swept 128/192/224/256:
    0.350/0.355/0.324/0.330 MFU). ``scan_unroll`` unrolls the depth scan
    so XLA can fuse across blocks (see TransformerConfig.scan_unroll).
    ``use_flash`` forces the attention kernel at seq 197 (None = the
    footprint auto-dispatch, which picks XLA fused attention here);
    ``mu_bf16`` keeps adamw's first moment in bf16 — halves the largest
    optimizer-state HBM stream (verdict r5: levers beyond the r3 grid)."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import optax

    from rafiki_tpu.models import vit

    cfg = vit.vit_b16(num_classes=1000, image_size=image_size)
    cfg = dataclasses.replace(
        cfg, encoder=dataclasses.replace(
            cfg.encoder, remat=remat, scan_unroll=scan_unroll,
            use_flash=use_flash, fused_qkv=fused_qkv))
    params = jax.jit(lambda r: vit.init(r, cfg))(jax.random.key(0))
    opt = optax.adamw(
        1e-3, mu_dtype=jnp.bfloat16 if mu_bf16 else None)
    opt_state = jax.jit(opt.init)(params)

    # bf16 inputs: the model computes in bf16 anyway (core.cast_for_compute);
    # shipping f32 just doubles the input HBM traffic
    x = jnp.zeros((batch_size, image_size, image_size, 3), jnp.bfloat16)
    y = jnp.zeros((batch_size,), jnp.int32)

    def loss_fn(p, batch, rng):
        xx, yy = batch
        logits = vit.apply(p, xx, cfg, rng, deterministic=False)
        return optax.softmax_cross_entropy_with_integer_labels(logits, yy).mean()

    def one_step(carry, _):
        p, s, rng = carry
        rng, sub = jax.random.split(rng)
        loss, grads = jax.value_and_grad(loss_fn)(p, (x, y), sub)
        updates, s = opt.update(grads, s, p)
        return (optax.apply_updates(p, updates), s, rng), loss

    def multi_step(p, s, rng):
        (p, s, rng), losses = jax.lax.scan(
            one_step, (p, s, rng), None, length=steps_per_call)
        return p, s, rng, losses

    jitted = jax.jit(multi_step, donate_argnums=(0, 1))
    xla_flops = _xla_flops(jitted, params, opt_state, jax.random.key(2))

    rng = jax.random.key(2)
    # warmup (compile + first dispatch); fetching the loss value fences
    # execution on any backend
    params, opt_state, rng, losses = jitted(params, opt_state, rng)
    _ = float(losses[-1])

    n_calls = max(n_steps // steps_per_call, 1)
    t0 = time.perf_counter()
    for _ in range(n_calls):
        params, opt_state, rng, losses = jitted(params, opt_state, rng)
    _ = float(losses[-1])
    step_s = (time.perf_counter() - t0) / (n_calls * steps_per_call)

    flops = vit_train_flops(cfg, batch_size)
    out = {
        "model": "ViT-B/16",
        "batch_size": batch_size,
        "remat": remat,
        "scan_unroll": scan_unroll,
        "use_flash": use_flash,
        "mu_bf16": mu_bf16,
        "fused_qkv": fused_qkv,
        "steps_per_call": steps_per_call,
        "step_time_ms": round(step_s * 1000, 2),
        "steps_per_s": round(1.0 / step_s, 3),
        "images_per_s": round(batch_size / step_s, 1),
        "backend": jax.default_backend(),
        "step_tflops_analytic": round(flops / 1e12, 3),
        "mfu": mfu(flops, step_s),
        "mfu_note": ("analytic matmul FLOPs (2*MAC, bwd=2x fwd) / the "
                     "device kind's published peak (PEAK_BF16_TFLOPS)"),
    }
    if xla_flops is not None:
        # cross-check only: cost_analysis counts each lax.scan body ONCE,
        # so its count for this program (an outer steps_per_call-step scan
        # whose body contains the depth-layer scan) must be scaled by both
        # trip counts before comparing to the per-step analytic number.
        # The reconciliation is printed so a reader can verify the 11x-ish
        # raw gap is scan accounting, not a FLOP miscount (VERDICT r3
        # weak #3).
        depth = cfg.encoder.depth
        eff_unroll = max(min(scan_unroll, depth), 1)
        scanned_iters = depth // eff_unroll
        reconciled = xla_flops * scanned_iters
        out["xla_cost_analysis_tflops"] = round(xla_flops / 1e12, 3)
        out["xla_reconciliation"] = (
            f"cost_analysis counts scan bodies once: raw {xla_flops/1e12:.3f}"
            f" TFLOP covers 1 of {steps_per_call} outer steps and "
            f"{eff_unroll} of {depth} layers -> x{scanned_iters} layer iters"
            f" ~= {reconciled/1e12:.3f} TFLOP/step vs analytic "
            f"{flops/1e12:.3f} (residual = optimizer/patchify/head + "
            f"per-call constants)")
    return out


def bench_pggan(resolution: int = 64, minibatch: int = 128,
                n_steps: int = 20) -> Dict[str, Any]:
    """Progressive-GAN D+G step at full resolution (the steady-state cost
    once growth completes — the reference's headline img/s regime).
    Minibatch 128 is the measured single-chip optimum (swept 64/128/256:
    0.374/0.459/0.427 MFU).

    MFU here uses XLA's ``cost_analysis`` of the two compiled steps: unlike
    the ViT bench (whose ``lax.scan`` bodies cost_analysis counts once),
    the PGGAN graph unrolls its stage loop in Python, so the compiler's
    count is the true per-execution FLOPs."""
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.models import pggan

    cfg = pggan.PgganConfig(resolution=resolution)
    trainer = pggan.PgganTrainer(cfg)
    trainer.init_optimizers(1e-3, 1e-3)
    max_stage = cfg.num_stages - 1
    d_step, g_step = trainer._get_steps(max_stage, minibatch)
    reals = jnp.zeros((minibatch, resolution, resolution, 3), jnp.float32)
    lod = jnp.float32(0.0)
    state = {"rng": jax.random.PRNGKey(0)}

    kd0, kg0 = jax.random.split(jax.random.PRNGKey(1))
    d_flops = _xla_flops(d_step, trainer.d_params, trainer.g_params,
                         trainer._opt_state["d"], reals, None, lod, kd0)
    g_flops = _xla_flops(g_step, trainer.g_params, trainer.d_params,
                         trainer._opt_state["g"], None, lod, kg0)

    def one():
        state["rng"], kd, kg = jax.random.split(state["rng"], 3)
        trainer.d_params, trainer._opt_state["d"], d_loss, _ = d_step(
            trainer.d_params, trainer.g_params, trainer._opt_state["d"],
            reals, None, lod, kd)
        trainer.g_params, trainer._opt_state["g"], g_loss = g_step(
            trainer.g_params, trainer.d_params, trainer._opt_state["g"],
            None, lod, kg)
        return g_loss

    _ = float(one())  # warmup: compiles both D and G directions
    t0 = time.perf_counter()
    last = None
    for _ in range(n_steps):
        last = one()
    _ = float(last)  # execution fence (see module docstring)
    step_s = (time.perf_counter() - t0) / n_steps
    out = {
        "model": f"PGGAN-{resolution}",
        "minibatch": minibatch,
        "step_time_ms": round(step_s * 1000, 2),
        "images_per_s": round(minibatch / step_s, 1),
        "kimg_per_hour": round(minibatch / step_s * 3.6, 1),
        "backend": jax.default_backend(),
    }
    if d_flops is not None and g_flops is not None:
        flops = d_flops + g_flops
        out["step_tflops_xla"] = round(flops / 1e12, 3)
        out["mfu"] = mfu(flops, step_s)
        out["mfu_note"] = ("XLA cost_analysis FLOPs (exact: no scan in this "
                           "graph) / the device kind's published peak "
                           "(PEAK_BF16_TFLOPS)")
    return out


def run_all(small: bool = False) -> Dict[str, Any]:
    """All flagship benches; ``small`` shrinks shapes for CPU smoke."""
    if small:
        return {
            "vit": bench_vit(batch_size=4, image_size=64, n_steps=4,
                             steps_per_call=2),
            "pggan": bench_pggan(resolution=16, minibatch=8, n_steps=3),
        }
    return {
        "vit": bench_vit(),
        "pggan": bench_pggan(),
    }


def sweep_pggan() -> None:
    """PGGAN minibatch sweep (the r3 optimum 128 was swept by hand);
    one JSON line per config, crash-safe. Grid: RAFIKI_SWEEP_MINIBATCH."""
    minibatches = [int(m) for m in os.environ.get(
        "RAFIKI_SWEEP_MINIBATCH", "64,128,256").split(",")]
    best = None
    for mb in minibatches:
        tag = {"minibatch": mb}
        try:
            r = bench_pggan(minibatch=mb)
        except Exception as e:
            print(json.dumps({**tag, "error": repr(e)[:300]}), flush=True)
            continue
        print(json.dumps({**tag, "mfu": r.get("mfu"),
                          "images_per_s": r["images_per_s"]}), flush=True)
        # rank by throughput: per-image FLOPs are fixed across minibatch,
        # so images/s orders identically to MFU and stays comparable even
        # when cost_analysis yields no MFU for some config
        if best is None or r["images_per_s"] > best[1]["images_per_s"]:
            best = (tag, r)
    if best is not None:
        print(json.dumps({"best": best[0], "result": best[1]}), flush=True)


def bench_longctx(seqs=(2048, 4096, 8192), b: int = 4, h: int = 12,
                  dh: int = 64, n_steps: int = 8) -> None:
    """Long-context attention fwd+bwd: XLA fused vs the pallas flash
    kernel at each sequence length, one JSON line per config (the
    BASELINE long-context row was a one-off session script in r3; this
    makes it reproducible). An XLA failure at long seq (the (S,S) score
    tensors exceed HBM) is RECORDED, not fatal: that asymmetry is the
    point of the flash kernel. Tile shapes come from
    RAFIKI_FLASH_BLOCK_Q/_K read HERE and passed explicitly — the
    production kernel's defaults stay untouched. Flash runs FIRST at
    each seq: the XLA long-seq attempt is the one expected to fail — the
    flash rows (the datapoints this bench exists for) must already be
    out."""
    import functools

    import jax
    import jax.numpy as jnp
    from jax import lax

    from rafiki_tpu.ops import flash_attention, mha_reference

    block_q = int(os.environ.get("RAFIKI_FLASH_BLOCK_Q", "128"))
    block_k = int(os.environ.get("RAFIKI_FLASH_BLOCK_K", "128"))
    # ALL flash seqs before ANY xla attempt: one hung XLA compile at an
    # early seq must not cost the later flash rows too
    for kind in ("flash", "xla"):
        for s in seqs:
            inner = (mha_reference if kind == "xla" else functools.partial(
                flash_attention, block_q=block_q, block_k=block_k))

            def loss(q, k, v):
                return inner(q, k, v).astype(jnp.float32).sum()

            def multi(q, k, v):
                # n_steps grad computations in ONE dispatch (see module
                # docstring) — the tiny grad-scaled update forces each iteration to
                # depend on the last so XLA cannot collapse the scan
                def body(c, _):
                    g = jax.grad(loss)(c, k, v)
                    return c + g.astype(c.dtype) * 1e-9, ()

                c, _ = lax.scan(body, q, None, length=n_steps)
                return c.astype(jnp.float32).sum()

            jitted = jax.jit(multi)
            shape = (b, h, s, dh)
            ks = jax.random.split(jax.random.key(0), 3)
            q, k, v = (jax.random.normal(kk, shape, jnp.bfloat16)
                       for kk in ks)
            tag = {"seq": s, "kind": kind, "batch": b, "heads": h,
                   "dh": dh,
                   "block_q": block_q if kind == "flash" else None,
                   "block_k": block_k if kind == "flash" else None}
            try:
                _ = float(jitted(q, k, v))  # compile + warmup, fenced
                t0 = time.perf_counter()
                _ = float(jitted(q, k, v))
                wall = time.perf_counter() - t0
            except Exception as e:
                print(json.dumps({**tag, "error": repr(e)[:300]}),
                      flush=True)
                continue
            print(json.dumps({
                **tag,
                "ms_per_step": round(wall / n_steps * 1000, 2),
                "backend": jax.default_backend(),
            }), flush=True)


def bench_ablation() -> None:
    """ViT-B/16 step-time COST ATTRIBUTION (not a tuning sweep): where
    does the gap between measured MFU (~0.36) and peak go? One JSON line
    per variant so a mid-run hang loses nothing. The first two rows
    calibrate the ACHIEVABLE peak — if a chained square bf16 GEMM cannot
    approach the datasheet peak on this chip, every MFU in the
    record should be read against the calibrated ceiling, not the
    datasheet. Then: fwd-only vs fwd+bwd vs full step splits compute
    between forward, backward(+remat recompute), and optimizer;
    remat=None at batches that fit without remat prices the recompute;
    forced-flash prices the attention kernel choice at seq 196."""
    import dataclasses

    import jax
    import jax.numpy as jnp
    import optax

    from rafiki_tpu.models import vit

    # the CPU rehearsal only walks the trace paths: no peak, no shares
    peak = (None if jax.default_backend() == "cpu"
            else peak_tflops() * 1e12)

    def gemm(tag, make_operands, chain_body, flops, iters=24):
        try:
            ops = make_operands()

            def chain(*ops):
                c, _ = jax.lax.scan(lambda c, _: (chain_body(c, *ops[1:]), ()),
                                    ops[0], None, length=iters)
                return c

            jitted = jax.jit(chain)
            c = jitted(*ops)
            _ = float(jnp.sum(c.astype(jnp.float32)))
            t0 = time.perf_counter()
            c = jitted(*ops)
            _ = float(jnp.sum(c.astype(jnp.float32)))
            dt = time.perf_counter() - t0
            print(json.dumps({
                "tag": tag, "tflops_per_s": round(flops * iters / dt / 1e12, 1),
                "pct_of_peak": (None if peak is None else round(
                    flops * iters / dt / peak * 100, 1)),
                "backend": jax.default_backend()}), flush=True)
        except Exception as e:
            print(json.dumps({"tag": tag, "error": repr(e)[:200]}), flush=True)

    # CPU backend (or RAFIKI_ABLATE_SMALL=1) = tiny smoke of every
    # variant's trace path: a trace error must surface before the run
    # spends a TPU window, and a CPU box must never attempt 8192-cube
    # GEMMs. Same falsy rule as __main__'s RAFIKI_BENCH_SMALL.
    small = (jax.default_backend() == "cpu"
             or os.environ.get("RAFIKI_ABLATE_SMALL", "").strip().lower()
             not in ("", "0", "false"))
    n = 256 if small else 8192
    gemm(f"gemm_calibration_{n}",
         lambda: (jax.random.normal(jax.random.key(0), (n, n), jnp.bfloat16),
                  jax.random.normal(jax.random.key(1), (n, n), jnp.bfloat16)),
         lambda c, b: c @ b, 2.0 * n * n * n)
    m, k, nn = (256, 64, 128) if small else (192 * 196, 768, 3072)
    gemm("gemm_vit_proj_shape",
         lambda: (jax.random.normal(jax.random.key(2), (m, k), jnp.bfloat16),
                  jax.random.normal(jax.random.key(3), (k, nn), jnp.bfloat16)),
         lambda c, w: (c @ w)[:, :k], 2.0 * m * k * nn)

    def mkcfg(remat, unroll=1, flash=None):
        cfg = (vit.tiny(image_size=32) if small
               else vit.vit_b16(num_classes=1000, image_size=224))
        return dataclasses.replace(cfg, encoder=dataclasses.replace(
            cfg.encoder, remat=remat, scan_unroll=unroll, use_flash=flash))

    def run(tag, cfg, batch, steps_per_call=8, n_steps=32, mode="full",
            flops_mult=3.0):
        params = jax.jit(lambda r: vit.init(r, cfg))(jax.random.key(0))
        opt = optax.adamw(1e-3)
        opt_state = jax.jit(opt.init)(params)
        x = jnp.zeros((batch, cfg.image_size, cfg.image_size, 3),
                      jnp.bfloat16)
        y = jnp.zeros((batch,), jnp.int32)

        def loss_fn(p, rng):
            logits = vit.apply(p, x, cfg, rng, deterministic=False)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, y).mean()

        if mode == "fwd":
            def multi(p, s, rng):
                def one(carry, _):
                    acc, r = carry
                    r = jax.random.split(r)[0]
                    # accumulate the real loss — a *0 here would let XLA
                    # dead-code-eliminate the whole forward
                    return (acc + loss_fn(p, r), r), acc
                (acc, rng), _ = jax.lax.scan(
                    one, (jnp.zeros(()), rng), None, length=steps_per_call)
                return p, s, rng, acc
        else:  # "grad"
            def multi(p, s, rng):
                def one(carry, _):
                    pp, r = carry
                    r, sub = jax.random.split(r)
                    loss, g = jax.value_and_grad(loss_fn)(pp, sub)
                    # consume the grads without an optimizer: a non-zero
                    # scale keeps XLA from dead-code-eliminating backward
                    pp = jax.tree.map(
                        lambda a, b: a - jnp.asarray(1e-30, a.dtype)
                        * b.astype(a.dtype), pp, g)
                    return (pp, r), loss
                (p, rng), ls = jax.lax.scan(one, (p, rng), None,
                                            length=steps_per_call)
                return p, s, rng, ls[-1]

        jitted = jax.jit(multi, donate_argnums=(0, 1))
        rng = jax.random.key(1)
        try:
            params, opt_state, rng, out = jitted(params, opt_state, rng)
            _ = float(jnp.sum(out))
            n_calls = max(n_steps // steps_per_call, 1)
            t0 = time.perf_counter()
            for _ in range(n_calls):
                params, opt_state, rng, out = jitted(params, opt_state, rng)
            _ = float(jnp.sum(out))
            dt = (time.perf_counter() - t0) / (n_calls * steps_per_call)
        except Exception as e:
            print(json.dumps({"tag": tag, "error": repr(e)[:200]}),
                  flush=True)
            return
        fl = vit_train_flops(cfg, batch) * flops_mult / 3.0
        print(json.dumps({
            "tag": tag, "batch": batch, "mode": mode,
            "step_ms": round(dt * 1000, 2),
            "eff_mfu": None if peak is None else round(fl / (dt * peak), 4),
            "imgs_per_s": round(batch / dt, 1),
            "backend": jax.default_backend()}), flush=True)

    def full(tag, **kwargs):
        # full-step rows delegate to bench_vit — ONE timing harness for
        # the fused train step, so ablation rows stay comparable to the
        # sweep's and cannot drift from it
        if small:
            kwargs = {**kwargs, "batch_size": 4, "image_size": 64,
                      "n_steps": 4, "steps_per_call": 2}
        try:
            r = bench_vit(**kwargs)
        except Exception as e:
            print(json.dumps({"tag": tag, "error": repr(e)[:200]}),
                  flush=True)
            return
        print(json.dumps({"tag": tag, **{k: r[k] for k in (
            "batch_size", "remat", "use_flash", "steps_per_call",
            "step_time_ms", "images_per_s", "mfu", "backend")}}),
            flush=True)

    B = 4 if small else 192
    steps = dict(steps_per_call=2, n_steps=4) if small else {}
    full("full_dots", batch_size=192, remat="dots")
    full("full_dots_spc16", batch_size=192, remat="dots", steps_per_call=16)
    run("fwd_dots", mkcfg("dots"), B, mode="fwd", flops_mult=1.0, **steps)
    run("grad_dots", mkcfg("dots"), B, mode="grad", **steps)
    run("fwd_none", mkcfg(None), B, mode="fwd", flops_mult=1.0, **steps)
    for b in ((8,) if small else (64, 96, 128)):
        full(f"full_none_b{b}", batch_size=b, remat=None)
        full(f"full_dots_b{b}", batch_size=b, remat="dots")
    full("full_full_b192", batch_size=192, remat="full")
    full("full_dots_flash", batch_size=192, remat="dots", use_flash=True)


def bench_int8(batches=(1, 8, 64), seq: int = 128, n_calls: int = 30) -> None:
    """Weight-only int8 serving delta in the regime it targets: a
    weight-bandwidth-bound predict (BERT-base, ~110M params — each
    small-batch call streams every kernel out of HBM while the MXU
    idles). The end-to-end bench measures the delta on its small CNN,
    where dequant overhead dominates and int8 LOSES (BENCH_r05
    int8_unloaded_speedup ~0.8); this is the companion measurement on a
    model the feature is actually for, per batch size. One JSON line per
    (batch, mode)."""
    import jax
    import jax.numpy as jnp

    from rafiki_tpu.models import bert
    from rafiki_tpu.sdk.quant import dequantize_pytree, quantize_pytree

    cfg = bert.bert_base(num_classes=2)
    params = jax.jit(lambda r: bert.init(r, cfg))(jax.random.key(0))
    # serving keeps bf16 masters; the int8 copy is quantized from them
    params = jax.tree.map(lambda a: a.astype(jnp.bfloat16)
                          if a.dtype == jnp.float32 else a, params)
    qparams = jax.device_put(quantize_pytree(params))

    def predict(p, ids):
        return jax.nn.softmax(bert.apply(p, ids, cfg), axis=-1)

    def predict_q(qp, ids):
        return jax.nn.softmax(
            bert.apply(dequantize_pytree(qp), ids, cfg), axis=-1)

    for batch in batches:
        ids = jnp.zeros((batch, seq), jnp.int32)
        base_wall = None
        for mode, fn, p in (("bf16", predict, params),
                            ("int8", predict_q, qparams)):
            jitted = jax.jit(fn)
            try:
                _ = np.asarray(jitted(p, ids))  # compile + fence
                t0 = time.perf_counter()
                for _ in range(n_calls):
                    out = jitted(p, ids)
                _ = np.asarray(out)  # one fence: per-call overhead stays in
                wall = (time.perf_counter() - t0) / n_calls
            except Exception as e:
                print(json.dumps({"model": "BERT-base", "batch": batch,
                                  "mode": mode, "error": repr(e)[:300]}),
                      flush=True)
                continue
            row = {"model": "BERT-base", "seq": seq, "batch": batch,
                   "mode": mode, "ms_per_call": round(wall * 1000, 2),
                   "backend": jax.default_backend()}
            if mode == "bf16":
                base_wall = wall
            elif base_wall:
                row["speedup_vs_bf16"] = round(base_wall / wall, 3)
            print(json.dumps(row), flush=True)


def sweep_vit() -> None:
    """Single-chip ViT tuning sweep (VERDICT r3 "next" #2): remat policy x
    batch x scan-unroll, one JSON line per config (so a crash mid-sweep
    loses nothing), best-by-MFU summary last. Grid via env:
    RAFIKI_SWEEP_BATCHES / RAFIKI_SWEEP_REMATS / RAFIKI_SWEEP_UNROLLS."""
    batches = [int(b) for b in os.environ.get(
        "RAFIKI_SWEEP_BATCHES", "128,192,256").split(",")]
    remats = [None if r in ("none", "") else r for r in os.environ.get(
        "RAFIKI_SWEEP_REMATS", "dots,none").split(",")]
    unrolls = [int(u) for u in os.environ.get(
        "RAFIKI_SWEEP_UNROLLS", "1,2,4").split(",")]
    # attention kernel at seq 197 (auto = footprint dispatch -> XLA fused;
    # flash forces the pallas kernel) and bf16 adamw first moment
    flashes = [{"auto": None, "flash": True, "xla": False}[f]
               for f in os.environ.get("RAFIKI_SWEEP_FLASH", "auto").split(",")]
    mus = [m == "bf16" for m in os.environ.get(
        "RAFIKI_SWEEP_MU", "f32,bf16").split(",")]
    qkvs = [q == "1" for q in os.environ.get(
        "RAFIKI_SWEEP_QKV", "0,1").split(",")]
    best = None
    for remat in remats:
        for unroll in unrolls:
            for flash in flashes:
                for mu in mus:
                    for qkv in qkvs:
                        for batch in batches:
                            tag = {"batch": batch, "remat": remat,
                                   "unroll": unroll, "flash": flash,
                                   "mu_bf16": mu, "fused_qkv": qkv}
                            try:
                                r = bench_vit(batch_size=batch, remat=remat,
                                              scan_unroll=unroll,
                                              use_flash=flash, mu_bf16=mu,
                                              fused_qkv=qkv)
                            except Exception as e:  # e.g. OOM without remat
                                print(json.dumps(
                                    {**tag, "error": repr(e)[:300]}),
                                    flush=True)
                                continue
                            print(json.dumps(
                                {**tag, "mfu": r["mfu"],
                                 "images_per_s": r["images_per_s"],
                                 "step_time_ms": r["step_time_ms"]}),
                                flush=True)
                            if best is None or r["mfu"] > best[1]["mfu"]:
                                best = (tag, r)
    if best is not None:
        print(json.dumps({"best": best[0], "result": best[1]}), flush=True)


if __name__ == "__main__":
    import sys

    import jax

    # "0"/"false"/"" (any case/whitespace) must NOT count as small
    small = (jax.default_backend() == "cpu"
             or os.environ.get("RAFIKI_BENCH_SMALL", "").strip().lower()
             not in ("", "0", "false"))
    if "--sweep-vit" in sys.argv:
        sweep_vit()
    elif "--sweep-pggan" in sys.argv:
        sweep_pggan()
    elif "--ablate" in sys.argv:
        bench_ablation()
    elif "--int8" in sys.argv:
        bench_int8(batches=(1, 4) if small else (1, 8, 64),
                   seq=32 if small else 128,
                   n_calls=3 if small else 30)
    elif "--longctx" in sys.argv:
        bench_longctx(seqs=(256, 512) if small else (2048, 4096, 8192),
                      n_steps=2 if small else 8)
    else:
        print(json.dumps(run_all(small=small), indent=2))
