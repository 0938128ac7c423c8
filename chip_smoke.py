#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on the chip.

Drives the two main paths once, through the entry points a user calls, at
the published width of GPT-2 124M (12 layers, 768 wide, 12 heads of 64,
vocab 50257, context 1024) with seeded random weights:

* train:   ``python main.py --model gpt2 ...`` (the CLI) for one epoch of
  32 optimizer steps, then ``--resume`` for one more, so checkpoint write
  and restore both run on device arrays;
* serve:   ``Server(get_model("gpt2"), variables).serve_http()`` answering
  concurrent ``POST /v1/generate`` requests of different prompt lengths,
  once on the contiguous KV cache and once on the paged pool, every
  request's final state, ``engine_errors`` and greedy tokens checked
  (tokens against a standalone ``generate()`` on the same weights);
* kernels: every Pallas kernel compiled by Mosaic (``interpret=False``) at
  GPT-2 shapes and compared with its ``lax`` reference, and the lowered
  train step / prefill program shown to contain the Mosaic custom call;
* dp:      only when JAX reports more than one device — the same training
  through ``Trainer(is_parallel=True)`` with ``dp_update='fused'`` and
  ``'sharded'``, against a one-device run at the same global batch.

It FAILS (non-zero exit, no result line) when the platform is not ``tpu``,
when any phase fails, and in a directory without the rest of the repo.  It
sets neither ``JAX_PLATFORMS`` nor a compile-cache directory.

One process per chip: this parent imports neither ``jax`` nor
``ml_trainer_tpu`` and runs its children — ``main.py`` and
``chip_smoke.py --phase ...`` — one after another.

Every time it prints is a SMOKE TIMING of one cold-or-warm run, compile
included — never a measurement of the system's speed.

Last line of stdout on success, as the driver expects it:
``{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}``
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request
from concurrent.futures import ThreadPoolExecutor

HERE = os.path.dirname(os.path.abspath(__file__))
RESULT_TAG = "SMOKE_RESULT "
DEVICE_TAG = "SMOKE_DEVICE "
EXIT_WRONG_PLATFORM = 3
# A child gets this long before the parent kills its process group; the
# whole smoke must end inside the driver's 1200 s.
CHILD_TIMEOUT_S = 900.0


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


class WrongPlatform(SmokeFailure):
    """JAX came up on another platform than the configuration is for."""


@dataclasses.dataclass(frozen=True)
class SmokeConfig:
    """What the phases run.  The default IS the acceptance configuration;
    tests/test_chip_smoke.py swaps in ``gpt2_tiny`` on the CPU mesh with
    the kernels in interpret mode."""

    model: str = "gpt2"
    vocab_size: int = 50257
    seq_len: int = 1024          # training context == model max_len
    platform: str = "tpu"        # what jax.devices()[0].platform must say
    interpret: bool = False      # Pallas interpret mode (CPU test only)
    # train (CLI) — 256 / 8 = 32 optimizer steps an epoch
    batch_size: int = 8
    train_size: int = 256
    val_size: int = 64
    loss_chunk: int = 128
    lr: float = 1e-4
    # serve
    prompt_lens: tuple = (5, 23, 70, 130, 200)   # buckets 8/32/128/256/256
    new_tokens: int = 12
    kv_page_size: int = 16
    serve_modes: tuple = ("contiguous", "paged")
    # dp — 4 steps an epoch at the same global batch as the CLI run
    dp_train_size: int = 32
    dp_epochs: int = 2
    seed: int = 0


FULL = SmokeConfig()

# A greedy token may differ from generate()'s only at a near-tie: batch-8
# slots, the padded flash prefill and batch-1 generate() tile their
# matmuls differently, and on the TPU an f32 matmul runs as bf16 passes,
# so two programs agree on a logit to a few parts in a thousand of the
# logit scale, not to the bit.  A divergence is accepted when the
# reference margin between the two candidate tokens, taken from a plain
# forward over the agreed prefix, is under this fraction of the row's
# logit spread (max - mean); anything larger fails the phase.
TIE_FRACTION = 0.02

# Absolute floors of the kernel criterion (_judge), relative to the
# reference's largest magnitude.  An f32 matmul on the MXU at default
# precision rounds its operands to bf16 — 2^-8 = 3.9e-3 — whether Mosaic or
# XLA issues it (first chip run: 1.5e-3..2.8e-3 on either side); a bf16
# RESULT is itself rounded to 8 bits of mantissa before any arithmetic
# differs (first chip run: 4.4e-3..7.3e-3).
F32_MXU_FLOOR = 5e-3
BF16_FLOOR = 1e-2


# ===================================================================== child
def _say(msg: str) -> None:
    print(msg, flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)
    _say(f"    ok: {what}")


def require_platform(cfg: SmokeConfig) -> dict:
    """First thing every child does: name the device as JAX reports it,
    and refuse any platform but the one the configuration is for."""
    import jax

    devices = jax.devices()
    facts = {"platform": devices[0].platform,
             "kind": devices[0].device_kind, "count": len(devices)}
    _say(DEVICE_TAG + json.dumps(facts))
    if facts["platform"] != cfg.platform:
        raise WrongPlatform(
            f"platform is {facts['platform']!r} ({facts['kind']}), need "
            f"{cfg.platform!r}: no accelerator, no smoke"
        )
    return facts


def _model(cfg: SmokeConfig, **kw):
    from ml_trainer_tpu.models import get_model

    return get_model(
        cfg.model, vocab_size=cfg.vocab_size, max_len=cfg.seq_len, **kw
    )


def _rel_err(got, want) -> float:
    import numpy as np

    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    scale = float(np.max(np.abs(want))) or 1.0
    return float(np.max(np.abs(got - want))) / scale


def _judge(name: str, kernel_out, lax_out, ref_out, floor: float) -> dict:
    """The repo's kernel criterion (scripts/validate_flash_tpu.py): against
    the lax path traced under float32 matmul precision, the kernel's error
    is under an absolute floor or within 3x of the error of the default-
    precision lax path it replaces — no less accurate than its reference,
    rather than held to a bound the reference itself misses on the MXU."""
    import jax

    k_leaves = jax.tree.leaves(kernel_out)
    l_leaves = jax.tree.leaves(lax_out)
    r_leaves = jax.tree.leaves(ref_out)
    k_err = max(_rel_err(k, r) for k, r in zip(k_leaves, r_leaves))
    l_err = max(_rel_err(x, r) for x, r in zip(l_leaves, r_leaves))
    bound = max(floor, 3.0 * l_err)
    _check(
        k_err <= bound,
        f"{name}: kernel rel err {k_err:.2e} <= {bound:.2e} "
        f"(lax default-precision err {l_err:.2e})",
    )
    return {"kernel_rel_err": k_err, "lax_rel_err": l_err}


def _three_ways(name, kernel_fn, lax_fn, args, floor):
    """kernel (Mosaic, or interpret in the CPU test) vs lax at default
    precision vs lax under float32 matmul precision."""
    import jax

    kernel_out = jax.block_until_ready(jax.jit(kernel_fn)(*args))
    lax_out = jax.block_until_ready(jax.jit(lax_fn)(*args))
    with jax.default_matmul_precision("float32"):
        ref_out = jax.block_until_ready(jax.jit(lax_fn)(*args))
    return _judge(name, kernel_out, lax_out, ref_out, floor)


def kernel_cases(cfg: SmokeConfig):
    """Every Pallas kernel at the model's shapes beside its lax reference:
    yields ``(name, kernel_fn, lax_fn, args, floor)``."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ml_trainer_tpu.ops.attention import (
        dot_product_attention,
        flash_attention,
    )
    from ml_trainer_tpu.ops.kernels import (
        decode_attention,
        decode_attention_append,
        fused_adam_update,
        int8_matmul,
        paged_attention,
        quantize_per_channel,
        slot_cache_write,
        unscale_sqsum,
    )
    from ml_trainer_tpu.ops.kernels.fused_adam import _SQSUM_VMEM_ELEMS

    m = _model(cfg)
    H, E, V, L = m.num_heads, m.embed_dim, m.vocab_size, m.max_len
    D = E // H
    rng = np.random.default_rng(cfg.seed)
    interp = cfg.interpret

    def normal(shape, dtype, scale=1.0):
        return jnp.asarray(rng.normal(size=shape) * scale, dtype)

    # -- flash attention: the default training and prefill path ------------
    for tag, dtype, b, s, causal, lens in (
        ("train bf16 causal", jnp.bfloat16, 2, L, True, None),
        ("prefill f32 causal", jnp.float32, 1, min(256, L), True, None),
        ("kv_lens bf16", jnp.bfloat16, 2, L, False,
         jnp.asarray([L, max(L // 5, 1)], jnp.int32)),
    ):
        qkv = tuple(normal((b, H, s, D), dtype, 0.5) for _ in range(3))
        mask = None if lens is None else (
            jnp.arange(s)[None, None, None, :] < lens[:, None, None, None]
        )

        def flash(q, k, v, lens=lens, causal=causal):
            return flash_attention(
                q, k, v, lens, causal, None, None, None, interp
            )

        def lax_attn(q, k, v, mask=mask, causal=causal):
            return dot_product_attention(q, k, v, causal=causal, mask=mask)

        def grads(fn):
            return jax.grad(
                lambda q, k, v: fn(q, k, v).astype(jnp.float32).sum(),
                argnums=(0, 1, 2),
            )

        bf16 = dtype == jnp.bfloat16
        yield (f"flash forward [{tag}] {(b, H, s, D)}", flash, lax_attn,
               qkv, BF16_FLOOR if bf16 else F32_MXU_FLOOR)
        yield (f"flash backward dq/dk/dv [{tag}]", grads(flash),
               grads(lax_attn), qkv, 5e-2 if bf16 else 2e-2)

    # -- fused optimizer tail: on by itself for dp_update='sharded' + Adam -
    scalars = dict(
        bc1=jnp.float32(1 - 0.9 ** 3), bc2=jnp.float32(1 - 0.999 ** 3),
        step_size=jnp.float32(-1e-3), lr_scale=jnp.float32(1.0),
    )
    for tag, shape, factor in (
        ("embedding, whole", (V, E), None),          # GPT-2: 38.6M elements
        ("mlp shard /4", (E // 4, 4 * E), jnp.float32(0.5)),
        ("bias shard /4", (E // 4,), jnp.float32(0.5)),
    ):
        args = (normal(shape, jnp.float32, 1e-2), normal(shape, jnp.float32),
                normal(shape, jnp.float32, 1e-3),
                jnp.square(normal(shape, jnp.float32, 1e-2)))

        def adam(impl, factor=factor):
            return lambda g, p, mu, nu: fused_adam_update(
                g, p, mu, nu, factor=factor, implementation=impl,
                interpret=interp, **scalars,
            )

        yield (f"fused_adam_update [{tag}] {shape}", adam("pallas"),
               adam("reference"), args, 1e-5)
    for tag, shape, static in (
        ("mlp shard /4, static denom", (E // 4, 4 * E), True),
        ("mlp shard /4, traced denom", (E // 4, 4 * E), False),
        ("bias shard /4", (E // 4,), True),
        ("at the VMEM cap", (_SQSUM_VMEM_ELEMS // 1024, 1024), True),
    ):
        def unscale(impl, static=static):
            return lambda g, d: unscale_sqsum(
                g, 32.0 if static else d, implementation=impl,
                interpret=interp)

        yield (f"unscale_sqsum [{tag}] {shape}", unscale("pallas"),
               unscale("reference"),
               (normal(shape, jnp.float32, 1e-2), jnp.float32(32.0)), 1e-5)

    # -- paged-attention decode (opt-in: Server(paged_kernel=True)) --------
    B, ps = 8, cfg.kv_page_size
    P = L // ps
    for dtype in (jnp.float32, jnp.bfloat16):
        args = (
            normal((B, H, D), dtype, 0.5),
            normal((B * P + 1, H, ps, D), dtype, 0.5),
            normal((B * P + 1, H, ps, D), dtype, 0.5),
            jnp.asarray(rng.permutation(B * P).reshape(B, P) + 1, jnp.int32),
            jnp.asarray(rng.integers(1, L, size=B), jnp.int32),
        )

        def paged(impl):
            return lambda q, k, v, t, n: paged_attention(
                q, k, v, t, n, implementation=impl, interpret=interp)

        yield (f"paged_attention [{dtype.__name__}] B={B} L={L} page={ps}",
               paged("pallas"), paged("reference"), args,
               BF16_FLOOR if dtype == jnp.bfloat16 else F32_MXU_FLOOR)

    # -- slot-cache write: every decode step of the slot engine ------------
    for dtype in (jnp.float32, jnp.bfloat16):
        pos = rng.integers(0, L, size=B)
        pos[:4] = (0, L - 1, L, L + 500)     # both ends, and the clamp
        args = (
            normal((B, H, L, D), dtype, 0.5), normal((B, H, L, D), dtype, 0.5),
            normal((B, H, 1, D), dtype, 0.5), normal((B, H, 1, D), dtype, 0.5),
            jnp.asarray(pos, jnp.int32),
        )

        def write(impl):
            return lambda kc, vc, kn, vn, at: slot_cache_write(
                kc, vc, kn, vn, at, implementation=impl, interpret=interp)

        # It moves bytes and rounds nothing: no floor.
        yield (f"slot_cache_write [{dtype.__name__}] {(B, H, L, D)}",
               write("pallas"), write("reference"), args, 0.0)

    # -- decode attention: every decode step of the slot engine -----------
    # The two serving cells' caches, one a layout (heads of 64: the
    # position on the lanes; of 128: on the sublanes, eight query heads a
    # key-value head), rows of ragged lengths; the interpreter gets the same
    # two layouts small.
    for b, h, g, length, d in (
            ((4, 4, 4, 256, 64), (4, 16, 2, 256, 128)) if interp else
            ((32, 20, 20, 1024, 64), (64, 64, 8, 2048, 128))):
        lens = rng.integers(1, length + 1, size=b)
        lens[:4] = (1, length, length + 500, 257)   # the ends, clamp, an edge
        args = (
            normal((b, h, 1, d), jnp.bfloat16, 0.5),
            normal((b, g, length, d), jnp.bfloat16, 0.5),
            normal((b, g, length, d), jnp.bfloat16, 0.5),
            jnp.asarray(lens, jnp.int32),
        )

        def attend(impl):
            return lambda q, kc, vc, n: decode_attention(
                q, kc, vc, n, implementation=impl, interpret=interp)

        yield (f"decode_attention [bfloat16] {(b, h, 1, d)} x "
               f"{(b, g, length, d)}", attend("pallas"), attend("reference"),
               args, BF16_FLOOR)

        # The step as the engine makes it since PR 35, one call that appends
        # this step's rows at each row's position while it reads the row:
        # the output and both caches against the scatter then the masked
        # attention (the caches move bytes: any difference is past the floor
        # of a bfloat16's rounding).
        pos = lens - 1
        pos[2] = length + 500                       # a free row's: clamped
        step_args = (
            args[0], normal((b, g, 1, d), jnp.bfloat16, 0.5),
            normal((b, g, 1, d), jnp.bfloat16, 0.5), args[1], args[2],
            jnp.asarray(pos, jnp.int32),
        )

        def append(impl):
            return lambda *a: decode_attention_append(
                *a, implementation=impl, interpret=interp)

        yield (f"decode_attention_append [bfloat16] {(b, h, 1, d)} x "
               f"{(b, g, length, d)}", append("pallas"), append("reference"),
               step_args, BF16_FLOOR)

    # -- retention state step: every decode step of a power retention layer
    # A slot's key-value heads at the published group (five query heads of
    # 128 over a state of 128 x 8,320), the padding under phi's last half
    # row nought as the prompt's form leaves it; the interpreter gets the
    # same call small.  All float32 on the VPU: held to 1e-5.
    from ml_trainer_tpu.ops.kernels.retention_state_step import (
        retention_state_step,
    )
    from ml_trainer_tpu.ops.power_retention import phi_padded

    for b, g, r, d in ((2, 2, 5, 128),) if interp else ((4, 8, 5, 128),):
        p = phi_padded(d)
        live = jnp.arange(p) < p - d // 2
        args = (
            normal((b, g, r, d), jnp.float32), normal((b, g, d), jnp.float32),
            normal((b, g, d), jnp.float32),
            jnp.asarray(rng.uniform(size=(b, g)), jnp.float32),
            normal((b, g, d, p), jnp.float32) * live,
            jnp.abs(normal((b, g, p), jnp.float32)) * live,
        )

        def step(impl):
            return lambda *a: retention_state_step(
                *a, implementation=impl, interpret=interp)

        yield (f"retention_state_step [float32] {(b, g, r, d)} x "
               f"{(b, g, d, p)}", step("pallas"), step("reference"), args,
               1e-5)

    # -- int8 decode matmul (opt-in: Server(quant_int8=True)) --------------
    for tag, kk, nn in (("qkv", E, 3 * E), ("proj", E, E),
                        ("fc_in", E, 4 * E), ("fc_out", 4 * E, E)):
        w_q, scale = quantize_per_channel(
            normal((kk, nn), jnp.float32, 0.02))

        def int8(impl):
            return lambda x, w, s: int8_matmul(
                x, w, s, implementation=impl, interpret=interp)

        yield (f"int8_matmul [{tag}] K={kk} N={nn}", int8("pallas"),
               int8("reference"),
               (normal((B, 1, kk), jnp.float32), w_q, scale), F32_MXU_FLOOR)


def check_kernels(cfg: SmokeConfig) -> dict:
    """Compile and run every kernel case; the first that misses its bound
    (or that the compiler refuses) fails the phase.  No timing — speed is
    the benchmark's business."""
    return {
        name: _three_ways(name, kernel_fn, lax_fn, args, floor)
        for name, kernel_fn, lax_fn, args, floor in kernel_cases(cfg)
    }


def check_native_library() -> str:
    """Build (from the sources, keyed by their content) and load the C++
    batch worker once; a machine with g++ must manage both.  The GPT-2
    token stream has no native plan, so the Python loader is what trains
    there — this is the only place the smoke touches the library."""
    if shutil.which("g++") is None:
        return "skipped: no g++ on this machine"
    from ml_trainer_tpu.data import native

    native.load_library()
    return os.path.basename(native._library_path())


def _trainer(cfg: SmokeConfig, workdir: str, tag: str, **kw):
    """The CLI run's configuration through the library entry point (the
    CLI has no flag for ``dp_update``)."""
    import jax.numpy as jnp

    from ml_trainer_tpu import Trainer
    from ml_trainer_tpu.data import SyntheticTokens

    tokens = dict(seq_len=cfg.seq_len, vocab_size=cfg.vocab_size)
    return Trainer(
        _model(cfg, dtype=jnp.bfloat16, loss_chunk=cfg.loss_chunk),
        datasets=(SyntheticTokens(size=cfg.dp_train_size, **tokens),
                  SyntheticTokens(size=cfg.batch_size, seed=1, **tokens)),
        epochs=cfg.dp_epochs, batch_size=cfg.batch_size, metric=None,
        optimizer="adam", lr=cfg.lr, seed=cfg.seed,
        model_dir=os.path.join(workdir, tag), **kw,
    )


def _first_batch(trainer):
    from ml_trainer_tpu.data import prefetch_to_device

    return next(iter(prefetch_to_device(
        iter(trainer.train_loader), size=1, sharding=trainer._batch_sharding
    )))


def _lower_train_step(trainer):
    import jax.numpy as jnp

    x, y = _first_batch(trainer)
    return trainer._train_step.lower(
        trainer.state, x, y, jnp.asarray(1.0, jnp.float32)
    )


def mosaic_call_sites(lowered_text: str) -> int:
    """Mosaic custom calls that ``main`` of a lowered module executes.  A
    jitted kernel wrapper lowers to ONE private function that every layer
    calls, so the text holds each kernel once however many layers run it:
    follow the calls instead of counting the text."""
    import re

    bodies = re.split(r"func\.func (?:public |private )?@([\w.$-]+)\(",
                      lowered_text)
    funcs = dict(zip(bodies[1::2], bodies[2::2]))
    totals: dict = {}

    def total(name: str) -> int:
        if name not in totals:
            body = funcs.get(name, "")
            totals[name] = body.count("tpu_custom_call") + sum(
                total(callee)
                for callee in re.findall(r"(?<!\w)call @([\w.$-]+)\(", body)
            )
        return totals[name]

    return total("main")


def phase_kernels(cfg: SmokeConfig, workdir: str) -> dict:
    facts = {"native_library": check_native_library()}
    _say(f"    native batch worker: {facts['native_library']}")
    facts["kernels"] = check_kernels(cfg)
    # 'auto' means "Pallas on TPU": a shape guard could route the chip run
    # to the lax path unnoticed, so look at the lowered train step.
    trainer = _trainer(cfg, workdir, "lowering")
    facts["loader"] = type(trainer.train_loader).__name__
    calls = mosaic_call_sites(_lower_train_step(trainer).as_text())
    facts["train_step_custom_calls"] = calls
    if cfg.platform == "tpu":
        depth = trainer.model.depth
        _check(calls >= 3 * depth,
               f"lowered GPT-2 train step holds {calls} Mosaic custom "
               f"calls (flash fwd + dq + dkv in each of {depth} layers)")
    return facts


# ---------------------------------------------------------------- serve
def _post_generate(url: str, prompt, new_tokens: int) -> dict:
    body = json.dumps({
        "prompt": [int(t) for t in prompt], "max_new_tokens": new_tokens,
    }).encode()
    req = urllib.request.Request(
        url + "/v1/generate", data=body,
        headers={"Content-Type": "application/json"},
    )
    try:
        with urllib.request.urlopen(req, timeout=CHILD_TIMEOUT_S) as r:
            return {"status": r.status, **json.loads(r.read())}
    except urllib.error.HTTPError as e:
        return {"status": e.code, "error": e.read().decode(errors="replace")}


def _get_json(url: str, path: str) -> dict:
    with urllib.request.urlopen(url + path, timeout=60) as r:
        return json.loads(r.read())


def serve_round(url: str, prompts, new_tokens: int) -> list:
    """POST every prompt at once, as concurrent clients would."""
    with ThreadPoolExecutor(max_workers=len(prompts)) as pool:
        futures = [
            pool.submit(_post_generate, url, p, new_tokens) for p in prompts
        ]
        return [f.result() for f in futures]


def check_serve_outcome(server, url: str, n_requests: int) -> dict:
    """The loop in serving/api.py survives an engine exception by ending
    the affected requests in state ``error`` and carrying on — so a decode
    step the compiler refuses would still be a run of 200-series answers.
    Here every request's final state and the error counters are read."""
    states = [tl["state"] for tl in server.slo.timelines()]
    metrics = _get_json(url, "/metrics.json")
    slo = _get_json(url, "/slo")
    _check(len(states) == n_requests and all(s == "done" for s in states),
           f"all {n_requests} requests ended in state 'done' "
           f"(states: {sorted(set(states))}, {len(states)} seen)")
    _check(metrics["engine_errors"] == 0 and metrics["watchdog_trips"] == 0,
           f"/metrics.json engine_errors={metrics['engine_errors']} "
           f"watchdog_trips={metrics['watchdog_trips']}")
    _check(metrics["requests_completed"] == n_requests
           and slo["requests_failed"] == 0,
           f"requests_completed={metrics['requests_completed']} "
           f"/slo requests_failed={slo['requests_failed']}")
    return {"tokens_total": metrics["tokens_total"],
            "max_active_slots": metrics["max_active_slots"]}


def check_tokens(model, variables, answers, prompts, references,
                 new_tokens: int) -> list:
    """Greedy tokens against generate().  Returns the near-tie
    divergences (empty when every token agrees)."""
    import jax
    import numpy as np

    ties = []
    for i, (ans, prompt, ref) in enumerate(zip(answers, prompts, references)):
        _check(ans["status"] == 200, f"request {i}: HTTP {ans['status']} "
                                     f"{ans.get('error', '')}")
        got = np.asarray(ans["tokens"], np.int32)
        if got.shape != ref.shape or not np.array_equal(
                got[:len(prompt)], prompt):
            raise SmokeFailure(
                f"request {i}: {got.shape[0]} tokens back for a prompt of "
                f"{len(prompt)} + {new_tokens} new, or the prompt changed"
            )
        diff = np.flatnonzero(got != ref)
        if diff.size == 0:
            continue
        # First divergence: the reference margin over the agreed prefix.
        at = int(diff[0])
        logits = np.asarray(jax.jit(
            lambda v, ids: model.apply(v, ids, train=False)
        )(variables, ref[None, :at])[0, -1], np.float32)
        margin = float(logits[ref[at]] - logits[got[at]])
        spread = float(logits.max() - logits.mean())
        tie = {"request": i, "prompt_len": len(prompt),
               "position": at - len(prompt), "margin": margin,
               "logit_spread": spread}
        _say(f"    request {i} (prompt {len(prompt)}): first divergence at "
             f"new token {tie['position']}, reference margin "
             f"{margin:.4g} of spread {spread:.4g}")
        if abs(margin) > TIE_FRACTION * spread:
            raise SmokeFailure(
                f"request {i} diverges from generate() at new token "
                f"{tie['position']} with reference margin {margin:.4g} "
                f"> {TIE_FRACTION} x logit spread {spread:.4g}: not a "
                "near-tie"
            )
        ties.append(tie)
    return ties


def phase_serve(cfg: SmokeConfig, workdir: str) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ml_trainer_tpu import generate
    from ml_trainer_tpu.serving.api import Server
    from ml_trainer_tpu.telemetry import compile_watch
    from ml_trainer_tpu.telemetry.memory import live_memory_snapshot

    facts = {}
    model = _model(cfg)
    variables = jax.jit(model.init, static_argnames="train")(
        {"params": jax.random.PRNGKey(cfg.seed)},
        jnp.zeros((1, 8), jnp.int32), train=False,
    )
    rng = np.random.default_rng(cfg.seed)
    # Two prompt sets of the same lengths: A warms every program up, B runs
    # under the compile watch (fresh tokens, so the paged pool's prefix
    # cache cannot turn B into a different program).
    sets = [
        [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
         for n in cfg.prompt_lens]
        for _ in range(2)
    ]
    t0 = time.time()
    refs = [
        [np.asarray(generate(model, variables, p[None], cfg.new_tokens))[0]
         for p in prompts]
        for prompts in sets
    ]
    _say(f"    generate() references for {len(cfg.prompt_lens)} prompt "
         f"lengths x 2 sets: {time.time() - t0:.1f} s (smoke timing)")
    facts["modes"] = {}
    for mode in cfg.serve_modes:
        _say(f"  serve [{mode}]")
        server = Server(
            model, variables, max_batch=8,
            kv_page_size=cfg.kv_page_size if mode == "paged" else 0,
            # First-hit compiles run on the engine thread; the default
            # 60 s is sized for a warm server.
            watchdog_timeout=CHILD_TIMEOUT_S,
        )
        try:
            host, port = server.serve_http(port=0)
            url = f"http://{host}:{port}"
            t0 = time.time()
            warm = serve_round(url, sets[0], cfg.new_tokens)
            t_warm = time.time() - t0
            compiles_before = compile_watch.compile_count()
            t0 = time.time()
            hot = serve_round(url, sets[1], cfg.new_tokens)
            t_hot = time.time() - t0
            recompiles = compile_watch.compile_count() - compiles_before
            outcome = check_serve_outcome(
                server, url, 2 * len(cfg.prompt_lens))
            ties = check_tokens(model, variables, warm + hot,
                                sets[0] + sets[1], refs[0] + refs[1],
                                cfg.new_tokens)
            _check(recompiles == 0,
                   f"compiles after warm-up: {recompiles} (compile_watch)")
            facts["modes"][mode] = {
                **outcome, "near_ties": ties,
                "exact_requests": 2 * len(cfg.prompt_lens) - len(ties),
                "warmup_round_s": round(t_warm, 1),
                "warm_round_s": round(t_hot, 2),
            }
            _say(f"    round A (compiles) {t_warm:.1f} s, round B "
                 f"{t_hot:.2f} s (smoke timings); "
                 f"{facts['modes'][mode]['exact_requests']}/"
                 f"{2 * len(cfg.prompt_lens)} requests token-exact with "
                 f"generate(), {len(ties)} near-tie(s)")
            if mode == cfg.serve_modes[0] and cfg.platform == "tpu":
                bucket = 128
                lowered = server.engine._build_prefill(bucket).lower(
                    server.engine.params, np.zeros((1, bucket), np.int32),
                    np.int32(bucket - 1), jnp.asarray(0.0, jnp.float32),
                    jax.random.PRNGKey(0), np.int32(0),
                )
                calls = lowered.as_text().count("tpu_custom_call")
                _check(calls >= model.depth,
                       f"lowered prefill (bucket {bucket}) holds {calls} "
                       "Mosaic custom calls (flash forward per layer)")
                facts["prefill_custom_calls"] = calls
        finally:
            server.close()
    mem = live_memory_snapshot()
    facts["memory"] = {"source": mem["source"],
                       "max_peak_bytes": mem["max_peak_bytes_in_use"]}
    if cfg.platform == "tpu":
        _check(mem["source"] == "memory_stats"
               and mem["max_peak_bytes_in_use"] > 0,
               f"peak device memory from memory_stats(): "
               f"{mem['max_peak_bytes_in_use'] / 2 ** 30:.2f} GiB")
    return facts


# ------------------------------------------------------------------- dp
def _flash_call_shapes(compiled_text: str) -> dict:
    """Operand shapes of the Mosaic custom calls in a compiled module, and
    how many all-gathers it holds."""
    import re

    shapes: dict = {}
    for line in compiled_text.splitlines():
        if "tpu_custom_call" not in line or "custom-call(" not in line:
            continue
        operands = line.split("custom-call(", 1)[1]
        found = re.findall(r"(?:bf16|f32|s32)\[[0-9,]*\]", operands)[:3]
        key = " ".join(found)
        shapes[key] = shapes.get(key, 0) + 1
    return {
        "custom_call_operands": shapes,
        "all_gathers": len(re.findall(r"\ball-gather(?:-start)?\(",
                                      compiled_text)),
    }


def phase_dp(cfg: SmokeConfig, workdir: str) -> dict:
    import jax

    from ml_trainer_tpu.telemetry.memory import live_memory_snapshot

    n = jax.device_count()
    facts = {"runs": {}}
    # The one-device run goes LAST: peak_bytes_in_use never resets, and
    # device 0's peak must be the data-parallel runs' own.
    for tag, kw in (
        ("fused", dict(is_parallel=True, dp_update="fused")),
        ("sharded", dict(is_parallel=True, dp_update="sharded")),
        ("one_device", dict(is_parallel=False)),
    ):
        _say(f"  dp [{tag}]")
        t0 = time.time()
        trainer = _trainer(cfg, workdir, tag, **kw)
        run = {"mesh": dict(trainer.mesh.shape),
               "dp_update": trainer.dp_update,
               "fused_adam": trainer.fused_adam,
               "loader": type(trainer.train_loader).__name__}
        if tag != "one_device":
            _check(run["mesh"] == {"data": n}, f"mesh is data={n}")
            x, _ = _first_batch(trainer)
            state_devices = set()
            for leaf in jax.tree.leaves(trainer.state):
                state_devices |= set(leaf.sharding.device_set)
            _check(len(x.sharding.device_set) == n
                   and len(state_devices) == n,
                   f"batch sharding {x.sharding.spec} and the state's "
                   f"shardings cover {n} devices; a batch shard is "
                   f"{x.addressable_shards[0].data.shape}")
            compiled = _lower_train_step(trainer).compile()
            run.update(_flash_call_shapes(compiled.as_text()))
            _say(f"    Mosaic custom calls by leading operand shapes: "
                 f"{run['custom_call_operands']}; all-gathers: "
                 f"{run['all_gathers']}")
            if cfg.platform == "tpu":
                _check(bool(run["custom_call_operands"]),
                       "compiled step holds Mosaic custom calls")
        trainer.fit()
        run["train_loss"] = [float(v) for v in trainer.train_losses]
        run["val_loss"] = [float(v) for v in trainer.val_losses]
        run["seconds"] = round(time.time() - t0, 1)
        mem = live_memory_snapshot()
        run["memory_source"] = mem["source"]
        run["peak_bytes"] = {
            d: v["peak_bytes_in_use"] for d, v in mem["devices"].items()
        }
        _say(f"    train loss {run['train_loss']} val loss "
             f"{run['val_loss']}; {run['seconds']} s (smoke timing)")
        _say("    peak bytes by device: " + ", ".join(
            f"{d}: {b / 2 ** 30:.2f} GiB"
            for d, b in run["peak_bytes"].items()))
        if tag != "one_device":
            peaks = list(run["peak_bytes"].values())
            _check((mem["source"] == "memory_stats"
                    or cfg.platform != "tpu") and len(peaks) == n
                   and min(peaks) > 0 and max(peaks) <= 2 * min(peaks),
                   f"peak memory ({mem['source']}) is of the same order "
                   f"on all {n} devices")
        facts["runs"][tag] = run
        del trainer
    ref = facts["runs"]["one_device"]
    for tag in ("fused", "sharded"):
        run = facts["runs"][tag]
        gaps = [abs(a - b) / abs(b) for a, b in
                zip(run["train_loss"] + run["val_loss"],
                    ref["train_loss"] + ref["val_loss"])]
        _check(max(gaps) <= 5e-3 and run["train_loss"][-1] <
               run["train_loss"][0],
               f"{tag}: epoch losses within {max(gaps):.2e} (<= 5e-03 "
               f"relative) of the one-device run at the same global batch, "
               "and falling")
    return facts


PHASES = {"kernels": phase_kernels, "serve": phase_serve, "dp": phase_dp}


def child_main(phase: str, workdir: str, cfg: SmokeConfig = FULL) -> int:
    """Run one in-process phase; the parent reads the tagged lines."""
    t0 = time.time()
    try:
        facts = {"device": require_platform(cfg)}
        import jax

        from ml_trainer_tpu.telemetry import compile_watch
        from ml_trainer_tpu.trainer import enable_compilation_cache

        enable_compilation_cache()  # the package's one place; not set here
        compile_watch.install()
        facts.update(PHASES[phase](cfg, workdir))
    except SmokeFailure as e:
        _say(f"  FAIL [{phase}]: {e}")
        return EXIT_WRONG_PLATFORM if isinstance(e, WrongPlatform) else 1
    facts["compile_cache"] = {
        "dir": jax.config.jax_compilation_cache_dir,
        **compile_watch.persistent_cache_counts(),
    }
    facts["seconds"] = round(time.time() - t0, 1)
    _say(RESULT_TAG + json.dumps(facts, default=str))
    return 0


# ==================================================================== parent
def run_child(cmd: list, log: list, full_log, announce_device: bool) -> int:
    """Run one child to its end, echoing its output (all of it goes to
    ``full_log``); a child that outlives CHILD_TIMEOUT_S is killed.  It
    stays in this process's group, so whoever kills the smoke's group
    stops the child too.  With ``announce_device`` the child's device line
    is printed before anything else of the run: what the child says ahead
    of it is held back until then."""
    full_log.write(f"$ {' '.join(cmd)}\n")
    proc = subprocess.Popen(
        cmd, cwd=HERE, stdout=subprocess.PIPE, stderr=subprocess.STDOUT,
        text=True,
    )
    timer = threading.Timer(CHILD_TIMEOUT_S, proc.kill)
    timer.start()
    held: list = []
    try:
        for line in proc.stdout:
            line = line.rstrip("\n")
            log.append(line)
            full_log.write(line + "\n")
            if line.startswith(DEVICE_TAG) and announce_device:
                announce_device = False
                d = json.loads(line[len(DEVICE_TAG):])
                held.insert(0, f"device: platform={d['platform']} "
                               f"device_kind={d['kind']} count={d['count']}")
            elif not line.startswith((RESULT_TAG, DEVICE_TAG)) and (
                    line.strip() and "/batch" not in line
                    and "batch/s" not in line):
                held.append("  | " + line)  # (tqdm bars left out)
            if held and not announce_device:
                print("\n".join(held), flush=True)
                held.clear()
        return proc.wait()
    finally:
        timer.cancel()
        proc.kill()  # (a no-op once it has exited)
        if held:
            print("\n".join(held), flush=True)


def _tagged(log: list, tag: str):
    for line in reversed(log):
        if line.startswith(tag):
            return json.loads(line[len(tag):])
    return None


def _cache_entries(cache_dir) -> int:
    try:
        return len(os.listdir(cache_dir)) if cache_dir else 0
    except OSError:
        return 0


def check_history(workdir: str, epochs: int, steps_per_epoch: int) -> str:
    """What the CLI left behind: the history the Trainer writes."""
    with open(os.path.join(workdir, "train", "history.json")) as f:
        hist = json.load(f)
    losses = hist["train_loss"] + hist["val_loss"]
    if hist["epochs"] != list(range(1, epochs + 1)):
        raise SmokeFailure(f"history holds epochs {hist['epochs']}, "
                           f"expected 1..{epochs}")
    if not all(v == v and abs(v) != float("inf") for v in losses):
        raise SmokeFailure(f"non-finite loss in history: {losses}")
    if any(hist["skipped_steps"]) or hist["rollbacks"]:
        raise SmokeFailure(
            f"the non-finite guard skipped steps {hist['skipped_steps']} "
            f"(rollbacks {hist['rollbacks']})")
    if epochs > 1 and not hist["train_loss"][-1] < hist["train_loss"][0]:
        raise SmokeFailure(f"train loss is not falling: "
                           f"{hist['train_loss']}")
    return (f"{epochs * steps_per_epoch} optimizer steps, train loss "
            f"{[round(v, 4) for v in hist['train_loss']]}, val loss "
            f"{[round(v, 4) for v in hist['val_loss']]}")


def parent_main(cfg: SmokeConfig = FULL) -> int:
    t_start = time.time()
    workdir = tempfile.mkdtemp(prefix="chip_smoke_")
    me = [sys.executable, os.path.abspath(__file__), "--workdir", workdir]
    cli = [
        sys.executable, os.path.join(HERE, "main.py"), "--model", cfg.model,
        "--synthetic_tokens", "--vocab_size", str(cfg.vocab_size),
        "--seq_len", str(cfg.seq_len), "--dtype", "bfloat16",
        "--loss_chunk", str(cfg.loss_chunk), "--optimizer", "adamw",
        "--lr", str(cfg.lr), "--batch_size", str(cfg.batch_size),
        "--synthetic_train_size", str(cfg.train_size),
        "--synthetic_val_size", str(cfg.val_size),
        "--model_dir", os.path.join(workdir, "train"),
    ]
    steps = cfg.train_size // cfg.batch_size
    # Everything the children print, for when the end of stdout is not
    # enough (chiprun brings chiprun_out/ back).
    os.makedirs(os.path.join(HERE, "chiprun_out"), exist_ok=True)
    full_log = open(os.path.join(HERE, "chiprun_out", "chip_smoke.log"),
                    "a", encoding="utf-8")
    results: list = []   # (phase, passed, seconds, detail)
    device = None
    cache_dir = None

    def phase(name, cmd, verify=None):
        nonlocal device, cache_dir
        log: list = []
        before = _cache_entries(cache_dir)
        t0 = time.time()
        rc = run_child(cmd, log, full_log, announce_device=device is None)
        secs = time.time() - t0
        detail, passed = f"exit code {rc}", rc == 0
        facts = _tagged(log, RESULT_TAG)
        device = device or _tagged(log, DEVICE_TAG)
        if facts and facts["compile_cache"]["dir"]:
            cache_dir = facts["compile_cache"]["dir"]
        if passed and verify is not None:
            try:
                detail = verify(log, facts)
            except (SmokeFailure, OSError, KeyError, ValueError) as e:
                passed, detail = False, f"{type(e).__name__}: {e}"
        cache = ""
        if facts:
            c = facts["compile_cache"]
            cache = (f"; compile cache hits={c['hits']} "
                     f"misses={c['misses']}")
        elif cache_dir:
            cache = (f"; compile cache entries added: "
                     f"{_cache_entries(cache_dir) - before}")
        results.append((name, passed, secs, detail))
        print(f"phase {name}: {'PASS' if passed else 'FAIL'} in "
              f"{secs:.0f} s (smoke timing, compile included{cache}) — "
              f"{detail}", flush=True)
        return rc, facts

    def kernels_detail(log, facts):
        return (f"{len(facts['kernels'])} kernel checks, "
                f"{facts['train_step_custom_calls']} Mosaic custom calls "
                f"in the lowered train step, native library "
                f"{facts['native_library']}, loader {facts['loader']}")

    def train_detail(epochs):
        def verify(log, facts):
            text = "\n".join(log)
            if "Training on device: tpu." not in text and (
                    cfg.platform == "tpu"):
                raise SmokeFailure("the CLI did not train on the tpu")
            if epochs > 1 and "Resuming from epoch 2" not in text:
                raise SmokeFailure("--resume did not restore epoch 1")
            native = "Using the native (C++) input pipeline." in text
            return (check_history(workdir, epochs, steps) + ", loader "
                    + ("native" if native else "python"))
        return verify

    def serve_detail(log, facts):
        return "; ".join(
            f"{mode}: {m['exact_requests']} token-exact + "
            f"{len(m['near_ties'])} near-tie of "
            f"{m['exact_requests'] + len(m['near_ties'])} requests, all "
            f"'done', engine_errors 0"
            for mode, m in facts["modes"].items()
        ) + f"; peak memory source {facts['memory']['source']}"

    def dp_detail(log, facts):
        return "; ".join(
            f"{tag}: mesh {r['mesh']} loss {r['train_loss']}"
            for tag, r in facts["runs"].items()
        )

    try:
        rc, _ = phase("kernels", me + ["--phase", "kernels"],
                      kernels_detail)
        if device is None or device["platform"] != cfg.platform:
            print("chip_smoke: JAX found no "
                  f"{cfg.platform} (device: {device}); nothing was "
                  "checked", flush=True)
            return EXIT_WRONG_PLATFORM if rc == EXIT_WRONG_PLATFORM else 1
        phase("train", cli + ["--epochs", "1"], train_detail(1))
        phase("resume", cli + ["--epochs", "2", "--resume"],
              train_detail(2))
        phase("serve", me + ["--phase", "serve"], serve_detail)
        if device["count"] > 1:
            phase("dp", me + ["--phase", "dp"], dp_detail)
    finally:
        shutil.rmtree(workdir, ignore_errors=True)
        full_log.close()

    total = time.time() - t_start
    print(f"chip_smoke: {sum(p for _, p, _, _ in results)}/{len(results)} "
          f"phases passed in {total:.0f} s (smoke timing)", flush=True)
    for name, passed, secs, _ in results:
        print(f"  {name:<8} {'PASS' if passed else 'FAIL'} {secs:5.0f} s",
              flush=True)
    if not all(passed for _, passed, _, _ in results):
        print("chip_smoke: FAILED — "
              + ", ".join(n for n, p, _, _ in results if not p), flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--phase", choices=sorted(PHASES), default=None,
                        help="run ONE in-process phase (how the parent "
                        "starts its children)")
    parser.add_argument("--workdir", default=None,
                        help="scratch directory for --phase")
    args = parser.parse_args(argv)
    if args.phase is None:
        return parent_main()
    workdir = args.workdir or tempfile.mkdtemp(prefix="chip_smoke_")
    try:
        return child_main(args.phase, workdir)
    finally:
        if args.workdir is None:
            shutil.rmtree(workdir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
