"""Decode-path performance: prefill tokens/s, steady-state per-token
latency, greedy vs beam, and the GQA cache-size win — measured, not
claimed (VERDICT r4 weak #7: the generation stack had zero performance
evidence).

The reference has no serving path at all (SURVEY.md §1), so there is no
reference row to beat; these numbers exist so "fast decode" is a
measurement.  Method:

- One compiled program per (model, shape, horizon) — the ``generate``
  program cache.  First call compiles (excluded); timed calls are the
  median of ``--reps`` fenced repeats (``profiler.force`` documents this
  platform returning from ``block_until_ready`` early).
- Steady-state per-token latency is a two-horizon difference:
  ``(t(H_long) - t(H_short)) / (H_long - H_short)`` — subtracting the
  shared prefill + dispatch cost instead of guessing it.
- Prefill tokens/s backs the one-step horizon out of ``t(H_short)``:
  ``B*P / (t_short - H_short*per_token)``.
- The GQA win is the measured byte size of the llama decode cache vs the
  same model built with ``num_kv_heads == num_heads`` (MHA): K/V leaves
  shrink by exactly H/Hkv; the measured ratio is computed from real
  cache pytrees, not the formula.

Writes one JSON document (``--out``, default docs/decode_bench.json on
TPU, stdout always).  CPU smoke: ``--models gpt2_tiny,llama_tiny --cpu``.
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

import jax
import jax.numpy as jnp
import numpy as np

from ml_trainer_tpu.generate import _cache_shapes, beam_search, generate
from ml_trainer_tpu.models import get_model
from ml_trainer_tpu.utils.profiler import force

# (batch, prompt_len, short horizon, long horizon) per benched model.
# Prompt fills half the context; horizons stay inside max_len.
SHAPES = {
    "gpt2": (8, 512, 16, 144),
    "llama": (8, 512, 16, 144),
    "gpt2_tiny": (4, 32, 4, 20),
    "llama_tiny": (4, 32, 4, 20),
}
BEAMS = 4


def _timed(fn, reps):
    """Median wall seconds of ``reps`` fenced calls (post-compile)."""
    out = fn()  # compile + warm
    force(out)
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn()
        force(out)
        times.append(time.perf_counter() - t0)
    return statistics.median(times)


def _cache_bytes(model, b):
    dm = model.clone(decode=True)
    shapes = _cache_shapes(dm, b, jnp.int32)
    return sum(
        int(np.prod(s.shape)) * s.dtype.itemsize
        for s in jax.tree.leaves(shapes)
    )


def bench_model(name, reps):
    b, p, h_short, h_long = SHAPES[name]
    model = get_model(name, dtype=jnp.bfloat16)
    prompt = jnp.asarray(
        np.random.default_rng(0).integers(1, model.vocab_size, (b, p)),
        jnp.int32,
    )
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, prompt[:, :1], train=False
    )

    t_short = _timed(
        lambda: generate(model, variables, prompt, h_short), reps
    )
    t_long = _timed(
        lambda: generate(model, variables, prompt, h_long), reps
    )
    per_tok = (t_long - t_short) / (h_long - h_short)
    prefill_s = max(t_short - h_short * per_tok, 1e-9)
    row = {
        "model": name,
        "batch": b,
        "prompt_len": p,
        "greedy": {
            "per_token_ms": round(per_tok * 1e3, 3),
            "decode_tokens_per_sec": round(b / per_tok, 1),
            "prefill_tokens_per_sec": round(b * p / prefill_s, 1),
            "horizons": [h_short, h_long],
        },
    }

    tb_short = _timed(
        lambda: beam_search(model, variables, prompt, h_short,
                            num_beams=BEAMS), reps
    )
    tb_long = _timed(
        lambda: beam_search(model, variables, prompt, h_long,
                            num_beams=BEAMS), reps
    )
    beam_tok = (tb_long - tb_short) / (h_long - h_short)
    row["beam"] = {
        "num_beams": BEAMS,
        "per_token_ms": round(beam_tok * 1e3, 3),
        # B*K candidate sequences advance per step.
        "decode_tokens_per_sec": round(b * BEAMS / beam_tok, 1),
        "vs_greedy_per_token": round(beam_tok / per_tok, 2),
    }

    if "llama" in name:
        gqa = _cache_bytes(model, b)
        mha = _cache_bytes(
            get_model(name, dtype=jnp.bfloat16,
                      num_kv_heads=model.num_heads), b
        )
        row["gqa_cache"] = {
            "bytes": gqa,
            "mha_equivalent_bytes": mha,
            "ratio": round(mha / gqa, 2),
            "num_heads": model.num_heads,
            "num_kv_heads": model.num_kv_heads,
        }
    return row


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--models", default="gpt2,llama",
                    help="comma list from %s" % sorted(SHAPES))
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--cpu", action="store_true",
                    help="pin the CPU backend (smoke run; no file written "
                    "unless --out is given)")
    ap.add_argument("--out", default=None,
                    help="output path (default docs/decode_bench.json "
                    "when the backend is TPU)")
    args = ap.parse_args()
    if args.cpu:
        jax.config.update("jax_platforms", "cpu")

    dev = jax.devices()[0]
    doc = {
        "device": str(dev.device_kind),
        "backend": "cpu" if args.cpu or dev.platform == "cpu" else "tpu",
        "measured": time.strftime("%Y-%m-%d %H:%MZ", time.gmtime()),
        "reps": args.reps,
        "rows": [],
    }
    for name in args.models.split(","):
        name = name.strip()
        print(f"# decode bench: {name}", file=sys.stderr, flush=True)
        doc["rows"].append(bench_model(name, args.reps))

    out = args.out
    if out is None and doc["backend"] == "tpu":
        out = "docs/decode_bench.json"
    if out:
        Path(out).write_text(json.dumps(doc, indent=1) + "\n")
        print(f"# wrote {out}", file=sys.stderr)
    print(json.dumps(doc))


if __name__ == "__main__":
    main()
