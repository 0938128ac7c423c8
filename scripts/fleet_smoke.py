#!/usr/bin/env python
"""Multi-process serving-fleet smoke — the
PR 16 tentpole end to end, with REAL OS processes (serving/fleet.py):

1. A 4-process fleet (2 prefill + 2 decode), every replica its own
   ``python -m ml_trainer_tpu.serving.fleet --worker`` process, the
   router driving them ONLY over HTTP sockets: greedy and seeded-
   sampled outputs byte-identical to in-driver ``generate()``, KV
   migration metered in real socket bytes, chunked prefill engaged on
   the long prompts (``prefill_chunks_total`` on the prefill replicas'
   ``/metrics.json``), distinct worker pids on ``/healthz``.
2. A REAL ``SIGKILL`` mid-stream (no goodbye — the socket severs; the
   router discovers the death via failed health polls and retryable
   stream errors): every in-flight stream redistributes and finishes
   BYTE-IDENTICAL to the uninterrupted reference.
3. The SLO-burn autoscaler's replace-dead repair spawns a REAL
   replacement process (``Fleet.factory``) with a fresh pid, and the
   restored fleet serves byte-identical traffic.

Prints ``FLEET_SMOKE OK`` / ``FLEET_SMOKE FAIL: <why>``; non-zero exit
on any violation.  CPU-only, ~4 worker processes, tiny model.
"""

import json
import os
import sys
import time
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import numpy as np  # noqa: E402


def fail(msg: str) -> int:
    print(f"FLEET_SMOKE FAIL: {msg}")
    return 1


def main() -> int:
    import jax

    from ml_trainer_tpu.generate import generate
    from ml_trainer_tpu.models import get_model
    from ml_trainer_tpu.serving import Autoscaler, AutoscalerConfig
    from ml_trainer_tpu.serving.fleet import Fleet

    model = get_model("gpt2_tiny", max_len=64)
    variables = model.init(
        {"params": jax.random.PRNGKey(0)}, np.zeros((1, 8), np.int32),
        train=False,
    )
    rng = np.random.default_rng(0)
    # Long prompts (> prefill_chunk=16) force chunked prefill; short
    # ones ride a single window — both must be byte-identical.
    prompts = [
        np.asarray(rng.integers(0, 1024, n), np.int32)
        for n in (9, 40, 12, 33)
    ]
    refs = [
        np.asarray(generate(model, variables, p[None], 12))[0]
        for p in prompts
    ]
    ref_sampled = np.asarray(
        generate(model, variables, prompts[0][None], 10, temperature=0.7,
                 rng=jax.random.PRNGKey(7))
    )[0]
    long_new = [min(28, 64 - len(p) - 1) for p in prompts]
    long_refs = [
        np.asarray(generate(model, variables, p[None], n))[0]
        for p, n in zip(prompts, long_new)
    ]

    fleet = Fleet(
        roles=["prefill", "prefill", "decode", "decode"],
        model_name="gpt2_tiny", max_len=64, max_batch=2,
        kv_page_size=8, prefill_chunk=16, seed=0,
    )
    fleet.start()
    router = fleet.make_router(hedging=False)
    autoscaler = None
    try:
        # -- leg 1: byte identity through socket migration ------------
        pids = {n: r.pid for n, r in fleet.replicas.items()}
        if len(set(pids.values())) != 4 or os.getpid() in pids.values():
            return fail(f"workers are not distinct processes: {pids}")
        outs = [
            np.asarray(router.complete(p, 12, timeout=300))
            for p in prompts
        ]
        sampled = np.asarray(
            router.complete(prompts[0], 10, temperature=0.7, rng=7,
                            timeout=300)
        )
        for out, ref in zip(outs, refs):
            if not np.array_equal(out, ref):
                return fail("migrated output diverged from generate()")
        if not np.array_equal(sampled, ref_sampled):
            return fail("sampled migrated output diverged")
        snap = router.snapshot()
        if snap["migrations_total"] < len(prompts):
            return fail(
                f"expected socket migrations, got "
                f"{snap['migrations_total']}"
            )
        if snap["kv_migrated_bytes_total"] <= 0:
            return fail("migrated socket bytes not metered")
        chunks = 0
        for name in ("prefill0", "prefill1"):
            with urllib.request.urlopen(
                f"{fleet.replicas[name].url}/metrics.json", timeout=10
            ) as resp:
                m = json.loads(resp.read())
            chunks += int(m.get("prefill_chunks_total", 0))
            h = fleet.replicas[name].health()
            if h.get("transport") != "http" or h.get("pid") != pids[name]:
                return fail(f"worker health pid/transport wrong: {h}")
        if chunks < 2:
            return fail(f"chunked prefill never engaged (chunks={chunks})")
        print(f"# fleet smoke: {len(prompts) + 1} requests "
              f"byte-identical across 4 processes, "
              f"{snap['migrations_total']} socket migration(s) / "
              f"{snap['kv_migrated_bytes_total']} bytes, "
              f"{chunks} prefill chunk(s)")

        # -- leg 2: real SIGKILL mid-stream ----------------------------
        streams = [
            router.submit(p, n) for p, n in zip(prompts, long_new)
        ]
        deadline = time.monotonic() + 120
        while any(len(s.tokens) < 2 for s in streams):
            if time.monotonic() > deadline:
                return fail("streams never started decoding")
            time.sleep(0.02)
        victim = fleet.replicas["decode0"]
        fleet.kill("decode0")  # SIGKILL, no goodbye
        if victim.proc is not None and victim.proc.poll() is None:
            return fail("SIGKILL'd worker still running")
        outs = [np.asarray(s.result(timeout=300)) for s in streams]
        for out, ref in zip(outs, long_refs):
            if not np.array_equal(out, ref):
                return fail("post-SIGKILL stream diverged from reference")
        snap = router.snapshot()
        if snap["redistributes_total"] < 1:
            return fail("SIGKILL produced no redistribution")
        print(f"# fleet smoke: SIGKILL pid {victim.pid} mid-stream -> "
              f"{snap['redistributes_total']} redistribution(s), all "
              f"streams byte-identical")

        # -- leg 3: autoscaler respawns a real process -----------------
        autoscaler = Autoscaler(
            router, fleet.factory,
            AutoscalerConfig(poll_interval_s=0.2, min_prefill=2,
                             min_decode=2, replace_cooldown_s=0.2),
        ).start()
        deadline = time.monotonic() + 180
        new_pid = None
        while time.monotonic() < deadline:
            alive_decode = [
                r for r in router.replicas.values()
                if r.healthy and not r.removing
                and r.role in ("decode", "both")
            ]
            if len(alive_decode) >= 2:
                fresh = [r for r in alive_decode
                         if r.name.startswith("auto")]
                if fresh:
                    new_pid = fresh[0].server.pid
                    break
            time.sleep(0.2)
        if new_pid is None:
            return fail("autoscaler never respawned the dead decode")
        if new_pid == victim.pid or new_pid == os.getpid():
            return fail(f"respawn reused a pid: {new_pid}")
        out = np.asarray(router.complete(prompts[1], 12, timeout=300))
        if not np.array_equal(out, refs[1]):
            return fail("restored fleet output diverged")
        actions = [a["action"] for a in autoscaler.actions]
        if "scale_up" not in actions:
            return fail(f"no scale_up action recorded: {actions}")
        print(f"# fleet smoke: autoscaler respawned decode as pid "
              f"{new_pid}, restored fleet byte-identical")
    finally:
        if autoscaler is not None:
            autoscaler.close()
        router.close()
        fleet.stop()
    print("FLEET_SMOKE OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
